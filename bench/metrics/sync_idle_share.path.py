"""sync_idle_share.path: % of the traced window of back-to-back lambda
paths in which the device was idle while the host was inside the
program's span ``shotgun.path.sync``: a device→host read of
``core/path.solve_path`` waiting on the chip.  None when the program has
no spans."""
from program_trace import idle_share


def read(ctx):
    return idle_share(ctx, "shotgun.path.sync")
