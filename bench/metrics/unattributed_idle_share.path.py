"""unattributed_idle_share.path: % of the traced window of back-to-back
lambda paths in which the device was idle while the host was in none of
the program's spans (``shotgun.``): the benchmark's own loop and the
program's code outside every span.  None when the program has no
spans."""
from program_trace import NO_SPAN, idle_share


def read(ctx):
    return idle_share(ctx, NO_SPAN)
