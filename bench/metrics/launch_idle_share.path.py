"""launch_idle_share.path: % of the traced window of back-to-back lambda
paths in which the device was idle while the host was launching a chunk:
innermost in the program's span ``shotgun.solve``
(``kernels/ops.block_shotgun_solve`` padding the problem and dispatching
the jitted solve) or in ``shotgun.path.chunk`` outside its solve and its
reads (the key split and the convergence test).  One share for both, as
the profiler's clock drift moves idle across the boundary between them
(``program_trace``).  None when the program has no spans."""
from program_trace import idle_share


def read(ctx):
    return idle_share(ctx, "shotgun.solve", "shotgun.path.chunk")
