"""path_syncs: device→host reads per lambda path, the program's spans
``shotgun.path.sync`` in the traced window over the paths completed in
it: each one a wait of ``core/path.solve_path``'s host loop on the chip
(``PathResult.syncs`` counts the same reads).  None when the program has
no such span."""
from program_trace import count


def read(ctx):
    n = count(ctx["trace"], ctx["window_ns"], "shotgun.path.sync")
    return n / ctx["units"] if n and ctx["units"] else None
