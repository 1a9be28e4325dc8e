"""idle_share.solve: % of the traced window of back-to-back solves in
which no operation ran on the device (averaged over the chips)."""


def read(ctx):
    w0, w1 = ctx["window_ns"]
    return 100.0 * (1.0 - ctx["busy_ns"] / (w1 - w0)) if w1 > w0 else None
