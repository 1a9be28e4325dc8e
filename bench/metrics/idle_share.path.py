"""idle_share.path: % of the traced window of back-to-back lambda paths
in which no operation ran on the device (averaged over the chips): the
host loop's share of a path."""


def read(ctx):
    w0, w1 = ctx["window_ns"]
    return 100.0 * (1.0 - ctx["busy_ns"] / (w1 - w0)) if w1 > w0 else None
