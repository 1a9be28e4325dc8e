"""fused_kernel_roofline: the fused Block-Shotgun kernel's share of its
roofline, in %.

The least time the window's solves could take on this chip (bytes and
operations from the cell's shapes, ``reference/roofline.py``; peaks from
``peaks.json`` by ``device_kind``) over the summed device time of the
fused kernel's events in the trace.  None when the trace holds no such
event.
"""
from reference import roofline
from tracing import op_time_ns

# The fused kernel's custom call takes the name of the jitted entry that
# issues it, ``kernels/shotgun_block.fused_shotgun_rounds``.
KERNEL_NAME = "fused_shotgun_rounds"


def read(ctx):
    t_ns = op_time_ns(ctx["trace"], ctx["window_ns"],
                      lambda name: name == KERNEL_NAME)
    if not t_ns:
        return None
    cfg = ctx["config"]
    K = -(-cfg["P"] // roofline.BLOCK)
    b, f = roofline.solve_cost(cfg["n"], cfg["d"], K, cfg["rounds"],
                               newton=cfg.get("newton", False))
    least, _bound = roofline.least_time_s(b * ctx["units"], f * ctx["units"],
                                          ctx["peak"])
    return 100.0 * least / (t_ns * 1e-9)
