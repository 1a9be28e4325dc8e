"""Distributed Shotgun over a feature-sharded device mesh (DESIGN §3) — the
multi-pod adaptation of the paper's shared-Ax multicore algorithm, plus the
Pallas Block-Shotgun kernel path.

Run with 8 simulated devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_shotgun.py
"""
import jax

from repro.core import objectives as obj
from repro.core.sharded import shotgun_sharded_solve, make_feature_mesh
from repro.core.shotgun import shotgun_solve
from repro.core.spectral import p_star
from repro.data import synthetic as syn
from repro.kernels import ops


def main():
    devs = jax.devices()
    print(f"devices: {len(devs)}")
    A, y, _ = syn.sparco(seed=0, n=1024, d=4096)
    prob = obj.make_problem(A, y, lam=0.5)
    ps = p_star(prob.A)
    print(f"P* = {ps}")

    # 1. feature-sharded SPMD Shotgun: every device updates its own
    #    coordinates; one psum per round merges the shared margin z = Ax
    P_local = max(1, min(ps // max(len(devs), 1), 16))
    res = shotgun_sharded_solve(prob, jax.random.PRNGKey(0),
                                P_local=P_local, rounds=2000)
    print(f"sharded Shotgun (P = {P_local} x {len(devs)}): "
          f"F = {float(res.trace.objective[-1]):.4f}, "
          f"nnz = {int(res.trace.nnz[-1])}")

    # 2. Block-Shotgun (Pallas kernel; the interpreter on the CPU): aligned
    #    128-coordinate blocks -> MXU matmuls instead of scalar gathers, 10
    #    rounds a pallas_call with the margin resident in VMEM (DESIGN §4.2)
    K = max(1, min(ps // ops.BLOCK, 4))
    res_blk = ops.block_shotgun_solve(prob, jax.random.PRNGKey(0), K=K,
                                      rounds=500, rounds_per_launch=10)
    print(f"Block-Shotgun (K = {K} blocks of {ops.BLOCK}, R = 10/launch): "
          f"F = {float(res_blk.trace.objective[-1]):.4f}")

    # 3. reference: single-device scalar Shotgun
    ref = shotgun_solve(prob, jax.random.PRNGKey(1), P=K * ops.BLOCK,
                        rounds=500)
    print(f"scalar Shotgun (P = {K * ops.BLOCK}):      "
          f"F = {float(ref.trace.objective[-1]):.4f}")


if __name__ == "__main__":
    main()
