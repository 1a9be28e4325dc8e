"""Roofline of the PAPER'S OWN solver on the production mesh (§Perf cell 5).

Lowers `core.sharded._sharded_solve` against ShapeDtypeStruct stand-ins at
the scale of the paper's largest dataset (Kogan et al. financial reports:
n = 30,465 samples, d = 5,845,762 features — scaled to d = 5,868,544 for
256-way divisibility) on the 256-chip pod and the 512-chip multi-pod mesh.

Per round the algorithm moves one n-vector all-reduce (the shared-Ax write);
cost_analysis counts the scan body once, so the reported terms ARE per-round
costs (plus amortized overhead).  Must be run in its own process:

    PYTHONPATH=src python -m benchmarks.shotgun_scale
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

RESULTS = pathlib.Path(__file__).resolve().parent / "results"

SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, re
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import sharded as SHD
from repro.launch.dryrun import collective_bytes, PEAK_FLOPS, HBM_BW, ICI_BW

N, D = 30465, 5868544            # Kogan-scale, 256|D and 512|D
P_LOCAL = 16                     # P = 16 x devices coordinates per round
ROUNDS = 100

out = {}
for devs, note in [(256, "single_pod"), (512, "multi_pod")]:
    mesh = Mesh(np.array(jax.devices()[:devs]), ("f",))
    A = jax.ShapeDtypeStruct((N, D), jnp.float32)
    y = jax.ShapeDtypeStruct((N,), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lam = jax.ShapeDtypeStruct((), jnp.float32)
    for trace_every, tag in [(1, "baseline"), (100, "trace_thinned")]:
        def fn(A, y, lam, key):
            return SHD._sharded_solve(A, y, lam, 1.0, key, P_LOCAL, ROUNDS,
                                      mesh, "lasso", trace_every)
        ns = NamedSharding(mesh, P(None, "f"))
        with mesh:
            lowered = jax.jit(fn, in_shardings=(ns, NamedSharding(mesh, P(None)),
                                                NamedSharding(mesh, P()),
                                                NamedSharding(mesh, P()))).lower(A, y, lam, key)
            comp = lowered.compile()
        cost = comp.cost_analysis()
        coll = collective_bytes(comp.as_text())
        flops = float(cost.get("flops", 0.0))
        byt = float(cost.get("bytes accessed", 0.0))
        ct = float(sum(coll.values()))
        rec = {
            "devices": devs, "trace_every": trace_every,
            "per_round": {
                "flops": flops, "bytes": byt, "collective_bytes": ct,
                "compute_s": flops / PEAK_FLOPS,
                "memory_s": byt / HBM_BW,
                "collective_s": ct / ICI_BW,
            },
            "collectives": coll,
            "P_total": P_LOCAL * devs,
        }
        out[f"{note}/{tag}"] = rec
        t = rec["per_round"]
        print(f"shotgun_scale,{note},{tag},P={P_LOCAL*devs},"
              f"compute={t['compute_s']:.3e}s,memory={t['memory_s']:.3e}s,"
              f"collective={t['collective_s']:.3e}s", flush=True)
print("JSON" + json.dumps(out))
"""


def run() -> list[dict]:
    # the 512 devices are virtual CPU ones: the child never needs a chip
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                         text=True, timeout=3000, env=env)
    for line in out.stdout.splitlines():
        if line.startswith("shotgun_scale,"):
            print(line, flush=True)
    payload = [l for l in out.stdout.splitlines() if l.startswith("JSON")]
    if not payload:
        print(out.stdout[-2000:], out.stderr[-3000:])
        raise RuntimeError("shotgun_scale subprocess failed")
    rows = json.loads(payload[0][4:])
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "shotgun_scale.json").write_text(json.dumps(rows, indent=1))
    return [dict(name=k, **v) for k, v in rows.items()]


if __name__ == "__main__":
    run()
