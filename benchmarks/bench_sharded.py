"""Distributed round-engine benchmark (DESIGN §3): per-round wall time of
the scalar / fused engines × merge modes on a forced 8-device host
mesh, plus the modeled Δz ``wire_bytes`` per round for each §7 compression
scheme (the psum itself moves dense f32 in this SPMD emulation — the wire
accounting is what a real multi-host deployment would put on the network).

Engines run at matched effective parallelism (P_eff = shards × K × 128 for
the fused engine, P_local = K × 128 for the scalar engine).  Interpret-mode
Pallas timings; the structural claims (1/R launches per merge, block DMA vs
random column gather) carry to TPU.

Appends its rows (tagged ``"bench": "sharded"``) to the repo-root
``BENCH_kernels.json`` perf-trajectory artifact — full runs only; a
BENCH_SMOKE=1 pass shrinks the shape and leaves the committed artifact
alone.  Spawns its own subprocess so the forced device count never leaks
into the caller's jax; the parent never imports JAX, so the child is the
only process that can hold an accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import REPO_ROOT, emit, merge_root

SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, numpy as np
from repro.core import objectives as obj
from repro.core.sharded import make_feature_mesh, shotgun_sharded_solve
from repro.data import synthetic as syn

SMOKE = bool(int(os.environ.get("BENCH_SMOKE_SUB", "0")))
n, d, rounds = (512, 1024, 16) if SMOKE else (4096, 2048, 16)
K, R_LAUNCH, SHARDS = 1, 8, 8

A, y, _ = syn.sparse_imaging(seed=0, n=n, d=d, density=0.002)
prob = obj.make_problem(A, y, lam=0.5)
mesh = make_feature_mesh()


def bench(reps=3, **kw):
    run = lambda: shotgun_sharded_solve(prob, jax.random.PRNGKey(0),
                                        rounds=rounds, mesh=mesh, **kw)
    res = run()
    jax.block_until_ready(res)                # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(run())
    us = (time.time() - t0) / reps / rounds * 1e6
    return us, float(res.trace.objective[-1])


from repro.dist.compression import wire_bytes
wire = {s: wire_bytes({"dz": np.zeros(n, np.float32)}, s, topk_frac=0.01)
        for s in ("none", "bf16", "int8", "topk")}
from benchmarks.roofline import sharded_merge_model
t_model = sharded_merge_model(n)["wire_us_per_merge"]

rows = []
for engine, ekw in [("scalar", dict(P_local=K * 128)),
                    ("fused", dict(engine="fused", K=K))]:
    launch_kw = dict(merge="launch", rounds_per_launch=R_LAUNCH,
                     trace_every=rounds // R_LAUNCH)
    us_round, f_round = bench(merge="round", trace_every=rounds, **ekw)
    us_launch, f_launch = bench(**launch_kw, **ekw)
    us_async, f_async = bench(pipeline=True, **launch_kw, **ekw)

    # exposed-wire accounting (DESIGN §3.4): the per-merge collective cost
    # from differencing the two cadences, floored by the modeled ICI wire
    # time (the psum of this SPMD emulation moves through shared memory, so
    # the difference can drown in timing noise) and capped by the launch
    # window it would have to hide in.  Synchronously every merge is on the
    # critical path; pipelined only the epilogue drain is (steady-state
    # merges overlap the window), plus whatever the window cannot hide.
    t_meas = max(us_round - us_launch, 0.0) * R_LAUNCH / (R_LAUNCH - 1)
    window = us_launch * R_LAUNCH
    t_merge = max(min(t_meas, window), t_model)
    exposed_sync = t_merge / R_LAUNCH
    exposed_async = max(t_merge - window, 0.0) / R_LAUNCH + t_merge / rounds
    overlap_eff = 1.0 - exposed_async / exposed_sync

    common = {
        "bench": "sharded", "n": n, "d": d, "shards": SHARDS,
        "engine": engine, "K": K, "P_eff": K * 128 * SHARDS,
        "merge_wire_us": round(t_merge, 3),
    }
    for merge, us, f, extra in [
            ("round", us_round, f_round, {"merges_per_round": 1.0}),
            ("launch", us_launch, f_launch,
             {"merges_per_round": 1.0 / R_LAUNCH, "pipeline": False,
              "exposed_wire_us_per_round": round(exposed_sync, 3)}),
            ("launch", us_async, f_async,
             {"merges_per_round": 1.0 / R_LAUNCH, "pipeline": True,
              "exposed_wire_us_per_round": round(exposed_async, 3),
              "overlap_efficiency": round(overlap_eff, 4)})]:
        merge_rounds = 1 if merge == "round" else R_LAUNCH
        rows.append({
            **common, "merge": merge,
            "round_us": round(us, 1), "objective_final": f,
            "wire_bytes_per_round_none": wire["none"] / merge_rounds,
            "wire_bytes_per_round_bf16": wire["bf16"] / merge_rounds,
            "wire_bytes_per_round_int8": wire["int8"] / merge_rounds,
            "wire_bytes_per_round_topk": wire["topk"] / merge_rounds,
            **extra,
        })
        tag = merge + ("_async" if extra.get("pipeline") else "")
        print(f"sharded,{engine},{tag},n={n},d={d},round_us={us:.0f}",
              flush=True)
    assert exposed_async < exposed_sync, (exposed_async, exposed_sync)
    print(f"sharded,{engine},overlap_efficiency={overlap_eff:.3f}",
          flush=True)

# bf16 wire parity: the compressed async merge must not move the optimum
launch_kw = dict(engine="fused", K=K, merge="launch",
                 rounds_per_launch=R_LAUNCH,
                 trace_every=rounds // R_LAUNCH, pipeline=True)
us16, f16 = bench(compression="bf16", **launch_kw)
f32 = [r for r in rows if r["engine"] == "fused"
       and r.get("pipeline")][0]["objective_final"]
rows.append({
    "bench": "sharded", "n": n, "d": d, "shards": SHARDS,
    "engine": "fused", "merge": "launch", "K": K, "pipeline": True,
    "compression": "bf16", "round_us": round(us16, 1),
    "objective_final": f16,
    "objective_rel_err_vs_f32": abs(f16 - f32) / abs(f32),
    "wire_bytes_per_round_bf16": wire["bf16"] / R_LAUNCH,
})
assert abs(f16 - f32) / abs(f32) < 0.01, (f16, f32)
print(f"sharded,fused,launch_async_bf16,round_us={us16:.0f},"
      f"rel_err={abs(f16 - f32) / abs(f32):.2e}", flush=True)

by = {(r["engine"], r["merge"]): r["round_us"] for r in rows
      if not r.get("pipeline")}
speedup = by[("scalar", "round")] / by[("fused", "round")]
for r in rows:
    r["speedup_fused_round_vs_scalar_round"] = round(speedup, 2)
print("RESULT_JSON " + json.dumps(rows))
"""


def run() -> list[dict]:
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    src = str(REPO_ROOT / "src")
    pypath = os.environ.get("PYTHONPATH", "")
    env = {**os.environ,
           "PYTHONPATH": src + (os.pathsep + pypath if pypath else ""),
           "BENCH_SMOKE_SUB": "1" if smoke else "0"}
    out = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                         text=True, timeout=3600, env=env)
    sys.stdout.write(out.stdout)
    if out.returncode:
        sys.stderr.write(out.stderr)
        raise RuntimeError("bench_sharded subprocess failed")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT_JSON ")]
    rows = json.loads(line[-1][len("RESULT_JSON "):])

    emit(rows, "bench_sharded")
    if not smoke:
        # append to the committed perf trajectory, replacing any previous
        # sharded rows (bench_kernels owns the untagged rows)
        merge_root(rows, tag="sharded")
    return rows


if __name__ == "__main__":
    run()
