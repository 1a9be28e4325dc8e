"""Benchmark orchestrator — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig2 fig5  # subset

Each sub-benchmark runs as ``python -m benchmarks.<module>`` in a child
process of its own, one after another, and this parent never imports
JAX: a process that has touched JAX holds the accelerator, and a child
that needs it would then fail or hang.  Each child prints progress lines
and writes JSON under benchmarks/results/; this wrapper ends with a
``name,seconds`` CSV summary and exits non-zero if any child failed.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

MODULES = {
    "fig2": "fig2_parallelism",
    "fig3": "fig3_lasso_solvers",
    "fig4": "fig4_logreg",
    "logreg": "fig4_logreg",   # alias: the bench=logreg kernel rows
    "fig5": "fig5_speedup",
    "kernels": "bench_kernels",
    "serve": "bench_serve",
    "sharded": "bench_sharded",
    "sparse": "bench_sparse",
    "shotgun_scale": "shotgun_scale",
    "roofline": "roofline",
}


def main() -> None:
    picks = [a for a in sys.argv[1:] if a in MODULES] or [
        name for name in MODULES if name != "logreg"]
    summary = []
    for name in picks:
        t0 = time.time()
        rc = subprocess.run([sys.executable, "-m",
                             f"benchmarks.{MODULES[name]}"],
                            env=os.environ).returncode
        summary.append((name, time.time() - t0, rc))
    print("\n# name,seconds")
    for name, dt, rc in summary:
        print(f"{name},{dt:.1f}" + ("" if rc == 0 else f",FAILED rc={rc}"))
    if any(rc for _, _, rc in summary):
        sys.exit(1)


if __name__ == "__main__":
    main()
