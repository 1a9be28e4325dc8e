"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload zeta_logreg.solve --seed 7 \
        --seconds 10 --trace 0

Prints set-up phases and the output check's numbers, each beside its
limit, on standard error, and one JSON result as the last line of
standard output: ``--trace 0`` gives the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of a "
                         "temporary directory")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
