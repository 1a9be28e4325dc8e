"""The control of the output check.

    python3 bench/control.py --workload zeta_logreg.solve \
        --seeds 11 12 13 --seconds 10

Runs the cell's set-up, window and output check once per seed in one
process, with the design the timed path is handed rounded to bfloat16,
one step below the float32 the configurations state: the numbers the
program's own bfloat16 path computes with, since its fused kernel widens
each streamed bfloat16 tile of A to float32 before any product.  The
rounding takes the place of the float32 design on the device, so the
control needs no more memory than the cell; the check reads the float32
design made afresh from the seed.  Everything else, the block draws
included, is the timed path's.  Prints one JSON line per seed with every
compared number.  The benchmark's runs never run this; the program's own
readings are those of ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


@functools.cache
def bf16_design():
    """A -> A rounded to bfloat16 (kept in float32), consuming A."""
    import jax
    return jax.jit(lambda A: jax.lax.reduce_precision(
        A, exponent_bits=8, mantissa_bits=7), donate_argnums=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    for seed in args.seeds:
        r = harness.run_cell(cell, seed, args.seconds, False,
                             design=bf16_design())
        print(json.dumps({
            "workload": cell.name, "seed": seed, "side": "control",
            "attempted": r["attempted"], "failed": r["failed"],
            "numbers": {k: c["value"] for k, c in r["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
