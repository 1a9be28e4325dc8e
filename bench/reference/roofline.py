"""Bytes and operations a fused Block-Shotgun solve needs, from the shapes
alone, and the chip's peaks by ``device_kind``.

Counted as the algorithm needs them, whatever implements it: every round
reads each of its K selected 128-column panels of A once, at the
configuration's dtype; a solve reads y and writes x and the margin z
once.  Operations are the multiply-adds on those panels: A_B^T r and
A_B delta (2 flops per element each), and with per-block Newton also
(A_B * A_B)^T w (3 per element).  Elementwise work on the n-vectors is
left out: at about one flop per byte the round sits far below the ridge
point, so the byte term is the bound.
"""
from __future__ import annotations

import json
import pathlib

BLOCK = 128
PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def padded(n: int, d: int) -> tuple[int, int]:
    """(n, d) as the solver lays them out: n up to a multiple of 512
    samples, d up to whole 128-blocks (the padding is read too)."""
    return -(-n // 512) * 512, -(-d // BLOCK) * BLOCK


def solve_cost(n: int, d: int, K: int, rounds: int, a_bytes: int = 4,
               newton: bool = False) -> tuple[int, int]:
    """(bytes, flops) of one solve of ``rounds`` rounds of K blocks."""
    n_p, d_p = padded(n, d)
    panel = n_p * BLOCK
    bytes_ = rounds * K * panel * a_bytes + 4 * (2 * n_p + d_p)
    flops = rounds * K * panel * (7 if newton else 4)
    return bytes_, flops


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The peak row for ``device_kind``; an unknown device is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time_s(bytes_: float, flops: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of bytes over HBM bandwidth and flops
    over peak compute, and which of the two it is."""
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "compute")
