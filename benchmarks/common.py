"""Shared benchmark plumbing: timing + CSV emission + F* oracles.

JAX is imported inside the functions that use it, so a parent process
that only emits or merges results (``bench_sharded``) never touches it.
"""
from __future__ import annotations

import json
import pathlib
import time

RESULTS = pathlib.Path(__file__).resolve().parent / "results"
REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def fstar_of(prob, iters=6000) -> float:
    from repro.core.baselines.fista import fista_solve
    return float(fista_solve(prob, iters).objective[-1])


def timed(fn, *args, **kw):
    """(result, seconds) with block_until_ready on jax outputs."""
    import jax
    t0 = time.time()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.time() - t0


def emit(rows, name):
    """Write rows (list of dicts) to results/<name>.json and echo CSV."""
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{name}.json").write_text(json.dumps(rows, indent=1))
    return rows


# Row keys that are cross-PR trajectory fields: lifted to the top level of
# the merged artifact so harnesses that read only the root object (not the
# per-shape rows) still see the headline numbers.
TRAJECTORY_KEYS = ("overlap_efficiency", "slot_occupancy")
TRAJECTORY_PREFIXES = ("speedup_",)


def _is_trajectory_key(key: str) -> bool:
    return key in TRAJECTORY_KEYS or any(
        key.startswith(p) for p in TRAJECTORY_PREFIXES)


def trajectory_fields(rows) -> dict:
    """Top-level trajectory dict for ``rows``: every ``speedup_*`` /
    ``overlap_efficiency`` field, the LAST row (in list order) carrying a
    key winning — deterministic, so re-merging is idempotent."""
    out: dict = {}
    for row in rows:
        for key, val in row.items():
            if _is_trajectory_key(key) and val is not None:
                out[key] = val
    return dict(sorted(out.items()))


def load_root_rows(path) -> list:
    """Rows of a perf-trajectory artifact, reading both the legacy bare-list
    format and the current ``{trajectory..., "rows": [...]}`` dict."""
    data = json.loads(pathlib.Path(path).read_text())
    return data["rows"] if isinstance(data, dict) else data


def merge_root(rows, tag, root_name="BENCH_kernels.json"):
    """Merge ``rows`` into the committed repo-root perf-trajectory artifact,
    replacing only the rows this bench owns: its ``"bench": tag`` rows, or
    the untagged rows for ``tag=None`` (bench_kernels).  The artifact is a
    dict — the ``speedup_*`` / ``overlap_efficiency`` trajectory fields at
    the top level (recomputed from the merged rows on every call, so the
    merge is idempotent) plus the full ``"rows"`` list; a legacy bare-list
    artifact is migrated on first touch.  Full runs only — callers skip
    this under BENCH_SMOKE."""
    root = REPO_ROOT / root_name
    hist = load_root_rows(root) if root.exists() else []
    hist = [r for r in hist if r.get("bench") != tag] + rows
    out = trajectory_fields(hist)
    out["rows"] = hist
    root.write_text(json.dumps(out, indent=1))
    return rows


def time_us(fn, reps=3):
    """Mean wall time of ``fn`` in µs after one warm/compile call."""
    import jax
    fn()
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.time() - t0) / reps * 1e6
