"""Reduction of a profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes
into three plain lists, which is also the form the recorded test fixture
keeps:

  ops     [chip, name, start_ns, duration_ns] of every operation on the
          device op line of each chip (``XLA Ops``); the name is the HLO
          instruction's, without its ``%`` and numeric suffix
          (``fused_shotgun_rounds``), and control flow such as ``while``
          holds the operations it runs;
  spans   [name, start_ns, duration_ns] of the benchmark's own host spans
          (``TraceAnnotation`` names starting ``bench.``);
  host    [name, start_ns, duration_ns] of the other events on the host
          thread that holds those spans (dispatch, transfers, waits).

The rest works on those lists: the union of busy intervals, time by
operation name, and the idle gaps labelled by what the host was in.
"""
from __future__ import annotations

import collections
import glob
import os

SPAN_PREFIX = "bench."
DEVICE_OP_LINE = "XLA Ops"


def short_name(hlo_text: str) -> str:
    """``fused_shotgun_rounds`` of ``%fused_shotgun_rounds.7 = (...) ...``."""
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def extract(log_dir: str) -> dict:
    """Plain event lists of the newest trace under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, spans, host = [], [], []
    chips = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == DEVICE_OP_LINE]
            if lines:
                for ev in lines[0].events:
                    ops.append([chips, short_name(ev.name),
                                float(ev.start_ns), float(ev.duration_ns)])
                chips += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                       for ev in line.events]
                ours = [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                if ours:
                    spans += ours
                    host += [e for e in evs
                             if not e[0].startswith(SPAN_PREFIX)]
    return {"chips": chips, "ops": ops, "spans": spans, "host": host}


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint (start, end) covering the given (start, end)s."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(tr: dict, name: str = "bench.window") -> tuple[float, float]:
    """(start, end) in ns of the host span ``name``."""
    for n, s, d in tr["spans"]:
        if n == name:
            return s, s + d
    raise KeyError(f"no host span {name!r} in the trace")


def busy_ns(tr: dict, win: tuple[float, float]) -> float:
    """Device busy time in the window: for each chip the length of the
    union of its operation intervals clipped to the window, averaged over
    the chips."""
    w0, w1 = win
    per_chip = collections.defaultdict(list)
    for chip, _, s, d in tr["ops"]:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            per_chip[chip].append((s, e))
    if not per_chip:
        return 0.0
    total = sum(sum(e - s for s, e in merge(iv)) for iv in per_chip.values())
    return total / max(tr["chips"], len(per_chip))


def op_time_ns(tr: dict, win: tuple[float, float], match) -> float:
    """Summed device duration of the operations whose name satisfies
    ``match``, starting inside the window, over all chips."""
    w0, w1 = win
    return sum(d for _, n, s, d in tr["ops"] if w0 <= s < w1 and match(n))


def self_times(ops) -> list[float]:
    """Each operation's duration less the time of the operations it holds
    on the same chip (a ``while`` less its body), in the order given."""
    out = [float(op[3]) for op in ops]
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], ops[i][2],
                                                    -ops[i][3]))
    stack: list[int] = []
    for i in order:
        chip, _, s, d = ops[i]
        while stack and (ops[stack[-1]][0] != chip or
                         ops[stack[-1]][2] + ops[stack[-1]][3] <= s):
            stack.pop()
        if stack:
            out[stack[-1]] -= d
        stack.append(i)
    return out


def top_ops(tr: dict, win: tuple[float, float], k: int = 10):
    """[name, seconds] of the k operation names with the most device self
    time (a ``while`` counts only what its body does not)."""
    w0, w1 = win
    by = collections.Counter()
    for op, t in zip(tr["ops"], self_times(tr["ops"])):
        if w0 <= op[2] < w1:
            by[op[1]] += t
    return [[n, t * 1e-9] for n, t in by.most_common(k)]


def _innermost(events, t: float):
    """Name of the shortest event in ``events`` that covers time t."""
    best = None
    for n, s, d in events:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else None


def _most_overlap(events, g0: float, g1: float):
    """Name of the event that overlaps [g0, g1) the longest."""
    best = None
    for n, s, d in events:
        o = min(s + d, g1) - max(s, g0)
        if o > 0 and (best is None or o > best[1]):
            best = (n, o)
    return best[0] if best else None


def idle_gaps(tr: dict, win: tuple[float, float], k: int = 10):
    """[label, seconds] of the k longest stretches of the window in which
    no chip ran an operation.  A gap is labelled by the innermost
    benchmark span at its start and the host event (dispatch, transfer,
    wait) that overlaps it most: ``bench.path/PjitFunction(...)``."""
    w0, w1 = win
    busy = merge((max(s, w0), min(s + d, w1)) for _, _, s, d in tr["ops"]
                 if s + d > w0 and s < w1)
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for s, e in gaps[:k]:
        span = _innermost(tr["spans"], s) or "outside"
        what = _most_overlap(tr["host"], s, e)
        out.append([f"{span}/{what}" if what else span, (e - s) * 1e-9])
    return out
