"""The program's own spans and scopes in a profiler trace.

The program marks its steps itself (``core/path.solve_path``,
``kernels/ops.block_shotgun_solve``, ``kernels/ops._fused_solve``,
``core/spectral.spectral_radius``):

  host spans   ``jax.profiler.TraceAnnotation`` names starting
               ``shotgun.`` on the calling thread, the thread that holds
               the benchmark's ``bench.`` spans; ``tracing.extract``
               keeps them among its ``host`` events, without their
               keyword stats;
  scopes       ``jax.named_scope`` names in each device op's ``op_name``
               path, and the jitted program the op ran in
               (``jit_pad_problem``), which ``tracing.extract`` drops.

This module reads the spans out of ``tracing.extract``'s lists, and
``extract`` adds what that leaves out: ``op_scopes`` ([op_name, program]
of each op, in the order of ``ops``) and ``prog_spans`` ([name, start_ns,
duration_ns, stats] of the program spans).  On a v5e the op_name path is
the stat ``tf_op`` of the ``XLA Ops`` event's metadata, e.g.
``jit(_fused_solve)/shotgun.rounds/while/body/closed_call/shotgun.draw/
vmap(jit(_shuffle))/sort:``; the events themselves carry only
``device_offset_ps`` and ``device_duration_ps``, and the profiler's Python
reader hands out no metadata stats, so ``op_names`` decodes them from the
``.xplane.pb``.  The program an op ran in is the ``XLA Modules`` event
covering it.  The CPU's trace has no device plane, so there both are
empty.

Host spans and device ops are placed on one clock by the profiler.  In
the first seconds of a trace on a v5e they agree to a few tenths of a
millisecond; 10 s into a 20 s trace the device's ops read 0.7–2 ms early.
That moves a chunk's padded copy of A from inside ``shotgun.solve`` to
the chunk span just before it, and the same amount of idle the other
way, so a share that splits the two swings from run to run, while the
reads' (``shotgun.path.sync``) idle moved by half a point between a 3 s
and a 20 s trace of one seed.  The launch share therefore counts both
(``PERF.md`` §5).
"""
from __future__ import annotations

import bisect
import collections
import glob
import heapq
import os

import tracing

PREFIX = "shotgun."
NO_SPAN = "none"
OP_NAME_STAT = "tf_op"
MODULE_LINE = "XLA Modules"


def spans(tr: dict) -> list:
    """[name, start_ns, duration_ns] of the program's host spans."""
    if "prog_spans" in tr:
        return [e[:3] for e in tr["prog_spans"]]
    return [e for e in tr["host"] if e[0].startswith(PREFIX)]


def count(tr: dict, win: tuple[float, float], name: str) -> int:
    """Program spans called ``name`` that start inside the window."""
    w0, w1 = win
    return sum(1 for n, s, _ in spans(tr) if n == name and w0 <= s < w1)


def _gaps(tr: dict, win: tuple[float, float]) -> list[tuple[float, float]]:
    """Sorted (start, end) of the stretches of the window in which no chip
    ran an operation."""
    w0, w1 = win
    busy = tracing.merge((max(s, w0), min(s + d, w1))
                         for _, _, s, d in tr["ops"] if s + d > w0 and s < w1)
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def _host_segments(evs, win: tuple[float, float]):
    """(start, end, name) cutting the window at every span boundary, each
    piece named by the innermost (shortest) span covering it, or NO_SPAN."""
    w0, w1 = win
    cuts = sorted({w0, w1, *(t for _, s, d in evs for t in (s, s + d)
                             if w0 < t < w1)})
    starts = sorted(((s, i) for i, (_, s, _d) in enumerate(evs)),
                    reverse=True)
    active: list[tuple[float, float, int]] = []     # (duration, end, index)
    out = []
    for a, b in zip(cuts, cuts[1:]):
        while starts and starts[-1][0] <= a:
            i = starts.pop()[1]
            heapq.heappush(active, (evs[i][2], evs[i][1] + evs[i][2], i))
        while active and active[0][1] <= a:     # the shortest has ended
            heapq.heappop(active)
        out.append((a, b, evs[active[0][2]][0] if active else NO_SPAN))
    return out


def idle_by_span(tr: dict, win: tuple[float, float]) -> dict[str, float]:
    """Idle nanoseconds of the window (no chip running an operation), by
    the innermost program span the host was in; NO_SPAN for idle time
    with the host in none.  The values sum to the window's idle time."""
    out: dict[str, float] = collections.defaultdict(float)
    gaps = _gaps(tr, win)
    i = 0
    for a, b, name in _host_segments(spans(tr), win):
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            out[name] += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
    return dict(out)


def idle_share(ctx: dict, *names: str) -> float | None:
    """% of the traced window idle with the host innermost in one of the
    program spans ``names`` (NO_SPAN: in none); None when the program has
    no spans."""
    tr, (w0, w1) = ctx["trace"], ctx["window_ns"]
    if not spans(tr) or w1 <= w0:
        return None
    idle = idle_by_span(tr, (w0, w1))
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / (w1 - w0)


def scope_time_ns(tr: dict, win: tuple[float, float], scope: str) -> float:
    """Summed device self time of the operations starting inside the
    window whose op_name path or program (``jit_pad_problem``) contains
    ``scope``, over all chips; 0 for a trace without ``op_scopes``."""
    w0, w1 = win
    if not tr.get("op_scopes"):
        return 0.0
    return sum(t for op, (path, module), t in
               zip(tr["ops"], tr["op_scopes"], tracing.self_times(tr["ops"]))
               if w0 <= op[2] < w1 and (scope in path or scope in module))


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one protobuf message's bytes: an int for
    a varint, a memoryview for anything else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_names(path: str) -> dict[str, str]:
    """{device event name: OP_NAME_STAT} from the event metadata of the
    TPU planes of an ``.xplane.pb`` (an XSpace: planes 1; a plane's name 2,
    event_metadata 4, stat_metadata 5; a metadata's name 2, stats 5; a
    stat's metadata_id 1, str_value 5, ref_value 7).  The profiler's
    Python reader hands out event stats only, and the op_name is a stat
    of the event's metadata.  A name two ops share with different
    op_names maps to ""; a name with none is left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        for k, v in fields:
            if k != 4:
                continue
            md = list(_fields(dict(_fields(v)).get(2, b"")))
            ev = next((bytes(x).decode() for f, x in md if f == 2), "")
            op = ""
            for f, x in md:
                st = dict(_fields(x)) if f == 5 else {}
                if stat_names.get(st.get(1)) == OP_NAME_STAT:
                    op = (bytes(st[5]).decode() if 5 in st
                          else stat_names.get(st.get(7), ""))
            if op:
                out[ev] = op if out.get(ev, op) == op else ""
    return out


def _module_at(modules, t: float) -> str:
    """Name of the program (``XLA Modules`` event, ``jit_f(123)`` read as
    ``jit_f``) running at time t, or ""."""
    i = bisect.bisect_right(modules, (t, float("inf"))) - 1
    if i >= 0 and modules[i][1] > t:
        return modules[i][2]
    return ""


def extract(log_dir: str) -> dict:
    """``tracing.extract``'s lists of the newest trace under ``log_dir``,
    with ``op_scopes`` and ``prog_spans`` added."""
    import jax
    tr = tracing.extract(log_dir)
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    names = op_names(path)
    scopes, prog = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if tracing.DEVICE_OP_LINE not in lines:
                continue
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
                for e in (lines[MODULE_LINE].events
                          if MODULE_LINE in lines else ()))
            scopes += [[names.get(e.name, ""), _module_at(modules, e.start_ns)]
                       for e in lines[tracing.DEVICE_OP_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(line.events)
                if any(e.name.startswith(tracing.SPAN_PREFIX) for e in evs):
                    prog += [[e.name, float(e.start_ns), float(e.duration_ns),
                              dict(e.stats)]
                             for e in evs if e.name.startswith(PREFIX)]
    tr["op_scopes"], tr["prog_spans"] = scopes, prog
    return tr
