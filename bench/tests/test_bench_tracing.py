"""The trace reduction on a small recorded trace: 6 ms of a window of
back-to-back fused lasso solves (n = 4096, d = 65536) on one v5e chip,
around the boundary between two solves (``fixtures/trace_sparco_solve_boundary.json``, cut from the
lists ``tracing.extract`` reads out of the profiler's ``.xplane.pb``)."""
import json
import pathlib

import numpy as np
import pytest

import _paths  # noqa: F401
import harness
import tracing

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / \
    "trace_sparco_solve_boundary.json"


@pytest.fixture(scope="module")
def tr():
    return json.loads(FIXTURE.read_text())


def _coverage_ns(intervals, w0, w1):
    """Busy time by brute force on a 10 ns grid."""
    grid = np.zeros(int((w1 - w0) // 10) + 1, bool)
    for s, e in intervals:
        a, b = max(s, w0), min(e, w1)
        if b > a:
            grid[int((a - w0) // 10):int((b - w0) // 10)] = True
    return grid.sum() * 10.0


def test_busy_is_the_union_of_device_intervals(tr):
    w = tracing.window(tr)
    busy = tracing.busy_ns(tr, w)
    brute = _coverage_ns([(s, s + d) for _, _, s, d in tr["ops"]], *w)
    assert busy == pytest.approx(brute, abs=10.0 * len(tr["ops"]) + 20)
    # nested operations (a ``while`` and its body) are not counted twice
    assert busy < sum(d for *_, d in tr["ops"])
    assert 0 < busy < w[1] - w[0]


def test_merge_and_idle_gaps_partition_the_window(tr):
    w = tracing.window(tr)
    gaps = tracing.idle_gaps(tr, w, k=10 ** 6)
    idle = sum(s for _, s in gaps) * 1e9
    assert idle + tracing.busy_ns(tr, w) == pytest.approx(w[1] - w[0],
                                                          rel=1e-9)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert tracing.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]


def test_idle_gaps_are_labelled_by_host_span_and_host_event(tr):
    w = tracing.window(tr)
    gaps = tracing.idle_gaps(tr, w, k=3)
    assert all(label.split("/")[0] in ("bench.window", "bench.solve")
               for label, _ in gaps)
    # the longest gap: the host dispatching the next solve's first
    # conversions while the chip waits
    assert gaps[0][0] == "bench.window/PjitFunction(convert_element_type)"
    assert gaps[0][1] == pytest.approx(0.001747223, rel=1e-6)


def test_kernel_time_by_name_and_top_ops(tr):
    w = tracing.window(tr)
    want = sum(d for _, n, s, d in tr["ops"]
               if n == "fused_shotgun_rounds" and w[0] <= s < w[1])
    got = tracing.op_time_ns(tr, w, lambda n: n == "fused_shotgun_rounds")
    assert got == want > 0
    top = dict(tracing.top_ops(tr, w))
    assert top["fused_shotgun_rounds"] == pytest.approx(want * 1e-9)
    # self time: the while loop is left with what its body does not cover
    assert top.get("while", 0.0) < 1e-4
    assert tracing.short_name(
        "%fused_shotgun_rounds.7 = (f32[4096,1]) custom-call(...)") == \
        "fused_shotgun_rounds"
    assert tracing.short_name("%copy-start.1 = f32[1] copy-start(x)") == \
        "copy-start"


def test_idle_share_reader(tr):
    w = tracing.window(tr)
    ctx = {"window_ns": w, "busy_ns": tracing.busy_ns(tr, w)}
    share = harness.reader("idle_share.solve")(ctx)
    assert share == harness.reader("idle_share.path")(ctx)
    assert share == pytest.approx(100 * (1 - ctx["busy_ns"] / (w[1] - w[0])))
    assert 0 < share < 100
