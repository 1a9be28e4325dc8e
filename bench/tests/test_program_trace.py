"""The program's own spans and scopes, and the readers built on them.

A λ path recorded on the CPU under ``jax.profiler.trace`` (its spans,
their stats and the read counter), synthetic traces for the arithmetic,
and the committed solve fixture, which holds no program span and reads as
it did before the program had spans."""
import json
import pathlib

import pytest

import _paths  # noqa: F401
import harness
import program_trace
import tracing

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
READERS = ["sync_idle_share.path", "launch_idle_share.path",
           "unattributed_idle_share.path", "path_syncs"]


# ---------------------------------------------------------------------------
# a path recorded on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    from repro.core import SolverSpec, solve_path
    from repro.core import objectives as obj
    from repro.core.batched import WarmStartCache
    A = jax.random.normal(jax.random.PRNGKey(0), (256, 512)) / 16.0
    y = A @ jax.numpy.zeros(512).at[:8].set(2.0)
    lam = 0.1 * float(obj.lambda_max(A, y, "lasso"))
    prob = obj.make_problem(A, y, lam, normalize=False)

    def path():
        return solve_path(prob, jax.random.PRNGKey(1), lam_target=lam,
                          num_lambdas=4, solver="block_fused",
                          spec=SolverSpec(P=64, rounds=32),
                          cache=WarmStartCache(), tol=1e-4)
    path()                                   # compile outside the trace
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.path"):
                res = path()
    return res, program_trace.extract(log_dir)


def _named(tr, name):
    return [e for e in tr["prog_spans"] if e[0] == name]


def test_one_sync_span_for_each_counted_read(recorded):
    res, tr = recorded
    assert res.syncs == len(_named(tr, "shotgun.path.sync")) > 0
    w = tracing.window(tr)
    assert program_trace.count(tr, w, "shotgun.path.sync") == res.syncs


def test_one_chunk_span_for_each_chunk_run(recorded):
    res, tr = recorded
    chunks = _named(tr, "shotgun.path.chunk")
    assert len(chunks) == res.rounds.sum() // 8
    assert len(_named(tr, "shotgun.solve")) == len(chunks)
    assert len(_named(tr, "shotgun.path")) == 1
    assert len(_named(tr, "shotgun.path.p_star")) == 1
    assert len(_named(tr, "shotgun.path.lambda_max")) == 1


def test_each_chunk_sits_inside_its_lambda_and_carries_its_index(recorded):
    res, tr = recorded
    lams = sorted(_named(tr, "shotgun.path.lambda"), key=lambda e: e[1])
    assert [e[3] for e in lams] == [{"lam": i} for i in range(4)]
    for _, s, d, st in _named(tr, "shotgun.path.chunk"):
        owner = [e for e in lams if e[1] <= s and s + d <= e[1] + e[2]]
        assert len(owner) == 1
        i = owner[0][3]["lam"]
        assert st["chunk"] < res.rounds[i] // 8


def test_idle_by_span_on_the_recorded_path_sums_to_the_window(recorded):
    _, tr = recorded
    w = tracing.window(tr)
    idle = program_trace.idle_by_span(tr, w)
    assert sum(idle.values()) == pytest.approx(
        (w[1] - w[0]) - tracing.busy_ns(tr, w), rel=1e-9)
    assert idle["shotgun.path.sync"] > 0 and idle["shotgun.solve"] > 0


# ---------------------------------------------------------------------------
# synthetic traces
# ---------------------------------------------------------------------------

def _trace(ops=(), spans=(), scopes=None):
    tr = {"chips": 1, "ops": [list(o) for o in ops],
          "spans": [["bench.window", 0.0, 100.0]],
          "host": [["PjitFunction(f)", 0.0, 5.0]] + [list(s) for s in spans]}
    if scopes is not None:
        tr["op_scopes"] = [list(s) for s in scopes]
    return tr


def test_idle_by_span_names_the_innermost_span_or_none():
    tr = _trace(ops=[(0, "a", 10.0, 10.0), (0, "b", 50.0, 30.0)],
                spans=[("shotgun.path", 5.0, 90.0),
                       ("shotgun.path.chunk", 20.0, 40.0),
                       ("shotgun.path.sync", 30.0, 10.0)])
    idle = program_trace.idle_by_span(tr, (0.0, 100.0))
    assert idle == {"none": 10.0,                   # [0, 5) and [95, 100)
                    "shotgun.path": 5.0 + 15.0,     # [5, 10) and [80, 95)
                    "shotgun.path.chunk": 20.0,     # [20, 30) and [40, 50)
                    "shotgun.path.sync": 10.0}      # [30, 40)
    assert sum(idle.values()) == 100.0 - tracing.busy_ns(tr, (0.0, 100.0))
    assert program_trace.count(tr, (0.0, 100.0), "shotgun.path.sync") == 1
    assert program_trace.count(tr, (35.0, 100.0), "shotgun.path.sync") == 0


def test_idle_by_span_clips_to_the_window_and_handles_siblings():
    tr = _trace(ops=[(0, "a", 40.0, 20.0)],
                spans=[("shotgun.solve", 10.0, 20.0),
                       ("shotgun.path.sync", 30.0, 10.0),
                       ("shotgun.solve", 60.0, 30.0)])
    idle = program_trace.idle_by_span(tr, (20.0, 80.0))
    assert idle == {"shotgun.solve": 10.0 + 20.0,   # [20, 30), [60, 80)
                    "shotgun.path.sync": 10.0}      # [30, 40)


def test_scope_time_counts_self_time_by_op_name_or_program():
    ops = [(0, "while", 0.0, 50.0), (0, "fusion", 5.0, 10.0),
           (0, "pad", 60.0, 20.0), (0, "fusion", 90.0, 5.0),
           (0, "fusion", 150.0, 5.0)]
    scopes = [("jit(_fused_solve)/shotgun.rounds/while", "jit__fused_solve"),
              ("jit(_fused_solve)/shotgun.rounds/while/body/shotgun.draw/sort",
               "jit__fused_solve"),
              ("jit(pad_problem)/pad", "jit_pad_problem"),
              ("jit(_fused_solve)/shotgun.warm_margin/dot_general",
               "jit__fused_solve"),
              ("jit(_fused_solve)/shotgun.warm_margin/dot_general",
               "jit__fused_solve")]
    tr = _trace(ops=ops, scopes=scopes)
    w = (0.0, 100.0)
    assert program_trace.scope_time_ns(tr, w, "shotgun.rounds") == 50.0
    assert program_trace.scope_time_ns(tr, w, "shotgun.draw") == 10.0
    assert program_trace.scope_time_ns(tr, w, "jit_pad_problem") == 20.0
    assert program_trace.scope_time_ns(tr, w, "shotgun.warm_margin") == 5.0
    assert program_trace.scope_time_ns(_trace(ops=ops), w, "pad") == 0.0


def _pb(num, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def _event_metadata(mid, name, *stats):
    md = _pb(1, mid) + _pb(2, name) + b"".join(_pb(5, st) for st in stats)
    return _pb(4, _pb(1, mid) + _pb(2, md))


def test_op_names_read_the_op_name_stat_of_each_device_event(tmp_path):
    def stat(mid, name):
        return _pb(5, _pb(1, mid) + _pb(2, _pb(1, mid) + _pb(2, name)))
    tpu = (_pb(1, 7) + _pb(2, "/device:TPU:0") + _pb(3, _pb(2, "XLA Ops"))
           + stat(1, program_trace.OP_NAME_STAT) + stat(2, "long_name")
           + stat(3, "jit(_fused_solve)/shotgun.rounds/while")
           + _event_metadata(11, "%fusion.1 = f32[8] fusion(x)",
                             _pb(1, 2) + _pb(5, "%fusion.1 = ..."),
                             _pb(1, 1) + _pb(5, "jit(f)/shotgun.draw/sort"))
           + _event_metadata(12, "%while.2 = while(x)",
                             _pb(1, 1) + _pb(7, 3))
           + _event_metadata(13, "%copy = f32[8] copy(x)",
                             _pb(1, 1) + _pb(5, "jit(f)/a/copy"))
           + _event_metadata(14, "%copy = f32[8] copy(x)",
                             _pb(1, 1) + _pb(5, "jit(g)/b/copy")))
    host = _pb(2, "/host:CPU") + _event_metadata(1, "bench.path")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, tpu) + _pb(1, host) + _pb(4, "localhost"))
    assert program_trace.op_names(str(path)) == {
        "%fusion.1 = f32[8] fusion(x)": "jit(f)/shotgun.draw/sort",
        "%while.2 = while(x)": "jit(_fused_solve)/shotgun.rounds/while",
        "%copy = f32[8] copy(x)": ""}     # one name, two op_names


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_from_a_program_without_spans(metric):
    tr = _trace(ops=[(0, "a", 10.0, 10.0)])
    ctx = {"trace": tr, "window_ns": (0.0, 100.0), "busy_ns": 10.0,
           "units": 1, "counters": {}}
    assert harness.reader(metric)(ctx) is None


def test_readers_on_a_synthetic_window_of_two_paths():
    ops = [(0, "a", 10.0, 10.0), (0, "b", 50.0, 30.0)]
    spans = [("shotgun.path", 5.0, 40.0), ("shotgun.solve", 6.0, 4.0),
             ("shotgun.path.sync", 20.0, 10.0),
             ("shotgun.path.chunk", 30.0, 12.0),
             ("shotgun.path", 50.0, 45.0), ("shotgun.solve", 85.0, 5.0),
             ("shotgun.path.sync", 90.0, 5.0)]
    tr = _trace(ops=ops, spans=spans)
    ctx = {"trace": tr, "window_ns": (0.0, 100.0), "busy_ns": 40.0,
           "units": 2, "counters": {}}
    read = {m: harness.reader(m)(ctx) for m in READERS}
    assert read == {"sync_idle_share.path": 15.0,     # [20, 30), [90, 95)
                    # [6, 10), [85, 90) in solve; [30, 42) in the chunk
                    "launch_idle_share.path": 21.0,
                    "unattributed_idle_share.path": 15.0,
                    "path_syncs": 1.0}
    idle = 100.0 * (1 - 40.0 / 100.0)
    assert sum(v for k, v in read.items() if k != "path_syncs") <= idle


# ---------------------------------------------------------------------------
# the committed solve fixture reads as it did
# ---------------------------------------------------------------------------

def test_committed_fixture_readings_and_breakdown_are_unchanged():
    tr = json.loads((FIXTURES / "trace_sparco_solve_boundary.json")
                    .read_text())
    w = tracing.window(tr)
    assert w == (433794737.0, 439801207.0)
    assert tracing.busy_ns(tr, w) == 993900.0
    assert [n for n, _ in tracing.top_ops(tr, w, k=2)] == \
        ["multiply_reduce_fusion", "fused_shotgun_rounds"]
    gaps = tracing.idle_gaps(tr, w, k=2)
    assert gaps == [["bench.window/PjitFunction(convert_element_type)",
                     pytest.approx(0.001747223)],
                    ["bench.window", pytest.approx(0.00163386)]]
    assert program_trace.spans(tr) == []
    idle = program_trace.idle_by_span(tr, w)
    assert list(idle) == ["none"]
    assert idle["none"] == pytest.approx((w[1] - w[0]) - 993900.0)
    ctx = {"trace": tr, "window_ns": w, "busy_ns": 993900.0, "units": 1,
           "counters": {}}
    assert all(harness.reader(m)(ctx) is None for m in READERS)


# ---------------------------------------------------------------------------
# a few chunks of spc_lasso.path recorded on a v5e chip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip():
    """Four chunks from the middle of a 3 s ``--trace 1`` window of
    ``spc_lasso.path`` on one v5e chip (seed 3141592653), cut from
    ``program_trace.extract``'s lists
    (``fixtures/trace_spc_path_chunks.json``)."""
    return json.loads((FIXTURES / "trace_spc_path_chunks.json").read_text())


def _chunks(tr):
    """(chunk span, its shotgun.solve span, its last shotgun.path.sync
    span) of each chunk held whole in the trace."""
    spans = sorted(tr["prog_spans"], key=lambda e: e[1])
    out = []
    for c in (e for e in spans if e[0] == "shotgun.path.chunk"):
        inner = [e for e in spans
                 if c[1] <= e[1] and e[1] + e[2] <= c[1] + c[2]]
        solve = [e for e in inner if e[0] == "shotgun.solve"]
        sync = [e for e in inner if e[0] == "shotgun.path.sync"]
        if solve and sync:
            out.append((c, solve[0], sync[-1]))
    return out


def test_host_spans_and_device_ops_share_one_clock(chip):
    """Each chunk's padded copy of A starts on the device after the host
    entered the chunk's ``shotgun.solve``, and its kernel ends before the
    host's read of the chunk's objectives returns."""
    chunks = _chunks(chip)
    assert len(chunks) >= 3
    ops = list(zip(chip["ops"], chip["op_scopes"]))
    for chunk, solve, sync in chunks:
        end = chunk[1] + chunk[2]
        pad = min((op for op, (_, mod) in ops
                   if mod == "jit_pad_problem" and chunk[1] <= op[2] < end),
                  key=lambda op: op[2])
        kernel = min((op for op, _ in ops if op[1] == "fused_shotgun_rounds"
                      and chunk[1] <= op[2] < end), key=lambda op: op[2])
        assert solve[1] <= pad[2] < kernel[2]
        assert kernel[2] + kernel[3] <= sync[1] + sync[2]


def test_chip_ops_carry_the_program_scopes(chip):
    paths = " ".join(p for p, _ in chip["op_scopes"])
    modules = {m for _, m in chip["op_scopes"]}
    assert "shotgun.warm_margin" in paths and "shotgun.draw" in paths
    assert "shotgun.rounds" in paths
    assert "jit_pad_problem" in modules
    w = tracing.window(chip)
    busy = tracing.busy_ns(chip, w)
    named = sum(program_trace.scope_time_ns(chip, w, s)
                for s in ("jit_pad_problem", "shotgun.warm_margin",
                          "shotgun.rounds"))
    assert 0 < named <= busy
    assert tracing.op_time_ns(chip, w, lambda n: n == "fused_shotgun_rounds")


def test_chip_idle_shares_sum_to_at_most_the_idle_share(chip):
    w = tracing.window(chip)
    ctx = {"trace": chip, "window_ns": w, "busy_ns": tracing.busy_ns(chip, w),
           "units": 1, "counters": {}}
    idle = harness.reader("idle_share.path")(ctx)
    parts = [harness.reader(m)(ctx) for m in READERS[:3]]
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) <= idle + 1e-9
    by_span = program_trace.idle_by_span(chip, w)
    assert sum(by_span.values()) == pytest.approx(
        (w[1] - w[0]) - ctx["busy_ns"], rel=1e-9)
