"""shotgun-lint suite tests (DESIGN §10).

Per-rule positive + negative fixtures, allowlist suppression, deterministic
ordering, the whole-repo zero-findings run, and the three trace-level
regression demos the acceptance criteria name: a deliberately leaked
Python scalar (SL102), an oversized scratch config (SL101), and a
misnamed mesh axis (SL103).
"""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))

from repro.analyze.allowlist import load_allowlist          # noqa: E402
from repro.analyze.ast_checks import run_ast_checks         # noqa: E402
from repro.analyze.findings import (Finding, render_report,  # noqa: E402
                                    sort_findings)
from repro.analyze.runner import run_checkers               # noqa: E402

AST_RULES = ("SL001", "SL002", "SL003")


def lint_snippet(tmp_path, source, rel="mod.py", rules=None):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run_ast_checks(tmp_path, rules)


# ---------------------------------------------------------------------------
# SL001 — trace purity
# ---------------------------------------------------------------------------

def test_sl001_flags_host_effects_in_jit(tmp_path):
    fs = lint_snippet(tmp_path, """
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            print("tracing")          # flagged
            t = time.time()           # flagged
            return x * np.random.rand() + t   # flagged
    """)
    assert [f.rule for f in fs] == ["SL001"] * 3
    assert {f.line for f in fs} == {8, 9, 10}


def test_sl001_flags_scan_and_kernel_bodies(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax
        import numpy as np

        def body(c, x):
            return c, np.random.rand()          # flagged: scan body

        def foo_kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * np.random.rand()   # flagged: kernel

        def drive(xs):
            return jax.lax.scan(body, 0.0, xs)
    """)
    assert [f.rule for f in fs] == ["SL001"] * 2


def test_sl001_negative_outside_trace_and_debug_print(tmp_path):
    fs = lint_snippet(tmp_path, """
        import time
        import jax
        import numpy as np

        def host_setup():
            print("host side is fine")
            return np.random.rand(), time.time()

        @jax.jit
        def f(x):
            jax.debug.print("x = {}", x)   # the sanctioned form
            return x * 2.0
    """)
    assert fs == []


def test_sl001_flags_nonlocal_mutation(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def make(scale):
            calls = 0
            @jax.jit
            def f(x):
                nonlocal calls
                calls += 1
                return x * scale
            return f
    """)
    assert [f.rule for f in fs] == ["SL001"]
    assert "nonlocal calls" in fs[0].message


# ---------------------------------------------------------------------------
# SL002 — dtype accumulation
# ---------------------------------------------------------------------------

def test_sl002_flags_uncast_matmuls_in_kernels_dir(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax.numpy as jnp

        def margin(A, x):
            return A @ x                              # flagged

        def margin_dot(A, x):
            return jnp.dot(A, x)                      # flagged

        def margin_ok(A, x):
            return A.astype(jnp.float32) @ x          # cast: fine

        def margin_ok_t(A, x):
            return jnp.dot(A.astype(jnp.float32).T, x)   # cast under .T: fine
    """, rel="kernels/k.py")
    assert [f.rule for f in fs] == ["SL002"] * 2
    assert {f.line for f in fs} == {5, 8}


def test_sl002_matmul_rule_scoped_to_kernels_and_dist(tmp_path):
    # outside kernels// dist/ the operator form is not flagged (core code
    # is all-f32 by construction); dot_general is flagged everywhere
    fs = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        def core_margin(A, x):
            return A @ x                              # core/: fine

        def raw(a, b, dims):
            return jax.lax.dot_general(a, b, dims)    # flagged anywhere
    """, rel="core/c.py")
    assert [f.rule for f in fs] == ["SL002"]
    assert "dot_general" in fs[0].message


def test_sl002_dot_general_negative_with_preferred_type(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        def acc(a, b, dims):
            return jax.lax.dot_general(
                a, b, dims, preferred_element_type=jnp.float32)
    """, rel="kernels/k.py")
    assert fs == []


def test_sl002_flags_bf16_vmem_scratch(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        SCRATCH_BAD = pltpu.VMEM((128, 128), jnp.bfloat16)   # flagged
        SCRATCH_OK = pltpu.VMEM((128, 128), jnp.float32)
    """, rel="kernels/k.py")
    assert [f.rule for f in fs] == ["SL002"]
    assert "bf16 VMEM scratch" in fs[0].message


# ---------------------------------------------------------------------------
# SL003 — bare assert on shape arithmetic
# ---------------------------------------------------------------------------

def test_sl003_flags_bare_shape_asserts(tmp_path):
    fs = lint_snippet(tmp_path, """
        def split(n, d, block):
            assert d % block == 0                 # flagged
            assert n > 0                          # plain compare: fine

        def check(x, d):
            assert x.shape == (d,)                # flagged (.shape)

        def good(n, tile):
            if n % tile:
                raise ValueError(f"n={n} not a multiple of tile={tile}")
    """)
    assert [f.rule for f in fs] == ["SL003", "SL003"]
    assert {f.line for f in fs} == {3, 7}


def test_sl003_ignores_non_shape_asserts(tmp_path):
    fs = lint_snippet(tmp_path, """
        LASSO = "lasso"

        def check_loss(prob):
            assert prob.loss == LASSO
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# allowlist + determinism
# ---------------------------------------------------------------------------

def test_allowlist_suppresses_and_reports_stale(tmp_path):
    (tmp_path / "m.py").write_text("def f(n, b):\n    assert n % b == 0\n")
    allow = tmp_path / "allow.toml"
    allow.write_text(textwrap.dedent("""
        # vetted: demo entry
        [[allow]]
        rule = "SL003"
        path = "m.py"
        match = "n % b"
        reason = "demo suppression"

        [[allow]]
        rule = "SL001"
        path = "never.py"
        reason = "stale entry"
    """))
    report = run_checkers(tmp_path, rules=["SL001", "SL003"],
                          allowlist=allow)
    assert report.ok
    assert [f.rule for f in report.suppressed] == ["SL003"]
    assert [e.path for e in report.unused_allows] == ["never.py"]
    # without the allowlist the finding comes back
    report = run_checkers(tmp_path, rules=["SL003"], allowlist=None)
    assert [f.rule for f in report.findings] == ["SL003"]


def test_allowlist_parser_requires_keys(tmp_path):
    bad = tmp_path / "allow.toml"
    bad.write_text('[[allow]]\nrule = "SL001"\n')
    with pytest.raises(ValueError, match="missing required keys"):
        load_allowlist(bad)


def test_findings_deterministic_ordering(tmp_path):
    findings = [
        Finding("b.py", 9, "SL002", "error", "m1"),
        Finding("a.py", 20, "SL001", "error", "m2"),
        Finding("a.py", 3, "SL003", "error", "m3"),
        Finding("a.py", 3, "SL001", "error", "m4"),
    ]
    out = sort_findings(findings)
    assert [(f.path, f.line, f.rule) for f in out] == [
        ("a.py", 3, "SL001"), ("a.py", 3, "SL003"),
        ("a.py", 20, "SL001"), ("b.py", 9, "SL002")]
    assert render_report(findings) == render_report(reversed(findings))
    # two scans of the same tree render identically
    (tmp_path / "m.py").write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    print(x)\n    return x\n")
    r1 = render_report(run_ast_checks(tmp_path))
    r2 = render_report(run_ast_checks(tmp_path))
    assert r1 == r2 and "SL001" in r1


# ---------------------------------------------------------------------------
# trace-level regressions (acceptance demos)
# ---------------------------------------------------------------------------

def test_sl101_catches_oversized_scratch_config():
    from repro.analyze.trace_checks import check_vmem
    over = {"kind": "dense", "n": 131072, "d": 131072, "K": 8,
            "tile_n": 131072, "label": "oversized"}
    fits = {"kind": "dense", "n": 1024, "d": 2048, "K": 4}
    fs = check_vmem(REPO, configs=[over, fits])
    assert len(fs) == 1 and fs[0].rule == "SL101"
    assert "oversized" in fs[0].message and "VMEM" in fs[0].message
    # sparse twin: a huge nnz tile blows the budget the same way
    from repro.analyze.trace_checks import config_vmem_bytes
    big, _, _ = config_vmem_bytes(
        {"kind": "sparse", "n": 2048, "nblk": 128, "tile": 16384, "K": 4})
    small, _, _ = config_vmem_bytes(
        {"kind": "sparse", "n": 2048, "nblk": 128, "tile": 16, "K": 4})
    assert big > 16 * 2 ** 20 > small


def test_sl101_registered_bench_configs_fit_budget():
    from repro.analyze.trace_checks import (check_vmem,
                                            registered_vmem_configs)
    assert len(registered_vmem_configs(REPO)) >= 4   # dense+sparse, 2 variants
    assert check_vmem(REPO) == []


def test_sl102_catches_leaked_python_scalar(tmp_path):
    # a float leaked into the trace key (here: a per-call static arg, the
    # λ-path failure mode) must retrace; the clean twin must not
    (tmp_path / "shotgun_lint_fixtures.py").write_text(textwrap.dedent("""
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("lam",))
        def _leaky(x, lam):
            return x * lam

        @jax.jit
        def _clean(x, lam):
            return x * lam

        RETRACE_TARGETS = [
            ("leaky", lambda: _leaky(jnp.ones(8), lam=0.5),
                      lambda: _leaky(jnp.ones(8), lam=0.6)),
            ("clean", lambda: _clean(jnp.ones(8), jnp.float32(0.5)),
                      lambda: _clean(jnp.ones(8), jnp.float32(0.6))),
        ]
    """))
    from repro.analyze.trace_checks import check_retrace
    fs = check_retrace(tmp_path)
    assert len(fs) == 1 and fs[0].rule == "SL102"
    assert "'leaky'" in fs[0].message and "_leaky" in fs[0].message


def test_sl102_solver_entry_hits_cache():
    # one real SOLVER_NAMES entry end-to-end: same shapes, different key
    # and lam values must hit the jaxpr cache (the full sweep runs in the
    # CI lint-analyze job)
    from repro.analyze.trace_checks import count_retraces
    import jax
    import jax.numpy as jnp
    from repro.core import objectives as obj
    from repro.core.shotgun import shotgun_solve
    from repro.data import synthetic as syn

    A, y, _ = syn.sparco(seed=0, n=128, d=256)
    prob = obj.make_problem(A, y, lam=0.4)
    prob2 = obj.Problem(A=prob.A, y=prob.y, lam=jnp.float32(0.45),
                        loss=prob.loss, scales=prob.scales)
    leaked = count_retraces(
        lambda: shotgun_solve(prob, jax.random.PRNGKey(0), P=4, rounds=3),
        lambda: shotgun_solve(prob2, jax.random.PRNGKey(1), P=4, rounds=3))
    assert leaked == []


def test_sl103_catches_misnamed_mesh_axis():
    from repro.analyze.trace_checks import probe_shard_map
    err = probe_shard_map((1,), ("f",), "g")     # axis "g" does not exist
    assert err is not None and "g" in err
    assert probe_shard_map((1,), ("f",), "f") is None


def test_sl103_axis_literal_sweep(tmp_path):
    from repro.analyze.trace_checks import _sweep_axis_literals
    d = tmp_path / "src" / "repro" / "core"
    d.mkdir(parents=True)
    (d / "sharded.py").write_text(textwrap.dedent("""
        from jax.sharding import PartitionSpec as P
        import jax

        SPEC_BAD = P("ghost")
        SPEC_OK = P("f", None)

        def merge(x):
            return jax.lax.psum(x, "ghost")
    """))
    fs = _sweep_axis_literals(tmp_path)
    assert [f.rule for f in fs] == ["SL103"] * 2
    assert all("ghost" in f.message for f in fs)
    assert _sweep_axis_literals(REPO) == []


# ---------------------------------------------------------------------------
# whole repo + CLI
# ---------------------------------------------------------------------------

def test_whole_repo_ast_rules_clean():
    report = run_checkers(REPO, rules=list(AST_RULES))
    assert report.ok, render_report(report.findings)
    assert report.unused_allows == []


def test_cli_exits_nonzero_on_seeded_tree(tmp_path):
    # one violation per rule: SL001-SL003 via a source file, SL101-SL103
    # via the fixture hook — the CLI must report all six and exit 1
    (tmp_path / "bad.py").write_text(textwrap.dedent("""
        import jax
        import numpy as np

        @jax.jit
        def f(A, x, block):
            assert A.shape[1] % block == 0
            return jax.lax.dot_general(
                A, x, (((1,), (0,)), ((), ()))) * np.random.rand()
    """))
    (tmp_path / "shotgun_lint_fixtures.py").write_text(textwrap.dedent("""
        import functools
        import jax
        import jax.numpy as jnp

        VMEM_CONFIGS = [{"kind": "dense", "n": 131072, "d": 131072, "K": 8,
                         "tile_n": 131072, "label": "oversized"}]

        @functools.partial(jax.jit, static_argnames=("lam",))
        def _leaky(x, lam):
            return x * lam

        RETRACE_TARGETS = [("leaky",
                            lambda: _leaky(jnp.ones(8), lam=0.5),
                            lambda: _leaky(jnp.ones(8), lam=0.6))]

        SPEC_PROBES = [("bad-axis", (1,), ("f",), "ghost")]
    """))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "shotgun_lint.py"),
         "--all", "--root", str(tmp_path), "--allowlist", "none"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    for rule in ("SL001", "SL002", "SL003", "SL101", "SL102", "SL103"):
        assert rule in proc.stdout, (rule, proc.stdout)


def test_cli_ast_level_exits_zero_on_clean_tree(tmp_path):
    (tmp_path / "ok.py").write_text("def f(x):\n    return x + 1\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "shotgun_lint.py"),
         "--ast", "--root", str(tmp_path), "--allowlist", "none"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


# ---------------------------------------------------------------------------
# BENCH trajectory artifact (satellite: merge_root repair)
# ---------------------------------------------------------------------------

def test_bench_root_has_toplevel_trajectory_fields():
    data = json.loads((REPO / "BENCH_kernels.json").read_text())
    assert isinstance(data, dict) and data["rows"]
    traj = [k for k in data
            if k.startswith("speedup_") or k == "overlap_efficiency"]
    assert traj, sorted(data)


def test_merge_root_idempotent_and_legacy_tolerant(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "REPO_ROOT", tmp_path)
    root = tmp_path / "BENCH_kernels.json"
    # legacy bare-list artifact migrates on first touch
    root.write_text(json.dumps([
        {"n": 1, "speedup_fused_vs_block": 2.0},
        {"bench": "sparse", "n": 2,
         "speedup_fused_sparse_vs_block_sparse": 3.0}]))
    common.merge_root([{"bench": "sharded", "n": 3,
                        "overlap_efficiency": 0.9}], tag="sharded")
    data = json.loads(root.read_text())
    assert data["speedup_fused_vs_block"] == 2.0
    assert data["speedup_fused_sparse_vs_block_sparse"] == 3.0
    assert data["overlap_efficiency"] == 0.9
    assert len(data["rows"]) == 3
    # re-merging the same rows changes nothing (idempotent)
    common.merge_root([{"bench": "sharded", "n": 3,
                        "overlap_efficiency": 0.9}], tag="sharded")
    assert json.loads(root.read_text()) == data
    # replacing a tag's rows drops its trajectory contribution
    common.merge_root([], tag="sharded")
    data = json.loads(root.read_text())
    assert "overlap_efficiency" not in data and len(data["rows"]) == 2
