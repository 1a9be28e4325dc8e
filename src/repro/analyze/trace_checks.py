"""Trace-level shotgun-lint rules (DESIGN §10) — these import the checked
code and run tiny probes under jax-on-CPU, so they catch what no AST walk
can: actual VMEM footprints, actual jaxpr cache behaviour, actual mesh/spec
binding.

  SL101  VMEM budget        every registered fused config (the rows of the
                            committed ``BENCH_kernels.json`` perf artifact)
                            must fit its whole VMEM resident set — scratch +
                            BlockSpec tiles — inside ``VMEM_BUDGET``
                            per ``fused_vmem_bytes`` (dense) and
                            ``fused_sparse_vmem_bytes`` (BlockedCSC).
                            Interpret mode never notices an oversized
                            scratch; real hardware OOMs at compile time.
  SL102  retrace leak       tracing each ``SOLVER_NAMES`` entry twice on
                            shape-identical inputs must hit the jaxpr cache
                            — a Python scalar leaked into a closure or a
                            per-call static argument retraces (and for the
                            fused kernels, re-unrolls) every λ-path step.
  SL103  spec consistency   shard_map in_specs / out_specs / psum axis
                            names must exist on the meshes ``launch/mesh.py``
                            can build (1-D feature ``("f",)`` and the PR 7
                            2-D ``("pod", "f")`` hierarchy): literal axis
                            strings are swept by AST against the known axis
                            vocabulary, and live probes bind the sharded
                            solver to both mesh shapes.

A fixture tree can seed violations for any of the three rules by placing a
``shotgun_lint_fixtures.py`` at its root defining any of::

    VMEM_CONFIGS     list of dicts — {"kind": "dense", n, d, K[, tile_n,
                     emit_dz, a_bytes]} or {"kind": "sparse", n, nblk,
                     tile, K[, emit_dz, val_bytes]}
    RETRACE_TARGETS  list of (label, call_a, call_b) — two zero-arg thunks
                     that must hit the same jaxpr cache entries
    SPEC_PROBES      list of (label, mesh_shape, mesh_axes, spec_axis)

(the repo's own tree has no fixture module, so the defaults above apply).
"""
from __future__ import annotations

import ast
import importlib.util
import json
import pathlib
import sys
from typing import Iterable

from repro.analyze.findings import Finding

# Every axis name a repo mesh can carry: launch/mesh.py production + host
# meshes ("pod"/"data"/"model"), the feature mesh ("f"), and the 2-D
# solver hierarchy outer axis ("pod").  Tests use throwaway "x" meshes.
KNOWN_AXES = frozenset({"f", "pod", "data", "model", "x"})

# Files whose shard_map / PartitionSpec axis literals SL103 sweeps.
SPEC_SWEEP_FILES = ("core/sharded.py", "core/engines.py", "launch/specs.py",
                    "dist/collectives.py")

_PSUM_FAMILY = {"psum", "psum_scatter", "all_gather", "all_to_all",
                "axis_index", "pmean", "ppermute"}

FIXTURE_MODULE = "shotgun_lint_fixtures.py"


def load_fixture_module(root: pathlib.Path):
    """Import ``<root>/shotgun_lint_fixtures.py`` when present (fixture
    trees seed trace-level violations through it); None otherwise."""
    path = pathlib.Path(root) / FIXTURE_MODULE
    if not path.exists():
        return None
    spec = importlib.util.spec_from_file_location("shotgun_lint_fixtures",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    # registered so jit_cache_sizes() can see the fixture's jitted fns
    sys.modules["shotgun_lint_fixtures"] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# SL101 — VMEM budget
# ---------------------------------------------------------------------------

def config_vmem_bytes(cfg: dict) -> tuple[int, str, int]:
    """(bytes, anchor_path, anchor_line) for one fused-config dict."""
    import inspect

    kind = cfg.get("kind", "dense")
    if kind == "dense":
        from repro.kernels import shotgun_block as sb
        tile_n = cfg.get("tile_n") or sb.auto_tile_n(
            cfg["n"], cfg.get("block", sb.BLOCK), d=cfg["d"])
        bytes_ = sb.fused_vmem_bytes(
            cfg["n"], cfg["d"], cfg["K"], block=cfg.get("block", sb.BLOCK),
            tile_n=tile_n, emit_dz=cfg.get("emit_dz", False),
            a_bytes=cfg.get("a_bytes", 4), slots=cfg.get("slots", 1),
            loss=cfg.get("loss", "lasso"))
        fn = sb.fused_vmem_bytes
    else:
        from repro.kernels import shotgun_sparse as ss
        bytes_ = ss.fused_sparse_vmem_bytes(
            cfg["n"], cfg["nblk"], cfg["tile"], cfg["K"],
            block=cfg.get("block", 128), emit_dz=cfg.get("emit_dz", False),
            val_bytes=cfg.get("val_bytes", 4), slots=cfg.get("slots", 1),
            loss=cfg.get("loss", "lasso"))
        fn = ss.fused_sparse_vmem_bytes
    path = pathlib.Path(inspect.getsourcefile(fn))
    line = inspect.getsourcelines(fn)[1]
    try:
        rel = path.resolve().relative_to(
            pathlib.Path.cwd().resolve()).as_posix()
    except ValueError:
        rel = path.as_posix()
    return bytes_, rel, line


def registered_vmem_configs(root: pathlib.Path) -> list[dict]:
    """Fused configs registered in the committed BENCH_kernels.json perf
    artifact (both legacy list and trajectory-dict formats), with a builtin
    fallback mirroring the benchmark grids when the artifact is absent.
    Engine variants (``emit_dz=True``) are what the sharded solver launches,
    so each point is checked in both variants."""
    bench = pathlib.Path(root) / "BENCH_kernels.json"
    if bench.exists():
        data = json.loads(bench.read_text())
        rows = data["rows"] if isinstance(data, dict) else data
    else:
        rows = [{"n": 1024, "d": 2048, "K": 4},
                {"n": 2048, "d": 8192, "K": 4},
                {"bench": "sparse", "n": 2048, "d": 16384, "K": 4,
                 "tile": 16},
                {"bench": "sparse", "n": 2048, "d": 65536, "K": 4,
                 "tile": 16}]
    configs = []
    for row in rows:
        if not {"n", "d", "K"} <= set(row):
            continue                       # sharded wall-time rows
        if row.get("bench") == "serve":
            # continuous-batched service rows (DESIGN §11): the stacked
            # kernel holds ``slots`` copies of every per-problem scratch
            # buffer, so the budget is checked on the whole stack (the
            # service never emits dz).  Shapes are the stream canvas —
            # samples padded to a TILE_N multiple, features to BLOCK.
            from repro.kernels.shotgun_block import BLOCK, TILE_N
            slots = row.get("slots", 1)
            configs.append({
                "kind": "dense", "n": row["n"] + (-row["n"]) % TILE_N,
                "d": row["d"] + (-row["d"]) % BLOCK, "K": row["K"],
                "slots": slots,
                "label": f"serve n={row['n']} d={row['d']} K={row['K']} "
                         f"slots={slots}"})
            continue
        if row.get("bench") == "logreg":
            # fused logistic rows (DESIGN §12): budget both kernel twins —
            # the gradient-form tile and the Newton variant whose curvature
            # scratch adds one n-vector and one (K, block) accumulator.
            for loss in ("logistic", "logistic_newton"):
                for emit_dz in (False, True):
                    configs.append({
                        "kind": "dense", "n": row["n"], "d": row["d"],
                        "K": row["K"], "tile_n": row.get("tile_n"),
                        "emit_dz": emit_dz, "loss": loss,
                        "label": f"logreg n={row['n']} d={row['d']} "
                                 f"K={row['K']} loss={loss}"})
            continue
        for emit_dz in (False, True):
            if row.get("bench") == "sparse":
                configs.append({
                    "kind": "sparse", "n": row["n"],
                    "nblk": row["d"] // 128, "tile": row["tile"],
                    "K": row["K"], "emit_dz": emit_dz,
                    "label": f"sparse n={row['n']} d={row['d']} "
                             f"K={row['K']} tile={row['tile']}"})
            elif row.get("bench") is None:
                configs.append({
                    "kind": "dense", "n": row["n"], "d": row["d"],
                    "K": row["K"], "emit_dz": emit_dz,
                    "label": f"dense n={row['n']} d={row['d']} "
                             f"K={row['K']}"})
    return configs


def check_vmem(root: pathlib.Path, configs: list[dict] | None = None,
               budget: int | None = None) -> list[Finding]:
    from repro.kernels.shotgun_block import VMEM_BUDGET
    budget = VMEM_BUDGET if budget is None else budget
    if configs is None:
        fixtures = load_fixture_module(root)
        configs = getattr(fixtures, "VMEM_CONFIGS", None) if fixtures \
            else None
    if configs is None:
        configs = registered_vmem_configs(root)
    findings = []
    for cfg in configs:
        bytes_, path, line = config_vmem_bytes(cfg)
        if bytes_ > budget:
            label = cfg.get("label") or ", ".join(
                f"{k}={v}" for k, v in sorted(cfg.items()) if k != "kind")
            findings.append(Finding(
                path, line, "SL101", "error",
                f"fused config ({label}, emit_dz={cfg.get('emit_dz', False)}"
                f") needs {bytes_} B of VMEM > {budget} B budget — shrink "
                "tile/K or split the launch; interpret mode hides this, "
                "real hardware OOMs at compile time"))
    return findings


# ---------------------------------------------------------------------------
# SL102 — retrace leak
# ---------------------------------------------------------------------------

def jit_cache_sizes() -> dict[str, int]:
    """Snapshot ``_cache_size()`` of every jitted function reachable from a
    loaded ``repro.*`` module (PjitFunction exposes it in jax 0.4.x)."""
    sizes: dict[str, int] = {}
    for modname, mod in list(sys.modules.items()):
        if not (modname == "repro" or modname.startswith("repro.")
                or modname == "shotgun_lint_fixtures"):
            continue
        for attr, val in list(vars(mod).items()):
            size_fn = getattr(val, "_cache_size", None)
            if callable(size_fn):
                try:
                    sizes[f"{modname}.{attr}"] = int(size_fn())
                except Exception:
                    pass
    return sizes


def count_retraces(call_a, call_b) -> list[str]:
    """Names of repro jit caches that grew on ``call_b`` after ``call_a``
    warmed them.  The two thunks must build shape-identical (but not
    value-identical) inputs; any growth on the second call is a retrace —
    some Python value is leaking into the trace key."""
    import jax

    jax.block_until_ready(call_a())
    warm = jit_cache_sizes()
    jax.block_until_ready(call_b())
    cold = jit_cache_sizes()
    return sorted(name for name, size in cold.items()
                  if size > warm.get(name, 0))


def default_retrace_targets() -> list[tuple]:
    """(label, call_a, call_b) per SOLVER_NAMES entry: same problem and
    shapes, different PRNG key (and a different lam value — lam is a traced
    Problem leaf, so it must not enter the trace key either)."""
    import jax
    import jax.numpy as jnp

    from repro.core import objectives as obj
    from repro.core.shotgun import SOLVER_NAMES, get_solver
    from repro.data import synthetic as syn

    A, y, _ = syn.sparco(seed=0, n=256, d=512)
    prob = obj.make_problem(A, y, lam=0.4)
    prob2 = obj.Problem(A=prob.A, y=prob.y, lam=jnp.float32(0.45),
                        loss=prob.loss, scales=prob.scales)
    Al, yl, _ = syn.logistic_data(seed=0, n=256, d=128)
    lprob = obj.make_problem(Al, yl, lam=0.05, loss=obj.LOGISTIC)
    lprob2 = obj.Problem(A=lprob.A, y=lprob.y, lam=jnp.float32(0.06),
                         loss=lprob.loss, scales=lprob.scales)
    Als, yls, _ = syn.logistic_data(seed=0, n=256, d=128, density=0.1,
                                    layout="bcsc")
    slprob = obj.make_problem(Als, yls, lam=0.05, loss=obj.LOGISTIC)
    slprob2 = obj.Problem(A=slprob.A, y=slprob.y, lam=jnp.float32(0.06),
                          loss=slprob.loss, scales=slprob.scales)
    k0, k1 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)

    def calls(name):
        solve = get_solver(name)
        if name == "shooting":
            return (lambda: solve(prob, k0, rounds=3),
                    lambda: solve(prob2, k1, rounds=3))
        if name == "shotgun":
            return (lambda: solve(prob, k0, P=4, rounds=3),
                    lambda: solve(prob2, k1, P=4, rounds=3))
        if name == "shotgun_dup":
            dp, dp2 = obj.dup_from(prob), obj.dup_from(prob2)
            return (lambda: solve(dp, k0, P=4, rounds=3),
                    lambda: solve(dp2, k1, P=4, rounds=3))
        if name == "shotgun_cdn":
            return (lambda: solve(lprob, k0, P=4, rounds=2),
                    lambda: solve(lprob2, k1, P=4, rounds=2))
        if name == "shooting_cdn":
            return (lambda: solve(lprob, k0, rounds=2),
                    lambda: solve(lprob2, k1, rounds=2))
        if name == "block_fused":
            return (lambda: solve(prob, k0, K=1, rounds=2,
                                  rounds_per_launch=2),
                    lambda: solve(prob2, k1, K=1, rounds=2,
                                  rounds_per_launch=2))
        if name == "sharded":
            return (lambda: solve(prob, k0, P_local=2, rounds=2,
                                  engine="scalar"),
                    lambda: solve(prob2, k1, P_local=2, rounds=2,
                                  engine="scalar"))
        if name == "shotgun_logreg_fused":
            return (lambda: solve(lprob, k0, K=1, rounds=2,
                                  rounds_per_launch=2),
                    lambda: solve(lprob2, k1, K=1, rounds=2,
                                  rounds_per_launch=2))
        if name == "sparse_logreg_fused":
            return (lambda: solve(slprob, k0, K=1, rounds=2,
                                  rounds_per_launch=2),
                    lambda: solve(slprob2, k1, K=1, rounds=2,
                                  rounds_per_launch=2))
        raise ValueError(f"no retrace target for solver {name!r}")

    targets = [(name,) + calls(name) for name in SOLVER_NAMES]
    targets.extend(_batched_retrace_targets())
    return targets


def _batched_retrace_targets() -> list[tuple]:
    """Batched entry points (DESIGN §11.2): the serving admission contract
    promises ONE jaxpr per stream canvas, so solving a second stream of
    different problems/λ/keys on the same canvas must hit the cached
    batched kernels — a leak here recompiles on every admission."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import objectives as obj
    from repro.core.batched import batched_block_shotgun_solve
    from repro.data.sparse import BlockedCSC

    def dense_probs(seed):
        rng = np.random.default_rng(seed)
        out = []
        for s in range(2):
            A = rng.standard_normal((192, 384)).astype(np.float32)
            y = rng.standard_normal(192).astype(np.float32)
            out.append(obj.make_problem(jnp.asarray(A), jnp.asarray(y),
                                        lam=0.1 * (s + 1) + 0.01 * seed))
        return out

    def sparse_probs(seed):
        # fixed nnz-tile depth: the canvas (not the draw) fixes the shape
        out = []
        for p in dense_probs(seed):
            A = np.array(p.A)              # writable copy
            A[np.random.default_rng(seed + 7).random(A.shape) < 0.8] = 0.0
            sp = obj.make_problem(jnp.asarray(A), p.y, lam=float(p.lam))
            out.append(sp._replace(A=BlockedCSC.from_dense(sp.A, block=128,
                                                           tile=64)))
        return out

    def solve(probs, seed):
        keys = [jax.random.PRNGKey(seed + s) for s in range(len(probs))]
        return batched_block_shotgun_solve(probs, keys, 1, 2,
                                           rounds_per_launch=2)

    return [
        ("batched_dense",
         lambda: solve(dense_probs(0), 0),
         lambda: solve(dense_probs(1), 2)),
        ("batched_sparse",
         lambda: solve(sparse_probs(0), 0),
         lambda: solve(sparse_probs(1), 2)),
    ]


def check_retrace(root: pathlib.Path,
                  targets: list[tuple] | None = None) -> list[Finding]:
    if targets is None:
        fixtures = load_fixture_module(root)
        targets = getattr(fixtures, "RETRACE_TARGETS", None) if fixtures \
            else None
    if targets is None:
        targets = default_retrace_targets()
    findings = []
    for label, call_a, call_b in targets:
        try:
            leaked = count_retraces(call_a, call_b)
        except Exception as e:                      # probe itself broke
            findings.append(Finding(
                "src/repro/core/shotgun.py", 0, "SL102", "error",
                f"retrace probe {label!r} failed to run: {e!r}"))
            continue
        for name in leaked:
            findings.append(Finding(
                "src/repro/core/shotgun.py", 0, "SL102", "error",
                f"solver {label!r}: {name} retraced on shape-identical "
                "inputs — a Python value is leaking into the trace key "
                "(closure scalar or per-call static arg); every λ-path "
                "step pays a recompile"))
    return findings


# ---------------------------------------------------------------------------
# SL103 — spec consistency
# ---------------------------------------------------------------------------

def probe_shard_map(mesh_shape, mesh_axes, spec_axis) -> str | None:
    """Bind a trivial shard_map with ``in_specs=P(spec_axis)`` to a host
    mesh of ``mesh_shape``/``mesh_axes`` and run it.  Returns None on
    success, the error string when the axis does not exist on the mesh —
    the live form of the SL103 invariant, reusable from tests."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    n_need = 1
    for s in mesh_shape:
        n_need *= s
    if len(jax.devices()) < n_need:
        return None                                 # cannot build the mesh
    try:
        mesh = make_mesh(mesh_shape, mesh_axes)
        size = n_need * 8
        f = jax.shard_map(lambda a: jax.lax.psum(a, spec_axis), mesh=mesh,
                          in_specs=(P(spec_axis),), out_specs=P(None),
                          check_vma=False)
        jax.block_until_ready(f(jnp.ones(size, jnp.float32)))
        return None
    except Exception as e:
        return f"{type(e).__name__}: {e}"


def _sweep_axis_literals(root: pathlib.Path) -> list[Finding]:
    """AST sweep: literal axis-name strings in P(...)/PartitionSpec(...)
    and psum-family calls must be in the known mesh-axis vocabulary."""
    src = pathlib.Path(root) / "src" / "repro"
    base = src if src.is_dir() else pathlib.Path(root)
    findings = []
    for rel in SPEC_SWEEP_FILES:
        path = base / rel
        if not path.exists():
            continue
        rel_repo = path.relative_to(root).as_posix() \
            if path.is_relative_to(root) else path.as_posix()
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fname = node.func.attr if isinstance(node.func, ast.Attribute) \
                else node.func.id if isinstance(node.func, ast.Name) else ""
            if fname in ("P", "PartitionSpec"):
                strings = [a for a in node.args
                           if isinstance(a, ast.Constant)
                           and isinstance(a.value, str)]
            elif fname in _PSUM_FAMILY:
                strings = [a for a in list(node.args)
                           + [k.value for k in node.keywords]
                           if isinstance(a, ast.Constant)
                           and isinstance(a.value, str)]
            else:
                continue
            for s in strings:
                if s.value not in KNOWN_AXES:
                    findings.append(Finding(
                        rel_repo, s.lineno, "SL103", "error",
                        f"axis name {s.value!r} in {fname}(...) is not an "
                        f"axis any launch/mesh.py mesh carries "
                        f"({sorted(KNOWN_AXES)}) — shard_map will fail to "
                        "bind at run time"))
    return findings


def _live_probes(root: pathlib.Path) -> list[Finding]:
    """Bind the sharded solver to the meshes launch/mesh.py builds: the 1-D
    ("f",) feature mesh always, the 2-D ("pod", "f") hierarchy when enough
    devices exist.  A failure anchors at sharded.py's shard_map call."""
    import jax

    findings = []
    src = pathlib.Path(root) / "src" / "repro" / "core" / "sharded.py"
    anchor_line = 0
    if src.exists():
        for i, ln in enumerate(src.read_text().splitlines(), 1):
            if "shard_map(" in ln:
                anchor_line = i
                break
    anchor = "src/repro/core/sharded.py"

    from repro.core import objectives as obj
    from repro.core.sharded import shotgun_sharded_solve
    from repro.data import synthetic as syn
    from repro.launch.mesh import make_mesh

    A, y, _ = syn.sparco(seed=0, n=256, d=512)
    prob = obj.make_problem(A, y, lam=0.4)
    key = jax.random.PRNGKey(0)

    ndev = len(jax.devices())
    try:                                            # 1-D feature mesh
        shotgun_sharded_solve(prob, key, P_local=2, rounds=2,
                              engine="scalar")
    except Exception as e:
        findings.append(Finding(
            anchor, anchor_line, "SL103", "error",
            f"sharded solve failed to bind the 1-D ('f',) feature mesh "
            f"({ndev} devices): {type(e).__name__}: {e}"))
    if ndev >= 4 and ndev % 2 == 0:                 # 2-D (pod, f) hierarchy
        try:
            mesh = make_mesh((2, ndev // 2), ("pod", "f"))
        except Exception:
            mesh = None
        if mesh is not None:
            try:
                shotgun_sharded_solve(prob, key, P_local=2, rounds=2,
                                      engine="scalar", mesh=mesh,
                                      hierarchical=True)
            except Exception as e:
                findings.append(Finding(
                    anchor, anchor_line, "SL103", "error",
                    f"sharded solve failed to bind the 2-D ('pod', 'f') "
                    f"hierarchical mesh {mesh.devices.shape}: "
                    f"{type(e).__name__}: {e}"))
    return findings


def check_specs(root: pathlib.Path,
                probes: list[tuple] | None = None) -> list[Finding]:
    findings = _sweep_axis_literals(root)
    if probes is None:
        fixtures = load_fixture_module(root)
        probes = getattr(fixtures, "SPEC_PROBES", None) if fixtures \
            else None
    if probes is not None:
        for label, mesh_shape, mesh_axes, spec_axis in probes:
            err = probe_shard_map(tuple(mesh_shape), tuple(mesh_axes),
                                  spec_axis)
            if err:
                findings.append(Finding(
                    "src/repro/core/sharded.py", 0, "SL103", "error",
                    f"spec probe {label!r}: axis {spec_axis!r} failed to "
                    f"bind on mesh {tuple(mesh_axes)}: {err}"))
    else:
        findings.extend(_live_probes(root))
    return findings


TRACE_RULES = {
    "SL101": check_vmem,
    "SL102": check_retrace,
    "SL103": check_specs,
}


def run_trace_checks(root: pathlib.Path,
                     rules: Iterable[str] | None = None) -> list[Finding]:
    wanted = set(rules) if rules is not None else set(TRACE_RULES)
    findings: list[Finding] = []
    for rule, check in TRACE_RULES.items():
        if rule in wanted:
            findings.extend(check(pathlib.Path(root)))
    return findings
