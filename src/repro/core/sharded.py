"""Distributed Shotgun via shard_map: a thin driver over round engines
(DESIGN §3).

The paper's multicore implementation shares one ``Ax`` vector through atomic
compare-and-swap.  On an SPMD mesh there is no shared memory; instead:

  * columns of A (features) are sharded over the mesh's devices — over ALL
    mesh axes flattened, so both a 1-D ``("f",)`` mesh and a production
    ``(pod, f)`` mesh work,
  * every device holds the full margin ``z`` (n,), replicated,
  * each merge window, device k runs a **round engine** (``core/engines.py``:
    scalar jnp / fused multi-round Pallas) for R rounds
    against the last merged ``z`` and emits Δz_k = A_k δx_k,
  * one all-reduce merges the contributions — the shared-Ax write.

Two merge cadences:

  ``merge="round"``    R = 1: one psum per round.  No staleness — this is
                       exactly Alg. 2 with P = P_shard × num_devices
                       (devices own disjoint coordinates, which only shrinks
                       Lemma 3.3's interference term), and for the fused
                       engine on a 1-shard mesh it is trace-equivalent to
                       ``block_shotgun_solve``.
  ``merge="launch"``   R = rounds_per_launch stale rounds per merge: each
                       shard sees its own updates immediately but other
                       shards' only at merge boundaries — the paper's
                       interference/staleness trade-off (Lemma 3.3) as an
                       explicit knob, paying 1/R of the collective traffic.

``pipeline=True`` software-pipelines the merge itself (DESIGN §3.4): the
carry holds the shard's own not-yet-merged wire ``w_pend`` from the previous
segment, each step issues the psum of ``w_pend`` — which the current
segment's engine launch does not read, so the collective and the compute
have no data dependence and XLA's latency-hiding scheduler can overlap them
— while the engine runs against the view ``z + w_pend`` (own updates
visible, other shards' one segment stale).  The catch-up ``z + psum(w_pend)``
counts the shard's own pending wire exactly once, an epilogue merge drains
the final in-flight segment, and on one shard the view equals the fully
merged margin, so 1-shard pipelined reproduces 1-shard synchronous exactly.
Net effect: one extra segment of staleness for *other* shards' updates
(Lemma 3.3's budget, now with R_eff = 2R) buys the wire off the critical
path.

The Δz all-reduce optionally routes through the §7 wire layer: int8/top-k
compression with error feedback (``dist/compression.py``; the psum carries
the receiver-side dense reconstruction, ``wire_bytes`` does the byte
accounting surfaced by ``benchmarks/roofline.py``) and/or
``dist/collectives.hierarchical_psum`` on a 2-D (outer, inner) mesh so the
slow inter-pod hop carries 1/inner of the bytes.

``trace_every`` thins the objective bookkeeping (2 scalar psums) out of the
hot loop; it counts *merges*, so the trace length is
``rounds // merge_rounds // trace_every`` and the update trajectory is
unchanged by thinning.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import health
from repro.core import objectives as obj
from repro.core.engines import ENGINE_NAMES, ScalarEngine, make_engine
from repro.core.health import GuardConfig
from repro.core.objectives import Problem
from repro.core.shotgun import Result, Trace
from repro.core.spec import SolverSpec, reject_legacy_kwargs
from repro.data.sparse import BlockedCSC, pad_feature_blocks

MERGE_MODES = ("round", "launch")
COMPRESSION_SCHEMES = ("none", "bf16", "int8", "topk")

_FAULT_SALT = 0x5EED  # fault keys branch off the solve key here (DESIGN §9.3)


def pad_features(A: jax.Array, num_shards: int) -> jax.Array:
    """Right-pad A with zero columns so d divides evenly across shards.

    Zero columns are fixed points of the update (grad = 0 -> delta = 0), so
    padding never changes the trajectory of real coordinates.
    """
    d = A.shape[1]
    d_pad = (-d) % num_shards
    if d_pad:
        A = jnp.concatenate([A, jnp.zeros((A.shape[0], d_pad), A.dtype)], axis=1)
    return A


def make_feature_mesh(devices=None) -> Mesh:
    devices = jax.devices() if devices is None else devices
    import numpy as np
    return Mesh(np.array(devices), ("f",))


def _compress_dz(dz, ef, scheme: str, topk_frac: float):
    """One §7 wire step for the Δz merge: returns (wire, ef_new) where wire
    is the receiver-side dense reconstruction of ``dz + ef`` and ef_new the
    error-feedback residual of what the scheme dropped."""
    from repro.dist import compression as C
    wire, ef_new = C.compress_grads({"dz": dz}, {"dz": ef}, scheme=scheme,
                                    topk_frac=topk_frac)
    return wire["dz"], ef_new["dz"]


@functools.partial(jax.jit, static_argnames=(
    "engine", "rounds", "merge_rounds", "mesh", "trace_every",
    "compression", "topk_frac", "hierarchical", "guard", "faults",
    "pipeline"))
def _engine_solve(A, y, mask, x0, lam, beta, key, *, engine, rounds: int,
                  merge_rounds: int, mesh: Mesh, trace_every: int,
                  compression: str = "none", topk_frac: float = 0.01,
                  hierarchical: bool = False,
                  guard: GuardConfig | None = None,
                  faults=None, pipeline: bool = False) -> Result:
    """shard_map driver over a RoundEngine on the (pre-padded) problem.

    ``guard`` arms the §9 sentinel at trace-point granularity: each
    bookkeeping step checks F (and the psum of the engines' health flags)
    against the last-good snapshot, rolling back (x_l, z) and halving the
    engines' ``p_eff`` on a trip — backoff is a dynamic scalar in the
    carry, so it never recompiles.  ``faults`` (a ``dist.faults.FaultPlan``)
    routes every Δz merge through ``faulty_psum``'s checksummed bounded
    re-merge; fault keys are salted off the solve key so coordinate draws
    are bit-identical with and without injection.  With ``hierarchical``
    the re-merge rides the slow inter-pod hop
    (``dist.collectives.hierarchical_faulty_psum``).

    ``pipeline`` selects the double-buffered merge schedule (module
    docstring): the carry gains the pending wire ``w_pend``, trace points
    report F at the stale ``z`` (one segment behind ``x_l``), and the final
    result is fully drained.  Guarded pipelined solves drain at each trace
    point instead, so the sentinel snapshots a consistent (x, z, F) triple
    and a rollback leaves no update in flight — health flags reach it at
    most one segment late.
    """
    n, d = A.shape
    axes = tuple(mesh.axis_names)
    nshards = mesh.devices.size
    if rounds % merge_rounds:
        raise ValueError(
            f"rounds={rounds} not divisible by merge_rounds={merge_rounds}")
    n_merges = rounds // merge_rounds
    if n_merges % trace_every:
        raise ValueError(
            f"number of merges {n_merges} (= rounds {rounds} / merge_rounds "
            f"{merge_rounds}) not divisible by trace_every={trace_every}")
    if hierarchical:
        if len(axes) < 2:
            raise ValueError(
                f"hierarchical=True needs a 2-D (outer, inner) mesh, got "
                f"axes {axes}")
        inner = 1
        for ax in axes[1:]:
            inner *= mesh.shape[ax]
        if n % inner:
            raise ValueError(
                f"n={n} not divisible by inner mesh size {inner} "
                f"(hierarchical reduce-scatter)")

    def solve_local(A_blk, y_rep, m_rep, x0_blk, key_rep):
        me = jnp.int32(0)
        for ax in axes:                      # flattened shard index
            me = me * mesh.shape[ax] + jax.lax.axis_index(ax)
        z = jax.lax.psum(obj.matvec(A_blk, x0_blk), axes)  # global margin of x0
        ef = jnp.zeros(n, jnp.float32)             # §7 error feedback
        # fault keys ride a salted side-stream: solve draws stay bit-equal
        fkey = jax.random.fold_in(key_rep, _FAULT_SALT)

        def objective(z, x_l):
            f_data = obj.masked_data_loss(z, y_rep, m_rep, engine.loss)
            return f_data + lam * jax.lax.psum(jnp.sum(jnp.abs(x_l)), axes)

        def merge_wire(w, m, h):
            """One Δz merge over the §7/§9 wire: flat psum, hierarchical
            two-level reduce, fault-injected, or both (the checksummed
            re-merge rides the slow inter-pod hop, DESIGN §9.3)."""
            if faults is not None and hierarchical:
                from repro.dist.collectives import hierarchical_faulty_psum
                w_g, h_f = hierarchical_faulty_psum(
                    w, jax.random.fold_in(fkey, m), me, faults,
                    axes[0], axes[1:])
                h = jnp.maximum(h, h_f)
            elif faults is not None:
                from repro.dist.faults import faulty_psum
                w_g, h_f = faulty_psum(w, jax.random.fold_in(fkey, m), me,
                                       faults, axes)
                h = jnp.maximum(h, h_f)
            elif hierarchical:
                from repro.dist.collectives import hierarchical_psum
                w_g = hierarchical_psum(w, axes[0], axes[1:])
            else:
                w_g = jax.lax.psum(w, axes)
            return w_g, h

        def fold_keys(keys_m):
            if engine.fold_always or nshards > 1:  # decorrelate shards
                keys_m = jax.vmap(
                    lambda kt: jax.random.fold_in(kt, me))(keys_m)
            return keys_m

        def merge_fn(carry, keys_m):
            x_l, z, ef, p_eff, m, h = carry
            x_l, dz, h_e = engine.run(A_blk, y_rep, m_rep, lam, beta, z, x_l,
                                      fold_keys(keys_m), p_eff)
            if compression != "none":
                dz, ef = _compress_dz(dz, ef, compression, topk_frac)
            dz_g, h = merge_wire(dz, m, h)
            h = jnp.maximum(h, h_e)
            return (x_l, z + dz_g, ef, p_eff, m + 1, h), None

        def merge_fn_pipe(carry, keys_m):
            # double-buffered schedule (module docstring): the collective
            # carries the PREVIOUS segment's wire, which this segment's
            # engine launch does not read — no data dependence, so the two
            # can overlap.  The prologue step merges the zero w_pend0.
            x_l, z, w_pend, ef, p_eff, m, h = carry
            w_g, h = merge_wire(w_pend, m, h)
            x_l, dz, h_e = engine.run_segment(A_blk, y_rep, m_rep, lam, beta,
                                              z, w_pend, x_l,
                                              fold_keys(keys_m), p_eff)
            if compression != "none":
                # pend the receiver-side reconstruction, not the raw Δz, so
                # the next segment's view matches what the merge will add
                dz, ef = _compress_dz(dz, ef, compression, topk_frac)
            h = jnp.maximum(h, h_e)
            return (x_l, z + w_g, dz, ef, p_eff, m + 1, h), None

        step_fn = merge_fn_pipe if pipeline else merge_fn

        def outer_fn(carry, keys_o):
            # trace_every merges without objective bookkeeping, then one
            # F(x)/nnz evaluation (2 scalar psums) — the bookkeeping psums
            # cost as much wire as the dz psum itself when traced per merge
            inner_c, gs = (carry, None) if guard is None else carry
            inner_c, _ = jax.lax.scan(step_fn, inner_c, keys_o)
            if pipeline:
                x_l, z, w_pend, ef, p_eff, m, h = inner_c
            else:
                (x_l, z, ef, p_eff, m, h), w_pend = inner_c, None
            if guard is None:
                # pipelined trace points report F at the stale z — one
                # segment behind x_l (consistent across shards: z is
                # replicated, w_pend is not); the final result is drained
                f_out = objective(z, x_l)
            else:
                if pipeline:
                    # the sentinel needs a consistent (x, z, F) snapshot to
                    # roll back to: drain the in-flight wire at the trace
                    # point (one extra merge per trace_every), so a rollback
                    # leaves nothing pending and health flags arrive at most
                    # one segment late
                    w_g, h = merge_wire(w_pend, m, h)
                    z, w_pend, m = z + w_g, jnp.zeros_like(w_pend), m + 1
                # health flags are shard-local (non-finite local Δz, failed
                # re-merges) — combine before the replicated trip decision
                h_g = jax.lax.psum(h, axes)
                x_l, z, f_out, gs, bad = health.apply_sentinel(
                    gs, x_l, z, objective(z, x_l), factor=guard.factor,
                    p_floor=p_floor, health=h_g)
                # discarded updates invalidate their §7 error feedback too
                ef = jnp.where(bad, jnp.zeros_like(ef), ef)
                p_eff = gs.p_eff
            nnz = jax.lax.psum(jnp.sum(x_l != 0), axes)
            h0 = jnp.zeros((), jnp.float32)      # sentinel consumed the flag
            if pipeline:
                inner_c = (x_l, z, w_pend, ef, p_eff, m, h0)
            else:
                inner_c = (x_l, z, ef, p_eff, m, h0)
            return (inner_c if guard is None else (inner_c, gs)), (f_out, nnz)

        keys = jax.random.split(key_rep, rounds)
        keys = keys.reshape(n_merges // trace_every, trace_every,
                            merge_rounds, -1)
        x0_l = x0_blk.astype(jnp.float32)
        m0 = jnp.zeros((), jnp.int32)
        h0 = jnp.zeros((), jnp.float32)
        p0 = jnp.int32(engine.p_full)
        if pipeline:      # prologue: nothing pending before the first merge
            inner0 = (x0_l, z, jnp.zeros(n, jnp.float32), ef, p0, m0, h0)
        else:
            inner0 = (x0_l, z, ef, p0, m0, h0)
        if guard is None:
            inner_c, (fs, nnzs) = jax.lax.scan(outer_fn, inner0, keys)
            backoffs = jnp.zeros((), jnp.int32)
        else:
            gs0 = health.init_guard_state(x0_l, z, objective(z, x0_l),
                                          engine.p_full)
            (inner_c, gs), (fs, nnzs) = jax.lax.scan(
                outer_fn, (inner0, gs0), keys)
            backoffs = gs.backoffs
        x_l, z = inner_c[0], inner_c[1]
        if pipeline and guard is None:
            # epilogue: drain the final segment's in-flight wire (guarded
            # pipelined solves already drained at the last trace point)
            w_pend, m, h = inner_c[2], inner_c[5], inner_c[6]
            w_g, _ = merge_wire(w_pend, m, h)
            z = z + w_g
        return x_l, z, fs, nnzs, backoffs

    p_floor = 1 if guard is None else max(1, min(guard.p_min, engine.p_full))
    if isinstance(A, BlockedCSC):
        # column-block sharding: split the (nblk, tile, block) tiles on the
        # leading axis; metadata rides along untouched (engines read shapes
        # from the arrays, DESIGN §8)
        a_spec = jax.tree_util.tree_map(lambda _: P(axes, None, None), A)
    else:
        a_spec = P(None, axes)
    solve = jax.shard_map(
        solve_local, mesh=mesh,
        in_specs=(a_spec, P(None), P(None), P(axes), P(None)),
        out_specs=(P(axes), P(None), P(None), P(None), P()),
        check_vma=False,
    )
    x, z, fs, nnzs, backoffs = solve(A, y, mask, x0, key)
    return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                  status=health.status_from_trace(fs, backoffs))


# Legacy entry point, kept positional-compatible for benchmarks
# (``benchmarks/shotgun_scale.py`` lowers it against ShapeDtypeStructs).
def _sharded_solve(A, y, lam, beta, key, P_local: int, rounds: int,
                   mesh: Mesh, loss: str, trace_every: int = 1) -> Result:
    n, d = A.shape
    engine = ScalarEngine(P_local=P_local, loss=loss)
    ones = jnp.ones(n, jnp.float32)
    x0 = jnp.zeros(d, jnp.float32)
    return _engine_solve(A, y, ones, x0, lam, beta, key, engine=engine,
                         rounds=rounds, merge_rounds=1, mesh=mesh,
                         trace_every=trace_every)


def shotgun_sharded_solve(prob: Problem, key: jax.Array,
                          P_local: int | None = None,
                          rounds: int | None = None,
                          mesh: Mesh | None = None,
                          trace_every: int = 1, *, engine: str = "scalar",
                          merge: str = "round", rounds_per_launch: int = 8,
                          K: int = 2, tile_n: int | None = None,
                          x0: jax.Array | None = None,
                          compression: str = "none", topk_frac: float = 0.01,
                          hierarchical: bool = False,
                          pipeline: bool = False,
                          guard: GuardConfig | None = None,
                          faults=None,
                          ckpt_dir=None, ckpt_every: int = 0,
                          fail_at_merge: int | None = None,
                          resume: bool = False,
                          newton: bool = False,
                          spec: SolverSpec | None = None) -> Result:
    """Distributed Shotgun over any round engine (DESIGN §3).

    engine      "scalar" (P = P_local × shards coordinate updates/round),
                "fused" (P = K × 128 × shards via the fused Pallas
                kernel), "sparse_fused" (same P but over a BlockedCSC
                design via the nnz-tile kernel, DESIGN §8.3 — column
                blocks sharded on nblk; the margin view and Δz stay in
                VMEM for the whole merge window).
    merge       "round" — one Δz psum per round (no staleness);
                "launch" — ``rounds_per_launch`` stale rounds per merge.
    x0          optional warm start (λ-continuation); zero-padded and
                sharded, with z initialized to the psum of A x0.
    compression "none" | "bf16" | "int8" | "topk": Δz merges route through
                the §7 wire layer with error feedback.
    hierarchical  on a 2-D (outer, inner) mesh, merge Δz via
                reduce-scatter(inner) → psum(outer) → all-gather(inner).
    pipeline    double-buffered async merge (module docstring / DESIGN
                §3.4): each segment's Δz psum is issued one segment late
                with no data dependence on the current segment's compute,
                so the wire overlaps the engine launch; other shards'
                updates land one extra segment stale, a final drain keeps
                the returned (x, z) exact, and trace points report F at the
                stale margin.  Composes with compression, hierarchical,
                faults, and guard (guarded solves drain at trace points so
                the sentinel snapshot stays consistent).
    guard       §9 sentinel + adaptive-P backoff (``health.GuardConfig``);
                ``guard.p_min`` is in the engine's parallelism units.
    faults      §9.3 Δz fault injection (``dist.faults.FaultPlan``): every
                merge runs through the checksummed re-merging psum — on a
                2-D hierarchical mesh, through
                ``hierarchical_faulty_psum``'s inter-pod re-merge.
    ckpt_every  > 0 segments the solve at merge granularity (must be a
                multiple of ``trace_every`` dividing the merge count): keys
                are folded per segment, z is rebuilt from x at each segment
                start, so a segmented solve is a deterministic function of
                (key, ckpt_every) regardless of interruption.  With
                ``ckpt_dir`` each segment is checkpointed (``ckpt/``,
                atomic, reshardable); ``resume=True`` continues from the
                newest checkpoint.  ``fail_at_merge`` simulates process
                death once that many merges have completed (raises
                ``health.SolverFailure`` — the ckpt/resume tests' kill
                switch).

    The trace has one (objective, nnz) point per ``trace_every`` merges.

    ``spec=SolverSpec(...)`` is the canonical solve description (DESIGN
    §12): P_local = spec.P, plus rounds / merge / pipeline / guard /
    newton; ``spec.loss`` is validated against ``prob.loss``.  ``engine``
    stays an explicit kwarg (it names a kernel, not a solve).  The legacy
    (P_local, rounds) kwargs still work through this shim but emit a
    ``DeprecationWarning``.  ``newton=True`` (or ``spec.newton``) requires
    a fused engine (per-block curvature tile, DESIGN §12).
    """
    if spec is not None:
        reject_legacy_kwargs(spec, P_local=P_local, rounds=rounds)
        spec.check_loss(prob.loss)
        P_local, rounds = spec.P, spec.rounds
        merge, pipeline = spec.merge, spec.pipeline
        guard, newton = spec.guard, spec.newton
    else:
        if P_local is not None or rounds is not None:
            warnings.warn(
                "shotgun_sharded_solve(P_local=..., rounds=...) kwargs are "
                "deprecated; pass spec=SolverSpec(...)", DeprecationWarning,
                stacklevel=2)
        P_local = 8 if P_local is None else P_local
        rounds = 500 if rounds is None else rounds
    if engine not in ENGINE_NAMES:
        raise ValueError(f"unknown engine {engine!r}; choose from {ENGINE_NAMES}")
    if merge not in MERGE_MODES:
        raise ValueError(f"unknown merge {merge!r}; choose from {MERGE_MODES}")
    if compression not in COMPRESSION_SCHEMES:
        raise ValueError(f"unknown compression {compression!r}; choose from "
                         f"{COMPRESSION_SCHEMES}")
    mesh = make_feature_mesh() if mesh is None else mesh
    nshards = mesh.devices.size
    merge_rounds = 1 if merge == "round" else rounds_per_launch

    if engine == "sparse_fused":
        if not isinstance(prob.A, BlockedCSC):
            raise ValueError(
                f"engine={engine!r} needs a BlockedCSC design; got "
                f"{type(prob.A).__name__} (use data.sparse.BlockedCSC."
                "from_dense or a layout='bcsc' generator)")
        from repro.kernels.shotgun_sparse import require_sparse_backend
        require_sparse_backend()
        A = pad_feature_blocks(prob.A, nshards)
        nblk_local = A.nblk // nshards
        if K > nblk_local:
            raise ValueError(
                f"K={K} blocks > {nblk_local} local blocks "
                f"(nblk={A.nblk}, shards={nshards})")
        y, mask = prob.y, jnp.ones(prob.n, jnp.float32)
        eng = make_engine(engine, loss=prob.loss, K=K, block=A.block,
                          newton=newton)
    elif isinstance(prob.A, BlockedCSC):
        raise ValueError(
            f"engine={engine!r} needs a dense design; BlockedCSC problems "
            "use engine='sparse_fused'")
    elif engine == "scalar":
        A, y = pad_features(prob.A, nshards), prob.y
        mask = jnp.ones(prob.n, jnp.float32)
        eng = make_engine(engine, loss=prob.loss, P_local=P_local,
                          newton=newton)
    else:
        from repro.kernels import ops
        from repro.kernels.shotgun_block import BLOCK
        A, y, mask = ops.pad_problem(prob.A, prob.y)
        A = pad_features(A, nshards * BLOCK)     # d_local must tile by 128
        d_local = A.shape[1] // nshards
        nblk_local = d_local // BLOCK
        if K > nblk_local:
            raise ValueError(
                f"K={K} blocks > {nblk_local} local blocks "
                f"(d_local={d_local}, block={BLOCK})")
        mask = mask.astype(jnp.float32)
        eng = make_engine(engine, loss=prob.loss, K=K, block=BLOCK,
                          tile_n=tile_n, newton=newton)

    d_full = A.d_pad if isinstance(A, BlockedCSC) else A.shape[1]
    x0 = (jnp.zeros(d_full, jnp.float32) if x0 is None
          else jnp.pad(jnp.asarray(x0, jnp.float32), (0, d_full - prob.d)))
    kw = dict(engine=eng, merge_rounds=merge_rounds, mesh=mesh,
              trace_every=trace_every, compression=compression,
              topk_frac=topk_frac, hierarchical=hierarchical,
              guard=guard, faults=faults, pipeline=pipeline)

    if ckpt_every <= 0:
        if fail_at_merge is not None or resume or ckpt_dir is not None:
            raise ValueError(
                "ckpt_dir/fail_at_merge/resume need ckpt_every > 0 "
                "(segmented solve)")
        res = _engine_solve(A, y, mask, x0, prob.lam, prob.beta, key,
                            rounds=rounds, **kw)
        return Result(x=res.x[: prob.d], z=res.z[: prob.n], trace=res.trace,
                      status=res.status)

    # --- segmented solve with periodic checkpointing (DESIGN §9.4) -------
    # Host-level segments: fold_in(key, seg) per segment and rebuild z from
    # x at each segment start, so the trajectory is a pure function of
    # (key, ckpt_every) — an interrupted+resumed run matches an
    # uninterrupted run with the same ckpt_every exactly, point for point.
    n_merges = rounds // merge_rounds
    if ckpt_every % trace_every or n_merges % ckpt_every:
        raise ValueError(
            f"ckpt_every={ckpt_every} must be a multiple of trace_every="
            f"{trace_every} and divide the merge count {n_merges}")
    n_seg = n_merges // ckpt_every
    seg_rounds = ckpt_every * merge_rounds
    pts_per_seg = ckpt_every // trace_every
    n_pts = n_merges // trace_every

    import numpy as np
    fs_full = np.zeros(n_pts, np.float32)
    nnz_full = np.zeros(n_pts, np.int32)
    seg0, status = 0, 0
    x_cur, z_cur = x0, None
    if resume:
        from repro.ckpt import checkpoint as ckpt
        template = {"x": jax.ShapeDtypeStruct((d_full,), jnp.float32),
                    "fs": jax.ShapeDtypeStruct((n_pts,), jnp.float32),
                    "nnz": jax.ShapeDtypeStruct((n_pts,), jnp.int32),
                    "seg": jax.ShapeDtypeStruct((), jnp.int32),
                    "status": jax.ShapeDtypeStruct((), jnp.int32)}
        step, state = ckpt.restore(ckpt_dir, template)
        seg0 = int(state["seg"])
        status = int(state["status"])
        fs_full[:] = np.asarray(state["fs"])
        nnz_full[:] = np.asarray(state["nnz"])
        x_cur = jnp.asarray(state["x"])

    for seg in range(seg0, n_seg):
        if fail_at_merge is not None and seg * ckpt_every >= fail_at_merge:
            raise health.SolverFailure(
                f"simulated death at merge {seg * ckpt_every} "
                f"({seg}/{n_seg} segments checkpointed)")
        res = _engine_solve(A, y, mask, x_cur, prob.lam, prob.beta,
                            jax.random.fold_in(key, seg),
                            rounds=seg_rounds, **kw)
        x_cur, z_cur = res.x, res.z
        fs_full[seg * pts_per_seg:(seg + 1) * pts_per_seg] = np.asarray(
            res.trace.objective)
        nnz_full[seg * pts_per_seg:(seg + 1) * pts_per_seg] = np.asarray(
            res.trace.nnz)
        status = max(status, int(res.status))    # DIVERGED > RECOVERED > OK
        if ckpt_dir is not None:
            from repro.ckpt import checkpoint as ckpt
            ckpt.save(ckpt_dir, seg + 1,
                      {"x": x_cur, "fs": jnp.asarray(fs_full),
                       "nnz": jnp.asarray(nnz_full),
                       "seg": jnp.int32(seg + 1), "status": jnp.int32(status)})

    if z_cur is None:               # resumed after the final segment
        z_cur = obj.matvec(A, x_cur)
    return Result(x=x_cur[: prob.d], z=z_cur[: prob.n],
                  trace=Trace(objective=jnp.asarray(fs_full),
                              nnz=jnp.asarray(nnz_full)),
                  status=jnp.int32(status))
