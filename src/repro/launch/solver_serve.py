"""Shotgun-as-a-service: continuous-batched solver serving (DESIGN §11).

    PYTHONPATH=src python -m repro.launch.solver_serve \
        --requests 12 --slots 4 --n 192 --d 384 --repeat-frac 0.5

The LM-side driver (``launch/serve.py``) keeps a fixed bank of decode
slots busy with per-slot refill; this is the same loop for the solver.
A stream of ``SolveRequest``\\ s — (problem_id, λ, optional x0) — is
served through ``slots`` stacked problems advanced together by ONE
batched launch of the fused kernels per scheduler step
(``core.batched.launch_rounds``), R rounds at a time:

  * admission normalizes every problem onto the stream's one canvas
    (``normalize_problem``) and warm-starts from the shared
    ``WarmStartCache`` — (problem_id, λ) exact hit or nearest-λ fallback;
  * per-slot convergence is detected at each launch boundary from the
    in-kernel objective trace (``launch_converged``) and health scalar;
    a converged slot is finalized, its solution written back to the
    cache, and the slot is refilled from the queue IMMEDIATELY — one
    slow problem never idles the batch;
  * empty / finalized slots ride along with ``k_eff = 0`` (bit-exact
    no-op, no retrace); a slot whose health scalar trips rolls back to
    its admission snapshot with ``k_eff`` halved (§9's backoff at
    launch granularity, per slot);
  * every device call is a module-level jit with stream-constant shapes
    and statics, so the whole request stream runs on one jaxpr per entry
    point (SL102: the lint's retrace check traces the batched entry
    points).

Slot/queue bookkeeping (free slots, FIFO refill, age, round-deadline
eviction with re-queue) is the shared ``launch.slots.SlotBoard`` — an
evicted solve keeps its partial iterate and resumes from it when
re-admitted.  A BlockedCSC stream raises ``NotImplementedError`` at
construction on a TPU (``shotgun_sparse.require_sparse_backend``).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import objectives as obj
from repro.core.batched import (BatchMeta, SlotArrays, WarmStartCache,
                                batch_meta_of, launch_converged,
                                launch_rounds, normalize_problem)
from repro.core.objectives import Problem
from repro.data.sparse import bcsc_matvec
from repro.kernels.batched import batched_draw_blocks
from repro.kernels.shotgun_sparse import require_sparse_backend
from repro.launch.slots import SlotBoard

GUARD_FACTOR = 10.0         # §9 trip threshold: F > factor·|F_prev| + factor


@dataclasses.dataclass
class SolveRequest:
    """One (problem_id, λ, x0) solve in the stream.  ``prob`` carries λ
    (``Problem.lam``); ``x0`` (true-d) overrides the warm cache when set.
    Filled in by the service: ``x`` (true-d solution), ``rounds_used``,
    ``status`` ("ok"/"diverged"/"gave_up"), ``warm`` (cache verdict)."""
    rid: int
    problem_id: object
    prob: Problem
    key: jax.Array
    x0: np.ndarray | None = None
    x: np.ndarray | None = None
    rounds_used: int = 0
    launches: int = 0
    status: str = ""
    warm: str = ""
    f_final: float = float("nan")
    done: bool = False
    evictions: int = 0
    # service-internal
    k_eff: int = 0
    f_prev: float = float("inf")
    key_sched: np.ndarray | None = None   # (max_launches, R, 2) uint32
    z_resume: np.ndarray | None = None    # evicted margin (padded n_pad)


# --- module-level jits: one jaxpr each for the whole stream (SL102) -------

@functools.partial(jax.jit, static_argnames=("loss",))
def _slot_objective(z, y, mask, lam, x, loss):
    return obj.masked_data_loss(z, y, mask, loss) + lam * jnp.sum(jnp.abs(x))


@jax.jit
def _dense_margin(A, x0):
    return obj.matvec(A.astype(jnp.float32), x0)


@functools.partial(jax.jit, static_argnames=("n",))
def _sparse_margin(rows, vals, x0, n):
    return bcsc_matvec(rows, vals, x0, n)


@jax.jit
def _write_slot(stacked: SlotArrays, x, z, x_snap, z_snap, slot, sa:
                SlotArrays, x0, z0):
    """Admit one normalized problem into slot ``slot`` of the stacked
    state (and refresh that slot's rollback snapshot)."""
    upd = lambda full, v: None if full is None else full.at[slot].set(v)
    stacked = SlotArrays(*(upd(f, v) for f, v in zip(stacked, sa)))
    return (stacked, x.at[slot].set(x0), z.at[slot].set(z0),
            x_snap.at[slot].set(x0), z_snap.at[slot].set(z0))


@jax.jit
def _rollback_slot(x, z, x_snap, z_snap, slot):
    return x.at[slot].set(x_snap[slot]), z.at[slot].set(z_snap[slot])


class SolverService:
    """Continuous-batched Shotgun solver over a fixed bank of slots.

    ``meta`` fixes the stream's canvas (build it from a template problem
    with ``batch_meta_of``); every request must normalize onto it.
    ``max_rounds`` is the fixed per-request budget (the cold-start
    budget); ``tol`` the launch-boundary relative-improvement stop.
    ``deadline_launches`` (optional) enables SlotBoard round-deadline
    eviction: a solve stuck past the deadline is re-queued at the tail
    and resumes from its partial iterate when re-admitted.
    """

    def __init__(self, meta: BatchMeta, *, slots: int = 4, K: int = 2,
                 max_rounds: int = 64, rounds_per_launch: int = 8,
                 tol: float = 1e-4,
                 cache: WarmStartCache | None = None,
                 deadline_launches: int | None = None,
                 max_evictions: int = 2):
        if max_rounds % rounds_per_launch:
            raise ValueError(f"max_rounds={max_rounds} not divisible by "
                             f"rounds_per_launch={rounds_per_launch}")
        if meta.layout == "bcsc":
            require_sparse_backend()
        self.meta = meta
        self.K = K
        self.R = rounds_per_launch
        self.max_launches = max_rounds // rounds_per_launch
        self.tol = tol
        self.cache = WarmStartCache() if cache is None else cache
        self.board = SlotBoard(slots, max_rounds=deadline_launches,
                               max_evictions=max_evictions)
        S, m = slots, meta
        zero = lambda shape: jnp.zeros(shape, jnp.float32)
        if m.layout == "bcsc":
            sa = SlotArrays(A=None,
                            rows=jnp.zeros((S, m.nblk, m.tile, m.block),
                                           jnp.int32),
                            vals=zero((S, m.nblk, m.tile, m.block)),
                            y=zero((S, m.n_pad)), mask=None,
                            lam=zero(S), beta=jnp.ones(S, jnp.float32))
        else:
            sa = SlotArrays(A=zero((S, m.n_pad, m.d_pad)), rows=None,
                            vals=None, y=zero((S, m.n_pad)),
                            mask=zero((S, m.n_pad)), lam=zero(S),
                            beta=jnp.ones(S, jnp.float32))
        self.stacked = sa
        self.x = zero((S, m.d_pad))
        self.z = zero((S, m.n_pad))
        self.x_snap = zero((S, m.d_pad))
        self.z_snap = zero((S, m.n_pad))
        self.launch_count = 0           # batched launches issued
        self.occupancy_samples: list[float] = []

    # -- admission ---------------------------------------------------------
    def _warm_start(self, req: SolveRequest):
        """Pick the slot's x0: explicit request x0 beats the warm cache
        (λ-path threading passes it directly); else (problem_id, λ) lookup
        with nearest-λ fallback; else cold zeros."""
        if req.x0 is not None:
            req.warm = req.warm or "given"
            return np.asarray(req.x0, np.float32)
        x0, kind = self.cache.get(req.problem_id, float(req.prob.lam),
                                  loss=req.prob.loss)
        req.warm = kind
        return None if x0 is None else x0

    def _admit(self, req: SolveRequest, slot: int) -> None:
        m = self.meta
        if req.prob.loss != m.loss:
            # one jaxpr per stream: a mixed-loss stream would either
            # retrace or silently run the wrong residual tile
            raise ValueError(
                f"mixed-loss stream: request {req.problem_id!r} carries "
                f"loss {req.prob.loss!r} but this stream is admitted for "
                f"loss {m.loss!r}")
        sa = normalize_problem(req.prob, m)
        x0 = self._warm_start(req)
        if x0 is None:
            x0 = jnp.zeros(m.d_pad, jnp.float32)
        else:
            x0 = jnp.pad(jnp.asarray(x0, jnp.float32),
                         (0, m.d_pad - x0.shape[0]))
        if req.z_resume is not None:
            # deadline-evicted solve resuming mid-trajectory: restore the
            # kernel-accumulated margin and objective exactly (recomputing
            # z = A·x0 would fork the fp trajectory — determinism test)
            z0 = jnp.asarray(req.z_resume, jnp.float32)
            req.z_resume = None
        elif m.layout == "bcsc":
            z0 = _sparse_margin(sa.rows, sa.vals, x0, m.n_pad)
            mask = jnp.ones(m.n_pad, jnp.float32)
        else:
            z0 = _dense_margin(sa.A, x0)
            mask = sa.mask
        (self.stacked, self.x, self.z, self.x_snap, self.z_snap) = \
            _write_slot(self.stacked, self.x, self.z, self.x_snap,
                        self.z_snap, slot, sa, x0, z0)
        if req.f_prev == float("inf"):
            req.f_prev = float(_slot_objective(z0, sa.y, mask, sa.lam, x0,
                                               m.loss))
        req.k_eff = self.K if req.k_eff == 0 else req.k_eff
        if req.key_sched is None:
            # The request's whole draw schedule is fixed at first admission
            # from ITS key — independent of slot, co-tenants, and eviction
            # history, which is what makes the served stream deterministic.
            req.key_sched = np.asarray(jax.random.split(
                req.key, self.max_launches * self.R)).reshape(
                    self.max_launches, self.R, -1)
        self.board.place(req, slot)

    # -- the batched scheduler step ---------------------------------------
    def _launch_step(self) -> None:
        S = len(self.board.slots)
        keys_l = np.zeros((S, self.R, 2), np.uint32)
        k_eff = np.zeros(S, np.float32)
        guard = np.full(S, np.inf, np.float32)
        for i, r in enumerate(self.board.slots):
            if r is None or r.done:
                continue
            keys_l[i] = r.key_sched[r.launches]
            k_eff[i] = r.k_eff
            guard[i] = GUARD_FACTOR * abs(r.f_prev) + GUARD_FACTOR
        idx = batched_draw_blocks(jnp.asarray(keys_l), self.K,
                                  self.meta.nblk)
        self.x, self.z, fs, _, hlt = launch_rounds(
            self.meta, self.stacked, self.z, self.x, idx,
            jnp.asarray(k_eff), guard_f=jnp.asarray(guard))
        self.launch_count += 1
        fs_h, hlt_h = np.asarray(fs), np.asarray(hlt)
        for i, r in enumerate(self.board.slots):
            if r is None or r.done:
                continue
            if hlt_h[i] > 0 or not np.isfinite(fs_h[i, -1]):
                # in-kernel guard tripped: §9 backoff at slot granularity —
                # roll back to the admission snapshot, halve k_eff
                if r.k_eff <= 1:
                    self._finalize(i, r, "diverged")
                    continue
                r.k_eff = max(1, r.k_eff // 2)
                self.x, self.z = _rollback_slot(self.x, self.z,
                                                self.x_snap, self.z_snap, i)
                r.launches += 1    # burn the launch: draws stay scheduled
                if r.launches >= self.max_launches:
                    self._finalize(i, r, "diverged")
                continue
            r.launches += 1
            r.rounds_used += self.R
            done_budget = r.launches >= self.max_launches
            if launch_converged(r.f_prev, fs_h[i], self.tol) or done_budget:
                r.f_prev = float(fs_h[i, -1])
                self._finalize(i, r, "ok")
            else:
                r.f_prev = float(fs_h[i, -1])

    def _finalize(self, slot: int, req: SolveRequest, status: str) -> None:
        req.x = np.asarray(self.x[slot][: req.prob.d])
        req.f_final = req.f_prev
        req.status = status
        req.done = True
        req.k_eff = 0
        if status == "ok":
            self.cache.put(req.problem_id, float(req.prob.lam), req.x,
                           loss=req.prob.loss)

    def _save_partials(self) -> None:
        """Before deadline eviction: stash each stale slot's iterate so the
        re-queued request resumes from it (as its x0) when re-admitted."""
        if self.board.max_rounds is None:
            return
        for i, r in enumerate(self.board.slots):
            if r is None or r.done or self.board.age[i] < \
                    self.board.max_rounds:
                continue
            r.x0 = np.asarray(self.x[i][: req_d(r)])
            r.z_resume = np.asarray(self.z[i])
            r.warm = r.warm or "given"

    # -- the serving loop --------------------------------------------------
    def serve(self, requests) -> list[SolveRequest]:
        """Serve a request list to completion; returns them finished (in
        completion order — sort by ``rid`` for stream order)."""
        self.board.queue.extend(requests)
        while self.board.pending():
            self.board.refill(self._admit)
            if not self.board.live():
                break
            self.occupancy_samples.append(self.board.occupancy())
            self._launch_step()
            self.board.tick()
            self._save_partials()
            # evicted slots go empty → k_eff 0 next launch (bit-exact idle)
            self.board.evict_stale()
        out = self.board.drain()
        for r in out:                 # give-ups keep their partial iterate
            if r.status == "":
                r.x = r.x0 if r.x0 is not None else r.x
                r.status = "gave_up"
        return out

    @property
    def slot_occupancy(self) -> float:
        """Mean live-slot fraction over all scheduler steps."""
        return (float(np.mean(self.occupancy_samples))
                if self.occupancy_samples else 0.0)


def req_d(req: SolveRequest) -> int:
    return req.prob.d


def solve_queue_sequential(requests, *, K: int = 2, max_rounds: int = 64,
                           rounds_per_launch: int = 8, tol: float = 1e-4,
                           cache: WarmStartCache | None = None):
    """The solve-one-at-a-time baseline: each request served through a
    1-slot service (same launch schedule, same early stop, same cache
    semantics) with no batching — the denominator of
    ``speedup_serve_vs_sequential``."""
    out = []
    for req in requests:
        svc = SolverService(batch_meta_of(req.prob), slots=1, K=K,
                            max_rounds=max_rounds,
                            rounds_per_launch=rounds_per_launch, tol=tol,
                            cache=cache)
        out.extend(svc.serve([req]))
    return out


def make_stream(n: int, d: int, *, requests: int, repeat_frac: float = 0.0,
                num_designs: int = 2, lam: float = 0.5, seed: int = 0):
    """A synthetic request stream over ``num_designs`` shared designs:
    unique (problem_id, λ) pairs with a ``repeat_frac`` tail of repeats
    (the warm-cache traffic of the ROADMAP serving scenario).  Designs are
    ``synthetic.sparco`` problems — low ρ(AᵀA), so K·128-wide parallel
    updates sit under the Thm 3.2 ceiling and solves converge."""
    from repro.data import synthetic as syn
    probs = {}
    for pid in range(num_designs):
        A, y, _ = syn.sparco(seed=seed + pid, n=n, d=d)
        probs[pid] = obj.make_problem(A, y, lam=lam)
    reqs = []
    n_unique = max(1, int(round(requests * (1.0 - repeat_frac))))
    for rid in range(requests):
        if rid < n_unique:
            pid = rid % num_designs
            lam_r = lam * (1.0 + 0.5 * (rid // num_designs))
        else:                       # repeat of an earlier (pid, λ)
            src = rid % n_unique
            pid = src % num_designs
            lam_r = lam * (1.0 + 0.5 * (src // num_designs))
        prob = probs[pid]._replace(lam=jnp.float32(lam_r))
        reqs.append(SolveRequest(rid=rid, problem_id=pid, prob=prob,
                                 key=jax.random.PRNGKey(1000 + rid)))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    # defaults: the bench_serve smoke config — K=1 at this shape/λ stays
    # under the paper's P* interference limit, so cold solves converge in
    # 48-72 rounds (K=2 dense gaussians at these shapes genuinely diverge)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--d", type=int, default=512)
    ap.add_argument("--K", type=int, default=1)
    ap.add_argument("--max-rounds", type=int, default=128)
    ap.add_argument("--rounds-per-launch", type=int, default=8)
    ap.add_argument("--repeat-frac", type=float, default=0.5)
    ap.add_argument("--lam", type=float, default=4.0)
    ap.add_argument("--tol", type=float, default=1e-4)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    reqs = make_stream(a.n, a.d, requests=a.requests,
                       repeat_frac=a.repeat_frac, lam=a.lam, seed=a.seed)
    svc = SolverService(batch_meta_of(reqs[0].prob), slots=a.slots, K=a.K,
                        max_rounds=a.max_rounds,
                        rounds_per_launch=a.rounds_per_launch, tol=a.tol)
    t0 = time.time()
    done = svc.serve(reqs)
    dt = time.time() - t0
    for r in sorted(done, key=lambda r: r.rid):
        print(f"[solver-serve] req {r.rid} pid={r.problem_id} "
              f"lam={float(r.prob.lam):.3f}: {r.status} "
              f"rounds={r.rounds_used} warm={r.warm} f={r.f_final:.5f}")
    st = svc.cache.stats
    print(f"[solver-serve] {len(done)} solves in {dt:.2f}s "
          f"({len(done)/max(dt,1e-9):.2f} solves/s), "
          f"{svc.launch_count} launches, "
          f"occupancy={svc.slot_occupancy:.2f}, cache "
          f"exact/near/miss={st.hits_exact}/{st.hits_near}/{st.misses}")


if __name__ == "__main__":
    main()
