"""SolverSpec: one declarative description of a solve (DESIGN §12).

Every solver entry point (``shotgun_solve``, ``block_shotgun_solve``,
``shotgun_sharded_solve``, ``solve_path``, ``batched_block_shotgun_solve``)
accepts ``spec=SolverSpec(...)`` in place of its historical kwarg sprawl.
The legacy kwargs still work — each entry point keeps a thin shim that
forwards them into the same jitted core (bit-for-bit identical
trajectories) and emits a ``DeprecationWarning``.

The spec is solver-family agnostic: fields a family does not implement are
simply ignored by it (``merge``/``pipeline`` only matter to the sharded
solver; ``newton`` only to the block solvers).  ``loss`` is
always validated against the problem's loss so a spec built for one
workload can never silently drive another.
"""
from __future__ import annotations

import dataclasses

from repro.core.health import GuardConfig


@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """Declarative solve description accepted everywhere via ``spec=``.

    loss      "lasso" or "logistic" — must match ``prob.loss``.
    P         target coordinate parallelism per round.  Scalar solvers use
              it directly; block solvers round up to K = ceil(P / 128)
              blocks; the sharded solver reads it as P_local.
    rounds    number of (outer) rounds.
    merge     sharded merge policy ("round" / "async" / ...); ignored
              elsewhere.
    pipeline  sharded double-buffered merge pipeline; ignored elsewhere.
    guard     ``health.GuardConfig`` enabling the divergence sentinel +
              adaptive-P backoff (DESIGN §9), or None.
    fused     always True: the block solvers have one round, the fused
              multi-round kernel, and ``False`` (the retired two-kernel
              round) raises.  The field stays only for callers that still
              pass ``fused=True``.
    newton    per-block Newton curvature (Bian et al.) instead of the
              β-Lipschitz step.
    """

    loss: str = "lasso"
    P: int = 8
    rounds: int = 500
    merge: str = "round"
    pipeline: bool = False
    guard: GuardConfig | None = None
    fused: bool = True
    newton: bool = False

    def __post_init__(self):
        if not self.fused:
            raise ValueError(
                "SolverSpec(fused=False) named the two-kernel Block-Shotgun "
                "round, which is retired: the block solvers run the fused "
                "multi-round kernel only")
        if self.P < 1 or self.rounds < 1:
            raise ValueError(
                f"SolverSpec needs P >= 1 and rounds >= 1, got "
                f"P={self.P}, rounds={self.rounds}")

    def check_loss(self, prob_loss: str):
        """Raise if this spec was built for a different loss than the
        problem's — both losses named, per the serve-layer convention."""
        if self.loss != prob_loss:
            raise ValueError(
                f"SolverSpec(loss={self.loss!r}) does not match problem "
                f"loss {prob_loss!r}")


def reject_legacy_kwargs(spec, **named):
    """Guard for the shim entry points: with ``spec=`` given, any
    explicitly-passed legacy solver-shape kwarg (non-None) is an error —
    the caller must pick one interface."""
    if spec is None:
        return
    bad = [k for k, v in named.items() if v is not None]
    if bad:
        raise ValueError(
            f"pass spec= or the legacy kwargs {bad}, not both")
