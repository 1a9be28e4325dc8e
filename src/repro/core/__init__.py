"""Shotgun: parallel coordinate descent for L1-regularized losses (ICML 2011).

Public API:
    make_problem, Problem, objective, lambda_max          (objectives)
    shooting_solve, shotgun_solve, shotgun_dup_solve      (Alg. 1 / Alg. 2)
    shotgun_cdn_solve, shooting_cdn_solve                 (CDN variants)
    get_solver, SOLVER_NAMES                              (solver registry)
    SolverSpec                                            (declarative solve spec)
    make_engine, ENGINE_NAMES                             (round-engine registry)
    spectral_radius, p_star                               (parallelism limit)
    solve_path                                            (lambda continuation)
    shotgun_sharded_solve                                 (multi-device driver)

The Pallas solver (``block_fused`` in ``get_solver``) lives in
``repro.kernels.ops``, and the round engines (``core/engines.py``) import
them lazily, to keep core import-light.  ``solve_path(solver=<name>)``
accepts any registry entry; ``shotgun_sharded_solve(engine=<name>)`` any
engine.
"""
from repro.core.objectives import (LASSO, LOGISTIC, Problem, DupProblem,
                                   make_problem, dup_from, objective,
                                   lambda_max, soft_threshold, unscale_x,
                                   matvec, rmatvec, gather_cols)
from repro.core.spec import SolverSpec
from repro.core.shotgun import (shooting_solve, shotgun_solve,
                                shotgun_dup_solve, rounds_to_tolerance,
                                diverged, get_solver, SOLVER_NAMES,
                                Result, Trace)
from repro.core.cdn import shotgun_cdn_solve, shooting_cdn_solve
from repro.core.engines import (ENGINE_NAMES, FusedEngine, ScalarEngine,
                                make_engine)
from repro.core.spectral import spectral_radius, p_star, p_star_dup
from repro.core.path import solve_path, lambda_sequence
from repro.core.sharded import shotgun_sharded_solve
