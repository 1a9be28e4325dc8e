"""The output check at a size a test run holds, on the CPU: the program
passes, and the control and each planted fault come out not correct.

Each case drives a whole run (set-up, window, reference, check) with the
committed limits, the chip check skipped and the timed path replaced."""
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
import control
import harness
from reference import solvers

# A cell's limits, traffic and metrics as committed; the configuration cut
# to a size the Pallas interpreter runs in seconds, lambda near a tenth
# (logistic: a twentieth) of lambda_max there.
TINY = {"spc_lasso": dict(n=512, d=1024, lam=12.0, P=256, rounds=64,
                          fista_iters=800),
        "zeta_logreg": dict(n=1024, d=500, lam=0.28, P=256, rounds=24,
                            fista_iters=400)}


def tiny_cell(workload):
    cell = harness.resolve(harness.load_benchmark(), workload)
    cell.config.update(TINY[cell.config["name"]])
    if cell.traffic["kind"] == "path":
        cell.traffic.update(num_lambdas=4)
    return cell


def run(cell, timed=None, seed=5, design=None):
    return harness.run_cell(cell, seed, 0.0, False, require_chip=False,
                            timed=timed, design=design, keep_fstar=False)


def program(cell):
    return harness.kind(cell.traffic["kind"]).timed(cell)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: "off")


def unchanged(solve, loss):
    """A solve that returns its starting state: x = 0, z = 0, and the
    objective of x = 0 for every round."""
    def run(A, y, lam, key):
        x, z, t = solve(A, y, lam, key)
        f0 = solvers.data_loss(jnp.zeros_like(y), y, loss)
        return jnp.zeros_like(x), jnp.zeros_like(z), jnp.full_like(t, f0)
    return run


def half_samples(solve, loss):
    """A solve that leaves out half of the samples."""
    def run(A, y, lam, key):
        h = A.shape[0] // 2
        x, z, t = solve(A[:h], y[:h], lam, key)
        return x, jnp.concatenate([z, jnp.zeros(A.shape[0] - h)]), t
    return run


def altered(solve, loss):
    """A solve whose answer is altered where it is produced: its largest
    coordinate scaled by 1.1."""
    def run(A, y, lam, key):
        x, z, t = solve(A, y, lam, key)
        j = jnp.argmax(jnp.abs(x))
        return x.at[j].multiply(1.1), z, t
    return run


def early_trace(solve, loss):
    """A solve whose per-round objective runs ahead of its iterates by two
    rounds, as a lagged or misplaced write of the in-kernel trace would:
    the answer is sound, the rounds to the criterion read too few."""
    def run(A, y, lam, key):
        x, z, t = solve(A, y, lam, key)
        return x, z, jnp.concatenate([t[2:], t[-1:], t[-1:]])
    return run


SOLVE_CELLS = ["zeta_logreg.solve"]


@pytest.mark.parametrize("workload", SOLVE_CELLS + ["spc_lasso.path"])
def test_program_comes_out_correct(workload):
    r = run(tiny_cell(workload))
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("workload", SOLVE_CELLS + ["spc_lasso.path"])
def test_control_comes_out_not_correct(workload):
    r = run(tiny_cell(workload), design=control.bf16_design())
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", [unchanged, half_samples, altered,
                                   early_trace])
@pytest.mark.parametrize("workload", SOLVE_CELLS)
def test_planted_fault_comes_out_not_correct(workload, fault):
    cell = tiny_cell(workload)
    r = run(cell, fault(program(cell), cell.config["loss"]))
    assert not r["correct"], r["checks"]


def _path_unchanged(path):
    def run(A, y, lam, key, num_lambdas):
        x, f, rounds = path(A, y, lam, key, num_lambdas)
        f0 = float(solvers.data_loss(jnp.zeros_like(y), y, "lasso"))
        return jnp.zeros_like(x), f0, rounds
    return run


def _path_altered(path):
    def run(A, y, lam, key, num_lambdas):
        x, f, rounds = path(A, y, lam, key, num_lambdas)
        j = jnp.argmax(jnp.abs(x))
        return x.at[j].multiply(1.1), f, rounds
    return run


@pytest.mark.parametrize("fault", [_path_unchanged, _path_altered])
def test_planted_path_fault_comes_out_not_correct(fault):
    cell = tiny_cell("spc_lasso.path")
    r = run(cell, fault(program(cell)))
    assert not r["correct"], r["checks"]
