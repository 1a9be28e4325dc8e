"""Chip bring-up contracts that hold on the CPU backend: the chip smoke
test refuses to run without a TPU, the compile cache lands where it should,
and BlockedCSC kernel solves refuse a TPU backend instead of falling back."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolverSpec
from repro.core import objectives as obj
from repro.data.sparse import BlockedCSC

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert repr(jax.devices()[0].platform) in str(e.value.code)
    out = capsys.readouterr().out
    assert f"platform={jax.devices()[0].platform}" in out
    assert '"ok"' not in out                       # no result line


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _sparse_problem():
    rng = np.random.default_rng(0)
    A = rng.standard_normal((64, 256)).astype(np.float32)
    A[rng.random(A.shape) < 0.8] = 0.0
    prob = obj.make_problem(jnp.asarray(A),
                            jnp.asarray(rng.standard_normal(64), jnp.float32),
                            lam=0.1)
    return prob._replace(A=BlockedCSC.from_dense(prob.A, block=128, tile=64))


@pytest.mark.parametrize("entry", ["solver", "sharded", "service", "batched"])
def test_sparse_solves_refuse_tpu(monkeypatch, entry):
    """Mosaic refuses the nnz-tile gather, so off the CPU backend every
    BlockedCSC kernel entry point raises at entry and names the refusal."""
    prob = _sparse_problem()
    key = jax.random.PRNGKey(0)
    spec = SolverSpec(P=128, rounds=8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        if entry == "solver":
            from repro.kernels import ops
            ops.block_shotgun_solve(prob, key, spec=spec)
        elif entry == "sharded":
            from repro.core.sharded import shotgun_sharded_solve
            shotgun_sharded_solve(prob, key, engine="sparse_fused", K=1,
                                  spec=spec)
        elif entry == "service":
            from repro.core.batched import batch_meta_of
            from repro.launch.solver_serve import SolverService
            SolverService(batch_meta_of(prob), slots=2)
        else:
            from repro.core.batched import batched_block_shotgun_solve
            batched_block_shotgun_solve([prob], [key], spec=spec)
