"""Compile the dense kernels of the main path for a described v5e chip.

No chip is attached: the TPU compiler lowers each ``pallas_call`` with
``interpret=False`` against a ``v5e:2x2`` topology description, so a kernel
Mosaic would refuse (an illegal block shape, a scalar store to VMEM, more
VMEM than ``VMEM_BUDGET``) fails here instead of on the chip.  Shapes are
the widths ``chip_smoke.py`` runs.  The topology is described inside a
module fixture, never at import: only the worker that runs this file may
load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched import batched_fused_shotgun_rounds
from repro.kernels.shotgun_block import (BLOCK, VMEM_BUDGET, auto_tile_n,
                                         fused_shotgun_delta_rounds,
                                         fused_shotgun_rounds,
                                         fused_vmem_bytes)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fused_args(sh, n, d, K, R=8, S=None):
    """(A, z, x, idx, lam, beta, y, mask) shapes; ``S`` stacks all but A."""
    lead = () if S is None else (S,)
    return (_sds(sh, (n, d)), _sds(sh, lead + (n,)), _sds(sh, lead + (d,)),
            _sds(sh, lead + (R, K), jnp.int32), _sds(sh, lead),
            _sds(sh, lead), _sds(sh, lead + (n,)), _sds(sh, lead + (n,)))


def _compile(fn, *args, **kw):
    return fn.lower(*args, **kw).compile()


@pytest.mark.parametrize("n,d,K,tile_n,loss", [
    (4096, 65536, 20, None, "lasso"),            # single-phase (T == 1)
    (4096, 65536, 20, None, "logistic"),
    (4096, 65536, 20, None, "logistic_newton"),
    (8192, 8192, 8, 512, "lasso"),               # two-phase (T == 16)
    (4096, 2048, 8, None, "logistic_newton"),    # per-block Newton
])
def test_fused_rounds_compile(one_chip, n, d, K, tile_n, loss):
    c = _compile(fused_shotgun_rounds, *_fused_args(one_chip, n, d, K),
                 loss=loss, tile_n=tile_n, interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_fused_delta_rounds_compile(one_chip):
    """The Δz engine variant at the per-chip shard of the 4-chip solve."""
    c = _compile(fused_shotgun_delta_rounds,
                 *_fused_args(one_chip, 4096, 65536, 6), loss="lasso",
                 interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_batched_fused_rounds_compile(one_chip):
    """The vmapped service kernel: 4 stacked slots at n=2048, d=16384."""
    S, n, d, K = 4, 2048, 16384, 4
    _, z, x, idx, lam, beta, y, mask = _fused_args(one_chip, n, d, K, S=S)
    c = _compile(batched_fused_shotgun_rounds, _sds(one_chip, (S, n, d)), z,
                 x, idx, lam, beta, y, mask, _sds(one_chip, (S,)),
                 _sds(one_chip, (S,)), loss="lasso", interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_zeta_cell_shape_compiles_single_phase(one_chip):
    """The Newton kernel at the zeta cell's padded shape (n = 24064,
    d = 2048, K = 8) takes the whole n as its tile (T == 1) and compiles."""
    n, d, K = 24064, 2048, 8
    assert auto_tile_n(n, d=d, K=K, loss="logistic_newton") == n
    c = _compile(fused_shotgun_rounds, *_fused_args(one_chip, n, d, K),
                 loss="logistic_newton", tile_n=None, interpret=False)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("d,K,loss,single", [
    (2048, 8, "logistic_newton", True),     # the A panel bounds n
    (256, 4, "lasso", False),               # the (1, n) rows bound n
])
def test_largest_admitted_n_compiles(one_chip, d, K, loss, single):
    """The VMEM model is the compiler's: the largest n ``auto_tile_n``
    admits under ``VMEM_BUDGET`` compiles, single-phase or on 512-row
    tiles, and the next falls back to tiles or is refused up front with
    the limit named.  (Two-phase at d = 256, where A still fits HBM.)"""
    step = 512

    def fits(n):
        return fused_vmem_bytes(n, d, K, tile_n=n if single else 512,
                                loss=loss) <= VMEM_BUDGET
    n = step
    while fits(2 * n):
        n *= 2
    while fits(n + step):
        n += step
    assert auto_tile_n(n, d=d, K=K, loss=loss) == (n if single else 512)
    if single:
        assert auto_tile_n(n + step, d=d, K=K, loss=loss) == 512
    else:
        with pytest.raises(ValueError, match="VMEM_BUDGET"):
            auto_tile_n(n + step, d=d, K=K, loss=loss)
    c = _compile(fused_shotgun_rounds, *_fused_args(one_chip, n, d, K),
                 loss=loss, interpret=False)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("emit_dz,name", [
    (False, "fused_shotgun_rounds"), (True, "fused_shotgun_delta_rounds")])
def test_fused_kernel_keeps_its_name_inside_any_jitted_caller(one_chip,
                                                               emit_dz, name):
    """The compiled custom call takes the kernel's own name
    (``pallas_call(name=...)``), not that of the jitted function holding it:
    a device trace finds the kernel by that name after its wrapper is
    inlined or renamed."""
    from repro.kernels.shotgun_block import _fused_call

    def holder(A, z, x, idx, lam, beta, y, mask):
        return _fused_call(A, z, x, idx, lam, beta, y, mask, "lasso", BLOCK,
                           None, False, emit_dz=emit_dz)
    text = _compile(jax.jit(holder),
                    *_fused_args(one_chip, 4096, 8192, 4)).as_text()
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call", text)
    assert "%holder" not in text
