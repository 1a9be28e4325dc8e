"""Data loader determinism/sharding + gradient compression + collectives."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.loader import LoaderConfig, TokenLoader
from repro.dist import compression as C


# ---------------------------------------------------------------------------
# Loader
# ---------------------------------------------------------------------------

def test_loader_deterministic_in_step():
    cfg = LoaderConfig(vocab_size=128, global_batch=4, seq_len=32, seed=7)
    ld = TokenLoader(cfg)
    b1 = ld.batch_at(5)
    b2 = ld.batch_at(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    b3 = ld.batch_at(6)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))


def test_loader_host_sharding_disjoint_and_sized():
    cfg = LoaderConfig(vocab_size=128, global_batch=8, seq_len=16, seed=0)
    parts = [TokenLoader(cfg, host_id=h, num_hosts=4).batch_at(3) for h in range(4)]
    for p in parts:
        assert p["tokens"].shape == (2, 16)
    # different hosts draw different (independent) streams
    assert not np.array_equal(np.asarray(parts[0]["tokens"]),
                              np.asarray(parts[1]["tokens"]))


def test_loader_rejects_indivisible_batch():
    """Shard-divisibility is a ValueError (asserts vanish under python -O)
    and names the offending values."""
    cfg = LoaderConfig(vocab_size=64, global_batch=6, seq_len=8, seed=0)
    with pytest.raises(ValueError, match="global_batch=6.*num_hosts=4"):
        TokenLoader(cfg, host_id=0, num_hosts=4)


def test_loader_labels_shift():
    cfg = LoaderConfig(vocab_size=64, global_batch=2, seq_len=24, seed=1)
    b = TokenLoader(cfg).batch_at(0)
    assert b["tokens"].shape == b["labels"].shape == (2, 24)


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

def test_int8_roundtrip_accuracy():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    qt = C.quantize_int8(x)
    err = np.abs(np.asarray(C.dequantize_int8(qt) - x))
    assert err.max() <= float(qt.scale) * 0.51 + 1e-7


def test_int8_stochastic_rounding_unbiased():
    x = jnp.full((2000,), 0.301, jnp.float32)
    outs = []
    for s in range(64):
        qt = C.quantize_int8(x, key=jax.random.PRNGKey(s))
        outs.append(np.asarray(C.dequantize_int8(qt)).mean())
    assert abs(np.mean(outs) - 0.301) < 2e-3


def test_topk_keeps_largest():
    x = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05], jnp.float32)
    out = C.topk_decompress(C.topk_compress(x, 2))
    np.testing.assert_allclose(np.asarray(out), [0, -5.0, 0, 3.0, 0])


def test_error_feedback_accumulates_dropped_mass():
    """With error feedback the *running sum* of wire values converges to the
    running sum of true gradients (no systematic loss)."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.standard_normal(64) * 0.01, jnp.float32)
    ef = C.ef_init({"g": g_true})
    sent = jnp.zeros(64)
    T = 50
    for t in range(T):
        wire, ef = C.compress_grads({"g": g_true}, ef, scheme="topk",
                                    topk_frac=0.1)
        sent = sent + wire["g"]
    # average transmitted ≈ true gradient (error feedback catches up)
    np.testing.assert_allclose(np.asarray(sent / T), np.asarray(g_true),
                               atol=5e-3)


def test_ef_convergence_parity_on_quadratic():
    """SGD on a quadratic with int8+EF compressed gradients reaches the same
    optimum as uncompressed (convergence-parity unit check, DESIGN §7)."""
    rng = np.random.default_rng(2)
    Q = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    Q = Q @ Q.T / 16 + jnp.eye(16)
    b = jnp.asarray(rng.standard_normal(16), jnp.float32)
    x_star = jnp.linalg.solve(Q, b)

    def run(scheme):
        x = jnp.zeros(16)
        ef = C.ef_init({"g": x})
        for t in range(300):
            g = Q @ x - b
            wire, ef = C.compress_grads({"g": g}, ef, scheme=scheme,
                                        key=jax.random.PRNGKey(t))
            x = x - 0.1 * wire["g"]
        return x

    for scheme in ["none", "int8"]:
        err = float(jnp.linalg.norm(run(scheme) - x_star))
        assert err < 1e-2, (scheme, err)


def test_wire_bytes_accounting():
    g = {"a": jnp.zeros((100,)), "b": jnp.zeros((10, 10))}
    assert C.wire_bytes(g, "none") == 200 * 4
    assert C.wire_bytes(g, "int8") == 200 + 8
    assert C.wire_bytes(g, "topk", topk_frac=0.1) == (10 + 10) * 8


# ---------------------------------------------------------------------------
# Collectives (need >1 device -> subprocess with forced host devices)
# ---------------------------------------------------------------------------

SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.dist.collectives import hierarchical_psum

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
# local shard dim0 = 32/8 = 4, divisible by the 4-way inner reduce-scatter
x = jnp.arange(32 * 8, dtype=jnp.float32).reshape(32, 8)

def f(xs):
    return hierarchical_psum(xs, "pod", ("data",))

def g(xs):
    return jax.lax.psum(xs, ("pod", "data"))

fm = jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=P(None), check_vma=False)
gm = jax.shard_map(g, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=P(None), check_vma=False)
np.testing.assert_allclose(np.asarray(fm(x)), np.asarray(gm(x)), rtol=1e-6)
print("HIERARCHICAL_OK")

# sharded shotgun solver on an 8-device feature mesh
from repro.core import objectives as obj
from repro.core.sharded import shotgun_sharded_solve, make_feature_mesh
from repro.data import synthetic as syn
A, y, _ = syn.sparco(seed=0, n=128, d=256)
prob = obj.make_problem(A, y, lam=0.5)
res = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), P_local=1, rounds=2000)
f_end = float(res.trace.objective[-1])
from repro.core.shotgun import shotgun_solve
f_ref = float(shotgun_solve(prob, jax.random.PRNGKey(1), P=8,
                            rounds=2000).trace.objective[-1])
assert abs(f_end - f_ref) / abs(f_ref) < 0.05, (f_end, f_ref)
np.testing.assert_allclose(np.asarray(res.z), np.asarray(prob.A @ res.x),
                           rtol=2e-3, atol=2e-3)
print("SHARDED_OK")

# sharding rules: param/cache specs on a (2 data x 4 model) mesh
from repro.configs import ARCHS
from repro.models import sharding as SH
from repro.models import model as M
import jax.numpy as jnp
mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
cfg = ARCHS["qwen3-4b"].smoke_config()
shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
specs = SH.param_specs(shapes, mesh2, SH.ShardingPolicy())
blk = specs["blocks"]["l0"]
assert tuple(blk["attn"]["wq"]) == (None, "data", "model"), blk["attn"]["wq"]
assert tuple(blk["attn"]["wo"]) == (None, "model", "data"), blk["attn"]["wo"]
assert tuple(blk["mlp"]["wi"]) == (None, "data", "model")
assert tuple(specs["embed"]) == (None, ("data", "model"))
assert all(a is None for a in tuple(blk["pre_norm"]["scale"])), blk["pre_norm"]
# cache: decode policy S-shards the kv seq on the model axis
cache = jax.eval_shape(lambda: M.init_cache(cfg, 8, 64))
cspecs = SH.cache_specs(cache, mesh2,
                        SH.ShardingPolicy(cache_seq_on_tensor=True))
kspec = tuple(cspecs["blocks"]["l0"]["kv"]["k"])
assert kspec[2] == "model", kspec       # (group, B, S@model, hkv, dh)
print("RULES_OK")
"""


@pytest.mark.slow
def test_multidevice_collectives_and_sharded_solver():
    out = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert "HIERARCHICAL_OK" in out.stdout, out.stdout + out.stderr
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr
    assert "RULES_OK" in out.stdout, out.stdout + out.stderr
