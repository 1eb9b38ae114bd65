"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend, ops, ref
from repro.kernels.block_prune import apply_block_mask, block_norms
from repro.kernels.block_sparse_matmul import block_sparse_matmul
from repro.kernels.stochastic_quant import stochastic_quant

SHAPES = [(128, 128), (256, 512), (384, 256)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_stochastic_quant_matches_ref(shape, dtype, bits):
    g = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    rand = jax.random.uniform(jax.random.PRNGKey(1), shape)
    a = jnp.abs(g.astype(jnp.float32))
    lo, hi = jnp.min(a), jnp.max(a)
    out_k = np.asarray(stochastic_quant(g, rand, lo, hi, bits,
                                        block=(128, 128)), np.float32)
    out_r = np.asarray(ref.stochastic_quant_ref(g, rand, lo, hi, bits),
                       np.float32)
    step = (float(hi) - float(lo)) / (2 ** bits - 1)
    diff = np.abs(out_k - out_r)
    # stochastic rounding: ULP differences at bucket boundaries may flip a
    # rare element by exactly one step; everything else must match
    assert np.mean(diff > 1e-6) < 1e-3
    assert diff.max() <= step * 1.001


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_block_norms_matches_ref(shape, dtype):
    w = jax.random.normal(jax.random.PRNGKey(2), shape).astype(dtype)
    out_k = np.asarray(block_norms(w, block=(128, 128)))
    out_r = np.asarray(ref.block_norms_ref(w, 128, 128))
    np.testing.assert_allclose(out_k, out_r, rtol=2e-2 if
                               dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_apply_mask_matches_ref(shape, dtype):
    w = jax.random.normal(jax.random.PRNGKey(3), shape).astype(dtype)
    tiles = (shape[0] // 128, shape[1] // 128)
    mask = jax.random.uniform(jax.random.PRNGKey(4), tiles) > 0.5
    out_k = np.asarray(apply_block_mask(w, mask, block=(128, 128)),
                       np.float32)
    out_r = np.asarray(ref.apply_block_mask_ref(w, mask, 128, 128),
                       np.float32)
    np.testing.assert_allclose(out_k, out_r)


@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 256, 512),
                                 (128, 384, 256)])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_block_sparse_matmul_matches_ref(mnk, dtype, density):
    m, n, k = mnk
    x = (jax.random.normal(jax.random.PRNGKey(5), (m, k)) / 8).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(6), (k, n)) / 8).astype(dtype)
    tiles = (k // 128, n // 128)
    mask = jax.random.uniform(jax.random.PRNGKey(7), tiles) < density
    out_k = np.asarray(block_sparse_matmul(x, w, mask,
                                           blocks=(128, 128, 128)),
                       np.float32)
    out_r = np.asarray(ref.block_sparse_matmul_ref(x, w, mask, 128, 128),
                       np.float32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(out_k, out_r, rtol=tol, atol=tol)


def test_ops_wrappers_roundtrip():
    g = jax.random.normal(jax.random.PRNGKey(8), (256, 256))
    q = ops.quantize_dequantize_2d(g, 8, jax.random.PRNGKey(9))
    assert float(jnp.max(jnp.abs(q - g))) < 0.05  # 8-bit: fine steps
    pruned, mask = ops.block_prune_2d(g, 0.25)
    assert mask.shape == (2, 2)
    assert int(jnp.sum(~mask)) == 1
    y = ops.pruned_matmul(jax.random.normal(jax.random.PRNGKey(10),
                                            (128, 256)), g, 0.25)
    assert y.shape == (128, 256)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_fully_masked_matmul_is_zero():
    x = jax.random.normal(jax.random.PRNGKey(11), (128, 256))
    w = jax.random.normal(jax.random.PRNGKey(12), (256, 128))
    mask = jnp.zeros((2, 1), bool)
    y = block_sparse_matmul(x, w, mask)
    np.testing.assert_array_equal(np.asarray(y), 0.0)


@pytest.mark.parametrize("platform,given,expected", [
    ("cpu", None, True), ("tpu", None, False),
    ("cpu", False, False), ("tpu", True, True), ("gpu", False, False)])
def test_interpret_mode_follows_backend(monkeypatch, platform, given,
                                        expected):
    monkeypatch.setattr(backend.jax, "default_backend", lambda: platform)
    assert backend.interpret_mode(given) is expected


def test_interpret_mode_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        backend.interpret_mode()


def test_quantizer_kernel_takes_128_lane_leaves():
    """An expert's (2, 256, 1408) leaf (1408 = 11 x 128 lanes, not a
    multiple of 256) goes through the quantizer kernel with a 128-lane
    block, and its upload equals the jnp path's for the same key; leaves
    that tile at 256 keep their block, and a last side that is not a
    multiple of 128 stays on the jnp path."""
    from repro.core.compressors import ltfl_quantizer
    assert ops.kernel_quant_block((2, 256, 1408)) == (256, 128)
    assert ops.kernel_quant_block((3, 3, 256, 512)) == (256, 256)
    assert ops.kernel_quant_block((2048, 576)) is None
    g = {"w": jax.random.normal(jax.random.PRNGKey(3), (2, 256, 1408))}
    key, delta = jax.random.PRNGKey(4), jnp.float32(4.0)
    out_k, _ = ltfl_quantizer(use_kernels=True).compress(g, delta, key, ())
    out_j, _ = ltfl_quantizer(use_kernels=False).compress(g, delta, key, ())
    np.testing.assert_array_equal(out_k["w"], out_j["w"])
