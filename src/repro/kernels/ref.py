"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's test sweeps shapes/dtypes and asserts allclose against these
references in the Pallas interpreter on the CPU; tests/test_tpu_compile.py
compiles the same kernels for a TPU v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def stochastic_quant_ref(g: jax.Array, rand: jax.Array, lo: jax.Array,
                         hi: jax.Array, bits: int) -> jax.Array:
    """Quantize-dequantize |g| onto 2^bits - 1 uniform steps in [lo, hi]
    with stochastic rounding driven by ``rand`` (uniform [0,1))."""
    gf = g.astype(jnp.float32)
    a = jnp.abs(gf)
    n = float(2 ** bits - 1)
    scale = (hi - lo) / n
    scale = jnp.where(scale > 0, scale, 1.0)
    t = (a - lo) / scale
    t_floor = jnp.floor(t)
    frac = t - t_floor
    up = (rand.astype(jnp.float32) < frac).astype(jnp.float32)
    level = jnp.clip(t_floor + up, 0.0, n)
    mag = lo + level * scale
    return jnp.where(gf >= 0, mag, -mag).astype(g.dtype)


def block_norms_ref(w: jax.Array, bm: int, bn: int) -> jax.Array:
    """Per-(bm x bn)-tile L2 norms of a 2-D array -> (M/bm, N/bn) f32."""
    m, n = w.shape
    t = w.astype(jnp.float32).reshape(m // bm, bm, n // bn, bn)
    return jnp.sqrt(jnp.sum(t * t, axis=(1, 3)))


def apply_block_mask_ref(w: jax.Array, mask: jax.Array, bm: int,
                         bn: int) -> jax.Array:
    """Zero masked (mask==0) tiles. mask (M/bm, N/bn)."""
    m, n = w.shape
    t = w.reshape(m // bm, bm, n // bn, bn)
    out = t * mask[:, None, :, None].astype(w.dtype)
    return out.reshape(m, n)


def block_sparse_matmul_ref(x: jax.Array, w: jax.Array, mask: jax.Array,
                            bk: int, bn: int) -> jax.Array:
    """x (M, K) @ w (K, N) with (bk x bn) tiles of w zeroed per mask
    (K/bk, N/bn). Accumulation in f32."""
    wm = apply_block_mask_ref(w, mask, bk, bn)
    return jnp.dot(x.astype(jnp.float32), wm.astype(jnp.float32)
                   ).astype(x.dtype)
