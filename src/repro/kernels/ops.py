"""Jitted public wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses; each picks
hardware-aligned block shapes and handles range/mask preparation. The
kernels compile on a TPU and run in the Pallas interpreter on the CPU
(``repro.kernels.backend``); any other backend is an error.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.pruning import rank_mask
from repro.kernels import block_prune as _bp
from repro.kernels import block_sparse_matmul as _bsmm
from repro.kernels import stochastic_quant as _sq


def quantize_dequantize_2d(g: jax.Array, bits: int, key: jax.Array,
                           block=(256, 256)) -> jax.Array:
    """Kernel-backed Q(g) for a 2-D tensor (paper Eq. 16-17), static
    bit-width; thin wrapper over the traced-bits path."""
    return quantize_dequantize_2d_dyn(g, jnp.float32(bits), key, block=block)


# the narrowest lane block the quantizer kernel takes: a TPU vreg's lanes
LANES = 128


def kernel_quant_block(shape: Tuple[int, ...], block=(256, 256)
                       ) -> Optional[Tuple[int, int]]:
    """The quantizer kernel's block for a >=2-D tensor viewed as
    (prod(leading), last): ``block`` where it tiles evenly (each side cut
    to the tensor's); else, where only the last side fails and is a
    multiple of 128 lanes (an expert's 1408 = 11 x 128), ``block`` with
    128 lanes; else None: the leaf stays on the jnp path (the two are
    bit-identical given the same key)."""
    if len(shape) < 2:
        return None
    m = 1
    for d in shape[:-1]:
        m *= d
    n = shape[-1]
    if m == 0 or n == 0 or m % min(block[0], m):
        return None
    if n % min(block[1], n) == 0:
        return tuple(block)
    if n % LANES == 0:
        return block[0], LANES
    return None


def quantize_dequantize_2d_dyn(g: jax.Array, bits: jax.Array, key: jax.Array,
                               block=(256, 256)) -> jax.Array:
    """Kernel-backed Q(g) with a *traced* bit-width — the unified round
    engine's 2-D fast path, where delta is a per-client array under vmap.
    Math and randomness match ``quantize_dequantize`` exactly."""
    a = jnp.abs(g.astype(jnp.float32))
    lo, hi = jnp.min(a), jnp.max(a)
    n_levels = jnp.maximum(
        jnp.round(2.0 ** jnp.asarray(bits, jnp.float32)) - 1.0, 1.0)
    rand = jax.random.uniform(key, g.shape, jnp.float32)
    return _sq.stochastic_quant_dyn(g, rand, lo, hi, n_levels, block=block)


def block_prune_2d(w: jax.Array, rho: float, block=(128, 128)
                   ) -> Tuple[jax.Array, jax.Array]:
    """Kernel-backed block pruning: returns (pruned_w, tile_mask).

    Tiles are ranked on the small norms matrix by the jnp path's own rule
    (``pruning.rank_mask``); the two bandwidth-heavy passes (norms,
    masking) are kernels.
    """
    norms = _bp.block_norms(w, block=block)
    mask = rank_mask(norms, rho)
    pruned = _bp.apply_block_mask(w, mask, block=block)
    return pruned, mask


def block_sparse_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                        blocks=(128, 128, 128)) -> jax.Array:
    """x @ w skipping pruned w tiles (the rho compute saving on MXU)."""
    return _bsmm.block_sparse_matmul(x, w, mask, blocks=blocks)


def pruned_matmul(x: jax.Array, w: jax.Array, rho: float,
                  blocks=(128, 128, 128)) -> jax.Array:
    """Convenience: block-prune w at ratio rho, then block-sparse matmul."""
    _, mask = block_prune_2d(w, rho, block=(blocks[2], blocks[1]))
    return block_sparse_matmul(x, w, mask, blocks=blocks)
