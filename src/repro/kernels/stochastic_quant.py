"""Pallas TPU kernel: fused stochastic quantize-dequantize (paper Eq. 16-17).

The gradient tensor streams HBM -> VMEM in (block_m, block_n) tiles; the
kernel performs the |g| -> level -> stochastic-round -> dequant chain in
registers, writing the quantized-value tensor back. The per-tensor range
and level count ride along as one (1, 3) block. Randomness is supplied as a
uniform tensor generated outside, so the kernel compiled on a TPU and the
interpreter on the CPU consume the same bits as the jnp reference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import interpret_mode

DEFAULT_BLOCK = (256, 256)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def stochastic_quant(g: jax.Array, rand: jax.Array, lo: jax.Array,
                     hi: jax.Array, bits: int,
                     block=DEFAULT_BLOCK,
                     interpret: Optional[bool] = None
                     ) -> jax.Array:
    """g, rand: (M, N); lo/hi: scalars. Returns Q(g) in g.dtype.

    Static-bits convenience over ``stochastic_quant_dyn`` — one kernel
    body serves both, so the Eq. 16-17 math cannot diverge between them.
    """
    return stochastic_quant_dyn(g, rand, lo, hi,
                                jnp.float32(2 ** bits - 1),
                                block=block, interpret=interpret)


def _quant_kernel_dyn(g_ref, rand_ref, range_ref, out_ref):
    """Like ``_quant_kernel`` but the level count rides in the range block
    ((1, 3): lo, hi, n_levels) so a traced per-client bit-width — the
    unified round engine's vmapped ``delta`` — reaches the kernel without
    retracing."""
    g = g_ref[...].astype(jnp.float32)
    rand = rand_ref[...].astype(jnp.float32)
    lo = range_ref[0, 0]
    hi = range_ref[0, 1]
    n_levels = range_ref[0, 2]
    scale = (hi - lo) / n_levels
    scale = jnp.where(scale > 0, scale, 1.0)
    a = jnp.abs(g)
    t = (a - lo) / scale
    t_floor = jnp.floor(t)
    up = (rand < (t - t_floor)).astype(jnp.float32)
    level = jnp.clip(t_floor + up, 0.0, n_levels)
    mag = lo + level * scale
    out_ref[...] = jnp.where(g >= 0, mag, -mag).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def stochastic_quant_dyn(g: jax.Array, rand: jax.Array, lo: jax.Array,
                         hi: jax.Array, n_levels: jax.Array,
                         block=DEFAULT_BLOCK,
                         interpret: Optional[bool] = None
                         ) -> jax.Array:
    """Traced-level-count variant: g, rand (M, N); lo/hi/n_levels scalars."""
    m, n = g.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (g.shape, block)
    rng = jnp.stack([lo.astype(jnp.float32), hi.astype(jnp.float32),
                     n_levels.astype(jnp.float32)]).reshape(1, 3)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _quant_kernel_dyn,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 3), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), g.dtype),
        interpret=interpret_mode(interpret),
    )(g, rand, rng)
