"""Pallas TPU kernels for block-structured pruning (DESIGN.md section 3).

Two kernels:
  * ``block_norms`` — per-tile L2 importance (the block analogue of the
    paper's Eq. 12 |w| importance): one grid step per (bm, bn) tile,
    reducing in VMEM and writing a single f32 per tile.
  * ``apply_block_mask`` — streams w through VMEM multiplying each tile by
    its {0,1} mask entry (the pruning application, Eq. 13).

The per-tile scalars (the norms out, the mask in) sit in SMEM one grid row
at a time, as a (rows, 1, cols) array whose (1, cols) block equals the
array's last two dims: a (1, 1) VMEM block would break the TPU rule that a
block's last two dims divide by (8, 128) or equal the array's, and the
whole tile grid in SMEM outgrows its 1 MiB at a vocabulary-sized leaf
(256000 x 18432 needs 2,048,000 B). A row needs 8 * ceil(cols / 128) * 128
bytes, double-buffered.

The global tile *ranking* (choosing which tiles die) happens outside on the
tiny (M/bm x N/bn) norm matrix — that part is control logic, not a
bandwidth problem.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

DEFAULT_BLOCK = (128, 128)


def _row_of_tiles(grid) -> pl.BlockSpec:
    """Grid row i of a (rows, 1, cols) array of per-tile scalars, in SMEM."""
    return pl.BlockSpec((None, 1, grid[1]), lambda i, j: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _norms_kernel(w_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)
    out_ref[0, pl.program_id(1)] = jnp.sqrt(jnp.sum(w * w))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_norms(w: jax.Array, block=DEFAULT_BLOCK,
                interpret: Optional[bool] = None) -> jax.Array:
    m, n = w.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (w.shape, block)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _norms_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=_row_of_tiles(grid),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, grid[1]), jnp.float32),
        interpret=interpret_mode(interpret),
    )(w).reshape(grid)


def _mask_kernel(w_ref, mask_ref, out_ref):
    keep = mask_ref[0, pl.program_id(1)]
    out_ref[...] = w_ref[...] * keep.astype(w_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def apply_block_mask(w: jax.Array, mask: jax.Array, block=DEFAULT_BLOCK,
                     interpret: Optional[bool] = None) -> jax.Array:
    """mask (M/bm, N/bn) in {0,1}; zeroes masked tiles of w."""
    m, n = w.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0
    assert mask.shape == (m // bm, n // bn), (mask.shape, (m // bm, n // bn))
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _mask_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            _row_of_tiles(grid),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        interpret=interpret_mode(interpret),
    )(w, mask.astype(jnp.float32).reshape(grid[0], 1, grid[1]))
