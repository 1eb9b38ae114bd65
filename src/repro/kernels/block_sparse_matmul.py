"""Pallas TPU kernel: block-sparse matmul over a pruned weight matrix.

This is where the paper's pruning ratio rho becomes real MXU FLOP savings
on TPU (DESIGN.md section 3): the weight's (bk, bn) tiles carry a {0,1}
mask from the block-pruner, and the kernel *skips the dot* for dead tiles
via @pl.when — the tile never reaches the MXU, so compute scales with
(1 - rho) exactly as the paper's delay model (Eq. 31) assumes.

Grid is (M/bm, N/bn, K/bk) with K innermost so each output tile is
revisited across the contraction; a VMEM f32 scratch accumulates partial
products and spills to the output dtype once, at the last K step. The
tile mask sits in SMEM one output column of tiles at a time (transposed,
as in ``block_prune``), so its footprint does not grow with the grid.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

DEFAULT_BLOCKS = (128, 128, 128)   # bm, bn, bk


def _bsmm_kernel(x_ref, w_ref, mask_ref, out_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[0, k_idx] != 0)
    def _dot():
        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            w_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def block_sparse_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                        blocks=DEFAULT_BLOCKS,
                        interpret: Optional[bool] = None) -> jax.Array:
    """x (M, K) @ w (K, N), skipping w tiles where mask (K/bk, N/bn) == 0."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = (min(blocks[0], m), min(blocks[1], n), min(blocks[2], k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, w.shape,
                                                         blocks)
    assert mask.shape == (k // bk, n // bn), mask.shape
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_bsmm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((None, 1, n_k), lambda i, j, kk: (j, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, w, mask.T.astype(jnp.int32)[:, None, :])
