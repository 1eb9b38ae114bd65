"""Model pruning (paper Eq. 11-13, Lemma 2).

Two granularities:

* ``magnitude_prune`` — the paper's unstructured importance I_v = |w_v|
  (Eq. 12): zero the smallest rho-fraction of entries. This is what the
  edge-mode (paper-scale) experiments use.

* ``block_prune`` — the TPU adaptation (DESIGN.md section 3): importance is
  the L2 norm of 128x128 parameter tiles; whole tiles are zeroed so the
  sparsity is MXU-structured and the Pallas block-sparse matmul can skip
  them. Lemma 2's bound ||w - w_hat||^2 <= rho ||w||^2 holds at tile
  granularity for the same reason it holds per element (we zero the
  smallest-norm rho-fraction of mass carriers).

Both return (pruned, mask) and accept a traced rho.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

PyTree = Any
BLOCK = 128


def importance(w: jax.Array) -> jax.Array:
    """Eq. 12 importance: |w|."""
    return jnp.abs(w)


def _bisect(pred, lo: int, hi: int) -> jax.Array:
    """Smallest integer x in [lo, hi] at which the monotone predicate
    ``pred`` (false ... false, true ... true) holds; hi if it never does
    below hi."""
    def step(_, bounds):
        lo, hi = bounds
        mid = lo + (hi - lo) // 2
        ok = pred(mid)
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    iters = max(int(hi - lo).bit_length(), 1)
    _, x = jax.lax.fori_loop(0, iters, step,
                             (jnp.int32(lo), jnp.int32(hi)))
    return x


def rank_mask(a: jax.Array, rho: jax.Array) -> jax.Array:
    """True for entries NOT among the floor(rho * n) smallest of ``a``
    (a >= 0), ties broken by flat index: exactly ``ranks >= k`` with the
    ranks of a stable argsort, so exactly floor(rho*n) entries prune — a
    quantile threshold with strict comparison would zero *every* entry of
    a constant tensor.

    Found by bisection, not sorting: the k-th smallest value is searched
    over the int32 bit patterns of the non-negative floats (ordered as the
    values are; NaN sorts last), then the index cut among its ties. Each
    step is one counting pass, so the program holds no sort: a sort of a
    wide leaf costs the TPU compiler about half a minute per shape.
    """
    flat = a.reshape(-1).astype(jnp.float32)
    n = flat.size
    k = jnp.floor(jnp.clip(rho, 0.0, 1.0) * n).astype(jnp.int32)
    bits = jax.lax.bitcast_convert_type(flat, jnp.int32)
    # the k-th smallest value t (its bit pattern); t = 0 when k == 0
    t = _bisect(lambda v: jnp.sum(bits <= v) >= k, 0, 0x7FFFFFFF)
    ties = bits == t
    cut = k - jnp.sum(bits < t)             # ties that prune, lowest first
    idx = jnp.arange(n, dtype=jnp.int32)
    m = _bisect(lambda i: jnp.sum(ties & (idx < i)) >= cut, 0, n)
    return ((bits > t) | (ties & (idx >= m))).reshape(a.shape)


def magnitude_prune(w: jax.Array, rho: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Zero the smallest-|w| rho-fraction of entries (Eq. 12-13).
    rho may be traced."""
    mask = rank_mask(jnp.abs(w.astype(jnp.float32)), rho)
    return w * mask.astype(w.dtype), mask


def magnitude_prune_pytree(w: PyTree, rho: jax.Array) -> Tuple[PyTree, PyTree]:
    """Unstructured (paper-faithful) pruning; 1-D leaves exempt (see
    ``prune_pytree``)."""
    def leaf(x):
        if x.ndim < 2:
            return x, jnp.ones(x.shape, bool)
        return magnitude_prune(x, rho)

    pruned_and_masks = jax.tree_util.tree_map(
        leaf, w, is_leaf=lambda x: isinstance(x, jax.Array))
    pruned = jax.tree_util.tree_map(lambda t: t[0], pruned_and_masks,
                                    is_leaf=lambda x: isinstance(x, tuple))
    masks = jax.tree_util.tree_map(lambda t: t[1], pruned_and_masks,
                                   is_leaf=lambda x: isinstance(x, tuple))
    return pruned, masks


# --------------------------------------------------------------------------- #
# Block-structured pruning (TPU-native)
# --------------------------------------------------------------------------- #
def _tile_view(w: jax.Array, block: int):
    """Reshape the last two dims into (tiles_r, block, tiles_c, block).

    Requires divisibility; callers fall back to magnitude pruning for
    tensors whose trailing dims don't tile (biases, norms, small tables).
    """
    r, c = w.shape[-2], w.shape[-1]
    lead = w.shape[:-2]
    return w.reshape(*lead, r // block, block, c // block, block)


def tileable(w: jax.Array, block: int = BLOCK) -> bool:
    return (w.ndim >= 2 and w.shape[-2] % block == 0
            and w.shape[-1] % block == 0)


def block_importance(w: jax.Array, block: int = BLOCK) -> jax.Array:
    """L2 norm per (block x block) tile of the last two dims."""
    t = _tile_view(w.astype(jnp.float32), block)
    return jnp.sqrt(jnp.sum(t * t, axis=(-3, -1)))     # (..., tr, tc)


def block_prune(w: jax.Array, rho: jax.Array, block: int = BLOCK
                ) -> Tuple[jax.Array, jax.Array]:
    """Zero the smallest-L2 rho-fraction of tiles. Returns (pruned, tile_mask).

    tile_mask has shape (..., rows/block, cols/block).
    """
    imp = block_importance(w, block)
    tile_mask = rank_mask(imp, rho)                   # (..., tr, tc)
    t = _tile_view(w, block)
    m = tile_mask[..., :, None, :, None].astype(w.dtype)
    pruned = (t * m).reshape(w.shape)
    return pruned, tile_mask


def prune_pytree(w: PyTree, rho: jax.Array, block: int = BLOCK,
                 *, use_kernels: bool = False) -> Tuple[PyTree, PyTree]:
    """Block-prune tileable leaves; magnitude-prune other >=2-D leaves;
    EXEMPT 1-D leaves (norm scales, biases) — pruning them destroys the
    network for negligible savings, and no pruning system touches them.

    Returns (pruned_tree, element_mask_tree) where masks are element-level
    (tile masks are expanded) so they can gate gradients uniformly.

    ``use_kernels`` routes the bandwidth-heavy passes of tileable leaves
    (tile norms, masking) through the Pallas kernels in repro.kernels.ops.
    Collapsing the leading dims into rows keeps every tile intact
    (shape[-2] % block == 0) and preserves the global tile ranking,
    flatten order and all — masks are bit-identical to the jnp path.
    """
    if use_kernels:
        from repro.kernels import ops as kops

        def kernel_block_leaf(x):
            m2 = x.reshape(-1, x.shape[-1])
            pruned, tile_mask = kops.block_prune_2d(m2, rho,
                                                    block=(block, block))
            emask = jnp.broadcast_to(
                tile_mask[:, None, :, None],
                (tile_mask.shape[0], block, tile_mask.shape[1], block)
            ).reshape(x.shape)
            return pruned.reshape(x.shape), emask

    def leaf(x):
        if x.ndim < 2:
            return x, jnp.ones(x.shape, bool)
        if tileable(x, block):
            if use_kernels:
                return kernel_block_leaf(x)
            imp = block_importance(x, block)
            tile_mask = rank_mask(imp, rho)
            t = _tile_view(x, block)
            m = tile_mask[..., :, None, :, None]
            pruned = (t * m.astype(x.dtype)).reshape(x.shape)
            emask = jnp.broadcast_to(m, t.shape).reshape(x.shape)
            return pruned, emask
        return magnitude_prune(x, rho)

    out = jax.tree_util.tree_map(leaf, w)
    pruned = jax.tree_util.tree_map(lambda t: t[0], out,
                                    is_leaf=lambda x: isinstance(x, tuple))
    masks = jax.tree_util.tree_map(lambda t: t[1], out,
                                   is_leaf=lambda x: isinstance(x, tuple))
    return pruned, masks


def pruning_error_bound(rho: jax.Array, d_sq: float) -> jax.Array:
    """Lemma 2:  E||w - w_hat||^2 <= rho * D^2."""
    return rho * d_sq


def actual_pruning_error(w: PyTree, pruned: PyTree) -> jax.Array:
    """||w - w_hat||^2 (used by property tests against Lemma 2)."""
    def leaf(a, b):
        d = (a - b).astype(jnp.float32)
        return jnp.sum(d * d)
    return sum(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(leaf, w, pruned)))
