"""Gradient aggregation under packet loss (paper Eq. 9/14/19).

g^n = sum_u N_u alpha_u Q(g_u) / sum_u N_u alpha_u

If every packet drops (sum alpha = 0) the round contributes a zero update
(the server keeps the current model), matching the paper's semantics of a
wasted round.

Partial participation (population layer): with a sampled cohort the server
may instead divide by a FIXED denominator — pass ``denom`` = the population
sample total sum_j N_j and weights N_i / pi_i (pi_i the inclusion
probability) for the Horvitz-Thompson-style unbiased estimate of the
full-population update; the default (``denom=None``) renormalizes over the
received cohort, the paper's Eq. 19 convention.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def _normalizer(weights: jax.Array, alpha: jax.Array,
                denom: Optional[jax.Array]):
    """Eq. 19's weights N_u alpha_u, their total, and the divisor."""
    w = (weights * alpha).astype(jnp.float32)
    received = jnp.sum(w)
    norm = received if denom is None else jnp.asarray(denom, jnp.float32)
    return w, received, jnp.maximum(norm, 1e-12)


def _weighted(w: jax.Array, g: jax.Array) -> jax.Array:
    return jnp.tensordot(w.astype(g.dtype), g, axes=([0], [0]))


def _divided(wg: jax.Array, received: jax.Array, safe: jax.Array):
    out = wg / safe.astype(wg.dtype)
    return jnp.where(received > 0, out, jnp.zeros_like(out))


def weighted_sum(client_grads: PyTree, w: jax.Array) -> PyTree:
    """sum_u w_u g_u over the leading client axis of every leaf."""
    return jax.tree_util.tree_map(lambda g: _weighted(w, g), client_grads)


def aggregate(client_grads: PyTree, weights: jax.Array,
              alpha: jax.Array,
              denom: Optional[jax.Array] = None) -> PyTree:
    """client_grads: pytree with leading client axis C on every leaf;
    weights (C,) = N_u (or N_u / pi_u for unbiased partial participation);
    alpha (C,) in {0, 1} (float ok); ``denom`` fixes the normalizer
    instead of sum(weights * alpha)."""
    w, received, safe = _normalizer(weights, alpha, denom)
    return jax.tree_util.tree_map(
        lambda g: _divided(_weighted(w, g), received, safe), client_grads)


def aggregate_sum(summed: PyTree, weights: jax.Array, alpha: jax.Array,
                  denom: Optional[jax.Array] = None) -> PyTree:
    """Eq. 19 from the weighted sum sum_u N_u alpha_u Q(g_u) already
    taken (a cohort streamed in chunks adds it up itself): normalised by
    sum(weights * alpha), or by ``denom``."""
    _, received, safe = _normalizer(weights, alpha, denom)
    return jax.tree_util.tree_map(
        lambda wg: _divided(wg, received, safe), summed)
