"""Uniform cohorts: U of the N registered devices, drawn without
replacement, the same chance for each."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program():
    from repro.fed import UniformSampler
    return UniformSampler()


def cohort(key: jax.Array, n: int, u: int) -> jax.Array:
    """The reference's draw from the round's cohort key: every device
    when U = N, else U indices without replacement, in ascending order."""
    if u == n:
        return jnp.arange(n, dtype=jnp.int32)
    return jnp.sort(jax.random.choice(key, n, (u,), replace=False)
                    ).astype(jnp.int32)
