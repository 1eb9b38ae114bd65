"""Model pruning (paper Eq. 11-13, Lemma 2) — unstructured + block.

Property sweeps are seeded parameter grids (rho x seed) rather than
hypothesis strategies — same coverage, no extra dependency."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning import (
    actual_pruning_error,
    block_importance,
    block_prune,
    magnitude_prune,
    magnitude_prune_pytree,
    prune_pytree,
    rank_mask,
    tileable,
)

RHOS = (0.0, 0.07, 0.25, 0.5, 0.77, 0.9)
SEEDS = (0, 17, 1234, 52341)
RHO_SEED = list(itertools.product(RHOS, SEEDS))


def _tie_heavy(kind, shape, rng):
    """Non-negative float32 inputs with many multi-way ties."""
    n = int(np.prod(shape))
    if kind == "zeros":
        a = np.zeros(n)
    elif kind == "constant":
        a = np.full(n, 0.75)
    elif kind == "quantized":                    # 4 levels, as after Q(g)
        a = rng.integers(0, 4, n) * 0.5
    elif kind == "repeated_norms":               # few distinct tile norms
        a = rng.choice(rng.uniform(0.0, 3.0, 3), n)
    elif kind == "subnormal_zero_inf":
        a = rng.choice(np.array([0.0, 1e-45, 1e-40, 2.0, np.inf]), n)
    else:                                        # "distinct"
        a = np.abs(rng.normal(size=n))
    return a.astype(np.float32).reshape(shape)


@pytest.mark.parametrize("rho", (0.0, 0.1, 1 / 3, 0.5, 0.9, 1.0))
@pytest.mark.parametrize("kind", ("zeros", "constant", "quantized",
                                  "repeated_norms", "subnormal_zero_inf",
                                  "distinct"))
def test_rank_mask_matches_stable_argsort(kind, rho):
    """The bisection mask equals ``ranks >= floor(rho * n)`` with the ranks
    of a stable argsort, ties broken by flat index."""
    rng = np.random.default_rng(7)
    for shape in ((1,), (7,), (64, 32), (3, 5, 8)):
        a = _tie_heavy(kind, shape, rng)
        flat = a.reshape(-1)
        ranks = np.argsort(np.argsort(flat, kind="stable"), kind="stable")
        k = int(np.floor(np.float32(rho) * np.float32(flat.size)))
        expect = (ranks >= k).reshape(shape)
        got = np.asarray(jax.jit(rank_mask)(jnp.asarray(a), jnp.float32(rho)))
        np.testing.assert_array_equal(got, expect, err_msg=f"{shape}")


@pytest.mark.parametrize("rho,seed", RHO_SEED)
def test_exact_prune_fraction(rho, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (64, 32))
    pruned, mask = magnitude_prune(w, rho)
    expect = int(np.floor(rho * w.size))
    assert int(w.size - jnp.sum(mask)) == expect


@pytest.mark.parametrize("rho,seed", RHO_SEED)
def test_lemma2_bound(rho, seed):
    """||w - w_hat||^2 <= rho ||w||^2 for magnitude pruning."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (64, 64))
    pruned, _ = magnitude_prune(w, rho)
    err = float(actual_pruning_error(w, pruned))
    assert err <= rho * float(jnp.sum(w * w)) + 1e-5


def test_random_rho_sweep_prunes_exactly():
    """Randomized sweep (seeded np.random): the realized pruned fraction
    is exact for arbitrary rho draws, shapes and weight scales."""
    rng = np.random.default_rng(99)
    for _ in range(12):
        rho = float(rng.uniform(0.0, 0.95))
        shape = (int(rng.integers(8, 80)), int(rng.integers(8, 80)))
        w = jnp.asarray(rng.normal(scale=rng.uniform(0.1, 10.0),
                                   size=shape).astype(np.float32))
        _, mask = magnitude_prune(w, rho)
        assert int(w.size - jnp.sum(mask)) == int(np.floor(rho * w.size))


def test_smallest_entries_pruned():
    w = jnp.array([[0.01, -5.0], [0.02, 4.0]])
    pruned, mask = magnitude_prune(w, 0.5)
    assert not bool(mask[0, 0]) and not bool(mask[1, 0])
    assert bool(mask[0, 1]) and bool(mask[1, 1])


def test_block_prune_tile_structure():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    pruned, tile_mask = block_prune(w, 0.25, block=64)
    assert tile_mask.shape == (4, 4)
    assert int(jnp.sum(~tile_mask)) == 4   # floor(0.25 * 16)
    # pruned tiles are entirely zero; kept tiles untouched
    t = np.asarray(pruned).reshape(4, 64, 4, 64)
    for i in range(4):
        for j in range(4):
            if not bool(tile_mask[i, j]):
                assert np.all(t[i, :, j, :] == 0)


def test_block_lemma2_bound():
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    for rho in (0.1, 0.3, 0.5):
        pruned, tile_mask = block_prune(w, rho, block=64)
        frac = float(jnp.mean(~tile_mask))
        err = float(actual_pruning_error(w, pruned))
        # Lemma 2 at tile granularity, with the realized fraction
        assert err <= (frac + 1e-6) * float(jnp.sum(w * w))


def test_block_importance_matches_ref():
    from repro.kernels.ref import block_norms_ref
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 128))
    imp = block_importance(w, 64)
    ref = block_norms_ref(w, 64, 64)
    np.testing.assert_allclose(np.asarray(imp), np.asarray(ref), rtol=1e-5)


def test_pytree_exempts_1d():
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (128, 128)),
            "gamma": jnp.ones((128,))}
    pruned, masks = prune_pytree(tree, 0.5, block=64)
    np.testing.assert_array_equal(np.asarray(pruned["gamma"]), 1.0)
    assert bool(jnp.all(masks["gamma"]))
    assert float(jnp.mean(masks["w"].astype(jnp.float32))) < 1.0

    mp, mm = magnitude_prune_pytree(tree, 0.5)
    np.testing.assert_array_equal(np.asarray(mp["gamma"]), 1.0)


def test_tileable():
    assert tileable(jnp.zeros((256, 128)), 128)
    assert not tileable(jnp.zeros((100, 128)), 128)
    assert not tileable(jnp.zeros((128,)), 128)


def test_rho_zero_identity():
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 64))
    pruned, mask = magnitude_prune(w, 0.0)
    np.testing.assert_array_equal(np.asarray(pruned), np.asarray(w))
    assert bool(jnp.all(mask))
