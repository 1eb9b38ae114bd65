"""Inputs made from the seed: the weights' key, the samples, and the
program's seeded draws of its devices and their data shards.

Every input of a run comes from ``--seed`` through here. The benchmark
makes the weights and the data and hands them to the program; the
reference makes them again from the same seed. A family that defines
``dataset(seed, cfg)`` makes its own samples (``samples``); the others
get the images and labels of ``dataset`` below. ``devices_and_shards``
repeats the program's own seeded draws of the device registry and the
shards (the order of ``FedRunner.__init__``: Table 2 device draws, then
the partition), so the reference reads the same rows the program's
rounds read without taking the program's tables.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.spec import family

# stream tags under one seed
_WEIGHTS, _IMAGES, _LABELS = 1, 2, 3


def _key(seed: int, tag: int) -> jax.Array:
    word = np.random.SeedSequence([int(seed), tag]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def weights_key(seed: int) -> jax.Array:
    return _key(seed, _WEIGHTS)


def labels(seed: int, num: int, classes: int, split: int) -> np.ndarray:
    """Uniform class labels, int32, on the host (the partition reads
    them there)."""
    rng = np.random.default_rng([int(seed), _LABELS, split])
    return rng.integers(0, classes, size=num).astype(np.int32)


def images(seed: int, train_labels: np.ndarray, test_labels: np.ndarray,
           m: Dict) -> Tuple[jax.Array, jax.Array]:
    """CIFAR-shaped images in [-1, 1], float32, made on the device in one
    jitted call: a smooth random template per class (an 8x8 field
    upsampled) plus Gaussian noise of half its scale. Train and test share
    the templates."""
    side, ch, classes = m["image_size"], m["in_channels"], m["num_classes"]
    if side % 8:
        raise ValueError(f"image_size {side} is not a multiple of 8")

    def make(key, y_train, y_test):
        k_t, k_a, k_b = jax.random.split(key, 3)
        coarse = jax.random.normal(k_t, (classes, 8, 8, ch), jnp.float32)
        rep = side // 8
        tmpl = jnp.repeat(jnp.repeat(coarse, rep, axis=1), rep, axis=2)

        def split(k, y):
            x = tmpl[y] + 0.5 * jax.random.normal(
                k, (y.shape[0], side, side, ch), jnp.float32)
            return x / jnp.max(jnp.abs(x))

        return split(k_a, y_train), split(k_b, y_test)

    return jax.jit(make)(_key(seed, _IMAGES), jnp.asarray(train_labels),
                         jnp.asarray(test_labels))


def dataset(seed: int, cfg: Dict):
    """(train, test) as dicts of arrays: train images stay on the device,
    test images and all labels are host arrays."""
    m, dep = cfg["model"], cfg["deployment"]
    y_train = labels(seed, dep["train_samples"], m["num_classes"], 0)
    y_test = labels(seed, dep["test_samples"], m["num_classes"], 1)
    x_train, x_test = images(seed, y_train, y_test, m)
    return ({"images": x_train, "labels": y_train},
            {"images": np.asarray(x_test), "labels": y_test})


def samples(seed: int, cfg: Dict):
    """(train, test) as dicts of arrays keyed as the program model's batch:
    the family's own ``dataset`` where it defines one, else the images
    and labels of ``dataset`` here."""
    make = getattr(family(cfg["family"]), "dataset", dataset)
    return make(seed, cfg)


def shard_pool(cfg: Dict, train: Dict):
    """What the partition reads: the family's ``PARTITION_KEY`` array of
    one class label per sample, or where that key is None, the number of
    samples."""
    key = family(cfg["family"]).PARTITION_KEY
    if key is None:
        return int(next(iter(train.values())).shape[0])
    return np.asarray(train[key])


# --------------------------------------------------------------------------- #
# the program's seeded registry and partition draws
# --------------------------------------------------------------------------- #
def _iid_partition(num_samples: int, sizes: Sequence[int],
                   rng: np.random.Generator) -> List[np.ndarray]:
    perm = rng.permutation(num_samples)
    out, ofs = [], 0
    for s in sizes:
        out.append(np.sort(perm[ofs:ofs + s]))
        ofs += s
    return out


def _dirichlet_partition(y: np.ndarray, sizes: Sequence[int], alpha: float,
                         rng: np.random.Generator) -> List[np.ndarray]:
    classes = int(y.max()) + 1
    by_class = [list(rng.permutation(np.where(y == c)[0]))
                for c in range(classes)]
    out = []
    for size in sizes:
        counts = rng.multinomial(size, rng.dirichlet([alpha] * classes))
        idx: List[int] = []
        for c, k in enumerate(counts):
            pool = by_class[c]
            take = min(k, len(pool))
            idx.extend(pool[:take])
            del pool[:take]
            if take < k:        # class exhausted: draw it with replacement
                idx.extend(rng.choice(np.where(y == c)[0],
                                      size=k - take).tolist())
        out.append(np.asarray(sorted(idx), dtype=np.int64))
    return out


def _window_partition(num_samples: int, sizes: np.ndarray,
                      rng: np.random.Generator) -> List[np.ndarray]:
    """Shards as cyclic windows over stacked permutations of the pool."""
    total = int(sizes.sum())
    rows = max(1, -(-total // num_samples))
    if rows == 1:
        perms = rng.permutation(num_samples)[None]
    else:
        perms = rng.permuted(np.broadcast_to(
            np.arange(num_samples), (rows, num_samples)).copy(), axis=1)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return [np.sort(perms[s // num_samples,
                          (s + np.arange(n)) % num_samples])
            for s, n in zip(starts, sizes)]


def devices_and_shards(seed: int, cfg: Dict, train_labels):
    """The device registry's shard sizes, the (N, W) zero-padded shard
    table, and the registry itself (distance, interference, CPU frequency
    and sample count per device, float64), drawn as the program draws them from ``default_rng(seed)``:
    distances, interference and CPU frequencies (uniform per Table 2),
    shard sizes (integers in [samples_min, samples_max]), then the
    partition: Dirichlet when ``non_iid_alpha`` > 0, disjoint uniform
    shards when every device takes part in every round, else cyclic
    windows over the pool. ``train_labels`` is the pool's class label per
    sample, or the pool's number of samples where it has none (then
    ``non_iid_alpha`` has to be 0)."""
    dep, w, l = cfg["deployment"], cfg["wireless"], cfg["ltfl"]
    n = dep["population"]
    rng = np.random.default_rng(int(seed))
    registry = {
        "distance": rng.uniform(w["dist_min"], w["dist_max"], n),
        "interference": rng.uniform(w["interference_min"],
                                    w["interference_max"], n),
        "cpu": rng.uniform(w["cpu_min"], w["cpu_max"], n)}
    sizes = rng.integers(l["samples_min"], l["samples_max"] + 1, n)
    counted = isinstance(train_labels, (int, np.integer))
    num = int(train_labels) if counted else len(train_labels)
    if dep["non_iid_alpha"] > 0:
        if counted:
            raise ValueError("a Dirichlet partition (non_iid_alpha > 0) "
                             "needs a class label per sample")
        parts = _dirichlet_partition(train_labels, sizes,
                                     dep["non_iid_alpha"], rng)
    elif dep["cohort"] == n:
        parts = _iid_partition(num, sizes, rng)
    else:
        parts = _window_partition(num, sizes, rng)
    width = int(sizes.max())
    table = np.zeros((n, width), np.int32)
    for u, p in enumerate(parts):
        table[u, :p.size] = p
    registry["samples"] = sizes.astype(np.float64)
    return sizes.astype(np.int32), table, registry
