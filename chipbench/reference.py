"""The plain reference of the federated rounds one call drives.

One round (arXiv:2507.09546, Eq. 8-20), written out in ``jax.numpy`` with
no kernels and no scan, at float32 with the highest matmul precision:

1. the channel: under block fading, every device's mean fading power and
   interference drawn anew (``accounting.fading``);
2. the cohort (the deployment's ``samplers/<sampler>.py``) and each
   device's batch, drawn with replacement from its shard;
3. per device, magnitude pruning of every weight of two or more
   dimensions at that device's ratio rho, where the scheme prunes: the
   floor(rho * n) entries of smallest magnitude are zeroed, ties broken
   by flat index (Eq. 12-13);
4. the loss and its gradient at the pruned weights; pruned coordinates
   get no gradient;
5. the gradient's range statistic sum_v (max|g| - min|g|)^2 (Eq. 26);
6. the scheme's upload of each leaf (``schemes/<scheme>.py``: the
   stochastic quantizer of Eq. 16-17 under LTFL, the gradient itself
   under FedSGD);
7. the engine's aggregation of the received gradients (Eq. 19) and a
   gradient-descent step (Eq. 20).

The random draws follow the engine's key layout from ``PRNGKey(seed)``
(``engines/<engine>.round_keys``); the step key splits into one key per
device and one spare, and a device's key into one per leaf. Algorithm
1's choices (each device's rho, delta and packet error rate) are read
from the program's round log and checked apart (``compare.accounting``);
the transmission outcome is then alpha_u = [uniform >= PER_u] (Eq. 4).

``dtype=jnp.bfloat16`` is the control, one precision below what the
configuration states: the weights are kept in bfloat16 from the start
and after every step, and the forward and backward passes run in
bfloat16 at default precision; quantization and averaging stay float32.
``half_batch=True`` plants the fault of a device that trains on the
first half of its batch only.

Pruning ranks each leaf's entries by |w| in one stable sort on the
device, once per leaf and round; a device prunes the entries of rank <
floor(rho * n). While the whole cohort's per-device work fits in the
device's memory (``devices_per_step``), the cohort runs in one vmap and
one Eq. 19 sum, with each leaf's ranks handed to the round's step, which
makes every device's masks from them.

Where it does not fit, the per-device work (mask, loss and gradient,
range statistic, upload) runs k devices at a time, the most that fit,
and Eq. 19's weighted sum is kept as a running total over the chunks, so
that the cohort's trees are never held at once. No rank tree is made
then: a device's cut reads, from the sorted leaf, the value t at that
position and its flat index j, and the device prunes every entry below t
and those equal to t before j in flat order, which are the entries of
rank < cut. Each chunk's masks are made in a program of their own and
handed to the chunk's step.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import accounting, traffic
from chipbench.spec import family, module

PyTree = Any


def _stable_sort(w: jax.Array):
    """|w| in ascending order, each entry with its flat index, ties in
    flat order."""
    a = jnp.abs(w.astype(jnp.float32)).reshape(-1)
    return jax.lax.sort((a, jnp.arange(a.size, dtype=jnp.int32)),
                        num_keys=1, is_stable=True)


@jax.jit
def _ranks(w: jax.Array) -> jax.Array:
    """Each entry's position in a stable ascending sort of |w|."""
    _, idx = _stable_sort(w)
    return jnp.zeros(w.size, jnp.int32).at[idx].set(
        jnp.arange(w.size, dtype=jnp.int32)).reshape(w.shape)


@jax.jit
def _boundaries(w: jax.Array, rho: jax.Array):
    """Per device (rho is (U,)), the value t of |w| at the position cut =
    floor(rho * n) of a stable ascending sort, and that entry's flat index
    j; t is +inf where the cut takes every entry."""
    vals, idx = _stable_sort(w)
    cut = jnp.floor(jnp.clip(rho, 0.0, 1.0) * w.size).astype(jnp.int32)
    at = jnp.minimum(cut, w.size - 1)
    return jnp.where(cut < w.size, vals[at], jnp.inf), idx[at]


@jax.jit
def _masks(leaves, t, j):
    """Each pruned leaf's (k, *shape) masks of k devices: an entry is
    kept where |w| > t, or |w| = t at flat index j or after. ``t`` and
    ``j`` hold one (k,) row per leaf, None for a leaf left whole."""
    def leaf(w, tl, jl):
        if tl is None:
            return None
        a = jnp.abs(w.astype(jnp.float32))
        flat = jnp.arange(w.size, dtype=jnp.int32).reshape(w.shape)
        return jax.vmap(lambda tu, ju: (a > tu) | ((a == tu)
                                                  & (flat >= ju)))(tl, jl)
    return [leaf(w, tl, jl) for w, tl, jl in zip(leaves, t, j)]


def _cohort_rows(keys, table, sizes, *, cfg, half_batch):
    """The round's cohort, and each device's batch as rows of the
    training set."""
    dep = cfg["deployment"]
    n_pop, u, b = dep["population"], dep["cohort"], dep["batch_size"]
    cohort = module("samplers", dep["sampler"]).cohort(keys["cohort"],
                                                       n_pop, u)
    draws = jax.random.randint(keys["batch"], (u, b), 0,
                               jnp.maximum(sizes[cohort], 1)[:, None])
    rows = jnp.take_along_axis(table[cohort], draws, axis=1)
    if half_batch:
        rows = rows[:, :b // 2]
    return cohort, rows


def _device(params, masks, idx, d, k, inputs, labels, *, cfg, traffic_cfg,
            dtype, precision):
    """One device's loss, range statistic and upload at its masks."""
    fam = family(cfg["family"])
    scheme = module("schemes", traffic_cfg["scheme"])
    pruned = jax.tree_util.tree_map(
        lambda w, mk: (w * mk).astype(dtype), params, masks)
    loss, g = jax.value_and_grad(fam.loss)(
        pruned, inputs[idx], labels[idx], cfg["model"], precision)
    g = jax.tree_util.tree_map(
        lambda gi, mk: gi.astype(jnp.float32) * mk, g, masks)
    rsq = sum(jnp.square(jnp.max(jnp.abs(x)) - jnp.min(jnp.abs(x)))
              * float(x.size) for x in jax.tree_util.tree_leaves(g))
    g_leaves, g_def = jax.tree_util.tree_flatten(g)
    kk = jax.random.split(k, len(g_leaves))
    g = jax.tree_util.tree_unflatten(g_def, [
        scheme.compress(x, d, ki) for x, ki in zip(g_leaves, kk)])
    return loss, rsq, g


def _round(params, ranks, keys, inputs, labels, table, sizes, rho, delta,
           per, *, cfg, traffic_cfg, dtype, precision, half_batch):
    """One round with the whole cohort in one vmap and one Eq. 19 sum,
    each device's masks made in the step from the leaves' ranks."""
    engine = module("engines", traffic_cfg["engine"])
    u = cfg["deployment"]["cohort"]
    cohort, rows = _cohort_rows(keys, table, sizes, cfg=cfg,
                                half_batch=half_batch)
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def device(idx, r, d, k):
        def mask(w, rank):
            if rank is None:
                return jnp.ones(w.shape, bool)
            cut = jnp.floor(jnp.clip(r, 0.0, 1.0) * w.size).astype(
                jnp.int32)
            return rank >= cut

        masks = jax.tree_util.tree_unflatten(
            treedef, [mask(w, rk) for w, rk in zip(leaves, ranks)])
        return _device(params, masks, idx, d, k, inputs, labels, cfg=cfg,
                       traffic_cfg=traffic_cfg, dtype=dtype,
                       precision=precision)

    dev_keys = jax.random.split(keys["step"], u + 1)[:u]
    losses, rsqs, grads = jax.vmap(device)(rows, rho, delta, dev_keys)
    alpha = (jax.random.uniform(keys["alpha"], (u,)) >= per).astype(
        jnp.float32)
    agg = engine.aggregate(grads, sizes[cohort], alpha)
    params, norms = _update(params, agg, cfg=cfg, dtype=dtype)
    return params, jnp.mean(losses), rsqs, cohort, norms


def _update(params, agg, *, cfg, dtype):
    """Eq. 20's step on the average, and the average's norm per leaf."""
    lr = jnp.float32(cfg["ltfl"]["learning_rate"])
    params = jax.tree_util.tree_map(
        lambda p, g: (p.astype(jnp.float32) - lr * g).astype(dtype),
        params, agg)
    return params, jax.tree_util.tree_map(jnp.linalg.norm, agg)


def _draw(keys, table, sizes, per, *, cfg, traffic_cfg, half_batch):
    """A streamed round's draws: the cohort, the devices' batch rows and
    keys, and their Eq. 19 weights."""
    engine = module("engines", traffic_cfg["engine"])
    u = cfg["deployment"]["cohort"]
    cohort, rows = _cohort_rows(keys, table, sizes, cfg=cfg,
                                half_batch=half_batch)
    alpha = (jax.random.uniform(keys["alpha"], (u,)) >= per).astype(
        jnp.float32)
    return (cohort, rows, jax.random.split(keys["step"], u + 1)[:u],
            engine.weights(sizes[cohort], alpha))


def _chunk(params, summed, rows, masks, delta, keys, wts, inputs, labels,
           **kw):
    """k devices' work at the masks made for them, their weighted
    gradients added to the running Eq. 19 sum."""
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def device(idx, mk, d, k):
        mk = jax.tree_util.tree_unflatten(treedef, [
            jnp.ones(w.shape, bool) if m is None else m
            for w, m in zip(leaves, mk)])
        return _device(params, mk, idx, d, k, inputs, labels, **kw)

    losses, rsqs, grads = jax.vmap(device)(rows, masks, delta, keys)
    summed = jax.tree_util.tree_map(
        lambda s, g: s + jnp.tensordot(wts, g, axes=1), summed, grads)
    return summed, losses, rsqs


def _finish(params, summed, wts, *, cfg, traffic_cfg, dtype):
    engine = module("engines", traffic_cfg["engine"])
    return _update(params, engine.normalize(summed, wts), cfg=cfg,
                   dtype=dtype)


# what one device's work holds at once, in float32 trees of the weights'
# size: its pruned weights, masks, gradient, uniform draws and upload
TREES_PER_DEVICE = 5


def _memory_budget():
    """The bytes the cohort's per-device work may take: half of what the
    device has free, or None where the backend reports no limit (the
    CPU's)."""
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return (stats["bytes_limit"] - stats.get("bytes_in_use", 0)) // 2


def devices_per_step(params, u: int) -> int:
    """How many of the cohort's ``u`` devices the reference runs at a
    time: all of them while their work fits in ``_memory_budget``, else
    the most that fit, and at least one."""
    budget = _memory_budget()
    if budget is None:
        return u
    tree = sum(4 * x.size for x in jax.tree_util.tree_leaves(params))
    return int(max(1, min(u, budget // (TREES_PER_DEVICE * tree))))


def follow(cfg: Dict, traffic_cfg: Dict, seed: int, log: Dict[str, Any], *,
           dtype=jnp.float32, half_batch: bool = False,
           precision: str = None) -> Dict[str, Any]:
    """Run the rounds of the first call from the seed. ``log`` holds the
    program's per-round decisions: ``rho``, ``delta`` and ``pers``, each
    (R, U). Returns the per-round losses (R,), range statistics (R, U),
    cohorts (R, U), each round's channel over its cohort (``channel``: a
    list of dicts of (U,) float64 arrays), the first round's
    averaged-gradient norm per leaf and the weights after the R rounds,
    all as host arrays. ``precision`` ("highest" or "default") overrides
    the matmul precision that ``dtype`` implies."""
    m, dep = cfg["model"], cfg["deployment"]
    fam = family(cfg["family"])
    engine = module("engines", traffic_cfg["engine"])
    prune = module("schemes", traffic_cfg["scheme"]).PRUNES
    u = dep["cohort"]
    train, _ = traffic.samples(seed, cfg)
    sizes, table, registry = traffic.devices_and_shards(
        seed, cfg, traffic.shard_pool(cfg, train))
    # as wide as the program's table, so that every seed shares one program
    table = np.pad(table, ((0, 0), (0, engine.shard_width(cfg)
                                    - table.shape[1])))
    precision = precision or ("highest" if dtype == jnp.float32
                              else "default")
    params = jax.tree_util.tree_map(
        lambda p: p.astype(dtype),
        fam.init_params(m, traffic.weights_key(seed)))
    inputs = jnp.asarray(train[fam.INPUT_KEY])
    labels_dev = jnp.asarray(train["labels"])
    table_dev, sizes_dev = jnp.asarray(table), jnp.asarray(sizes)
    k = devices_per_step(params, u)
    kw = dict(cfg=cfg, traffic_cfg=traffic_cfg, dtype=dtype,
              precision=(jax.lax.Precision.HIGHEST if precision == "highest"
                         else jax.lax.Precision.DEFAULT))
    if k == u:
        step = jax.jit(functools.partial(_round, **kw,
                                         half_batch=half_batch))
    else:
        draw = jax.jit(functools.partial(
            _draw, cfg=cfg, traffic_cfg=traffic_cfg, half_batch=half_batch))
        chunk = jax.jit(functools.partial(_chunk, **kw), donate_argnums=1)
        finish = jax.jit(functools.partial(
            _finish, cfg=cfg, traffic_cfg=traffic_cfg, dtype=dtype))
    key = jax.random.PRNGKey(int(seed))
    n = dep["population"]
    fade = (np.full(n, cfg["wireless"]["fading_scale"]),
            registry["interference"])
    losses, rsqs, cohorts, channels = [], [], [], []
    norms0 = None
    with jax.default_matmul_precision(precision):
        for r in range(traffic_cfg["rounds_per_call"]):
            key, keys = engine.round_keys(key)
            if traffic_cfg["block_fading"]:
                fade = accounting.fading(cfg, keys["fading"], n)
            rho = jnp.asarray(log["rho"][r], jnp.float32)
            delta = jnp.asarray(log["delta"][r], jnp.float32)
            per = jnp.asarray(log["pers"][r], jnp.float32)
            leaves = jax.tree_util.tree_leaves(params)
            pruned = [prune and w.ndim >= 2 for w in leaves]
            if k == u:
                ranks = [_ranks(w) if p else None
                         for w, p in zip(leaves, pruned)]
                params, loss, rsq, cohort, norms = step(
                    params, ranks, keys, inputs, labels_dev, table_dev,
                    sizes_dev, rho, delta, per)
            else:
                cuts = [_boundaries(w, rho) if p else (None, None)
                        for w, p in zip(leaves, pruned)]
                cohort, rows, dev_keys, wts = draw(keys, table_dev,
                                                   sizes_dev, per)
                summed = jax.tree_util.tree_map(
                    lambda w: jnp.zeros(w.shape, jnp.float32), params)
                parts = []
                for lo in range(0, u, k):
                    hi = min(lo + k, u)
                    masks = _masks(leaves, *[
                        [None if x is None else x[lo:hi] for x in c]
                        for c in zip(*cuts)])
                    summed, *out = chunk(
                        params, summed, rows[lo:hi], masks, delta[lo:hi],
                        dev_keys[lo:hi], wts[lo:hi], inputs, labels_dev)
                    parts.append(out)
                params, norms = finish(params, summed, wts)
                loss = jnp.mean(jnp.concatenate([p[0] for p in parts]))
                rsq = jnp.concatenate([p[1] for p in parts])
            cohort = np.asarray(cohort)
            losses.append(float(loss))
            rsqs.append(np.asarray(rsq, np.float64))
            cohorts.append(cohort)
            channels.append({"distance": registry["distance"][cohort],
                             "cpu": registry["cpu"][cohort],
                             "samples": registry["samples"][cohort],
                             "fading": fade[0][cohort],
                             "interference": fade[1][cohort]})
            if norms0 is None:
                norms0 = jax.tree_util.tree_map(
                    lambda x: float(x), jax.device_get(norms))
    return {"loss": np.asarray(losses), "range_sq": np.stack(rsqs),
            "cohort": np.stack(cohorts), "channel": channels,
            "grad_norm0": norms0, "params": jax.device_get(params)}
