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


def _ranks(w: np.ndarray) -> np.ndarray:
    """Position of each entry of |w| in a stable ascending sort (on the
    host: a sort in the round's program would cost the TPU compiler
    minutes)."""
    flat = np.abs(w).reshape(-1)
    rank = np.empty(flat.size, np.int32)
    rank[np.argsort(flat, kind="stable")] = np.arange(flat.size,
                                                       dtype=np.int32)
    return rank.reshape(w.shape)


def _round(params, ranks, keys, images, labels, table, sizes, rho, delta,
           per, *, cfg, traffic_cfg, dtype, precision, half_batch):
    m, dep = cfg["model"], cfg["deployment"]
    fam = family(cfg["family"])
    engine = module("engines", traffic_cfg["engine"])
    scheme = module("schemes", traffic_cfg["scheme"])
    n_pop, u, b = dep["population"], dep["cohort"], dep["batch_size"]
    cohort = module("samplers", dep["sampler"]).cohort(keys["cohort"],
                                                       n_pop, u)
    draws = jax.random.randint(keys["batch"], (u, b), 0,
                               jnp.maximum(sizes[cohort], 1)[:, None])
    rows = jnp.take_along_axis(table[cohort], draws, axis=1)
    if half_batch:
        rows = rows[:, :b // 2]
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
        pruned = jax.tree_util.tree_map(
            lambda w, mk: (w * mk).astype(dtype), params, masks)
        loss, g = jax.value_and_grad(fam.loss)(
            pruned, images[idx], labels[idx], m, precision)
        g = jax.tree_util.tree_map(
            lambda gi, mk: gi.astype(jnp.float32) * mk, g, masks)
        rsq = sum(jnp.square(jnp.max(jnp.abs(x)) - jnp.min(jnp.abs(x)))
                  * float(x.size) for x in jax.tree_util.tree_leaves(g))
        g_leaves, g_def = jax.tree_util.tree_flatten(g)
        kk = jax.random.split(k, len(g_leaves))
        g = jax.tree_util.tree_unflatten(g_def, [
            scheme.compress(x, d, ki) for x, ki in zip(g_leaves, kk)])
        return loss, rsq, g

    dev_keys = jax.random.split(keys["step"], u + 1)[:u]
    losses, rsqs, grads = jax.vmap(device)(rows, rho, delta, dev_keys)
    alpha = (jax.random.uniform(keys["alpha"], (u,)) >= per).astype(
        jnp.float32)
    agg = engine.aggregate(grads, sizes[cohort], alpha)
    lr = jnp.float32(cfg["ltfl"]["learning_rate"])
    params = jax.tree_util.tree_map(
        lambda p, g: (p.astype(jnp.float32) - lr * g).astype(dtype),
        params, agg)
    norms = jax.tree_util.tree_map(jnp.linalg.norm, agg)
    return params, jnp.mean(losses), rsqs, cohort, norms


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
    train, _ = traffic.dataset(seed, cfg)
    sizes, table, registry = traffic.devices_and_shards(
        seed, cfg, train["labels"])
    # as wide as the program's table, so that every seed shares one program
    table = np.pad(table, ((0, 0), (0, engine.shard_width(cfg)
                                    - table.shape[1])))
    precision = precision or ("highest" if dtype == jnp.float32
                              else "default")
    params = jax.tree_util.tree_map(
        lambda p: p.astype(dtype),
        fam.init_params(m, traffic.weights_key(seed)))
    step = jax.jit(functools.partial(
        _round, cfg=cfg, traffic_cfg=traffic_cfg, dtype=dtype,
        precision=(jax.lax.Precision.HIGHEST if precision == "highest"
                   else jax.lax.Precision.DEFAULT), half_batch=half_batch))
    images = train["images"]
    labels_dev = jnp.asarray(train["labels"])
    table_dev, sizes_dev = jnp.asarray(table), jnp.asarray(sizes)
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
            ranks = [_ranks(np.asarray(w, np.float32))
                     if (prune and w.ndim >= 2) else None
                     for w in jax.tree_util.tree_leaves(
                         jax.device_get(params))]
            params, loss, rsq, cohort, norms = step(
                params, ranks, keys, images, labels_dev, table_dev,
                sizes_dev, jnp.asarray(log["rho"][r], jnp.float32),
                jnp.asarray(log["delta"][r], jnp.float32),
                jnp.asarray(log["pers"][r], jnp.float32))
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
