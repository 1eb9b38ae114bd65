"""The synchronous scanned round engine: ``ScanRunner`` with its random
draws, cohort draw and Algorithm 1 inside the compiled segment.

A traffic file names its engine (``"engine": "scan"``). This module
builds the program's runner for a cell, drives its first call, and gives
the reference the engine's semantics: the per-round key layout and the
aggregation of the received gradients (Eq. 19).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.spec import module


def build(cell, seed: int, params, train, test):
    """The program's device-resident ScanRunner for this cell."""
    from repro.configs.base import LTFLConfig, WirelessConfig
    from repro.data import ArrayDataset
    from repro.fed import ScanRunner
    cfg, t = cell.config, cell.traffic
    dep = cfg["deployment"]
    n, u = dep["population"], dep["cohort"]
    ltfl = LTFLConfig(num_devices=u, seed=int(seed),
                      wireless=WirelessConfig(**cfg["wireless"]),
                      **cfg["ltfl"])
    scheme = module("schemes", t["scheme"]).program(t)
    sampler = module("samplers", dep["sampler"]).program()
    fam = module("families", cfg["family"])
    model = fam.program_model(cfg["model"])
    partial = n != u
    runner = ScanRunner(
        model, params, ltfl, ArrayDataset(train), ArrayDataset(test), scheme,
        batch_size=dep["batch_size"], non_iid_alpha=dep["non_iid_alpha"],
        label_key=fam.PARTITION_KEY or "labels",
        seed=int(seed), eval_every=t["eval_every"],
        use_kernels=t["use_kernels"], block_fading=t["block_fading"],
        population_size=n if partial else None,
        cohort_size=u if partial else None,
        cohort_sampler=sampler, rng="device", control="device")
    # the shard table as wide as the largest shard the configuration
    # allows, not the largest this seed drew: every seed then runs the
    # same compiled segment, which the persistent cache keeps
    runner._ensure_device_world(pad_to=shard_width(cfg))
    return runner


def shard_width(cfg: Dict) -> int:
    """Columns of the padded shard table: the largest shard size."""
    return int(cfg["ltfl"]["samples_max"])


def first_call(runner, rounds: int) -> Dict[str, Any]:
    """Run the first call, keeping what the engine logs for each round
    of it: the per-device decisions, packet error rates and range
    statistics, the round's delay, energy and mean power, and Gamma."""
    seen: Dict[str, Any] = {}
    absorb = runner._absorb_segment

    def keep(a, b, ctl, carry, rlog):
        seen["log"] = jax.device_get(rlog)
        return absorb(a, b, ctl, carry, rlog)

    runner._absorb_segment = keep
    try:
        history = runner.run(rounds)
    finally:
        del runner._absorb_segment
    jax.block_until_ready(runner.params)
    rlog, done = seen["log"], history[:rounds]

    def f64(x):
        return np.asarray(x, np.float64)

    return {"loss": np.array([r.train_loss for r in done]),
            "gamma": np.array([r.gamma for r in done]),
            "rho": f64(rlog.rho_u), "delta": f64(rlog.gap_delta),
            "pers": f64(rlog.pers), "range_sq": f64(rlog.range_sq),
            "samples": f64(rlog.ns_u), "cohort": np.asarray(rlog.cohort),
            "delay": f64(rlog.delay), "energy": f64(rlog.energy),
            "power_mean": f64(rlog.power_mean)}


def round_keys(key: jax.Array):
    """The engine's split of the carried key at each round: the next
    carry, then the fading, cohort, batch, transmission, step and control
    keys."""
    key, k_fade, k_cohort, k_batch, k_alpha, k_step, _ = \
        jax.random.split(key, 7)
    return key, {"fading": k_fade, "cohort": k_cohort, "batch": k_batch,
                 "alpha": k_alpha, "step": k_step}


def weights(samples: jax.Array, alpha: jax.Array) -> jax.Array:
    """Eq. 19's weight of each device: N_u where its upload was
    received, else 0."""
    return samples.astype(jnp.float32) * alpha


def normalize(summed, wts: jax.Array, term=lambda s: s):
    """Eq. 19's weighted sum of the gradients, ``term`` of each leaf of
    ``summed``, over the total weight; nothing received averages to
    zero."""
    total = jnp.sum(wts)
    return jax.tree_util.tree_map(
        lambda s: jnp.where(total > 0, term(s) / jnp.maximum(total, 1e-12),
                            0.0), summed)


def aggregate(grads, samples: jax.Array, alpha: jax.Array):
    """Eq. 19: the received devices' gradients averaged with weights
    N_u (a cohort streamed in chunks adds up the weighted gradients
    itself and ends with ``normalize``)."""
    wts = weights(samples, alpha)
    return normalize(grads, wts, lambda g: jnp.tensordot(wts, g, axes=1))
