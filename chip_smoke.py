#!/usr/bin/env python3
"""Start the federated round engine on a TPU at the paper's ResNet width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded device registry

One chip: two rounds of the ``--edge`` CLI path (``FedRunner``), then one
4-round segment of the fully device-resident ``ScanRunner`` (in-scan
Algorithm 1, cohort draw, prune -> grad -> quantize -> aggregate) with the
Pallas kernels compiled, checked against the same segment on the jnp path.
``--four-chips`` runs only the sharded registry: N = 10^6 devices over a
("pop",) mesh of four chips, against the same run on one shard.

The model is the paper's Section-6 pre-activation ResNet (64-channel stem,
groups 64/128/256/512) with 30 devices, per-device batch 50 and the
Table 2 wireless parameters; weights and CIFAR-shaped data are random,
from fixed seeds. Every check asserts. Without a TPU the script exits
non-zero before any result. The last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DEVICES = 30            # LTFLConfig().num_devices
WIDTH = 64              # ResNetConfig(): stem 64, groups (64, 128, 256, 512)
BATCH = 50              # PaperExperimentConfig.batch_size
TRAIN_SAMPLES = 18000   # covers 30 shards of up to samples_max = 600
ROUNDS = 4              # rounds in the scanned segment
POPULATION = 10 ** 6    # registry size of the four-chip phase
# The compiled quantizer may differ from the jnp one only where a last-ulp
# difference moves a coordinate across a stochastic rounding threshold: by
# one level, at no more than this share of the entries (the bound of
# tests/test_kernels.py). A difference above 1e-3 of a level counts as a
# move: the ulps of a dequantized value stay below 3e-5 of a level at
# 8 bits (255 levels x 1.2e-7), and an unquantized upload misses it only
# within 1e-3 of a level, so at about 0.2% of the entries.
FLIP_SHARE = 1e-3
MOVE = 1e-3
# Rounds after the first train on uploads that went through the two
# quantizers, so their losses may part by the flips above; the quantizer
# itself is held to FLIP_SHARE, and this coarser limit only checks that
# the segment built around it agrees end to end.
LATER_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit("chip_smoke: JAX found no TPU; there is no CPU fallback")
    return dev, len(devices)


class CacheEvents:
    """Counts persistent compile-cache hits and misses in this process."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def check_finite(history, what: str) -> None:
    for rec in history:
        for field in ("train_loss", "delay", "energy"):
            value = getattr(rec, field)
            assert math.isfinite(value), (what, rec.round, field, value)


def scan_runner(**kwargs):
    """The Section-6 ResNet on the device-resident ScanRunner with in-scan
    Algorithm 1 every round."""
    from repro.fed import LTFLScheme, ScanRunner
    from repro.launch.train import edge_runner
    return edge_runner(scheme=LTFLScheme(recontrol_every=1),
                       devices=DEVICES, train_samples=TRAIN_SAMPLES,
                       batch_size=BATCH, width=WIDTH, engine=ScanRunner,
                       rng="device", control="device", **kwargs)


def cli_phase() -> None:
    """Two rounds of ``python -m repro.launch.train --edge``."""
    from repro.launch.train import edge_runner
    runner = edge_runner(devices=DEVICES, train_samples=TRAIN_SAMPLES,
                         batch_size=BATCH, width=WIDTH)
    log(f"cli: ResNet width {WIDTH}, {runner.num_params} parameters, "
        f"{DEVICES} devices, batch {BATCH}")
    t0 = time.perf_counter()
    history = runner.run(2, log_every=1)
    assert len(history) == 2, len(history)
    check_finite(history, "cli")
    assert all(math.isfinite(r.test_acc) for r in history), history
    log(f"cli: 2 rounds in {time.perf_counter() - t0:.2f} s, compiles "
        "included (smoke timing, not a benchmark)")


def flip_share(q, ref, g, bits) -> tuple:
    """Share of the entries of ``q`` that differ from ``ref`` by a level
    move, and the largest difference in levels, for per-client leaves
    ``g`` (clients first) quantized at per-client ``bits``."""
    import numpy as np
    q, ref, g = (np.asarray(x, np.float64) for x in (q, ref, g))
    a = np.abs(g.reshape(len(g), -1))
    levels = np.maximum(np.round(2.0 ** np.maximum(bits, 1.0)) - 1.0, 1.0)
    scale = (a.max(axis=1) - a.min(axis=1)) / levels
    scale = np.where(scale > 0, scale, 1.0)
    diff = np.abs(q - ref).reshape(len(g), -1) / scale[:, None]
    return float(np.mean(diff > MOVE)), float(diff.max())


def quantizer_phase(params) -> None:
    """The compiled quantizer against the jnp one on the same random bits,
    at the ResNet's leaf shapes under the 30-client vmap of the round
    step; then a planted fault (uploads left unquantized) must fail the
    same limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.compressors import ltfl_quantizer
    from repro.kernels.ops import kernel_quant_block

    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves) + 1)
    grads = jax.tree_util.tree_unflatten(treedef, [
        1e-2 * jax.random.normal(k, (DEVICES,) + x.shape, x.dtype)
        for x, k in zip(leaves, keys[1:])])
    bits = 1.0 + jnp.arange(DEVICES, dtype=jnp.float32) % 8   # 1..8 bits
    client_keys = jax.random.split(keys[0], DEVICES)

    def upload(use_kernels):
        compress = ltfl_quantizer(use_kernels=use_kernels).compress
        return jax.jit(jax.vmap(lambda g, d, k: compress(g, d, k, ())[0]))

    kern = upload(True)
    hlo = kern.lower(grads, bits, client_keys).compile().as_text()
    assert "tpu_custom_call" in hlo, "the quantizer kernel did not compile"
    q_kern = kern(grads, bits, client_keys)
    q_ref = upload(False)(grads, bits, client_keys)
    share = worst = 0.0
    plant_share = 1.0
    for qk, qr, g in zip(jax.tree_util.tree_leaves(q_kern),
                         jax.tree_util.tree_leaves(q_ref),
                         jax.tree_util.tree_leaves(grads)):
        s, w = flip_share(qk, qr, g, np.asarray(bits))
        share, worst = max(share, s), max(worst, w)
        if kernel_quant_block(g.shape[1:]) is not None:
            plant_share = min(plant_share,
                              flip_share(g, qr, g, np.asarray(bits))[0])
    log(f"quant: {hlo.count('tpu_custom_call')} kernel sites; compiled vs "
        f"jnp level moves: worst leaf share {share:.3g} (limit "
        f"{FLIP_SHARE:g}), largest {worst:.6g} levels (limit 1); planted "
        f"unquantized upload: smallest leaf share {plant_share:.3g}")
    assert share <= FLIP_SHARE and worst <= 1.0 + 1e-3, (share, worst)
    assert plant_share > FLIP_SHARE, plant_share


def hot_phase(dev) -> None:
    """One scanned segment with compiled kernels, against the jnp path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.pruning import prune_pytree

    kern = scan_runner(use_kernels=True)
    params0 = kern.params
    t0 = time.perf_counter()
    compiled = kern.lower_segment(ROUNDS).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "the Pallas kernels did not compile"
    log(f"hot: segment of {ROUNDS} rounds compiled in {compile_s:.2f} s; "
        f"{hlo.count('tpu_custom_call')} tpu_custom_call sites")
    log(f"hot: memory_analysis {compiled.memory_analysis()}")

    t0 = time.perf_counter()
    history = list(kern.run(ROUNDS))
    jax.block_until_ready(kern.params)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    kern.run(ROUNDS)
    jax.block_until_ready(kern.params)
    steady_s = time.perf_counter() - t0
    log(f"hot: first segment {first_s:.3f} s (program from the cache); "
        f"steady {steady_s / ROUNDS:.4f} s/round "
        "(smoke timing, not a benchmark)")
    check_finite(kern.history, "hot")
    for leaf in jax.tree_util.tree_leaves(kern.params):
        assert leaf.devices() == {dev}, leaf.devices()

    ref = scan_runner(use_kernels=False)
    ref_history = ref.run(ROUNDS)
    check_finite(ref_history, "hot/jnp")
    first, first_ref = history[0], ref_history[0]
    assert rel(first.train_loss, first_ref.train_loss) <= 1e-5, (
        first.train_loss, first_ref.train_loss)
    for field in ("rho_mean", "delta_mean", "power_mean"):
        assert getattr(first, field) == getattr(first_ref, field), field
    worst = max(rel(a.train_loss, b.train_loss)
                for a, b in zip(history[1:], ref_history[1:]))
    log("hot: train loss kernels vs jnp: "
        + " ".join(f"{a.train_loss:.6f}/{b.train_loss:.6f}"
                   for a, b in zip(history, ref_history))
        + f"; rounds 2+ worst rel {worst:.3g} (limit {LATER_RTOL:g})")
    assert worst <= LATER_RTOL, worst

    quantizer_phase(params0)

    # the block-prune kernels (the datacenter step's pruning) on the same
    # weights: masks must match the jnp path exactly, client by client
    rho = jnp.linspace(0.0, 0.9, DEVICES)

    def prune(use_kernels):
        return jax.jit(jax.vmap(lambda p, r: prune_pytree(
            p, r, use_kernels=use_kernels), in_axes=(None, 0)))

    prune_k = prune(True)
    assert "tpu_custom_call" in prune_k.lower(params0, rho).compile(
    ).as_text()
    pruned_k, masks_k = prune_k(params0, rho)
    pruned_j, masks_j = prune(False)(params0, rho)
    for a, b in zip(jax.tree_util.tree_leaves(masks_k),
                    jax.tree_util.tree_leaves(masks_j)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(pruned_k),
                    jax.tree_util.tree_leaves(pruned_j)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    log(f"hot: block-prune masks of {DEVICES} clients identical to the "
        "jnp path")
    stats = dev.memory_stats() or {}
    log(f"hot: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def four_chip_phase() -> None:
    """The sharded registry on four chips against one shard."""
    import jax
    import numpy as np
    from repro.fed import ChannelAwareSampler

    runs = {}
    for s in (4, 1):
        t0 = time.perf_counter()
        runner = scan_runner(
            use_kernels=True, eval_every=0, population_size=POPULATION,
            cohort_size=DEVICES, cohort_sampler=ChannelAwareSampler(),
            block_fading=True, population_sharding=s,
            population_dtype=np.float32)
        history = runner.run(ROUNDS)
        jax.block_until_ready(runner.params)
        check_finite(history, f"S={s}")
        table = runner._parts_padded
        per_chip = max(x.data.nbytes for x in table.addressable_shards)
        log(f"four: S={s} N={POPULATION} parts table {table.shape} "
            f"{table.nbytes} bytes, {per_chip} bytes per chip; "
            f"{ROUNDS} rounds in {time.perf_counter() - t0:.2f} s, setup "
            "and compile included (smoke timing, not a benchmark)")
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        log(f"four: peak_bytes_in_use per chip so far {peaks}")
        runs[s] = history
        del runner, table
        gc.collect()             # free this layout's arrays before the next
    for a, b in zip(runs[4], runs[1]):
        assert a.cohort == b.cohort, (a.round, a.cohort, b.cohort)
        assert rel(a.train_loss, b.train_loss) <= 1e-6, (
            a.round, a.train_loss, b.train_loss)
    log("four: S=4 and S=1 drew identical cohorts; losses "
        + " ".join(f"{a.train_loss:.6f}/{b.train_loss:.6f}"
                   for a, b in zip(runs[4], runs[1])))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded registry over four chips "
                         "and its one-shard comparison")
    args = ap.parse_args()

    dev, count = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    log(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        assert count == 4, f"--four-chips needs 4 chips, found {count}"
        four_chip_phase()
    else:
        cli_phase()
        hot_phase(dev)
    log(f"total {time.perf_counter() - t0:.1f} s; compile cache hits "
        f"{cache.hits}, misses {cache.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
