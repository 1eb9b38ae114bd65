#!/usr/bin/env python3
"""The readings a cell's limits are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--witness] [--detail]
    python3 chipbench/calibrate.py --masks --seeds 1 [--rounds 3]

For each seed: set-up and the first call as a run makes them, then the
numbers of ``compare`` for the program against the reference. For each
control seed also the training numbers for the control (the reference
in bfloat16, one precision below the configuration's) and for a planted
fault (every device trains on half of its batch), and with ``--witness``
for the reference at the configuration's own default matmul precision,
each against the reference. A step that leaves the weights unchanged
reads 1 on ``update_gap`` by construction and needs no run. With
``--detail`` each number also comes round by round and leaf by leaf. One
JSON line per seed.

``--masks`` times the reference's pruning masks, and reads the device's
peak memory, for a tree of float32 weights at the leaf shapes of one
chip's share of DeepSeek-V2-Lite (``share_shapes``: 535,035,904 entries)
and a cohort of 30: per round the stable sort of every leaf, then the
masks of k devices at a time, k = 1 and then 2, each chunk's uploads
stood in for by the masked weights times a uniform draw, added to a
running Eq. 19 sum. One JSON line per round and k, then the peaks.
Benchmark runs never run this.
"""
import os
import sys
import time


def _detail(run, ref, params0) -> dict:
    """Where each training number reads high: its gap round by round
    (the worst device for the range statistic), and the three worst
    leaves of the weight change with their sizes."""
    import jax
    import numpy as np
    loss = np.abs(run["loss"] - ref["loss"]) / np.abs(ref["loss"])
    rsq = np.abs(np.asarray(run["range_sq"]) - ref["range_sq"]) \
        / np.maximum(np.abs(ref["range_sq"]), 1e-30)
    leaves = [np.asarray(x, np.float64)
              for x in jax.tree_util.tree_leaves(params0)]
    d_run = np.array([np.linalg.norm(np.asarray(a, np.float64) - b) for a, b
                      in zip(jax.tree_util.tree_leaves(run["params"]),
                             leaves)])
    d_ref = np.array([np.linalg.norm(np.asarray(a, np.float64) - b) for a, b
                      in zip(jax.tree_util.tree_leaves(ref["params"]),
                             leaves)])
    g0 = np.asarray(jax.tree_util.tree_leaves(ref["grad_norm0"]), np.float64)
    keep = g0 >= 1e-3 * np.median(g0)
    upd = np.where(keep, np.abs(d_run - d_ref) / np.maximum(
        d_ref, np.median(d_ref[keep])), 0.0)
    worst = np.argsort(upd)[::-1][:3]
    tot = np.asarray(run["range_sq"]).sum(axis=1)
    tot_ref = ref["range_sq"].sum(axis=1)
    signed = (np.asarray(run["range_sq"]) - ref["range_sq"]) / ref["range_sq"]
    return {"loss_by_round": loss.tolist(),
            "range_cohort_signed": ((tot - tot_ref) / tot_ref).tolist(),
            "range_median_signed": np.median(signed, axis=1).tolist(),
            "range_by_round": rsq.max(axis=1).tolist(),
            "range_median_by_round": np.median(rsq, axis=1).tolist(),
            "update_median": float(np.median(upd[keep])),
            "update_worst": [[int(i), int(leaves[i].size), float(upd[i]),
                              float(d_ref[i]), float(d_run[i] - d_ref[i])]
                             for i in worst]}


def _control_detail(cfg, first, ref) -> dict:
    """The round's pruning ratios, and the power gap round by round with
    the packet error rate of the device that reads worst."""
    import numpy as np

    from chipbench import accounting
    out = {"rho_mean": np.mean(first["rho"], axis=1).tolist(),
           "rho_max": np.max(first["rho"], axis=1).tolist(),
           "power_by_round": [], "power_worst_per": []}
    for r, ch in enumerate(ref["channel"]):
        p = accounting.power_from_per(cfg["wireless"], ch, first["pers"][r])
        out["power_by_round"].append(float(
            abs(first["power_mean"][r] - np.mean(p)) / np.mean(p)))
        out["power_worst_per"].append([float(first["pers"][r].min()),
                                       float(first["pers"][r].max())])
    return out


def readings(cell, seed: int, variants=("program",),
             detailed: bool = False) -> dict:
    """The compared numbers for each of ``variants`` ("program",
    "control", "half_batch", and "default_precision": the reference in
    float32 at the TPU's default matmul precision, the configuration's
    own) against the reference, on one seed; with ``detailed``, also
    where each reads high."""
    import gc

    import jax
    import jax.numpy as jnp

    from chipbench import compare, reference, traffic
    from chipbench.spec import family, module

    cfg, t = cell.config, cell.traffic
    fam = family(cfg["family"])
    engine = module("engines", t["engine"])
    params0 = fam.init_params(cfg["model"], traffic.weights_key(seed))
    train, test = traffic.samples(seed, cfg)
    runner = engine.build(cell, seed, params0, train, test)
    first = engine.first_call(runner, t["rounds_per_call"])
    first["params"] = jax.device_get(runner.params)
    del runner, train, test
    gc.collect()
    params0 = jax.device_get(params0)
    ref = reference.follow(cfg, t, seed, first)
    out = {"seed": seed,
           "cohorts_agree": bool((first["cohort"] == ref["cohort"]).all())}
    for v in variants:
        if v == "program":
            other = first
        else:
            other = reference.follow(
                cfg, t, seed, first,
                dtype=jnp.bfloat16 if v == "control" else jnp.float32,
                half_batch=v == "half_batch",
                precision="default" if v == "default_precision" else None)
        out[v] = compare.gaps(other, ref, params0)
        if detailed:
            out[v]["detail"] = _detail(other, ref, params0)
    out["program"].update(compare.accounting_gaps(cfg, t, first, ref))
    if detailed:
        out["program"]["control_detail"] = _control_detail(cfg, first, ref)
    return out


def share_shapes() -> list:
    """The leaves of two or more dimensions of one chip's share of
    DeepSeek-V2-Lite (expert parallelism over 8 chips): the first, dense
    layer and 4 MoE layers, each with MLA attention (hidden 2048, 16
    heads; q 3072, the latent 512 and rope 64, keys and values 4096,
    output); 8 of the 64 routed experts and the 2 shared ones at width
    1408, and the router over all 64, in each MoE layer; the dense MLP at
    10944; an eighth of the vocabulary (12,800 rows) for the embedding and
    the untied head."""
    h, e, i, v = 2048, 1408, 10944, 12800
    mla = [(h, 3072), (h, 576), (512, 4096), (2048, h)]
    moe = [(8, h, e), (8, h, e), (8, e, h), (2, h, e), (2, h, e),
           (2, e, h), (h, 64)] + mla
    return mla + [(h, i), (h, i), (i, h)] + 4 * moe + [(v, h), (h, v)]


def mask_rounds(seed: int, rounds: int, ks=(1, 2), u: int = 30,
                rho_max: float = 0.5):
    """Yield, for each k and round, the seconds of the sorts, of the
    masks and of the whole round, then the peaks (``--masks``)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference
    from chipbench.engines.scan import weights

    shapes = share_shapes()

    @jax.jit
    def make(key):
        return [jax.random.normal(k, s, jnp.float32) for k, s in
                zip(jax.random.split(key, len(shapes)), shapes)]

    @functools.partial(jax.jit, donate_argnums=1)
    def stand_in(leaves, summed, masks, wts, key):
        keys = jax.random.split(key, len(leaves))
        return [s + jnp.tensordot(wts, (w * mk) * jax.random.uniform(
            kk, mk.shape), axes=1)
            for w, s, mk, kk in zip(leaves, summed, masks, keys)]

    leaves = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
    tree = 4 * sum(w.size for w in leaves)
    stats = jax.devices()[0].memory_stats() or {}
    yield {"entries": tree // 4, "leaves": len(leaves), "tree_bytes": tree,
           "bytes_limit": stats.get("bytes_limit"),
           "bytes_in_use": stats.get("bytes_in_use"),
           "devices_per_step": reference.devices_per_step(leaves, u),
           "trees_per_device": reference.TREES_PER_DEVICE}
    rng = np.random.default_rng([seed, 17])
    peaks = {}
    for k in ks:
        for r in range(rounds):
            rho = jnp.asarray(rng.uniform(0.0, rho_max, u), jnp.float32)
            wts = weights(jnp.asarray(rng.integers(400, 601, u)),
                          jnp.asarray(rng.uniform(size=u) >= 0.1,
                                      jnp.float32))
            t0 = time.perf_counter()
            cuts = jax.block_until_ready(
                [reference._boundaries(w, rho) for w in leaves])
            t1 = time.perf_counter()
            summed = [jnp.zeros(w.shape, jnp.float32) for w in leaves]
            masks_s = 0.0
            for lo in range(0, u, k):
                t2 = time.perf_counter()
                mk = jax.block_until_ready(reference._masks(
                    leaves, [c[0][lo:lo + k] for c in cuts],
                    [c[1][lo:lo + k] for c in cuts]))
                masks_s += time.perf_counter() - t2
                summed = stand_in(leaves, summed, mk, wts[lo:lo + k],
                                  jax.random.PRNGKey(r * u + lo))
                del mk
            jax.block_until_ready(summed)
            yield {"k": k, "round": r, "sort_s": t1 - t0,
                   "masks_s": masks_s,
                   "round_s": time.perf_counter() - t0}
            del summed, cuts
        peaks[k] = (jax.devices()[0].memory_stats()
                    or {}).get("peak_bytes_in_use")
    yield {"peak_bytes": peaks}


def main() -> None:
    import argparse
    import json

    import jax

    from chipbench.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--masks", action="store_true",
                    help="time the masks of a 535 M-entry tree instead")
    ap.add_argument("--rounds", type=int, default=3,
                    help="with --masks, rounds at each k")
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--witness", action="store_true",
                    help="on the control seeds, also the reference at the "
                         "configuration's default matmul precision")
    ap.add_argument("--detail", action="store_true",
                    help="also print where each number reads high")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: JAX found no TPU")
    enable_compile_cache()
    if args.masks:
        for line in mask_rounds(args.seeds[0], args.rounds):
            print(json.dumps(line), flush=True)
        return
    if not args.workload:
        ap.error("--workload is required without --masks")
    cell = load_cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        variants = ("program", "control", "half_batch") \
            if seed in args.control_seeds else ("program",)
        if args.witness and seed in args.control_seeds:
            variants += ("default_precision",)
        r = readings(cell, seed, variants, detailed=args.detail)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps({"workload": args.workload, **r}), flush=True)


if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    main()
