#!/usr/bin/env python3
"""The readings a cell's limits are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--witness] [--detail]

For each seed: set-up and the first call as a run makes them, then the
numbers of ``compare`` for the program against the reference. For each
control seed also the training numbers for the control (the reference
in bfloat16, one precision below the configuration's) and for a planted
fault (every device trains on half of its batch), and with ``--witness``
for the reference at the configuration's own default matmul precision,
each against the reference. A step that leaves the weights unchanged
reads 1 on ``update_gap`` by construction and needs no run. With
``--detail`` each number also comes round by round and leaf by leaf. One
JSON line per seed. Benchmark runs never run this.
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
    train, test = traffic.dataset(seed, cfg)
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


def main() -> None:
    import argparse
    import json

    import jax

    from chipbench.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
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
