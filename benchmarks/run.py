"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Default budget is CPU-friendly
(few rounds per figure); pass --full for the EXPERIMENTS.md-scale runs.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="EXPERIMENTS.md-scale rounds (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma list: ablation,schemes,channel,devices,"
                         "noniid,controller,kernels,roofline,population,"
                         "scan,asyncengine,devicecontrol,papertable")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    rounds = 24 if args.full else 10

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        ablation,
        async_engine,
        channel_sweep,
        controller_bench,
        device_control,
        device_count,
        kernels_bench,
        non_iid,
        paper_table,
        population_scale,
        roofline,
        scan_engine,
        schemes,
    )

    print("name,us_per_call,derived")
    if only is None or "kernels" in only:
        kernels_bench.run()
    if only is None or "scan" in only:
        # only a --full run may rewrite the committed scan_engine.json
        # baseline that check_regression gates on
        scan_engine.run(
            client_counts=(8, 16, 32) if args.full else (16,),
            round_counts=(16, 64),
            artifact=("scan_engine" if args.full else "scan_engine_reduced"))
    if only is None or "asyncengine" in only:
        # only a --full run may rewrite the committed async_engine.json
        # baseline that check_regression gates on; the metric (simulated
        # time-to-accuracy) is deterministic, so the reduced run keeps
        # the full round budgets and just drops the U=32 row
        async_engine.run(
            client_counts=(16, 32) if args.full else (16,),
            artifact=("async_engine" if args.full
                      else "async_engine_reduced"))
    if only is None or "devicecontrol" in only:
        # only a --full run may rewrite the committed device_control.json
        # baseline that check_regression gates on
        device_control.run(
            client_counts=(8, 16, 32) if args.full else (16,),
            artifact=("device_control" if args.full
                      else "device_control_reduced"))
    if only is None or "papertable" in only:
        # only a --full run may rewrite the committed paper_table.json
        # baseline that check_regression gates on; the reduced run uses
        # the CI smoke grid under the anti-clobber artifact name
        paper_table.run(
            smoke=not args.full,
            rounds=12 if args.full else 6,
            artifact=("paper_table" if args.full
                      else "paper_table_reduced"))
    if only is None or "controller" in only:
        controller_bench.run(
            device_counts=(16, 32, 64) if args.full else (16,))
    if only is None or "ablation" in only:
        ablation.run(rounds=rounds)
    if only is None or "schemes" in only:
        schemes.run(rounds=rounds)
    if only is None or "channel" in only:
        channel_sweep.run(rounds=max(rounds // 2, 3))
        channel_sweep.run_block_fading(rounds=max(rounds // 2, 3))
    if only is None or "devices" in only:
        device_count.run(rounds=max(rounds // 2, 3))
    if only is None or "population" in only:
        # only a --full run (the whole N sweep) may rewrite the committed
        # population_scale.json baseline; the reduced sweep writes its
        # own artifact (same anti-clobber convention as the bench smokes)
        population_scale.run(
            pop_sizes=(64, 256, 1024, 4096) if args.full
            else (64, 256, 1024),
            rounds=max(rounds // 2, 3),
            artifact=("population_scale" if args.full
                      else "population_scale_reduced"))
    if only is None or "noniid" in only:
        non_iid.run(rounds=max(rounds // 2, 3))
    if only is None or "roofline" in only:
        roofline.run()


if __name__ == "__main__":
    main()
