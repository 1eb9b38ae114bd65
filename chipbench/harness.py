"""One run of one cell: set-up, the measured window, the check.

Set-up makes the weights and the data from the seed, builds the
program's runner once (``engines/<engine>.py``), and drives its first call of
``rounds_per_call`` rounds: that call compiles the segment (or loads it
from the persistent cache), runs the in-scan eval head, and its rounds
are the ones the reference follows. The window then calls ``run`` on the
same runner until ``--seconds`` have passed. With ``--trace 1`` the
window runs under the JAX profiler and the per-layer metrics are read
from its trace. After the window the program is freed and the reference
follows the first call's rounds from the same seed.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Dict, Optional

import jax
import numpy as np

from chipbench import compare, reference, trace, traffic
from chipbench.spec import Cell, family, metric_reader, module, peaks

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Compiles:
    """JAX's compile events in this process: seconds spent, and programs
    compiled or loaded, since the last ``reset``."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            if event == _COMPILE_EVENTS[-1]:
                self.programs += 1

    def reset(self) -> None:
        self.seconds, self.programs = 0.0, 0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _spans(runner):
    """Harness spans around the engine's host steps, for the trace."""
    from jax.profiler import TraceAnnotation

    def wrap(name, fn):
        def inner(*a, **k):
            with TraceAnnotation(name):
                return fn(*a, **k)
        return inner

    for attr, name in (("_seg_jit", "chipbench.dispatch"),
                       ("_absorb_segment", "chipbench.absorb"),
                       ("_sync_host_population", "chipbench.sync")):
        fn = getattr(runner, attr, None)
        if fn is not None:
            setattr(runner, attr, wrap(name, fn))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t0: float, require_tpu: bool = True,
             keep_trace: Optional[str] = None) -> Dict[str, Any]:
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if require_tpu and dev.platform != "tpu":
        raise SystemExit("chipbench: JAX found no TPU; there is no "
                         "fallback")
    if len(devices) < cell.chips:
        raise SystemExit(f"chipbench: the cell needs {cell.chips} chips, "
                         f"JAX found {len(devices)}")
    compiles = Compiles()
    cfg, t = cell.config, cell.traffic
    fam = family(cfg["family"])
    engine = module("engines", t["engine"])
    rounds = t["rounds_per_call"]

    # ---- set-up ---------------------------------------------------------- #
    params0 = fam.init_params(cfg["model"], traffic.weights_key(seed))
    train, test = traffic.samples(seed, cfg)
    runner = engine.build(cell, seed, params0, train, test)
    first = engine.first_call(runner, rounds)
    params_after = runner.params
    setup_s = time.perf_counter() - t0
    compile_s = compiles.seconds
    log(f"setup: {setup_s:.4f} s, of which compile or cache load "
        f"{compile_s:.4f} s over {compiles.programs} programs")

    # ---- the window ------------------------------------------------------ #
    compiles.reset()
    trace_dir = None
    if traced:
        from jax.profiler import TraceAnnotation
        _spans(runner)
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-")
        jax.profiler.start_trace(trace_dir)
    calls, start = 0, time.perf_counter()
    while True:
        if traced:
            with TraceAnnotation(trace.CALL):
                runner.run(rounds)
                jax.block_until_ready(runner.params)
        else:
            runner.run(rounds)
            jax.block_until_ready(runner.params)
        calls += 1
        if time.perf_counter() - start >= seconds:
            break
    window_s = time.perf_counter() - start
    if traced:
        jax.profiler.stop_trace()
    in_window = compiles.programs
    done = runner.history[rounds:]
    failed = sum(1 for r in done if not (math.isfinite(r.train_loss)
                                         and math.isfinite(r.delay)
                                         and math.isfinite(r.energy)))
    log(f"window: {calls} calls, {len(done)} rounds in {window_s:.4f} s; "
        f"{in_window} programs compiled or loaded in the window")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    result: Dict[str, Any] = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak}}

    if traced:
        tr = trace.load(trace.find_xplane(trace_dir))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        else:
            trace.dump(tr, os.path.join(keep_trace, "events.json.gz"))
        used = tr.devices()[:cell.chips]
        busy = (sum(trace.busy_s(tr, d) for d in used) / len(used)
                if used else 0.0)
        ctx = SimpleNamespace(
            trace=tr, chips=cell.chips, cell=cell, family=fam,
            rounds=calls * rounds, compile_s=compile_s,
            peaks=peaks(dev.device_kind) if require_tpu else {
                "bf16_flops": 1.0, "hbm_bytes_per_s": 1.0})
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is None and "workloads" in m:
                raise SystemExit(f"chipbench: {m['name']} lists this cell "
                                 f"but its reader found nothing in the "
                                 f"trace")
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"].update(busy_s=busy, window_s=tr.window_s)
        if used:
            result["breakdown"] = trace.breakdown(tr, used[0])
    else:
        values = {"setup_s": setup_s,
                  "round_s": window_s / (calls * rounds)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # ---- the check ------------------------------------------------------- #
    first["params"] = jax.device_get(params_after)
    del runner, params_after, params0, train, test
    gc.collect()
    ref = reference.follow(cfg, t, seed, first)
    params0 = jax.device_get(fam.init_params(cfg["model"],
                                             traffic.weights_key(seed)))
    numbers = compare.gaps(first, ref, params0)
    numbers.update(compare.accounting_gaps(cfg, t, first, ref))
    cohorts_agree = bool(np.array_equal(first["cohort"], ref["cohort"]))
    checked = compare.checks(numbers, cell.limits)
    correct = (compare.passed(checked) and failed == 0 and cohorts_agree
               and in_window == 0)
    log(f"check: cohorts {'agree' if cohorts_agree else 'DIFFER'}; "
        f"program losses {first['loss'].tolist()}; reference "
        f"{ref['loss'].tolist()}; not compared: " + ", ".join(
            f"{k} {v!r}" for k, v in numbers.items() if k not in checked))
    result.update(correct=correct, attempted=len(done), failed=failed,
                  metrics=metrics, compiles_in_window=in_window,
                  checks=checked)
    for name, c in checked.items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None, t0: float = None) -> None:
    import argparse
    from chipbench.spec import ROOT, load_cell
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profiler trace to this directory and "
                         "keep it, with its events in the window as "
                         "events.json.gz")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0,
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
