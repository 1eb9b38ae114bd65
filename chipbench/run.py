#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, their configurations and metrics are listed in BENCHMARK.json
at the root of the checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``checks``: each number that decides ``correct``
beside its limit. Without a TPU, or with fewer chips than the cell needs,
it exits non-zero and prints no result.
"""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compile cache lives in the checkout, at a fixed path,
# and keeps every program, so that only a checkout's first run compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
# the checkout and the program, in place of this script's directory, whose
# module names (trace, ...) would shadow the standard library's
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from chipbench.harness import main
    main(t0=T0)
