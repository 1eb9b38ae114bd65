"""Kernel microbenchmarks: Pallas vs jnp references. On the CPU the
kernels run in the Pallas interpreter, so timings there are
correctness-path numbers, not TPU speed; on a TPU they run compiled."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_artifact
from repro.kernels import ref
from repro.kernels.block_prune import block_norms
from repro.kernels.block_sparse_matmul import block_sparse_matmul
from repro.kernels.stochastic_quant import stochastic_quant


def _time(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6


def run(m: int = 1024, n: int = 1024) -> dict:
    g = jax.random.normal(jax.random.PRNGKey(0), (m, n))
    rand = jax.random.uniform(jax.random.PRNGKey(1), (m, n))
    a = jnp.abs(g)
    lo, hi = jnp.min(a), jnp.max(a)
    results = {}

    us = _time(lambda: stochastic_quant(g, rand, lo, hi, 8))
    us_ref = _time(lambda: jax.jit(ref.stochastic_quant_ref,
                                   static_argnames="bits")(g, rand, lo, hi,
                                                           8))
    emit("kernels/stochastic_quant_interp", us, f"jnp_ref={us_ref:.0f}us")
    results["quant"] = {"kernel_us": us, "ref_us": us_ref}

    us = _time(lambda: block_norms(g))
    us_ref = _time(lambda: jax.jit(ref.block_norms_ref,
                                   static_argnames=("bm", "bn"))(g, 128, 128))
    emit("kernels/block_norms_interp", us, f"jnp_ref={us_ref:.0f}us")
    results["norms"] = {"kernel_us": us, "ref_us": us_ref}

    x = jax.random.normal(jax.random.PRNGKey(2), (256, m))
    mask_half = jax.random.uniform(jax.random.PRNGKey(3),
                                   (m // 128, n // 128)) > 0.5
    mask_full = jnp.ones((m // 128, n // 128), bool)
    us_half = _time(lambda: block_sparse_matmul(x, g, mask_half))
    us_full = _time(lambda: block_sparse_matmul(x, g, mask_full))
    emit("kernels/bsmm_rho0.5_interp", us_half,
         f"dense={us_full:.0f}us speedup={us_full/us_half:.2f}x "
         "(interpret mode; MXU tile-skip is structural)")
    results["bsmm"] = {"half_us": us_half, "dense_us": us_full}

    save_artifact("kernels_bench", results)
    return results


if __name__ == "__main__":
    run()
