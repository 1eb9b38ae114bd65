"""The paper's scheme: Algorithm 1 chooses each device's power, pruning
ratio and bit-width; the device prunes its weights by magnitude, and
quantizes its gradient stochastically to that many bits (Eq. 12-18)."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import accounting

PRUNES = True
# slack of the float64 recomputation against the program's float32 one
_RHO_TOL, _RAW_TOL = 1e-4, 1e-3


def program(t: Dict):
    from repro.fed import LTFLScheme
    return LTFLScheme(recontrol_every=t["recontrol_every"])


def compress(g: jax.Array, delta: jax.Array, key: jax.Array) -> jax.Array:
    """Stochastic uniform quantization of one leaf to ``delta`` bits
    between its smallest and largest magnitude, rounding up with the
    probability of the fractional level."""
    bits = jnp.maximum(delta, 1.0)
    n = jnp.maximum(jnp.round(2.0 ** bits) - 1.0, 1.0)
    a = jnp.abs(g)
    lo, hi = jnp.min(a), jnp.max(a)
    scale = (hi - lo) / n
    scale = jnp.where(scale > 0, scale, 1.0)
    t = (a - lo) / scale
    floor = jnp.floor(t)
    up = jax.random.uniform(key, g.shape, jnp.float32) < (t - floor)
    mag = lo + jnp.clip(floor + up.astype(jnp.float32), 0.0, n) * scale
    return jnp.where(delta > 0, jnp.where(g >= 0, mag, -mag), g)


def payload(cfg: Dict, v: int, delta: np.ndarray) -> np.ndarray:
    """Eq. 18: V delta + xi uplink bits before pruning, for V weights."""
    return float(v) * delta + cfg["ltfl"]["xi_bits"]


def charged_power(cfg: Dict, recovered: np.ndarray) -> np.ndarray:
    """The power the round's delay and energy are charged at: Algorithm
    1's, as recovered from the logged packet error rate."""
    return recovered


def decision_misses(cfg: Dict, v: int, ch: Dict, rho: np.ndarray,
                    delta: np.ndarray, powers) -> np.ndarray:
    """Per device, whether the applied controls break what Algorithm 1
    guarantees at every power of ``powers`` (the power and the ends of
    its range, see ``accounting.power_range``): rho in [0, rho_max];
    delta a whole number in [1, delta_max]; power in [p_min, p_max]; rho
    Theorem 2's ratio at that power for some admissible bit-width (the
    alternation's last), which is what meets the delay and energy budgets
    unless it sits at its clamp; and delta Theorem 3's bit-width at rho
    and that power. ``v`` is the model's number of weights."""
    w, lt = cfg["wireless"], cfg["ltfl"]
    p_low, p_high = np.min(powers, axis=0), np.max(powers, axis=0)
    bad = (rho < -_RHO_TOL) | (rho > lt["rho_max"] + _RHO_TOL)
    bad |= (delta != np.round(delta)) | (delta < 1) | (
        delta > lt["delta_max"])
    bad |= (p_high < w["p_min"] * (1 - 1e-4)) | (
        p_low > w["p_max"] * (1 + 1e-4))
    widths = np.arange(1, lt["delta_max"] + 1, dtype=np.float64)
    # (width, power, device); Theorem 2 is monotone in p over the range
    th2 = np.stack([np.stack([accounting.theorem2_rho(
        cfg, ch, payload(cfg, v, d), p) for p in powers]) for d in widths])
    bad |= ~np.any((th2.min(axis=1) - _RHO_TOL <= rho)
                   & (rho <= th2.max(axis=1) + _RHO_TOL), axis=0)
    raws = np.stack([accounting.theorem3_raw(cfg, ch, rho, p, v)
                     for p in powers])
    lo = accounting.theorem3_delta(raws.min(axis=0) - _RAW_TOL, cfg)
    hi = accounting.theorem3_delta(raws.max(axis=0) + _RAW_TOL, cfg)
    bad |= (delta < lo) | (delta > hi)
    return bad
