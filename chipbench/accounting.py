"""The wireless side of a round, in float64 on the host: the channel each
round sees, and the delay, energy, packet error and Gamma accounting of
arXiv:2507.09546 at the controls the program applied.

* Eq. 1: R = B E_h[log2(1 + p h / (I + B N0))], h = varpi d^-2 X with
  X ~ Exp(1) (Rayleigh), the expectation by 64-point Gauss-Laguerre;
* Eq. 3: q = E_h[1 - exp(-Upsilon (I + B N0) / (p h))], the same rule;
* Eq. 31-37: T_u = N_u c0 (1 - rho) / f_u + payload (1 - rho) / R and
  E_u = k f_u^(sigma - 1) N_u c0 (1 - rho) + p T_lu; the round's delay is
  max_u T_u plus the server's, its energy sum_u E_u. ``payload`` is the
  scheme's uplink bits before pruning (Eq. 18: V delta + xi under LTFL,
  32 V at full precision);
* Eq. 29: Gamma from the per-device range statistics, bit-widths, pruning
  ratios, packet error rates and sample counts;
* Theorem 2 (Eq. 40-42) and Theorem 3 (Eq. 44-46): the pruning ratio and
  bit-width that Algorithm 1's closed-form stages give at a power.

The program logs each device's packet error rate but not its power; the
power is recovered here by inverting Eq. 3, which falls strictly with p.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_GL_X, _GL_W = np.polynomial.laguerre.laggauss(64)


def fading(cfg: Dict, k_fade: jax.Array, n: int):
    """One block-fading epoch for ``n`` devices, as drawn from the round's
    fading key: mean fading power fading_scale * Exp(1) and interference
    uniform over Table 2's range, float32 draws."""
    w = cfg["wireless"]
    k_f, k_i = jax.random.split(k_fade)
    f = w["fading_scale"] * jax.random.exponential(k_f, (n,), jnp.float32)
    i = jax.random.uniform(k_i, (n,), jnp.float32,
                           minval=w["interference_min"],
                           maxval=w["interference_max"])
    return np.asarray(f, np.float64), np.asarray(i, np.float64)


def _snr_scale(w: Dict, ch: Dict) -> np.ndarray:
    """p h / (I + B N0) per watt and per unit of X."""
    gain = ch["fading"] * ch["distance"] ** -2.0
    return gain / (ch["interference"] + w["bandwidth_ul"] * w["n0"])


def rate(w: Dict, ch: Dict, power: np.ndarray) -> np.ndarray:
    c = np.asarray(power, np.float64) * _snr_scale(w, ch)
    return w["bandwidth_ul"] * np.sum(
        _GL_W * np.log2(1.0 + c[..., None] * _GL_X), axis=-1)


def packet_error(w: Dict, ch: Dict, power: np.ndarray) -> np.ndarray:
    c = w["waterfall"] / (np.asarray(power, np.float64) * _snr_scale(w, ch))
    x = np.maximum(_GL_X, 1e-12)
    q = np.sum(_GL_W * (1.0 - np.exp(-c[..., None] / x)), axis=-1)
    return np.clip(q, 0.0, 1.0)


# the float32 rounding of a packet error rate as the chip computes it (a
# sum over the 64 nodes of the rule, each 1 - exp(-y) with y small for
# most): relative, and absolute where the rate is small; where Eq. 3
# flattens (a deep fade), it leaves the power recovered from the rate
# that much less certain
PER_ROUNDING = 1e-3
PER_FLOOR = 1e-6


def power_range(w: Dict, ch: Dict, per: np.ndarray):
    """The powers at which Eq. 3 gives ``per``, and at which it gives
    ``per`` less and more its float32 rounding: (p, p_low, p_high)."""
    per = np.asarray(per, np.float64)
    slack = per * PER_ROUNDING + PER_FLOOR
    return (power_from_per(w, ch, per),
            power_from_per(w, ch, np.minimum(per + slack, 1)),
            power_from_per(w, ch, np.maximum(per - slack, 0)))


def power_from_per(w: Dict, ch: Dict, per: np.ndarray) -> np.ndarray:
    """The power at which Eq. 3 gives ``per``: bisection in log p over
    [p_min / 100, 100 p_max]."""
    lo = np.full(per.shape, np.log(w["p_min"] / 100.0))
    hi = np.full(per.shape, np.log(w["p_max"] * 100.0))
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        above = packet_error(w, ch, np.exp(mid)) > per
        lo, hi = np.where(above, mid, lo), np.where(above, hi, mid)
    return np.exp(0.5 * (lo + hi))


def delay_energy(cfg: Dict, ch: Dict, payload: np.ndarray, rho: np.ndarray,
                 power: np.ndarray):
    """Per-device round delay and energy (Eq. 31-37)."""
    w = cfg["wireless"]
    keep = 1.0 - rho
    comp = ch["samples"] * w["cycles_per_sample"] * keep
    t_up = payload * keep / np.maximum(rate(w, ch, power), 1e-9)
    t = comp / ch["cpu"] + t_up
    e = w["k_eff"] * ch["cpu"] ** (w["sigma_exp"] - 1.0) * comp \
        + power * t_up
    return t, e


def gamma(cfg: Dict, range_sq, delta, rho, per, samples) -> float:
    """Eq. 29 with every device taking part."""
    lt = cfg["ltfl"]
    steps = np.maximum(2.0 ** delta - 1.0, 1e-12)
    quant = 3.0 * np.sum(range_sq / (4.0 * steps * steps))
    prune = 3.0 * lt["lipschitz"] ** 2 * lt["d_sq"] * np.sum(rho)
    trans = 12.0 * lt["v1"] / np.sum(samples) * np.sum(samples * per)
    return float((quant + prune + trans) / (1.0 - 12.0 * lt["v2"]))


def theorem2_rho(cfg: Dict, ch: Dict, payload: np.ndarray,
                 power: np.ndarray) -> np.ndarray:
    """Theorem 2's pruning ratio at ``power`` and uplink bits
    ``payload``, clipped to [0, rho_max]."""
    w, lt = cfg["wireless"], cfg["ltfl"]
    r = np.maximum(rate(w, ch, power), 1e-30)
    c0 = ch["samples"] * w["cycles_per_sample"]
    phi1 = (lt["t_max"] - lt["server_delay"]) / (c0 / ch["cpu"]
                                                 + payload / r)
    phi2 = lt["e_max"] / (w["k_eff"] * ch["cpu"] ** (w["sigma_exp"] - 1.0)
                          * c0 + power * payload / r)
    return np.clip(1.0 - np.minimum(phi1, phi2), 0.0, lt["rho_max"])


def theorem3_raw(cfg: Dict, ch: Dict, rho: np.ndarray, power: np.ndarray,
                 num_params: int) -> np.ndarray:
    """Theorem 3's bit-width at ``rho`` and ``power`` before the floor
    and the clip to [1, delta_max]."""
    w, lt = cfg["wireless"], cfg["ltfl"]
    r = np.maximum(rate(w, ch, power), 1e-30)
    keep = np.maximum(1.0 - rho, 1e-9)
    c0 = ch["samples"] * w["cycles_per_sample"] * keep
    phi3 = (lt["t_max"] - lt["server_delay"] - c0 / ch["cpu"]) * r / keep
    phi4 = (lt["e_max"] - w["k_eff"] * ch["cpu"] ** (w["sigma_exp"] - 1.0)
            * c0) * r / (power * keep)
    v = float(num_params) * keep
    raw = np.minimum(np.minimum(phi3 - lt["xi_bits"], phi4 - lt["xi_bits"])
                     / v, lt["delta_max"])
    return np.where(np.isnan(raw), 1.0, raw)


def theorem3_delta(raw: np.ndarray, cfg: Dict) -> np.ndarray:
    return np.clip(np.floor(raw), 1.0, cfg["ltfl"]["delta_max"])
