"""The numbers that decide ``correct``, each against its limit.

* ``loss_gap``: over the rounds of the first call, the largest
  |loss - reference loss| / |reference loss|;
* ``range_gap``: over those rounds and the cohort, the largest relative
  gap of a device's gradient range statistic (Eq. 26), taken before
  quantization; ``range_gap.median``: per round, the signed relative gap
  of that statistic summed over the cohort, and of these the median over
  the rounds, as a magnitude. A device's statistic is the square of its
  gradient's extremes, which the chip's one-pass bfloat16 products move
  by a tenth and more; the cohort's sum and the median round keep the
  shift that a wrong batch makes (half of it: about +40%);
* ``update_gap``: per weight leaf, the gap between the norms of the
  program's and the reference's change of the weights over the call,
  |‖dw‖ - ‖dw_ref‖| / max(‖dw_ref‖, the median leaf's ‖dw_ref‖); the
  worst leaf, and in ``update_gap.median`` the median leaf. Leaves whose
  first averaged reference gradient is under a thousandth of the median
  leaf's are left out: nothing but rounding moves them.

and, from the wireless accounting (``accounting``, float64) at the
controls the program applied, over the rounds of the first call:

* ``power_gap``: the largest distance, relative to the mean power, of
  the round's logged mean power from the span of mean powers at which
  Eq. 3 gives each device's logged packet error rate on the round's
  channel within its float32 rounding (``accounting.power_range``); a
  deep fade that leaves a rate at 1 leaves that device's power open
  below, and does not count;
* ``delay_gap`` and ``energy_gap``: the largest relative gap of the
  round's logged delay and energy against Eq. 31-37 at the logged pruning
  ratios and bit-widths and the power the scheme charges
  (``schemes/<scheme>.charged_power``);
* ``gamma_gap``: the largest relative gap of the round's Gamma against
  Eq. 29 from the logged range statistics, bit-widths, pruning ratios and
  packet error rates;
* ``decision_misses``: the device-rounds whose controls break what the
  scheme guarantees (``schemes/<scheme>.decision_misses``) at every
  power that the logged packet error rate admits within its float32
  rounding (``accounting.power_range``).

A cell's ``limits/<cell>.json`` names the numbers it compares; the others
are printed.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np

from chipbench import accounting
from chipbench.spec import module

NAMES = ("loss_gap", "range_gap", "range_gap.median", "update_gap",
         "update_gap.median", "power_gap", "delay_gap", "energy_gap",
         "gamma_gap", "decision_misses")


def _leaves(tree) -> list:
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def gaps(run: Dict[str, Any], ref: Dict[str, Any],
         params0: Any) -> Dict[str, float]:
    """``run`` and ``ref`` hold ``loss`` (R,), ``range_sq`` (R, U) and
    ``params`` (the weights after the call); ``ref`` also holds
    ``grad_norm0``. ``params0`` are the weights before the call."""
    loss, loss_ref = np.asarray(run["loss"]), np.asarray(ref["loss"])
    rsq = np.asarray(run["range_sq"], np.float64)
    rsq_ref = np.asarray(ref["range_sq"], np.float64)
    g0 = np.asarray(jax.tree_util.tree_leaves(ref["grad_norm0"]), np.float64)
    keep = g0 >= 1e-3 * np.median(g0)
    p0 = _leaves(params0)
    d_run = np.array([np.linalg.norm(a - b)
                      for a, b in zip(_leaves(run["params"]), p0)])
    d_ref = np.array([np.linalg.norm(a - b)
                      for a, b in zip(_leaves(ref["params"]), p0)])
    floor = np.median(d_ref[keep])
    upd = np.abs(d_run - d_ref)[keep] / np.maximum(d_ref[keep], floor)
    losses = np.abs(loss - loss_ref) / np.abs(loss_ref)
    cohort = (rsq.sum(axis=1) - rsq_ref.sum(axis=1)) / np.maximum(
        rsq_ref.sum(axis=1), 1e-30)
    return {
        "loss_gap": float(np.max(losses)),
        "range_gap": float(np.max(np.abs(rsq - rsq_ref)
                                  / np.maximum(np.abs(rsq_ref), 1e-30))),
        "range_gap.median": float(np.abs(np.median(cohort))),
        "update_gap": float(np.max(upd)),
        "update_gap.median": float(np.median(upd)),
    }


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def accounting_gaps(cfg: Dict, traffic_cfg: Dict, run: Dict[str, Any],
                    ref: Dict[str, Any]) -> Dict[str, float]:
    """``run`` is the program's round log of the call (``rho``,
    ``delta``, ``pers``, ``range_sq``, ``delay``, ``energy``,
    ``power_mean``, ``gamma``); ``ref`` holds each round's ``channel``.
    Also returns, not compared: ``energy_gap.pruned_twice``, the same gap
    against Eq. 32 with the upload's (1 - rho) applied twice, and
    ``per_gap.charged``, the largest relative gap of a logged packet
    error rate against Eq. 3 at the charged power (under FedSGD, the
    rounding of the chip's float32 rate)."""
    w, lt = cfg["wireless"], cfg["ltfl"]
    scheme = module("schemes", traffic_cfg["scheme"])
    v = module("families", cfg["family"]).num_params(cfg["model"])
    power, delay, energy, twice, gam, misses = [], [], [], [], [], 0
    per_gap = []
    for r, ch in enumerate(ref["channel"]):
        rho, delta = run["rho"][r], run["delta"][r]
        p, p_low, p_high = accounting.power_range(w, ch, run["pers"][r])
        logged = run["power_mean"][r]
        power.append(max(0.0, np.mean(p_low) - logged,
                         logged - np.mean(p_high)) / np.mean(p))
        charged = scheme.charged_power(cfg, p)
        per_gap.append(_rel(run["pers"][r],
                            accounting.packet_error(w, ch, charged)))
        bits = scheme.payload(cfg, v, delta)
        t, e = accounting.delay_energy(cfg, ch, bits, rho, charged)
        delay.append(np.max(t) + lt["server_delay"])
        energy.append(np.sum(e))
        twice.append(np.sum(accounting.delay_energy(
            cfg, ch, bits * (1.0 - rho), rho, charged)[1]))
        gam.append(accounting.gamma(cfg, run["range_sq"][r], delta, rho,
                                    run["pers"][r], ch["samples"]))
        misses += int(np.sum(scheme.decision_misses(
            cfg, v, ch, rho, delta, (p_low, p, p_high))))
    return {"power_gap": float(np.max(power)),
            "delay_gap": _rel(run["delay"], delay),
            "energy_gap": _rel(run["energy"], energy),
            "gamma_gap": _rel(run["gamma"], gam),
            "decision_misses": float(misses),
            "energy_gap.pruned_twice": _rel(run["energy"], twice),
            "per_gap.charged": float(np.max(per_gap))}


def checks(numbers: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    return {k: {"value": numbers[k], "limit": float(limits[k])}
            for k in NAMES if k in limits and k in numbers}


def passed(checked: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checked.values())
