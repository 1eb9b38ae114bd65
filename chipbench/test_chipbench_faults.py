"""A run whose timed path is broken underneath reports ``correct`` false:
for a round step that leaves the weights unchanged, a step that trains
each device on half of its batch, a packet error rate logged at another
power than the one applied, a round's energy charged at the wrong power,
and Algorithm 1 with its pruning ratio stuck at 0 or its bit-width stuck
at the maximum."""
import time

import jax
import jax.numpy as jnp
import pytest

import repro.control.device_controller as controller
import repro.fed.rounds as rounds
import repro.fed.scan_engine as scan_engine
from chipbench import harness


def _state_unchanged(step):
    def broken(params, opt_state, comp_state, batch, controls, key):
        _, o, c, m = step(params, opt_state, comp_state, batch, controls,
                          key)
        return params, o, c, m
    return broken


def _half_batch(step):
    def broken(params, opt_state, comp_state, batch, controls, key):
        half = jax.tree_util.tree_map(lambda x: x[:, :x.shape[1] // 2],
                                      batch)
        return step(params, opt_state, comp_state, half, controls, key)
    return broken


def _broken_step(monkeypatch, fault):
    make = rounds.make_fl_train_step

    def make_broken(*args, **kwargs):
        step = make(*args, **kwargs)
        broken = fault(step)
        broken.compressor = step.compressor
        broken.init_comp_state = step.init_comp_state
        return broken

    monkeypatch.setattr(rounds, "make_fl_train_step", make_broken)


def _per_at_other_power(monkeypatch):
    per = scan_engine.packet_error_rate_dev
    monkeypatch.setattr(scan_engine, "packet_error_rate_dev",
                        lambda w, ch, power: per(w, ch, 0.8 * power))


def _energy_at_other_power(monkeypatch):
    acc = scan_engine.round_accounting_dev
    monkeypatch.setattr(scan_engine, "round_accounting_dev",
                        lambda ltfl, ch, payload, rho, power: acc(
                            ltfl, ch, payload, rho,
                            jnp.full_like(power, ltfl.wireless.p_max)))


def _rho_stuck_at_zero(monkeypatch):
    monkeypatch.setattr(controller, "optimal_rho_dev",
                        lambda ltfl, ch, payload, power:
                        jnp.zeros_like(jnp.asarray(power, jnp.float32)))


def _delta_stuck_at_max(monkeypatch):
    delta = controller.optimal_delta_dev
    monkeypatch.setattr(
        controller, "optimal_delta_dev",
        lambda ltfl, ch, rho, power, v: jnp.full_like(
            delta(ltfl, ch, rho, power, v), ltfl.delta_max))


FAULTS = {   # plant, scheme, the number that has to fail
    "state-unchanged": (lambda mp: _broken_step(mp, _state_unchanged),
                        "ltfl", "update_gap"),
    "half-batch": (lambda mp: _broken_step(mp, _half_batch), "ltfl",
                   "range_gap.median"),
    "per-at-other-power": (_per_at_other_power, "ltfl", "power_gap"),
    "energy-at-other-power": (_energy_at_other_power, "fedsgd",
                              "energy_gap"),
    "rho-stuck-at-zero": (_rho_stuck_at_zero, "ltfl", "decision_misses"),
    "delta-stuck-at-max": (_delta_stuck_at_max, "ltfl", "decision_misses"),
}


def _run(tiny_cell, scheme):
    # an energy budget that binds at this size, so that Theorems 2 and 3
    # have something to decide
    cell = tiny_cell(scheme=scheme, e_max=0.3,
                     limits_from=f"paper-{scheme}-u30")
    return harness.run_cell(cell, 5, 0.2, False, time.perf_counter(),
                            require_tpu=False)


@pytest.mark.parametrize("scheme", ["ltfl", "fedsgd"])
def test_the_unbroken_step_is_correct_under_a_binding_budget(tiny_cell,
                                                             scheme):
    res = _run(tiny_cell, scheme)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_step_is_not_correct(tiny_cell, monkeypatch, fault):
    plant, scheme, number = FAULTS[fault]
    plant(monkeypatch)
    res = _run(tiny_cell, scheme)
    assert not res["correct"], res["checks"]
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]
