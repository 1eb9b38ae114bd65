"""The reference's float64 wireless accounting agrees with the program's
own float64 host control plane on seeded channels, and recovers the
power from a packet error rate."""
import json

import numpy as np
import pytest

from chipbench import accounting
from chipbench.conftest import ROOT

CFG = json.loads((ROOT / "chipbench" / "configs" / "ltfl-resnet-paper.json")
                 .read_text())
V = CFG["parameters"]


def _channels(seed: int):
    """The same 30 devices as the reference's dict and the program's
    ``ChannelState``, with a block-fading draw."""
    from repro.configs.base import WirelessConfig
    from repro.core.channel import ChannelState
    w = WirelessConfig(**CFG["wireless"])
    lt = CFG["ltfl"]
    state = ChannelState.sample(w, 30, lt["samples_min"], lt["samples_max"],
                                np.random.default_rng(seed))
    state = state.redraw_fading(w, np.random.default_rng(seed + 1))
    ch = {"distance": state.distance, "fading": state.fading_mean,
          "interference": state.interference, "cpu": state.cpu_hz,
          "samples": np.asarray(state.num_samples, np.float64)}
    return w, state, ch


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_rates_and_theorems_match_the_program_host_path(seed):
    from repro.configs.base import LTFLConfig
    from repro.core import channel, controller
    from repro.core.convergence import gamma
    w, state, ch = _channels(seed)
    ltfl = LTFLConfig(num_devices=30, wireless=w, **CFG["ltfl"])
    rng = np.random.default_rng(seed)
    p = rng.uniform(CFG["wireless"]["p_min"], CFG["wireless"]["p_max"], 30)
    delta = rng.integers(1, 9, 30).astype(np.float64)
    np.testing.assert_allclose(accounting.rate(CFG["wireless"], ch, p),
                               channel.expected_rate(w, state, p),
                               rtol=1e-12)
    per = accounting.packet_error(CFG["wireless"], ch, p)
    np.testing.assert_allclose(per, channel.packet_error_rate(w, state, p),
                               rtol=1e-12)
    np.testing.assert_allclose(
        accounting.power_from_per(CFG["wireless"], ch, per), p, rtol=1e-9)
    payload = V * delta + CFG["ltfl"]["xi_bits"]
    rho = accounting.theorem2_rho(CFG, ch, payload, p)
    np.testing.assert_allclose(
        rho, controller.optimal_rho(ltfl, state, payload, p), atol=1e-12)
    np.testing.assert_array_equal(
        accounting.theorem3_delta(
            accounting.theorem3_raw(CFG, ch, rho, p, V), CFG),
        controller.optimal_delta(ltfl, state, rho, p, V))
    rsq = rng.uniform(1.0, 10.0, 30)
    assert accounting.gamma(CFG, rsq, delta, rho, per, ch["samples"]) == \
        pytest.approx(gamma(ltfl, rsq, delta, rho, per, state.num_samples),
                      rel=1e-12)
