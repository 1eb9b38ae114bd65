"""Scanned round engine: seeded parity with the per-round FedRunner,
compile cadence, device-rng mode, and the vmap-over-seeds sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LTFLConfig
from repro.core.channel import ChannelState, expected_rate, \
    expected_rate_dev, packet_error_rate, packet_error_rate_dev
from repro.core.convergence import gamma, gamma_dev
from repro.core.delay_energy import (
    device_round_delay,
    device_round_delay_dev,
    device_round_energy,
    device_round_energy_dev,
)
from repro.core.ltfl_step import make_fl_train_step
from repro.data import ArrayDataset, synthetic_cifar
from repro.fed import (
    ChannelAwareSampler,
    FedMPScheme,
    FedRunner,
    FedSGDScheme,
    LTFLScheme,
    ScanRunner,
    STCScheme,
    UniformSampler,
    make_scanned_step,
)
from repro.models import MLP
from repro.optim import sgd

LTFL = LTFLConfig(num_devices=4, samples_min=40, samples_max=60,
                  bo_iters=3, alt_max_iters=2)


@pytest.fixture(scope="module")
def world():
    imgs, labels = synthetic_cifar(600, seed=0)
    timgs, tlabels = synthetic_cifar(128, seed=1)
    train = ArrayDataset({"images": imgs, "labels": labels})
    test = ArrayDataset({"images": timgs, "labels": tlabels})
    model = MLP()
    params = model.init(jax.random.PRNGKey(0))
    return model, params, train, test


def assert_history_parity(h_loop, h_scan, *, loss_exact=True):
    """Round-by-round parity: the tensor trajectory is bit-comparable
    (stateless schemes; the scan body runs the identical step on the
    identical inputs), the f32 on-device accounting is tolerance-pinned
    to the float64 host accounting."""
    assert len(h_loop) == len(h_scan)
    for a, b in zip(h_loop, h_scan):
        assert a.round == b.round
        if loss_exact:
            assert a.train_loss == b.train_loss
        else:
            assert a.train_loss == pytest.approx(b.train_loss, rel=1e-5)
        assert a.received == b.received
        assert a.cohort == b.cohort
        assert a.delay == pytest.approx(b.delay, rel=1e-4)
        assert a.energy == pytest.approx(b.energy, rel=1e-4)
        assert a.cum_delay == pytest.approx(b.cum_delay, rel=1e-4)
        assert a.cum_energy == pytest.approx(b.cum_energy, rel=1e-4)
        assert a.gamma == pytest.approx(b.gamma, rel=1e-3)
        assert a.rho_mean == pytest.approx(b.rho_mean, abs=1e-7)
        assert a.delta_mean == pytest.approx(b.delta_mean, abs=1e-7)
        assert a.power_mean == pytest.approx(b.power_mean, rel=1e-6)
        if np.isnan(a.test_acc):
            assert np.isnan(b.test_acc)
        else:
            assert a.test_acc == pytest.approx(b.test_acc, abs=1e-6)


# --------------------------------------------------------------------------- #
# jnp accounting twins vs the float64 host path
# --------------------------------------------------------------------------- #
def test_dev_twins_match_host(rng):
    state = ChannelState.sample(LTFL.wireless, 8, 40, 60, rng)
    power = rng.uniform(LTFL.wireless.p_min, LTFL.wireless.p_max, 8)
    payload = rng.uniform(1e5, 1e7, 8)
    rho = rng.uniform(0.0, 0.5, 8)
    arrs = state.to_arrays()
    p32 = jnp.asarray(power, jnp.float32)

    np.testing.assert_allclose(
        expected_rate_dev(LTFL.wireless, arrs, p32),
        expected_rate(LTFL.wireless, state, power), rtol=1e-4)
    np.testing.assert_allclose(
        packet_error_rate_dev(LTFL.wireless, arrs, p32),
        packet_error_rate(LTFL.wireless, state, power), rtol=1e-4,
        atol=1e-7)
    np.testing.assert_allclose(
        device_round_delay_dev(LTFL.wireless, arrs,
                               jnp.asarray(payload, jnp.float32),
                               jnp.asarray(rho, jnp.float32), p32),
        device_round_delay(LTFL.wireless, state, payload, rho, power),
        rtol=1e-4)
    np.testing.assert_allclose(
        device_round_energy_dev(LTFL.wireless, arrs,
                                jnp.asarray(payload, jnp.float32),
                                jnp.asarray(rho, jnp.float32), p32),
        device_round_energy(LTFL.wireless, state, payload, rho, power),
        rtol=1e-4)

    rsq = rng.uniform(1.0, 100.0, 8)
    deltas = rng.integers(1, 9, 8).astype(float)
    pers = packet_error_rate(LTFL.wireless, state, power)
    g_host = gamma(LTFL, rsq, deltas, rho, pers, state.num_samples)
    g_dev = float(gamma_dev(LTFL, jnp.asarray(rsq, jnp.float32),
                            jnp.asarray(deltas, jnp.float32),
                            jnp.asarray(rho, jnp.float32),
                            jnp.asarray(pers, jnp.float32),
                            jnp.asarray(state.num_samples, jnp.float32)))
    assert g_dev == pytest.approx(g_host, rel=1e-4)
    # partial-participation HT convention
    pi = rng.uniform(0.2, 1.0, 8)
    tot = float(np.sum(state.num_samples) * 2)
    g_host = gamma(LTFL, rsq, deltas, rho, pers, state.num_samples,
                   inclusion=pi, population_samples=tot)
    g_dev = float(gamma_dev(LTFL, jnp.asarray(rsq, jnp.float32),
                            jnp.asarray(deltas, jnp.float32),
                            jnp.asarray(rho, jnp.float32),
                            jnp.asarray(pers, jnp.float32),
                            jnp.asarray(state.num_samples, jnp.float32),
                            inclusion=jnp.asarray(pi, jnp.float32),
                            population_samples=tot))
    assert g_dev == pytest.approx(g_host, rel=1e-4)


# --------------------------------------------------------------------------- #
# seeded parity vs FedRunner (host rng mode)
# --------------------------------------------------------------------------- #
def test_parity_stateless_scheme(world):
    """FedSGD, eval every 2 rounds: multi-round segments between evals."""
    model, params, train, test = world
    loop = FedRunner(model, params, LTFL, train, test, FedSGDScheme(),
                     batch_size=8, seed=0, eval_every=2)
    scan = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                      batch_size=8, seed=0, eval_every=2)
    assert_history_parity(loop.run(6), scan.run(6))


def test_parity_stateful_compressor(world):
    """STC's error-feedback residual is carried through the scan exactly
    as the per-round loop carries it through successive jit calls."""
    model, params, train, test = world
    loop = FedRunner(model, params, LTFL, train, test, STCScheme(),
                     batch_size=8, seed=0, eval_every=0)
    scan = ScanRunner(model, params, LTFL, train, test, STCScheme(),
                      batch_size=8, seed=0, eval_every=0)
    assert_history_parity(loop.run(5), scan.run(5))


@pytest.mark.parametrize("block_fading", [False, True])
def test_parity_ltfl_recontrol_segments(world, block_fading):
    """LTFL with recontrol_every=2: Algorithm 1 re-solves at segment
    boundaries on the identical np_rng stream, so decisions — and the
    scanned rounds between them — match the per-round loop."""
    model, params, train, test = world
    loop = FedRunner(model, params, LTFL, train, test,
                     LTFLScheme(recontrol_every=2), batch_size=8, seed=0,
                     eval_every=0, block_fading=block_fading)
    scan = ScanRunner(model, params, LTFL, train, test,
                      LTFLScheme(recontrol_every=2), batch_size=8, seed=0,
                      eval_every=0, block_fading=block_fading)
    assert_history_parity(loop.run(4), scan.run(4))
    if block_fading:
        assert scan.channel_epoch == loop.channel_epoch == 4
        np.testing.assert_array_equal(scan.channel.fading_mean,
                                      loop.channel.fading_mean)


def test_parity_partial_participation(world):
    """Uniform cohort sampling + Horvitz-Thompson aggregation through the
    scan: cohorts, weights and the HT population Gamma all match."""
    model, params, train, test = world
    kw = dict(batch_size=8, seed=0, eval_every=0, population_size=12,
              cohort_size=4, cohort_sampler=UniformSampler(),
              participation="unbiased")
    loop = FedRunner(model, params, LTFL, train, test, FedSGDScheme(), **kw)
    scan = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                      **kw)
    assert_history_parity(loop.run(5), scan.run(5))
    np.testing.assert_array_equal(loop._range_sq_pop, scan._range_sq_pop)


def test_max_segment_one_is_degenerate_loop(world):
    """max_segment=1 scans one round at a time — the classic FedRunner as
    the degenerate case, bit-comparable for a stateless scheme."""
    model, params, train, test = world
    loop = FedRunner(model, params, LTFL, train, test, FedSGDScheme(),
                     batch_size=8, seed=0, eval_every=0)
    scan = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                      batch_size=8, seed=0, eval_every=0, max_segment=1)
    h_loop, h_scan = loop.run(3), scan.run(3)
    assert all(s[1] - s[0] == 1 for s in scan._segment_spans(0, 3))
    assert_history_parity(h_loop, h_scan)


# --------------------------------------------------------------------------- #
# compile cadence
# --------------------------------------------------------------------------- #
def test_one_trace_per_segment_length(world):
    model, params, train, test = world
    scan = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                      batch_size=8, seed=0, eval_every=0, max_segment=4)
    scan.run(8)                      # two segments of length 4: one trace
    assert scan._n_traces == 1
    scan.run(8)                      # two more length-4 segments: cached
    assert scan._n_traces == 1
    scan.run(2)                      # one length-2 segment: second trace
    assert scan._n_traces == 2


# --------------------------------------------------------------------------- #
# device rng mode
# --------------------------------------------------------------------------- #
def test_device_mode_runs_and_mixes_fading(world):
    model, params, train, test = world
    scan = ScanRunner(model, params, LTFL, train, test, STCScheme(),
                      batch_size=8, seed=0, eval_every=3,
                      block_fading=True, rng="device")
    fading0 = scan.population.channel.fading_mean.copy()
    hist = scan.run(6)
    assert len(hist) == 6
    for rec in hist:
        assert np.isfinite(rec.train_loss)
        assert rec.delay > 0 and rec.energy > 0
        assert 0 <= rec.received <= LTFL.num_devices
    assert np.isfinite(hist[3].test_acc) and np.isnan(hist[1].test_acc)
    # the in-scan redraw reached the host mirror at the segment boundary
    assert not np.array_equal(scan.population.channel.fading_mean, fading0)
    assert scan.channel_epoch == 6


def test_lower_segment_takes_the_data_pool_as_an_argument(world):
    """``lower_segment`` is the program ``run`` compiles, and the training
    pool is one of its arguments: a pool closed over as a constant would
    be baked into the executable (hundreds of MB at paper scale)."""
    model, params, train, test = world
    scan = ScanRunner(model, params, LTFL, train, test, STCScheme(),
                      batch_size=8, seed=0, eval_every=0, rng="device")
    lowered = scan.lower_segment(3)
    args, _ = lowered.args_info
    assert args[3]["images"].shape == train.arrays["images"].shape
    assert len(lowered.as_text()) < train.arrays["images"].nbytes
    lowered.compile()
    assert len(scan.run(3)) == 3
    assert scan._n_traces == 1           # run reuses the lowered trace


def test_device_mode_tolerates_zero_sample_device(world):
    """Regression for the padded-parts empty-shard crash: a registered
    zero-sample device used to IndexError the table build (`p[0]` on an
    empty row). Now its row is zero-padded and the device engine clamps
    its draws — the round stays finite because its aggregation weight
    (num_samples = 0) zeroes the drawn sample's contribution."""
    model, params, train, test = world
    cfg = LTFLConfig(num_devices=4, samples_min=0, samples_max=3,
                     bo_iters=3, alt_max_iters=2)
    scan = ScanRunner(model, params, cfg, train, test, FedSGDScheme(),
                      batch_size=4, seed=0, eval_every=0,
                      population_size=16, cohort_size=4, rng="device")
    sizes = scan.batcher.client_sizes()
    assert (sizes == 0).any()            # the regression needs one present
    for rec in scan.run(3):
        assert np.isfinite(rec.train_loss)
    # host batching a zero-sample client stays a clear error
    zero = int(np.flatnonzero(sizes == 0)[0])
    with pytest.raises(ValueError, match="zero-sample"):
        scan.batcher.batch_indices(2, np.random.default_rng(0),
                                   clients=[zero])


def test_repeated_run_restarts_rounds_like_fedrunner(world):
    """run() numbering restarts at round 0 on every call, exactly like
    FedRunner.run — history appends, cum sums keep accumulating."""
    model, params, train, test = world
    loop = FedRunner(model, params, LTFL, train, test, FedSGDScheme(),
                     batch_size=8, seed=0, eval_every=0)
    scan = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                      batch_size=8, seed=0, eval_every=0)
    loop.run(2)
    scan.run(2)
    assert_history_parity(loop.run(2), scan.run(2))
    assert [r.round for r in scan.history] == [0, 1, 0, 1]
    assert scan.history[-1].cum_delay == pytest.approx(
        sum(r.delay for r in scan.history), rel=1e-6)


@pytest.mark.parametrize("participation", ["cohort", "unbiased"])
def test_device_mode_partial_participation(world, participation):
    model, params, train, test = world
    scan = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                      batch_size=8, seed=0, eval_every=0,
                      population_size=12, cohort_size=4, rng="device",
                      participation=participation)
    hist = scan.run(4)
    for rec in hist:
        cohort = np.asarray(rec.cohort)
        assert cohort.shape == (4,)
        assert len(np.unique(cohort)) == 4          # without replacement
        assert np.all((cohort >= 0) & (cohort < 12))
        assert np.all(np.diff(cohort) > 0)          # canonical order
        assert rec.participation == pytest.approx(4 / 12)


def test_scan_guards(world):
    model, params, train, test = world

    class HostOnlySampler(UniformSampler):
        """A scheduler with no traced twin (device_twin -> None)."""

        def device_twin(self, runner):
            return None

    class HostControlledScheme(FedSGDScheme):
        """Controls change every other round but only the host knows how
        (no scan_control_program)."""

        def scan_recontrol_every(self, runner):
            return 2

    # samplers without a device twin are rejected with a clear error
    with pytest.raises(ValueError, match="device_twin"):
        ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                   batch_size=8, seed=0, rng="device",
                   population_size=12, cohort_size=4,
                   cohort_sampler=HostOnlySampler())
    with pytest.raises(ValueError, match="rng="):
        ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                   batch_size=8, seed=0, rng="np")
    # host recontrol cannot see a cohort drawn in-scan...
    with pytest.raises(ValueError, match="recontrol"):
        ScanRunner(model, params, LTFL, train, test,
                   LTFLScheme(recontrol_every=1), batch_size=8, seed=0,
                   rng="device", population_size=12, cohort_size=4)
    # ...device control requires the device rng stream...
    with pytest.raises(ValueError, match="rng='device'"):
        ScanRunner(model, params, LTFL, train, test,
                   LTFLScheme(recontrol_every=1), batch_size=8, seed=0,
                   control="device")
    with pytest.raises(ValueError, match="control="):
        ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                   batch_size=8, seed=0, rng="device", control="auto")
    # ...and a scheme whose controls change in-scan must supply a program
    with pytest.raises(ValueError, match="scan_control_program"):
        ScanRunner(model, params, LTFL, train, test,
                   HostControlledScheme(), batch_size=8, seed=0,
                   rng="device", control="device")
    # deterministic device schedulers define no inclusion probabilities
    with pytest.raises(ValueError, match="inclusion"):
        ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                   batch_size=8, seed=0, rng="device",
                   population_size=12, cohort_size=4,
                   cohort_sampler=ChannelAwareSampler(),
                   participation="unbiased")


# --------------------------------------------------------------------------- #
# device control plane (control="device"): in-scan recontrol + eval head
# --------------------------------------------------------------------------- #
def test_device_control_single_segment_compile_counter(world):
    """The acceptance pin: LTFL with recontrol_every=1 — the config that
    degenerates host-control segmentation to length 1 — runs R rounds as
    ONE scanned segment under control='device', with in-scan eval, and
    pays exactly one trace (re-runs of the same length reuse it)."""
    model, params, train, test = world
    scan = ScanRunner(model, params, LTFL, train, test,
                      LTFLScheme(recontrol_every=1), batch_size=8, seed=0,
                      eval_every=2, rng="device", control="device",
                      block_fading=True)
    assert scan._segment_spans(0, 6) == [(0, 6)]
    hist = scan.run(6)
    assert scan._n_traces == 1
    scan.run(6)                       # same length: cached executable
    assert scan._n_traces == 1
    assert len(scan.history) == 12
    for rec in hist:
        assert np.isfinite(rec.train_loss) and np.isfinite(rec.gamma)
        assert 1.0 <= rec.delta_mean <= LTFL.delta_max
        assert 0.0 <= rec.rho_mean <= LTFL.rho_max
        assert LTFL.wireless.p_min <= rec.power_mean <= LTFL.wireless.p_max
    # eval cadence: in-scan eval lands exactly where the host head would
    assert np.isfinite(hist[0].test_acc) and np.isfinite(hist[2].test_acc)
    assert np.isnan(hist[1].test_acc) and np.isnan(hist[3].test_acc)
    # per-round recontrol under block fading actually tracks the channel
    powers = [rec.power_mean for rec in hist]
    assert len(set(np.round(powers, 6))) > 1


def test_device_control_coalesces_planner_spans(world):
    """The planner fix: boundaries that force length-1 segments under
    host control (recontrol_every=1, eval_every=1) vanish under device
    control — one span, no stray retraces."""
    model, params, train, test = world
    host_ctl = ScanRunner(model, params, LTFL, train, test,
                          LTFLScheme(recontrol_every=1), batch_size=8,
                          seed=0, eval_every=1)
    assert host_ctl._segment_spans(0, 4) == [(0, 1), (1, 2), (2, 3),
                                             (3, 4)]
    dev_ctl = ScanRunner(model, params, LTFL, train, test,
                         LTFLScheme(recontrol_every=1), batch_size=8,
                         seed=0, eval_every=1, rng="device",
                         control="device")
    assert dev_ctl._segment_spans(0, 4) == [(0, 4)]
    # max_segment still caps the coalesced span
    capped = ScanRunner(model, params, LTFL, train, test,
                        LTFLScheme(recontrol_every=1), batch_size=8,
                        seed=0, eval_every=1, rng="device",
                        control="device", max_segment=2)
    assert capped._segment_spans(0, 5) == [(0, 2), (2, 4), (4, 5)]


def test_in_scan_eval_matches_host_evaluate(world):
    """Same seed, same rng='device' stream: control='host' (eval between
    length-2 segments) and control='device' (in-scan eval head) follow
    the IDENTICAL key stream, so losses match bit-for-bit and the
    in-scan accuracy matches the host ``evaluate()`` to f32 tolerance."""
    model, params, train, test = world
    kw = dict(batch_size=8, seed=0, eval_every=2)
    host_eval = ScanRunner(model, params, LTFL, train, test,
                           FedSGDScheme(), rng="device", **kw)
    in_scan = ScanRunner(model, params, LTFL, train, test,
                         FedSGDScheme(), rng="device", control="device",
                         **kw)
    h_a, h_b = host_eval.run(6), in_scan.run(6)
    assert in_scan._n_traces == 1
    for a, b in zip(h_a, h_b):
        assert a.train_loss == b.train_loss
        if np.isnan(a.test_acc):
            assert np.isnan(b.test_acc)
        else:
            assert a.test_acc == pytest.approx(b.test_acc, abs=1e-6)


def test_device_control_partial_participation_runs(world):
    """The unlock: per-cohort Algorithm-1 recontrol under rng='device'
    (rejected outright under control='host') runs in-scan, one segment,
    against each round's own cohort and fading."""
    model, params, train, test = world
    scan = ScanRunner(model, params, LTFL, train, test, LTFLScheme(),
                      batch_size=8, seed=0, eval_every=0,
                      population_size=12, cohort_size=4, rng="device",
                      control="device", block_fading=True,
                      participation="unbiased")
    assert scan._segment_spans(0, 5) == [(0, 5)]
    hist = scan.run(5)
    assert scan._n_traces == 1
    for rec in hist:
        cohort = np.asarray(rec.cohort)
        assert cohort.shape == (4,) and len(np.unique(cohort)) == 4
        assert np.isfinite(rec.gamma) and np.isfinite(rec.train_loss)
        assert 1.0 <= rec.delta_mean <= LTFL.delta_max


# --------------------------------------------------------------------------- #
# FedMP scanning (the carried UCB bandit)
# --------------------------------------------------------------------------- #
def test_fedmp_host_control_parity_with_fedrunner(world):
    """control='host': FedMP's per-round cadence degenerates segments to
    length 1, and the host bandit updates between segments exactly as
    FedRunner updates it between rounds — full seeded parity."""
    model, params, train, test = world
    loop = FedRunner(model, params, LTFL, train, test, FedMPScheme(),
                     batch_size=8, seed=0, eval_every=0)
    scan = ScanRunner(model, params, LTFL, train, test, FedMPScheme(),
                      batch_size=8, seed=0, eval_every=0)
    assert all(b - a == 1 for a, b in scan._segment_spans(0, 5))
    assert_history_parity(loop.run(5), scan.run(5))
    np.testing.assert_array_equal(loop.scheme._counts, scan.scheme._counts)
    np.testing.assert_allclose(loop.scheme._rewards, scan.scheme._rewards,
                               rtol=1e-6)


def test_fedmp_device_bandit_parity_with_host_replay(world):
    """control='device': the (N, A) bandit rides the scan carry. Replay
    the host bandit's transition rule over the scanned history (choices
    from state, reward = loss decrease per delay) and check the carried
    state absorbed back into the scheme matches it."""
    model, params, train, test = world
    scheme = FedMPScheme()
    scan = ScanRunner(model, params, LTFL, train, test, scheme,
                      batch_size=8, seed=0, eval_every=0, rng="device",
                      control="device")
    assert scan._segment_spans(0, 6) == [(0, 6)]
    hist = scan.run(6)
    assert scan._n_traces == 1

    # host replay of the bandit over the measured (loss, delay) history
    arms = np.asarray(scheme.arms)
    n, a = 4, len(arms)
    counts = np.zeros((n, a))
    rewards = np.zeros((n, a))
    prev_loss = None
    for rnd, rec in enumerate(hist):
        choice = np.zeros(n, np.int64)
        for u in range(n):
            if np.any(counts[u] == 0):
                choice[u] = int(np.argmin(counts[u]))
            else:
                mean = rewards[u] / counts[u]
                ucb = mean + np.sqrt(2.0 * np.log(rnd + 1) / counts[u])
                choice[u] = int(np.argmax(ucb))
        assert rec.rho_mean == pytest.approx(
            float(np.mean(arms[choice])), abs=1e-6)
        reward = 0.0
        if prev_loss is not None:
            reward = max(prev_loss - rec.train_loss, 0.0) \
                / max(rec.delay, 1e-9)
        counts[np.arange(n), choice] += 1.0
        rewards[np.arange(n), choice] += reward
        prev_loss = rec.train_loss
    np.testing.assert_array_equal(scheme._counts, counts)
    np.testing.assert_allclose(scheme._rewards, rewards, rtol=1e-4,
                               atol=1e-9)
    assert scheme._prev_loss == pytest.approx(hist[-1].train_loss)


# --------------------------------------------------------------------------- #
# vmap over seeds
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["host", "device"])
def test_run_sweep_matches_single_runs(world, mode):
    """Each sweep lane's history equals the corresponding single seeded
    run, and the whole sweep re-uses one vmapped trace per length."""
    model, params, train, test = world
    runner = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                        batch_size=8, seed=0, eval_every=0, rng=mode)
    hists = runner.run_sweep([0, 1, 2], 4)
    assert len(hists) == 3
    assert runner._n_traces == 1       # one vmapped trace, every lane
    assert not runner.history          # the sweep never touches self
    for seed, hist in zip([0, 1, 2], hists):
        solo = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                          batch_size=8, seed=seed, eval_every=0, rng=mode)
        assert_history_parity(solo.run(4), hist, loss_exact=False)


def test_run_sweep_unbiased_uses_each_lanes_population(world):
    """Every replica's population draws its own sample total; the HT
    Gamma/denominator must come from the LANE's population, not the
    prototype runner's (regression: a closure over _pop_samples_total
    silently skewed every non-prototype lane's gamma)."""
    model, params, train, test = world
    kw = dict(batch_size=8, seed=0, eval_every=0, population_size=12,
              cohort_size=4, cohort_sampler=UniformSampler(),
              participation="unbiased")
    runner = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                        **kw)
    hists = runner.run_sweep([0, 1], 3)
    for seed, hist in zip([0, 1], hists):
        solo_kw = dict(kw)
        solo_kw["seed"] = seed
        solo = ScanRunner(model, params, LTFL, train, test, FedSGDScheme(),
                          **solo_kw)
        assert_history_parity(solo.run(3), hist, loss_exact=False)


def test_run_sweep_device_control_matches_solo(world):
    """Sweep lanes under control='device': the carried control state
    (LTFL's memoized decision) stacks per lane, and each lane still
    bit-matches its solo run."""
    model, params, train, test = world
    runner = ScanRunner(model, params, LTFL, train, test,
                        LTFLScheme(recontrol_every=1), batch_size=8,
                        seed=0, eval_every=0, rng="device",
                        control="device")
    hists = runner.run_sweep([0, 1], 3)
    assert runner._n_traces == 1
    for seed, hist in zip([0, 1], hists):
        solo = ScanRunner(model, params, LTFL, train, test,
                          LTFLScheme(recontrol_every=1), batch_size=8,
                          seed=seed, eval_every=0, rng="device",
                          control="device")
        assert_history_parity(solo.run(3), hist, loss_exact=False)


# --------------------------------------------------------------------------- #
# the minimal scanned API (examples / dry-run)
# --------------------------------------------------------------------------- #
def test_make_scanned_step_matches_loop(world):
    model, params, train, _ = world
    C, B, R = 3, 4, 5
    opt = sgd(0.1)
    step = make_fl_train_step(model, opt, C, prune=False, quantize=False,
                              simulate_drops=False)
    imgs = jnp.asarray(train.arrays["images"][:R * C * B]).reshape(
        R, C, B, 32, 32, 3)
    labels = jnp.asarray(train.arrays["labels"][:R * C * B]).reshape(
        R, C, B)
    controls = {"rho": jnp.zeros(C), "delta": jnp.zeros(C),
                "weights": jnp.ones(C), "alpha": jnp.ones(C)}
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(R)])

    p_l, o_l, c_l = params, opt.init(params), step.init_comp_state(params)
    jstep = jax.jit(step)
    for r in range(R):
        p_l, o_l, c_l, _ = jstep(
            p_l, o_l, c_l,
            {"images": imgs[r], "labels": labels[r]}, controls, keys[r])

    scanned = jax.jit(make_scanned_step(step))
    p_s, o_s, c_s, ms = scanned(
        params, opt.init(params), step.init_comp_state(params),
        {"images": imgs, "labels": labels}, controls, keys)
    assert ms["loss"].shape == (R,)
    for a, b in zip(jax.tree_util.tree_leaves(p_l),
                    jax.tree_util.tree_leaves(p_s)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
