"""Population-scale partial participation: N registered devices, U scheduled.

The paper's experiments fix U devices that all transmit every round. Real
wireless FL at the ROADMAP's scale instead has a large *population* of N
registered devices with persistent per-device state, from which the base
station schedules a per-round *cohort* of U << N under its limited radio
resources (cf. "Towards Scalable Wireless Federated Learning" and the
client-scheduling literature). This module is that layer:

* ``Population`` holds the (N,) struct-of-arrays ``ChannelState`` (PR 2)
  plus per-device persistent state that must survive across rounds even
  when a device is not scheduled: the fading epoch of its last channel
  realization, its data shard size and CPU frequency (the latter two live
  inside the ChannelState arrays).  Block fading advances a population
  epoch; realizations are refreshed *lazily*, only for scheduled devices
  (``refresh_fading``), so per-round host work stays O(U) — and unscheduled
  devices carry realistically stale CSI.
* ``CohortSampler`` is the pluggable scheduler protocol: ``select`` maps
  (population, cohort_size, round, rng, ltfl) to the (U,) population
  indices of this round's cohort plus, when well-defined, each member's
  inclusion probability pi_i (what the unbiased 1/(N pi_i)-style
  aggregation in ``FedRunner`` divides by).
* Three schedulers ship: ``UniformSampler`` (uniform without replacement,
  exact pi = U/N), ``ChannelAwareSampler`` (top-U by expected uplink rate
  at a reference power — deterministic, so no inclusion probabilities) and
  ``EnergyAwareSampler`` (probability proportional to per-round energy
  headroom; inclusion probabilities are the EXACT weighted
  without-replacement pi_i via ``gumbel_topk_inclusion``, not the
  first-order U * w_i approximation).
* ``ChurnSpec`` declares Bernoulli arrival/departure processes over the
  registry plus drop-mid-upload faults — consumed by the buffered-async
  engine (repro.fed.async_engine), which expresses them in-scan as
  masked arrivals so the registry layout never changes.

``FedRunner`` gathers the cohort's (U,) ``ChannelState`` view each round
(``ChannelState.take``); Algorithm 1, delay/energy and the Gamma gap run
on the view, and the jitted train step keeps its static (U,)-shaped
controls — changing the sampled cohort never retriggers compilation.

Sharded device-resident population (the million-device registry)
----------------------------------------------------------------
Host numpy caps this layer at N ~ 10^4: the O(N) scheduler scan and the
per-segment (N,) host<->device copies start to rival the compiled round.
``PopulationArrays`` is the device twin — the (N_pad,) ``ChannelArrays``
plus per-device fading epochs, laid out over a 1-D ("pop",) mesh
(repro.launch.sharding.population_mesh; N_pad pads N up to equal shard
blocks, and the pad tail is masked out of every draw). Per-round
population work runs under ``shard_map``:

* the cohort draw is TWO-STAGE: every shard ranks its own block and
  keeps its local top-U (``lax.top_k`` for channel-aware, Gumbel keys
  for energy-aware, uniform keys for uniform), then the S*U local
  winners are all-gathered and the global top-U merged — exact, because
  any global top-U member is a top-U member of its own block. Per-round
  cost is O(N/S) elementwise + O(S*U) merge: N = 10^6 schedules at the
  same wall clock as N = 10^3 (benchmarks/population_scale.py sharded
  sweep);
* the lazy block-fading refresh (``refresh_cohort_dev``) draws O(U)
  fading/interference values and drop-scatters them into each shard's
  block for the scheduled-and-stale members only — host ``Population``
  semantics (schedule on stale CSI, then refresh the cohort), never an
  O(N) redraw;
* the per-device DATA-INDEX table rides the same layout: the scan
  engine's (N_pad, W) int32 ``parts_padded`` (built vectorized from
  ``PackedParts`` — the setup complexity contract in
  repro.data.partition: no O(N) Python loops on the cold-start path)
  shards row-wise over 'pop', and ``gather_parts_dev`` psum-gathers just
  the cohort's (U, W) rows each round — per-device residency and setup
  both scale at N/S, never N.

The host ``Population`` stays the small-N reference: a single-shard mesh
degenerates to the host cohort sequence (seeded-parity-tested in
tests/test_sharded_population.py), and ``host_sync`` folds the device
state back so post-run inspection sees exactly what ran.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import LTFLConfig, WirelessConfig
from repro.control.device_samplers import (
    DeviceSamplerTwin,
    channel_aware_twin,
    energy_aware_twin,
    sharded_channel_aware_twin,
    sharded_energy_aware_twin,
    sharded_uniform_twin,
    uniform_twin,
)
from repro.core.channel import ChannelArrays, ChannelState, draw_fading_dev, \
    expected_rate
from repro.core.delay_energy import local_train_energy
from repro.launch.sharding import population_pad, population_sharding


@dataclass
class Population:
    """Persistent state for N registered devices.

    ``channel`` is the (N,) struct-of-arrays device state (distances, mean
    fading powers, interference, CPU frequencies, shard sizes).
    ``fading_epoch[i]`` records the population epoch at which device i's
    slow fading/interference realization was last drawn; ``epoch`` is the
    current population epoch (bumped once per block-fading round).  A
    device's realization is refreshed only when it is scheduled AND its
    epoch is stale — O(U) per round, never O(N).
    """

    channel: ChannelState          # (N,) persistent per-device state
    fading_epoch: np.ndarray       # (N,) epoch of each device's realization
    epoch: int = 0                 # current population (channel) epoch

    @classmethod
    def sample(cls, cfg: WirelessConfig, num: int, samples_min: int,
               samples_max: int, rng: np.random.Generator,
               dtype=np.float64) -> "Population":
        """Register N devices with one vectorized Table-2 draw (identical
        rng stream to ``ChannelState.sample``, so a population of N == U
        sees the exact devices the pre-population runner saw). ``dtype``
        is the float storage policy (draws stay on the f64 stream and
        cast after — see ChannelState.sample); million-device registries
        pass float32 to halve the resident footprint."""
        state = ChannelState.sample(cfg, num, samples_min, samples_max, rng,
                                    dtype=dtype)
        return cls(channel=state,
                   fading_epoch=np.zeros(num, dtype=np.int64))

    @property
    def num_devices(self) -> int:
        return self.channel.num_devices

    def __len__(self) -> int:
        return self.num_devices

    # ------------------------------------------------------------------ #
    def advance_epoch(self) -> int:
        """Start a new block-fading epoch; realizations refresh lazily."""
        self.epoch += 1
        return self.epoch

    def refresh_fading(self, cfg: WirelessConfig, idx: np.ndarray,
                       rng: np.random.Generator) -> np.ndarray:
        """Re-draw the slow fading/interference realization for the
        scheduled devices ``idx`` whose realization predates the current
        epoch (same per-device draws as ``ChannelState.redraw_fading``:
        fading_scale * Exp(1) mean fading power, Table-2 interference).
        Returns the refreshed indices.  With a full cohort this consumes
        the identical rng stream as the PR-2 full redraw.
        """
        idx = np.asarray(idx, dtype=np.int64)
        stale = idx[self.fading_epoch[idx] < self.epoch]
        if stale.size:
            fading, interference = ChannelState.draw_fading(
                cfg, rng, stale.size)
            self.channel.fading_mean[stale] = fading
            self.channel.interference[stale] = interference
            self.fading_epoch[stale] = self.epoch
        return stale

    def view(self, idx: np.ndarray) -> ChannelState:
        """(U,) cohort view of the channel state (a gathered copy)."""
        return self.channel.take(idx)


# --------------------------------------------------------------------------- #
# Device-resident sharded population (the scan engine's million-N registry)
# --------------------------------------------------------------------------- #
class PopulationArrays(NamedTuple):
    """jnp pytree twin of ``Population``: the (N_pad,) ``ChannelArrays``
    plus per-device fading epochs, every (N_pad,) leaf laid out over the
    1-D ("pop",) mesh. ``epoch`` is the replicated scalar population
    epoch (int32 — bumped per block-fading round inside the scan).
    Indices [n, N_pad) are padding: benign copies of device 0 that every
    sharded sampler masks out of the draw and no cohort ever contains."""

    channel: ChannelArrays       # (N_pad,) leaves, sharded over 'pop'
    fading_epoch: jax.Array      # (N_pad,) int32, sharded over 'pop'
    epoch: jax.Array             # scalar int32, replicated


def device_population(population: Population, mesh: Mesh,
                      dtype=jnp.float32) -> PopulationArrays:
    """Place a host ``Population`` on device, padded to equal per-shard
    blocks and sharded over the mesh's 'pop' axis. One upload per run —
    the scan carries the arrays afterwards (satellite of PR 6: no
    per-segment (N,) round trips)."""
    n = population.num_devices
    n_pad = population_pad(n, mesh)
    sh = population_sharding(mesh)

    def pad(x, out_dtype):
        a = np.asarray(x)
        if n_pad > n:   # benign pad: repeat device 0 (masked everywhere)
            a = np.concatenate([a, np.broadcast_to(a[0], (n_pad - n,))])
        return jax.device_put(a.astype(out_dtype), sh)

    ch = population.channel
    channel = ChannelArrays(
        distance=pad(ch.distance, dtype),
        fading_mean=pad(ch.fading_mean, dtype),
        interference=pad(ch.interference, dtype),
        cpu_hz=pad(ch.cpu_hz, dtype),
        num_samples=pad(ch.num_samples, dtype),
    )
    return PopulationArrays(
        channel=channel,
        fading_epoch=pad(population.fading_epoch, np.int32),
        epoch=jnp.int32(population.epoch))


def refresh_cohort_dev(cfg: WirelessConfig, mesh: Mesh,
                       pop: PopulationArrays, cohort: jax.Array,
                       key: jax.Array) -> PopulationArrays:
    """Traced lazy block-fading refresh (the device twin of
    ``Population.refresh_fading``): draw O(U) fading/interference values
    (``draw_fading_dev`` — same distributions as the host path) and
    scatter them into the scheduled devices whose realization predates
    ``pop.epoch``. Runs under ``shard_map``: each shard translates the
    replicated (U,) cohort into block-local indices and drop-scatters the
    members that fall in its block — per-shard work is O(U), never O(N),
    and the (N_pad,) leaves stay in place on their shards."""
    new_f, new_i = draw_fading_dev(cfg, key, cohort.shape[0])

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P("pop"), P("pop"), P("pop"), P(), P(), P(), P()),
             out_specs=(P("pop"), P("pop"), P("pop")), check_vma=False)
    def scatter(fading, interference, fading_epoch, coh, f, i, epoch):
        blk = fading.shape[0]
        loc = coh - jax.lax.axis_index("pop").astype(jnp.int32) * blk
        in_blk = (loc >= 0) & (loc < blk)
        stale = fading_epoch[jnp.clip(loc, 0, blk - 1)] < epoch
        # out-of-block (and fresh) members scatter to index blk => dropped
        idx = jnp.where(in_blk & stale, loc, blk)
        return (fading.at[idx].set(f, mode="drop"),
                interference.at[idx].set(i, mode="drop"),
                fading_epoch.at[idx].set(epoch, mode="drop"))

    fading, interference, fading_epoch = scatter(
        pop.channel.fading_mean, pop.channel.interference, pop.fading_epoch,
        cohort.astype(jnp.int32), new_f.astype(pop.channel.fading_mean.dtype),
        new_i.astype(pop.channel.interference.dtype), pop.epoch)
    return PopulationArrays(
        channel=pop.channel._replace(fading_mean=fading,
                                     interference=interference),
        fading_epoch=fading_epoch, epoch=pop.epoch)


def gather_cohort_dev(mesh: Mesh, channel: ChannelArrays,
                      cohort: jax.Array) -> ChannelArrays:
    """Traced sharded twin of ``ChannelArrays.take``: the (U,) replicated
    cohort view out of the (N_pad,) sharded registry, via psum-gather —
    each shard contributes the members that fall in its block (zeros
    elsewhere) and one ``psum`` over 'pop' assembles the view. Per-shard
    work is O(U); no shard (and no GSPMD fallback) ever materializes the
    full (N_pad,) operand on one device. The round's (U,)-static control
    plane then runs on the replicated view exactly as in the unsharded
    engine."""

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(None, "pop"), P()),
             out_specs=P(), check_vma=False)
    def gather(leaves, coh):
        blk = leaves.shape[-1]
        loc = coh - jax.lax.axis_index("pop").astype(jnp.int32) * blk
        in_blk = (loc >= 0) & (loc < blk)
        vals = leaves[:, jnp.clip(loc, 0, blk - 1)]
        return jax.lax.psum(jnp.where(in_blk, vals, 0.0), "pop")

    stacked = gather(jnp.stack(tuple(channel)),
                     cohort.astype(jnp.int32))
    return ChannelArrays(*stacked)


def gather_parts_dev(mesh: Mesh, table: jax.Array, sizes: jax.Array,
                     cohort: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Assemble the cohort's partition rows out of the SHARDED parts
    table: ``table`` is the (N_pad, W) int32 per-device data-index table
    laid out over 'pop' (rows), ``sizes`` the matching (N_pad,) shard
    sizes. Returns the replicated ((U, W) rows, (U,) sizes) pair the
    in-scan batch draw consumes — same psum-gather as
    ``gather_cohort_dev`` (each shard contributes the members in its
    block, zeros elsewhere; integer psum is exact), so the gathered rows
    match a replicated-table ``jnp.take`` bit for bit while per-device
    residency stays at N_pad/S rows. Per-shard work is O(U * W);
    the (N_pad, W) table never materializes on one device."""

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P("pop", None), P("pop"), P()),
             out_specs=(P(), P()), check_vma=False)
    def gather(tbl, sz, coh):
        blk = tbl.shape[0]
        loc = coh - jax.lax.axis_index("pop").astype(jnp.int32) * blk
        in_blk = (loc >= 0) & (loc < blk)
        locc = jnp.clip(loc, 0, blk - 1)
        rows = jnp.where(in_blk[:, None], jnp.take(tbl, locc, axis=0), 0)
        s = jnp.where(in_blk, jnp.take(sz, locc), 0)
        return jax.lax.psum(rows, "pop"), jax.lax.psum(s, "pop")

    return gather(table, sizes, cohort.astype(jnp.int32))


def host_sync(population: Population, pop: PopulationArrays) -> None:
    """Fold the device registry back into the host ``Population`` (one
    (N,) download — called once per ``run``, not per segment): realized
    fading/interference, per-device fading epochs and the population
    epoch. Post-run host inspection (views, host samplers, history
    tooling) then sees exactly the state the scan left behind."""
    n = population.num_devices
    ch = population.channel
    ch.fading_mean[:] = np.asarray(pop.channel.fading_mean)[:n]
    ch.interference[:] = np.asarray(pop.channel.interference)[:n]
    population.fading_epoch[:] = np.asarray(pop.fading_epoch)[:n]
    population.epoch = int(pop.epoch)


@dataclass(frozen=True)
class ChurnSpec:
    """Bernoulli device churn over the registry, for the async engine.

    Each round, every alive device departs with probability ``p_depart``
    and every departed device returns with probability ``p_return`` (a
    two-state Markov chain over the (N,) registry — stationary alive
    fraction p_return / (p_depart + p_return) when both are positive).
    Independently, each scheduled upload is dropped mid-flight with
    probability ``p_drop`` (the device trained and transmitted — its
    energy is spent — but the update never completes).

    The async engine consumes this as MASKED ARRIVALS inside the scan:
    the registry, sampler and channel state never change shape or
    layout; a dead or dropped device simply never arrives, so its
    update is excluded from the buffer and its staleness keeps aging.
    """

    p_depart: float = 0.0
    p_return: float = 0.0
    p_drop: float = 0.0

    def __post_init__(self):
        for name in ("p_depart", "p_return", "p_drop"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be a probability in "
                                 f"[0, 1], got {v}")


# --------------------------------------------------------------------------- #
# Cohort samplers (the scheduler protocol)
# --------------------------------------------------------------------------- #
SelectResult = Tuple[np.ndarray, Optional[np.ndarray]]


class CohortSampler:
    """Scheduler protocol: pick this round's cohort out of the population.

    ``select(population, cohort_size, rnd, rng, ltfl)`` returns

    * ``idx``   — (U,) int64 population indices, ascending (a canonical
      order keeps the cohort's identity comparable across rounds and the
      jitted step's control vectors deterministic);
    * ``probs`` — (U,) per-member inclusion probabilities pi_i when the
      scheduler defines them (required by ``FedRunner``'s ``"unbiased"``
      participation mode, which weights device i by N_i / pi_i against the
      fixed population total), or ``None`` for deterministic schedulers.

    Samplers see the *last-known* channel state: under lazy block fading,
    unscheduled devices carry stale CSI — exactly the staleness a real
    scheduler faces.
    """

    def select(self, population: Population, cohort_size: int, rnd: int,
               rng: np.random.Generator, ltfl: LTFLConfig) -> SelectResult:
        raise NotImplementedError

    def device_twin(self, runner) -> Optional[DeviceSamplerTwin]:
        """The traced in-scan scheduler twin (repro.control.
        device_samplers), or None when this scheduler is host-only —
        ``ScanRunner(rng="device")`` routes cohort selection through the
        twin and raises a clear ValueError when there isn't one. The twin
        sees the round's CURRENT carried channel realization (host
        samplers see the lazily-refreshed, possibly stale view) and must
        report inclusion probabilities if the runner aggregates with
        ``participation="unbiased"``."""
        return None

    def sharded_twin(self, runner, mesh: Mesh
                     ) -> Optional[DeviceSamplerTwin]:
        """The shard_map'd twin for a population laid out over ``mesh``'s
        'pop' axis (``ScanRunner(population_sharding=...)``): same
        ``select(ch_pop, key)`` protocol, but ``ch_pop`` is the (N_pad,)
        sharded registry and the draw is the two-stage per-shard-top-k +
        merge (module docstring; repro.control.device_samplers). None
        when this scheduler has no sharded twin — the runner raises at
        construction. The merge is an exact draw from the host sampler's
        distribution; reported inclusion probabilities are exact U/N for
        uniform, but the sharded energy-aware twin keeps the FIRST-ORDER
        pi ~ min(1, U w_i) (the exact per-device pi needs the full (N,)
        weight vector on one shard, which the registry layout forbids —
        the unsharded twin and host sampler are exact)."""
        return None


@dataclass
class UniformSampler(CohortSampler):
    """Uniform without replacement: exact inclusion probability U/N.

    The full-participation case (U == N) is a fast path that returns the
    identity cohort WITHOUT consuming rng state — a population of N with
    cohort U == N therefore reproduces the pre-population ``FedRunner``
    trajectory bit-for-bit.
    """

    def select(self, population, cohort_size, rnd, rng, ltfl):
        n = population.num_devices
        if cohort_size == n:            # full participation: identity cohort
            return np.arange(n, dtype=np.int64), np.ones(n)
        idx = np.sort(rng.choice(n, size=cohort_size, replace=False))
        return idx.astype(np.int64), np.full(cohort_size, cohort_size / n)

    def device_twin(self, runner) -> DeviceSamplerTwin:
        return uniform_twin(runner.population_size, runner.cohort_size)

    def sharded_twin(self, runner, mesh: Mesh) -> DeviceSamplerTwin:
        return sharded_uniform_twin(runner.population_size,
                                    runner.cohort_size, mesh)


@dataclass
class ChannelAwareSampler(CohortSampler):
    """Top-U by expected uplink rate at a reference power (opportunistic
    scheduling on last-known CSI).

    ``explore`` in [0, 1) reserves that fraction of the cohort (at least
    one slot whenever explore > 0) for uniform picks outside the top set
    — without it, lazy block fading never refreshes unscheduled devices'
    CSI and the top set can starve. Deterministic selection has no
    well-defined inclusion probabilities (``probs`` is None): combine
    with ``participation="cohort"``.
    """

    power: Optional[float] = None      # reference power; default mid-range
    explore: float = 0.0

    def select(self, population, cohort_size, rnd, rng, ltfl):
        w = ltfl.wireless
        p_ref = self.power if self.power is not None \
            else 0.5 * (w.p_min + w.p_max)
        rate = expected_rate(w, population.channel,
                             np.full(population.num_devices, p_ref))
        # an explicit explore opt-in must always explore: small cohorts
        # would otherwise truncate explore * U to zero slots and freeze
        # the top set on stale CSI forever
        n_explore = 0 if self.explore <= 0.0 else min(
            cohort_size, max(1, round(self.explore * cohort_size)))
        n_top = cohort_size - n_explore
        order = np.argsort(-rate, kind="stable")
        idx = order[:n_top]
        if n_explore:
            rest = order[n_top:]
            idx = np.concatenate(
                [idx, rng.choice(rest, size=n_explore, replace=False)])
        return np.sort(idx).astype(np.int64), None

    def device_twin(self, runner) -> DeviceSamplerTwin:
        return channel_aware_twin(runner.population_size,
                                  runner.cohort_size, runner.ltfl,
                                  power=self.power, explore=self.explore)

    def sharded_twin(self, runner, mesh: Mesh) -> DeviceSamplerTwin:
        return sharded_channel_aware_twin(
            runner.population_size, runner.cohort_size, runner.ltfl,
            mesh, power=self.power, explore=self.explore)


def gumbel_topk_inclusion(w, k: int, n_quad: int = 64) -> np.ndarray:
    """Exact inclusion probabilities for weighted sampling w/o replacement.

    Gumbel-top-k with log-weights log w_j is the exponential race: draw
    X_j ~ Exp(w_j) and keep the k smallest — the same distribution as
    numpy's sequential renormalized ``choice(replace=False, p=w)``
    (Plackett-Luce). Conditioning on X_i = x, device j beats i with
    probability p_j(x) = 1 - e^{-w_j x}, so

        pi_i = ∫ w_i e^{-w_i x} P[PoisBin({p_j(x)}_{j≠i}) <= k-1] dx.

    Substituting s = e^{-x} and then, PER DEVICE, v = s^{N w_i} (sum w =
    1, so N w_i ~ 1) absorbs the race density exactly:

        pi_i = ∫_0^1 Q_i(v^{1/(N w_i)}) dv,

    a bounded monotone integrand with no endpoint singularity — the raw
    s-integrand carries an s^{N w_i - 1} factor that is singular for
    light devices and makes fixed-node quadrature converge hopelessly
    slowly when k is close to N. ``n_quad``-node Gauss-Legendre on the
    v-form is essentially exact for every k. Per (device, node),
    Q_i is a truncated Poisson-binomial forward DP with device i's own
    arrival probability forced to zero (the leave-one-out convolution
    without the numerically-unstable deconvolution) — O(N^2 k n_quad)
    total, chunked over i to bound memory, and cached per
    (population, config, k) by the sampler.

    Analytic pins (tested): k = 1 gives pi = w exactly; uniform weights
    give k/N; k >= N gives all-ones; sum_i pi_i = k.
    """
    w = np.asarray(w, np.float64)
    n = w.shape[0]
    if k >= n:
        return np.ones(n)
    w = w / np.sum(w)
    a = n * w                                   # race exponents, ~O(1)
    nodes, qwts = np.polynomial.legendre.leggauss(n_quad)
    v = 0.5 * (nodes + 1.0)                     # map [-1, 1] -> (0, 1)
    qwts = 0.5 * qwts
    log_v = np.log(v)                           # (Q,)
    pi = np.empty(n)
    blk = max(1, int(4e6) // (n * n_quad))      # ~32 MB f64 per chunk
    for i0 in range(0, n, blk):
        idx = np.arange(i0, min(i0 + blk, n))
        # per-device nodes s_i(v) = v^(1/a_i); p_j = 1 - s^(a_j)
        log_s = log_v[None, :] / a[idx, None]            # (B, Q)
        p = 1.0 - np.exp(log_s[:, :, None] * a[None, None, :])
        p[np.arange(idx.size), :, idx] = 0.0             # leave i out
        q = 1.0 - p
        # truncated Poisson-binomial DP: F[b, m, c] = P(count == c),
        # counts beyond k-1 dropped (they can never rejoin the CDF)
        F = np.zeros((idx.size, n_quad, k))
        F[:, :, 0] = 1.0
        for j in range(n):
            Fp = q[:, :, j:j + 1] * F
            Fp[:, :, 1:] += p[:, :, j:j + 1] * F[:, :, :-1]
            F = Fp
        pi[idx] = F.sum(axis=2) @ qwts          # ∫ P(count <= k-1) dv
    return np.clip(pi, 0.0, 1.0)


@dataclass
class EnergyAwareSampler(CohortSampler):
    """Probability proportional to per-round energy headroom.

    A device's headroom is E^max minus its full (rho = 0) local-training
    energy (Eq. 35): devices whose compute alone (nearly) exhausts the
    budget are (nearly) never scheduled.  Sampling is weighted without
    replacement; the reported inclusion probabilities are the EXACT
    without-replacement pi_i (``gumbel_topk_inclusion``) — the old
    first-order min(1, U * w_i) overstates pi for heavy devices and
    understates it for light ones, a bias that Horvitz-Thompson
    aggregation (and now the staleness-HT Gamma) inherits directly.

    Headroom depends only on static device attributes (CPU frequency,
    shard size), so the O(N) weight vector is computed once per
    (population, config) and cached — select() stays O(U log N) per
    round. The cache holds a weakref to the population (never a bare
    id(), which CPython reuses after garbage collection) so a sampler
    instance shared across successive runners always recomputes.
    """

    min_headroom: float = 1e-6         # floor so every pi_i stays positive
    _cache: Optional[Tuple[Any, Any, np.ndarray]] = \
        field(default=None, repr=False, compare=False)
    _pi_cache: Optional[Tuple[Any, Any, int, np.ndarray]] = \
        field(default=None, repr=False, compare=False)

    def headroom(self, population: Population, ltfl: LTFLConfig
                 ) -> np.ndarray:
        e_comp = local_train_energy(ltfl.wireless, population.channel, 0.0)
        return np.maximum(ltfl.e_max - e_comp, self.min_headroom)

    def _norm_weights(self, population, ltfl) -> np.ndarray:
        if self._cache is not None:
            pop_ref, cfg, w = self._cache
            if pop_ref() is population and cfg is ltfl:
                return w
        head = self.headroom(population, ltfl)
        w = head / np.sum(head)
        self._cache = (weakref.ref(population), ltfl, w)
        return w

    def _inclusion(self, population, ltfl, cohort_size) -> np.ndarray:
        if self._pi_cache is not None:
            pop_ref, cfg, k, pi = self._pi_cache
            if pop_ref() is population and cfg is ltfl \
                    and k == cohort_size:
                return pi
        pi = gumbel_topk_inclusion(self._norm_weights(population, ltfl),
                                   cohort_size)
        self._pi_cache = (weakref.ref(population), ltfl, cohort_size, pi)
        return pi

    def select(self, population, cohort_size, rnd, rng, ltfl):
        w = self._norm_weights(population, ltfl)
        idx = np.sort(rng.choice(population.num_devices, size=cohort_size,
                                 replace=False, p=w))
        pi_all = self._inclusion(population, ltfl, cohort_size)
        pi = np.clip(pi_all[idx], 1e-9, 1.0)
        return idx.astype(np.int64), pi

    def device_twin(self, runner) -> DeviceSamplerTwin:
        # the twin recomputes the headroom weights in-scan from the
        # population ChannelArrays (static device attributes), so it
        # stays correct per run_sweep lane — no host cache to transfer
        return energy_aware_twin(runner.ltfl, runner.cohort_size,
                                 min_headroom=self.min_headroom)

    def sharded_twin(self, runner, mesh: Mesh) -> DeviceSamplerTwin:
        return sharded_energy_aware_twin(
            runner.ltfl, runner.population_size, runner.cohort_size,
            mesh, min_headroom=self.min_headroom)
