"""The paper-scale federated round engine (edge mode), on the unified
batched step.

One round (Section 2, Eq. 8-10/14-15/19-20):
  1. the scheme supplies vectorized controls (rho_u, delta_u, p_u) — for
     LTFL via Algorithm 1 — plus a jit-able compressor spec;
  2. a stacked (C, B, ...) batch is gathered across all clients at once
     (repro.data.ClientBatcher);
  3. the channel outcome alpha_u ~ Bernoulli(1 - q_u(p_u)) (Eq. 4) is
     sampled on host;
  4. ONE compiled call to the unified step (repro.core.ltfl_step) does all
     tensor work: vmapped per-client gradients at the pruned weights
     (Eq. 8/12-13), mask, compress (quantize / sign / ternarize+residual),
     weighted aggregate over received clients (Eq. 19) and the global
     update (Eq. 20). Compressor state (STC residuals) is carried through
     the jit between rounds;
  5. delay (Eq. 34) and energy (Eq. 37) are charged analytically on host
     from the scheme's payload declaration, and Gamma^n (Eq. 29) is
     evaluated with the *measured* per-client gradient ranges — all of it
     broadcast over the struct-of-arrays ChannelState (one array op per
     stage, no per-device Python loops), with packet error rates cached
     per (channel epoch, power vector).

``block_fading=True`` re-draws the slow channel components (mean fading
power + interference; see ChannelState.redraw_fading) every round through
the vectorized sampler; with ``LTFLScheme(recontrol_every=1)`` the
Algorithm-1 controller re-optimizes controls against each round's
channel.

Population-scale partial participation
--------------------------------------
``population_size=N`` registers N >> U devices with persistent per-device
state (repro.fed.population.Population); each round a pluggable
``cohort_sampler`` schedules a cohort of ``cohort_size=U`` devices, and
ONLY the cohort is touched: Algorithm 1 solves controls for the (U,)
cohort view of the channel, the batcher gathers U shards, the jitted step
keeps its static (U,)-shaped inputs (sampling never retriggers
compilation), and accounting/Gamma run on the view — per-round work is
governed by U, not N (benchmarks/population_scale.py). Aggregation follows
``participation``: ``"cohort"`` renormalizes over the received cohort
(Eq. 19 as-is) and ``"unbiased"`` weights device i by N_i / pi_i against
the fixed population total (Horvitz-Thompson; requires a sampler that
reports inclusion probabilities). The default (no population args) is the
degenerate N == U identity cohort with an rng stream bit-identical to the
pre-population engine.

This replaces the former per-device Python loop (O(U) jit dispatches +
host-side compression per round) — the same compiled operator chain now
serves both this edge engine and the datacenter launcher/dry-run.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LTFLConfig
from repro.core.channel import (
    packet_error_rate,
    sample_transmissions,
)
from repro.core.convergence import gap_terms
from repro.core.delay_energy import (
    device_round_delay,
    device_round_energy,
)
from repro.core.ltfl_step import clients_per_step, make_fl_train_step
from repro.data import ArrayDataset, ClientBatcher, dirichlet_partition, \
    iid_partition, population_partition
from repro.fed.population import CohortSampler, Population, UniformSampler
from repro.fed.schemes import BaseScheme
from repro.optim import sgd

PyTree = Any

# PER cache bound: distinct power vectors cached per channel/cohort epoch.
# One epoch rarely sees more than a couple (the decision vector and maybe
# a probe), but block-fading runs over thousands of rounds must not let
# old epochs' entries accumulate — the cache is cleared on every epoch
# change and LRU-bounded within one.
_PER_CACHE_MAX = 8


class HostRoundInputs(NamedTuple):
    """Everything the host decides for one round, in the exact np_rng
    consumption order of ``FedRunner.run_round``. Splitting this out of
    ``run_round`` is what lets the scanned engine (repro.fed.scan_engine)
    precompute a whole segment's rounds on an IDENTICAL rng stream and
    stay seeded-parity with the classic per-round loop by construction."""

    cohort: np.ndarray          # (U,) scheduled population indices
    ctl: Any                    # the scheme's Controls for this round
    weights: np.ndarray         # (U,) aggregation weights
    agg_denom: Optional[float]  # fixed normalizer (unbiased) or None
    batch_idx: np.ndarray       # (U, B) global sample indices
    key: Any                    # the round's jax PRNGKey
    alpha: np.ndarray           # (U,) transmission outcomes (Eq. 4)


@dataclass
class RoundRecord:
    round: int
    train_loss: float
    test_acc: float
    delay: float
    energy: float
    cum_delay: float
    cum_energy: float
    received: int
    gamma: float
    rho_mean: float
    delta_mean: float
    power_mean: float
    # population layer: which devices were scheduled, and what fraction of
    # the registered population they are — history_dict curves stay
    # analyzable per scheme under partial participation. Empty under full
    # participation (the identity cohort is derivable from the record).
    cohort: List[int] = field(default_factory=list)
    participation: float = 1.0
    # buffered-async engine (repro.fed.async_engine): mean staleness tau
    # over the round's cohort. 0.0 on the synchronous engines and in the
    # async engine's sync-degenerate configuration.
    staleness: float = 0.0
    # expert layers' routing counts (scanned engine; NaN without them):
    # the tokens each held expert took over the cohort, max and mean
    # over (MoE layer, held expert), and the share of the assignments
    # that went to experts the layer does not hold
    expert_tokens_max: float = float("nan")
    expert_tokens_mean: float = float("nan")
    absent_share: float = float("nan")


class FedRunner:
    """Shared loop: every scheme runs under identical channel, data and
    accounting so the comparison reproduces the paper's figures.

    ``eval_every`` evaluates test accuracy every k rounds (0 => never);
    ``use_kernels`` routes the 2-D quantization fast path through the
    Pallas kernels (compiled on a TPU, interpreted on the CPU);
    ``block_fading`` re-draws the per-device slow fading/interference
    state at the start of every round (lazily, for the scheduled cohort)
    — combined with ``LTFLScheme(recontrol_every=1)`` the controller
    re-optimizes against each round's channel.

    Population layer: ``population_size`` registers N devices (default:
    ``ltfl.num_devices``), ``cohort_size`` schedules U of them per round
    (default: all N — classic full participation), ``cohort_sampler``
    picks them (default ``UniformSampler``), and ``participation``
    chooses the aggregation convention: ``"cohort"`` (renormalize over
    the received cohort, Eq. 19) or ``"unbiased"`` (Horvitz-Thompson
    N_i / pi_i weights against the fixed population sample total)."""

    def __init__(self, model, params: PyTree, ltfl: LTFLConfig,
                 train: ArrayDataset, test: ArrayDataset,
                 scheme: BaseScheme, *, batch_size: int = 64,
                 non_iid_alpha: float = 0.0, label_key: str = "labels",
                 seed: int = 0, eval_every: int = 1,
                 use_kernels: bool = False, block_fading: bool = False,
                 population_size: Optional[int] = None,
                 cohort_size: Optional[int] = None,
                 cohort_sampler: Optional[CohortSampler] = None,
                 participation: str = "cohort",
                 population_dtype=None):
        if participation not in ("cohort", "unbiased"):
            raise ValueError(f"participation={participation!r} "
                             "(want 'cohort' or 'unbiased')")
        self.model = model
        self.params = params
        self.ltfl = ltfl
        self.scheme = scheme
        self.batch_size = batch_size
        self.eval_every = eval_every
        self.block_fading = block_fading
        self.np_rng = np.random.default_rng(seed)
        self._eval_rng_seed = (seed, 0xE7A1)   # fixed eval batches

        n_pop = (int(population_size) if population_size is not None
                 else ltfl.num_devices)
        u = int(cohort_size) if cohort_size is not None else n_pop
        if n_pop < 1:
            raise ValueError(f"population_size={n_pop} must be >= 1")
        if not 1 <= u <= n_pop:
            raise ValueError(f"cohort_size={u} must be in [1, {n_pop}]")
        self.population_size = n_pop
        self.cohort_size = u
        self.num_devices = u          # the engine's static client width
        self.participation = participation
        self.sampler = cohort_sampler or UniformSampler()

        # float storage policy for the (N,) per-device registry: None =>
        # f64 (the control plane's host precision, unchanged default);
        # million-device populations pass np.float32 — the draws stay on
        # the f64 rng stream either way (cast after drawing), so the
        # dtype never changes WHICH devices a seed registers
        self.population_dtype = np.dtype(
            population_dtype if population_dtype is not None
            else np.float64)
        self.population = Population.sample(
            ltfl.wireless, n_pop, ltfl.samples_min, ltfl.samples_max,
            self.np_rng, dtype=self.population_dtype)
        self._pop_samples_total = float(
            np.sum(self.population.channel.num_samples))
        self._channel_epoch = 0
        self._cohort_epoch = 0
        self.cohort = np.arange(u, dtype=np.int64)
        self._cohort_probs: Optional[np.ndarray] = None   # set per round
        self.channel = self.population.view(self.cohort)
        self._per_cache: "OrderedDict[bytes, np.ndarray]" = OrderedDict()
        self._per_cache_epoch = (-1, -1)

        # the (N,) shard-size vector stays an ndarray end to end: at
        # population scale a .tolist() here is an O(N) Python
        # materialization before the vectorized partition even starts
        sizes = self.population.channel.num_samples
        if non_iid_alpha > 0:
            parts = dirichlet_partition(train.arrays[label_key], sizes,
                                        non_iid_alpha, self.np_rng)
        elif population_size is None:
            # classic runner: disjoint shards, fail fast when the pool
            # cannot supply them (iid_partition's oversubscription guard)
            parts = iid_partition(train.size, sizes, self.np_rng)
        else:
            # explicit population: shards over a fixed simulation pool
            # (bit-identical to iid_partition while the pool suffices;
            # N-device populations don't need N * size distinct samples)
            parts = population_partition(train.size, sizes, self.np_rng)
        self.batcher = ClientBatcher(train, parts)
        self.test = test

        self.num_params = int(sum(
            np.prod(l.shape) for l in jax.tree_util.tree_leaves(params)))
        # per-device gradient-range mass, persistent across rounds: cohort
        # members update theirs from the measured metrics; the rest keep
        # the conservative prior until first scheduled
        self._range_sq_pop = np.full(n_pop, 1e-2 * self.num_params)

        self.opt = sgd(ltfl.learning_rate)
        self.opt_state = self.opt.init(params)
        self._eval_fn = jax.jit(model.accuracy) if hasattr(model, "accuracy") \
            else None
        scheme.setup(self)

        # the unified engine: every scheme's round is ONE compiled call,
        # shaped (U,) — cohort sampling swaps values, never shapes
        self._use_kernels = bool(use_kernels)
        self._clients_per_chunk = clients_per_step(params, self.num_devices)
        step_fn = make_fl_train_step(
            model, self.opt, self.num_devices,
            prune=scheme.uses_prune, prune_kind="magnitude",
            compressor=scheme.compressor(use_kernels=use_kernels),
            simulate_drops=False, use_kernels=use_kernels,
            clients_per_chunk=self._clients_per_chunk)
        self.comp_state = step_fn.init_comp_state(params)
        self._step_fn = step_fn          # pure step (the scan engine's body)
        self._step = jax.jit(step_fn)

        self.history: List[RoundRecord] = []
        self._cum_delay = 0.0
        self._cum_energy = 0.0

    # ------------------------------------------------------------------ #
    def _scan_shape_signature(self) -> tuple:
        """The static half of a scanned trace: every runner-level value
        that a compiled segment bakes in as a python constant — array
        shapes (cohort width, population, batch, parameter count),
        static loop bounds (Algorithm 1's BO draw count and alternation
        cap), and the hyperparameters closed over by the step function
        (kernel routing). ``ScanRunner.run_sweep`` groups heterogeneous
        lanes into one compiled program per distinct signature; config
        values NOT listed here are laned — stacked per lane and read
        in-trace (``scan_engine._LANED_WIRELESS`` / ``_LANED_LTFL``; the
        learning rate rides those laned consts into the step's
        ``controls["lr"]``, so lr-only grids share one bucket)."""
        return (self.num_devices, self.population_size, self.batch_size,
                self.num_params, self.eval_every, self.participation,
                self.block_fading, self._use_kernels,
                int(self.ltfl.bo_iters), int(self.ltfl.alt_max_iters))

    @property
    def devices(self):
        """Legacy tuple-of-DeviceChannel view of the cohort channel."""
        return self.channel.to_devices()

    @property
    def channel_epoch(self) -> int:
        """Bumped whenever the channel realization changes (block fading);
        PER caches and control decisions are valid for one epoch."""
        return self._channel_epoch

    @property
    def cohort_epoch(self) -> int:
        """Bumped whenever the scheduled cohort's composition changes; a
        per-device control decision is only valid for the cohort it was
        solved for."""
        return self._cohort_epoch

    @property
    def range_sq_estimates(self) -> np.ndarray:
        """(U,) gradient-range mass for the CURRENT cohort (what the
        Algorithm-1 controller consumes)."""
        return self._range_sq_pop[self.cohort]

    def _packet_error_rates(self, ctl) -> np.ndarray:
        """(U,) PERs at ctl.power — from the scheme's decision when fresh,
        else from a per-epoch LRU cache keyed on the power vector. The
        cache is cleared whenever the channel or cohort epoch changes and
        bounded to ``_PER_CACHE_MAX`` entries, so thousands of
        block-fading rounds never accumulate stale epochs' entries."""
        if ctl.per is not None:
            return np.asarray(ctl.per, np.float64)
        epoch = (self._channel_epoch, self._cohort_epoch)
        if self._per_cache_epoch != epoch:
            self._per_cache.clear()
            self._per_cache_epoch = epoch
        power = np.asarray(ctl.power, np.float64)
        key = power.tobytes()
        hit = self._per_cache.get(key)
        if hit is not None:
            self._per_cache.move_to_end(key)
            return hit
        per = packet_error_rate(self.ltfl.wireless, self.channel, power)
        self._per_cache[key] = per
        if len(self._per_cache) > _PER_CACHE_MAX:
            self._per_cache.popitem(last=False)
        return per

    def _aggregation_weights(self):
        """Per-round aggregation weights for the cohort view, plus the
        fixed denominator (or None => renormalize over received).

        ``"cohort"``: w_i = N_i, denominator sum_received N_i — the
        paper's Eq. 19 applied to the cohort. ``"unbiased"``: w_i =
        N_i / pi_i, denominator sum_population N_j — the Horvitz-Thompson
        estimate of the full-population update (equal in expectation,
        over cohort draws, to full participation)."""
        ns = self.channel.num_samples.astype(np.float64)
        if self.participation == "cohort":
            return ns, None
        if self._cohort_probs is None:
            raise ValueError(
                "participation='unbiased' needs a cohort sampler that "
                f"reports inclusion probabilities; "
                f"{type(self.sampler).__name__} does not")
        return ns / np.maximum(self._cohort_probs, 1e-12), \
            self._pop_samples_total

    # ------------------------------------------------------------------ #
    def _eval_batches(self, max_batches: int = 4,
                      batch: int = 256) -> List[Dict[str, np.ndarray]]:
        """The FIXED seeded eval batches ``evaluate`` scores — split out
        so the scanned engine's in-scan eval head (repro.fed.scan_engine,
        ``control="device"``) can upload the identical batches once and
        evaluate them inside the compiled segment. A test set smaller
        than one batch is scored once, as one batch of all its rows."""
        eval_rng = np.random.default_rng(self._eval_rng_seed)
        if self.test.size < batch:
            batch, max_batches = self.test.size, 1
        return [self.test.batch(batch, eval_rng)
                for _ in range(max_batches)]

    def evaluate(self, max_batches: int = 4, batch: int = 256) -> float:
        """Test accuracy over FIXED eval batches: the rng is re-seeded per
        call, so scheme-comparison curves carry no eval sampling noise."""
        if self._eval_fn is None:
            return float("nan")
        accs = []
        for b in self._eval_batches(max_batches, batch):
            accs.append(float(self._eval_fn(
                self.params, {k: jnp.asarray(v) for k, v in b.items()})))
        return float(np.mean(accs))

    # ------------------------------------------------------------------ #
    def _host_round_inputs(self, rnd: int) -> HostRoundInputs:
        """Advance all host-side per-round state (block-fading epoch,
        cohort schedule, scheme controls, batch draw, round key, channel
        outcomes) and return the round's inputs. The np_rng consumption
        order here IS the engine's seeded contract: the scanned engine
        replays this exact method per round when precomputing a segment."""
        ltfl, w = self.ltfl, self.ltfl.wireless
        if self.block_fading:
            # new block-fading epoch: realizations refresh lazily below,
            # only for the scheduled cohort; the epoch bump invalidates
            # PER caches + any stale LTFL decision PERs
            self.population.advance_epoch()
            self._channel_epoch += 1

        # ---- schedule this round's cohort (population layer) ----------- #
        cohort, probs = self.sampler.select(
            self.population, self.cohort_size, rnd, self.np_rng, ltfl)
        cohort = np.asarray(cohort, np.int64)
        if not np.array_equal(cohort, self.cohort):
            self._cohort_epoch += 1      # per-device decisions now stale
        self.cohort = cohort
        self._cohort_probs = None if probs is None \
            else np.asarray(probs, np.float64)
        self.population.refresh_fading(w, cohort, self.np_rng)
        self.channel = self.population.view(cohort)

        ctl = self.scheme.controls(rnd)
        weights, agg_denom = self._aggregation_weights()
        batch_idx = self.batcher.batch_indices(self.batch_size, self.np_rng,
                                               clients=cohort)
        key = jax.random.PRNGKey(
            int(self.np_rng.integers(0, 2 ** 31 - 1)))
        alpha = sample_transmissions(w, self.channel, ctl.power, self.np_rng)
        return HostRoundInputs(cohort=cohort, ctl=ctl, weights=weights,
                               agg_denom=agg_denom, batch_idx=batch_idx,
                               key=key, alpha=alpha)

    def run_round(self, rnd: int) -> RoundRecord:
        ltfl = self.ltfl
        h = self._host_round_inputs(rnd)
        cohort, ctl, weights, agg_denom, alpha = \
            h.cohort, h.ctl, h.weights, h.agg_denom, h.alpha
        key = h.key

        batch = {k: jnp.asarray(v[h.batch_idx])
                 for k, v in self.batcher.base.arrays.items()}
        controls = {
            "rho": jnp.asarray(ctl.rho, jnp.float32),
            "delta": jnp.asarray(ctl.delta, jnp.float32),
            "weights": jnp.asarray(weights, jnp.float32),
            "alpha": jnp.asarray(alpha, jnp.float32),
        }
        if agg_denom is not None:
            controls["agg_denom"] = jnp.float32(agg_denom)

        # all tensor work for the round: one jit dispatch (Eq. 8-20)
        self.params, self.opt_state, self.comp_state, m = self._step(
            self.params, self.opt_state, self.comp_state, batch, controls,
            key)
        rsqs = np.asarray(m["range_sq"], np.float64)
        self._range_sq_pop[cohort] = rsqs

        # ---- accounting (Eq. 31-37): one array op over the cohort axis - #
        w = ltfl.wireless
        payloads = np.asarray(self.scheme.payload_bits(ctl), np.float64)
        rho = np.asarray(ctl.rho, np.float64)
        power = np.asarray(ctl.power, np.float64)
        delay = float(np.max(device_round_delay(
            w, self.channel, payloads, rho, power))) + ltfl.server_delay
        energy = float(np.sum(device_round_energy(
            w, self.channel, payloads, rho, power)))
        self._cum_delay += delay
        self._cum_energy += energy

        pers = self._packet_error_rates(ctl)
        deltas_for_gap = np.where(ctl.delta > 0, ctl.delta, 32.0)
        # unbiased mode: HT estimate of the POPULATION Gamma + a
        # client-sampling variance term
        gap_kw = ({"inclusion": self._cohort_probs,
                   "population_samples": self._pop_samples_total}
                  if self.participation == "unbiased" else {})
        g_terms = gap_terms(ltfl, rsqs, deltas_for_gap, rho, pers,
                            self.channel.num_samples, **gap_kw)

        rec = RoundRecord(
            round=rnd,
            train_loss=float(m["loss"]),
            test_acc=(self.evaluate()
                      if self.eval_every and rnd % self.eval_every == 0
                      else float("nan")),
            delay=float(delay),
            energy=float(energy),
            cum_delay=self._cum_delay,
            cum_energy=self._cum_energy,
            received=int(np.sum(alpha)),
            gamma=float(g_terms.total),
            rho_mean=float(np.mean(ctl.rho)),
            delta_mean=float(np.mean(ctl.delta)),
            power_mean=float(np.mean(ctl.power)),
            # full participation (U == N) always schedules the identity
            # cohort — elide it so classic histories don't carry N ints
            # of derivable data per round
            cohort=(cohort.tolist()
                    if self.cohort_size < self.population_size else []),
            participation=self.cohort_size / self.population_size,
        )
        self.history.append(rec)
        self.scheme.post_round(rnd, {"train_loss": rec.train_loss,
                                     "delay": rec.delay,
                                     "test_acc": rec.test_acc})
        return rec

    def run(self, num_rounds: int, log_every: int = 0) -> List[RoundRecord]:
        for rnd in range(num_rounds):
            rec = self.run_round(rnd)
            if log_every and rnd % log_every == 0:
                print(f"[{self.scheme.name}] round={rnd:4d} "
                      f"loss={rec.train_loss:.4f} acc={rec.test_acc:.3f} "
                      f"delay={rec.delay:9.1f}s energy={rec.energy:8.2f}J "
                      f"recv={rec.received}/{self.num_devices}")
        return self.history

    def history_dict(self) -> List[Dict]:
        return [asdict(r) for r in self.history]
