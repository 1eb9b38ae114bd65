"""Buffered-async federated rounds: FedBuff-style aggregation as a scan.

The paper's round model (Eq. 34) is fully synchronous — the slowest
scheduled device gates every round. Real edge fleets don't wait:
asynchronous/buffered aggregation (Nguyen et al.'s FedBuff; the
async/semi-async designs surveyed by Chen et al. and Zhou et al. for
wireless FL) lets the server aggregate whatever arrives. ``AsyncRunner``
is that engine, built so the WHOLE async trajectory still runs as one
compiled ``lax.scan`` per segment, rides ``run_sweep`` lanes, and keeps
the sharded ("pop",) million-device registry unchanged.

The masked-arrival scan contract
--------------------------------
A literal event-driven simulator (a priority queue of in-flight uploads)
cannot live inside ``lax.scan``: its state is ragged and its control flow
data-dependent. The async engine instead expresses EVERY asynchrony
source as a fixed-shape mask over the scheduled cohort, decided inside
the scan from the same delay twins the synchronous engine already
evaluates:

* **arrival**: device u's upload completes at t_u =
  ``device_round_delay_dev`` (local training + uplink, this round's
  channel realization). It ARRIVES iff it is alive, not dropped
  mid-upload, and t_u <= ``deadline`` (the straggler cutoff);
* **buffer**: FedBuff's K-slot buffer admits the first
  ``buffer_size`` arrivals in completion-time order (a rank over
  ``argsort`` of masked t_u — no queue, just a mask). The round closes
  when the buffer fills (at the K-th arrival) or at the deadline
  (``buffered_round_accounting_dev``);
* **churn**: ``ChurnSpec`` Bernoulli departure/return chains over the
  (N,) registry plus drop-mid-upload faults. A dead or dropped device
  simply never arrives — the registry, sampler, and channel state keep
  their shapes, so the sharded registry and every sampler twin work
  unmodified;
* **staleness**: a device whose update misses the buffer keeps training
  against an old model. Per-device counters tau_i (reset on admission,
  +1 per scheduled-but-not-admitted round) ride the scan carry as a
  replicated (N,) leaf, and admitted updates are attenuated by the
  FedBuff weight 1 / sqrt(1 + tau_i).

A non-arrival still BURNS its round energy (it trained and transmitted)
— only its aggregation contribution is masked, via the packet-success
vector alpha. ``received`` therefore reports successfully-applied
updates, and the logged per-round ``delay`` is the buffered-round delay.

The staleness-HT convention
---------------------------
Partial participation already reports a Horvitz-Thompson population
Gamma (PR 3): per-device summands scaled by 1/pi_i plus a
client-sampling variance term. Buffered admission thins participation
further and attenuation discards update mass, so the async engine
extends the convention (``repro.core.convergence``):

* **effective inclusion**: the probability device i's update is APPLIED
  is pi_i * P(admitted | scheduled). The engine logs the plug-in
  pi_i * (n_admitted / U) per round in ``RoundLog.inclusion`` — the
  realized admission fraction estimates the admission probability —
  while the aggregation weights keep the scheduling-time N_i / pi_i
  (staleness-attenuated); the gap the plug-in closes is exactly what
  tests/test_async_engine.py's HT-unbiasedness test measures;
* **staleness term**: per-device tau_i ride ``RoundLog.tau`` out of the
  scan, and ``_absorb_segment`` passes them to the host float64 Eq. 29
  reduction (the PR-9 convention: gamma is NEVER reduced in-jit), which
  adds 12 v1 / N * sum_i N_i (1 - 1/sqrt(1+tau_i)) / pi_i — the
  HT-scaled update mass attenuation threw away. At tau = 0 the term is
  exactly +0.0.

The sync-degenerate contract (test-pinned)
------------------------------------------
``AsyncRunner(deadline=inf, buffer_size=U, churn=None)`` reproduces the
synchronous ``ScanRunner`` history BITWISE, by construction, not by
tolerance: every mask is the arithmetic identity (where(all-True, x, 0)
== x; weights * 1/sqrt(1+0) == weights; pi * (U/U) == pi), churn=None
statically keeps the 7-way key split (so the device rng stream never
shifts), and the buffered accounting shares ``round_accounting_dev``'s
exact expected-rate quadrature and op order. The async state (tau,
alive) rides the carry as an APPENDED last leaf the sync bodies never
see, so the parameter trajectory, the log, and every derived
``RoundRecord`` float are identical.

Control under async rounds: schemes see the buffered world through the
same interfaces — ``LTFLScheme.configure_async`` clamps Algorithm 1's
Eq. 30b delay budget to the deadline, per-cohort re-solves (recontrol
cadence 1 under partial participation) re-optimize against each round's
buffer composition via the carried range/channel state, and FedMP's
bandit feedback learns from the logged buffered-round delay.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delay_energy import (
    buffered_round_accounting_dev,
    device_round_delay_dev,
)
from repro.core.channel import expected_rate_dev
from repro.fed.population import ChurnSpec
from repro.fed.scan_engine import ScanRunner


class _AsyncSpec(NamedTuple):
    """Static async-round constants, baked into every compiled segment
    (and therefore part of the lane bucket signature)."""

    deadline: float          # straggler cutoff on t_u (s); inf = sync
    buffer_size: int         # K: admissions that close the round
    churn: Optional[ChurnSpec]


class AsyncRunner(ScanRunner):
    """``ScanRunner`` with buffered-async rounds (module docstring).

    Additional construction args:

    * ``deadline``: per-device completion cutoff in seconds, measured
      from round start and excluding the server aggregation delay
      (``inf`` disables the cutoff);
    * ``buffer_size``: FedBuff's K — the round closes at the K-th
      arrival (default: the cohort size U, i.e. wait for everyone);
    * ``churn``: a ``ChurnSpec`` (None = a fixed fleet).

    ``deadline=inf, buffer_size=U, churn=None`` IS the synchronous
    engine, bitwise. Per-round async diagnostics (tau, admission masks)
    land on ``async_history``; ``RoundRecord.staleness`` carries the
    cohort-mean tau and the reported gamma includes the staleness-HT
    term.
    """

    def __init__(self, model, params, ltfl, train, test, scheme, *,
                 deadline: float = float("inf"),
                 buffer_size: Optional[int] = None,
                 churn: Optional[ChurnSpec] = None, **kwargs):
        if not deadline > 0.0:
            raise ValueError(f"deadline={deadline} must be positive "
                             "(use inf for no straggler cutoff)")
        if churn is not None and not isinstance(churn, ChurnSpec):
            raise TypeError(f"churn must be a ChurnSpec, got "
                            f"{type(churn).__name__}")
        super().__init__(model, params, ltfl, train, test, scheme,
                         **kwargs)
        u = self.num_devices
        if buffer_size is None:
            buffer_size = u
        if not 1 <= buffer_size <= u:
            raise ValueError(
                f"buffer_size={buffer_size} must be in [1, {u}] (the "
                "cohort size — the buffer admits scheduled arrivals)")
        self._async = _AsyncSpec(float(deadline), int(buffer_size), churn)
        # async carry state, device-resident across segments (same
        # lifecycle as the scan engine's (N,) population leaves)
        self._tau_dev: Optional[jax.Array] = None
        self._alive_dev: Optional[jax.Array] = None
        # host-rng churn replays on its OWN stream: the FedRunner replay
        # stream stays untouched, which is what keeps the churn-free
        # async host-rng trajectory bitwise-equal to ScanRunner's
        self._churn_rng = np.random.default_rng(
            int(kwargs.get("seed", 0)) + 0x5EED)
        self._alive_host = np.ones(self.population_size, bool)
        self.async_history: List[Dict[str, Any]] = []
        self.scheme.configure_async(self)

    # ------------------------------------------------------------------ #
    # lane plumbing
    # ------------------------------------------------------------------ #
    def _lane_extra_kwargs(self) -> Dict[str, Any]:
        return dict(deadline=self._async.deadline,
                    buffer_size=self._async.buffer_size,
                    churn=self._async.churn)

    def _engine_signature(self) -> tuple:
        c = self._async.churn
        return ("async", self._async.deadline, self._async.buffer_size,
                None if c is None else (c.p_depart, c.p_return, c.p_drop))

    # ------------------------------------------------------------------ #
    # async carry state
    # ------------------------------------------------------------------ #
    def _astate(self):
        """The appended carry leaf: tau (N,) f32 — replicated even under
        population sharding, where the admission mask is ordinary math on
        the gathered cohort view — plus the alive (N,) bool chain when
        churn draws in-scan (device rng). Host-rng churn keeps alive on
        the host (masks ride the stacked xs rows)."""
        if self._tau_dev is None:
            self._tau_dev = jnp.zeros(self.population_size, jnp.float32)
        if self._async.churn is not None and self.rng == "device":
            if self._alive_dev is None:
                self._alive_dev = jnp.ones(self.population_size, bool)
            return (self._tau_dev, self._alive_dev)
        return self._tau_dev

    def _host_carry(self):
        return super()._host_carry() + (self._astate(),)

    def _device_carry(self):
        return super()._device_carry() + (self._astate(),)

    # ------------------------------------------------------------------ #
    # host-rng churn: masks precomputed on the dedicated stream
    # ------------------------------------------------------------------ #
    def _prepare_host_segment(self, a: int, b: int):
        xs, consts, ctl0 = super()._prepare_host_segment(a, b)
        churn = self._async.churn
        if churn is not None:
            cohorts = self._reads(xs["cohort"])
            alive_rows, drop_rows = [], []
            for i in range(b - a):
                alive = self._alive_host
                depart = self._churn_rng.random(alive.shape) < \
                    churn.p_depart
                comeback = self._churn_rng.random(alive.shape) < \
                    churn.p_return
                self._alive_host = np.where(alive, ~depart, comeback)
                alive_rows.append(self._alive_host[cohorts[i]])
                drop_rows.append(
                    self._churn_rng.random(cohorts.shape[1]) <
                    churn.p_drop)
            xs["alive_c"] = jnp.asarray(np.stack(alive_rows))
            xs["drop"] = jnp.asarray(np.stack(drop_rows))
        return xs, consts, ctl0

    # ------------------------------------------------------------------ #
    # the in-scan admission hook (called by ScanRunner's bodies)
    # ------------------------------------------------------------------ #
    def _admission(self, ltfl, ch, cohort, alpha, weights, inclusion,
                   rho, power, payload, astate, k_churn, masks):
        """Mask this round's cohort into buffered-async arrivals.

        Runs INSIDE the compiled scan body, after the transmission draw
        and before the train step. Returns the masked
        (alpha, weights, inclusion), the pre-reset staleness tau_c and
        admission mask for the log, the buffered (delay, energy), and
        the updated async carry state. Every branch below is static
        (churn spec, rng mode), so the trace contains only the active
        path."""
        asy = self._async
        churn = asy.churn
        u = cohort.shape[0]
        alive = None
        if churn is None:
            tau_pop = astate
            alive_c = jnp.ones((u,), bool)
            drop = jnp.zeros((u,), bool)
        elif masks is not None:          # host rng: precomputed masks
            tau_pop = astate
            alive_c, drop = masks
        else:                            # device rng: in-scan Bernoulli
            tau_pop, alive = astate
            k_dep, k_ret, k_drop = jax.random.split(k_churn, 3)
            stay = ~jax.random.bernoulli(k_dep, churn.p_depart,
                                         alive.shape)
            comeback = jax.random.bernoulli(k_ret, churn.p_return,
                                            alive.shape)
            alive = jnp.where(alive, stay, comeback)
            alive_c = jnp.take(alive, cohort)
            drop = jax.random.bernoulli(k_drop, churn.p_drop, (u,))
        # arrivals: completion times from the SAME delay twin (and the
        # same shared-rate quadrature) the sync accounting evaluates —
        # XLA CSEs the duplicate against buffered_round_accounting_dev's
        w_cfg = ltfl.wireless
        rate = expected_rate_dev(w_cfg, ch, power)
        t_u = device_round_delay_dev(w_cfg, ch, payload, rho, power,
                                     rate=rate)
        deadline = jnp.float32(asy.deadline)
        arrive = alive_c & (~drop) & (t_u <= deadline)
        # FedBuff buffer: first K arrivals in completion-time order.
        # rank[i] = position of device i in the masked arrival order
        # (non-arrivals sort to the back behind +inf)
        order = jnp.argsort(jnp.where(arrive, t_u, jnp.inf))
        rank = jnp.zeros((u,), jnp.int32).at[order].set(
            jnp.arange(u, dtype=jnp.int32))
        admitted = arrive & (rank < asy.buffer_size)
        # staleness attenuation on the PRE-reset counters; then reset
        # admitted devices, age scheduled-but-missed ones, leave the
        # unscheduled untouched
        tau_c = jnp.take(tau_pop, cohort)
        stale_w = 1.0 / jnp.sqrt(1.0 + tau_c)
        alpha = jnp.where(admitted, alpha, 0.0)
        weights = weights * stale_w
        if inclusion is not None:
            n_adm = jnp.sum(admitted).astype(jnp.float32)
            inclusion = inclusion * (n_adm / jnp.float32(u))
        delay, energy, _ = buffered_round_accounting_dev(
            ltfl, ch, payload, rho, power, admitted, deadline,
            asy.buffer_size)
        tau_pop = tau_pop.at[cohort].set(
            jnp.where(admitted, 0.0, tau_c + 1.0))
        astate = tau_pop if alive is None else (tau_pop, alive)
        return (alpha, weights, inclusion, tau_c, admitted,
                (delay, energy), astate)

    # ------------------------------------------------------------------ #
    # post-segment absorption: strip the async leaf, keep diagnostics
    # ------------------------------------------------------------------ #
    def _absorb_segment(self, a: int, b: int, ctl, carry, log) -> None:
        carry, astate = tuple(carry)[:-1], carry[-1]
        if isinstance(astate, tuple):
            self._tau_dev, self._alive_dev = astate
        else:
            self._tau_dev = astate
        super()._absorb_segment(a, b, ctl, carry, log)

    def _fetch_segment(self, carry, log):
        host = super()._fetch_segment(carry, log)
        host["admitted"] = self._reads(log.admitted, bool)
        return host

    def _record_rounds(self, a, b, ctl, host, gammas) -> None:
        super()._record_rounds(a, b, ctl, host, gammas)
        for i, r in enumerate(range(a, b)):
            self.async_history.append({
                "round": r,
                "tau": host["tau"][i],
                "admitted": host["admitted"][i],
                "n_admitted": int(host["admitted"][i].sum()),
            })

    # host-visible staleness state (tests / serving) ------------------- #
    @property
    def staleness(self) -> np.ndarray:
        """Current per-device tau counters, (N,) float64 on host."""
        if self._tau_dev is None:
            return np.zeros(self.population_size)
        return np.asarray(self._tau_dev, np.float64)
