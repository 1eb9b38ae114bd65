"""Device-resident cohort-sampler twins (the in-scan scheduler).

``ScanRunner(rng="device")`` draws each round's cohort INSIDE the
compiled ``lax.scan``; a host ``CohortSampler`` participates by returning
one of these traced twins from ``device_twin(runner)`` (repro.fed.
population). A twin sees the CURRENT carried channel realization — under
block fading that is this round's fading, fresher CSI than the host
samplers' lazily-refreshed view — and returns the (U,) cohort plus, when
defined, the members' inclusion probabilities pi_i (what the unbiased
Horvitz-Thompson aggregation divides by).

Sampling without replacement on device uses the Gumbel-top-k trick:
adding i.i.d. Gumbel(0, 1) noise to log-weights and taking the top U
keys is distributed EXACTLY as sequential weighted sampling without
replacement (probability proportional to the remaining weights at every
draw) — numpy's ``rng.choice(replace=False, p=w)`` procedure. Inclusion
probabilities keep the host samplers' convention: exact U/N for uniform,
and for the energy-aware weights the EXACT without-replacement pi_i via
the traced quadrature twin of ``repro.fed.population.
gumbel_topk_inclusion`` (tests/test_device_control.py pins the empirical
Gumbel-top-k inclusion against it; the old first-order min(1, U w_i) is
biased exactly where HT aggregation — and the async engine's
staleness-HT Gamma — is most sensitive, at heavy/light weight extremes).

Sharded twins (the million-device registry)
-------------------------------------------
``sharded_*_twin`` are the ``shard_map`` variants for a population laid
out over a 1-D ("pop",) mesh (repro.fed.population.PopulationArrays;
``ScanRunner(population_sharding=...)``). Every draw is TWO-STAGE:

1. each shard scores its own (N_pad/S,) block — uniform keys, a
   monotone-in-rate SNR score, or Gumbel keys — masks the pad tail
   (global index >= N) to -inf, and keeps its local ``lax.top_k``;
2. the S*U local winners' (key, global index) pairs are all-gathered
   and the global top-U merged on every shard.

The merge is EXACT, not approximate: any member of the global top-U is
by definition among the top-U of its own block, so it survives stage 1
(the standard distributed top-k argument). Consequences:

* uniform keys    -> exactly uniform without replacement over N
  (a key-draw replaces ``jax.random.choice``'s O(N log N) permutation);
* Gumbel keys     -> exactly the Gumbel-top-k weighted draw — sharding
  redistributes the computation, not the distribution (normalizing the
  weights only shifts every key by a constant, so the per-shard keys
  skip the global normalizer entirely). Reported pi stays FIRST-ORDER
  min(1, U w_i) here (one ``psum`` for the normalizer): the exact
  leave-one-out quadrature needs the full (N,) weight vector, which the
  registry layout deliberately never materializes on one shard — the
  unsharded twin and the host sampler report exact pi;
* the channel-aware score ranks by mean SNR p*E[h]/(I + B N0) instead
  of the Eq.-1 rate: the Gauss-Laguerre expectation is strictly
  increasing in SNR, so top-U by SNR IS top-U by rate, at O(N/S)
  elementwise instead of O(64 N/S) quadrature — that substitution is
  what holds the N=10^6 draw to ~single-digit ms on a CPU shard.

Per-shard randomness folds the shard index into the round key
(``fold_in``), so shards draw independent streams and the realized
cohort is reproducible for a fixed (key, mesh shape) — but differs from
the unsharded twins' stream, exactly like host-vs-device rng modes.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import numpy as np

from repro.core.channel import ChannelArrays, _mean_gain_dev, _noise_dev, \
    expected_rate_dev
from repro.core.delay_energy import local_train_energy_dev
from repro.launch.sharding import population_pad

SelectFn = Callable[[ChannelArrays, jax.Array],
                    Tuple[jax.Array, Optional[jax.Array]]]


def _gumbel_topk_inclusion_dev(w: jax.Array, k: int,
                               n_quad: int = 64) -> jax.Array:
    """Traced twin of ``repro.fed.population.gumbel_topk_inclusion``:
    exact weighted-without-replacement inclusion probabilities for ALL N
    devices, f32, traceable inside jit/scan. Same exponential-race
    quadrature: the per-device substitution v = s^{N w_i} absorbs the
    race density (no endpoint singularity, so Gauss-Legendre converges
    for every k — the baked-in constants are the host's nodes), and the
    leave-one-out Poisson-binomial CDF forces device i's own arrival
    probability to zero inside the truncated forward DP (a ``lax.map``
    over devices of a ``lax.scan`` DP — no unstable deconvolution).
    O(N^2 k n_quad), but loop-invariant in the round scan whenever the
    weights are (XLA hoists it out of the ``lax.scan`` body, so the
    per-round cost is the (U,) gather)."""
    n = w.shape[0]
    if k >= n:
        return jnp.ones((n,), jnp.float32)
    nodes, qwts = np.polynomial.legendre.leggauss(n_quad)
    log_v = jnp.log(jnp.asarray(0.5 * (nodes + 1.0), jnp.float32))
    qw = jnp.asarray(0.5 * qwts, jnp.float32)
    nw = n * jnp.asarray(w, jnp.float32)

    def per_device(args):
        a_i, i = args
        log_s = log_v / a_i                          # (Q,) nodes for i
        p = 1.0 - jnp.exp(jnp.outer(log_s, nw))      # (Q, N)
        p = p.at[:, i].set(0.0)                      # leave i out
        q = 1.0 - p

        def dp(F, pq):                               # truncated PB DP
            pj, qj = pq
            Fp = qj[:, None] * F
            Fp = Fp.at[:, 1:].add(pj[:, None] * F[:, :-1])
            return Fp, None

        F0 = jnp.zeros((n_quad, k), jnp.float32).at[:, 0].set(1.0)
        F, _ = jax.lax.scan(dp, F0, (p.T, q.T))
        return qw @ jnp.sum(F, axis=1)               # ∫ P(cnt<=k-1) dv

    pi = jax.lax.map(per_device, (nw, jnp.arange(n)))
    return jnp.clip(pi, 0.0, 1.0)


class DeviceSamplerTwin(NamedTuple):
    """Traced scheduler: ``select(ch_pop, key) -> (cohort, pi | None)``.

    ``ch_pop`` is the (N,) population ``ChannelArrays`` at the round's
    carried realization; ``cohort`` is (U,) int32, ascending (the
    engine's canonical order); ``pi`` is the (U,) inclusion probability
    vector, or None for deterministic schedulers (``provides_inclusion``
    mirrors it statically so the engine can validate
    ``participation="unbiased"`` at construction time, before tracing).
    """

    select: SelectFn
    provides_inclusion: bool


def uniform_twin(num_devices: int, cohort_size: int) -> DeviceSamplerTwin:
    """Uniform without replacement; exact pi = U/N. U == N is the
    identity cohort (no key consumed), mirroring the host fast path."""
    n, u = num_devices, cohort_size

    def select(ch_pop: ChannelArrays, key: jax.Array):
        if u == n:
            return jnp.arange(n, dtype=jnp.int32), jnp.ones((n,),
                                                            jnp.float32)
        cohort = jnp.sort(jax.random.choice(
            key, n, (u,), replace=False)).astype(jnp.int32)
        return cohort, jnp.full((u,), jnp.float32(u / n))

    return DeviceSamplerTwin(select=select, provides_inclusion=True)


def channel_aware_twin(num_devices: int, cohort_size: int, ltfl,
                       power: Optional[float] = None,
                       explore: float = 0.0) -> DeviceSamplerTwin:
    """Traced twin of ``ChannelAwareSampler``: top-U by expected uplink
    rate at a reference power, on the CURRENT carried realization (the
    host twin ranks on lazily-refreshed, possibly stale CSI — in-scan
    the realization is always this round's). ``explore`` reserves the
    host sampler's slot count (at least one when explore > 0) for
    uniform picks outside the top set. Deterministic selection has no
    inclusion probabilities."""
    n, u = num_devices, cohort_size
    w = ltfl.wireless
    p_ref = power if power is not None else 0.5 * (w.p_min + w.p_max)
    n_explore = 0 if explore <= 0.0 else min(
        u, max(1, round(explore * u)))
    n_top = u - n_explore

    def select(ch_pop: ChannelArrays, key: jax.Array):
        rate = expected_rate_dev(
            w, ch_pop, jnp.full((n,), jnp.float32(p_ref)))
        # stable descending order (host: argsort(-rate, kind="stable"))
        order = jnp.argsort(-rate, stable=True)
        idx = order[:n_top]
        if n_explore:
            rest = order[n_top:]
            picks = jax.random.choice(key, rest, (n_explore,),
                                      replace=False)
            idx = jnp.concatenate([idx, picks])
        return jnp.sort(idx).astype(jnp.int32), None

    return DeviceSamplerTwin(select=select, provides_inclusion=False)


def energy_aware_twin(ltfl, cohort_size: int,
                      min_headroom: float = 1e-6) -> DeviceSamplerTwin:
    """Traced twin of ``EnergyAwareSampler``: weighted sampling without
    replacement via Gumbel-top-k, probability proportional to per-round
    energy headroom (E^max minus the rho = 0 local-training energy,
    Eq. 35). The (N,) weight vector is recomputed in-scan from the
    population ``ChannelArrays`` — headroom depends only on static device
    attributes (CPU frequency, shard size) that ride along in the struct,
    which keeps the twin correct per ``run_sweep`` lane (each replica's
    population draws different devices) with no host-side cache to
    transfer. Inclusion probabilities are the EXACT without-replacement
    pi_i (``_gumbel_topk_inclusion_dev`` — the Horvitz-Thompson weights
    the unbiased aggregation divides by; pinned against the empirical
    Gumbel-top-k inclusion in tests/test_device_control.py). The exact-pi
    quadrature depends only on the weights, so XLA hoists it out of the
    round scan — per-round cost stays the top-k draw + a (U,) gather."""
    u = cohort_size
    w_cfg = ltfl.wireless
    e_max = float(ltfl.e_max)

    def select(ch_pop: ChannelArrays, key: jax.Array):
        head = jnp.maximum(
            e_max - local_train_energy_dev(w_cfg, ch_pop,
                                           jnp.float32(0.0)),
            jnp.float32(min_headroom))
        w = head / jnp.sum(head)
        keys = jnp.log(jnp.maximum(w, 1e-30)) \
            + jax.random.gumbel(key, w.shape, jnp.float32)
        _, idx = jax.lax.top_k(keys, u)
        cohort = jnp.sort(idx).astype(jnp.int32)
        pi_all = _gumbel_topk_inclusion_dev(w, u)
        pi = jnp.clip(pi_all[cohort], 1e-9, 1.0)
        return cohort, pi

    return DeviceSamplerTwin(select=select, provides_inclusion=True)


# --------------------------------------------------------------------------- #
# sharded twins: two-stage per-shard top-k + cross-shard merge
# --------------------------------------------------------------------------- #
_NEG = jnp.float32(-jnp.inf)


def _check_mesh(num_devices: int, cohort_size: int, mesh: Mesh) -> int:
    """Validate the (N, U, mesh) triple; returns the per-shard block."""
    if "pop" not in mesh.axis_names:
        raise ValueError(f"mesh axes {mesh.axis_names} have no 'pop' axis "
                         "(use repro.launch.sharding.population_mesh)")
    blk = population_pad(num_devices, mesh) // int(mesh.shape["pop"])
    if cohort_size > blk:
        raise ValueError(
            f"cohort_size={cohort_size} exceeds the per-shard block "
            f"{blk} (N={num_devices} over {int(mesh.shape['pop'])} "
            "shards); stage-1 keeps U local winners per shard, so U must "
            "fit in one block — use fewer shards")
    return blk


def _block_gids(blk: int) -> jax.Array:
    """(blk,) GLOBAL indices of this shard's block (inside shard_map)."""
    i = jax.lax.axis_index("pop").astype(jnp.int32)
    return i * blk + jnp.arange(blk, dtype=jnp.int32)


def _merge_topk(vals: jax.Array, gids: jax.Array, k: int) -> jax.Array:
    """Stage 2 (inside shard_map): all-gather the S local (k,) winners
    and take the global top-k. Ties resolve to the lowest global index
    (shards gather in axis order, blocks are index-ordered), matching
    the host samplers' stable descending sort."""
    gv = jax.lax.all_gather(vals, "pop")       # (S, k)
    gi = jax.lax.all_gather(gids, "pop")
    _, mloc = jax.lax.top_k(gv.reshape(-1), k)
    return gi.reshape(-1)[mloc]


def sharded_uniform_twin(num_devices: int, cohort_size: int,
                         mesh: Mesh) -> DeviceSamplerTwin:
    """Sharded ``uniform_twin``: per-shard uniform keys, two-stage top-U
    — an EXACT uniform draw without replacement (every size-U subset has
    the same probability of holding the U largest of N i.i.d. uniform
    keys), with exact pi = U/N, at O(N/S) per shard instead of
    ``jax.random.choice``'s O(N log N) global permutation. U == N stays
    the identity fast path (no key consumed)."""
    n, u = num_devices, cohort_size
    blk = _check_mesh(n, u, mesh)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def draw(key):
        gid = _block_gids(blk)
        noise = jax.random.uniform(
            jax.random.fold_in(key, jax.lax.axis_index("pop")),
            (blk,), jnp.float32)
        keys = jnp.where(gid < n, noise, _NEG)      # pad tail never drawn
        vals, loc = jax.lax.top_k(keys, u)
        return _merge_topk(vals, gid[loc], u)

    def select(ch_pop: ChannelArrays, key: jax.Array):
        if u == n:
            return jnp.arange(n, dtype=jnp.int32), jnp.ones((n,),
                                                            jnp.float32)
        cohort = jnp.sort(draw(key)).astype(jnp.int32)
        return cohort, jnp.full((u,), jnp.float32(u / n))

    return DeviceSamplerTwin(select=select, provides_inclusion=True)


def sharded_channel_aware_twin(num_devices: int, cohort_size: int, ltfl,
                               mesh: Mesh, power: Optional[float] = None,
                               explore: float = 0.0) -> DeviceSamplerTwin:
    """Sharded ``channel_aware_twin``: per-shard top-k on the mean-SNR
    score p * E[h] / (I + B N0) — a strictly monotone surrogate of the
    Eq.-1 expected rate (module docstring), so the merged top-U is the
    host sampler's top-U by rate without the O(64 N) quadrature.
    ``explore`` slots run a second two-stage pass over uniform keys with
    the top set masked out — exactly uniform over the complement.
    Deterministic selection: no inclusion probabilities."""
    n, u = num_devices, cohort_size
    blk = _check_mesh(n, u, mesh)
    w = ltfl.wireless
    p_ref = power if power is not None else 0.5 * (w.p_min + w.p_max)
    n_explore = 0 if explore <= 0.0 else min(
        u, max(1, round(explore * u)))
    n_top = u - n_explore

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("pop"), P()),
             out_specs=P(), check_vma=False)
    def draw(ch, key):
        gid = _block_gids(blk)
        snr = jnp.float32(p_ref) * _mean_gain_dev(ch) / _noise_dev(w, ch)
        score = jnp.where(gid < n, snr, _NEG)
        vals, loc = jax.lax.top_k(score, n_top)
        top = _merge_topk(vals, gid[loc], n_top)
        if n_explore:
            noise = jax.random.uniform(
                jax.random.fold_in(key, jax.lax.axis_index("pop")),
                (blk,), jnp.float32)
            noise = jnp.where(gid < n, noise, _NEG)
            # mask this shard's members of the merged top set (drop-
            # scatter at block-local indices; out-of-block -> dropped)
            loc_top = top - jax.lax.axis_index("pop").astype(jnp.int32) * blk
            in_blk = (loc_top >= 0) & (loc_top < blk)
            noise = noise.at[jnp.where(in_blk, loc_top, blk)].set(
                _NEG, mode="drop")
            nvals, nloc = jax.lax.top_k(noise, n_explore)
            picks = _merge_topk(nvals, gid[nloc], n_explore)
            top = jnp.concatenate([top, picks])
        return top

    def select(ch_pop: ChannelArrays, key: jax.Array):
        return jnp.sort(draw(ch_pop, key)).astype(jnp.int32), None

    return DeviceSamplerTwin(select=select, provides_inclusion=False)


def sharded_energy_aware_twin(ltfl, num_devices: int, cohort_size: int,
                              mesh: Mesh, min_headroom: float = 1e-6
                              ) -> DeviceSamplerTwin:
    """Sharded ``energy_aware_twin``: per-shard Gumbel keys over the
    log-headroom, two-stage top-U — EXACTLY the Gumbel-top-k weighted
    draw without replacement (the global weight normalizer shifts every
    key by the same constant, so shards never need it to select). The
    normalizer enters once, via ``psum``, in the reported inclusion
    probabilities, which here stay FIRST-ORDER pi_i ~ min(1, U w_i):
    the exact leave-one-out quadrature (unsharded twin, host sampler)
    needs the full (N,) weight vector on one shard, which the registry
    layout forbids. The cohort's headroom values come back through a
    psum-gather so no shard ever materializes another's block."""
    n, u = num_devices, cohort_size
    blk = _check_mesh(n, u, mesh)
    w_cfg = ltfl.wireless
    e_max = float(ltfl.e_max)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("pop"), P()),
             out_specs=(P(), P()), check_vma=False)
    def draw(ch, key):
        gid = _block_gids(blk)
        valid = gid < n
        head = jnp.maximum(
            e_max - local_train_energy_dev(w_cfg, ch, jnp.float32(0.0)),
            jnp.float32(min_headroom))
        head = jnp.where(valid, head, 0.0)
        total = jax.lax.psum(jnp.sum(head), "pop")
        gumb = jax.random.gumbel(
            jax.random.fold_in(key, jax.lax.axis_index("pop")),
            (blk,), jnp.float32)
        keys = jnp.where(valid,
                         jnp.log(jnp.maximum(head, 1e-30)) + gumb, _NEG)
        vals, loc = jax.lax.top_k(keys, u)
        cohort = jnp.sort(_merge_topk(vals, gid[loc], u))
        # distributed gather of the cohort's headroom for pi
        loc_c = cohort - jax.lax.axis_index("pop").astype(jnp.int32) * blk
        in_blk = (loc_c >= 0) & (loc_c < blk)
        contrib = jnp.where(in_blk, head[jnp.clip(loc_c, 0, blk - 1)], 0.0)
        head_cohort = jax.lax.psum(contrib, "pop")
        pi = jnp.clip(u * head_cohort / total, 1e-9, 1.0)
        return cohort, pi

    def select(ch_pop: ChannelArrays, key: jax.Array):
        cohort, pi = draw(ch_pop, key)
        return cohort.astype(jnp.int32), pi

    return DeviceSamplerTwin(select=select, provides_inclusion=True)
