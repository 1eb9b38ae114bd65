"""The device-resident experiment engine: ``lax.scan`` over rounds,
``vmap`` over seeds.

The classic ``FedRunner`` pays one host<->device round trip per round:
channel sampling, cohort selection, PER lookup, delay/energy accounting
and Gamma all run in numpy between single-round jit dispatches. For the
paper's experiment regime — many-round, many-seed accuracy-vs-round
sweeps over small edge models — that dispatch overhead IS the cost.
``ScanRunner`` folds whole *segments* of rounds into ONE compiled
``lax.scan`` whose body is the unified train step (repro.core.ltfl_step)
plus the jnp-native accounting twins (``packet_error_rate_dev``,
``device_round_delay_dev`` / ``_energy_dev``), and ``run_sweep`` batches
S seeded replicas of the whole experiment through ``vmap`` so a
scheme-comparison curve costs one compile. Gamma (Eq. 29) is the one
diagnostic NOT reduced in-scan: its per-device input vectors ride
``RoundLog`` and the host reduces them in float64 afterwards
(``_absorb_segment``), so every ``run_sweep`` lane and its solo run
share one numpy code path and report bit-identical gamma — in-jit
reductions lower differently under the sweep ``vmap`` (reduce strategy,
FMA fusion) and drift by a ulp.

Segmentation
------------
With the default ``control="host"``, host-side work — Algorithm 1's
Bayesian-optimized power control and ``evaluate()`` — runs BETWEEN
scans: the round range is split at recontrol/eval boundaries, so
``LTFLScheme(recontrol_every=k)`` scans segments of length k and the
classic per-round ``FedRunner`` is exactly the ``max_segment=1``
degenerate case. One trace is paid per DISTINCT segment length (the scan
body compiles once regardless of trip count); equal-length segments
reuse the compiled executable.

``control="device"`` (requires ``rng="device"``) removes those
boundaries entirely: Algorithm-1 recontrol runs INSIDE the scan through
the scheme's ``scan_control_program`` (repro.control — ``solve_dev``'s
traced Theorems 2/3 + fixed-shape BO for LTFL, the carried UCB bandit
for FedMP), and eval runs in-scan against the same fixed seeded batches
``evaluate()`` scores (the accuracy rides ``RoundLog``). The planner
then coalesces what would have been per-round segments into one scanned
range — ``LTFLScheme(recontrol_every=1)`` over R rounds is ONE segment,
one trace, and each round's recontrol sees that round's OWN fading
realization and cohort (fresh CSI, where host recontrol under
``rng="device"`` could only ever see segment-start state).

Two rng modes
-------------
* ``rng="host"`` (default): every random decision (cohort draw, fading
  refresh, batch indices, round key, transmission outcomes) is
  precomputed on the host by replaying ``FedRunner._host_round_inputs``
  on the IDENTICAL np_rng stream and fed to the scan as stacked per-round
  inputs. Histories are seeded-parity with ``FedRunner.run`` by
  construction (accounting is f32 on device vs float64 on host, so
  delay/energy/Gamma agree to tolerance; the tensor trajectory is
  bit-comparable for stateless schemes).
* ``rng="device"``: the scan body carries a ``jax.random`` key stream and
  draws everything on device. Cohort selection routes through the host
  sampler's ``device_twin()`` (repro.control.device_samplers): uniform
  without replacement, channel-aware ``lax.top_k``, or energy-aware
  Gumbel-top-k weighted choice with Horvitz-Thompson inclusion
  probabilities; a sampler with no twin raises at construction. Block
  fading redraws via ``draw_fading_dev``, batch draws via ``randint``,
  packet outcomes via ``sample_transmissions_dev``. Zero per-round host
  work; an independent (jax, not numpy) rng stream over the same
  distributions, with one deliberate simplification: per-client
  minibatches are drawn WITH replacement (bootstrap), where the host
  batcher draws without replacement whenever a shard covers the batch —
  a slightly different within-round gradient-noise profile.

NOTE the inherited default ``eval_every=1`` evaluates after EVERY round,
which under ``control="host"`` (by the segmentation rule) degenerates
every segment to length 1 — correct, but no faster than ``FedRunner``.
Pass ``eval_every=0`` (or a cadence of k rounds) to actually amortize,
or ``control="device"`` to evaluate in-scan; ``run`` warns once
otherwise.

Sweep lanes
-----------
``run_sweep`` batches whole experiments as vmapped LANES of one compiled
segment — originally seeded replicas, now heterogeneous configs: a
``SweepSpec`` stacks scheme ablations, channel regimes and U/N cohort
grids as lanes. Two mechanisms make one trace serve many configs:

* **laned config**: the lane-varying half of the LTFL/wireless config
  (power bounds, bandwidth, noise, budgets — ``_LANED_WIRELESS`` /
  ``_LANED_LTFL``) rides the segment constants as f32 scalar leaves and
  is rehydrated in-trace into a per-lane config VIEW (``_laned_ltfl``),
  so every regime-dependent expression reads traced values. Solo ``run``
  uses the identical laned trace, which is what makes a lane bitwise
  equal to its solo run;
* **shape buckets**: everything NOT laned — array shapes (U, N, batch),
  static loop bounds (BO iterations), step-function hyperparameters
  (compressor constants; the learning rate itself is LANED, riding the
  segment consts into ``controls["lr"]``) — is baked into the trace and
  therefore part of the lane's bucket signature
  (``_lane_signature``). ``run_sweep`` groups lanes by signature and
  compiles ONE program per bucket, not one per config: an 8-config
  scheme x regime grid over two cohort widths costs a handful of traces.

Recontrol cadence: a ``ControlProgram`` with ``every=k > 1`` declares
that it only re-decides every k rounds. The planner aligns segment
boundaries to that cadence and passes a STATIC ``decide_first`` flag, so
hold rounds scan through a trace that never embeds the Algorithm-1
solve — a ``lax.cond`` would lower to a select under the sweep vmap and
pay the solve every round in every lane.
"""
from __future__ import annotations

import copy
import dataclasses
import warnings
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import spans
from repro.core.channel import (
    ChannelArrays,
    draw_fading_dev,
    packet_error_rate_dev,
    sample_transmissions_dev,
)
from repro.core.convergence import gamma
from repro.core.delay_energy import round_accounting_dev
from repro.fed.population import (
    PopulationArrays,
    UniformSampler,
    device_population,
    gather_cohort_dev,
    gather_parts_dev,
    host_sync,
    refresh_cohort_dev,
)
from repro.fed.rounds import FedRunner, RoundRecord
from repro.launch.sharding import (
    base_rules,
    make_pspec,
    population_mesh,
    population_pad,
)

PyTree = Any

# The lane-varying ("laned") config fields: stacked per lane as f32
# scalars in the segment constants and read in-trace, so one compiled
# program serves every channel regime / budget in a shape bucket.
# Everything else on the configs is STATIC — baked into the trace from
# the bucket representative (shapes, BO/alternation loop bounds) or
# consumed on the host (population draws, partitions) — and therefore
# part of the bucket signature (``_lane_signature``), never laned.
# ``learning_rate`` lanes through ``controls["lr"]`` into the step's
# ``Optimizer.update_with_lr`` — lr-only grids share one bucket.
_LANED_WIRELESS = (
    "p_max", "p_min", "bandwidth_ul", "n0", "waterfall", "fading_scale",
    "interference_min", "interference_max", "cycles_per_sample", "k_eff",
    "sigma_exp")
_LANED_LTFL = (
    "rho_max", "delta_max", "xi_bits", "t_max", "e_max", "server_delay",
    "bo_xi", "alt_tol", "lipschitz", "d_sq", "v1", "v2", "learning_rate")


def _rebuild_config(cfg, overrides):
    """Dataclass copy with field overrides that BYPASSES __post_init__:
    its validation (range checks, ``v2 < 1/12``) calls ``bool()`` on
    values that are vmap tracers here."""
    out = object.__new__(type(cfg))
    for f in dataclasses.fields(cfg):
        object.__setattr__(out, f.name,
                           overrides.get(f.name, getattr(cfg, f.name)))
    return out


def _laned_ltfl(ltfl, cfg):
    """The traced per-lane config view: ``ltfl`` with every laned field
    replaced by its (possibly per-lane-traced) f32 leaf from ``cfg``."""
    wireless = _rebuild_config(
        ltfl.wireless, {k: cfg["w_" + k] for k in _LANED_WIRELESS})
    over: Dict[str, Any] = {k: cfg[k] for k in _LANED_LTFL}
    over["wireless"] = wireless
    return _rebuild_config(ltfl, over)


class RoundLog(NamedTuple):
    """Stacked per-round outputs of one scanned segment — the traced
    mirror of ``RoundRecord``'s measured fields (leading axis = round).
    Host-derivable fields (cum sums in f64) are filled in by the runner
    afterwards. ``test_acc`` and the control means are live only under
    ``control="device"`` (in-scan eval / in-scan recontrol); host-control
    segments fill them from the segment constants (means) and NaN
    (test_acc, which the host evaluates between segments instead).

    Gamma (Eq. 29) is deliberately NOT reduced in-scan: the ``range_sq``
    .. ``agg_denom`` fields carry its measured per-device inputs out of
    the scan and ``_absorb_segment`` reduces them on host in float64 —
    one shared numpy code path, so run_sweep lanes and solo runs report
    bit-identical gamma (see the module docstring)."""

    train_loss: jax.Array   # (R,)
    delay: jax.Array        # (R,)  Eq. 34 incl. server delay
    energy: jax.Array       # (R,)  Eq. 37 summed
    received: jax.Array     # (R,)  sum alpha
    range_sq: jax.Array     # (R, U) measured per-device range^2 sums
    gap_delta: jax.Array    # (R, U) applied delta (32 where delta == 0)
    rho_u: jax.Array        # (R, U) applied pruning ratios
    pers: jax.Array         # (R, U) packet error rates at applied power
    ns_u: jax.Array         # (R, U) cohort sample counts
    inclusion: Optional[jax.Array]  # (R, U) HT pi_i; None unless unbiased
    agg_denom: Optional[jax.Array]  # (R,) HT denominator; None likewise
    cohort: jax.Array       # (R, U) scheduled population indices
    test_acc: jax.Array     # (R,)  in-scan eval head (NaN when not due)
    rho_mean: jax.Array     # (R,)  mean of the round's applied controls
    delta_mean: jax.Array   # (R,)
    power_mean: jax.Array   # (R,)
    # buffered-async fields (repro.fed.async_engine); None on the
    # synchronous engine, where the pytree simply has no such leaves
    tau: Optional[jax.Array] = None       # (R, U) staleness tau_i
    admitted: Optional[jax.Array] = None  # (R, U) buffer admission mask


def make_scanned_step(step_fn: Callable) -> Callable:
    """Wrap a unified FL step into one compiled multi-round segment.

    ``scanned(params, opt_state, comp_state, batches, controls, keys)``
    runs ``batches.shape[0]`` rounds in a single ``lax.scan``: ``batches``
    leaves carry a leading round axis (R, C, B, ...), ``keys`` is (R, 2),
    and ``controls`` is held constant across the segment. Returns the
    final (params, opt_state, comp_state) plus the per-round stacked
    metrics pytree. This is the minimal scanned API used by the
    datacenter example / dry-run; ``ScanRunner`` is the full edge engine.
    """

    def scanned(params, opt_state, comp_state, batches, controls, keys):
        def body(carry, x):
            p, o, c = carry
            batch, key = x
            p, o, c, m = step_fn(p, o, c, batch, controls, key)
            return (p, o, c), m

        (params, opt_state, comp_state), metrics = jax.lax.scan(
            body, (params, opt_state, comp_state), (batches, keys))
        return params, opt_state, comp_state, metrics

    return scanned


@dataclasses.dataclass(frozen=True)
class LaneSpec:
    """One vmapped lane of a heterogeneous ``run_sweep``.

    * ``seed``: the lane's np_rng / population / key-stream seed;
    * ``scheme_factory``: builds the lane's scheme (None deep-copies the
      parent runner's scheme as constructed — the seeded-replica case);
    * ``ltfl``: the lane's ``LTFLConfig`` (None inherits the parent's).
      Laned float fields (channel regime, budgets — see
      ``_LANED_WIRELESS`` / ``_LANED_LTFL``) vary freely WITHIN a
      compiled bucket; static fields (``num_devices``, learning rate, BO
      iteration counts) are part of the bucket signature and lanes that
      differ in them land in separate buckets;
    * ``kwargs``: per-lane overrides of the parent's construction kwargs
      (``population_size``, ``cohort_size``, ``batch_size``, ... — the
      U/N grid axis). Shape-changing overrides open a new bucket;
    * ``label``: free-form tag carried through to results tables.
    """

    seed: int = 0
    scheme_factory: Optional[Callable[[], Any]] = None
    ltfl: Optional[Any] = None
    kwargs: Optional[Dict[str, Any]] = None
    label: str = ""


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A heterogeneous experiment grid for ``ScanRunner.run_sweep``: the
    lanes run vmapped, one compiled program per static-shape bucket.
    ``grid`` builds the usual cross product (the paper-table shape)."""

    lanes: Tuple[LaneSpec, ...]

    def __post_init__(self):
        object.__setattr__(self, "lanes", tuple(self.lanes))
        if not self.lanes:
            raise ValueError("SweepSpec needs at least one lane")

    @classmethod
    def grid(cls, *, schemes: Optional[Dict[str, Any]] = None,
             ltfls: Optional[Dict[str, Any]] = None,
             kwargs: Optional[Dict[str, Dict[str, Any]]] = None,
             seeds: Sequence[int] = (0,)) -> "SweepSpec":
        """Cross product of named scheme factories x named configs x
        named kwargs overrides x seeds; lane labels join the axis names
        (``"ltfl/highband/s0"``). Omitted axes contribute one unnamed
        inherit-from-parent point."""
        s_ax = dict(schemes) if schemes else {"": None}
        c_ax = dict(ltfls) if ltfls else {"": None}
        k_ax = dict(kwargs) if kwargs else {"": None}
        lanes = []
        for sname, factory in s_ax.items():
            for cname, cfg in c_ax.items():
                for kname, kw in k_ax.items():
                    for seed in seeds:
                        label = "/".join(
                            x for x in (sname, cname, kname, f"s{seed}")
                            if x)
                        lanes.append(LaneSpec(
                            seed=int(seed), scheme_factory=factory,
                            ltfl=cfg, kwargs=kw, label=label))
        return cls(lanes=tuple(lanes))


class ScanRunner(FedRunner):
    """``FedRunner`` with the per-round loop replaced by scanned segments.

    Drop-in: construction args, ``history`` / ``history_dict`` and the
    per-round ``RoundRecord`` semantics match ``FedRunner``; only ``run``
    executes differently. Additional args:

    * ``rng``: ``"host"`` (seeded-parity replay; default) or
      ``"device"`` (fully device-resident rng — see module docstring);
    * ``control``: ``"host"`` (Algorithm 1 / eval between segments;
      default) or ``"device"`` (in-scan recontrol via the scheme's
      ``scan_control_program``, in-scan eval head; requires
      ``rng="device"``);
    * ``max_segment``: optional cap on scanned segment length
      (``max_segment=1`` degenerates to the classic per-round engine,
      used by the parity tests).

    Schemes must declare ``scan_supported`` and segment-constant controls
    via ``scan_recontrol_every`` (``control="device"`` additionally needs
    ``scan_control_program`` whenever that cadence is nonzero).
    """

    # Buffered-async spec — set by AsyncRunner (repro.fed.async_engine),
    # which also provides the ``_admission`` hook the scan bodies call.
    # None means the synchronous engine: every async branch in ``_segment``
    # is a python-level conditional that folds away at trace time, so the
    # sync traces are structurally unchanged.
    _async: Optional[Any] = None

    def __init__(self, model, params, ltfl, train, test, scheme, *,
                 rng: str = "host", control: str = "host",
                 max_segment: Optional[int] = None,
                 population_sharding=None, **kwargs):
        if rng not in ("host", "device"):
            raise ValueError(f"rng={rng!r} (want 'host' or 'device')")
        if population_sharding is not None and rng != "device":
            raise ValueError(
                "population_sharding lays the device registry out over a "
                "('pop',) mesh and draws cohorts in-scan via the sharded "
                "sampler twins; pass rng='device'")
        if control not in ("host", "device"):
            raise ValueError(
                f"control={control!r} (want 'host' or 'device')")
        if control == "device" and rng != "device":
            raise ValueError(
                "control='device' folds recontrol into the scan carry, "
                "which needs the in-scan rng stream; pass rng='device'")
        if not scheme.scan_supported:
            raise ValueError(
                f"{type(scheme).__name__} needs per-round host feedback "
                "and cannot run scanned; use FedRunner")
        if max_segment is not None and max_segment < 1:
            raise ValueError(f"max_segment={max_segment} must be >= 1")
        # capture construction inputs for run_sweep's seeded replicas
        self._ctor = dict(model=model, params=params, ltfl=ltfl,
                          train=train, test=test, kwargs=dict(kwargs))
        self._scheme_proto = copy.deepcopy(scheme)   # pre-setup state
        super().__init__(model, params, ltfl, train, test, scheme, **kwargs)
        self.rng = rng
        self.control = control
        self.max_segment = max_segment
        self._ctl_program = None
        self._ctl_state: Optional[PyTree] = None
        self._sampler_twin = None
        rc = scheme.scan_recontrol_every(self)
        if control == "device" and rc:
            self._ctl_program = scheme.scan_control_program(self)
            if self._ctl_program is None:
                raise ValueError(
                    f"{type(scheme).__name__} recontrols every {rc} "
                    "round(s) but provides no scan_control_program "
                    "(no device twin of its control loop); use "
                    "control='host'")
            self._ctl_state = self._ctl_program.init
        self._pop_mesh = None
        self._pop_pad = None
        if population_sharding is not None:
            mesh = (population_mesh(population_sharding)
                    if isinstance(population_sharding, int)
                    else population_sharding)
            if "pop" not in mesh.axis_names:
                raise ValueError(
                    f"population_sharding mesh axes {mesh.axis_names} "
                    "have no 'pop' axis (use repro.launch.sharding."
                    "population_mesh)")
            self._pop_mesh = mesh
            self._pop_pad = population_pad(self.population_size, mesh)
        if rng == "device":
            if self._pop_mesh is not None:
                self._sampler_twin = self.sampler.sharded_twin(
                    self, self._pop_mesh)
                if self._sampler_twin is None:
                    raise ValueError(
                        f"population_sharding needs a sharded sampler "
                        f"twin, but {type(self.sampler).__name__}."
                        "sharded_twin() returned None; use an unsharded "
                        "runner or a sampler with a sharded twin "
                        "(repro.control.device_samplers)")
            else:
                self._sampler_twin = self.sampler.device_twin(self)
            if self._sampler_twin is None:
                raise ValueError(
                    f"rng='device' draws cohorts in-scan, but "
                    f"{type(self.sampler).__name__}.device_twin() "
                    "returned None (host-only scheduler); use rng='host' "
                    "or a sampler with a device twin "
                    "(repro.control.device_samplers)")
            if self.participation == "unbiased" and \
                    not self._sampler_twin.provides_inclusion:
                raise ValueError(
                    "participation='unbiased' needs inclusion "
                    f"probabilities; the {type(self.sampler).__name__} "
                    "device twin does not provide them")
            if control == "host" and rc and \
                    self.cohort_size < self.population_size:
                raise ValueError(
                    "rng='device' cannot host-recontrol against a cohort "
                    "drawn in-scan; use control='device' (in-scan "
                    "recontrol) or rng='host' (per-round segments)")
        self._scan_key = jax.random.PRNGKey(int(kwargs.get("seed", 0)))
        self._data_dev: Optional[Dict[str, jax.Array]] = None
        self._parts_padded: Optional[jax.Array] = None
        self._part_sizes: Optional[jax.Array] = None
        self._eval_batches_dev: Optional[Dict[str, jax.Array]] = None
        # persistent device-resident (N,) population state (device rng):
        # uploaded ONCE, then carried across segments and synced back to
        # the host population lazily at the end of run() — segment
        # boundaries cost zero (N,) host<->device round trips
        self._pop_dev: Optional[PopulationArrays] = None
        self._static_consts_dev: Optional[Dict[str, jax.Array]] = None
        self._fading_dev: Optional[jax.Array] = None
        self._interference_dev: Optional[jax.Array] = None
        self._range_sq_dev: Optional[jax.Array] = None
        self._host_pop_stale = False
        self._n_pop_uploads = 0   # (N,)-state host->device upload events
        # one per (segment length, decide_first, single|sweep) trace
        self._n_traces = 0
        # every device->host read of the engine goes through this counter;
        # ``_seg`` counts absorbed segments (the ``seg`` of each span)
        self._reads = spans.Reads()
        self._seg = 0
        self._seg_jit = jax.jit(self._segment, static_argnums=(4, 5))
        self._sweep_jit = jax.jit(
            jax.vmap(self._segment, in_axes=(0, 0, 0, None, None, None)),
            static_argnums=(4, 5))
        # populated by run_sweep: bucket metadata of the last sweep
        # (signature, representative runner, lane indices) — the
        # compile-counter tests and benchmarks read trace counts off it
        self._last_sweep_buckets: List[Dict[str, Any]] = []

    # ------------------------------------------------------------------ #
    # device-resident world
    # ------------------------------------------------------------------ #
    def _ensure_device_world(self, pad_to: Optional[int] = None) -> None:
        """Materialize the device-resident training pool (both modes) and,
        for device rng, the padded per-device partition table. ``pad_to``
        widens the table to a common width (run_sweep stacks lanes).
        Under ``control="device"`` the in-scan eval head's fixed seeded
        batches (the exact arrays ``evaluate`` scores) go device-resident
        here too.

        Setup complexity contract: the (N, W) table comes out of
        ``ClientBatcher.padded_parts`` in one vectorized pass — no O(N)
        Python loop anywhere on the cold-start path. Under
        ``population_sharding`` the table and the (N,) size vector are
        zero-padded to ``N_pad`` rows and laid out over the ('pop',)
        mesh via the "population" sharding rule, so per-device
        residency is N_pad/S rows, not N — the in-scan batch gather
        assembles the cohort's rows with ``gather_parts_dev``."""
        if self._data_dev is None:
            self._data_dev = {k: jnp.asarray(v)
                              for k, v in self.batcher.base.arrays.items()}
        if self.control == "device" and self._eval_batches_dev is None \
                and self._eval_fn is not None and self.eval_every:
            batches = self._eval_batches()
            self._eval_batches_dev = {
                k: jnp.asarray(np.stack([b[k] for b in batches]))
                for k in batches[0]}
        if self.rng != "device":
            return
        if self._pop_mesh is not None and self._pop_dev is None:
            # the sharded registry: ONE padded upload, sharded over 'pop'
            self._pop_dev = device_population(
                self.population, self._pop_mesh)
            self._n_pop_uploads += 1
        if self._static_consts_dev is None:
            # static (N,) device attributes (distances, CPUs, shard
            # sizes): device-resident once, never re-uploaded per segment
            if self._pop_mesh is not None:
                ch_dev = self._pop_dev.channel
                self._static_consts_dev = dict(
                    distance=ch_dev.distance, cpu=ch_dev.cpu_hz,
                    ns=ch_dev.num_samples)
            else:
                ch = self.population.channel
                self._static_consts_dev = dict(
                    distance=jnp.asarray(ch.distance, jnp.float32),
                    cpu=jnp.asarray(ch.cpu_hz, jnp.float32),
                    ns=jnp.asarray(ch.num_samples, jnp.float32))
                self._n_pop_uploads += 1
        sizes = self.batcher.client_sizes().astype(np.int32)
        width = int(sizes.max(initial=0)) if pad_to is None else int(pad_to)
        width = max(width, 1)            # keep the gather well-formed even
        if self._parts_padded is not None and \
                self._parts_padded.shape[1] >= width:     # if all-empty
            return
        table = self.batcher.padded_parts(width=width)
        if self._pop_mesh is None:
            self._parts_padded = jnp.asarray(table)
            self._part_sizes = jnp.asarray(sizes)
            return
        # sharded registry: zero rows pad N up to equal shard blocks
        # (size-0 devices the samplers mask out of every draw), then the
        # table/sizes lay out over 'pop' — resident at N_pad/S per device
        mesh = self._pop_mesh
        n, n_pad = table.shape[0], self._pop_pad
        if n_pad > n:
            table = np.concatenate(
                [table, np.zeros((n_pad - n, width), np.int32)])
            sizes = np.concatenate(
                [sizes, np.zeros(n_pad - n, np.int32)])
        rules = base_rules(mesh)
        self._parts_padded = jax.device_put(
            table, NamedSharding(mesh, make_pspec(
                (n_pad, width), ("population", None), rules, mesh)))
        self._part_sizes = jax.device_put(
            sizes, NamedSharding(mesh, make_pspec(
                (n_pad,), ("population",), rules, mesh)))

    # ------------------------------------------------------------------ #
    # segmentation
    # ------------------------------------------------------------------ #
    def _segment_spans(self, start: int, end: int):
        """Split [start, end) at host boundaries: a new segment starts at
        every recontrol round, ends after every eval round, and never
        exceeds ``max_segment`` rounds. Under ``control="device"`` the
        recontrol AND eval boundaries vanish (both run in-scan), so the
        spans that would have degenerated to length 1 coalesce into one
        scanned range — no stray retraces (compile-counter-tested).

        A device control program with cadence ``every=k > 1`` re-splits
        at multiples of k: ``decide`` is a STATIC per-segment bool (at
        most the segment's FIRST round decides), so a segment crossing a
        decide round would skip that solve. The split costs nothing over
        host recontrol (same boundaries) and buys hold segments whose
        traces never embed the solve."""
        if self.control == "device":
            # in-scan recontrol + in-scan eval head; only a cadence-k
            # program keeps (cheaper, aligned) boundaries
            p = self._ctl_program
            rc = p.every if p is not None and p.every > 1 else 0
            ev = 0
        else:
            rc = self.scheme.scan_recontrol_every(self)
            ev = self.eval_every
        spans = []
        a = start
        while a < end:
            b = a + 1
            while b < end:
                if rc and b % rc == 0:
                    break                 # host recontrol due at b
                if ev and (b - 1) % ev == 0:
                    break                 # eval due after round b-1
                if self.max_segment and b - a >= self.max_segment:
                    break
                b += 1
            spans.append((a, b))
            a = b
        return spans

    def _decide_first(self, a: int) -> bool:
        """Whether the segment starting at round ``a`` opens with a
        decide round (static: it picks which compiled program runs).
        Cadence-1 programs decide every round; cadence-k programs decide
        iff the segment start is on-cadence (``_segment_spans`` aligns
        boundaries so no LATER round of the segment ever is)."""
        if self._ctl_program is None:
            return False
        if self._ctl_program.every <= 1:
            return True
        return a % self._ctl_program.every == 0

    # ------------------------------------------------------------------ #
    # per-segment host preparation
    # ------------------------------------------------------------------ #
    def _laned_cfg(self) -> Dict[str, jax.Array]:
        """This runner's laned config leaves (f32 scalars). Rides the
        segment constants of EVERY segment — solo runs too, so a solo
        trace is structurally identical to a sweep lane's and the two
        produce bitwise-equal histories."""
        w, l = self.ltfl.wireless, self.ltfl
        cfg = {"w_" + k: jnp.float32(getattr(w, k))
               for k in _LANED_WIRELESS}
        cfg.update({k: jnp.float32(getattr(l, k)) for k in _LANED_LTFL})
        return cfg

    def _segment_consts(self, ctl, agg_denom) -> Dict[str, jax.Array]:
        consts = {
            "cfg": self._laned_cfg(),
            "rho": jnp.asarray(ctl.rho, jnp.float32),
            "delta": jnp.asarray(ctl.delta, jnp.float32),
            "power": jnp.asarray(ctl.power, jnp.float32),
            "payload": jnp.asarray(
                np.asarray(self.scheme.payload_bits(ctl), np.float64),
                jnp.float32),
        }
        if agg_denom is not None:
            consts["agg_denom"] = jnp.float32(agg_denom)
        return consts

    def _prepare_host_segment(self, a: int, b: int):
        """Replay the host half of rounds [a, b) on the np_rng stream
        (identical consumption order to ``FedRunner.run_round``) and stack
        the per-round inputs for the scan."""
        rows = []
        ctl0 = None
        agg_denom = None
        for r in range(a, b):
            h = self._host_round_inputs(r)
            agg_denom = h.agg_denom
            if ctl0 is None:
                ctl0 = h.ctl
            elif not (np.array_equal(ctl0.rho, h.ctl.rho)
                      and np.array_equal(ctl0.delta, h.ctl.delta)
                      and np.array_equal(ctl0.power, h.ctl.power)):
                raise ValueError(
                    f"{type(self.scheme).__name__} changed controls inside "
                    f"a scan segment (round {r}); its scan_recontrol_every "
                    "declaration is wrong")
            view = self.channel          # cohort view set by the replay
            row = {
                "cohort": h.cohort.astype(np.int32),
                "distance": view.distance,
                "fading": view.fading_mean,
                "interference": view.interference,
                "cpu": view.cpu_hz,
                "ns": view.num_samples,
                "weights": h.weights,
                "batch_idx": h.batch_idx.astype(np.int32),
                "key": np.asarray(h.key),
                "alpha": h.alpha,
            }
            if self.participation == "unbiased":
                row["inclusion"] = self._cohort_probs
            rows.append(row)
        int_keys = {"cohort", "batch_idx", "key"}
        xs = {}
        for k in rows[0]:
            stacked = np.stack([row[k] for row in rows])
            xs[k] = jnp.asarray(stacked if k in int_keys
                                else stacked.astype(np.float32))
        return xs, self._segment_consts(ctl0, agg_denom), ctl0

    def _prepare_device_segment(self, a: int, b: int):
        """Segment-start controls (or nothing, when the scheme's control
        program recomputes them in-scan) + the (N,)-shaped device
        constants; all per-round randomness comes from the carried key
        stream in-scan.

        Unbiased aggregation is resolved here, not via FedRunner's
        ``_aggregation_weights`` — that host path needs per-round sampler
        probabilities, which device mode never materializes; the device
        sampler twin reports its own inclusion probabilities in-scan and
        only the fixed denominator is a constant."""
        agg_denom = (self._pop_samples_total
                     if self.participation == "unbiased" else None)
        if self._ctl_program is None:
            ctl = self.scheme.controls(a)
            consts = self._segment_consts(ctl, agg_denom)
        else:
            ctl = None                   # controls live in the scan carry
            consts = {"cfg": self._laned_cfg()}
            if agg_denom is not None:
                consts["agg_denom"] = jnp.float32(agg_denom)
        consts.update(
            self._static_consts_dev,     # device-resident; zero uploads
            part_sizes=self._part_sizes,
            parts_padded=self._parts_padded,
            r0=jnp.int32(a),
        )
        if self._eval_batches_dev is not None:
            consts["eval"] = self._eval_batches_dev
        return consts, ctl

    def _host_carry(self):
        return (self.params, self.opt_state, self.comp_state,
                jnp.asarray(self._range_sq_pop, jnp.float32))

    def _device_carry(self):
        """The device-rng carry, built from PERSISTENT device arrays:
        the (N,) fading/interference/range-sq state uploads once (first
        segment ever) and afterwards the previous segment's carry leaves
        feed the next — segment boundaries move no (N,) state across the
        host boundary (``_n_pop_uploads`` counts upload events; the
        host population syncs back lazily, see ``_sync_host_population``)."""
        if self._range_sq_dev is None:
            self._range_sq_dev = jnp.asarray(self._range_sq_pop,
                                             jnp.float32)
            self._n_pop_uploads += 1
        if self._pop_mesh is not None:
            pop = self._pop_dev
            carry = (self.params, self.opt_state, self.comp_state,
                     self._range_sq_dev, pop.channel.fading_mean,
                     pop.channel.interference, pop.fading_epoch,
                     pop.epoch, self._scan_key)
        else:
            if self._fading_dev is None:
                ch = self.population.channel
                self._fading_dev = jnp.asarray(ch.fading_mean, jnp.float32)
                self._interference_dev = jnp.asarray(ch.interference,
                                                     jnp.float32)
                self._n_pop_uploads += 1
            carry = (self.params, self.opt_state, self.comp_state,
                     self._range_sq_dev, self._fading_dev,
                     self._interference_dev, self._scan_key)
        if self._ctl_program is not None:
            carry = carry + (self._ctl_state,)
        return carry

    # ------------------------------------------------------------------ #
    # the compiled segment
    # ------------------------------------------------------------------ #
    def _segment(self, carry, xs, consts, data, length: int,
                 decide_first: bool = False):
        """One scanned segment. Traced once per distinct ``(length,
        decide_first)`` (and once more inside the run_sweep vmap);
        ``self._n_traces`` counts traces for the compile-cadence tests.

        ``ltfl`` here is the LANED config view rehydrated from
        ``consts["cfg"]`` — under the sweep vmap its float leaves are
        per-lane tracers, so every channel/budget expression below is
        per-lane even though the trace is shared. ``decide_first`` is
        static: under a cadence-k control program only the segment's
        first round may decide, and it runs OUTSIDE the scan so the
        scanned hold body never embeds the solve."""
        self._n_traces += 1
        ltfl = _laned_ltfl(self.ltfl, consts["cfg"])
        w = ltfl.wireless
        step_fn = self._step_fn
        if self._pop_mesh is not None:
            # the round's tensor work is replicated over the 'pop' mesh:
            # run it per device, since the SPMD partitioner refuses the
            # Pallas (Mosaic) kernels it calls under use_kernels
            step_fn = jax.shard_map(step_fn, mesh=self._pop_mesh,
                                    in_specs=PartitionSpec(),
                                    out_specs=PartitionSpec(),
                                    check_vma=False)
        asy = self._async
        unbiased = self.participation == "unbiased"
        U, N, B = self.num_devices, self.population_size, self.batch_size
        block_fading = self.block_fading
        program = self._ctl_program
        twin = self._sampler_twin
        eval_every = self.eval_every
        in_scan_eval = "eval" in consts and eval_every > 0

        def eval_acc(params):
            """The in-scan eval head: the SAME fixed seeded batches
            ``evaluate()`` scores, averaged (f32 vs the host's f64
            mean-of-floats — tolerance, not bitwise)."""
            accs = jax.vmap(
                lambda b: self.model.accuracy(params, b))(consts["eval"])
            return jnp.mean(accs).astype(jnp.float32)

        def finish(params, opt_state, comp_state, range_sq, batch, ch,
                   cohort, weights, alpha, inclusion, key,
                   rho, delta, power, payload, r,
                   tau=None, admitted=None, accounting=None):
            # the learning rate is a LANED leaf (per-lane traced under the
            # sweep vmap); the step routes it to update_with_lr — bitwise
            # equal to the baked-lr solo path (repro.optim.Optimizer)
            controls = {"rho": rho, "delta": delta,
                        "weights": weights, "alpha": alpha,
                        "lr": ltfl.learning_rate}
            if "agg_denom" in consts:
                controls["agg_denom"] = consts["agg_denom"]
            params, opt_state, comp_state, m = step_fn(
                params, opt_state, comp_state, batch, controls, key)
            with jax.named_scope(spans.RANGE):
                range_sq = range_sq.at[cohort].set(m["range_sq"])
            with jax.named_scope(spans.CHANNEL):
                if accounting is None:
                    delay, energy = round_accounting_dev(
                        ltfl, ch, payload, rho, power)
                else:                    # async: buffered-round accounting
                    delay, energy = accounting
                pers = packet_error_rate_dev(w, ch, power)
            # gamma's inputs only — the Eq. 29 reduction happens on host
            # in f64 (_absorb_segment), NOT here: one numpy code path for
            # solo runs and every run_sweep lane keeps lane==solo gamma
            # bitwise. unbiased: the fixed HT denominator IS the
            # population sample total — read it from consts (per-lane
            # under run_sweep, where every replica's population draws a
            # different total), never from a closure over this runner's
            # own population
            gap_delta = jnp.where(delta > 0, delta, 32.0)
            denom = consts["agg_denom"] if unbiased else None
            if in_scan_eval:
                with jax.named_scope(spans.EVAL):
                    acc = jax.lax.cond(r % eval_every == 0, eval_acc,
                                       lambda p: jnp.float32(jnp.nan),
                                       params)
            else:
                acc = jnp.float32(jnp.nan)
            log = RoundLog(train_loss=m["loss"], delay=delay, energy=energy,
                           received=jnp.sum(alpha),
                           range_sq=m["range_sq"], gap_delta=gap_delta,
                           rho_u=rho, pers=pers, ns_u=ch.num_samples,
                           inclusion=inclusion if unbiased else None,
                           agg_denom=denom, cohort=cohort,
                           test_acc=acc, rho_mean=jnp.mean(rho),
                           delta_mean=jnp.mean(delta),
                           power_mean=jnp.mean(power),
                           tau=tau, admitted=admitted)
            return params, opt_state, comp_state, range_sq, log

        if xs is not None:               # host rng: stacked replay inputs
            def body(carry, x):
                if asy is not None:      # async state rides as LAST leaf
                    carry, astate = carry[:-1], carry[-1]
                params, opt_state, comp_state, range_sq = carry
                ch = ChannelArrays(x["distance"], x["fading"],
                                   x["interference"], x["cpu"], x["ns"])
                with jax.named_scope(spans.SAMPLER):
                    batch = {k: arr[x["batch_idx"]]
                             for k, arr in data.items()}
                weights, alpha, inclusion = (x["weights"], x["alpha"],
                                             x.get("inclusion"))
                tau = admitted = accounting = None
                if asy is not None:
                    masks = ((x["alive_c"], x["drop"])
                             if "alive_c" in x else None)
                    with jax.named_scope(spans.CONTROL):
                        (alpha, weights, inclusion, tau, admitted,
                         accounting, astate) = self._admission(
                            ltfl, ch, x["cohort"], alpha, weights,
                            inclusion, consts["rho"], consts["power"],
                            consts["payload"], astate, None, masks)
                params, opt_state, comp_state, range_sq, log = finish(
                    params, opt_state, comp_state, range_sq, batch, ch,
                    x["cohort"], weights, alpha,
                    inclusion, x["key"],
                    consts["rho"], consts["delta"], consts["power"],
                    consts["payload"], jnp.int32(0),
                    tau=tau, admitted=admitted, accounting=accounting)
                out = (params, opt_state, comp_state, range_sq)
                if asy is not None:
                    out = out + (astate,)
                return out, log

            return jax.lax.scan(body, carry, xs)

        # device rng: carried key stream, everything drawn in-scan.
        # ``decide`` is a python bool: the round body is traced once per
        # decide value actually used, and hold bodies contain no solve
        def body_dev(carry, r, decide=True):
            if asy is not None:          # async state rides as LAST leaf
                carry, astate = carry[:-1], carry[-1]
            if program is not None:
                (params, opt_state, comp_state, range_sq,
                 fading, interference, key, ctl_state) = carry
            else:
                (params, opt_state, comp_state, range_sq,
                 fading, interference, key) = carry
                ctl_state = None
            if asy is not None and asy.churn is not None:
                # one EXTRA split only when churn draws in-scan; the
                # churn-free async key stream stays bitwise-identical to
                # the synchronous engine's (the degenerate-case contract)
                (key, k_fade, k_cohort, k_batch, k_alpha, k_step, k_ctl,
                 k_churn) = jax.random.split(key, 8)
            else:
                key, k_fade, k_cohort, k_batch, k_alpha, k_step, k_ctl = \
                    jax.random.split(key, 7)
                k_churn = None
            if block_fading:
                # eager full-population redraw: O(N) vectorized on device
                # (the host loop's LAZY per-cohort refresh is a host-side
                # optimization; the realized distributions match)
                with jax.named_scope(spans.CHANNEL):
                    fading, interference = draw_fading_dev(w, k_fade, N)
            ch_pop = ChannelArrays(
                distance=consts["distance"], fading_mean=fading,
                interference=interference, cpu_hz=consts["cpu"],
                num_samples=consts["ns"])
            with jax.named_scope(spans.SAMPLER):
                # the sampler twin sees the round's CURRENT realization —
                # in-scan scheduling tracks fading at per-round cadence
                cohort, pi = twin.select(ch_pop, k_cohort)
                ch = ch_pop.take(cohort)
                sizes = jnp.take(consts["part_sizes"], cohort)
                # maximum(sizes, 1): a zero-sample device's clamped draw
                # reads its all-zero pad row — harmless, its aggregation
                # weight (num_samples) is 0; sizes >= 1 draws are untouched
                draws = jax.random.randint(k_batch, (U, B), 0,
                                           jnp.maximum(sizes, 1)[:, None])
                gidx = jnp.take_along_axis(
                    jnp.take(consts["parts_padded"], cohort, axis=0),
                    draws, axis=1)
                batch = {k: arr[gidx] for k, arr in data.items()}
            if program is not None:
                with jax.named_scope(spans.CONTROL):
                    dctl, ctl_state = program.controls(
                        ctl_state, r, cohort, ch,
                        jnp.take(range_sq, cohort), k_ctl, ltfl,
                        decide=decide)
                rho, delta, power, payload = dctl
            else:
                rho, delta, power, payload = (
                    consts["rho"], consts["delta"], consts["power"],
                    consts["payload"])
            with jax.named_scope(spans.CHANNEL):
                alpha = sample_transmissions_dev(w, ch, power, k_alpha)
            if unbiased:
                weights, inclusion = ch.num_samples / pi, pi
            else:
                weights, inclusion = ch.num_samples, None
            tau = admitted = accounting = None
            if asy is not None:
                with jax.named_scope(spans.CONTROL):
                    (alpha, weights, inclusion, tau, admitted, accounting,
                     astate) = self._admission(
                        ltfl, ch, cohort, alpha, weights, inclusion,
                        rho, power, payload, astate, k_churn, None)
            params, opt_state, comp_state, range_sq, log = finish(
                params, opt_state, comp_state, range_sq, batch, ch,
                cohort, weights, alpha, inclusion, k_step,
                rho, delta, power, payload, r,
                tau=tau, admitted=admitted, accounting=accounting)
            if program is not None and program.feedback is not None:
                with jax.named_scope(spans.CONTROL):
                    ctl_state = program.feedback(ctl_state, cohort,
                                                 log.train_loss, log.delay)
            out = (params, opt_state, comp_state, range_sq,
                   fading, interference, key)
            if program is not None:
                out = out + (ctl_state,)
            if asy is not None:
                out = out + (astate,)
            return out, log

        # sharded registry: the (N_pad,) population leaves stay laid out
        # over the ('pop',) mesh; per-round population work is the
        # shard_map'd two-stage cohort draw + lazy O(U) fading refresh +
        # psum-gather of the cohort view — never an O(N) redraw and never
        # a host round trip (repro.fed.population module docstring)
        mesh = self._pop_mesh

        def body_dev_sharded(carry, r, decide=True):
            if asy is not None:          # async state rides as LAST leaf
                carry, astate = carry[:-1], carry[-1]
            if program is not None:
                (params, opt_state, comp_state, range_sq, fading,
                 interference, fading_epoch, epoch, key, ctl_state) = carry
            else:
                (params, opt_state, comp_state, range_sq, fading,
                 interference, fading_epoch, epoch, key) = carry
                ctl_state = None
            if asy is not None and asy.churn is not None:
                (key, k_fade, k_cohort, k_batch, k_alpha, k_step, k_ctl,
                 k_churn) = jax.random.split(key, 8)
            else:
                key, k_fade, k_cohort, k_batch, k_alpha, k_step, k_ctl = \
                    jax.random.split(key, 7)
                k_churn = None
            if block_fading:
                epoch = epoch + 1        # new epoch; realizations lazy
            pop = PopulationArrays(
                channel=ChannelArrays(
                    distance=consts["distance"], fading_mean=fading,
                    interference=interference, cpu_hz=consts["cpu"],
                    num_samples=consts["ns"]),
                fading_epoch=fading_epoch, epoch=epoch)
            # schedule on LAST-KNOWN (possibly stale) CSI — the host
            # Population semantics — then lazily refresh the scheduled
            # devices' realizations for this epoch
            with jax.named_scope(spans.SAMPLER):
                cohort, pi = twin.select(pop.channel, k_cohort)
            if block_fading:
                with jax.named_scope(spans.CHANNEL):
                    pop = refresh_cohort_dev(w, mesh, pop, cohort, k_fade)
                fading = pop.channel.fading_mean
                interference = pop.channel.interference
                fading_epoch = pop.fading_epoch
            with jax.named_scope(spans.SAMPLER):
                ch = gather_cohort_dev(mesh, pop.channel, cohort)
                # the (N_pad, W) table stays sharded over 'pop'; only the
                # cohort's (U, W) rows are assembled (psum-gather),
                # exactly matching a replicated-table take — same draws,
                # same indices
                rows, sizes = gather_parts_dev(
                    mesh, consts["parts_padded"], consts["part_sizes"],
                    cohort)
                draws = jax.random.randint(k_batch, (U, B), 0,
                                           jnp.maximum(sizes, 1)[:, None])
                gidx = jnp.take_along_axis(rows, draws, axis=1)
                batch = {k: arr[gidx] for k, arr in data.items()}
            if program is not None:
                with jax.named_scope(spans.CONTROL):
                    dctl, ctl_state = program.controls(
                        ctl_state, r, cohort, ch,
                        jnp.take(range_sq, cohort), k_ctl, ltfl,
                        decide=decide)
                rho, delta, power, payload = dctl
            else:
                rho, delta, power, payload = (
                    consts["rho"], consts["delta"], consts["power"],
                    consts["payload"])
            with jax.named_scope(spans.CHANNEL):
                alpha = sample_transmissions_dev(w, ch, power, k_alpha)
            if unbiased:
                weights, inclusion = ch.num_samples / pi, pi
            else:
                weights, inclusion = ch.num_samples, None
            tau = admitted = accounting = None
            if asy is not None:
                # async state stays REPLICATED (N,) — ordinary ops on the
                # gathered (replicated) cohort view, outside shard_map
                with jax.named_scope(spans.CONTROL):
                    (alpha, weights, inclusion, tau, admitted, accounting,
                     astate) = self._admission(
                        ltfl, ch, cohort, alpha, weights, inclusion,
                        rho, power, payload, astate, k_churn, None)
            params, opt_state, comp_state, range_sq, log = finish(
                params, opt_state, comp_state, range_sq, batch, ch,
                cohort, weights, alpha, inclusion, k_step,
                rho, delta, power, payload, r,
                tau=tau, admitted=admitted, accounting=accounting)
            if program is not None and program.feedback is not None:
                with jax.named_scope(spans.CONTROL):
                    ctl_state = program.feedback(ctl_state, cohort,
                                                 log.train_loss, log.delay)
            out = (params, opt_state, comp_state, range_sq,
                   fading, interference, fading_epoch, epoch, key)
            if program is not None:
                out = out + (ctl_state,)
            if asy is not None:
                out = out + (astate,)
            return out, log

        rounds = consts["r0"] + jnp.arange(length, dtype=jnp.int32)
        body = body_dev if mesh is None else body_dev_sharded
        if program is None or program.every <= 1:
            # nothing to hold: every round decides (or no program at all)
            return jax.lax.scan(body, carry, rounds)
        # cadence k > 1: the planner aligned segment starts to the
        # cadence, so at most the FIRST round decides. It runs outside
        # the scan (its trace embeds the solve only when decide_first);
        # the remaining rounds scan through a pure hold body
        carry, log0 = body(carry, rounds[0], decide=decide_first)
        if length == 1:
            return carry, jax.tree_util.tree_map(lambda h: h[None], log0)
        carry, logs = jax.lax.scan(
            lambda c, r: body(c, r, decide=False), carry, rounds[1:])
        log = jax.tree_util.tree_map(
            lambda h, t: jnp.concatenate([h[None], t]), log0, logs)
        return carry, log

    # ------------------------------------------------------------------ #
    # post-segment host absorption
    # ------------------------------------------------------------------ #
    def _absorb_segment(self, a: int, b: int, ctl, carry, log) -> None:
        """Pull the segment's carry/log back to host state and append the
        per-round ``RoundRecord``s (cum sums in f64). Under host control,
        eval runs here, at the segment's final round when due —
        segmentation guarantees eval rounds are segment-final; under
        device control the in-scan eval head already measured it and the
        accuracy is read off the log."""
        seg = self._seg
        with spans.span(spans.ABSORB, self._reads, seg=seg):
            with spans.span(spans.FETCH, seg=seg):
                host = self._fetch_segment(carry, log)
            self.params, self.opt_state, self.comp_state = carry[:3]
            cohorts = host["cohort"]
            if self.rng != "device":
                touched = np.unique(cohorts)
                self._range_sq_pop[touched] = host["range_sq_pop"][touched]
            else:
                # keep the (N,)-state DEVICE-resident across segments (its
                # leaves feed the next _device_carry directly); the host
                # population syncs back lazily — once, at the end of run()
                self._range_sq_dev = carry[3]
                if self._pop_mesh is not None:
                    (fading, interference, fading_epoch, epoch,
                     key) = carry[4:9]
                    self._pop_dev = PopulationArrays(
                        channel=self._pop_dev.channel._replace(
                            fading_mean=fading, interference=interference),
                        fading_epoch=fading_epoch, epoch=epoch)
                else:
                    fading, interference, key = carry[4], carry[5], carry[6]
                    self._fading_dev = fading
                    self._interference_dev = interference
                self._scan_key = key
                self._host_pop_stale = True
                if self._ctl_program is not None:
                    self._ctl_state = self._ctl_carry(carry)
                    if self._ctl_program.absorb is not None:
                        with spans.span(spans.CTL_ABSORB, seg=seg):
                            self._ctl_program.absorb(self.scheme,
                                                     host["ctl"])
                if self.block_fading:
                    # the scan advanced (b - a) fading epochs on device;
                    # keep the host epoch bookkeeping (PER caches,
                    # stale-decision checks) consistent
                    self._channel_epoch += b - a
                    self.population.epoch += b - a
                self.cohort = cohorts[-1]
                if self.control == "host" and \
                        self.scheme.scan_recontrol_every(self):
                    # host recontrol reads the cohort channel view between
                    # segments — it must see the carried realization now,
                    # not at the end of run()
                    self._sync_host_population()
            with spans.span(spans.GAMMA, seg=seg):
                # Eq. 29 from the logged per-round input vectors, reduced
                # HERE in float64: solo runs and run_sweep lanes share this
                # exact numpy path, so lane==solo gamma is bitwise by
                # construction (in-jit reductions drift a ulp between the
                # solo and sweep-vmapped traces — see the module
                # docstring). Async: per-device staleness rides the log
                # and enters the same reduction (the staleness-HT
                # convention — repro.core.convergence module docstring);
                # tau = 0 adds exactly +0.0, so the sync-degenerate gammas
                # stay bitwise.
                incl, denoms, taus = (host["inclusion"], host["agg_denom"],
                                      host["tau"])
                gammas = np.asarray([
                    gamma(self.ltfl, host["range_sq"][i],
                          host["gap_delta"][i], host["rho_u"][i],
                          host["pers"][i], host["ns_u"][i],
                          **({"inclusion": incl[i],
                              "population_samples": float(denoms[i])}
                             if incl is not None else {}),
                          **({"staleness": taus[i]}
                             if taus is not None else {}))
                    for i in range(b - a)], np.float64)
            with spans.span(spans.RECORDS, seg=seg):
                self._record_rounds(a, b, ctl, host, gammas)
        self._seg += 1

    def _ctl_carry(self, carry):
        """The control program's state among a device-rng carry's leaves."""
        return carry[9] if self._pop_mesh is not None else carry[7]

    def _fetch_segment(self, carry, log) -> Dict[str, Any]:
        """Every device-to-host read of a segment's results, through the
        counting reader: the log's cohorts, the host-rng range statistic
        or the control program's carry (when its ``absorb`` reads it),
        then the log's fields (None stays None)."""
        read = self._reads
        host: Dict[str, Any] = {"cohort": read(log.cohort, np.int64)}
        if self.rng != "device":
            host["range_sq_pop"] = read(carry[3], np.float64)
        elif self._ctl_program is not None and \
                self._ctl_program.absorb is not None:
            host["ctl"] = jax.tree_util.tree_map(read,
                                                 self._ctl_carry(carry))
        for field in ("train_loss", "delay", "energy", "received",
                      "range_sq", "gap_delta", "rho_u", "pers", "ns_u",
                      "inclusion", "agg_denom", "tau", "test_acc",
                      "rho_mean", "delta_mean", "power_mean"):
            value = getattr(log, field)
            host[field] = None if value is None else read(value, np.float64)
        return host

    def _record_rounds(self, a: int, b: int, ctl, host: Dict[str, Any],
                       gammas: np.ndarray) -> None:
        """Append rounds [a, b)'s ``RoundRecord``s and feed the scheme's
        ``post_round``."""
        losses, delays, energies = (host["train_loss"], host["delay"],
                                    host["energy"])
        taus = host["tau"]
        device_ctl = self.control == "device"
        # a control program's feedback IS the scheme's post_round, traced
        # — calling both would double-apply it
        in_scan_feedback = (self._ctl_program is not None
                            and self._ctl_program.feedback is not None)
        partial = self.cohort_size < self.population_size
        for i, r in enumerate(range(a, b)):
            self._cum_delay += float(delays[i])
            self._cum_energy += float(energies[i])
            eval_due = bool(self.eval_every and r % self.eval_every == 0)
            if device_ctl:
                test_acc = float(host["test_acc"][i])
            else:
                assert not eval_due or i == (b - a - 1), \
                    "segmentation must end segments at eval rounds"
                test_acc = self.evaluate() if eval_due else float("nan")
            rec = RoundRecord(
                round=r,
                train_loss=float(losses[i]),
                test_acc=test_acc,
                delay=float(delays[i]),
                energy=float(energies[i]),
                cum_delay=self._cum_delay,
                cum_energy=self._cum_energy,
                received=int(host["received"][i]),
                gamma=float(gammas[i]),
                rho_mean=(float(host["rho_mean"][i]) if ctl is None
                          else float(np.mean(ctl.rho))),
                delta_mean=(float(host["delta_mean"][i]) if ctl is None
                            else float(np.mean(ctl.delta))),
                power_mean=(float(host["power_mean"][i]) if ctl is None
                            else float(np.mean(ctl.power))),
                cohort=host["cohort"][i].tolist() if partial else [],
                participation=self.cohort_size / self.population_size,
                staleness=(float(np.mean(taus[i]))
                           if taus is not None else 0.0),
            )
            self.history.append(rec)
            if not in_scan_feedback:
                self.scheme.post_round(r, {"train_loss": rec.train_loss,
                                           "delay": rec.delay,
                                           "test_acc": rec.test_acc})

    # ------------------------------------------------------------------ #
    # lazy host sync (device rng)
    # ------------------------------------------------------------------ #
    def _sync_host_population(self) -> None:
        """Fold the device-resident (N,) population state back into the
        host ``Population`` + range estimates and refresh the host cohort
        view. Called once at the end of ``run()`` (or eagerly between
        segments only when host recontrol needs the view) — the fix for
        the old per-segment (N,) download/upload round trip."""
        if not self._host_pop_stale:
            return
        read = self._reads
        with spans.span(spans.SYNC, read, seg=self._seg):
            if self._pop_mesh is not None:
                host_sync(self.population, self._pop_dev, read=read)
            else:
                ch = self.population.channel
                ch.fading_mean[:] = read(self._fading_dev)
                ch.interference[:] = read(self._interference_dev)
                if self.block_fading:
                    # the unsharded device body redraws the FULL
                    # population each epoch (eager), so every realization
                    # is current
                    self.population.fading_epoch[:] = self.population.epoch
            n = self.population_size
            self._range_sq_pop[:] = read(self._range_sq_dev, np.float64)[:n]
            self.channel = self.population.view(self.cohort)
            self._host_pop_stale = False

    # ------------------------------------------------------------------ #
    # the public loop
    # ------------------------------------------------------------------ #
    def _run_segment(self, a: int, b: int) -> None:
        with spans.span(spans.PREPARE, seg=self._seg) as t:
            decide_first = self._decide_first(a)
            if self.rng == "host":
                xs, consts, ctl = self._prepare_host_segment(a, b)
                carry = self._host_carry()
            else:
                xs = None
                consts, ctl = self._prepare_device_segment(a, b)
                carry = self._device_carry()
            t.set_metadata(uploads=self._n_pop_uploads)
        with spans.span(spans.DISPATCH, seg=self._seg, rounds=b - a) as t:
            carry, log = self._seg_jit(carry, xs, consts, self._data_dev,
                                       b - a, decide_first)
            t.set_metadata(traces=self._n_traces)
        self._absorb_segment(a, b, ctl, carry, log)

    def run(self, num_rounds: int, log_every: int = 0) -> List[RoundRecord]:
        if self.eval_every == 1 and self.max_segment != 1 \
                and num_rounds > 1 and self.control == "host":
            warnings.warn(
                "ScanRunner with eval_every=1 (the FedRunner default) "
                "evaluates after every round, so every scanned segment "
                "has length 1 and nothing is amortized; pass eval_every=0, "
                "an eval cadence of k rounds, or control='device' (the "
                "in-scan eval head)", stacklevel=2)
        with spans.span(spans.RUN, seg=self._seg):
            self._ensure_device_world()
            # round numbering restarts at 0 on every run() call, exactly
            # like FedRunner.run (history keeps appending; eval cadence and
            # LTFL's recontrol_every schedule restart with the numbering)
            for a, b in self._segment_spans(0, num_rounds):
                self._run_segment(a, b)
                if log_every:
                    for rec in self.history[-(b - a):]:
                        if rec.round % log_every == 0:
                            print(f"[{self.scheme.name}] "
                                  f"round={rec.round:4d} "
                                  f"loss={rec.train_loss:.4f} "
                                  f"acc={rec.test_acc:.3f} "
                                  f"delay={rec.delay:9.1f}s "
                                  f"energy={rec.energy:8.2f}J "
                                  f"recv={rec.received}/{self.num_devices}")
            if self.rng == "device":
                self._sync_host_population()
        return self.history

    def lower_segment(self, num_rounds: int) -> jax.stages.Lowered:
        """AOT-lower the first segment that ``run(num_rounds)`` would
        execute (device rng), for inspecting the program ``run`` compiles:
        its HLO, memory analysis and compile time."""
        if self.rng != "device":
            raise ValueError("lower_segment needs rng='device'")
        self._ensure_device_world()
        a, b = self._segment_spans(0, num_rounds)[0]
        consts, _ = self._prepare_device_segment(a, b)
        return self._seg_jit.lower(self._device_carry(), None, consts,
                                   self._data_dev, b - a,
                                   self._decide_first(a))

    # ------------------------------------------------------------------ #
    # vmap over lanes (seeds, schemes, regimes, cohort grids)
    # ------------------------------------------------------------------ #
    def _lane_extra_kwargs(self) -> Dict[str, Any]:
        """Engine-specific constructor kwargs a lane must inherit from
        the parent ({} here; AsyncRunner forwards its deadline / buffer /
        churn spec so lanes run the same async scenario)."""
        return {}

    def _engine_signature(self) -> tuple:
        """Engine statics baked into the compiled segment beyond the
        base ScanRunner set (() here; AsyncRunner contributes its
        deadline / buffer-size / churn constants)."""
        return ()

    def _build_lane(self, spec: LaneSpec) -> "ScanRunner":
        """A lane runner: the parent's construction inputs with the
        spec's seed / scheme / config / kwargs overrides applied.
        ``type(self)`` keeps subclasses (AsyncRunner) laning as
        themselves."""
        c = self._ctor
        kw = dict(c["kwargs"])
        kw.update(self._lane_extra_kwargs())
        if spec.kwargs:
            kw.update(spec.kwargs)
        kw["seed"] = int(spec.seed)
        scheme = (spec.scheme_factory() if spec.scheme_factory is not None
                  else copy.deepcopy(self._scheme_proto))
        lane = type(self)(c["model"], c["params"],
                          spec.ltfl if spec.ltfl is not None else c["ltfl"],
                          c["train"], c["test"], scheme, rng=self.rng,
                          control=self.control,
                          max_segment=self.max_segment,
                          population_sharding=self._pop_mesh, **kw)
        lane._eval_fn = self._eval_fn          # share the jitted eval
        return lane

    def _lane_signature(self, lane: "ScanRunner") -> tuple:
        """The shape-bucket key: everything a compiled segment BAKES in
        as a python constant. Lanes share one vmapped trace iff their
        signatures match — a static value missing here would let one
        lane silently run under another lane's constants."""
        sig = (lane._scan_shape_signature(), lane.rng, lane.control,
               lane.max_segment, type(lane.sampler).__name__,
               lane.scheme.scan_lane_signature(lane),
               lane._engine_signature())
        if lane.rng == "device" and \
                not isinstance(lane.sampler, UniformSampler):
            # channel-/energy-aware sampler twins close over host config
            # floats (reference power, energy budget, CPU energy model):
            # lanes may only share a trace when those baked values match
            w, l = lane.ltfl.wireless, lane.ltfl
            sig += ((float(w.p_min), float(w.p_max), float(l.e_max),
                     float(w.k_eff), float(w.sigma_exp),
                     float(w.cycles_per_sample)),)
        return sig

    def run_sweep(self, sweep: Union[SweepSpec, Sequence[int]],
                  num_rounds: int,
                  scheme_factory: Optional[Callable[[], Any]] = None
                  ) -> List[List[RoundRecord]]:
        """Run a batch of experiment lanes with ALL device work vmapped.

        ``sweep`` is either a sequence of seeds (homogeneous replicas of
        THIS runner's config — the original API) or a ``SweepSpec``
        whose lanes vary scheme, channel regime, budgets, seed and
        cohort shape heterogeneously. Lanes are grouped into
        static-shape BUCKETS (``_lane_signature``): each bucket runs as
        one jitted ``vmap``-over-lanes scan per segment plan, so the
        whole grid costs one compile per bucket x (segment length,
        decide phase) — not one per config. Host work between segments
        (Algorithm 1 under host control, eval) runs per lane.

        Static vs laned: a lane's channel regime, budget floats and
        learning rate are LANED (stacked per lane, read in-trace — see
        ``_LANED_WIRELESS`` / ``_LANED_LTFL``), so they vary freely
        within a bucket; shapes (U, N, batch), static loop bounds
        (``bo_iters``, ``alt_max_iters``) and scheme constants
        (compressor parameters, arm grids, cadences) are STATIC — lanes
        that differ in them open a new bucket, which is correct but
        costs a separate compile. Each lane's history is bitwise equal
        to a solo ``ScanRunner`` run of the same config (solo traces run
        the identical laned arithmetic).

        A ``population_sharding`` runner sweeps too: per-lane registries
        and parts tables stack lane-major over the SAME ('pop',) mesh
        (the lane axis rides replicated, each lane's (N_pad,) block
        structure intact), so U-grid / regime / seed lanes vmap over the
        sharded scan bodies. The one unsupported combination is
        heterogeneous N across lanes (incompatible block structures) —
        rejected up front with the lane's label.

        ``scheme_factory`` applies only to the seed-list form; SweepSpec
        lanes carry their own factories. Returns one ``RoundRecord``
        history per lane, in lane order; bucket metadata lands on
        ``self._last_sweep_buckets``.
        """
        if isinstance(sweep, SweepSpec):
            if scheme_factory is not None:
                raise ValueError(
                    "scheme_factory is the legacy seed-list argument; "
                    "SweepSpec lanes carry per-lane scheme factories")
            specs = list(sweep.lanes)
        else:
            specs = [LaneSpec(seed=int(s), scheme_factory=scheme_factory)
                     for s in sweep]
        if self._pop_mesh is not None:
            # sharded lanes stack lane-major OVER the same ('pop',)
            # layout; a lane with a different N would need its own
            # (N_pad,) block structure and cannot share the registry
            for spec in specs:
                n_lane = (spec.kwargs or {}).get(
                    "population_size", self.population_size)
                if n_lane is not None and \
                        int(n_lane) != self.population_size:
                    raise ValueError(
                        f"run_sweep lane {spec.label!r} sets "
                        f"population_size={int(n_lane)} but the sharded "
                        f"parent registers {self.population_size} devices; "
                        "lanes over one population_sharding mesh must "
                        "share N (cohort-size/regime/seed grids are fine) "
                        "— run heterogeneous-N points as separate sweeps")
        lanes = [self._build_lane(spec) for spec in specs]
        self._ensure_device_world()

        def stack(trees):
            # lane-major stack that KEEPS the ('pop',) layout: a leaf
            # sharded over the mesh (registry channel state, the parts
            # table, carried fading) comes back as (L, ...) with the
            # lane axis replicated and the original spec intact, so the
            # sweep vmap's shard_map bodies see per-lane sharded blocks
            # instead of an L-times-replicated (N_pad,) gather
            def s(*x):
                out = jnp.stack(x)
                sh = getattr(x[0], "sharding", None)
                if isinstance(sh, NamedSharding) and \
                        any(a is not None for a in sh.spec):
                    out = jax.device_put(out, NamedSharding(
                        sh.mesh, PartitionSpec(None, *sh.spec)))
                return out
            return jax.tree_util.tree_map(s, *trees)

        def unstack(tree, i):
            return jax.tree_util.tree_map(lambda x: x[i], tree)

        # static-shape bucketing: one compiled program per distinct
        # signature. The parent runner fronts for its own bucket (its
        # cached _sweep_jit + closures keep serving repeat sweeps);
        # other buckets elect their first lane as trace representative.
        self_sig = self._lane_signature(self)
        buckets: Dict[tuple, List[int]] = {}
        for i, lane in enumerate(lanes):
            buckets.setdefault(self._lane_signature(lane), []).append(i)
        self._last_sweep_buckets = []
        for sig, idxs in buckets.items():
            glanes = [lanes[i] for i in idxs]
            rep = self if sig == self_sig else glanes[0]
            self._last_sweep_buckets.append(
                {"signature": sig, "rep": rep, "lane_indices": list(idxs)})
            pad = None
            if self.rng == "device":
                pad = max(int(lane.batcher.client_sizes().max(initial=0))
                          for lane in glanes)
            for lane in glanes:
                lane._data_dev = self._data_dev   # one shared backing pool
                lane._ensure_device_world(pad_to=pad)
            for a, b in rep._segment_spans(0, num_rounds):
                decide_first = rep._decide_first(a)
                if self.rng == "host":
                    preps = [lane._prepare_host_segment(a, b)
                             for lane in glanes]
                    xss = stack([p[0] for p in preps])
                    constss = stack([p[1] for p in preps])
                    carries = stack([lane._host_carry()
                                     for lane in glanes])
                    carries, logs = rep._sweep_jit(
                        carries, xss, constss, self._data_dev, b - a,
                        decide_first)
                    ctls = [p[2] for p in preps]
                else:
                    preps = [lane._prepare_device_segment(a, b)
                             for lane in glanes]
                    constss = stack([p[0] for p in preps])
                    carries = stack([lane._device_carry()
                                     for lane in glanes])
                    carries, logs = rep._sweep_jit(
                        carries, None, constss, self._data_dev, b - a,
                        decide_first)
                    ctls = [p[1] for p in preps]
                for i, lane in enumerate(glanes):
                    lane._absorb_segment(a, b, ctls[i],
                                         unstack(carries, i),
                                         unstack(logs, i))
            if self.rng == "device":
                for lane in glanes:
                    lane._sync_host_population()
        return [lane.history for lane in lanes]
