"""The program's own tracing: host spans of ``ScanRunner`` read back from a
profiler trace, their counters, the stage scopes in the compiled
segment's op-name metadata, and results unchanged by the profiler."""
import glob
import math

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import spans
from repro.configs.base import LTFLConfig
from repro.data import ArrayDataset, synthetic_cifar
from repro.fed import FedMPScheme, FedSGDScheme, LTFLScheme, ScanRunner
from repro.models import MLP

LTFL = LTFLConfig(num_devices=4, samples_min=40, samples_max=60,
                  bo_iters=3, alt_max_iters=2)


class _Watched:
    """A device array of the segment's log whose every host read is
    recorded (its bytes appended to ``seen``)."""

    def __init__(self, x, seen):
        self.x, self.seen = x, seen

    @property
    def nbytes(self):
        return self.x.nbytes

    def __array__(self, dtype=None, copy=None):
        self.seen.append(self.x.nbytes)
        return np.asarray(self.x, dtype)


@pytest.fixture(scope="module")
def world():
    imgs, labels = synthetic_cifar(600, seed=0)
    timgs, tlabels = synthetic_cifar(128, seed=1)
    train = ArrayDataset({"images": imgs, "labels": labels})
    test = ArrayDataset({"images": timgs, "labels": tlabels})
    model = MLP()
    return model, model.init(jax.random.PRNGKey(0)), train, test


def _runner(world, scheme=None, **kw):
    model, params, train, test = world
    return ScanRunner(model, params, LTFL, train, test,
                      scheme or LTFLScheme(recontrol_every=1),
                      batch_size=8, seed=0, eval_every=2, rng="device",
                      control="device", block_fading=True, **kw)


def _traced_run(runner, rounds, d):
    """``runner.run(rounds)`` under the profiler, with every read of each
    segment's log recorded; the host events (name, start, end, arguments)
    in time order, the outer span first, and the reads per segment."""
    dispatch, logs = runner._seg_jit, []

    def watched(*args):
        carry, log = dispatch(*args)
        logs.append([])
        return carry, log._replace(**{
            k: _Watched(v, logs[-1]) for k, v in log._asdict().items()
            if v is not None})

    runner._seg_jit = watched
    try:
        with jax.profiler.trace(d):
            runner.run(rounds)
    finally:
        runner._seg_jit = dispatch
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        events.append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats)))
    return sorted(events, key=lambda e: (e[1], -e[2])), logs


@pytest.fixture(scope="module")
def traced(world, tmp_path_factory):
    """Six LTFL rounds in three 2-round segments under the profiler."""
    runner = _runner(world, max_segment=2)
    return (runner,) + _traced_run(runner, 6,
                                   str(tmp_path_factory.mktemp("trace")))


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(events, outer, name):
    return [e for e in _named(events, name)
            if outer[1] <= e[1] and e[2] <= outer[2]]


def test_host_span_tree_per_segment(traced):
    runner, events, _ = traced
    (run,) = _named(events, spans.RUN)
    assert run[3]["seg"] == 0
    segs = []
    for name in (spans.PREPARE, spans.DISPATCH, spans.ABSORB):
        found = _inside(events, run, name)
        assert [e[3]["seg"] for e in found] == [0, 1, 2]
        segs.append(found)
    for prepare, dispatch, absorb in zip(*segs):
        # in order, one segment's spans tied together by ``seg``
        assert prepare[2] <= dispatch[1] and dispatch[2] <= absorb[1]
        fetch, gam, rec = (_inside(events, absorb, n) for n in
                           (spans.FETCH, spans.GAMMA, spans.RECORDS))
        assert len(fetch) == len(gam) == len(rec) == 1
        assert fetch[0][2] <= gam[0][1] and gam[0][2] <= rec[0][1]
        assert {e[3]["seg"] for e in fetch + gam + rec} == \
            {absorb[3]["seg"]}
    (sync,) = _inside(events, run, spans.SYNC)
    assert sync[3]["seg"] == 3 and sync[1] >= segs[2][-1][2]
    assert runner._seg == 3


def test_fetches_count_every_read(traced):
    """Each absorb span's ``fetches`` and ``fetch_bytes`` are the log
    arrays actually read in it; with sync's, they are every read the
    counter made."""
    runner, events, logs = traced
    absorbs = _named(events, spans.ABSORB)
    # the cohorts and 13 log fields, each once (no inclusion, agg_denom
    # or tau in a synchronous full-cohort run)
    assert [e[3]["fetches"] for e in absorbs] == \
        [len(seen) for seen in logs] == [14, 14, 14]
    assert [e[3]["fetch_bytes"] for e in absorbs] == \
        [sum(seen) for seen in logs]
    # sync reads the carried fading, interference and range statistic
    (sync,) = _named(events, spans.SYNC)
    assert sync[3]["fetches"] == 3
    assert sync[3]["fetch_bytes"] == 3 * 4 * LTFL.num_devices
    assert runner._reads.count == 3 * 14 + 3
    assert runner._reads.bytes == sum(
        e[3]["fetch_bytes"] for e in absorbs + [sync])


def test_control_absorb_reads_the_carry(world, tmp_path):
    """A control program with a host ``absorb`` (FedMP's bandit) has its
    carry read inside ``repro.fetch`` and absorbed in its own span."""
    runner = _runner(world, FedMPScheme())
    events, logs = _traced_run(runner, 2, str(tmp_path))
    (absorb,) = _named(events, spans.ABSORB)
    (fetch,) = _inside(events, absorb, spans.FETCH)
    (ctl,) = _inside(events, absorb, spans.CTL_ABSORB)
    assert fetch[2] <= ctl[1] and ctl[3]["seg"] == 0
    leaves = len(jax.tree_util.tree_leaves(runner._ctl_state))
    assert leaves == 5 and len(logs[0]) == 14
    assert absorb[3]["fetches"] == leaves + 14 == runner._reads.count - 3


def test_dispatch_and_prepare_counters(traced):
    runner, events, _ = traced
    dispatches = _named(events, spans.DISPATCH)
    assert [e[3]["rounds"] for e in dispatches] == [2, 2, 2]
    assert [e[3]["traces"] for e in dispatches] == [runner._n_traces] * 3
    assert runner._n_traces == 1
    prepares = _named(events, spans.PREPARE)
    assert [e[3]["uploads"] for e in prepares] == \
        [runner._n_pop_uploads] * 3


def _same_history(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        for k, va in vars(ra).items():
            vb = getattr(rb, k)
            if isinstance(va, float) and math.isnan(va):
                assert math.isnan(vb), k
            else:
                assert va == vb, k


def test_profiler_leaves_results_bitwise(traced, world):
    runner = traced[0]
    plain = _runner(world, max_segment=2)
    plain.run(6)
    _same_history(runner.history, plain.history)
    for x, y in zip(jax.tree_util.tree_leaves(runner.params),
                    jax.tree_util.tree_leaves(plain.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("scheme", ["ltfl", "fedsgd"])
def test_stage_scopes_in_compiled_segment(world, scheme):
    """Every stage scope names its operations in the optimized program,
    the backward pass's under ``repro.grad``; FedSGD prunes nothing and
    runs no control program."""
    runner = _runner(world, LTFLScheme(recontrol_every=1)
                     if scheme == "ltfl" else FedSGDScheme())
    paths = spans.op_paths(runner.lower_segment(4).compile().as_text())
    stages = {spans.stage_of(p) for p in paths.values()}
    backward = [p for p in paths.values() if "transpose(" in p]
    assert backward
    assert {spans.stage_of(p) for p in backward} == {spans.GRAD}
    if scheme == "ltfl":
        assert set(spans.STAGES) <= stages
    else:
        assert spans.PRUNE not in stages and spans.CONTROL not in stages
        assert {spans.GRAD, spans.RANGE, spans.AGGREGATE, spans.UPDATE,
                spans.CHANNEL, spans.SAMPLER, spans.EVAL} <= stages


def test_op_paths_and_innermost_stage():
    text = (
        '  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'calls=%fc, metadata={op_type="mul" op_name="jit(_segment)/while/'
        'body/repro.control/jit(f)/repro.grad/mul" source_line=3}\n'
        '  ROOT %copy.1 = f32[4]{0} copy(f32[4]{0} %fusion.3)\n'
        '  %sin.0 = f32[4]{0} sine(f32[4]{0} %p), metadata={op_name='
        '"jit(_segment)/vmap(repro.grad)/transpose(jvp(repro.grad))/sin"}\n')
    paths = spans.op_paths(text)
    assert set(paths) == {"fusion.3", "sin.0"}
    assert spans.stage_of(paths["fusion.3"]) == spans.GRAD
    assert spans.stage_of("jit(_segment)/while/body/add") is None
    assert spans.stage_of("a/repro.sampler/b/repro.channel/c") == \
        spans.CHANNEL


def test_expert_stages_and_routing_counters():
    """An expert layer's share through the round: its routing and grouped
    products carry ``repro.route`` and ``repro.experts`` in the compiled
    segment's op names, inside ``repro.grad`` (and ``repro.eval``; the
    routing's index arithmetic, which reads no input, is computed once
    outside them); each round's record holds
    the tokens its held experts took over the cohort (max and mean over
    MoE layer and expert) and the share of assignments to the experts it
    does not hold."""
    from repro import configs
    from repro.configs.deepseek_v2_lite_16b import CONFIG, ep_share
    from repro.models import build_model
    small = configs.reduce_for_smoke(CONFIG)
    cfg = ep_share(small, chips=2, moe_layers=1)          # 2 of 4 experts
    model = build_model(cfg)
    params = jax.tree_util.tree_map(lambda x: x.astype(np.float32),
                                    model.init(jax.random.PRNGKey(0)))
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (300, 16)).astype(np.int32)
    data = ArrayDataset({"tokens": rows, "labels": rows})
    runner = ScanRunner(model, params, LTFL, data, ArrayDataset(
        {"tokens": rows[:4], "labels": rows[:4]}), FedSGDScheme(),
        batch_size=2, seed=0, eval_every=2, rng="device", control="device")
    paths = spans.op_paths(runner.lower_segment(2).compile().as_text())
    for stage in (spans.ROUTE, spans.EXPERTS):
        mine = [p for p in paths.values() if spans.stage_of(p) == stage]
        assert any(spans.GRAD in p for p in mine), stage
        assert any(spans.EVAL in p for p in mine), stage
    records = runner.run(2)
    k, u, held = cfg.moe.top_k, LTFL.num_devices, cfg.moe.held
    for r in records:
        assigned = u * 2 * 16 * k                    # one MoE layer
        assert 0 < r.expert_tokens_mean <= r.expert_tokens_max <= u * 2 * 16
        assert math.isclose(r.absent_share, 1 - held * r.expert_tokens_mean
                            / assigned, rel_tol=1e-6)
