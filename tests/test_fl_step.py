"""The unified jit-able LTFL round step (repro.core.ltfl_step)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import make_fl_train_step, make_plain_train_step
from repro.models import build_model, make_train_batch
from repro.optim import sgd

C = 4


@pytest.fixture(scope="module")
def setup():
    cfg = configs.reduce_for_smoke(configs.get_arch("granite-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = make_train_batch(cfg, C * 2, 32)
    batch = jax.tree_util.tree_map(
        lambda x: x.reshape(C, 2, *x.shape[1:]), b)
    return cfg, model, params, batch


def _controls(drop=0.0):
    return {"rho": jnp.array([0.0, 0.2, 0.4, 0.5]),
            "delta": jnp.array([8.0, 4.0, 2.0, 8.0]),
            "drop_prob": jnp.full((C,), drop),
            "weights": jnp.array([400.0, 500.0, 450.0, 600.0])}


def _build(model, opt, **kw):
    step_fn = make_fl_train_step(model, opt, C, prune_block=32, **kw)
    return step_fn, jax.jit(step_fn)


def test_loss_decreases(setup):
    cfg, model, params, batch = setup
    opt = sgd(0.1)
    opt_state = opt.init(params)
    step_fn, step = _build(model, opt)
    cs = step_fn.init_comp_state(params)
    losses = []
    for i in range(8):
        params, opt_state, cs, m = step(params, opt_state, cs, batch,
                                        _controls(), jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


def test_all_received_without_drops(setup):
    cfg, model, params, batch = setup
    opt = sgd(0.1)
    step_fn, step = _build(model, opt)
    _, _, _, m = step(params, opt.init(params),
                      step_fn.init_comp_state(params), batch, _controls(0.0),
                      jax.random.PRNGKey(0))
    assert int(m["clients_received"]) == C
    assert m["range_sq"].shape == (C,)


def test_certain_drop_freezes_params(setup):
    cfg, model, params, batch = setup
    opt = sgd(0.1)
    step_fn, step = _build(model, opt)
    new_params, _, _, m = step(params, opt.init(params),
                               step_fn.init_comp_state(params), batch,
                               _controls(1.0), jax.random.PRNGKey(0))
    assert int(m["clients_received"]) == 0
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, new_params)
    assert max(jax.tree_util.tree_leaves(diffs)) == 0.0


def test_host_sampled_alpha(setup):
    """The edge-engine mode: the channel outcome is sampled on host and
    passed in as controls['alpha'] — drop pattern must be honored."""
    cfg, model, params, batch = setup
    opt = sgd(0.1)
    step_fn, step = _build(model, opt, simulate_drops=False)
    ctl = dict(_controls(), alpha=jnp.array([1.0, 0.0, 1.0, 0.0]))
    _, _, _, m = step(params, opt.init(params),
                      step_fn.init_comp_state(params), batch, ctl,
                      jax.random.PRNGKey(0))
    assert int(m["clients_received"]) == 2


def test_ablation_switches(setup):
    cfg, model, params, batch = setup
    opt = sgd(0.1)
    for kw in ({"quantize": False}, {"prune": False},
               {"simulate_drops": False}, {"prune_kind": "magnitude"}):
        step_fn, step = _build(model, opt, **kw)
        p, _, _, m = step(params, opt.init(params),
                          step_fn.init_comp_state(params), batch,
                          _controls(), jax.random.PRNGKey(0))
        assert np.isfinite(float(m["loss"]))


def test_compressor_plugins(setup):
    """SignSGD and STC lower into the same compiled step; STC's residual
    state is carried and becomes non-zero after one round."""
    cfg, model, params, batch = setup
    opt = sgd(0.1)
    for name in ("sign", "stc"):
        step_fn, step = _build(model, opt, compressor=name, prune=False)
        cs = step_fn.init_comp_state(params)
        p, _, cs, m = step(params, opt.init(params), cs, batch,
                           _controls(), jax.random.PRNGKey(0))
        assert np.isfinite(float(m["loss"]))
        if name == "stc":
            leaves = jax.tree_util.tree_leaves(cs)
            assert leaves and all(l.shape[0] == C for l in leaves)
            assert any(float(jnp.max(jnp.abs(l))) > 0 for l in leaves)


def test_plain_step(setup):
    cfg, model, params, _ = setup
    batch = make_train_batch(cfg, 4, 32)
    opt = sgd(0.1)
    step = jax.jit(make_plain_train_step(model, opt))
    p, s, m = step(params, opt.init(params), batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


# --------------------------------------------------------------------------- #
# clients streamed k at a time (clients_per_chunk)
# --------------------------------------------------------------------------- #
C5 = 5
STREAM = {"ltfl": dict(compressor="ltfl", prune_kind="magnitude"),
          "fedsgd": dict(compressor="none", prune=False),
          "stc": dict(compressor="stc", prune=False)}


@pytest.fixture(scope="module")
def tiny():
    from repro.models import MLP, MLPConfig
    model = MLP(MLPConfig(input_shape=(4, 4, 3), hidden=(16,)))
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (C5, 6, 4, 4, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (C5, 6), 0, 10)
    return model, params, {"images": x, "labels": y}


def _streamed_step(model, scheme, k):
    step_fn = make_fl_train_step(model, sgd(1.0), C5, clients_per_chunk=k,
                                 simulate_drops=False, **STREAM[scheme])
    return step_fn, jax.jit(step_fn)


def _stream_controls(weights):
    return {"rho": jnp.array([0.0, 0.1, 0.3, 0.5, 0.2]),
            "delta": jnp.array([8.0, 3.0, 2.0, 6.0, 1.0]),
            "alpha": jnp.array([1.0, 1.0, 0.0, 1.0, 1.0]),
            "weights": weights}


@pytest.mark.parametrize("scheme", sorted(STREAM))
def test_streamed_clients_match_the_whole_cohort(tiny, scheme):
    """k in {1, 2, 3} of 5 clients against k = 5: each client's loss and
    range statistic, the STC residuals and each client's upload (its
    weight alone non-zero: the step then moves the weights by that
    upload) bit for bit; the aggregate and the mean loss to float32
    summation order (a relative 1e-6: five float32 terms summed in
    another order)."""
    model, params, batch = tiny
    opt_state = sgd(1.0).init(params)
    weights = jnp.array([40.0, 55.0, 61.0, 47.0, 52.0])
    key = jax.random.PRNGKey(7)
    runs = {}
    for k in (C5, 1, 2, 3):
        step_fn, step = _streamed_step(model, scheme, k)
        cs = step_fn.init_comp_state(params)
        out = step(params, opt_state, cs, batch, _stream_controls(weights),
                   key)
        alone = [step(params, opt_state, cs, batch, _stream_controls(
            jnp.zeros(C5).at[u].set(1.0)), key)[0] for u in (0, 3, 4)]
        runs[k] = (out, alone)
    (p_all, _, cs_all, m_all), alone_all = runs[C5]
    for k in (1, 2, 3):
        (p, _, cs, m), alone = runs[k]
        np.testing.assert_array_equal(m["range_sq"], m_all["range_sq"])
        np.testing.assert_array_equal(m["losses"], m_all["losses"])
        np.testing.assert_allclose(m["loss"], m_all["loss"], rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves((cs, alone)),
                        jax.tree_util.tree_leaves((cs_all, alone_all))):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(p),
                        jax.tree_util.tree_leaves(p_all)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_whole_cohort_step_has_no_loop_over_clients(tiny):
    """At k = C the step is the vmap over all clients: its jaxpr holds no
    scan; streamed, it holds one."""
    model, params, batch = tiny
    ctl = _stream_controls(jnp.ones(C5))
    for k, loops in ((C5, False), (2, True)):
        step_fn, _ = _streamed_step(model, "fedsgd", k)
        jaxpr = jax.make_jaxpr(step_fn)(
            params, (), (), batch, ctl, jax.random.PRNGKey(0))
        assert ("scan" in str(jaxpr)) == loops, k


def test_clients_per_step_fills_the_free_memory(monkeypatch):
    """All clients without a device memory limit; otherwise the most
    whose work (five trees each) fits beside two trees, at least one."""
    from repro.core import ltfl_step

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    tree = {"w": jnp.zeros((1000,), jnp.float32)}          # 4,000 bytes
    for stats, k in ((None, 8), ({"bytes_limit": 10 ** 9}, 8),
                     ({"bytes_limit": 8000 + 3 * 20000, "bytes_in_use": 0},
                      3),
                     ({"bytes_limit": 50000, "bytes_in_use": 45000}, 1)):
        monkeypatch.setattr(jax, "devices", lambda s=stats: [Dev(s)])
        assert ltfl_step.clients_per_step(tree, 8) == k, stats
