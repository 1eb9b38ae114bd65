"""GroupNorm in the channel-minor form against the reshape formulation.

``repro.models.resnet.group_norm`` keeps channels the minor dimension and
groups only the (B, C) statistics. The plain reference below is the
textbook formulation, which views each activation as (B, H, W, g, C // g).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ltfl_paper import ResNetConfig
from repro.models import resnet
from repro.models.resnet import ResNet, group_norm

B, H, W = 3, 4, 5
# float32 rounding of either formula: a gradient entry is compared
# relative to its leaf's largest entry, since entries that cancel to near
# zero carry no relative precision in float32 under either
RTOL = 1e-5


def reshape_group_norm(x, gamma, beta, groups=resnet.GN_GROUPS, eps=1e-5):
    B, H, W, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, W, g, C // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(B, H, W, C) * gamma + beta


def _inputs(C, seed=0):
    kx, kg, kb, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    # an offset mean, so the two-pass variance matters
    x = 3.0 + 2.0 * jax.random.normal(kx, (B, H, W, C), jnp.float32)
    gamma = 1.0 + 0.3 * jax.random.normal(kg, (C,), jnp.float32)
    beta = 0.3 * jax.random.normal(kb, (C,), jnp.float32)
    w = jax.random.normal(kw, (B, H, W, C), jnp.float32)
    return x, gamma, beta, w


def _loss(norm):
    # a weighted sum, so every output element carries its own cotangent
    return lambda x, gamma, beta, w: jnp.sum(norm(x, gamma, beta) * w)


@pytest.mark.parametrize("C,groups", [(64, 8), (128, 8), (256, 8), (512, 8),
                                      (4, 8)])
def test_group_norm_matches_reshape_formulation(C, groups):
    x, gamma, beta, w = _inputs(C)
    got = jax.jit(group_norm, static_argnums=3)(x, gamma, beta, groups)
    want = jax.jit(reshape_group_norm, static_argnums=3)(x, gamma, beta,
                                                         groups)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=RTOL)
    g_got = jax.jit(jax.grad(_loss(lambda *a: group_norm(*a, groups)),
                             argnums=(0, 1, 2)))(x, gamma, beta, w)
    g_want = jax.jit(jax.grad(_loss(lambda *a: reshape_group_norm(*a, groups)),
                              argnums=(0, 1, 2)))(x, gamma, beta, w)
    for name, a, b in zip(("x", "gamma", "beta"), g_got, g_want):
        assert a.dtype == jnp.float32, name
        np.testing.assert_allclose(a, b, rtol=RTOL,
                                   atol=RTOL * float(jnp.max(jnp.abs(b))),
                                   err_msg=name)


def test_group_norm_channels_not_divisible_by_groups_raises():
    x, gamma, beta, _ = _inputs(12)
    with pytest.raises(TypeError):
        reshape_group_norm(x, gamma, beta, 8)
    with pytest.raises(TypeError):
        group_norm(x, gamma, beta, 8)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("C", [64, 512])
def test_group_norm_gradient_reshapes_only_statistics(C):
    """No reshape or transpose in the forward or backward pass takes an
    operand larger than the (B, C) statistics: the lane split stays out."""
    x, gamma, beta, w = _inputs(C)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(group_norm), argnums=(0, 1, 2)))(
        x, gamma, beta, w).jaxpr
    moves = [e for e in _eqns(jaxpr)
             if e.primitive.name in ("reshape", "transpose")]
    assert moves, "the (B, C) statistics are grouped by a reshape"
    for e in moves:
        size = int(np.prod(e.invars[0].aval.shape))
        assert size <= B * C, (e.primitive.name, e.invars[0].aval.shape)
    # the reference does reshape the activation, which the check catches
    ref = jax.make_jaxpr(jax.grad(_loss(reshape_group_norm),
                                  argnums=(0, 1, 2)))(x, gamma, beta, w).jaxpr
    assert any(int(np.prod(e.invars[0].aval.shape)) > B * C
               for e in _eqns(ref) if e.primitive.name == "reshape")


def test_resnet_loss_and_gradient_match_reshape_group_norm(monkeypatch):
    model = ResNet(ResNetConfig(stem_channels=16,
                                group_channels=(16, 32, 32, 64)))
    params = model.init(jax.random.PRNGKey(0))
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    batch = {"images": jax.random.normal(kx, (4, 32, 32, 3), jnp.float32),
             "labels": jax.random.randint(ky, (4,), 0, 10)}
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    monkeypatch.setattr(resnet, "group_norm", reshape_group_norm)
    loss_ref, grads_ref = jax.jit(jax.value_and_grad(model.loss))(params,
                                                                  batch)
    np.testing.assert_allclose(loss, loss_ref, rtol=RTOL)

    def check(path, a, b):
        np.testing.assert_allclose(a, b, rtol=RTOL,
                                   atol=RTOL * float(jnp.max(jnp.abs(b))),
                                   err_msg=jax.tree_util.keystr(path))

    jax.tree_util.tree_map_with_path(check, grads, grads_ref)
