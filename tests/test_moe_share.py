"""An expert layer's share of its experts, no dropped token, YaRN, and
next-token accuracy (repro.models: moe, common, mla, transformer)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ArchConfig, MoEConfig
from repro.configs.deepseek_v2_lite_16b import CONFIG, ep_share
from repro.models import moe as moe_mod
from repro.models.common import (init_params, rope_inv_freq, rope_tables,
                                 softmax_mscale)
from repro.models.layers import mlp_apply

E, K, SHARES = 8, 3, 4


def _layer(held=0, first=0, norm=False):
    return ArchConfig(
        name="moe-test", family="moe", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=16, vocab_size=64, mlp_act="silu", glu=True,
        moe=MoEConfig(num_experts=E, top_k=K, d_expert=16,
                      num_shared_experts=1, d_shared_expert=16,
                      norm_topk_prob=norm, routed_scaling_factor=1.5,
                      aux_loss_coef=0.01, experts_held=held,
                      first_expert=first))


def _whole():
    cfg = _layer()
    p = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        init_params(jax.random.PRNGKey(0), moe_mod.moe_specs(cfg)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    return cfg, p, x


def _share(p, held, first):
    return {k: (v[first:first + held] if k in ("w_gate", "w_up", "w_down")
                else v) for k, v in p.items()}


def _dense(cfg, p, x):
    """Every token through all of its top-k experts, weighted by the gate,
    plus the shared experts: the layer written densely."""
    _, top_w, top_i = moe_mod.gate(cfg, p["router"], x)
    y = mlp_apply(cfg, p["shared"], x)
    for e in range(cfg.moe.num_experts):
        g = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)[..., None]
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        y = y + g * (h @ p["w_down"][e])
    return y


@pytest.mark.parametrize("norm", [False, True], ids=["published", "renorm"])
def test_shares_sum_to_the_uncut_layer(norm):
    """Four shares of two experts each: their held experts' outputs, plus
    the shared experts counted once, are the whole layer's; each share's
    balance loss is the whole layer's, and their counts add up to its.
    Tolerance: float32 sums of the same products in another order."""
    cfg, p, x = _whole()
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, norm_topk_prob=norm))
    y, aux, counts = moe_mod.moe_apply(cfg, p, x)
    shared = mlp_apply(cfg, p["shared"], x)
    held = E // SHARES
    total = shared
    tokens = []
    for s in range(SHARES):
        sc = cfg.replace(moe=dataclasses.replace(
            cfg.moe, experts_held=held, first_expert=s * held))
        ys, aux_s, c = moe_mod.moe_apply(sc, _share(p, held, s * held), x)
        total = total + (ys - shared)
        assert float(aux_s) == float(aux)
        assert float(c["absent"]) == x.shape[0] * x.shape[1] * K \
            - float(jnp.sum(c["expert_tokens"]))
        tokens.append(c["expert_tokens"])
    np.testing.assert_allclose(total, y, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate(tokens),
                                  counts["expert_tokens"])
    assert float(counts["absent"]) == 0.0
    np.testing.assert_allclose(y, _dense(cfg, p, x), rtol=1e-5, atol=1e-5)


def test_no_token_drops_when_every_token_picks_one_expert():
    """A router that sends every token to expert 5 (and two others): the
    layer equals the dense one, where a capacity of 1.25 T K / E tokens
    per expert would drop most of them; the share holding expert 5 alone
    gives its every token."""
    cfg, p, x = _whole()
    p = dict(p, router=p["router"].at[:, 5].set(0.0))
    x = x.at[..., 0].set(10.0)
    p["router"] = p["router"].at[0, 5].set(10.0)
    _, _, top_i = moe_mod.gate(cfg, p["router"], x)
    assert bool(jnp.all(jnp.any(top_i == 5, axis=-1)))
    y, _, counts = moe_mod.moe_apply(cfg, p, x)
    np.testing.assert_allclose(y, _dense(cfg, p, x), rtol=1e-5, atol=1e-5)
    assert float(counts["expert_tokens"][5]) == x.shape[0] * x.shape[1]
    one = cfg.replace(moe=dataclasses.replace(cfg.moe, experts_held=1,
                                              first_expert=5))
    _, _, c = moe_mod.moe_apply(one, _share(p, 1, 5), x)
    assert float(c["expert_tokens"][0]) == x.shape[0] * x.shape[1]


def _yarn_published(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                    mscale_all_dim, seq_len):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` in float64 numpy:
    inverse frequencies, and the cos table of (seq_len, dim / 2)."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    inv = inter * (1 - mask) + extra * mask
    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    return inv, np.cos(np.outer(np.arange(seq_len), inv)) * m


def test_yarn_tables_are_the_published_ones():
    """DeepSeek-V2-Lite's YaRN at 4,096 positions: the frequencies to
    float32 rounding; the tables to 1e-3, the float32 rounding of
    angles up to 4,096 rad (an ulp there is 4.9e-4); the softmax scale's
    factor (0.1 x 0.707 x ln 40 + 1)^2."""
    rs, dim = CONFIG.rope_scaling, CONFIG.mla.qk_rope_head_dim
    inv, cos = _yarn_published(dim, CONFIG.rope_theta, rs.factor,
                               rs.original_max_position_embeddings,
                               rs.beta_fast, rs.beta_slow, rs.mscale,
                               rs.mscale_all_dim, 4096)
    np.testing.assert_allclose(rope_inv_freq(dim, CONFIG.rope_theta, rs),
                               inv, rtol=1e-6)
    c, s = rope_tables(4096, dim, CONFIG.rope_theta, scaling=rs)
    np.testing.assert_allclose(c, cos, atol=1e-3)
    assert math.isclose(softmax_mscale(rs),
                        (0.1 * 0.707 * math.log(40) + 1) ** 2, rel_tol=1e-12)
    assert 1.58 < softmax_mscale(rs) < 1.60
    # the ramp: dims below the beta_fast correction keep theta's
    # frequencies, those above the beta_slow one are divided by 40
    assert inv[0] == 1.0 and math.isclose(inv[-1] * 40,
                                          CONFIG.rope_theta ** (-62 / 64))


def test_the_published_gate_and_the_share():
    """DeepSeek-V2-Lite routes without renormalising, at scale 1, with
    the balance loss weight 0.001; the share holds experts 0-7 of 64, the
    dense layer and 4 MoE layers, and 12,800 vocabulary rows."""
    mo = CONFIG.moe
    assert (mo.norm_topk_prob, mo.routed_scaling_factor,
            mo.aux_loss_coef) == (False, 1.0, 0.001)
    share = ep_share()
    assert (share.n_layers, share.vocab_size, share.moe.held,
            share.moe.first_expert, share.moe.num_experts) == \
        (5, 12800, 8, 0, 64)
    assert ep_share(chip=3).moe.first_expert == 24


def test_decoder_accuracy_on_a_planted_batch():
    """Next-token accuracy with the loss's shift: labels that agree with
    the model's own prediction at exactly half of the positions read
    0.5."""
    from repro import configs
    from repro.models import build_model
    cfg = configs.reduce_for_smoke(CONFIG)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                cfg.vocab_size)
    logits, _ = jax.jit(model.forward)(params, {"tokens": tokens})
    pred = jnp.argmax(logits[:, :-1], axis=-1)              # (2, 16)
    right = jnp.arange(16) % 2 == 0
    labels = jnp.concatenate([tokens[:, :1], jnp.where(
        right, pred, (pred + 1) % cfg.vocab_size)], axis=1)
    acc = jax.jit(model.accuracy)(params, {"tokens": tokens,
                                           "labels": labels})
    assert float(acc) == 0.5
