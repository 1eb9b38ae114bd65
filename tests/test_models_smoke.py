"""Per-architecture smoke tests (deliverable (f)): every assigned arch at
reduced scale — one forward/train step + one decode step on CPU, asserting
output shapes and finiteness. Plus prefill/decode consistency for a
representative subset."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, make_decode_inputs, make_train_batch

ARCHS = configs.list_archs()


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = configs.reduce_for_smoke(configs.get_arch(name))
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            cache[name] = (cfg, model, params)
        return cache[name]

    return get


@pytest.mark.parametrize("name", ARCHS)
def test_forward_and_loss(name, built):
    cfg, model, params = built(name)
    batch = make_train_batch(cfg, 2, 32)
    logits, aux = jax.jit(model.forward)(params, batch)
    S_extra = cfg.num_image_tokens if cfg.family == "vlm" else 0
    assert logits.shape == (2, 32 + S_extra, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    loss = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    # untrained loss should be near ln(vocab)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 2.0


@pytest.mark.parametrize("name", ARCHS)
def test_one_train_step_reduces_loss(name, built):
    cfg, model, params = built(name)
    batch = make_train_batch(cfg, 2, 32)
    loss_fn = jax.jit(jax.value_and_grad(model.loss))
    l0, g = loss_fn(params, batch)
    params2 = jax.tree_util.tree_map(
        lambda p, gi: p - (0.2 * gi.astype(jnp.float32)).astype(p.dtype),
        params, g)
    l1, _ = loss_fn(params2, batch)
    assert float(l1) < float(l0), (float(l0), float(l1))


@pytest.mark.parametrize("name", ARCHS)
def test_decode_step(name, built):
    cfg, model, params = built(name)
    dec = make_decode_inputs(model, cfg, 2, 64)
    logits, cache = jax.jit(model.decode_step)(
        params, dec["token"], dec["pos"], dec["cache"])
    assert logits.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # cache structure preserved
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(dec["cache"])


# consistency: prefill(prompt) then decode(next) == forward(prompt+next)
CONSISTENCY_ARCHS = ["granite-8b", "rwkv6-7b", "deepseek-v2-lite-16b",
                     "zamba2-2.7b", "whisper-medium"]


@pytest.mark.parametrize("name", CONSISTENCY_ARCHS)
def test_prefill_decode_consistency(name, built):
    cfg, model, params = built(name)
    S = 16
    batch = make_train_batch(cfg, 2, S + 1)
    tokens = batch["tokens"]
    full = dict(batch)
    full.pop("labels")

    # reference: full forward over S+1 tokens; compare the logits that
    # predict token S+1 (position index S).
    logits_full, _ = jax.jit(model.forward)(params, full)

    prompt = {k: (v[:, :S] if k == "tokens" else v) for k, v in full.items()}
    _, prompt_cache = jax.jit(model.prefill)(params, prompt)
    # build a decode cache with headroom and splice the prompt cache in:
    # pads ONLY genuinely seq-sized axes (cross caches / recurrent states
    # keep their shapes)
    cache = model.init_cache(2, S + 8)

    def splice(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        for ax in range(dst.ndim):
            if src.shape[ax] != dst.shape[ax]:
                sl = [slice(None)] * dst.ndim
                sl[ax] = slice(0, src.shape[ax])
                return dst.at[tuple(sl)].set(src.astype(dst.dtype))
        return src.astype(dst.dtype)

    cache = jax.tree_util.tree_map(splice, cache, prompt_cache)
    pos = jnp.full((2,), S, jnp.int32)
    logits_dec, _ = jax.jit(model.decode_step)(
        params, tokens[:, S], pos, cache)

    n_img = cfg.num_image_tokens if cfg.family == "vlm" else 0
    ref = logits_full[:, n_img + S, :].astype(np.float32)
    got = np.asarray(logits_dec, np.float32)
    # bf16 params + different contraction orders: modest tolerance
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.15)


def test_param_counts_match_published():
    expectations = {
        "qwen1.5-32b": (30e9, 40e9),
        "rwkv6-7b": (6e9, 8.5e9),
        "deepseek-v2-lite-16b": (14e9, 18e9),
        "nemotron-4-340b": (320e9, 360e9),
        "granite-8b": (7e9, 9e9),
        "whisper-medium": (0.6e9, 1.0e9),
        "olmoe-1b-7b": (6e9, 8e9),
        "zamba2-2.7b": (2.0e9, 3.2e9),
        "phi-3-vision-4.2b": (3.3e9, 4.5e9),
        "mistral-large-123b": (115e9, 130e9),
    }
    for name, (lo, hi) in expectations.items():
        n = configs.get_arch(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B outside [{lo},{hi}]"


def test_reduced_configs_within_smoke_budget():
    for name in ARCHS:
        r = configs.reduce_for_smoke(configs.get_arch(name))
        assert r.n_layers <= 2
        assert r.d_model <= 512
        if r.moe:
            assert r.moe.num_experts <= 4
