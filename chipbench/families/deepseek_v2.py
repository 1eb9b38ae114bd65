"""DeepSeek-V2's decoder: weights from the seed, packed token rows, the
plain float32 reference of its loss, and its model FLOPs.

The configuration is one chip's share of an expert-parallel deployment
(``model``: the published config's keys, ``num_hidden_layers`` and
``vocab_size`` cut to the share, ``experts_held`` routed experts from id
``first_expert``, rows of ``seq_len`` tokens). The parameter tree has the
layout the program's ``repro.models.transformer.DecoderLM`` reads: the
embedding and head, the dense first layers as a list, the MoE layers
stacked on a leading axis. Nothing here imports the program except
``program_model``, which builds the system under test.

The reference (``loss``) is the published model written out again in
``jax.numpy``, with these departures, the program's too:

* rope rotates the halves (i, i + d/2) of the 64 rope dimensions, where
  DeepSeek's code interleaves them (pairs (2i, 2i + 1)): a fixed
  permutation of the rope columns of ``wq`` and ``w_dkv``;
* the softmax of the next-token loss spans the share's vocabulary rows,
  not all 102,400;
* the routed experts this share does not hold contribute nothing: under
  expert parallelism their chips add their parts;
* LTFL prunes every leaf of two or more dimensions, and the MoE layers'
  norm scales are stacked into (layers, width) leaves.

It computes in blocks so that one device's loss and gradient fit beside
the trees: each layer under ``jax.checkpoint``, attention in chunks of
``Q_CHUNK`` queries, each under ``jax.checkpoint``, and each held expert
densely on every token, weighted by its gate (zero where the token did
not choose it), one expert at a time.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

INPUT_KEY = "tokens"
# no class per sample: the partition reads the number of samples
PARTITION_KEY = None
_TOKENS = 17                # the samples' stream tag under the seed
Q_CHUNK = 512               # queries per attention chunk in the reference
# ``flops_per_sample`` over XLA's CPU count of the reference's loss and
# gradient of one row (1.03 at the share's sizes): XLA counts a loop's
# body once, so of the reference's 8 attention chunks and 8 held experts
# (each run densely on every token) one each, and adds the recompute of
# every layer's forward in the backward pass; the two nearly cancel
XLA_FLOPS_RATIO = (0.95, 1.1)


# --------------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------------- #
def _sizes(m: Dict):
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return d, h, nope, rope, v, m["kv_lora_rank"]


def _attention_shapes(m: Dict) -> Dict:
    d, h, nope, rope, v, r = _sizes(m)
    return {"wq": (d, h * (nope + rope)), "w_dkv": (d, r + rope),
            "kv_norm": (r,), "w_uk": (r, h * nope), "w_uv": (r, h * v),
            "wo": (h * v, d)}


def _ffn_shapes(d: int, f: int) -> Dict:
    return {"wi_gate": (d, f), "wi_up": (d, f), "wo": (f, d)}


def param_shapes(m: Dict) -> Dict:
    """The parameter tree as shapes (tuples), in the program's layout."""
    d, vocab = m["hidden_size"], m["vocab_size"]
    dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - dense
    e, f = m["experts_held"], m["moe_intermediate_size"]

    def layer(ffn):
        return {"ln1": {"gamma": (d,)}, "attn": _attention_shapes(m),
                "ln2": {"gamma": (d,)}, "ffn": ffn}

    moe = {"router": (d, m["n_routed_experts"]), "w_gate": (e, d, f),
           "w_up": (e, d, f), "w_down": (e, f, d),
           "shared": _ffn_shapes(d, f * m["n_shared_experts"])}
    stacked = jax.tree_util.tree_map(lambda s: (n_moe,) + s, layer(moe),
                                     is_leaf=lambda x: isinstance(x, tuple))
    return {"embed": {"tok": (vocab, d), "head": (d, vocab)},
            "final_norm": {"gamma": (d,)},
            "layers": stacked,
            "prefix_layers": [layer(_ffn_shapes(d, m["intermediate_size"]))
                              for _ in range(dense)]}


def _shape_leaves(m: Dict) -> List[tuple]:
    return jax.tree_util.tree_leaves(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))


def num_params(m: Dict) -> int:
    return int(sum(math.prod(s) for s in _shape_leaves(m)))


def init_params(m: Dict, key: jax.Array):
    """Unit norm scales; a normal embedding of scale 0.02; every matrix
    normal with std 1/sqrt(fan-in), fan-in its second-to-last side;
    float32, in one jitted call on the default device."""
    shapes = param_shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for k, (path, s) in zip(jax.random.split(key, len(leaves)),
                                jax.tree_util.tree_flatten_with_path(
                                    shapes, is_leaf=lambda x: isinstance(
                                        x, tuple))[0]):
            name = jax.tree_util.keystr(path)
            if "gamma" in name or "kv_norm" in name:
                out.append(jnp.ones(s, jnp.float32))
            elif "'tok'" in name:
                out.append(0.02 * jax.random.normal(k, s, jnp.float32))
            else:
                out.append(jax.random.normal(k, s, jnp.float32)
                           / math.sqrt(s[-2]))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


# --------------------------------------------------------------------------- #
# samples
# --------------------------------------------------------------------------- #
def _rows(seed: int, num: int, m: Dict, split: int) -> np.ndarray:
    """(num, seq_len) int32 rows of packed documents, attention running
    across their boundaries as in packed pre-training. Each document opens
    with token 0 and runs for 2 + floor(64 * Pareto(1.2)) tokens, at most
    4 * seq_len; its other tokens step through 1..vocab-1 by a stride of
    its own (1 to 16) from a random start, plus a noise of 0, 1 or 2."""
    rng = np.random.default_rng([int(seed), _TOKENS, split])
    v, t = m["vocab_size"], m["seq_len"]
    total = num * t
    lengths = np.minimum(2 + np.floor(64 * rng.pareto(1.2, total // 2 + 1)),
                         4 * t).astype(np.int64)
    lengths = lengths[:np.searchsorted(np.cumsum(lengths), total) + 1]
    doc = np.repeat(np.arange(lengths.size), lengths)[:total]
    pos = np.arange(total) - np.concatenate(
        [[0], np.cumsum(lengths)[:-1]])[doc]
    start = rng.integers(0, v - 1, lengths.size)
    stride = rng.integers(1, 17, lengths.size)
    noise = rng.integers(0, 3, total)
    toks = np.where(pos == 0, 0,
                    1 + (start[doc] + stride[doc] * pos + noise) % (v - 1))
    return toks.reshape(num, t).astype(np.int32)


def dataset(seed: int, cfg: Dict):
    """(train, test) as dicts of host arrays ``tokens`` and ``labels``,
    each (N, seq_len) int32 and the same rows: the model's loss predicts
    ``labels[:, t + 1]`` from ``tokens[:, :t + 1]``."""
    m, dep = cfg["model"], cfg["deployment"]
    out = []
    for split, num in enumerate((dep["train_samples"], dep["test_samples"])):
        rows = _rows(seed, num, m, split)
        out.append({"tokens": rows, "labels": rows})
    return tuple(out)


# --------------------------------------------------------------------------- #
# plain reference
# --------------------------------------------------------------------------- #
def _yarn(m: Dict):
    """YaRN as DeepSeek-V2's modelling code defines it, in float64: the
    rope's inverse frequencies, the cos/sin multiplier and the factor on
    the softmax scale."""
    rs, dim, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    def mscale(s):
        return 1.0 if factor <= 1 else 0.1 * s * math.log(factor) + 1.0

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = extra / factor * ramp + extra * (1 - ramp)
    return (inv, mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]),
            mscale(rs["mscale_all_dim"]) ** 2)


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def _rope(x, cos, sin):
    """x (B, S, heads, d): rotate the halves."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _attention(p, x, m, precision):
    d, h, nope, rope, vd, r = _sizes(m)
    B, S, _ = x.shape
    mm = lambda a, w: jnp.matmul(a, w, precision=precision)  # noqa: E731
    q = mm(x, p["wq"]).reshape(B, S, h, nope + rope)
    dkv = mm(x, p["w_dkv"])
    c = _rms(dkv[..., :r], p["kv_norm"], m["rms_norm_eps"])
    inv, mult, smult = _yarn(m)
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * mult, jnp.float32)
    sin = jnp.asarray(np.sin(ang) * mult, jnp.float32)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k_rope = _rope(dkv[..., None, r:], cos, sin)               # (B,S,1,rope)
    k = jnp.concatenate([mm(c, p["w_uk"]).reshape(B, S, h, nope),
                         jnp.broadcast_to(k_rope, (B, S, h, rope))], -1)
    v = mm(c, p["w_uv"]).reshape(B, S, h, vd)
    scale = (nope + rope) ** -0.5 * smult
    chunk = math.gcd(S, Q_CHUNK)

    @jax.checkpoint
    def queries(i, qc):
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k, precision=precision
                       ).astype(jnp.float32) * scale
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=precision)

    qs = jnp.moveaxis(q.reshape(B, S // chunk, chunk, h, nope + rope), 1, 0)
    out = jax.lax.map(lambda a: queries(*a), (jnp.arange(S // chunk), qs))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, h * vd)
    return mm(out, p["wo"])


def _ffn(p, x, precision):
    mm = lambda a, w: jnp.matmul(a, w, precision=precision)  # noqa: E731
    return mm(jax.nn.silu(mm(x, p["wi_gate"])) * mm(x, p["wi_up"]), p["wo"])


def _moe(p, x, m, precision):
    """The held experts' gated outputs plus the shared experts', and the
    sequence-wise balance loss over all E scores."""
    e_all, k = m["n_routed_experts"], m["num_experts_per_tok"]
    S = x.shape[1]
    scores = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), p["router"], precision=precision), axis=-1)
    top_w, top_i = jax.lax.top_k(scores, k)
    if m["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * m["routed_scaling_factor"]
    chosen = jax.nn.one_hot(top_i, e_all, dtype=jnp.float32)   # (B,S,K,E)
    gates = jnp.einsum("bsk,bske->bse", top_w, chosen)
    f = jnp.sum(chosen, axis=(1, 2)) * e_all / (k * S)
    aux = jnp.mean(jnp.sum(f * jnp.mean(scores, axis=1), axis=-1)) \
        * m["aux_loss_alpha"]
    y = _ffn(p["shared"], x, precision)

    @jax.checkpoint
    def expert(y, i):
        wg, wu, wd = p["w_gate"][i], p["w_up"][i], p["w_down"][i]
        g = gates[..., m["first_expert"] + i][..., None].astype(x.dtype)
        h = jax.nn.silu(jnp.matmul(x, wg, precision=precision)) \
            * jnp.matmul(x, wu, precision=precision)
        return y + g * jnp.matmul(h, wd, precision=precision), None

    y, _ = jax.lax.scan(expert, y, jnp.arange(m["experts_held"]))
    return y, aux


def _layer(p, x, m, precision, moe: bool):
    eps = m["rms_norm_eps"]
    x = x + _attention(p["attn"], _rms(x, p["ln1"]["gamma"], eps), m,
                       precision)
    h = _rms(x, p["ln2"]["gamma"], eps)
    if moe:
        y, aux = _moe(p["ffn"], h, m, precision)
    else:
        y, aux = _ffn(p["ffn"], h, precision), jnp.zeros((), jnp.float32)
    return x + y, aux


def loss(params, tokens, labels, m: Dict,
         precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Mean next-token cross-entropy (position t predicts labels[t + 1]),
    reduced in float32, plus the balance loss of every MoE layer."""
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    aux = jnp.zeros((), jnp.float32)
    for p in params["prefix_layers"]:
        x, a = jax.checkpoint(_layer, static_argnums=(2, 3, 4))(
            p, x, m, precision, False)
        aux = aux + a
    n_moe = m["num_hidden_layers"] - m["first_k_dense_replace"]
    for i in range(n_moe):
        p = jax.tree_util.tree_map(lambda w: w[i], params["layers"])
        x, a = jax.checkpoint(_layer, static_argnums=(2, 3, 4))(
            p, x, m, precision, True)
        aux = aux + a
    x = _rms(x, params["final_norm"]["gamma"], m["rms_norm_eps"])
    z = jnp.matmul(x[:, :-1], params["embed"]["head"],
                   precision=precision).astype(jnp.float32)
    gold = jnp.take_along_axis(z, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold) + aux


# --------------------------------------------------------------------------- #
# model FLOPs
# --------------------------------------------------------------------------- #
def _token_macs(m: Dict) -> Dict[str, float]:
    """Multiply-adds per token of one forward pass, by part: attention
    projections, attention scores and values over all ``seq_len`` keys,
    the dense FFN, the MoE layers' router and shared experts, their
    routed experts at the expected load (top-k of E, of which the share
    holds ``experts_held``), and the head."""
    d, h, nope, rope, vd, r = _sizes(m)
    s = m["seq_len"]
    layers = m["num_hidden_layers"]
    dense = m["first_k_dense_replace"]
    n_moe = layers - dense
    f = m["moe_intermediate_size"]
    proj = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) \
        + h * vd * d
    load = m["num_experts_per_tok"] * m["experts_held"] \
        / m["n_routed_experts"]
    return {"attention": layers * proj,
            "scores": layers * s * h * (nope + rope + vd),
            "dense_ffn": dense * 3 * d * m["intermediate_size"],
            "shared": n_moe * (d * m["n_routed_experts"]
                               + 3 * d * f * m["n_shared_experts"]),
            "routed": n_moe * load * 3 * d * f,
            "head": d * m["vocab_size"]}


def flops_per_sample(m: Dict) -> float:
    """Model FLOPs of one row of ``seq_len`` tokens, forward once and
    backward twice (2 per multiply-add), with the routed experts at the
    expected load: 725.6 MFLOP a token forward at the share's sizes,
    8.92 TFLOP a row."""
    return 3 * 2 * m["seq_len"] * sum(_token_macs(m).values())


def expert_flops(m: Dict, rows: int) -> float:
    """FLOPs of the held experts' grouped products in one device's step
    of ``rows`` rows, forward and backward, at the expected load."""
    return 3 * 2 * rows * m["seq_len"] * _token_macs(m)["routed"]


def quant_entries(m: Dict, block=(256, 256)) -> int:
    """Entries of one device's gradient that the quantizer kernel takes
    per round: those of each leaf of two or more dimensions which, viewed
    as (prod(leading), last), splits into whole (min(256, rows),
    min(256, cols)) tiles, or into 128-lane ones where the last side is a
    multiple of 128; the other leaves are quantized outside it."""
    total = 0
    for shape in _shape_leaves(m):
        if len(shape) < 2:
            continue
        rows, cols = math.prod(shape[:-1]), shape[-1]
        if rows % min(block[0], rows) == 0 and (
                cols % min(block[1], cols) == 0 or cols % 128 == 0):
            total += rows * cols
    return total


# --------------------------------------------------------------------------- #
# the system under test
# --------------------------------------------------------------------------- #
def program_model(m: Dict):
    """The program's DecoderLM at the share's sizes, from its
    ``ArchConfig`` (``repro.configs.deepseek_v2_lite_16b.from_hf``)."""
    from repro.configs.deepseek_v2_lite_16b import from_hf
    from repro.models.transformer import DecoderLM
    if m["q_lora_rank"] or m["tie_word_embeddings"] or \
            m["scoring_func"] != "softmax" or m["topk_method"] != "greedy" \
            or m["rms_norm_eps"] != 1e-6:
        raise ValueError("the program runs DeepSeek-V2-Lite's choices: no q "
                         "LoRA, untied head, softmax greedy top-k routing, "
                         "rms_norm_eps 1e-6")
    return DecoderLM(from_hf(m))
