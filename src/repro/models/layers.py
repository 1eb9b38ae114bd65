"""Attention (GQA / sliding-window / cross / decode-with-cache), MLPs and
embeddings shared by the transformer families.

All functions are functional: ``*_specs(cfg)`` declares parameters,
``*_apply``-style functions consume a matching params dict. Attention uses a
query-chunked formulation for long sequences so prefill_32k never
materializes an (S, S) score matrix.
"""
from __future__ import annotations

import math

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (
    ParamSpec,
    activation,
    apply_rope,
    apply_rope_at,
    rope_tables,
    shard_hint,
)

NEG_INF = -1e30
# query-chunked attention kicks in above this sequence length
CHUNKED_ATTN_THRESHOLD = 8192
Q_CHUNK = 512


# --------------------------------------------------------------------------- #
# Embeddings
# --------------------------------------------------------------------------- #
def embedding_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    s = {
        "tok": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                         "embed", scale=0.02),
    }
    if not cfg.tie_embeddings:
        s["head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                              ("embed", "vocab"), "normal")
    if cfg.pos_emb == "learned":
        # sized generously; decode indexes by absolute position
        s["pos"] = ParamSpec((max(cfg.encoder_seq, 4096), cfg.d_model),
                             (None, "embed"), "embed", scale=0.02)
    return s


def embed_tokens(cfg: ArchConfig, p, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    return shard_hint(x, ("batch", "act_seq", "act_embed"))


def lm_head(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    logits = jnp.einsum("...d,dv->...v", x, w)
    return logits


# --------------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------------- #
def attention_specs(cfg: ArchConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, h * hd), ("embed", "heads_fused"), "normal"),
        "wk": ParamSpec((d, kv * hd), ("embed", "kv_fused"), "normal"),
        "wv": ParamSpec((d, kv * hd), ("embed", "kv_fused"), "normal"),
        "wo": ParamSpec((h * hd, d), ("heads_fused", "embed"), "normal"),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((h * hd,), ("heads_fused",), "zeros")
        s["bk"] = ParamSpec((kv * hd,), ("kv_fused",), "zeros")
        s["bv"] = ParamSpec((kv * hd,), ("kv_fused",), "zeros")
    return s


def _project_qkv(cfg: ArchConfig, p, x: jax.Array, kv_x: jax.Array):
    B, S = x.shape[0], x.shape[1]
    Skv = kv_x.shape[1]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"])
    k = jnp.einsum("bsd,df->bsf", kv_x, p["wk"])
    v = jnp.einsum("bsd,df->bsf", kv_x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, Skv, kv, hd)
    v = v.reshape(B, Skv, kv, hd)
    return q, k, v


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _attend_full(q, k, v, mask_bias, scale=None):
    """Grouped-query attention without materializing repeated KV.

    q (B,Sq,KV,G,hd); k/v (B,Skv,KV,hd); mask_bias (Sq,Skv) or None;
    ``scale`` of the scores, hd^-0.5 by default. Returns (B,Sq,KV,G,hd).
    """
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) \
        * scale
    if mask_bias is not None:
        scores = scores + mask_bias[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def _causal_bias(sq: int, skv: int, q_offset: int,
                 window: int) -> jax.Array:
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)


def attend(cfg: ArchConfig, q, k, v, *, causal: bool,
           q_offset: int = 0, scale: Optional[float] = None,
           chunk_above: int = CHUNKED_ATTN_THRESHOLD) -> jax.Array:
    """Dispatch between full and query-chunked attention: queries run in
    chunks of ``Q_CHUNK`` once there are more than ``chunk_above`` of
    them, each chunk recomputed in the backward pass rather than its
    scores kept.

    q (B,Sq,H,hd); k,v (B,Skv,KV,hd). Returns (B,Sq,H,hd).
    """
    B, sq, H, hd = q.shape
    kv = k.shape[2]
    hd_v = v.shape[-1]            # may differ from hd (MLA: qk 192, v 128)
    groups = H // kv
    qg = q.reshape(B, sq, kv, groups, hd)
    skv = k.shape[1]
    window = cfg.sliding_window
    if sq <= chunk_above:
        bias = _causal_bias(sq, skv, q_offset, window) if causal else None
        out = _attend_full(qg, k, v, bias, scale)
        return out.reshape(B, sq, H, hd_v)

    # -- query-chunked path: never materialize (Sq, Skv) at once ---------- #
    # chunk must divide sq (vlm prefixes make sq irregular: gcd handles it)
    qc_len = math.gcd(sq, Q_CHUNK)
    assert qc_len >= 16, (sq, Q_CHUNK)
    n_chunks = sq // qc_len
    qs = qg.reshape(B, n_chunks, qc_len, kv, groups, hd)
    qs = jnp.moveaxis(qs, 1, 0)  # (n_chunks, B, qc, KV, G, hd)

    def one_chunk(i, qc):
        if causal:
            qpos = jnp.arange(qc_len)[:, None] + i * qc_len + q_offset
            kpos = jnp.arange(skv)[None, :]
            m = kpos <= qpos
            if window > 0:
                m &= kpos > qpos - window
            bias = jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)
        else:
            bias = None
        return _attend_full(qc, k, v, bias, scale)

    out = jax.lax.map(jax.checkpoint(lambda args: one_chunk(*args)),
                      (jnp.arange(n_chunks), qs))
    out = jnp.moveaxis(out, 0, 1)  # (B, n_chunks, qc, KV, G, hd_v)
    return out.reshape(B, sq, H, hd_v)


def attention_train(cfg: ArchConfig, p, x: jax.Array, *,
                    causal: bool = True,
                    kv_x: Optional[jax.Array] = None,
                    rope: bool = True,
                    q_offset: int = 0) -> jax.Array:
    """Full-sequence attention for train/prefill (self or cross)."""
    kv_in = x if kv_x is None else kv_x
    q, k, v = _project_qkv(cfg, p, x, kv_in)
    if rope and cfg.pos_emb == "rope":
        cos_q, sin_q = rope_tables(q.shape[1], cfg.head_dim, cfg.rope_theta,
                                   offset=q_offset)
        q = apply_rope(q, cos_q, sin_q)
        cos_k, sin_k = rope_tables(k.shape[1], cfg.head_dim, cfg.rope_theta)
        k = apply_rope(k, cos_k, sin_k)
    q = shard_hint(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_hint(k, ("batch", "seq", "kv_heads", "head_dim"))
    out = attend(cfg, q, k, v, causal=causal, q_offset=q_offset)
    out = out.reshape(x.shape[0], x.shape[1], cfg.n_heads * cfg.head_dim)
    y = jnp.einsum("bsf,fd->bsd", out, p["wo"])
    return shard_hint(y, ("batch", "act_seq", "act_embed"))


def attention_prefill_kv(cfg: ArchConfig, p, x: jax.Array):
    """Return the roped (k, v) pair for cache construction during prefill."""
    _, k, v = _project_qkv(cfg, p, x, x)
    if cfg.pos_emb == "rope":
        cos, sin = rope_tables(k.shape[1], cfg.head_dim, cfg.rope_theta)
        k = apply_rope(k, cos, sin)
    return k, v


def attention_decode(cfg: ArchConfig, p, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, pos: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a KV cache.

    x (B, D); cache_k/v (B, S_cache, KV, hd); pos (B,) absolute positions.
    With a sliding window the cache is a ring buffer of length window.
    Returns (y (B, D), new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cache_len = cache_k.shape[1]
    q = jnp.einsum("bd,df->bf", x, p["wq"])
    k = jnp.einsum("bd,df->bf", x, p["wk"])
    v = jnp.einsum("bd,df->bf", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, h, hd)
    k = k.reshape(B, kv, hd)
    v = v.reshape(B, kv, hd)
    if cfg.pos_emb == "rope":
        q = apply_rope_at(q, pos, hd, cfg.rope_theta)
        k = apply_rope_at(k, pos, hd, cfg.rope_theta)

    slot = pos % cache_len if cfg.sliding_window else pos
    cache_k = cache_k.at[jnp.arange(B), slot].set(k.astype(cache_k.dtype))
    cache_v = cache_v.at[jnp.arange(B), slot].set(v.astype(cache_v.dtype))
    # pin the updated cache to its resident layout — without this GSPMD may
    # re-shard the whole cache inside the decode loop ("involuntary full
    # rematerialization")
    cache_axes = ("batch", "seq", "kv_heads", "head_dim")
    cache_k = shard_hint(cache_k, cache_axes)
    cache_v = shard_hint(cache_v, cache_axes)

    groups = h // kv
    qg = shard_hint(q.reshape(B, kv, groups, hd),
                    ("batch", "kv_heads", None, "head_dim"))
    kk = shard_hint(cache_k.astype(q.dtype), cache_axes)  # (B, S, KV, hd)
    vv = shard_hint(cache_v.astype(q.dtype), cache_axes)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, kk).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    kpos = jnp.arange(cache_len)[None, :]
    if cfg.sliding_window:
        valid = kpos < jnp.minimum(pos + 1, cache_len)[:, None]
    else:
        valid = kpos <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vv).reshape(B, h * hd)
    y = jnp.einsum("bf,fd->bd", out, p["wo"])
    return y, cache_k, cache_v


# --------------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------------- #
def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None
              ) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.glu:
        return {
            "wi_gate": ParamSpec((d, f), ("embed", "d_ff"), "normal"),
            "wi_up": ParamSpec((d, f), ("embed", "d_ff"), "normal"),
            "wo": ParamSpec((f, d), ("d_ff", "embed"), "normal"),
        }
    return {
        "wi": ParamSpec((d, f), ("embed", "d_ff"), "normal"),
        "wo": ParamSpec((f, d), ("d_ff", "embed"), "normal"),
    }


def mlp_apply(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    act = activation(cfg.mlp_act)
    if cfg.glu:
        h = act(x @ p["wi_gate"]) * (x @ p["wi_up"])
    else:
        h = act(x @ p["wi"])
    h = shard_hint(h, ("batch", "seq", "act_ff")) if h.ndim == 3 else h
    return h @ p["wo"]
