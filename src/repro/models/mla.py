"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434).

Train/prefill use the expanded (naive) formulation, with queries in
chunks of ``layers.Q_CHUNK`` past that length so that no (S, S) score
matrix of all heads is held; decode uses the *absorbed* formulation
attending directly in the latent space, so the KV cache per token is just
``kv_lora_rank + qk_rope_head_dim`` floats — MLA's memory contribution.

Positions: rope on the ``qk_rope_head_dim`` part, under YaRN where the
configuration has ``rope_scaling`` (DeepSeek-V2's frequencies, cos/sin
multiplier, and softmax scale qd^-0.5 times (0.1 mscale_all_dim
ln(factor) + 1)^2). DeepSeek's modelling code interleaves the rope
dimensions (pairs (2i, 2i+1)) where this one rotates the halves (i, i +
d/2): a fixed permutation of the columns of ``wq``'s and ``w_dkv``'s rope
part, so the same family of models, not added here.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (
    ParamSpec,
    apply_rope,
    apply_rope_at,
    rms_norm,
    rope_tables,
    shard_hint,
    softmax_mscale,
)
from repro.models.layers import attend, NEG_INF, Q_CHUNK


def mla_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamSpec((d, h * qd), ("embed", "heads_fused"), "normal"),
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("embed", "kv_lora"), "normal"),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("kv_lora",), "ones"),
        "w_uk": ParamSpec((m.kv_lora_rank, h * m.qk_nope_head_dim),
                          ("kv_lora", "heads_fused"), "normal"),
        "w_uv": ParamSpec((m.kv_lora_rank, h * m.v_head_dim),
                          ("kv_lora", "heads_fused"), "normal"),
        "wo": ParamSpec((h * m.v_head_dim, d), ("heads_fused", "embed"),
                        "normal"),
    }


def _scale(cfg: ArchConfig) -> float:
    """The attention softmax scale."""
    m = cfg.mla
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return qd ** -0.5 * softmax_mscale(cfg.rope_scaling)


def _latent(cfg: ArchConfig, p, x: jax.Array):
    """x (B,S,D) -> (c_kv (B,S,R) normed, k_rope (B,S,rope))."""
    m = cfg.mla
    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv, k_rope = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm"])
    return c_kv, k_rope


def mla_train(cfg: ArchConfig, p, x: jax.Array, *, causal: bool = True,
              q_offset: int = 0) -> jax.Array:
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim

    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(B, S, h, qd)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    c_kv, k_rope = _latent(cfg, p, x)

    cos, sin = rope_tables(S, m.qk_rope_head_dim, cfg.rope_theta,
                           offset=q_offset, scaling=cfg.rope_scaling)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)      # (B,S,1,rope)

    k_nope = jnp.einsum("bsr,rf->bsf", c_kv, p["w_uk"]).reshape(
        B, S, h, m.qk_nope_head_dim)
    v = jnp.einsum("bsr,rf->bsf", c_kv, p["w_uv"]).reshape(
        B, S, h, m.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, h, m.qk_rope_head_dim))],
        axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    qq = shard_hint(qq, ("batch", "seq", "heads", "head_dim"))

    # v may be narrower than qk head_dim; attend() only needs matching q/k
    out = attend(cfg.replace(n_kv_heads=cfg.n_heads), qq, k, v,
                 causal=causal, q_offset=q_offset, scale=_scale(cfg),
                 chunk_above=Q_CHUNK)
    out = out.reshape(B, S, h * m.v_head_dim)
    y = jnp.einsum("bsf,fd->bsd", out, p["wo"])
    return shard_hint(y, ("batch", "act_seq", "act_embed"))


def mla_prefill_cache(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    """Latent cache for prefill: (B, S, kv_lora + rope), rope applied."""
    m = cfg.mla
    c_kv, k_rope = _latent(cfg, p, x)
    cos, sin = rope_tables(x.shape[1], m.qk_rope_head_dim, cfg.rope_theta,
                           scaling=cfg.rope_scaling)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def mla_decode(cfg: ArchConfig, p, x: jax.Array, cache: jax.Array,
               pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Absorbed decode step. x (B,D); cache (B,S,R+rope); pos (B,)."""
    m = cfg.mla
    B = x.shape[0]
    h = cfg.n_heads
    R = m.kv_lora_rank
    S = cache.shape[1]
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim

    q = jnp.einsum("bd,df->bf", x, p["wq"]).reshape(B, h, qd)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope_at(q_rope, pos, m.qk_rope_head_dim, cfg.rope_theta,
                           cfg.rope_scaling)

    c_kv, k_rope = _latent(cfg, p, x[:, None, :])
    k_rope = apply_rope_at(k_rope[:, 0, None, :], pos, m.qk_rope_head_dim,
                           cfg.rope_theta, cfg.rope_scaling)[:, 0, :]
    new_entry = jnp.concatenate([c_kv[:, 0, :], k_rope], axis=-1)
    cache = cache.at[jnp.arange(B), pos].set(new_entry.astype(cache.dtype))

    lat, rope_k = cache[..., :R], cache[..., R:]               # (B,S,*)
    w_uk = p["w_uk"].reshape(R, h, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)           # (B,h,R)
    scores = (jnp.einsum("bhr,bsr->bhs", q_abs, lat.astype(q_abs.dtype))
              + jnp.einsum("bhn,bsn->bhs", q_rope,
                           rope_k.astype(q_rope.dtype))).astype(jnp.float32)
    scores = scores * _scale(cfg)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, lat.astype(x.dtype))  # (B,h,R)
    w_uv = p["w_uv"].reshape(R, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv).reshape(B, h * m.v_head_dim)
    y = jnp.einsum("bf,fd->bd", out, p["wo"])
    return y, cache
