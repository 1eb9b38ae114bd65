"""Decoder-only transformer LM covering the dense, MoE and VLM families.

Layers are scanned (stacked params + ``jax.lax.scan``) with optional remat,
so 96-layer configs lower to compact HLO. The VLM family prepends stub
image-patch embeddings (the vision tower is out of scope per the assignment
carve-out); MoE layers route via ``repro.models.moe``; DeepSeek's MLA
attention is dispatched via ``repro.models.mla``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.common import (
    ParamSpec,
    abstract_params,
    apply_norm,
    cross_entropy_loss,
    init_params,
    norm_specs,
    shard_hint,
    stack_specs,
)
from repro.models.layers import (
    attention_decode,
    attention_prefill_kv,
    attention_specs,
    attention_train,
    embed_tokens,
    embedding_specs,
    lm_head,
    mlp_apply,
    mlp_specs,
)

PyTree = Any


class DecoderLM:
    """families: dense | moe | vlm."""

    def __init__(self, cfg: ArchConfig, remat: bool = True):
        assert cfg.family in ("dense", "moe", "vlm"), cfg.family
        self.cfg = cfg
        self.remat = remat
        self.n_prefix = cfg.moe.first_k_dense if cfg.moe else 0
        self.n_scanned = cfg.n_layers - self.n_prefix

    # ------------------------------------------------------------------ #
    # params
    # ------------------------------------------------------------------ #
    def _attn_specs(self) -> Dict[str, ParamSpec]:
        if self.cfg.mla is not None:
            return mla_mod.mla_specs(self.cfg)
        return attention_specs(self.cfg)

    def _layer_specs(self, moe_layer: bool,
                     dense_ff: Optional[int] = None) -> Dict:
        cfg = self.cfg
        s = {
            "ln1": norm_specs(cfg, cfg.d_model),
            "attn": self._attn_specs(),
            "ln2": norm_specs(cfg, cfg.d_model),
        }
        if moe_layer:
            s["ffn"] = moe_mod.moe_specs(cfg)
        else:
            s["ffn"] = mlp_specs(cfg, d_ff=dense_ff)
        return s

    def param_specs(self) -> Dict:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "embed": embedding_specs(cfg),
            "final_norm": norm_specs(cfg, cfg.d_model),
        }
        moe_layer = cfg.moe is not None
        specs["layers"] = stack_specs(
            self.n_scanned, self._layer_specs(moe_layer))
        if self.n_prefix:
            specs["prefix_layers"] = [
                self._layer_specs(False, dense_ff=cfg.moe.dense_d_ff)
                for _ in range(self.n_prefix)
            ]
        return specs

    def init(self, key: jax.Array) -> PyTree:
        return init_params(key, self.param_specs())

    def abstract_params(self) -> PyTree:
        return abstract_params(self.param_specs())

    # ------------------------------------------------------------------ #
    # forward (train / prefill)
    # ------------------------------------------------------------------ #
    def _embed_inputs(self, params, batch) -> jax.Array:
        cfg = self.cfg
        x = embed_tokens(cfg, params["embed"], batch["tokens"])
        if cfg.family == "vlm":
            img = batch["image_embeds"].astype(x.dtype)   # (B, Ni, D)
            x = jnp.concatenate([img, x], axis=1)
        return x

    def _train_block(self, layer_p, x, moe_layer: bool):
        cfg = self.cfg
        h = apply_norm(cfg, x, layer_p["ln1"])
        if cfg.mla is not None:
            a = mla_mod.mla_train(cfg, layer_p["attn"], h)
        else:
            a = attention_train(cfg, layer_p["attn"], h)
        x = x + a
        h2 = apply_norm(cfg, x, layer_p["ln2"])
        if moe_layer:
            f, aux, counts = moe_mod.moe_apply(cfg, layer_p["ffn"], h2)
        else:
            f, aux = mlp_apply(cfg, layer_p["ffn"], h2), jnp.zeros((),
                                                                   jnp.float32)
            counts = {}
        x = x + f
        x = shard_hint(x, ("batch", "act_seq", "act_embed"))
        return x, (aux, counts)

    def _forward(self, params, batch):
        """-> (logits (B, S_total, V), aux_loss scalar, the MoE layers'
        routing counts stacked over them: ``{}`` without MoE layers)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        aux_total = jnp.zeros((), jnp.float32)
        moe_layer = cfg.moe is not None

        block = self._train_block
        if self.remat:
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=(2,))
        for lp in params.get("prefix_layers", []):
            x, (aux, _) = block(lp, x, False)
            aux_total += aux
        x, (auxes, counts) = jax.lax.scan(
            lambda carry, layer_p: block(layer_p, carry, moe_layer), x,
            params["layers"])
        aux_total = aux_total + jnp.sum(auxes)
        x = apply_norm(cfg, x, params["final_norm"])
        logits = lm_head(cfg, params["embed"], x)
        return logits, aux_total, counts

    def forward(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """-> (logits (B, S_total, V), aux_loss scalar)."""
        logits, aux, _ = self._forward(params, batch)
        return logits, aux

    def _text_logits(self, logits):
        if self.cfg.family == "vlm":
            return logits[:, self.cfg.num_image_tokens:, :]
        return logits

    def loss_and_counts(self, params, batch):
        """Next-token loss plus the balance loss, and the MoE layers'
        routing counts (``moe.moe_apply``) stacked over them:
        ``expert_tokens`` (layers, held) and ``absent`` (layers,)."""
        logits, aux, counts = self._forward(params, batch)
        logits = self._text_logits(logits)
        # next-token prediction: position t predicts token t + 1
        loss = cross_entropy_loss(logits[:, :-1, :], batch["labels"][:, 1:])
        return loss + aux, counts

    def loss(self, params, batch) -> jax.Array:
        return self.loss_and_counts(params, batch)[0]

    def accuracy(self, params, batch) -> jax.Array:
        """Next-token accuracy, with the shift of ``loss``."""
        logits = self._text_logits(self._forward(params, batch)[0])
        pred = jnp.argmax(logits[:, :-1, :], axis=-1)
        return jnp.mean((pred == batch["labels"][:, 1:]).astype(jnp.float32))

    # ------------------------------------------------------------------ #
    # decode
    # ------------------------------------------------------------------ #
    def cache_struct(self, batch_size: int, cache_len: int
                     ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        cfg = self.cfg
        if cfg.sliding_window:
            cache_len = min(cache_len, cfg.sliding_window)
        L = cfg.n_layers
        dt = jnp.bfloat16
        if cfg.mla is not None:
            m = cfg.mla
            return {"ckv": ((L, batch_size, cache_len,
                             m.kv_lora_rank + m.qk_rope_head_dim), dt)}
        return {
            "k": ((L, batch_size, cache_len, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": ((L, batch_size, cache_len, cfg.n_kv_heads, cfg.head_dim), dt),
        }

    def cache_axes(self) -> Dict[str, tuple]:
        """Logical sharding axes matching cache_struct's entries."""
        if self.cfg.mla is not None:
            return {"ckv": ("layers", "batch", "seq", "kv_lora")}
        ax = ("layers", "batch", "seq", "kv_heads", "head_dim")
        return {"k": ax, "v": ax}

    def init_cache(self, batch_size: int, cache_len: int) -> PyTree:
        return {k: jnp.zeros(sh, dt)
                for k, (sh, dt) in self.cache_struct(batch_size,
                                                     cache_len).items()}

    def abstract_cache(self, batch_size: int, cache_len: int) -> PyTree:
        return {k: jax.ShapeDtypeStruct(sh, dt)
                for k, (sh, dt) in self.cache_struct(batch_size,
                                                     cache_len).items()}

    def _decode_block(self, layer_p, x, cache_l, pos):
        cfg = self.cfg
        h = apply_norm(cfg, x, layer_p["ln1"])
        if cfg.mla is not None:
            a, ckv = mla_mod.mla_decode(cfg, layer_p["attn"], h,
                                        cache_l["ckv"], pos)
            new_cache = {"ckv": ckv}
        else:
            a, k, v = attention_decode(cfg, layer_p["attn"], h,
                                       cache_l["k"], cache_l["v"], pos)
            new_cache = {"k": k, "v": v}
        x = x + a
        h2 = apply_norm(cfg, x, layer_p["ln2"])
        if "router" in layer_p["ffn"]:   # MoE layer (prefix layers are dense)
            f = moe_mod.moe_apply_token(cfg, layer_p["ffn"], h2)
        else:
            f = mlp_apply(cfg, layer_p["ffn"], h2)
        return x + f, new_cache

    def decode_step(self, params, token: jax.Array, pos: jax.Array,
                    cache: PyTree) -> Tuple[jax.Array, PyTree]:
        """token (B,) int32; pos (B,) absolute position; cache stacked (L,...).

        Returns (logits (B, V), new_cache).
        """
        cfg = self.cfg
        x = jnp.take(params["embed"]["tok"], token, axis=0)   # (B, D)
        x = shard_hint(x, ("batch", "act_embed"))
        n_pref = self.n_prefix

        # prefix (unstacked) layers consume cache slices [0, n_prefix)
        new_prefix_caches = []
        for i, lp in enumerate(params.get("prefix_layers", [])):
            cache_l = jax.tree_util.tree_map(lambda c: c[i], cache)
            x, nc = self._decode_block(lp, x, cache_l, pos)
            new_prefix_caches.append(nc)

        scanned_cache = jax.tree_util.tree_map(lambda c: c[n_pref:], cache)

        def body(carry, xs):
            layer_p, cache_l = xs
            y, nc = self._decode_block(layer_p, carry, cache_l, pos)
            return y, nc

        x, new_scanned = jax.lax.scan(body, x,
                                      (params["layers"], scanned_cache))
        if n_pref:
            stacked_prefix = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_prefix_caches)
            new_cache = jax.tree_util.tree_map(
                lambda pre, scan: jnp.concatenate([pre, scan], axis=0),
                stacked_prefix, new_scanned)
        else:
            new_cache = new_scanned
        x = apply_norm(cfg, x, params["final_norm"])
        logits = lm_head(cfg, params["embed"], x)
        return logits, new_cache

    # ------------------------------------------------------------------ #
    # prefill (forward + cache construction)
    # ------------------------------------------------------------------ #
    def prefill(self, params, batch) -> Tuple[jax.Array, PyTree]:
        """Full-sequence forward that also returns the KV cache."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        caches = []
        for lp in params.get("prefix_layers", []):
            caches.append(self._cache_entry(lp, x))
            x, _ = self._train_block(lp, x, moe_layer=False)
        moe_layer = cfg.moe is not None

        def body(carry, layer_p):
            entry = self._cache_entry(layer_p, carry)
            y, _ = self._train_block(layer_p, carry, moe_layer)
            return y, entry

        x, scanned_cache = jax.lax.scan(body, x, params["layers"])
        if caches:
            stacked_prefix = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *caches)
            cache = jax.tree_util.tree_map(
                lambda pre, scan: jnp.concatenate([pre, scan], axis=0),
                stacked_prefix, scanned_cache)
        else:
            cache = scanned_cache
        x = apply_norm(cfg, x, params["final_norm"])
        logits = lm_head(cfg, params["embed"], x)
        return logits, cache

    def _cache_entry(self, layer_p, x):
        cfg = self.cfg
        h = apply_norm(cfg, x, layer_p["ln1"])
        if cfg.mla is not None:
            return {"ckv": mla_mod.mla_prefill_cache(cfg, layer_p["attn"], h)
                    .astype(jnp.bfloat16)}
        k, v = attention_prefill_kv(cfg, layer_p["attn"], h)
        return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
