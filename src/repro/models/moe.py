"""Mixture-of-experts FFN: every token routed, none dropped, over the
share of the routed experts this layer holds.

The router scores all ``num_experts`` experts (softmax), each token
takes its ``top_k`` best (greedy), with the weights renormalised over
the chosen ones only where the configuration says so
(``norm_topk_prob``) and scaled by ``routed_scaling_factor``. A layer
holds ``experts_held`` of the routed experts, from id ``first_expert``:
under expert parallelism over P chips each chip holds E/P of them and
the assignments to the others travel to their chips. This layer
computes its own experts' part, every assignment to a held expert with
no capacity limit, as grouped products over the held experts' weights
(``expert_products``); the whole layer is the share that holds all E.
The shared experts (DeepSeek style) are an always-on dense FFN added to
every token. Nothing here stands in for the absent experts: a share's
output is its part of the routed sum plus the shared experts.

The balance loss is DeepSeek-V2's sequence-wise one (``seq_aux``): per
sequence, sum_i f_i P_i with f_i = E / (K S) times the tokens that chose
expert i and P_i the mean score of expert i, averaged over sequences and
weighted by ``aux_loss_coef``. It reads all E scores, so every share
computes the same value.

The decode path (``moe_apply_token``) routes one token per sequence with
the same gate over the whole layer; ``TOKEN_DISPATCH`` picks how.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.configs.base import ArchConfig
from repro.models.common import ParamSpec, activation
from repro.models.layers import mlp_specs, mlp_apply

# decode-path dispatch: "gather" moves the top-k expert weights to the
# token (paper-obvious, but on a sharded mesh it all-gathers whole expert
# matrices); "dense" runs every (sharded) expert on the tiny token batch
# and combines by routing weight — E/k x more FLOPs on a negligible
# decode-step compute budget, zero weight movement. See §Perf.
TOKEN_DISPATCH = "gather"


def moe_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    mo = cfg.moe
    e, f = mo.held, mo.d_expert
    s: Dict[str, ParamSpec] = {
        # router kept in f32 at its full E outputs: routing decisions are
        # precision-sensitive, and every share scores every expert
        "router": ParamSpec((d, mo.num_experts), ("embed", "experts"),
                            "normal", dtype=jnp.float32),
    }
    if cfg.glu:
        s["w_gate"] = ParamSpec((e, d, f), ("experts", "embed", "expert_ff"),
                                "normal")
        s["w_up"] = ParamSpec((e, d, f), ("experts", "embed", "expert_ff"),
                              "normal")
        s["w_down"] = ParamSpec((e, f, d), ("experts", "expert_ff", "embed"),
                                "normal")
    else:
        s["w_in"] = ParamSpec((e, d, f), ("experts", "embed", "expert_ff"),
                              "normal")
        s["w_down"] = ParamSpec((e, f, d), ("experts", "expert_ff", "embed"),
                                "normal")
    if mo.num_shared_experts > 0:
        shared_f = mo.d_shared_expert * mo.num_shared_experts
        s["shared"] = mlp_specs(cfg, d_ff=shared_f)
    return s


def gate(cfg: ArchConfig, router: jax.Array, x: jax.Array):
    """The published gate on x (..., D): softmax scores over all E
    experts (float32), and each token's top-k weights and expert ids."""
    mo = cfg.moe
    scores = jax.nn.softmax(
        jnp.einsum("...d,de->...e", x.astype(jnp.float32), router), axis=-1)
    top_w, top_i = jax.lax.top_k(scores, mo.top_k)
    if mo.norm_topk_prob:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return scores, top_w * mo.routed_scaling_factor, top_i


def _ragged(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes)


@jax.custom_vjp
def expert_products(x: jax.Array, w: jax.Array,
                    group_sizes: jax.Array) -> jax.Array:
    """Grouped products: rows [o_g, o_g + n_g) of x (M, K) times w[g]
    (G, K, N), with the groups' rows in order from row 0 (n_g =
    ``group_sizes[g]``); rows past the last group are not computed.

    The TPU compiler runs each product as a kernel named ``ragged-dot``
    that visits the groups' rows alone. It takes no batch dimension, so
    a vmap over clients runs the clients one after another
    (``sequential_vmap``), in the backward pass too."""
    return jax.custom_batching.sequential_vmap(_ragged)(x, w, group_sizes)


def _products_fwd(x, w, group_sizes):
    return expert_products(x, w, group_sizes), (x, w, group_sizes)


def _products_bwd(res, dy):
    x, w, group_sizes = res

    def one(x, w, group_sizes, dy):
        return jax.vjp(lambda a, b: _ragged(a, b, group_sizes), x, w)[1](dy)

    dx, dw = jax.custom_batching.sequential_vmap(one)(x, w, group_sizes, dy)
    return dx, dw, None


expert_products.defvjp(_products_fwd, _products_bwd)


def _balance_loss(cfg: ArchConfig, scores: jax.Array,
                  top_i: jax.Array) -> jax.Array:
    """DeepSeek-V2's sequence-wise balance loss on scores (B, S, E) and
    choices (B, S, K)."""
    mo = cfg.moe
    E, K = mo.num_experts, mo.top_k
    S = scores.shape[1]
    counts = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                     axis=(1, 2))                              # (B, E)
    f = counts * (E / (K * S))
    p = jnp.mean(scores, axis=1)                               # (B, E)
    return jnp.mean(jnp.sum(f * p, axis=-1)) * mo.aux_loss_coef


def moe_apply(cfg: ArchConfig, p, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x (B, S, D) -> (y (B, S, D), balance loss (f32 scalar), counts):
    ``expert_tokens`` (held,), the assignments each held expert took,
    and ``absent`` (), the assignments to experts this layer does not
    hold."""
    mo = cfg.moe
    B, S, D = x.shape
    T, K, H = B * S, mo.top_k, mo.held
    xf = x.reshape(T, D)
    with jax.named_scope(spans.ROUTE):
        scores, top_w, top_i = gate(cfg, p["router"], x)
        aux = _balance_loss(cfg, scores, top_i)
        # the (T*K,) assignments; a held one goes to its expert's group.
        # Its row in the grouped buffer: its group's offset plus the held
        # assignments to that expert before it, in token order
        local = top_i.reshape(T * K) - mo.first_expert
        held = (local >= 0) & (local < H)
        onehot = jax.nn.one_hot(jnp.where(held, local, H), H,
                                dtype=jnp.int32)               # (T*K, H)
        sizes = jnp.sum(onehot, axis=0)                        # (H,)
        offsets = jnp.cumsum(sizes) - sizes
        before = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot,
                         axis=1)
        # no token ever drops: the buffer holds every assignment a token
        # can make to held experts (at most min(K, H) each)
        m = T * min(K, H)
        row = jnp.where(held, jnp.sum(onehot * offsets, axis=1) + before, m)
        token = jnp.zeros((m,), jnp.int32).at[row].set(
            jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
        weight = jnp.zeros((m,), jnp.float32).at[row].set(
            top_w.reshape(T * K), mode="drop")
        used = jnp.arange(m) < jnp.sum(sizes)
        xs = jnp.where(used[:, None], xf[token], 0)
    act = activation(cfg.mlp_act)
    with jax.named_scope(spans.EXPERTS):
        if cfg.glu:
            h = act(expert_products(xs, p["w_gate"], sizes)) \
                * expert_products(xs, p["w_up"], sizes)
        else:
            h = act(expert_products(xs, p["w_in"], sizes))
        ys = expert_products(h, p["w_down"], sizes)
    with jax.named_scope(spans.ROUTE):
        ys = jnp.where(used[:, None], ys * weight[:, None].astype(ys.dtype),
                       0)
        y = jnp.zeros((T, D), ys.dtype).at[token].add(ys).reshape(B, S, D)
        counts = {"expert_tokens": sizes.astype(jnp.float32),
                  "absent": jnp.sum(~held).astype(jnp.float32)}
    if mo.num_shared_experts > 0:
        y = y + mlp_apply(cfg, p["shared"], x)
    return y, aux, counts


def moe_apply_token(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    """Decode-path MoE for a single token per sequence: x (B, D) -> (B, D),
    over a layer that holds all its experts.

    With one token there is no capacity contention: gather the top-k expert
    weights per token and run them as small batched matmuls (or, with
    TOKEN_DISPATCH == "dense", run all sharded experts in place — see
    module docstring).
    """
    mo = cfg.moe
    B, D = x.shape
    K = mo.top_k
    _, top_w, top_i = gate(cfg, p["router"], x)                # (B, K)
    top_w = top_w.astype(x.dtype)
    act = activation(cfg.mlp_act)

    if TOKEN_DISPATCH == "dense":
        # combine weight per expert: sum of top-k weights routed to it
        cw = jnp.zeros((B, mo.num_experts), x.dtype)
        for k in range(K):
            cw = cw + jax.nn.one_hot(top_i[:, k], mo.num_experts,
                                     dtype=x.dtype) * top_w[:, k][:, None]
        if cfg.glu:
            h = act(jnp.einsum("bd,edf->ebf", x, p["w_gate"])) \
                * jnp.einsum("bd,edf->ebf", x, p["w_up"])
        else:
            h = act(jnp.einsum("bd,edf->ebf", x, p["w_in"]))
        y_e = jnp.einsum("ebf,efd->ebd", h, p["w_down"])       # (E, B, D)
        y = jnp.einsum("ebd,be->bd", y_e, cw)
        if mo.num_shared_experts > 0:
            y = y + mlp_apply(cfg, p["shared"], x)
        return y

    if cfg.glu:
        wg = jnp.take(p["w_gate"], top_i, axis=0)              # (B, K, D, F)
        wu = jnp.take(p["w_up"], top_i, axis=0)
        wd = jnp.take(p["w_down"], top_i, axis=0)              # (B, K, F, D)
        h = act(jnp.einsum("bd,bkdf->bkf", x, wg)) \
            * jnp.einsum("bd,bkdf->bkf", x, wu)
    else:
        wi = jnp.take(p["w_in"], top_i, axis=0)
        wd = jnp.take(p["w_down"], top_i, axis=0)
        h = act(jnp.einsum("bd,bkdf->bkf", x, wi))
    y = jnp.einsum("bkf,bkfd->bd", h * top_w[..., None], wd)
    if mo.num_shared_experts > 0:
        y = y + mlp_apply(cfg, p["shared"], x)
    return y
