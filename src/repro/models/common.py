"""Lightweight functional parameter system shared by every model family.

Models declare a pytree of ``ParamSpec`` (shape + logical sharding axes +
init recipe). ``init_params`` materializes real arrays from a PRNG key;
``abstract_params`` materializes ``jax.ShapeDtypeStruct`` for AOT dry-runs
(no allocation); ``logical_axes`` extracts the logical-axis tree that the
launcher's sharding-rule table maps onto the mesh.

Logical axis vocabulary (see launch/sharding.py for the mesh mapping):
  layers, embed, embed_in, vocab, heads_fused, kv_fused, head_dim, kv_lora,
  d_ff, experts, expert_ff, state, conv, batch, seq, generic
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed
    scale: float = 1.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def _fan_in(shape: Tuple[int, ...]) -> int:
    if len(shape) == 1:
        return shape[0]
    # contraction dim convention: second-to-last for matrices/stacks
    return shape[-2]


def _init_leaf(key: jax.Array, spec: ParamSpec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * spec.scale).astype(spec.dtype)
    if spec.init == "normal":
        std = spec.scale / math.sqrt(max(_fan_in(spec.shape), 1))
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * std).astype(spec.dtype)
    if spec.init == "uniform":
        return (jax.random.uniform(key, spec.shape, jnp.float32,
                                   -spec.scale, spec.scale)).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(key: jax.Array, specs: PyTree) -> PyTree:
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    out = [_init_leaf(k, s) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(specs: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_axes(specs: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_specs(n_layers: int, specs: PyTree) -> PyTree:
    """Prepend a scanned 'layers' axis to every ParamSpec in the tree."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n_layers,) + s.shape, ("layers",) + s.axes,
                            s.init, s.scale, s.dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count_tree(specs: PyTree) -> int:
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return int(sum(int(np.prod(s.shape)) for s in leaves))


# --------------------------------------------------------------------------- #
# Common numerics
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x, p, prefix=""):
    if cfg.norm == "layernorm":
        return layer_norm(x, p[prefix + "gamma"], p[prefix + "beta"])
    return rms_norm(x, p[prefix + "gamma"])


def norm_specs(cfg, d: int) -> Dict[str, ParamSpec]:
    s: Dict[str, ParamSpec] = {"gamma": ParamSpec((d,), ("embed",), "ones")}
    if cfg.norm == "layernorm":
        s["beta"] = ParamSpec((d,), ("embed",), "zeros")
    return s


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return partial(jax.nn.gelu, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "relu":
        return jax.nn.relu
    raise ValueError(f"unknown activation {name!r}")


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) / (
        2 * math.log(theta))


def rope_inv_freq(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """The (head_dim/2,) inverse frequencies, f32. Under YaRN scaling
    (``configs.base.YarnScaling``, DeepSeek-V2's ``yarn_*`` functions):
    the extrapolated frequencies theta^(-2i/d) and the interpolated ones
    (over ``factor``) blended by a linear ramp between the correction
    dimensions of ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position_embeddings``."""
    half = head_dim // 2
    extra = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is None:
        return extra
    orig = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(
        scaling.beta_fast, head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        scaling.beta_slow, head_dim, theta, orig)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: extrapolate, 0: interpolate
    return extra / scaling.factor * (1.0 - keep) + extra * keep


def rope_mscale(scaling=None) -> float:
    """YaRN's multiplier of the cos/sin tables: mscale over
    mscale_all_dim, each as 0.1 m ln(factor) + 1 (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (_yarn_mscale(scaling.factor, scaling.mscale)
            / _yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def softmax_mscale(scaling=None) -> float:
    """YaRN's factor on the attention softmax scale: the square of
    0.1 mscale_all_dim ln(factor) + 1 (1 without scaling)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return _yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def rope_tables(seq_len: int, head_dim: int, theta: float,
                offset: int = 0, scaling=None
                ) -> Tuple[jax.Array, jax.Array]:
    """Rotary embedding cos/sin tables of shape (seq_len, head_dim/2), f32;
    ``scaling`` is YaRN's (``rope_inv_freq``, ``rope_mscale``)."""
    freqs = rope_inv_freq(head_dim, theta, scaling)
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]
    cos, sin, m = jnp.cos(ang), jnp.sin(ang), rope_mscale(scaling)
    return (cos, sin) if m == 1.0 else (cos * m, sin * m)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); cos/sin: (seq, head_dim/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :].astype(x.dtype)
    s = sin[..., :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def apply_rope_at(x: jax.Array, pos: jax.Array, head_dim: int,
                  theta: float, scaling=None) -> jax.Array:
    """Rope for decode: x (batch, heads, head_dim), pos (batch,) int32."""
    half = head_dim // 2
    freqs = rope_inv_freq(head_dim, theta, scaling)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]   # (B, half)
    c, s, m = jnp.cos(ang), jnp.sin(ang), rope_mscale(scaling)
    if m != 1.0:
        c, s = c * m, s * m
    c, s = c[:, None, :].astype(x.dtype), s[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------------------- #
# Activation sharding hints (resolved against the launcher's logical rules)
# --------------------------------------------------------------------------- #
_LOGICAL_RULES: Dict[str, Any] = {"rules": None, "mesh": None}


class logical_rule_scope:
    """Context manager the launcher uses to activate activation-sharding
    hints: ``with logical_rule_scope(rules, mesh): ... jit(...)``.
    ``rules`` maps logical axis name -> mesh axis (str/tuple/None)."""

    def __init__(self, rules, mesh):
        self.rules, self.mesh = rules, mesh

    def __enter__(self):
        self._saved = dict(_LOGICAL_RULES)
        _LOGICAL_RULES["rules"] = self.rules
        _LOGICAL_RULES["mesh"] = self.mesh
        return self

    def __exit__(self, *exc):
        _LOGICAL_RULES.update(self._saved)
        return False


def shard_hint(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    """Apply with_sharding_constraint per the active logical rules (no-op
    outside a logical_rule_scope, so models run unchanged on one device)."""
    rules, mesh = _LOGICAL_RULES["rules"], _LOGICAL_RULES["mesh"]
    if rules is None or mesh is None:
        return x
    spec = []
    used = set()
    for dim, name in zip(x.shape, axes):
        mesh_axes = rules.get(name) if name is not None else None
        if mesh_axes is None:
            spec.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        mesh_axes = tuple(a for a in mesh_axes if a not in used)
        size = 1
        for a in mesh_axes:
            size *= mesh.shape[a]
        if mesh_axes and size and dim % size == 0:
            used.update(mesh_axes)
            spec.append(tuple(mesh_axes) if len(mesh_axes) > 1 else mesh_axes[0])
        else:
            spec.append(None)  # indivisible: replicate rather than pad
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec)))


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy in f32. logits (..., V), labels (...).

    The gold logit is picked with a fused one-hot einsum rather than
    take_along_axis: a gather over a vocab-sharded logits tensor forces an
    all-gather of the full-precision logits, which dominated train-step
    memory for 50k-150k vocabularies.
    """
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.einsum("...v,...v->...", logits.astype(jnp.float32), onehot)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
