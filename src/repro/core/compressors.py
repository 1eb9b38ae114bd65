"""Pluggable, jit-able gradient compressor stages for the unified round
engine (repro.core.ltfl_step).

A ``Compressor`` is the tensor-side half of an FL scheme: it maps one
client's (already pruned/masked) gradient pytree to the pytree that goes
over the air, optionally carrying per-client state across rounds (STC's
error-feedback residual), plus a server-side transform applied to the
aggregated update (SignSGD's majority vote). All three callables are pure
JAX so the whole chain lowers into the single vmapped/jitted round step —
no scheme runs host-side per-device Python anymore.

Provided compressors (the paper's Section-6.1 comparison set):

* ``identity``      — FedSGD / FedMP: full-precision kept entries.
* ``ltfl_quantizer``— the paper's stochastic uniform quantizer (Eq. 16-17)
  at a per-client, possibly traced bit-width ``delta`` (0 => passthrough,
  the Fig. 2 no-quant ablation). With ``use_kernels=True``, 2-D-reshapable
  leaves route through the Pallas kernel (repro.kernels.ops, block by
  ``kernel_quant_block``) — the TPU fast path; the jnp path is
  bit-identical given the same key.
* ``sign_compressor`` — SignSGD: sign(g) uplink, sign(aggregate) * lr_scale
  majority vote on the server.
* ``stc_compressor``  — Sattler et al. sparse ternary compression: top-k +
  ternarize with client-side error accumulation. The residual is the
  carried state pytree (stacked (C, ...) leaves, f32).

Contract (per client; the engine vmaps over the leading client axis):

    init_state(params, n_clients) -> state        # stacked (C, ...) or ()
    compress(g, delta, key, state_u) -> (g_wire, new_state_u)
    server_transform(aggregated) -> aggregated
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantization import quantize_dequantize

PyTree = Any


@dataclass(frozen=True)
class Compressor:
    """One scheme's jit-able compression stage (see module docstring)."""

    name: str
    compress: Callable[[PyTree, jax.Array, jax.Array, PyTree],
                       Tuple[PyTree, PyTree]]
    init_state: Callable[[PyTree, int], PyTree] = \
        field(default=lambda params, n_clients: ())
    server_transform: Callable[[PyTree], PyTree] = field(default=lambda g: g)


def identity_compressor() -> Compressor:
    """Full-precision uplink (FedSGD, FedMP)."""
    return Compressor(name="none", compress=lambda g, d, k, s: (g, s))


def ltfl_quantizer(*, use_kernels: bool = False,
                   kernel_block: Tuple[int, int] = (256, 256)) -> Compressor:
    """Stochastic uniform quantization at per-client delta (Eq. 16-17).

    delta may be traced; delta <= 0 passes the gradient through unchanged
    (the paper's no-quant ablation shares the compiled step). Keys split
    per leaf exactly like ``quantize_pytree`` so the per-device reference
    path reproduces this bit-for-bit.
    """
    if use_kernels:
        from repro.kernels import ops as kops

    def compress(g, delta, key, state):
        leaves, treedef = jax.tree_util.tree_flatten(g)
        keys = jax.random.split(key, len(leaves))
        bits = jnp.maximum(delta, 1.0)
        out = []
        for leaf, k in zip(leaves, keys):
            block = (kops.kernel_quant_block(leaf.shape, kernel_block)
                     if use_kernels else None)
            if block is not None:
                m2 = leaf.reshape(-1, leaf.shape[-1])
                q = kops.quantize_dequantize_2d_dyn(
                    m2, bits, k, block=block).reshape(leaf.shape)
            else:
                q = quantize_dequantize(leaf, bits, k)
            out.append(jnp.where(delta > 0, q, leaf))
        return jax.tree_util.tree_unflatten(treedef, out), state

    return Compressor(name="ltfl", compress=compress)


def sign_compressor(lr_scale: float = 0.02) -> Compressor:
    """SignSGD: 1 bit/coordinate uplink + server majority vote."""

    def compress(g, delta, key, state):
        return jax.tree_util.tree_map(jnp.sign, g), state

    def server_transform(agg):
        return jax.tree_util.tree_map(
            lambda x: (jnp.sign(x) * lr_scale).astype(x.dtype), agg)

    return Compressor(name="sign", compress=compress,
                      server_transform=server_transform)


def stc_compressor(sparsity: float = 0.01) -> Compressor:
    """Sparse ternary compression with carried error-feedback residual.

    The residual is an explicit (C, ...) f32 pytree in the step signature;
    carrying it through jit (instead of a host-side dict keyed by device)
    is what lets STC share the one compiled round.
    """

    def init_state(params, n_clients):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((n_clients,) + p.shape, jnp.float32), params)

    def ternarize(x):
        flat = jnp.abs(x).reshape(-1)
        k = max(int(sparsity * flat.size), 1)
        thresh = jnp.sort(flat)[-k]
        keep = jnp.abs(x) >= thresh
        mu = jnp.sum(jnp.abs(x) * keep) / jnp.maximum(jnp.sum(keep), 1)
        return jnp.sign(x) * mu * keep

    def compress(g, delta, key, residual):
        acc = jax.tree_util.tree_map(
            lambda gi, r: gi.astype(jnp.float32) + r, g, residual)
        tern = jax.tree_util.tree_map(ternarize, acc)
        new_residual = jax.tree_util.tree_map(
            lambda a, t: a - t, acc, tern)
        wire = jax.tree_util.tree_map(
            lambda t, gi: t.astype(gi.dtype), tern, g)
        return wire, new_residual

    return Compressor(name="stc", compress=compress, init_state=init_state)


_REGISTRY = {
    "none": identity_compressor,
    "ltfl": ltfl_quantizer,
    "sign": sign_compressor,
    "stc": stc_compressor,
}


def get_compressor(spec, **kwargs) -> Compressor:
    """Resolve a compressor: pass-through for Compressor instances,
    registry lookup for names."""
    if isinstance(spec, Compressor):
        return spec
    if spec in _REGISTRY:
        return _REGISTRY[spec](**kwargs)
    raise KeyError(f"unknown compressor {spec!r}; have {sorted(_REGISTRY)}")
