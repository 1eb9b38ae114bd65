"""The unified, jit-able LTFL federated round step.

This is the single batched realization of the paper's round (Eq. 8-20)
that BOTH engines share: the edge-mode ``repro.fed.rounds.FedRunner``
(CIFAR/ResNet, wireless accounting on host) and the datacenter launcher /
dry-run (clients on mesh axes, DESIGN.md section 3). The batch carries an
explicit leading client axis C; per-client gradients are computed with
vmap(grad), pruned (unstructured for paper-faithful edge runs, block-
structured for MXU), compressed by a pluggable jit-able ``Compressor``
stage (repro.core.compressors: LTFL stochastic quantization, SignSGD
sign + majority vote, STC ternary + carried error-feedback residual,
identity), dropped per the packet-error Bernoulli (Eq. 4), and aggregated
with sample-count weights (Eq. 19). Compressor state (STC residuals) is an
explicit carried pytree in the step signature, so stateful schemes retain
one-compiled-call-per-round semantics.

``controls`` come from the scheme / Algorithm-1 controller:
    rho        (C,) pruning ratios
    delta      (C,) quantization bit-widths (0 => passthrough)
    weights    (C,) sample counts N_u
    drop_prob  (C,) packet error rates q_u(p_u)  (in-jit Bernoulli), OR
    alpha      (C,) host-sampled transmission outcomes (edge engine: the
               channel stays on host, Eq. 4, only tensor work is jitted)
    lr         () optional laned learning rate; when present it is routed
               to ``optimizer.update_with_lr`` so lr-only sweep grids
               share one compiled program (bitwise-identical to the baked
               ``optimizer.update`` path — see repro.optim.Optimizer)

With ``use_kernels=True`` the 2-D-tileable leaves route through the Pallas
kernels in repro.kernels.ops (block-prune norms/masking and the dynamic-
bits stochastic quantizer): compiled on a TPU, interpreted on the CPU.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.aggregation import aggregate
from repro.core.compressors import (
    Compressor,
    get_compressor,
    identity_compressor,
    ltfl_quantizer,
)
from repro.core.pruning import magnitude_prune_pytree, prune_pytree
from repro.core.quantization import (
    dequantize_int8,
    quantize_int8_pytree,
    range_sq_sum,
)
from repro.optim import Optimizer, apply_updates, global_norm

PyTree = Any


def make_fl_train_step(model, optimizer: Optimizer, n_clients: int,
                       *, prune_block: int = 128,
                       quantize: bool = True,
                       prune: bool = True,
                       prune_kind: str = "block",
                       simulate_drops: bool = True,
                       compressor: Union[Compressor, str, None] = None,
                       use_kernels: bool = False,
                       param_shardings=None,
                       int8_collective: bool = False,
                       gather_shardings=None
                       ) -> Callable:
    """Build step(params, opt_state, comp_state, batch, controls, key)
    -> (params, opt_state, comp_state, metrics).

    batch leaves carry a leading client axis C == n_clients. ``compressor``
    selects the uplink compression stage (a Compressor, a registry name,
    or None => the legacy quantize/no-quantize switch); ``comp_state`` is
    its carried pytree — use the returned step's ``init_comp_state(params)``
    to build the initial value (() for stateless compressors).
    ``use_kernels`` reaches the compressor only for None/name-based specs;
    a ready-made Compressor instance keeps whatever kernel setting it was
    built with (thread use_kernels into its factory yourself), while the
    flag still controls the pruning stage.

    ``prune_kind`` picks unstructured "magnitude" pruning (the edge
    engine's paper-faithful Eq. 12-13) or MXU-"block" pruning (datacenter).
    The quantize/prune/simulate_drops switches exist for the paper's
    ablation (Fig. 2) and for baselines. ``param_shardings`` (a pytree of
    NamedShardings shaped like the STACKED (n_clients, ...) grads) pins the
    per-client gradient tree — and, via propagation, the prune/quantize
    temporaries — to the parameter layout; without it GSPMD may replicate
    multi-GB masks and random bits on every device.
    """
    if compressor is None:
        comp = ltfl_quantizer(use_kernels=use_kernels) if quantize \
            else identity_compressor()
    else:
        if int8_collective:
            raise ValueError(
                "int8_collective is a wire-format override; "
                "pass compressor=None")
        # name-based specs get the engine-wide kernel flag threaded through
        # (only the ltfl quantizer has a kernel variant)
        kw = {"use_kernels": use_kernels} if compressor == "ltfl" else {}
        comp = get_compressor(compressor, **kw)
    if prune_kind not in ("block", "magnitude"):
        raise ValueError(f"prune_kind={prune_kind!r}")

    def constrain_stacked(tree):
        """Pin the (C, ...) per-client grad tree to its shardings — applied
        OUTSIDE the vmap so the client axis keeps its mesh placement."""
        if param_shardings is None:
            return tree
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, param_shardings)

    def _prune(params, rho):
        if prune_kind == "magnitude":
            return magnitude_prune_pytree(params, rho)
        return prune_pytree(params, rho, block=prune_block,
                            use_kernels=use_kernels)

    def client_grad(params, cbatch, rho):
        if prune:
            pruned, masks = _prune(params, rho)
        else:
            pruned, masks = params, None
        loss, g = jax.value_and_grad(model.loss)(pruned, cbatch)
        if prune:
            # pruned coordinates are neither trained nor uploaded (Eq. 32)
            g = jax.tree_util.tree_map(
                lambda gi, m: gi * m.astype(gi.dtype), g, masks)
        rsq = range_sq_sum(g)
        return g, loss, rsq

    def step(params: PyTree, opt_state: PyTree, comp_state: PyTree,
             batch: PyTree, controls: Dict[str, jax.Array], key: jax.Array
             ) -> Tuple[PyTree, PyTree, PyTree, Dict[str, jax.Array]]:
        keys = jax.random.split(key, n_clients + 1)
        grads, losses, rsqs = jax.vmap(
            client_grad, in_axes=(None, 0, 0))(
            params, batch, controls["rho"])
        grads = constrain_stacked(grads)
        # int8_collective with an explicit compressor was rejected above
        if quantize and int8_collective:
            # beyond-paper wire format: move int8 levels across the client
            # axis (all-gather of 1 byte/coord) instead of letting XLA
            # all-reduce bf16 partial sums (2 bytes/coord x 2 passes);
            # dequant + weighted mean happen after the gather, locally.
            levels, scales = jax.vmap(quantize_int8_pytree)(
                grads, keys[:n_clients])
            if gather_shardings is not None:
                levels = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, levels,
                    gather_shardings)
            grads = jax.tree_util.tree_map(
                lambda lv, sc: dequantize_int8(
                    lv, sc.reshape((n_clients,) + (1,) * (lv.ndim - 1))),
                levels, scales)
        else:
            grads, comp_state = jax.vmap(
                comp.compress, in_axes=(0, 0, 0, 0))(
                grads, controls["delta"], keys[:n_clients], comp_state)
            grads = constrain_stacked(grads)

        if "alpha" in controls:                    # host-sampled channel
            alpha = controls["alpha"].astype(jnp.float32)
        elif simulate_drops:
            alpha = (jax.random.uniform(keys[-1], (n_clients,))
                     >= controls["drop_prob"]).astype(jnp.float32)   # Eq. 4
        else:
            alpha = jnp.ones((n_clients,), jnp.float32)

        # Eq. 19; "agg_denom" (population layer, unbiased partial
        # participation) fixes the normalizer at the population sample
        # total instead of renormalizing over the received cohort
        g = aggregate(grads, controls["weights"], alpha,
                      denom=controls.get("agg_denom"))
        g = comp.server_transform(g)
        lr = controls.get("lr")
        if lr is None:
            updates, opt_state = optimizer.update(g, opt_state, params)
        elif optimizer.update_with_lr is None:
            raise ValueError(
                "controls['lr'] lanes the learning rate through the step, "
                "but this optimizer does not provide update_with_lr")
        else:
            updates, opt_state = optimizer.update_with_lr(
                g, opt_state, params, lr)
        params = apply_updates(params, updates)                      # Eq. 20
        metrics = {
            "loss": jnp.mean(losses),
            "grad_norm": global_norm(g),
            "clients_received": jnp.sum(alpha),
            "range_sq": rsqs,
            "range_sq_mean": jnp.mean(rsqs),
        }
        return params, opt_state, comp_state, metrics

    step.compressor = comp
    step.init_comp_state = lambda params: comp.init_state(params, n_clients)
    return step


def make_plain_train_step(model, optimizer: Optimizer) -> Callable:
    """Non-federated reference step (single global batch) — used by the
    FedSGD-style baselines and as the no-LTFL control in benchmarks."""

    def step(params, opt_state, batch, key):
        loss, g = jax.value_and_grad(model.loss)(params, batch)
        updates, opt_state = optimizer.update(g, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "grad_norm": global_norm(g)}

    return step
