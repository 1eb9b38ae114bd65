"""Dry-run builders + roofline analysis (deliverables (e) and (g)).

For every (architecture x input shape x mesh) this module AOT-lowers and
compiles the appropriate step — the LTFL federated train step for
``train_4k``, ``model.prefill`` for ``prefill_32k``, ``model.decode_step``
for the decode shapes — against ``jax.ShapeDtypeStruct`` inputs (no
allocation), then derives the three roofline terms from the compiled,
partitioned module:

    compute    = HLO_FLOPs(per device)        / peak_FLOP/s
    memory     = HLO_bytes(per device)        / HBM_bw
    collective = wire_bytes(per device, ring) / ICI_bw

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s
ICI per link, 16 GB HBM per chip.

``variant`` is the hillclimb hook (EXPERIMENTS.md section Perf): a dict of
overrides such as {"prune": False}, {"agg": "int8"}, {"fsdp": True},
{"remat": "dots"}, {"moe_token": "dense"}.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import ArchConfig, ShapeConfig, shape_applicable
from repro.core.ltfl_step import make_fl_train_step
from repro.launch import sharding as shlib
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import (
    client_axes,
    make_production_mesh,
    make_test_mesh,
    num_clients,
)
from repro.models import build_model
from repro.models.common import logical_rule_scope
from repro.models.registry import (
    prefill_batch_struct,
    train_batch_struct,
)
from repro.optim import sgd

HW = {
    "peak_flops": 197e12,     # bf16 FLOP/s per chip
    "hbm_bw": 819e9,          # bytes/s per chip
    "ici_bw": 50e9,           # bytes/s per link
    "hbm_bytes": 16e9,        # HBM capacity per chip
}


@dataclass
class DryRunRecord:
    arch: str
    shape: str
    mesh: str
    mode: str
    n_clients: int
    variant: Dict[str, Any]
    # memory (per device)
    bytes_per_device: float
    fits_hbm: bool
    # compute / memory / collective raw
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_operand_bytes: float
    collective_wire_bytes: float
    collective_count: int
    # roofline terms (seconds)
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    # usefulness
    model_flops: float
    useful_ratio: float
    compile_seconds: float
    args_bytes: float = 0.0
    out_bytes: float = 0.0
    temp_bytes: float = 0.0
    alias_bytes: float = 0.0

    def to_dict(self):
        return asdict(self)


# --------------------------------------------------------------------------- #
# builders
# --------------------------------------------------------------------------- #
def _arch_for(arch_name: str, shape: ShapeConfig,
              variant: Dict[str, Any]) -> ArchConfig:
    return configs.arch_for_shape(configs.get_arch(arch_name), shape)



def _apply_variant_rules(rules, variant):
    """Perf-pass rule overrides: {"act": "seq"} switches the residual
    stream from d_model-sharding to sequence-parallel sharding;
    {"rules_override": {...}} sets arbitrary logical->mesh entries."""
    if variant.get("act") == "seq":
        rules["act_seq"] = ("model",)
        rules["act_embed"] = None
    for k, v in (variant.get("rules_override") or {}).items():
        rules[k] = tuple(v) if isinstance(v, list) else v
    return rules


def build_train(arch: ArchConfig, shape: ShapeConfig, mesh,
                variant: Dict[str, Any]):
    """LTFL federated train step, AOT."""
    remat = variant.get("remat", True)
    model = build_model(arch, remat=remat)
    multi_pod = "pod" in mesh.axis_names
    pod_only = arch.fl_clients_on_pod_only
    fsdp = variant.get("fsdp", shlib.policy_for(arch)["fsdp"])
    c_axes = client_axes(multi_pod, pod_only)
    rules = _apply_variant_rules(
        shlib.base_rules(mesh, fsdp=fsdp, client_axes=c_axes), variant)

    n_clients = num_clients(mesh, pod_only)
    assert shape.global_batch % n_clients == 0, (shape, n_clients)
    per_client = shape.global_batch // n_clients

    params_abs = model.abstract_params()
    param_sh = shlib.param_shardings(mesh, model, rules)

    # stacked (n_clients, ...) shardings for the per-client gradient tree
    from repro.models.common import logical_axes
    specs = model.param_specs()
    stacked_sh = shlib.sharding_tree(
        mesh, rules,
        jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, s.dtype),
            params_abs),
        jax.tree_util.tree_map(
            lambda a: ("client",) + a, logical_axes(specs),
            is_leaf=lambda x: isinstance(x, tuple)))

    # client-axis-replicated shardings: the int8 all-gather target layout
    gather_sh = shlib.sharding_tree(
        mesh, rules,
        jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, s.dtype),
            params_abs),
        jax.tree_util.tree_map(
            lambda a: (None,) + a, logical_axes(specs),
            is_leaf=lambda x: isinstance(x, tuple)))

    opt = sgd(0.05)
    step = make_fl_train_step(
        model, opt, n_clients,
        prune_block=variant.get("prune_block", 128),
        quantize=variant.get("quant", True),
        prune=variant.get("prune", True),
        simulate_drops=variant.get("drops", True),
        param_shardings=None if variant.get("no_constraints")
        else stacked_sh,
        int8_collective=variant.get("agg") == "int8",
        gather_shardings=gather_sh,
    )
    bs = train_batch_struct(arch, shape.global_batch, shape.seq_len)
    batch_abs = {k: jax.ShapeDtypeStruct((n_clients, per_client)
                                         + v.shape[1:], v.dtype)
                 for k, v in bs.items()}
    batch_sh = {
        k: jax.sharding.NamedSharding(
            mesh, shlib.make_pspec(v.shape,
                                   ("client", "batch")
                                   + (None,) * (len(v.shape) - 2),
                                   rules, mesh))
        for k, v in batch_abs.items()
    }
    rep = shlib.replicated(mesh)
    ctrl_abs = {k: jax.ShapeDtypeStruct((n_clients,), jnp.float32)
                for k in ("rho", "delta", "drop_prob", "weights")}
    ctrl_sh = {k: rep for k in ctrl_abs}
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)

    # variant {"scan": R}: AOT-lower R federated rounds as ONE scanned
    # segment (repro.fed.make_scanned_step) — the scanned engine's
    # datacenter shape. Batches/keys gain a leading (replicated) round
    # axis; controls stay segment-constant; the roofline analysis is
    # already scan-aware (hlo_analysis multiplies loop bodies by trip
    # count).
    scan_rounds = int(variant.get("scan") or 0)
    if scan_rounds:
        from repro.fed.scan_engine import make_scanned_step
        step = make_scanned_step(step)
        batch_abs = {k: jax.ShapeDtypeStruct((scan_rounds,) + v.shape,
                                             v.dtype)
                     for k, v in batch_abs.items()}
        batch_sh = {
            k: jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, *s.spec))
            for k, s in batch_sh.items()
        }
        key_abs = jax.ShapeDtypeStruct((scan_rounds, 2), jnp.uint32)

    # comp_state is the carried compressor pytree — () for the stateless
    # LTFL quantizer; stateful compressors (STC) would pin it like params.
    jf = jax.jit(step,
                 in_shardings=(param_sh, (), (), batch_sh, ctrl_sh, rep),
                 out_shardings=(param_sh, (), (), rep),
                 donate_argnums=(0, 1, 2))
    args = (params_abs, (), (), batch_abs, ctrl_abs, key_abs)
    return jf, args, rules, n_clients


def build_prefill(arch: ArchConfig, shape: ShapeConfig, mesh,
                  variant: Dict[str, Any]):
    model = build_model(arch, remat=False)
    fsdp = variant.get("fsdp", shlib.policy_for(arch)["fsdp"])
    rules = _apply_variant_rules(shlib.base_rules(mesh, fsdp=fsdp), variant)
    params_abs = model.abstract_params()
    param_sh = shlib.param_shardings(mesh, model, rules)
    bs = prefill_batch_struct(arch, shape.global_batch, shape.seq_len)
    batch_sh = shlib.batch_shardings(mesh, rules, bs)
    jf = jax.jit(lambda p, b: model.prefill(p, b),
                 in_shardings=(param_sh, batch_sh))
    return jf, (params_abs, bs), rules, 0


def build_decode(arch: ArchConfig, shape: ShapeConfig, mesh,
                 variant: Dict[str, Any]):
    model = build_model(arch, remat=False)
    fsdp = variant.get("fsdp", shlib.policy_for(arch)["fsdp"])
    rules = _apply_variant_rules(shlib.base_rules(mesh, fsdp=fsdp), variant)
    if variant.get("cache_rules"):
        rules.update(variant["cache_rules"])
    params_abs = model.abstract_params()
    param_sh = shlib.param_shardings(mesh, model, rules)
    B = shape.global_batch
    cache_abs = model.abstract_cache(B, shape.seq_len)
    cache_sh = shlib.cache_shardings(mesh, rules, model, cache_abs)
    tok_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_sh = jax.sharding.NamedSharding(
        mesh, shlib.make_pspec((B,), ("batch",), rules, mesh))
    jf = jax.jit(lambda p, t, pos, c: model.decode_step(p, t, pos, c),
                 in_shardings=(param_sh, tok_sh, tok_sh, cache_sh),
                 donate_argnums=(3,))
    return jf, (params_abs, tok_abs, pos_abs, cache_abs), rules, 0


# --------------------------------------------------------------------------- #
# analysis
# --------------------------------------------------------------------------- #
def _model_flops(arch: ArchConfig, shape: ShapeConfig, n_chips: int) -> float:
    """MODEL_FLOPS per device: 6 N D (train) / 2 N D (inference forward),
    N = active params, D = tokens processed globally."""
    n_active = arch.param_count(active_only=True)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / n_chips
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / n_chips
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / n_chips


def analyze(arch: ArchConfig, shape: ShapeConfig, mesh, lowered, compiled,
            n_clients: int, variant: Dict[str, Any],
            compile_seconds: float) -> DryRunRecord:
    n_chips = int(np.prod(list(mesh.shape.values())))
    mem = compiled.memory_analysis()
    bytes_dev = float(mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    mem_kw = dict(args_bytes=float(mem.argument_size_in_bytes),
                  out_bytes=float(mem.output_size_in_bytes),
                  temp_bytes=float(mem.temp_size_in_bytes),
                  alias_bytes=float(mem.alias_size_in_bytes))
    # scan-aware HLO accounting (xla cost_analysis counts while bodies once,
    # which would undercount 96-layer scanned models ~96x — see hlo_analysis)
    hlo = analyze_hlo(compiled.as_text())
    flops = float(hlo["flops"])
    hbm_bytes = float(hlo["hbm_bytes"])
    coll = {k[len("coll_"):]: v for k, v in hlo.items()
            if k.startswith("coll_")}

    t_comp = flops / HW["peak_flops"]
    t_mem = hbm_bytes / HW["hbm_bw"]
    t_coll = coll["wire_total"] / HW["ici_bw"]
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    mflops = _model_flops(arch, shape, n_chips)

    return DryRunRecord(
        arch=arch.name,
        shape=shape.name,
        mesh="x".join(f"{k}{v}" for k, v in mesh.shape.items()),
        mode=shape.mode,
        n_clients=n_clients,
        variant=variant,
        bytes_per_device=bytes_dev,
        fits_hbm=bytes_dev <= HW["hbm_bytes"],
        **mem_kw,
        flops_per_device=flops,
        hbm_bytes_per_device=hbm_bytes,
        collective_operand_bytes=coll["total"],
        collective_wire_bytes=coll["wire_total"],
        collective_count=int(coll["count"]),
        t_compute=t_comp,
        t_memory=t_mem,
        t_collective=t_coll,
        bottleneck=bottleneck,
        model_flops=mflops,
        useful_ratio=(mflops / flops) if flops else 0.0,
        compile_seconds=compile_seconds,
    )


def run_pair(arch_name: str, shape_name: str, *, multi_pod: bool = False,
             variant: Optional[Dict[str, Any]] = None,
             test_mesh: bool = False,
             out_dir: Optional[str] = None,
             verbose: bool = True) -> Optional[DryRunRecord]:
    """Lower + compile + analyze one (arch, shape, mesh). Returns None for
    documented skips (DESIGN.md section 4)."""
    variant = dict(variant or {})
    shape = configs.get_shape(shape_name)
    arch = configs.arch_for_shape(configs.get_arch(arch_name), shape)
    ok, why = shape_applicable(arch, shape)
    if not ok:
        if verbose:
            print(f"SKIP {arch_name} x {shape_name}: {why}")
        return None

    mesh = (make_test_mesh(multi_pod=multi_pod) if test_mesh
            else make_production_mesh(multi_pod=multi_pod))
    from repro.models import moe as moe_mod
    from repro.models import rwkv6 as rwkv_mod
    from repro.models import mamba2 as mamba_mod
    saved_moe = (moe_mod.TOKEN_DISPATCH, rwkv_mod.CHUNK, mamba_mod.CHUNK)
    if variant.get("moe_token") and arch.moe is not None:
        moe_mod.TOKEN_DISPATCH = variant["moe_token"]
    if variant.get("rwkv_chunk"):
        rwkv_mod.CHUNK = int(variant["rwkv_chunk"])
    if variant.get("mamba_chunk"):
        mamba_mod.CHUNK = int(variant["mamba_chunk"])

    builder = {"train": build_train, "prefill": build_prefill,
               "decode": build_decode}[shape.mode]
    t0 = time.time()
    try:
        with mesh:
            jf, args, rules, n_clients = builder(arch, shape, mesh, variant)
            with logical_rule_scope(rules, mesh):
                lowered = jf.lower(*args)
                compiled = lowered.compile()
    finally:
        (moe_mod.TOKEN_DISPATCH, rwkv_mod.CHUNK,
         mamba_mod.CHUNK) = saved_moe
    dt = time.time() - t0
    rec = analyze(arch, shape, mesh, lowered, compiled, n_clients, variant,
                  dt)
    if verbose:
        print(f"{arch_name:24s} {shape_name:12s} {rec.mesh:18s} "
              f"fits={rec.fits_hbm} mem={rec.bytes_per_device/1e9:7.2f}GB "
              f"tc={rec.t_compute*1e3:9.2f}ms tm={rec.t_memory*1e3:9.2f}ms "
              f"tx={rec.t_collective*1e3:9.2f}ms dom={rec.bottleneck} "
              f"useful={rec.useful_ratio:5.2f} compile={dt:.1f}s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        vtag = "_".join(f"{k}-{v}" for k, v in sorted(variant.items())) \
            or "baseline"
        fn = f"{arch_name}__{shape_name}__{rec.mesh}__{vtag}.json"
        with open(os.path.join(out_dir, fn.replace('/', '-')), "w") as f:
            json.dump(rec.to_dict(), f, indent=2)
    return rec
