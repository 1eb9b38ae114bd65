"""deepseek-v2-lite-16b — MoE with multi-head latent attention (MLA).

Published config (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json; arXiv:2405.04434): 27 layers, hidden 2048, 16 heads, vocab
102400 with untied embedding and head; MLA with kv_lora_rank 512, no q
LoRA, qk_nope 128, qk_rope 64, v 128; rope theta 10000 under YaRN (factor
40 over 4096 original positions, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 0.707); the first layer a dense FFN of 10944
(``first_k_dense_replace`` 1), the other 26 MoE layers of 64 routed
experts of width 1408, top-6 by softmax score (greedy, not renormalised,
``routed_scaling_factor`` 1) plus 2 shared experts; the sequence-wise
balance loss (``seq_aux``) with ``aux_loss_alpha`` 0.001; rms_norm_eps
1e-6.

``ep_share`` cuts one chip's share of an expert-parallel deployment from
it: the dense layer and the first MoE layers, 1/P of the routed experts
of each MoE layer with the router at all 64 outputs, and 1/P of the
vocabulary rows for the embedding and the head; every width as
published.
"""
import dataclasses

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,          # qk_nope (128) + qk_rope (64)
    d_ff=1408,             # routed expert hidden width
    vocab_size=102400,
    mlp_act="silu",
    glu=True,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    mla=MLAConfig(
        kv_lora_rank=512,
        q_lora_rank=0,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_expert=1408,
        num_shared_experts=2,
        d_shared_expert=1408,
        first_k_dense=1,
        dense_d_ff=10944,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        aux_loss_coef=0.001,
    ),
    source="[hf:deepseek-ai/DeepSeek-V2-Lite] [arXiv:2405.04434]",
)


def from_hf(c: dict) -> ArchConfig:
    """The ``ArchConfig`` of a DeepSeek-V2 ``config.json`` (its keys),
    holding ``experts_held`` routed experts from ``first_expert`` where
    it names them."""
    rs = c.get("rope_scaling")
    return ArchConfig(
        name=CONFIG.name,
        family="moe",
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["moe_intermediate_size"],
        vocab_size=c["vocab_size"],
        mlp_act=c["hidden_act"],
        glu=True,
        rope_theta=float(c["rope_theta"]),
        rope_scaling=None if rs is None else YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        tie_embeddings=c["tie_word_embeddings"],
        mla=MLAConfig(
            kv_lora_rank=c["kv_lora_rank"],
            q_lora_rank=c["q_lora_rank"] or 0,
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(
            num_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            d_expert=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            d_shared_expert=c["moe_intermediate_size"],
            first_k_dense=c["first_k_dense_replace"],
            dense_d_ff=c["intermediate_size"],
            norm_topk_prob=c["norm_topk_prob"],
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            aux_loss_coef=c["aux_loss_alpha"],
            experts_held=c.get("experts_held", 0),
            first_expert=c.get("first_expert", 0)),
        source=CONFIG.source)


def ep_share(cfg: ArchConfig = CONFIG, chips: int = 8, moe_layers: int = 4,
             chip: int = 0) -> ArchConfig:
    """Chip ``chip``'s share of ``cfg`` under expert parallelism over
    ``chips`` chips per layer: the dense layers and ``moe_layers`` MoE
    layers, experts [chip * E/chips, (chip + 1) * E/chips) of each, and
    vocab_size / chips rows of the embedding and the head."""
    mo = cfg.moe
    held = mo.num_experts // chips
    return cfg.replace(
        name=f"{cfg.name}-ep{chips}-share",
        n_layers=mo.first_k_dense + moe_layers,
        vocab_size=cfg.vocab_size // chips,
        moe=dataclasses.replace(mo, experts_held=held,
                                first_expert=chip * held))
