"""DeepSeek-V2-Lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]:
multi-head latent attention without query compression (kv_lora 512, rope
64, nope 128, v 128, 16 heads) under YaRN rope scaling (factor 40 over 4096
positions), one dense first layer of width 10944, then 26 MoE layers of 64
routed experts (width 1408, top-6 under softmax, not renormalised) beside 2
shared experts.

``from_published`` builds the program's ``ModelConfig`` from the keys of
the published ``config.json`` (``PUBLISHED``).  ``CONFIG`` holds all 64
experts, dropless (``moe.moe_held``); a deployment that divides them gives
each chip its share through ``held``."""

import dataclasses

from .base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

# the published config.json, without the keys that say nothing of the shape
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}

# what the program implements of the published options
_SUPPORTED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1, "n_group": 1,
              "topk_group": 1, "topk_method": "greedy", "scoring_func": "softmax"}


def from_published(config: dict, held: tuple[int, int] | None = None) -> ModelConfig:
    """The ``ModelConfig`` of a DeepSeek-V2 ``config.json``.  ``held`` =
    (first expert, count) is the layers' expert share (default: all of
    ``n_routed_experts``).  Options the program does not implement are
    refused."""
    off = {k: config[k] for k, v in _SUPPORTED.items() if config.get(k, v) != v}
    if off:
        raise ValueError(f"unsupported DeepSeek-V2 options {off}; supported: {_SUPPORTED}")
    rs = config.get("rope_scaling")
    if rs and rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling {rs!r}: only yarn is implemented")
    n_experts = config["n_routed_experts"]
    first, n_held = held if held is not None else (0, n_experts)
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        attn_type="mla",
        mla=MLAConfig(q_lora_rank=config["q_lora_rank"] or 0,
                      kv_lora_rank=config["kv_lora_rank"],
                      qk_nope_head_dim=config["qk_nope_head_dim"],
                      qk_rope_head_dim=config["qk_rope_head_dim"],
                      v_head_dim=config["v_head_dim"]),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        ) if rs else None,
        n_dense_layers=config["first_k_dense_replace"],
        moe=MoEConfig(n_experts=n_experts, top_k=config["num_experts_per_tok"],
                      d_expert_ff=config["moe_intermediate_size"],
                      n_shared_experts=config["n_shared_experts"],
                      norm_topk_prob=bool(config["norm_topk_prob"]),
                      routed_scaling_factor=float(config["routed_scaling_factor"]),
                      held_first=first, n_held=n_held),
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        max_seq_len=config["max_position_embeddings"],
    )


CONFIG = from_published(PUBLISHED)

REDUCED = dataclasses.replace(
    CONFIG,
    n_layers=4,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    d_ff=256,
    vocab=512,
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=MoEConfig(n_experts=16, top_k=4, d_expert_ff=32, n_shared_experts=2,
                  norm_topk_prob=False, routed_scaling_factor=1.0, n_held=16),
)
