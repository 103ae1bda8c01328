"""Weights laid out as a published checkpoint names them, placed in the
program's parameter tree.

``deepseek_v2_params`` reads DeepSeek-V2's names (``q_proj``,
``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``,
``mlp.gate``, ``mlp.experts.*``, ``mlp.shared_experts.*``, ...), with every
matrix as ``[in, out]`` and each layer's weights stacked along a leading
axis in two groups: ``dense_layers`` (the first ``first_k_dense_replace``)
and ``moe_layers`` (the rest), the experts of the held share stacked after
the layer axis.  Norm weights are as published (around 1); the program
stores an offset from 1.  The checkpoint lays each rotary part of
``q_proj`` and ``kv_a_proj_with_mqa`` out in adjacent pairs (2i, 2i+1),
which the published code de-interleaves before rotating half against half;
placing the weights permutes those columns once (``rope_deinterleave``), so
the program rotates halves and its queries and keys come out as the
published code's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig

__all__ = ["deepseek_v2_params", "rope_deinterleave"]


@jax.jit
def _offsets(norms):
    return jax.tree.map(lambda w: w - jnp.ones((), w.dtype), norms)


def rope_deinterleave(d: int) -> np.ndarray:
    """The order of a rotary part's ``d`` columns after the published
    de-interleave: even columns, then odd ones."""
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def _mixer(attn: dict, cfg: ModelConfig) -> dict:
    m = cfg.mla
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    kv_b = attn["kv_b_proj"]
    lead = kv_b.shape[:-2]
    kv_b = kv_b.reshape(lead + (m.kv_lora_rank, cfg.n_heads, nope + m.v_head_dim))
    q = attn["q_proj"]
    q = q.reshape(q.shape[:-1] + (cfg.n_heads, nope + rope))
    q_cols = np.concatenate([np.arange(nope), nope + rope_deinterleave(rope)])
    dkv_cols = np.concatenate([np.arange(m.kv_lora_rank),
                               m.kv_lora_rank + rope_deinterleave(rope)])
    return {
        "w_q": q[..., q_cols].reshape(attn["q_proj"].shape),
        "w_dkv": attn["kv_a_proj_with_mqa"][..., dkv_cols],
        "kv_norm": attn["kv_a_layernorm"],
        "w_uk": kv_b[..., : m.qk_nope_head_dim].reshape(lead + (m.kv_lora_rank, -1)),
        "w_uv": kv_b[..., m.qk_nope_head_dim :].reshape(lead + (m.kv_lora_rank, -1)),
        "w_o": attn["o_proj"],
    }


def _swiglu(mlp: dict) -> dict:
    return {"w_gate": mlp["gate_proj"], "w_up": mlp["up_proj"], "w_down": mlp["down_proj"]}


def _block(group: dict, cfg: ModelConfig, moe: bool) -> dict:
    mlp = group["mlp"]
    if moe:
        ffn = {"router": mlp["gate"], **_swiglu(mlp["experts"])}
        if "shared_experts" in mlp:
            ffn["shared"] = _swiglu(mlp["shared_experts"])
    else:
        ffn = _swiglu(mlp)
    return {"ln1": group["input_layernorm"], "mixer": _mixer(group["self_attn"], cfg),
            "ln2": group["post_attention_layernorm"], "ffn": ffn}


def deepseek_v2_params(w: dict, cfg: ModelConfig) -> dict:
    """The program's parameters (``Model(cfg).init``'s tree) from weights
    in DeepSeek-V2's published layout (module docstring)."""
    norm_names = ("input_layernorm", "post_attention_layernorm", "kv_a_layernorm")

    def with_offsets(group):
        attn = dict(group["self_attn"])
        norms = _offsets({"ln1": group["input_layernorm"], "ln2": group["post_attention_layernorm"],
                          "kv": attn["kv_a_layernorm"]})
        attn["kv_a_layernorm"] = norms["kv"]
        out = {k: v for k, v in group.items() if k not in norm_names}
        return {**out, "self_attn": attn, "input_layernorm": norms["ln1"],
                "post_attention_layernorm": norms["ln2"]}

    dense, moe = with_offsets(w["dense_layers"]), with_offsets(w["moe_layers"])
    n_moe = cfg.n_layers - cfg.n_dense_layers
    block = _block(moe, cfg, moe=True)
    if n_moe == 1:
        block = jax.tree.map(lambda t: t[0], block)
    prelude = tuple(_block(jax.tree.map(lambda t, i=i: t[i], dense), cfg, moe=False)
                    for i in range(cfg.n_dense_layers))
    params = {"embed": w["embed_tokens"], "final_norm": _offsets(w["norm"]),
              "lm_head": w["lm_head"], "decoder": (block,)}
    if prelude:
        params["prelude"] = prelude
    return params
