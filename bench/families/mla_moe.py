"""The ``mla_moe`` family: DeepSeek-V2's decoder.  Multi-head latent
attention (no query compression, YaRN rope on interleaved pairs), the first
``first_k_dense_replace`` layers with a dense SwiGLU MLP, every later layer
an MoE of routed experts (softmax top-k, optionally renormalised) beside
shared ones, RMSNorm and an untied LM head.

A configuration of this family is one chip's share of expert parallelism:
its file gives the experts held here under ``n_routed_experts`` (listed in
``reduced``) and the router's published width under ``published``.  The
chip holds experts ``0 .. n_routed_experts - 1`` of every MoE layer and all
the rest of the model; program and reference compute the same share.

The weight tree is in DeepSeek-V2's published names (``shapes``), and the
program places it (``repro.models.published``).  The reference is
``bench/reference/mla_moe.py``; the work counts are below.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import mla_moe as reference

logits_at = reference.logits_at

ROPE_KEYS = ("beta_fast", "beta_slow", "factor", "mscale", "mscale_all_dim",
             "original_max_position_embeddings")


def model_config(config: dict, reduced: bool = False):
    """The program's ``ModelConfig`` from the file's published keys, the
    router at its published width and the layers holding the file's share
    of the experts, in the file's dtype.  ``reduced`` takes the registry's
    CPU-sized preset with the same fraction of its experts held (tests
    only)."""
    from repro.configs import deepseek_v2_lite
    from repro.configs.base import get_config

    dtype = config["torch_dtype"]
    held, width = config["n_routed_experts"], config["published"]["n_routed_experts"]
    if reduced:
        base = get_config(config["registry_id"], reduced=True)
        moe = dataclasses.replace(base.moe, held_first=0,
                                  n_held=max(1, base.moe.n_experts * held // width))
        return dataclasses.replace(base, moe=moe, param_dtype=dtype, compute_dtype=dtype)
    cfg = deepseek_v2_lite.from_published({**config, "n_routed_experts": width}, held=(0, held))
    return dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)


def reference_config(cfg) -> dict:
    """The plain reference's sizes, read back from the ``ModelConfig`` that
    runs (so reduced test sizes agree too): published keys, the dense
    layers' width as ``dense_width`` and the share as ``held_first`` /
    ``held_experts``."""
    m, moe, rs = cfg.mla, cfg.moe, cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "vocab_size": cfg.vocab,
        "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None if rs is None else dict(zip(ROPE_KEYS, (
            rs.beta_fast, rs.beta_slow, rs.factor, rs.mscale, rs.mscale_all_dim,
            rs.original_max_position))),
        "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim,
        "v_head_dim": m.v_head_dim,
        "first_k_dense_replace": cfg.n_dense_layers,
        "dense_width": cfg.ffn_width(0),
        "moe_intermediate_size": moe.d_expert_ff,
        "n_routed_experts": moe.n_experts,
        "num_experts_per_tok": moe.top_k,
        "n_shared_experts": moe.n_shared_experts,
        "norm_topk_prob": moe.norm_topk_prob,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "held_first": moe.held_first,
        "held_experts": moe.n_held,
        "tie_word_embeddings": cfg.tie_embeddings,
    }


def _attn_shapes(cfg: dict, n: int) -> dict:
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {
        "q_proj": (n, d, h * (nope + rope)),
        "kv_a_proj_with_mqa": (n, d, r + rope),
        "kv_a_layernorm": (n, r),
        "kv_b_proj": (n, r, h * (nope + dv)),
        "o_proj": (n, h * dv, d),
    }


def _swiglu_shapes(lead: tuple, d: int, width: int) -> dict:
    return {"gate_proj": lead + (d, width), "up_proj": lead + (d, width),
            "down_proj": lead + (width, d)}


def shapes(cfg: dict) -> dict:
    """The weight tree in DeepSeek-V2's published names, every matrix
    ``[in, out]``, each group's layers stacked along a leading axis (the
    held experts after it)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - k
    f = cfg["moe_intermediate_size"]
    moe_mlp = {"gate": (n, d, cfg["n_routed_experts"]),
               "experts": _swiglu_shapes((n, cfg["held_experts"]), d, f)}
    if cfg["n_shared_experts"]:
        moe_mlp["shared_experts"] = _swiglu_shapes((n,), d, cfg["n_shared_experts"] * f)
    return {
        "embed_tokens": (v, d),
        "norm": (d,),
        "lm_head": (d, v),
        "dense_layers": {"input_layernorm": (k, d), "self_attn": _attn_shapes(cfg, k),
                         "post_attention_layernorm": (k, d),
                         "mlp": _swiglu_shapes((k,), d, cfg["dense_width"])},
        "moe_layers": {"input_layernorm": (n, d), "self_attn": _attn_shapes(cfg, n),
                       "post_attention_layernorm": (n, d), "mlp": moe_mlp},
    }


def program_params(w: dict, model) -> dict:
    """The weights (published layout) in the program's tree, as the program
    loads a DeepSeek-V2 checkpoint; matrices stay the same device arrays but
    ``kv_b_proj``, which the program keeps as its key and value halves, and
    ``q_proj`` and ``kv_a_proj_with_mqa``, whose rotary columns it puts in
    de-interleaved order."""
    from repro.models.published import deepseek_v2_params

    return deepseek_v2_params(w, model.cfg)


# ---------------------------------------------------------------- work counts
#
# The operations and bytes the algorithm needs, from the published sizes and
# the live lengths of a step, whatever implements it.  FLOPs are 2 per
# multiply-add; weights are read once a step in ``weight_bytes`` an element
# (bf16 as served), the embedding as one row a token.  Attention is latent:
# at decode the absorbed form (the query taken into the latent space by the
# key half of kv_b_proj, scores over [latent, rope key], the output read
# from the latent and taken out by the value half), at prefill the expanded
# form (keys and values made per head, attention at nope + rope and v).
# Decode reads each active row's live latent cache (rank + rope a layer and
# token) and writes one entry a row.  Routed experts: a held expert's
# weights are read in a step where at least one assignment lands on it.
# ``decode_step`` and ``prefill`` count the routing a trained router gives,
# balanced by its training: each of a token's ``num_experts_per_tok``
# assignments lands on the held share with probability
# held/n_routed_experts (``expected_held``).  ``held_experts_step`` counts
# the routing a run reports.  Nothing here counts padding, inactive rows or
# whole-ring reads.


def _attn_params(cfg: dict) -> int:
    return sum(int(np.prod(s[1:])) for s in _attn_shapes(cfg, 1).values())


def _n_moe(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _held_share(cfg: dict) -> float:
    """Expected assignments a token makes to the held experts."""
    return cfg["num_experts_per_tok"] * cfg["held_experts"] / cfg["n_routed_experts"]


def _always_params(cfg: dict) -> int:
    """Weights every token passes through, LM head aside: attention (its
    kv_b_proj once, as the key and value halves) and norms of every layer,
    the dense layers' MLP, each MoE layer's router and shared experts."""
    d = cfg["hidden_size"]
    mlp = (cfg["first_k_dense_replace"] * 3 * d * cfg["dense_width"]
           + _n_moe(cfg) * (d * cfg["n_routed_experts"]
                            + cfg["n_shared_experts"] * _expert_params(cfg)))
    return cfg["num_hidden_layers"] * (_attn_params(cfg) + 2 * d) + mlp


def latent_bytes_per_token(cfg: dict, elem_bytes: int = 2) -> int:
    return (cfg["num_hidden_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * elem_bytes)


def expected_held(cfg: dict, rows: int) -> tuple[float, float]:
    """(held assignments, held experts given at least one) of a decode
    step of ``rows`` tokens under uniform routing, summed over the MoE
    layers."""
    touched = cfg["held_experts"] * (
        1.0 - (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** rows)
    return _n_moe(cfg) * rows * _held_share(cfg), _n_moe(cfg) * touched


def held_experts_step(cfg: dict, assignments: float, experts: float,
                      weight_bytes: int = 2) -> dict:
    """The held experts' work in one decode step that made ``assignments``
    (token, held expert) assignments over ``experts`` (layer, held expert)
    pairs, both summed over the MoE layers: the assignments' FLOPs, those
    experts' weights read once, and the rows they read and write."""
    bytes_ = (experts * _expert_params(cfg) + 2 * assignments * cfg["hidden_size"]) * weight_bytes
    return {"flops": float(assignments * 2 * _expert_params(cfg)), "bytes": float(bytes_)}


def decode_step(cfg: dict, ctx, weight_bytes: int = 2, kv_elem_bytes: int = 2) -> dict:
    """One decode step over the active rows; ``ctx[i]`` is row i's context
    length including the token being decoded."""
    ctx = np.asarray(ctx, np.int64)
    rows = int(ctx.size)
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n = cfg["num_hidden_layers"]
    always = _always_params(cfg)
    head = d * cfg["vocab_size"]
    held = held_experts_step(cfg, *expected_held(cfg, rows), weight_bytes)
    attended = int(ctx.sum())
    # every weight a row passes through once (kv_b_proj as the absorbed key
    # half and the value half); a (row, key) pair: scores over [latent,
    # rope key] and the output over the latent, each head
    pair_flops = n * 2 * h * (2 * r + cfg["qk_rope_head_dim"])
    flops = rows * 2 * (always + head) + pair_flops * attended + held["flops"]
    kv = latent_bytes_per_token(cfg, kv_elem_bytes)
    bytes_ = ((always + head) * weight_bytes + held["bytes"] + rows * d * weight_bytes
              + kv * attended + kv * rows)
    return {"flops": float(flops), "bytes": float(bytes_)}


def prefill(cfg: dict, length: int) -> dict:
    """One prompt of ``length`` tokens at its true length: every layer over
    every position in the expanded form, logits at the last one."""
    h = cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    always = _always_params(cfg)
    pairs = length * (length + 1) // 2
    routed = length * _held_share(cfg) * _n_moe(cfg) * 2 * _expert_params(cfg)
    attn = cfg["num_hidden_layers"] * 2 * h * (nope + rope + dv) * pairs
    flops = 2 * always * length + routed + attn + 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return {"flops": float(flops)}
