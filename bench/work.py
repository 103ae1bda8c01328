"""The operations and bytes the algorithm needs, from a configuration's
published sizes and the live lengths of a step, whatever implements it:
the ``gqa`` family's work counts (``bench/families/gqa.py``).

``cfg`` is a dict in the published keys (the family's ``reference_config``).
The counts are those of a decoder-only GQA transformer with a SwiGLU MLP:

* weights: every matrix is read once per step, in ``weight_bytes`` per
  element (bf16 as served); the embedding is a gather of one row per token;
* FLOPs: 2 per multiply-add.  Matrices: ``2 * matmul_params`` per token, the
  LM head only where logits are needed (every decode row, the last prompt
  position of a prefill).  Attention: ``4 * heads * head_dim`` per (query,
  key) pair the mask admits: causal, and within ``sliding_window`` keys;
* decode bytes: the weights once, each active row's live KV entries read
  (at most the window), and one new entry written per row.

Nothing here counts what an implementation does beyond that (padding,
inactive rows, whole-ring rewrites): those are the cost a roofline shows.
"""

from __future__ import annotations

import numpy as np


def layer_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    attn = d * hd * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp + 2 * d  # + the two RMSNorm weights


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def param_count(cfg: dict) -> int:
    """All parameters: embedding, layers, final norm, LM head (untied)."""
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    head = 0 if cfg["tie_word_embeddings"] else head_params(cfg)
    return embed + cfg["num_hidden_layers"] * layer_params(cfg) + cfg["hidden_size"] + head


def kv_bytes_per_token(cfg: dict, elem_bytes: int = 2) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"]
            * elem_bytes)


def _attended(cfg: dict, ctx) -> np.ndarray:
    """Keys one query at context length ``ctx`` (itself included) attends."""
    ctx = np.asarray(ctx, np.int64)
    w = cfg.get("sliding_window") or 0
    return np.minimum(ctx, w) if w else ctx


def _attn_flops_per_pair(cfg: dict) -> int:
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * cfg["num_hidden_layers"]


def decode_step(cfg: dict, ctx, weight_bytes: int = 2, kv_elem_bytes: int = 2) -> dict:
    """One decode step over the active rows; ``ctx[i]`` is row i's context
    length including the token being decoded."""
    ctx = np.asarray(ctx, np.int64)
    rows = int(ctx.size)
    body = cfg["num_hidden_layers"] * layer_params(cfg) + head_params(cfg)
    attended = int(_attended(cfg, ctx).sum())
    flops = 2 * body * rows + _attn_flops_per_pair(cfg) * attended
    kv = kv_bytes_per_token(cfg, kv_elem_bytes)
    embed_rows = rows * cfg["hidden_size"] * weight_bytes
    bytes_ = body * weight_bytes + embed_rows + kv * attended + kv * rows
    return {"flops": float(flops), "bytes": float(bytes_)}


def prefill(cfg: dict, length: int) -> dict:
    """One prompt of ``length`` tokens at its true length: every layer over
    every position, logits at the last one."""
    attended = int(_attended(cfg, np.arange(1, length + 1)).sum())
    body = cfg["num_hidden_layers"] * layer_params(cfg)
    flops = 2 * body * length + 2 * head_params(cfg) + _attn_flops_per_pair(cfg) * attended
    return {"flops": float(flops)}
