"""Plain reference of DeepSeek-V2's decoder, in ``jax.numpy``.

It follows the published description (arXiv:2405.04434) and the modelling
code published with DeepSeek-V2-Lite (``modeling_deepseek.py``):

    x = embed_tokens[tokens]
    for each layer:
        h = n1(x)
        q = h . q_proj                       (no query compression)
        c, k_pe = split(h . kv_a_proj_with_mqa)
        k_nope, v = split(n_kv(c) . kv_b_proj)
        q_pe, k_pe = YaRN-RoPE(q_pe, k_pe)   (k_pe shared by every head)
        x = x + o_proj . softmax(scale * [q_nope, q_pe] . [k_nope, k_pe], causal) . v
        h = n2(x)
        x = x + (dense layer) down(silu(gate(h)) * up(h))
              | (MoE layer)   sum over the top-k experts e of w_e * expert_e(h) + shared(h)
    logits = lm_head . n(x)

with ``n(x) = x / sqrt(mean(x^2) + eps) * w`` (RMSNorm).  RoPE, as published:
the rotary columns come in adjacent pairs, which the code de-interleaves
(``view(d/2, 2).transpose``) before rotating half against half; YaRN's
frequencies blend interpolated and original ones over a ramp of dimensions,
cos and sin carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
and the softmax scale ``qk_dim ** -0.5 * mscale(factor, mscale_all_dim) **
2``.  The router: logits in float32 over every routed expert, softmax, the
top ``num_experts_per_tok``, renormalised only with ``norm_topk_prob``, else
scaled by ``routed_scaling_factor``.

One sequence at a time, no cache and no batching, float32 throughout under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program.  It runs layer by layer (one jitted function per kind of layer,
indexed into the stacked weights), attends in blocks of query rows, and
runs the experts one after another; none of that changes the arithmetic.

Departures: the weights are random, made by the benchmark from the seed
(``bench/weights.py``), and the norm weights are stored as published
(around 1).  Of the routed experts only the chip's share is present
(``held_first``, ``held_experts``), as in the program: an assignment to an
expert outside it adds nothing, while the router still ranks all of them.

``control=True`` is the comparison's control: the same computation with
both operands of every matrix product rounded to float8 e4m3 (per-row
scales for activations, per-column scales for weights), the step below the
bf16 the configuration states.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn
Q_BLOCK = 512  # query rows attended at once
LENGTH_STEP = Q_BLOCK  # sequences pad to a multiple of this (fewer compiles)


def _f8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if control:
        x, w = _f8(x, -1), _f8(w, 0)
    return x @ w


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(
        jnp.float32
    )


def yarn_get_mscale(scale, mscale):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _yarn_find_correction_dim(num_rotations, dim, base, max_position_embeddings):
    return (dim * math.log(max_position_embeddings / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base))


def yarn_inv_freq(dim, base, rs):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies."""
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (rs["factor"] * base ** exps)
    orig = rs["original_max_position_embeddings"]
    low = max(math.floor(_yarn_find_correction_dim(rs["beta_fast"], dim, base, orig)), 0)
    high = min(math.ceil(_yarn_find_correction_dim(rs["beta_slow"], dim, base, orig)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def rope(x, positions, cfg):
    """x [T, heads, d], its columns in adjacent pairs as published; returns
    the rotated [T, heads, d] in the de-interleaved order the published code
    leaves them in."""
    d = x.shape[-1]
    rs = dict(cfg["rope_scaling"]) if cfg["rope_scaling"] else None
    if rs:
        inv = yarn_inv_freq(d, cfg["rope_theta"], rs)
        m = yarn_get_mscale(rs["factor"], rs["mscale"]) / yarn_get_mscale(
            rs["factor"], rs["mscale_all_dim"])
    else:
        inv, m = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)), 1.0
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = dict(cfg["rope_scaling"]) if cfg["rope_scaling"] else None
    if rs and rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


def attention(q, k, v, scale):
    """q, k [T, H, dq], v [T, H, dv] -> [T, H, dv]; causal."""
    t, h, _ = q.shape
    key_pos = jnp.arange(t)

    def block(q_blk, start):
        q_pos = start + jnp.arange(q_blk.shape[0])
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        s = jnp.where((key_pos[None, :] <= q_pos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    n = t // Q_BLOCK if t % Q_BLOCK == 0 and t > Q_BLOCK else 1
    if n == 1:
        return block(q, 0)
    qb = q.reshape((n, Q_BLOCK) + q.shape[1:])
    out = jax.lax.map(lambda a: block(a[0], a[1]), (qb, jnp.arange(n) * Q_BLOCK))
    return out.reshape((t,) + out.shape[2:])


def _swiglu(h, mlp, control):
    return _mm(jax.nn.silu(_mm(h, mlp["gate_proj"], control)) * _mm(h, mlp["up_proj"], control),
               mlp["down_proj"], control)


def _moe(h, mlp, cfg, control):
    t = h.shape[0]
    probs = jax.nn.softmax(_mm(h, mlp["gate"], control), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["num_experts_per_tok"] > 1 and cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    else:
        top_w = top_w * cfg["routed_scaling_factor"]
    weight = jnp.zeros((t, cfg["n_routed_experts"]), jnp.float32)
    weight = weight.at[jnp.arange(t)[:, None], top_i].set(top_w)
    first, held = cfg["held_first"], cfg["held_experts"]
    weight = weight[:, first:first + held]  # [T, held]

    def expert(y, ew):
        e, w_e = ew
        return y + w_e[:, None] * _swiglu(h, e, control), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (mlp["experts"], weight.T))
    if cfg["n_shared_experts"]:
        y = y + _swiglu(h, mlp["shared_experts"], control)
    return y


@partial(jax.jit, static_argnames=("cfg_items", "control", "moe"))
def _layer(x, group, i, *, cfg_items, control, moe):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        lw = jax.tree.map(lambda a: a[i], group)
        attn = lw["self_attn"]
        t = x.shape[0]
        nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        eps = cfg["rms_norm_eps"]
        pos = jnp.arange(t)
        h = rms_norm(x, lw["input_layernorm"], eps)
        q = _mm(h, attn["q_proj"], control).reshape(t, nh, nope + rp)
        ckv = _mm(h, attn["kv_a_proj_with_mqa"], control)
        c, k_pe = ckv[:, :r], ckv[:, r:]
        kv = _mm(rms_norm(c, attn["kv_a_layernorm"], eps), attn["kv_b_proj"], control)
        kv = kv.reshape(t, nh, nope + dv)
        q_pe = rope(q[..., nope:], pos, cfg)
        k_pe = rope(k_pe[:, None, :], pos, cfg)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (t, nh, rp))], axis=-1)
        a = attention(q, k, kv[..., nope:], softmax_scale(cfg)).reshape(t, nh * dv)
        x = x + _mm(a, attn["o_proj"], control)
        h = rms_norm(x, lw["post_attention_layernorm"], eps)
        return x + (_moe(h, lw["mlp"], cfg, control) if moe else _swiglu(h, lw["mlp"], control))


@partial(jax.jit, static_argnames=("cfg_items", "control"))
def _head(x, norm, lm_head, rows, *, cfg_items, control):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return _mm(rms_norm(x[rows], norm, cfg["rms_norm_eps"]), lm_head, control)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
                        for k, v in cfg.items()))


def logits_at(w: dict, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
              control: bool = False) -> jax.Array:
    """Logits [len(rows), vocab] (float32, on the device) of the sequence
    ``tokens`` at positions ``rows``: the full causal forward of the
    sequence alone.  Padding at the end (to a multiple of ``LENGTH_STEP``)
    cannot reach earlier positions, so it changes nothing."""
    t = len(tokens)
    tp = -(-t // LENGTH_STEP) * LENGTH_STEP
    padded = np.zeros(tp, np.int32)
    padded[:t] = tokens
    items = _items(cfg)
    x = _embed(w["embed_tokens"], jnp.asarray(padded))
    n_dense = cfg["first_k_dense_replace"]
    for i in range(cfg["num_hidden_layers"]):
        moe = i >= n_dense
        group = w["moe_layers"] if moe else w["dense_layers"]
        x = _layer(x, group, jnp.int32(i - n_dense if moe else i), cfg_items=items,
                   control=control, moe=moe)
    return _head(x, w["norm"], w["lm_head"], jnp.asarray(rows, jnp.int32), cfg_items=items,
                 control=control)
