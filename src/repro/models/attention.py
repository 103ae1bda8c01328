"""Attention: GQA (with qk-norm, RoPE, sliding window) and MLA.

Three execution paths:
  * train/prefill: blockwise attention over query chunks (bounded VMEM/HBM
    footprint at 32k contexts) — the XLA reference path; the Pallas flash
    kernel (``repro.kernels.flash_attention``) implements the same math for
    TPU and is validated against it.
  * decode: single-token attention against a KV cache.  Sliding-window
    layers keep a ring buffer of ``window`` entries (O(window) memory at
    524k contexts); full-attention layers keep the whole context.
  * MLA decode uses the absorbed formulation and caches only the latent
    KV (+ decoupled RoPE keys) — the compression that makes MiniCPM3 cheap.

Caches are dicts of arrays so they stack cleanly under ``lax.scan``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Initializer, apply_rope, dense_init, rms_norm

__all__ = [
    "attention_init",
    "attention_apply",
    "init_attention_cache",
    "mla_init",
    "mla_apply",
    "init_mla_cache",
    "blockwise_attention",
]

NEG_INF = -1e30


# --------------------------------------------------------------------------
# core masked attention (shared by train / prefill / decode)
# --------------------------------------------------------------------------


def _gqa_scores(q, k):
    """q [B,Sq,H,D], k [B,Sk,Kv,D] -> scores [B,Kv,G,Sq,Sk] (G = H // Kv)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, sq, kv, h // kv, d)
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)


def _gqa_out(probs, v):
    """probs [B,Kv,G,Sq,Sk], v [B,Sk,Kv,D] -> out [B,Sq,H,D].

    probs arrive in the compute dtype (bf16 on TPU) — storing fp32
    probabilities doubles the dominant HBM stream of the XLA attention
    path; accumulation stays fp32 via preferred_element_type."""
    b, kv, g, sq, sk = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, preferred_element_type=jnp.float32)
    return out.reshape(b, sq, kv * g, v.shape[-1])


def masked_attention(q, k, v, mask, scale):
    """Softmax attention with additive mask; fp32 softmax reduction, compute-
    dtype probabilities (the Pallas flash kernel keeps them in VMEM only).

    mask: broadcastable to [B, 1, 1, Sq, Sk] boolean (True = attend).
    """
    scores = _gqa_scores(q, k) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = _gqa_out(probs, v)
    return out.astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, window: int, q_offset, scale, q_chunk: int = 4096):
    # default q_chunk=4096: §Perf iteration showed the chunk-scan's stacked
    # ys buffers cost ~1.4x extra HBM traffic at 4k training shapes; longer
    # contexts (32k prefill) still chunk to bound live score memory
    """Scan over query chunks against the full key range.

    Bounds the live score tensor to [B, Kv, G, q_chunk, Sk].  ``q_offset``
    is the absolute position of q[0] (prefill continuation / chunked
    serving).  ``window`` <= 0 means full causal attention.  The value head
    dim may differ from the query head dim (MLA).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]
    if sq <= q_chunk:
        return _chunk_attn(q, k, v, jnp.asarray(q_offset), causal, window, scale, sk)
    n_chunks = sq // q_chunk
    rem = sq - n_chunks * q_chunk
    qs = q[:, : n_chunks * q_chunk].reshape(b, n_chunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    offs = jnp.asarray(q_offset) + jnp.arange(n_chunks) * q_chunk

    def step(carry, xs):
        qc, off = xs
        return carry, _chunk_attn(qc, k, v, off, causal, window, scale, sk)

    _, outs = jax.lax.scan(step, None, (qs, offs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * q_chunk, h, dv)
    if rem:
        tail = _chunk_attn(
            q[:, n_chunks * q_chunk :], k, v, jnp.asarray(q_offset) + n_chunks * q_chunk,
            causal, window, scale, sk,
        )
        out = jnp.concatenate([out, tail], axis=1)
    return out


def _chunk_attn(qc, k, v, off, causal, window, scale, sk):
    sq = qc.shape[1]
    q_pos = off + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    return masked_attention(qc, k, v, mask[None, None, None], scale)


# --------------------------------------------------------------------------
# GQA layer
# --------------------------------------------------------------------------


def attention_init(init: Initializer, cfg: ModelConfig, dtype):
    d, h = cfg.d_model, cfg.head_dim
    params = {
        "w_q": dense_init(init, (d, cfg.n_heads * h), dtype),
        "w_k": dense_init(init, (d, cfg.n_kv_heads * h), dtype),
        "w_v": dense_init(init, (d, cfg.n_kv_heads * h), dtype),
        "w_o": dense_init(init, (cfg.n_heads * h, d), dtype),
    }
    axes = {
        "w_q": ("embed", "heads"),
        "w_k": ("embed", "heads"),
        "w_v": ("embed", "heads"),
        "w_o": ("heads", "embed"),
    }
    if cfg.qk_norm:
        params["q_norm"] = jnp.zeros((h,), dtype)
        params["k_norm"] = jnp.zeros((h,), dtype)
        axes["q_norm"] = (None,)
        axes["k_norm"] = (None,)
    return params, axes


def init_attention_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    """KV cache for one attention layer.  SWA layers use a ring buffer."""
    h = cfg.head_dim
    length = seq_len
    if cfg.attn_type == "swa" and cfg.sliding_window:
        length = min(seq_len, cfg.sliding_window)
    return {
        "k": jnp.zeros((batch, length, cfg.n_kv_heads, h), dtype),
        "v": jnp.zeros((batch, length, cfg.n_kv_heads, h), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


def attention_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # [B, S] absolute positions
    cache: dict | None = None,
    update_cache: bool = False,
    impl: str = "xla",
    ragged: bool = False,
):
    """Returns (out [B,S,D], new_cache)."""
    compute = x.dtype
    b, s, _ = x.shape
    h = cfg.head_dim
    q = (x @ params["w_q"].astype(compute)).reshape(b, s, cfg.n_heads, h)
    k = (x @ params["w_k"].astype(compute)).reshape(b, s, cfg.n_kv_heads, h)
    v = (x @ params["w_v"].astype(compute)).reshape(b, s, cfg.n_kv_heads, h)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    scale = h**-0.5
    window = cfg.sliding_window if cfg.attn_type == "swa" else 0

    if cache is None:
        # train / prefill over the full sequence
        if impl == "pallas":
            from ..kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
        else:
            out = blockwise_attention(
                q, k, v, causal=True, window=window, q_offset=0, scale=scale
            )
        new_cache = None
        if update_cache:
            new_cache = {
                "k": k,
                "v": v,
                "pos": positions.astype(jnp.int32),
            }
    else:
        # decode: s == 1, write into (ring) cache then attend.
        #
        # Lockstep mode (``ragged=False``, the one-shot ServingEngine
        # contract): the batch advances together, so the write is one
        # dynamic_update_slice at a scalar slot — a scatter here gets
        # promoted to fp32 by XLA-CPU float normalization, materialising
        # fp32 copies of the whole cache.
        #
        # Ragged mode (continuous batching): every row sits at its own
        # absolute position, so each row writes its own ring slot.  A
        # per-row one-hot select keeps it a fusable select (not a scatter,
        # which hits the same fp32-normalization trap as above).
        assert s == 1, "decode path expects a single new token"
        pos = positions[:, 0]  # [B]
        length = cache["k"].shape[1]
        if ragged:
            hit = (pos[:, None] % length) == jnp.arange(length)[None]  # [B, L]
            ck = jnp.where(hit[:, :, None, None], k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hit[:, :, None, None], v.astype(cache["v"].dtype), cache["v"])
            cpos = jnp.where(hit, pos[:, None].astype(jnp.int32), cache["pos"])
        else:
            slot = (pos[0] % length).astype(jnp.int32)
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1
            )
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], pos[:, None].astype(jnp.int32), slot, axis=1
            )
        delta = pos[:, None] - cpos  # [B, L]
        valid = (cpos >= 0) & (delta >= 0)
        if window > 0:
            valid &= delta < window
        mask = valid[:, None, None, None, :]  # [B,1,1,1,L]
        # the barrier pins any dtype conversion of the cache *inside* the
        # layer scan: without it XLA hoists convert(dynamic-slice(xs)) into
        # dynamic-slice(convert(xs)), materialising an fp32 copy of the
        # full multi-layer KV cache
        ku, vu = jax.lax.optimization_barrier((ck, cv))
        out = masked_attention(q, ku.astype(compute), vu.astype(compute), mask, scale)
        new_cache = {"k": ck, "v": cv, "pos": cpos}

    out = out.reshape(b, s, cfg.n_heads * h)
    return out @ params["w_o"].astype(compute), new_cache


# --------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3 / DeepSeek-V2 style)
# --------------------------------------------------------------------------


def mla_init(init: Initializer, cfg: ModelConfig, dtype):
    m = cfg.mla
    d = cfg.d_model
    nh = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    params = {
        "w_dq": dense_init(init, (d, m.q_lora_rank), dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "w_uq": dense_init(init, (m.q_lora_rank, nh * qk), dtype),
        "w_dkv": dense_init(init, (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(init, (m.kv_lora_rank, nh * m.qk_nope_head_dim), dtype),
        "w_uv": dense_init(init, (m.kv_lora_rank, nh * m.v_head_dim), dtype),
        "w_o": dense_init(init, (nh * m.v_head_dim, d), dtype),
    }
    axes = {
        "w_dq": ("embed", None),
        "q_norm": (None,),
        "w_uq": (None, "heads"),
        "w_dkv": ("embed", None),
        "kv_norm": (None,),
        "w_uk": (None, "heads"),
        "w_uv": (None, "heads"),
        "w_o": ("heads", "embed"),
    }
    return params, axes


def init_mla_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, seq_len), -1, jnp.int32),
    }


def _mla_qkv(params, x, cfg, positions):
    m = cfg.mla
    compute = x.dtype
    b, s, _ = x.shape
    nh = cfg.n_heads
    cq = rms_norm(x @ params["w_dq"].astype(compute), params["q_norm"], cfg.norm_eps)
    q = (cq @ params["w_uq"].astype(compute)).reshape(
        b, s, nh, m.qk_nope_head_dim + m.qk_rope_head_dim
    )
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = x @ params["w_dkv"].astype(compute)
    ckv = rms_norm(dkv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(dkv[..., None, m.kv_lora_rank :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: dict | None = None,
    update_cache: bool = False,
    impl: str = "xla",
    ragged: bool = False,
):
    m = cfg.mla
    compute = x.dtype
    b, s, _ = x.shape
    nh = cfg.n_heads
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, x, cfg, positions)

    if cache is None:
        # expanded formulation for the parallel (train/prefill) pass
        k_nope = (ckv @ params["w_uk"].astype(compute)).reshape(b, s, nh, m.qk_nope_head_dim)
        v = (ckv @ params["w_uv"].astype(compute)).reshape(b, s, nh, m.v_head_dim)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)], axis=-1)
        out = blockwise_attention(q, k, v, causal=True, window=0, q_offset=0, scale=scale)
        new_cache = None
        if update_cache:
            new_cache = {"ckv": ckv, "k_rope": k_rope, "pos": positions.astype(jnp.int32)}
    else:
        # absorbed decode: score = q_nope W_uk^T . ckv + q_rope . k_rope
        assert s == 1
        pos = positions[:, 0]
        length = cache["ckv"].shape[1]
        if ragged:
            # per-row ring slot (continuous batching) — see attention_apply
            hit = (pos[:, None] % length) == jnp.arange(length)[None]  # [B, L]
            cckv = jnp.where(hit[:, :, None], ckv.astype(cache["ckv"].dtype), cache["ckv"])
            ckrope = jnp.where(
                hit[:, :, None], k_rope.astype(cache["k_rope"].dtype), cache["k_rope"]
            )
            cpos = jnp.where(hit, pos[:, None].astype(jnp.int32), cache["pos"])
        else:
            slot = (pos[0] % length).astype(jnp.int32)
            cckv = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), slot, axis=1
            )
            ckrope = jax.lax.dynamic_update_slice_in_dim(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), slot, axis=1
            )
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], pos[:, None].astype(jnp.int32), slot, axis=1
            )
        w_uk = params["w_uk"].astype(compute).reshape(m.kv_lora_rank, nh, m.qk_nope_head_dim)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)  # [B,1,H,rank]
        scores = jnp.einsum(
            "bshr,blr->bhsl", q_lat, cckv.astype(compute), preferred_element_type=jnp.float32
        ) + jnp.einsum(
            "bshd,bld->bhsl", q_rope, ckrope.astype(compute), preferred_element_type=jnp.float32
        )
        valid = (cpos >= 0) & (pos[:, None] >= cpos)
        scores = jnp.where(valid[:, None, None, :], scores * scale, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhsl,blr->bshr", probs, cckv.astype(jnp.float32))  # [B,1,H,rank]
        w_uv = params["w_uv"].astype(compute).reshape(m.kv_lora_rank, nh, m.v_head_dim)
        out = jnp.einsum("bshr,rhd->bshd", o_lat.astype(compute), w_uv)
        new_cache = {"ckv": cckv, "k_rope": ckrope, "pos": cpos}

    out = out.reshape(b, s, nh * m.v_head_dim).astype(compute)
    return out @ params["w_o"].astype(compute), new_cache
