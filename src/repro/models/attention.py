"""Attention: GQA (with qk-norm, RoPE, sliding window) and MLA.

Three execution paths:
  * train/prefill: blockwise attention over query chunks (bounded VMEM/HBM
    footprint at 32k contexts) — the XLA reference path; the Pallas flash
    kernel (``repro.kernels.flash_attention``) implements the same math for
    TPU and is validated against it.
  * decode: single-token attention against a KV cache.  Sliding-window
    layers keep a ring buffer of ``window`` entries (O(window) memory at
    524k contexts); full-attention layers keep the whole context.  Decode
    caches are layer-stacked (a leading layer axis on every leaf); a GQA
    cache holds K and V side by side in one head-major ``[r, B, Kv, L, 2*D]``
    array, the layout the decode einsums read.  Each step writes one entry
    per row into its layer in place and reads K and V straight out of the
    stack (``layer_slice``), so no layer is copied out or written back.
  * MLA decode uses the absorbed formulation and caches only the latent
    KV and the decoupled RoPE key, side by side in one ``[r, B, L, rank +
    rope]`` array — the compression that makes MiniCPM3 and DeepSeek-V2
    cheap.  The layers read the stack without writing it: each attends to
    its new entry beside the cached ones, and the entries are written after
    the last layer (``stage_latent`` / ``flush_latent``).  The scores,
    softmax and output run under the named scope ``mla.attend``.

Caches are dicts of arrays so they stack cleanly under ``lax.scan``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Initializer, apply_rope, dense_init, rms_norm, rope_attention_scale

__all__ = [
    "attention_init",
    "attention_apply",
    "init_attention_cache",
    "mla_init",
    "mla_apply",
    "init_mla_cache",
    "stage_latent",
    "flush_latent",
    "blockwise_attention",
]

NEG_INF = -1e30


# --------------------------------------------------------------------------
# core masked attention (shared by train / prefill / decode)
# --------------------------------------------------------------------------


def _gqa_scores(q, k, kv_spec):
    """q [B,Sq,H,D], k [B,Sk,Kv,D] (``kv_spec`` "bskd") or head-major
    [B,Kv,Sk,D] ("bksd") -> scores [B,Kv,G,Sq,Sk] (G = H // Kv)."""
    b, sq, h, d = q.shape
    kv = k.shape[kv_spec.index("k")]
    q = q.reshape(b, sq, kv, h // kv, d)
    return jnp.einsum(f"bqkgd,{kv_spec}->bkgqs", q, k, preferred_element_type=jnp.float32)


def _gqa_out(probs, v, kv_spec):
    """probs [B,Kv,G,Sq,Sk], v laid out as ``kv_spec`` -> out [B,Sq,H,D].

    probs arrive in the compute dtype (bf16 on TPU) — storing fp32
    probabilities doubles the dominant HBM stream of the XLA attention
    path; accumulation stays fp32 via preferred_element_type."""
    b, kv, g, sq, sk = probs.shape
    if kv_spec == "bskd":
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, preferred_element_type=jnp.float32)
    else:  # XLA-CPU has no bf16 x bf16 -> f32 dot with the output in that order
        out = jnp.einsum(
            f"bkgqs,{kv_spec}->bkgqd", probs, v, preferred_element_type=jnp.float32
        ).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, sq, kv * g, v.shape[-1])


def masked_attention(q, k, v, mask, scale, *, heads_first: bool = False):
    """Softmax attention with additive mask; fp32 softmax reduction, compute-
    dtype probabilities (the Pallas flash kernel keeps them in VMEM only).

    k, v are [B, Sk, Kv, D], or head-major [B, Kv, Sk, D] (``heads_first``,
    the decode cache's layout).
    mask: broadcastable to [B, 1, 1, Sq, Sk] boolean (True = attend).
    """
    kv_spec = "bksd" if heads_first else "bskd"
    scores = _gqa_scores(q, k, kv_spec) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = _gqa_out(probs, v, kv_spec)
    return out.astype(q.dtype)


def layer_slice(stack, layer, lo: int = 0, size: int | None = None):
    """Layer ``layer`` of a layer-stacked cache leaf, optionally only the
    entries ``[lo, lo + size)`` of its last axis.  Give each consumer its own
    slice: the compiler then reads it inside the consumer's fusion, straight
    from the stack, where a slice shared by two consumers is copied out."""
    size = stack.shape[-1] - lo if size is None else size
    start = (layer,) + (0,) * (stack.ndim - 2) + (lo,)
    shape = (1,) + stack.shape[1:-1] + (size,)
    return jax.lax.dynamic_slice(stack, start, shape, allow_negative_indices=False)[0]


def _pos_write(cache_pos, layer, pos, slot):
    """Record each row's new position at its ring slot of layer ``layer`` of
    ``cache_pos`` [r, B, L]: a select over the layer's (int32, one entry per
    slot) positions, where ``ring_write`` would be one op a row."""
    length = cache_pos.shape[-1]
    hit = jnp.arange(length)[None] == jnp.broadcast_to(slot, pos.shape)[:, None]
    row = jnp.where(hit, pos[:, None].astype(jnp.int32), layer_slice(cache_pos, layer))
    return jax.lax.dynamic_update_index_in_dim(cache_pos, row, layer, 0)


def ring_write(cache, new, layer, slot, axis: int):
    """Write ``new`` (one layer's entries, batch on axis 0, size 1 along
    ``axis``) into layer ``layer`` of the layer-stacked ``cache`` at ring
    ``slot`` along ``axis`` of the layer, in place.

    A scalar ``slot`` (lockstep decode) writes the whole batch with one
    ``dynamic_update_slice``; a ``slot`` [B] (continuous batching: every row
    at its own position) writes each row with its own, the same form on
    every backend (XLA-CPU would promote a bf16 scatter to fp32 and rewrite
    the cache)."""
    new = new.astype(cache.dtype)[None]
    start = [layer] + [0] * (cache.ndim - 1)
    if jnp.ndim(slot) == 0:
        start[axis + 1] = slot
        return jax.lax.dynamic_update_slice(cache, new, start, allow_negative_indices=False)
    for b in range(cache.shape[1]):
        start[1], start[axis + 1] = b, jax.lax.index_in_dim(slot, b, keepdims=False)
        row = jax.lax.slice_in_dim(new, b, b + 1, axis=1)
        cache = jax.lax.dynamic_update_slice(cache, row, start, allow_negative_indices=False)
    return cache


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
    """KV cache for one attention layer: K and V side by side along the last
    axis of one head-major ``[B, Kv, L, 2*D]`` array, so a decode step writes
    both with one op a row (decode stacks one per layer, ``init_stack_cache``).
    SWA layers use a ring buffer."""
    h = cfg.head_dim
    length = seq_len
    if cfg.attn_type == "swa" and cfg.sliding_window:
        length = min(seq_len, cfg.sliding_window)
    return {
        "kv": jnp.zeros((batch, cfg.n_kv_heads, length, 2 * h), dtype),
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
    layer=None,
):
    """Returns (out [B,S,D], new_cache).  A decode ``cache`` is layer-stacked
    and this call reads and writes its layer ``layer``."""
    compute = x.dtype
    b, s, _ = x.shape
    h = cfg.head_dim
    q, k, v = (x @ params[w].astype(compute) for w in ("w_q", "w_k", "w_v"))
    if cache is not None:
        # decode: a barrier keeps each projection in the layout of its dot.
        # Without it the TPU compiler picks the layout that makes the head
        # split below free, and to get it copies every layer's weight into
        # a transposed layout on every step.
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    q = q.reshape(b, s, cfg.n_heads, h)
    k = k.reshape(b, s, cfg.n_kv_heads, h)
    v = v.reshape(b, s, cfg.n_kv_heads, h)
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
        # decode: s == 1, write into (ring) cache then attend.  Lockstep mode
        # (``ragged=False``, the one-shot ServingEngine contract) advances
        # the batch together, so all rows share one ring slot; ragged mode
        # (continuous batching) writes each row at its own slot.
        assert s == 1, "decode path expects a single new token"
        pos = positions[:, 0]  # [B]
        slot = (pos % cache["kv"].shape[3]).astype(jnp.int32)
        if not ragged:
            slot = slot[0]
        new_kv = jnp.concatenate([k, v], axis=-1).swapaxes(1, 2)  # [B, Kv, 1, 2D]
        ckv = ring_write(cache["kv"], new_kv, layer, slot, axis=2)
        cpos = _pos_write(cache["pos"], layer, pos, slot)
        delta = pos[:, None] - layer_slice(cpos, layer)  # [B, L]
        valid = (delta >= 0) & (layer_slice(cpos, layer) >= 0)
        if window > 0:
            valid &= delta < window
        mask = valid[:, None, None, None, :]  # [B,1,1,1,L]
        k_l = layer_slice(ckv, layer, 0, h).astype(compute)
        v_l = layer_slice(ckv, layer, h, h).astype(compute)
        out = masked_attention(q, k_l, v_l, mask, scale, heads_first=True)
        new_cache = {"kv": ckv, "pos": cpos}

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
    if m.q_lora_rank:
        params = {
            "w_dq": dense_init(init, (d, m.q_lora_rank), dtype),
            "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
            "w_uq": dense_init(init, (m.q_lora_rank, nh * qk), dtype),
        }
        axes = {"w_dq": ("embed", None), "q_norm": (None,), "w_uq": (None, "heads")}
    else:  # no query compression (DeepSeek-V2-Lite)
        params = {"w_q": dense_init(init, (d, nh * qk), dtype)}
        axes = {"w_q": ("embed", "heads")}
    params.update({
        "w_dkv": dense_init(init, (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(init, (m.kv_lora_rank, nh * m.qk_nope_head_dim), dtype),
        "w_uv": dense_init(init, (m.kv_lora_rank, nh * m.v_head_dim), dtype),
        "w_o": dense_init(init, (nh * m.v_head_dim, d), dtype),
    })
    axes.update({
        "w_dkv": ("embed", None),
        "kv_norm": (None,),
        "w_uk": (None, "heads"),
        "w_uv": (None, "heads"),
        "w_o": ("heads", "embed"),
    })
    return params, axes


def init_mla_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    """The latent cache of one MLA layer: the normalised latent KV and the
    rotated shared key side by side in one ``[B, L, rank + rope]`` array, so
    a row's entry is one write."""
    m = cfg.mla
    return {
        "latent": jnp.zeros((batch, seq_len, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, seq_len), -1, jnp.int32),
    }


def stage_latent(block_cache: dict) -> dict:
    """Before a decode pass over a layer-stacked MLA cache: room for each
    layer's new entry (``"new"`` [r, B, 1, rank + rope]).  The layers read
    the stack and leave it as it is; ``flush_latent`` writes the entries
    after the last layer.  Written inside the loop over the layers, one row
    at a time, the entries make the TPU compiler lay the stack out with the
    ring axis outside the rows and relay the whole pool on entry and exit;
    read-only in the loop it keeps the pool's own layout."""
    mixer = block_cache.get("mixer")
    if not isinstance(mixer, dict) or "latent" not in mixer:
        return block_cache
    lat = mixer["latent"]
    new = jnp.zeros(lat.shape[:2] + (1,) + lat.shape[3:], lat.dtype)
    return {**block_cache, "mixer": {**mixer, "new": new}}


def flush_latent(block_cache: dict, pos, ragged: bool) -> dict:
    """After a decode pass: each layer's staged entry into its ring slot of
    the stack, in place, and its position beside it (``stage_latent``)."""
    mixer = block_cache.get("mixer")
    if not isinstance(mixer, dict) or "new" not in mixer:
        return block_cache
    lat, cpos, new = mixer["latent"], mixer["pos"], mixer["new"]
    slot = (pos % lat.shape[2]).astype(jnp.int32)
    if not ragged:  # lockstep: one shared slot (see attention_apply)
        slot = slot[0]
    for layer in range(lat.shape[0]):
        lat = ring_write(lat, new[layer], layer, slot, axis=1)
        cpos = _pos_write(cpos, layer, pos, slot)
    return {**block_cache, "mixer": {"latent": lat, "pos": cpos}}


def _mla_rope(x, positions, cfg):
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def _mla_qkv(params, x, cfg, positions):
    m = cfg.mla
    compute = x.dtype
    b, s, _ = x.shape
    nh = cfg.n_heads
    if m.q_lora_rank:
        cq = rms_norm(x @ params["w_dq"].astype(compute), params["q_norm"], cfg.norm_eps)
        q = cq @ params["w_uq"].astype(compute)
    else:
        q = x @ params["w_q"].astype(compute)
    q = q.reshape(b, s, nh, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_rope = _mla_rope(q_rope, positions, cfg)

    dkv = x @ params["w_dkv"].astype(compute)
    ckv = rms_norm(dkv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = _mla_rope(dkv[..., None, m.kv_lora_rank :], positions, cfg)[:, :, 0]
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
    layer=None,
):
    m = cfg.mla
    compute = x.dtype
    b, s, _ = x.shape
    nh = cfg.n_heads
    rank = m.kv_lora_rank
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling is not None:
        scale *= rope_attention_scale(cfg.rope_scaling)
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
            new_cache = {"latent": jnp.concatenate([ckv, k_rope], axis=-1),
                         "pos": positions.astype(jnp.int32)}
    else:
        # absorbed decode over layer ``layer`` of the stack, which it only
        # reads: score = [q_nope W_uk^T, q_rope] . [ckv, k_rope] over the
        # cached entries, the one this step overwrites left out, and over the
        # new entry itself, staged for ``flush_latent``
        assert s == 1
        pos = positions[:, 0]
        lat = cache["latent"]
        new = jnp.concatenate([ckv, k_rope], axis=-1)  # [B, 1, rank + rope]
        staged = jax.lax.dynamic_update_index_in_dim(
            cache["new"], new.astype(lat.dtype), layer, 0)
        own_entry = new[:, 0].astype(lat.dtype).astype(compute)  # as the cache will hold it
        ring = jnp.arange(lat.shape[2])
        slot = jnp.broadcast_to(pos % lat.shape[2], pos.shape)
        lpos = layer_slice(cache["pos"], layer)  # [B, L]
        valid = (lpos >= 0) & (pos[:, None] >= lpos) & (ring[None] != slot[:, None])
        w_uk = params["w_uk"].astype(compute).reshape(rank, nh, m.qk_nope_head_dim)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)  # [B,H,rank]
        q_full = jnp.concatenate([q_lat, q_rope[:, 0].astype(compute)], axis=-1)
        with jax.named_scope("mla.attend"):
            scores = jnp.einsum("bhc,blc->bhl", q_full, layer_slice(lat, layer).astype(compute),
                                preferred_element_type=jnp.float32)
            scores = jnp.where(valid[:, None, :], scores * scale, NEG_INF)
            own = jnp.einsum("bhc,bc->bh", q_full, own_entry,
                             preferred_element_type=jnp.float32)[..., None] * scale
            top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), own)
            e, e_own = jnp.exp(scores - top), jnp.exp(own - top)
            denom = jnp.sum(e, axis=-1, keepdims=True) + e_own
            o_lat = jnp.einsum(  # [B,H,rank]
                "bhl,blr->bhr", (e / denom).astype(compute),
                layer_slice(lat, layer, 0, rank).astype(compute),
                preferred_element_type=jnp.float32)
            o_lat = o_lat + (e_own / denom) * own_entry[:, None, :rank].astype(jnp.float32)
        w_uv = params["w_uv"].astype(compute).reshape(rank, nh, m.v_head_dim)
        out = jnp.einsum("bhr,rhd->bhd", o_lat.astype(compute), w_uv)[:, None]
        new_cache = {**cache, "new": staged}

    out = out.reshape(b, s, nh * m.v_head_dim).astype(compute)
    return out @ params["w_o"].astype(compute), new_cache
