"""Plain reference of a decoder-only GQA transformer, in ``jax.numpy``.

It follows the published description of both configurations (InternLM2,
arXiv:2403.17297; H2O-Danube, arXiv:2401.16818, Mistral layout):

    x = embed[tokens]
    for each layer:
        h = x + W_o . attention(RoPE(W_q . n1(x)), RoPE(W_k . n1(x)), W_v . n1(x))
        x = h + W_down . (silu(W_gate . n2(h)) * (W_up . n2(h)))
    logits = W_head . n(x)

with ``n(x) = x / sqrt(mean(x^2) + eps) * w`` (RMSNorm), rotate-half RoPE,
grouped-query attention (query head ``i`` reads KV head ``i // (H / KV)``),
causal masking, and for a ``sliding_window`` W a query at position p sees
keys p - W < k <= p.

One sequence at a time, no cache and no batching, float32 throughout under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program.  To fit one chip at the served sizes it runs layer by layer (one
jitted layer function, indexed into the stacked weights) and attends in
blocks of query rows; neither changes the arithmetic.

Departures: none in the mathematics.  The weights are random, made by the
benchmark from the seed (``bench/weights.py``), and the norm weights are
stored as published (around 1).

``control=True`` is the comparison's control: the same computation with
both operands of every matrix product rounded to float8 e4m3 (per-row
scales for activations, per-column scales for weights), the step below the
bf16 the configurations state.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn
Q_BLOCK = 512  # query rows attended at once
# sequences pad to a multiple of this (fewer compiles); a whole number of
# query blocks, or a long sequence would attend in one block of T x T scores
LENGTH_STEP = Q_BLOCK


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


def rope(x, positions, theta):
    """x [T, heads, hd]; rotate-half RoPE at ``positions`` [T]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window):
    """q [T, H, hd], k/v [T, KV, hd] -> [T, H, hd]; causal (+ window)."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=1)  # query head i reads KV head i // rep
    v = jnp.repeat(v, rep, axis=1)
    key_pos = jnp.arange(t)

    def block(q_blk, start):
        q_pos = start + jnp.arange(q_blk.shape[0])
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) / np.sqrt(hd)
        ok = key_pos[None, :] <= q_pos[:, None]
        if window:
            ok &= key_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    n = t // Q_BLOCK if t % Q_BLOCK == 0 and t > Q_BLOCK else 1
    if n == 1:
        return block(q, 0)
    qb = q.reshape(n, Q_BLOCK, h, hd)
    out = jax.lax.map(lambda a: block(a[0], a[1]), (qb, jnp.arange(n) * Q_BLOCK))
    return out.reshape(t, h, hd)


@partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(x, layers, i, *, cfg_items, control):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        lw = jax.tree.map(lambda a: a[i], layers)
        t = x.shape[0]
        hd = cfg["head_dim"]
        nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        pos = jnp.arange(t)
        h = rms_norm(x, lw["attn_norm"], cfg["rms_norm_eps"])
        q = _mm(h, lw["wq"], control).reshape(t, nh, hd)
        k = _mm(h, lw["wk"], control).reshape(t, nkv, hd)
        v = _mm(h, lw["wv"], control).reshape(t, nkv, hd)
        q = rope(q, pos, cfg["rope_theta"])
        k = rope(k, pos, cfg["rope_theta"])
        a = attention(q, k, v, cfg["sliding_window"]).reshape(t, nh * hd)
        x = x + _mm(a, lw["wo"], control)
        h = rms_norm(x, lw["mlp_norm"], cfg["rms_norm_eps"])
        g = _mm(h, lw["w_gate"], control)
        u = _mm(h, lw["w_up"], control)
        return x + _mm(jax.nn.silu(g) * u, lw["w_down"], control)


@partial(jax.jit, static_argnames=("cfg_items", "control"))
def _head(x, final_norm, lm_head, rows, *, cfg_items, control):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[rows], final_norm, cfg["rms_norm_eps"])
        return _mm(h, lm_head, control)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


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
    items = tuple(sorted(cfg.items()))
    x = _embed(w["embed"], jnp.asarray(padded))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, w["layers"], jnp.int32(i), cfg_items=items, control=control)
    return _head(x, w["final_norm"], w["lm_head"], jnp.asarray(rows, jnp.int32),
                 cfg_items=items, control=control)
