"""Jitted public wrapper for the flash attention kernel, with a backward
path: the forward runs the Pallas kernel; the VJP recomputes attention via
the pure-jnp reference from the saved q/k/v residuals.  Note the recompute
*does* build the dense S x S score matrix at grad time (XLA path), so the
O(S) memory advantage holds for inference and for residual storage only —
a Pallas backward kernel is the follow-up that lifts this for long-context
training."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd
from .ref import reference_attention


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, window, block_q, block_k, interpret):
    b, s, h, d = q.shape
    kv = k.shape[2]
    assert h % kv == 0
    scale = d**-0.5
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, k.shape[1], d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, v.shape[1], d)
    out = flash_attention_bhsd(
        qr, kr, vr, kv_map=h // kv, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = _flash_attention(q, k, v, causal, window, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, residuals, g):
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: reference_attention(q_, k_, v_, causal=causal, window=window),
        q, k, v,
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, Skv, KV, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    return _flash_attention(q, k, v, causal, window, block_q, block_k, interpret)
