"""Jitted wrappers: grouped GEMM + the full expert SwiGLU FFN.

``gmm`` is differentiable: the backward of a grouped matmul is two grouped
matmuls, so the VJP reuses the same Pallas kernel (dx = g @ w^T per expert,
dw = x^T @ g per expert).  ``expert_ffn`` composes differentiable ``gmm``
calls, so it backprops end to end."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import grouped_matmul


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gmm(x, w, interpret):
    return grouped_matmul(x, w, interpret=interpret)


def _gmm_fwd(x, w, interpret):
    return _gmm(x, w, interpret), (x, w)


def _gmm_bwd(interpret, residuals, g):
    x, w = residuals
    dx = grouped_matmul(g, w.transpose(0, 2, 1), interpret=interpret)
    dw = grouped_matmul(x.transpose(0, 2, 1), g, interpret=interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@partial(jax.jit, static_argnames=("interpret",))
def gmm(x, w, interpret: bool | None = None):
    return _gmm(x, w, interpret)


def expert_ffn(params, buckets, interpret: bool | None = None):
    """SwiGLU per expert over capacity buckets — Pallas grouped GEMMs."""
    compute = buckets.dtype
    wg = params["w_gate"].astype(compute)
    wu = params["w_up"].astype(compute)
    wd = params["w_down"].astype(compute)
    h = jax.nn.silu(_gmm(buckets, wg, interpret)) * _gmm(buckets, wu, interpret)
    return _gmm(h, wd, interpret)
