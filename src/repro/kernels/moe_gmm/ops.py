"""Jitted wrappers: grouped GEMM + the full expert SwiGLU FFN.

``gmm`` is differentiable: the backward of a grouped matmul is two grouped
matmuls, so the VJP reuses the same Pallas kernel (dx = g @ w^T per expert,
dw = x^T @ g per expert).  ``expert_ffn`` composes differentiable ``gmm``
calls, so it backprops end to end.

``ragged_gmm`` is the grouped product over rows sorted by group, given the
group sizes (no capacity buckets): JAX's Pallas megablox kernel, which
visits only the row tiles that hold a group's rows and reads each group's
weights for those alone.  Being a ``pallas_call``, its op on the device
trace keeps the caller's named scope (XLA's own ``ragged-dot`` rewrite
drops it)."""

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


def ragged_gmm(x, w, group_sizes):
    """Rows of ``x`` [m, k], sorted by group, each times its group's weight:
    rows ``[offset_i, offset_i + group_sizes[i])`` by ``w[i]`` ([g, k, n]).
    Rows past ``sum(group_sizes)`` are not computed and hold no defined value:
    the caller selects them away.  Row tiles of 128 (512 for long inputs),
    contraction tiles of up to 1024 and output tiles of up to 2048 columns.
    The kernel where the program is lowered for a TPU, interpret mode
    elsewhere, chosen at lowering: a program compiled for the chip on a host
    without one holds the kernel the chip runs."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

    m, k = x.shape
    n = w.shape[2]
    tm = 512 if m > 4096 else 128 if m >= 128 else -(-m // 8) * 8
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tiling = (tm, min(k, 1024), min(n, 2048))

    def gmm(interp):
        return lambda x, w, sizes: megablox_gmm(x, w, sizes, preferred_element_type=x.dtype,
                                                tiling=tiling, interpret=interp)

    out = jax.lax.platform_dependent(x, w.astype(x.dtype), group_sizes.astype(jnp.int32),
                                     tpu=gmm(False), default=gmm(True))
    return out[:m]
