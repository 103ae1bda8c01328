"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Grid (batch, heads, chunks) with the chunk dimension sequential; the
running state S [P, N] lives in fp32 VMEM scratch across chunks.  Per
chunk (all 2-D MXU matmuls):

    cum    = cumsum(dt * a)                       [Q]
    G      = tril(C B^T  *  exp(cum_i - cum_j))   [Q, Q]
    y      = G @ u  +  exp(cum) * (C @ S^T)       [Q, P]
    S_new  = exp(cum_Q) S + (exp(cum_Q - cum) u)^T @ B   [P, N]

The decay matrix masks the *exponent* (upper triangle would overflow).
This is the TPU-native shape of the SSD algorithm: the GPU version's
warp-level scan becomes per-chunk MXU matmuls + one sequential grid axis.

Layout: the TPU tiles the last two dimensions of every block, which must
be multiples of (8, 128) or whole.  So the wrapper moves heads ahead of
the sequence axis (x, dt and y are ``[B, H, S, ·]`` inside the call, with
dt as a ``[Q, 1]`` column per block), and the per-head decay rates ``a``
sit whole in SMEM and are read as one scalar per grid cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_final_ref, s_scr,
                *, chunk: int, nc: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)  # [Q, 1]
    a = a_ref[hi]  # scalar decay rate for this head
    b = b_ref[0].astype(jnp.float32)  # [Q, N]
    c = c_ref[0].astype(jnp.float32)  # [Q, N]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = row >= col
    # inclusive cumsum of the per-step log decay as a lower-triangular
    # row sum: cum_i = sum_{j <= i} dt_j * a
    la_row = jnp.broadcast_to(dt * a, (chunk, chunk)).T  # [Q, Q]: la_j on row i
    cum = jnp.sum(jnp.where(mask, la_row, 0.0), axis=1, keepdims=True)  # [Q, 1]
    u = x * dt  # [Q, P]

    cum_sq = jnp.broadcast_to(cum, (chunk, chunk))
    diff = cum_sq - cum_sq.T  # cum_i - cum_j
    decay = jnp.exp(jnp.where(mask, diff, NEG_INF))
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    g = cb * decay  # [Q, Q]
    y = jax.lax.dot_general(g, u, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    s_prev = s_scr[...]  # [P, N]
    y += jnp.exp(cum) * jax.lax.dot_general(
        c, s_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    cum_last = cum[chunk - 1:chunk]  # [1, 1]
    tail = jnp.exp(cum_last - cum)  # [Q, 1]
    s_scr[...] = jnp.exp(cum_last) * s_prev + jax.lax.dot_general(
        (u * tail).T, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        s_final_ref[0, 0] = s_scr[...].astype(s_final_ref.dtype)


def ssd_scan(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H] (softplus'ed, positive)
    a: jax.Array,  # [H] (negative)
    b: jax.Array,  # [B, S, N]
    c: jax.Array,  # [B, S, N]
    *,
    chunk: int = 256,
    interpret: bool | None = None,
):
    import jax.experimental.pallas.tpu as pltpu

    bs, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    xt = x.transpose(0, 2, 1, 3)  # [B, H, S, P]
    dtt = dt.transpose(0, 2, 1)[..., None]  # [B, H, S, 1]
    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(bs, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bs, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(xt, dtt, a.astype(jnp.float32), b, c)
    return y.transpose(0, 2, 1, 3), s_final
