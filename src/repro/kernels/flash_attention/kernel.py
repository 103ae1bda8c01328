"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA-aware).

Online-softmax over KV blocks: grid = (batch*heads, q_blocks, kv_blocks)
with the innermost (kv) dimension iterated sequentially per core
("arbitrary" semantics); running max / normaliser / accumulator live in
fp32 VMEM scratch across kv iterations.  Fully-masked KV blocks (beyond
the causal frontier or outside the sliding window) are skipped with
``pl.when`` — on TPU this prunes both the MXU work and the HBM->VMEM copy
of the never-used block, which is what halves attention FLOPs vs the
unmasked XLA path.

BlockSpec tiling: q/o [1, block_q, d_head], k/v [1, block_k, d_head] —
the working set (2*block_q*d + 2*block_k*d + block_q*block_k fp32) is
sized for ~16 MB VMEM with the default 512/512 blocks at d_head <= 256.

Validated in interpret mode against ``ref.reference_attention`` over shape
and dtype sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, block_q: int, block_k: int, nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level pruning: causal frontier and sliding window
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window > 0:
        live &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # [BH, S, D]
    k: jax.Array,  # [BKV, Skv, D]
    v: jax.Array,
    *,
    kv_map: int,  # q row b attends kv row (b // kv_map) — GQA grouping
    scale: float,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    import jax.experimental.pallas.tpu as pltpu

    bh, s, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, skv)
    assert s % block_q == 0 and skv % block_k == 0, (s, skv, block_q, block_k)
    nq, nk = s // block_q, skv // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // kv_map, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // kv_map, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
