"""Grouped expert GEMM Pallas TPU kernel.

buckets [E, C, D] x weights [E, D, F] -> [E, C, F]: one MXU matmul per
(expert, row-block, col-block) grid cell, accumulating over the contraction
dimension in fp32 VMEM scratch.  This is the dense-bucket analogue of
MegaBlocks' grouped GEMM — the capacity-bucket layout keeps every tile
shape static (TPU-friendly) at the cost of padding, which the dispatch
keeps below `capacity_factor`.

Block shapes default to (128, 512, 128): MXU-aligned (multiples of 128 on
the matmul dims) and ~0.75 MB VMEM working set per input tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr, *, nd: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(di == nd - 1)
    def _write():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def grouped_matmul(
    x: jax.Array,  # [E, C, D]
    w: jax.Array,  # [E, D, F]
    *,
    block_c: int = 128,
    block_d: int = 512,
    block_f: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    import jax.experimental.pallas.tpu as pltpu

    e, c, d = x.shape
    f = w.shape[2]
    block_c = min(block_c, c)
    block_d = min(block_d, d)
    block_f = min(block_f, f)
    assert c % block_c == 0 and d % block_d == 0 and f % block_f == 0
    nd = d // block_d

    kernel = functools.partial(_gmm_kernel, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(e, c // block_c, f // block_f, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(x, w)
