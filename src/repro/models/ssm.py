"""Mamba-2 (SSD — state-space duality) layer [arXiv:2405.21060].

Chunked SSD: the sequence is split into chunks of Q tokens; within a chunk
the recurrence is computed as a (masked, decay-weighted) attention-like
matmul (MXU-friendly), and chunk-final states are passed through a single
``lax.scan`` over chunks.  Mathematically identical to the sequential scan

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T ;   y_t = C_t^T h_t + D x_t

with scalar-per-head A (the SSD restriction).  The Pallas kernel
(``repro.kernels.ssd_scan``) implements the same chunked schedule for TPU;
this module is its reference.

Sharding notes: the input projections to z/x/B/C/dt are *separate* weight
matrices (one fused [d, 2*d_inner+2N+H] projection is mathematically
identical but its channel-wise slices cross TP shard boundaries, forcing
GSPMD to all-gather the full fp32 activation — 8.7 GB/layer for Jamba);
the depthwise conv likewise runs per-part so no sharded concat is needed.

Decode keeps O(1) state per layer: conv ring buffers and h [H, P, N].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Initializer, dense_init, rms_norm

__all__ = ["ssm_init", "ssm_apply", "init_ssm_cache", "ssd_chunked", "ssd_step"]


def ssm_init(init: Initializer, cfg: ModelConfig, dtype):
    c = cfg.ssm
    d = cfg.d_model
    d_inner = c.expand * d
    n_heads = d_inner // c.head_dim
    params = {
        "w_z": dense_init(init, (d, d_inner), dtype),
        "w_x": dense_init(init, (d, d_inner), dtype),
        "w_b": dense_init(init, (d, c.state_dim), dtype),
        "w_c": dense_init(init, (d, c.state_dim), dtype),
        "w_dt": dense_init(init, (d, n_heads), dtype),
        "conv_wx": dense_init(init, (c.conv_width, d_inner), dtype, scale=0.5),
        "conv_wb": dense_init(init, (c.conv_width, c.state_dim), dtype, scale=0.5),
        "conv_wc": dense_init(init, (c.conv_width, c.state_dim), dtype, scale=0.5),
        "conv_bx": jnp.zeros((d_inner,), dtype),
        "conv_bb": jnp.zeros((c.state_dim,), dtype),
        "conv_bc": jnp.zeros((c.state_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(dtype),
        "d_skip": jnp.ones((n_heads,), dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.linspace(c.dt_min, c.dt_max, n_heads))).astype(dtype),
        "norm": jnp.zeros((d_inner,), dtype),
        "w_out": dense_init(init, (d_inner, d), dtype),
    }
    axes = {
        "w_z": ("embed", "ff"),
        "w_x": ("embed", "ff"),
        "w_b": ("embed", None),
        "w_c": ("embed", None),
        "w_dt": ("embed", None),
        "conv_wx": (None, "ff"),
        "conv_wb": (None, None),
        "conv_wc": (None, None),
        "conv_bx": ("ff",),
        "conv_bb": (None,),
        "conv_bc": (None,),
        "a_log": (None,),
        "d_skip": (None,),
        "dt_bias": (None,),
        "norm": ("ff",),
        "w_out": ("ff", "embed"),
    }
    return params, axes


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    c = cfg.ssm
    d_inner = c.expand * cfg.d_model
    n_heads = d_inner // c.head_dim
    return {
        "conv_x": jnp.zeros((batch, c.conv_width - 1, d_inner), dtype),
        "conv_b": jnp.zeros((batch, c.conv_width - 1, c.state_dim), dtype),
        "conv_c": jnp.zeros((batch, c.conv_width - 1, c.state_dim), dtype),
        "h": jnp.zeros((batch, n_heads, c.head_dim, c.state_dim), jnp.float32),
    }


def _causal_depthwise_conv(x, w, bias, compute):
    """x [B,S,C]; w [W,C]; causal, silu activation."""
    bsz, s, _ = x.shape
    width = w.shape[0]
    pad = jnp.zeros((bsz, width - 1, x.shape[-1]), compute)
    padded = jnp.concatenate([pad, x], axis=1)
    out = sum(padded[:, i : i + s] * w[i][None, None, :] for i in range(width))
    return jax.nn.silu(out + bias.astype(compute)), padded[:, -(width - 1) :] if width > 1 else None


def _conv_step(hist, new, w, bias, compute):
    """hist [B,W-1,C] ring; new [B,1,C] -> (out [B,C], new_hist)."""
    full = jnp.concatenate([hist.astype(compute), new], axis=1)  # [B,W,C]
    out = (full * w[None]).sum(axis=1) + bias.astype(compute)
    return jax.nn.silu(out), full[:, 1:]


def ssd_chunked(x, dt, a, b, c, chunk: int, h0=None, head_group: int = 8):
    """Chunked SSD scan.

    x  [B, S, H, P]   inputs per head
    dt [B, S, H]      positive step sizes (already softplus'ed)
    a  [H]            negative per-head decay rates
    b  [B, S, N], c [B, S, N]   input/output projections (single group)
    h0 [B, H, P, N]   initial state (decode restarts); None = zeros

    Returns (y [B, S, H, P], h_final [B, H, P, N]).  fp32 state math.

    Heads are processed in groups of ``head_group`` under ``lax.map`` so
    the decay tensor [B, NC, Q, Q, Hg] stays bounded (the full-H version
    is O(S*Q*H) fp32 — 17 GB/layer for Jamba at Q=256 — and is exactly
    what the Pallas kernel keeps in VMEM instead).
    """
    bs, s, nh, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    nc = s // chunk
    hg = min(head_group, nh)
    while nh % hg:
        hg -= 1
    ng = nh // hg

    bf = b.astype(jnp.float32).reshape(bs, nc, chunk, n)
    cf = c.astype(jnp.float32).reshape(bs, nc, chunk, n)
    cb = jnp.einsum("bqin,bqjn->bqij", cf, bf)  # [B,NC,Q,Q] shared by heads
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    init = h0.astype(jnp.float32) if h0 is not None else jnp.zeros((bs, nh, p, n), jnp.float32)

    def per_group(args):
        xg, dtg, ag, h0g = args  # [B,S,Hg,P], [B,S,Hg], [Hg], [B,Hg,P,N]
        xf = xg.astype(jnp.float32).reshape(bs, nc, chunk, hg, p)
        dtf = dtg.astype(jnp.float32).reshape(bs, nc, chunk, hg)
        la = dtf * ag.astype(jnp.float32)[None, None, None, :]
        cum = jnp.cumsum(la, axis=2)  # [B,NC,Q,Hg]
        u = xf * dtf[..., None]

        # intra-chunk decay matrix — mask the exponent (upper triangle
        # overflows and 0*inf => NaN in backward if masked post-exp)
        diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,NC,Qi,Qj,Hg]
        decay = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
        y_intra = jnp.einsum("bqij,bqijh,bqjhp->bqihp", cb, decay, u)

        tail = jnp.exp(cum[:, :, -1:, :] - cum)  # [B,NC,Q,Hg]
        s_chunk = jnp.einsum("bqjh,bqjn,bqjhp->bqhpn", tail, bf, u)
        chunk_decay = jnp.exp(cum[:, :, -1, :])  # [B,NC,Hg]

        def step(h, xs):
            s_c, g = xs
            return h * g[:, :, None, None] + s_c, h

        h_final, h_in = jax.lax.scan(
            step, h0g, (s_chunk.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2))
        )
        h_in = h_in.transpose(1, 0, 2, 3, 4)  # [B,NC,Hg,P,N]
        y_inter = jnp.einsum("bqih,bqin,bqhpn->bqihp", jnp.exp(cum), cf, h_in)
        return (y_intra + y_inter), h_final

    if ng == 1:
        y, h_final = per_group((x, dt, a, init))
        return y.reshape(bs, s, nh, p).astype(x.dtype), h_final

    xs = (
        x.reshape(bs, s, ng, hg, p).transpose(2, 0, 1, 3, 4),
        dt.reshape(bs, s, ng, hg).transpose(2, 0, 1, 3),
        a.reshape(ng, hg),
        init.reshape(bs, ng, hg, p, n).transpose(1, 0, 2, 3, 4),
    )
    ys, hs = jax.lax.map(per_group, xs)
    # ys [NG,B,NC,Q,Hg,P] -> [B,S,H,P]; hs [NG,B,Hg,P,N] -> [B,H,P,N]
    y = ys.transpose(1, 2, 3, 0, 4, 5).reshape(bs, s, nh, p)
    h_final = hs.transpose(1, 0, 2, 3, 4).reshape(bs, nh, p, n)
    return y.astype(x.dtype), h_final


def ssd_step(h, xt, dtt, a, bt, ct):
    """One decode step.  h [B,H,P,N]; xt [B,H,P]; dtt [B,H]; bt/ct [B,N]."""
    g = jnp.exp(dtt.astype(jnp.float32) * a.astype(jnp.float32)[None, :])  # [B,H]
    u = xt.astype(jnp.float32) * dtt.astype(jnp.float32)[..., None]
    h_next = h * g[:, :, None, None] + jnp.einsum("bhp,bn->bhpn", u, bt.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", h_next, ct.astype(jnp.float32))
    return y, h_next


def ssm_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions=None,  # unused; signature-compatible with attention
    cache: dict | None = None,
    update_cache: bool = False,
    impl: str = "xla",
):
    """Returns (out [B,S,D], new_cache)."""
    c = cfg.ssm
    compute = x.dtype
    bsz, s, d = x.shape
    d_inner = c.expand * d
    nh = d_inner // c.head_dim

    z = x @ params["w_z"].astype(compute)
    xin = x @ params["w_x"].astype(compute)
    braw = x @ params["w_b"].astype(compute)
    craw = x @ params["w_c"].astype(compute)
    dt = x @ params["w_dt"].astype(compute)

    if cache is None:
        xc_, tail_x = _causal_depthwise_conv(
            xin, params["conv_wx"].astype(compute), params["conv_bx"], compute
        )
        bc, tail_b = _causal_depthwise_conv(
            braw, params["conv_wb"].astype(compute), params["conv_bb"], compute
        )
        ccg, tail_c = _causal_depthwise_conv(
            craw, params["conv_wc"].astype(compute), params["conv_bc"], compute
        )
        xc = xc_.reshape(bsz, s, nh, c.head_dim)
        dtp = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(params["a_log"].astype(jnp.float32))
        if impl == "pallas":
            from ..kernels.ssd_scan import ops as ssd_ops

            y, h_final = ssd_ops.ssd(xc, dtp, a, bc, ccg, chunk=c.chunk_size)
        else:
            y, h_final = ssd_chunked(xc, dtp, a, bc, ccg, chunk=min(c.chunk_size, s))
        new_cache = None
        if update_cache:
            new_cache = {"conv_x": tail_x, "conv_b": tail_b, "conv_c": tail_c, "h": h_final}
    else:
        assert s == 1
        xc_, hist_x = _conv_step(
            cache["conv_x"], xin, params["conv_wx"].astype(compute), params["conv_bx"], compute
        )
        bc, hist_b = _conv_step(
            cache["conv_b"], braw, params["conv_wb"].astype(compute), params["conv_bb"], compute
        )
        ccg, hist_c = _conv_step(
            cache["conv_c"], craw, params["conv_wc"].astype(compute), params["conv_bc"], compute
        )
        xc = xc_.reshape(bsz, nh, c.head_dim)
        dtp = jax.nn.softplus(
            dt[:, 0].astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
        )
        a = -jnp.exp(params["a_log"].astype(jnp.float32))
        y, h_next = ssd_step(cache["h"], xc, dtp, a, bc, ccg)
        y = y[:, None]  # [B,1,H,P]
        new_cache = {"conv_x": hist_x, "conv_b": hist_b, "conv_c": hist_c, "h": h_next}

    y = y + xin.reshape(bsz, s, nh, c.head_dim).astype(jnp.float32) * params["d_skip"].astype(
        jnp.float32
    ).reshape(1, 1, nh, 1)
    y = y.reshape(bsz, s, d_inner).astype(compute)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    return y @ params["w_out"].astype(compute), new_cache
