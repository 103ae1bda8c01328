"""Composable transformer stack.

A model is a repeated *pattern* of blocks (the smallest period of the
(mixer, ffn) layer spec — 1 for uniform models, 8 for Jamba's 1:7
Mamba/attention interleave).  Training and prefill scan over pattern
repeats (`lax.scan`) so compile time and HLO size are O(pattern), with
optional rematerialisation per repeat.  Decode caches are layer-stacked
too (leaves ``[r, B, ...]``), and decode loops over the repeats with the
whole stack in the loop's carry: each layer writes its new entries into the
stack in place and attention reads its layer straight out of it.

Block = norm -> mixer (attention | MLA | SSM) [+ cross-attention for
decoders] -> residual -> norm -> FFN (dense SwiGLU | MoE) -> residual.
Pure-SSM configs (d_ff == 0) use the Mamba block as the whole layer.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..launch.jax_compat import resolve_mesh
from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import Initializer, mlp_apply, mlp_init, rms_norm

__all__ = [
    "block_init", "block_apply", "stack_init", "stack_apply", "init_stack_cache",
    "stack_layers",
]


def constrain_residual(x: jax.Array, cfg: ModelConfig, mesh=None) -> jax.Array:
    """Sequence-parallel residual stream (Megatron-SP adapted to GSPMD):
    saved layer boundaries are sharded [batch->dp, seq->model], cutting the
    dominant remat-residual footprint by the TP degree.  ``mesh`` is the
    explicitly threaded Mesh/MeshContext (ambient ``use_mesh`` as fallback);
    no-op when no mesh is given or dims don't divide."""
    mesh = resolve_mesh(mesh)
    if mesh is None or x.ndim != 3:
        return x
    sizes = mesh.axis_sizes()
    dp = mesh.dp_axes()
    dpn = mesh.dp_size()
    entries = [None, None, None]
    if dp and x.shape[0] % dpn == 0 and x.shape[0] >= dpn:
        entries[0] = dp
    if (
        cfg.sequence_parallel
        and "model" in mesh.axis_names
        and sizes["model"] > 1
        and x.shape[1] % sizes["model"] == 0
    ):
        entries[1] = "model"
    if all(e is None for e in entries):
        return x
    return mesh.constrain(x, jax.sharding.PartitionSpec(*entries))


def _mixer_kind(cfg: ModelConfig, j: int, encoder: bool) -> str:
    if encoder or cfg.layer_is_attention(j):
        return "mla" if cfg.attn_type == "mla" else "attn"
    return "ssm"


def block_init(init: Initializer, cfg: ModelConfig, j: int, dtype, *, encoder=False, cross=False):
    d = cfg.d_model
    kind = _mixer_kind(cfg, j, encoder)
    params = {"ln1": jnp.zeros((d,), dtype)}
    axes = {"ln1": ("embed",)}
    if kind == "attn":
        params["mixer"], axes["mixer"] = attn_mod.attention_init(init, cfg, dtype)
    elif kind == "mla":
        params["mixer"], axes["mixer"] = attn_mod.mla_init(init, cfg, dtype)
    else:
        params["mixer"], axes["mixer"] = ssm_mod.ssm_init(init, cfg, dtype)
    if cross:
        params["ln_cross"] = jnp.zeros((d,), dtype)
        axes["ln_cross"] = ("embed",)
        params["cross"], axes["cross"] = attn_mod.attention_init(init, cfg, dtype)
    if cfg.layer_is_moe(j) and not encoder:
        params["ln2"] = jnp.zeros((d,), dtype)
        axes["ln2"] = ("embed",)
        params["ffn"], axes["ffn"] = moe_mod.moe_init(init, cfg, dtype)
    elif cfg.d_ff:
        params["ln2"] = jnp.zeros((d,), dtype)
        axes["ln2"] = ("embed",)
        params["ffn"], axes["ffn"] = mlp_init(init, cfg.d_model, cfg.d_ff, dtype)
    return params, axes


def init_block_cache(cfg: ModelConfig, j: int, batch: int, seq_len: int, *, encoder=False,
                     cross=False, mem_len: int = 0, dtype=jnp.bfloat16):
    kind = _mixer_kind(cfg, j, encoder)
    cache = {}
    if kind == "attn":
        cache["mixer"] = attn_mod.init_attention_cache(cfg, batch, seq_len, dtype)
    elif kind == "mla":
        cache["mixer"] = attn_mod.init_mla_cache(cfg, batch, seq_len, dtype)
    else:
        cache["mixer"] = ssm_mod.init_ssm_cache(cfg, batch, dtype)
    if cross:
        h = cfg.head_dim
        cache["cross"] = {
            "k": jnp.zeros((batch, mem_len, cfg.n_kv_heads, h), dtype),
            "v": jnp.zeros((batch, mem_len, cfg.n_kv_heads, h), dtype),
        }
    return cache


def _cross_attention(params, x, memory_kv, cfg, scale_dtype):
    """Decoder cross-attention against precomputed encoder K/V."""
    compute = x.dtype
    b, s, _ = x.shape
    h = cfg.head_dim
    q = (x @ params["w_q"].astype(compute)).reshape(b, s, cfg.n_heads, h)
    k, v = memory_kv["k"].astype(compute), memory_kv["v"].astype(compute)
    mask = jnp.ones((1, 1, 1, s, k.shape[1]), bool)
    out = attn_mod.masked_attention(q, k, v, mask, h**-0.5)
    return out.reshape(b, s, cfg.n_heads * h) @ params["w_o"].astype(compute)


def cross_kv(params, memory, cfg):
    """Precompute cross-attention K/V from encoder output (prefill)."""
    compute = memory.dtype
    b, s, _ = memory.shape
    h = cfg.head_dim
    k = (memory @ params["w_k"].astype(compute)).reshape(b, s, cfg.n_kv_heads, h)
    v = (memory @ params["w_v"].astype(compute)).reshape(b, s, cfg.n_kv_heads, h)
    return {"k": k, "v": v}


def block_apply(
    params,
    x,
    cfg: ModelConfig,
    j: int,
    *,
    positions,
    cache=None,
    update_cache=False,
    encoder=False,
    causal=True,
    impl="xla",
    key=None,
    mesh=None,
    ragged=False,
    layer=None,
):
    """Returns (x, new_cache, aux).  With ``layer`` (decode) every leaf of
    ``cache`` is layer-stacked and the block reads and writes layer
    ``layer``: attention in place, the other (small) caches sliced out and
    written back."""
    kind = _mixer_kind(cfg, j, encoder)
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    mixer_cache = cache.get("mixer") if cache else None
    if layer is not None and kind == "ssm":
        mixer_cache = jax.tree.map(lambda t: attn_mod.layer_slice(t, layer), mixer_cache)
    if kind == "attn":
        if encoder or not causal:
            out = attn_mod.blockwise_attention(
                *_enc_qkv(params["mixer"], h, cfg),
                causal=False,
                window=0,
                q_offset=0,
                scale=cfg.head_dim**-0.5,
            )
            b, s, _ = x.shape
            out = out.reshape(b, s, -1) @ params["mixer"]["w_o"].astype(x.dtype)
            new_mixer_cache = None
        else:
            out, new_mixer_cache = attn_mod.attention_apply(
                params["mixer"], h, cfg, positions=positions, cache=mixer_cache,
                update_cache=update_cache, impl=impl, ragged=ragged, layer=layer,
            )
    elif kind == "mla":
        out, new_mixer_cache = attn_mod.mla_apply(
            params["mixer"], h, cfg, positions=positions, cache=mixer_cache,
            update_cache=update_cache, impl=impl, ragged=ragged, layer=layer,
        )
    else:
        out, new_mixer_cache = ssm_mod.ssm_apply(
            params["mixer"], h, cfg, positions=positions, cache=mixer_cache,
            update_cache=update_cache, impl=impl,
        )
        if layer is not None:
            new_mixer_cache = jax.tree.map(
                lambda t, n: jax.lax.dynamic_update_index_in_dim(t, n.astype(t.dtype), layer, 0),
                cache["mixer"], new_mixer_cache,
            )
    x = x + out

    if "cross" in params:
        hc = rms_norm(x, params["ln_cross"], cfg.norm_eps)
        memory_kv = cache["cross"]
        if layer is not None:
            memory_kv = jax.tree.map(lambda t: attn_mod.layer_slice(t, layer), memory_kv)
        x = x + _cross_attention(params["cross"], hc, memory_kv, cfg, x.dtype)

    aux = jnp.zeros((), jnp.float32)
    if "ffn" in params:
        h2 = rms_norm(x, params["ln2"], cfg.norm_eps)
        if cfg.layer_is_moe(j) and not encoder:
            out2, aux = moe_mod.moe_apply(params["ffn"], h2, cfg, impl=impl, key=key, mesh=mesh)
        else:
            out2 = mlp_apply(params["ffn"], h2, x.dtype, mesh=mesh)
        x = x + out2

    new_cache = None
    if cache is not None or update_cache:
        new_cache = dict(cache) if cache else {}
        if new_mixer_cache is not None:
            new_cache["mixer"] = new_mixer_cache
    return x, new_cache, aux


def _enc_qkv(params, h, cfg):
    compute = h.dtype
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = (h @ params["w_q"].astype(compute)).reshape(b, s, cfg.n_heads, hd)
    k = (h @ params["w_k"].astype(compute)).reshape(b, s, cfg.n_kv_heads, hd)
    v = (h @ params["w_v"].astype(compute)).reshape(b, s, cfg.n_kv_heads, hd)
    return q, k, v


# --------------------------------------------------------------------------
# stacked layers: scan over pattern repeats
# --------------------------------------------------------------------------


def _stack_period(cfg: ModelConfig, n_layers: int, encoder: bool) -> int:
    p = 1 if encoder else cfg.pattern_period()
    return p if n_layers % p == 0 else 1


def stack_init(init: Initializer, cfg: ModelConfig, dtype, *, n_layers=None, encoder=False,
               cross=False):
    n_layers = n_layers or cfg.n_layers
    p = _stack_period(cfg, n_layers, encoder)
    r = n_layers // p
    rows = [
        [block_init(init, cfg, j, dtype, encoder=encoder, cross=cross)[0] for j in range(p)]
        for _ in range(r)
    ]
    pattern = []
    for j in range(p):
        if r > 1:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[rows[i][j] for i in range(r)])
        else:
            stacked = rows[0][j]
        pattern.append(stacked)
    return tuple(pattern)


def stack_axes(cfg: ModelConfig, *, n_layers=None, encoder=False, cross=False):
    """Logical axis names per param leaf; scanned leaves get 'layers' first."""
    n_layers = n_layers or cfg.n_layers
    p = _stack_period(cfg, n_layers, encoder)
    r = n_layers // p
    dummy = Initializer(jax.random.PRNGKey(0), abstract=True)
    pattern_axes = []
    for j in range(p):
        _, aj = block_init(dummy, cfg, j, jnp.float32, encoder=encoder, cross=cross)
        if r > 1:
            aj = jax.tree.map(
                lambda t: ("layers",) + tuple(t), aj, is_leaf=lambda t: isinstance(t, tuple)
            )
        pattern_axes.append(aj)
    return tuple(pattern_axes)


def init_stack_cache(cfg: ModelConfig, batch: int, seq_len: int, *, n_layers=None, cross=False,
                     mem_len=0, dtype=jnp.bfloat16):
    """Decode caches, one entry per pattern position, every leaf stacked over
    the pattern's ``r`` repeats (``[r, B, ...]``, ``r`` >= 1): the carry of
    the decode loop in ``stack_apply``, donated and updated in place."""
    n_layers = n_layers or cfg.n_layers
    p = _stack_period(cfg, n_layers, False)
    r = n_layers // p

    def stack(t):
        return jnp.broadcast_to(t, (r,) + t.shape)

    return tuple(
        jax.tree.map(stack, init_block_cache(cfg, j, batch, seq_len, cross=cross,
                                             mem_len=mem_len, dtype=dtype))
        for j in range(p)
    )


def stack_layers(caches: tuple, n_layers: int) -> tuple:
    """Caches as a scanned prefill emits them (one entry per pattern
    position, leaves ``[r, B, ...]`` only where the pattern repeats ``r > 1``
    times) with the leading repeat axis that decode caches always carry."""
    if n_layers // len(caches) > 1:
        return tuple(caches)
    return tuple(jax.tree.map(lambda t: t[None], c) for c in caches)


def stack_apply(
    pattern_params: tuple,
    x,
    cfg: ModelConfig,
    *,
    positions,
    caches: tuple | None = None,
    update_cache: bool = False,
    encoder: bool = False,
    impl: str = "xla",
    key=None,
    n_layers: int | None = None,
    mesh=None,
    ragged: bool = False,
):
    """Returns (x, new_caches, aux_total)."""
    n_layers = n_layers or cfg.n_layers
    p = len(pattern_params)
    r = n_layers // p

    if caches is not None and "mixer" in caches[0]:
        # decode (only decode caches hold the mixers' state; training and
        # prefill take none, or an encoder's cross K/V alone): the
        # layer-stacked caches ride in the loop's carry, so each layer writes
        # its entries into the donated stack in place and reads its K/V
        # straight out of it; a repeat's params are sliced from the stacked
        # params inside the ops that read them
        def layer(rep, carry):
            h, aux, stacks = carry
            new = []
            for j in range(p):
                lp = pattern_params[j]
                if r > 1:
                    lp = jax.tree.map(lambda t: t[rep], lp)
                h, nc, a = block_apply(
                    lp, h, cfg, j, positions=positions, cache=stacks[j], encoder=encoder,
                    impl=impl, key=key, mesh=mesh, ragged=ragged, layer=rep,
                )
                aux = aux + a
                new.append(nc)
            return h, aux, tuple(new)

        carry = (x, jnp.zeros((), jnp.float32), tuple(caches))
        x, aux, new_caches = (layer(0, carry) if r == 1
                              else jax.lax.fori_loop(0, r, layer, carry))
        return x, new_caches, aux

    def body(carry, xs):
        h, aux = carry
        layer_params, layer_caches = xs
        new_caches = []
        h = constrain_residual(h, cfg, mesh)
        for j in range(p):
            cache_j = layer_caches[j] if layer_caches is not None else None
            h, nc, a = block_apply(
                layer_params[j], h, cfg, j, positions=positions, cache=cache_j,
                update_cache=update_cache, encoder=encoder, impl=impl, key=key, mesh=mesh,
                ragged=ragged,
            )
            aux = aux + a
            new_caches.append(nc if nc is not None else {})
        h = constrain_residual(h, cfg, mesh)
        return (h, aux), tuple(new_caches)

    fn = body
    if cfg.remat and r > 1:
        fn = jax.checkpoint(fn, prevent_cse=False)

    if r == 1:
        (x, aux), emit = fn(
            (x, jnp.zeros((), jnp.float32)),
            (pattern_params, caches),
        )
        new_caches = emit if (caches is not None or update_cache) else None
        return x, new_caches, aux

    xs = (pattern_params, caches if caches is not None else tuple({} for _ in range(p)))
    (x, aux), emitted = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), xs)
    new_caches = emitted if (caches is not None or update_cache) else None
    return x, new_caches, aux
