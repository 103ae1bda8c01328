"""Composable transformer stack.

A model is a repeated *pattern* of blocks (the smallest period of the
(mixer, ffn) layer spec — 1 for uniform models, 8 for Jamba's 1:7
Mamba/attention interleave), after an optional *prelude* of leading dense
layers (``cfg.n_dense_layers``, DeepSeek's first dense layer) that run
once each, unstacked.  Training and prefill scan over pattern
repeats (`lax.scan`) so compile time and HLO size are O(pattern), with
optional rematerialisation per repeat.  Decode caches are layer-stacked
too (leaves ``[r, B, ...]``), and decode loops over the repeats with the
whole stack in the loop's carry: each layer writes its new entries into the
stack in place (an MLA layer stages its entry, written after the last
layer: ``attention.stage_latent``) and attention reads its layer straight
out of it.  A prelude layer's decode cache carries a leading layer axis of
1, so every decode-cache leaf has its rows on axis 1.

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
    "stack_layers", "prelude_init", "prelude_axes", "prelude_apply", "init_prelude_cache",
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


def zero_aux(cfg: ModelConfig):
    """A block's auxiliary outputs, zero: the MoE balance loss, and for a
    held expert share also the (token, expert) assignments it computed and
    the held experts it gave rows (``moe.moe_held``)."""
    balance = jnp.zeros((), jnp.float32)
    if cfg.moe is not None and cfg.moe.n_held:
        zero = jnp.zeros((), jnp.int32)
        return {"balance": balance, "held": zero, "held_experts": zero}
    return balance


def add_aux(a, b):
    return jax.tree.map(jnp.add, a, b)


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
        params["ffn"], axes["ffn"] = mlp_init(init, cfg.d_model, cfg.ffn_width(j), dtype)
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

    aux = zero_aux(cfg)
    if "ffn" in params:
        h2 = rms_norm(x, params["ln2"], cfg.norm_eps)
        if cfg.layer_is_moe(j) and not encoder:
            out2, aux = moe_mod.moe_apply(params["ffn"], h2, cfg, impl=impl, key=key, mesh=mesh,
                                          layer=layer)
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


def _stack_range(cfg: ModelConfig, n_layers, encoder: bool) -> tuple[int, int, int]:
    """(first, n, p): the stack's first layer index, its layer count (after
    a decoder's prelude) and the period its blocks repeat with."""
    first = 0 if encoder else cfg.n_dense_layers
    n = n_layers or cfg.n_layers - first
    p = 1 if encoder else cfg.pattern_period()
    return first, n, (p if n % p == 0 else 1)


def stack_init(init: Initializer, cfg: ModelConfig, dtype, *, n_layers=None, encoder=False,
               cross=False):
    first, n_layers, p = _stack_range(cfg, n_layers, encoder)
    r = n_layers // p
    rows = [
        [block_init(init, cfg, first + j, dtype, encoder=encoder, cross=cross)[0]
         for j in range(p)]
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
    first, n_layers, p = _stack_range(cfg, n_layers, encoder)
    r = n_layers // p
    dummy = Initializer(jax.random.PRNGKey(0), abstract=True)
    pattern_axes = []
    for j in range(p):
        _, aj = block_init(dummy, cfg, first + j, jnp.float32, encoder=encoder, cross=cross)
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
    first, n_layers, p = _stack_range(cfg, n_layers, False)
    r = n_layers // p

    def stack(t):
        return jnp.broadcast_to(t, (r,) + t.shape)

    return tuple(
        jax.tree.map(stack, init_block_cache(cfg, first + j, batch, seq_len, cross=cross,
                                             mem_len=mem_len, dtype=dtype))
        for j in range(p)
    )


def stack_layers(caches: tuple, n_layers: int) -> tuple:
    """Caches as a scanned prefill emits them (one entry per pattern
    position, leaves ``[r, B, ...]`` only where the pattern repeats ``r > 1``
    times) with the leading repeat axis that decode caches always carry.
    ``n_layers`` counts the stack's layers."""
    if n_layers // len(caches) > 1:
        return tuple(caches)
    return tuple(jax.tree.map(lambda t: t[None], c) for c in caches)


# --------------------------------------------------------------------------
# prelude: leading dense layers, each its own block, before the stack
# --------------------------------------------------------------------------


def prelude_init(init: Initializer, cfg: ModelConfig, dtype) -> tuple:
    return tuple(block_init(init, cfg, i, dtype)[0] for i in range(cfg.n_dense_layers))


def prelude_axes(cfg: ModelConfig) -> tuple:
    dummy = Initializer(jax.random.PRNGKey(0), abstract=True)
    return tuple(block_init(dummy, cfg, i, jnp.float32)[1] for i in range(cfg.n_dense_layers))


def init_prelude_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16) -> tuple:
    """One decode cache per prelude layer, each leaf with a leading layer
    axis of 1 (``[1, B, ...]``), the layout of the stack's."""
    return tuple(jax.tree.map(lambda t: t[None], init_block_cache(cfg, i, batch, seq_len,
                                                                   dtype=dtype))
                 for i in range(cfg.n_dense_layers))


def prelude_apply(params: tuple, x, cfg: ModelConfig, *, positions, caches=None,
                  update_cache=False, impl="xla", mesh=None, ragged=False):
    """Returns (x, new_caches, aux) after the prelude layers.  Decode
    ``caches`` are one-deep stacks, read and written at layer 0."""
    decode = caches is not None
    aux, new = zero_aux(cfg), []
    for i, lp in enumerate(params):
        cache = attn_mod.stage_latent(caches[i]) if decode else None
        x, nc, a = block_apply(
            lp, x, cfg, i, positions=positions, cache=cache, update_cache=update_cache,
            impl=impl, mesh=mesh, ragged=ragged, layer=0 if decode else None,
        )
        aux = add_aux(aux, a)
        new.append(attn_mod.flush_latent(nc, positions[:, 0], ragged) if decode else nc)
    return x, tuple(new), aux


def _layer_params(block_params: dict, rep, cfg: ModelConfig) -> dict:
    """Repeat ``rep`` of a pattern position's stacked params; a held expert
    share's weights stay stacked for ``moe.moe_held``, whose grouped kernel
    reads the layer's experts out of the stack in place."""
    out = jax.tree.map(lambda t: t[rep], block_params)
    ffn = block_params.get("ffn", {})
    if cfg.moe is not None and cfg.moe.n_held and "router" in ffn:
        out["ffn"] = {**out["ffn"], **{k: ffn[k] for k in moe_mod.HELD_WEIGHTS}}
    return out


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
    first, n_layers, _ = _stack_range(cfg, n_layers, encoder)
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
                    lp = _layer_params(lp, rep, cfg)
                h, nc, a = block_apply(
                    lp, h, cfg, first + j, positions=positions, cache=stacks[j],
                    encoder=encoder, impl=impl, key=key, mesh=mesh, ragged=ragged, layer=rep,
                )
                aux = add_aux(aux, a)
                new.append(nc)
            return h, aux, tuple(new)

        carry = (x, zero_aux(cfg), tuple(attn_mod.stage_latent(c) for c in caches))
        x, aux, new_caches = (layer(0, carry) if r == 1
                              else jax.lax.fori_loop(0, r, layer, carry))
        pos = positions[:, 0]
        return x, tuple(attn_mod.flush_latent(c, pos, ragged) for c in new_caches), aux

    def body(carry, xs):
        h, aux = carry
        layer_params, layer_caches = xs
        new_caches = []
        h = constrain_residual(h, cfg, mesh)
        for j in range(p):
            cache_j = layer_caches[j] if layer_caches is not None else None
            h, nc, a = block_apply(
                layer_params[j], h, cfg, first + j, positions=positions, cache=cache_j,
                update_cache=update_cache, encoder=encoder, impl=impl, key=key, mesh=mesh,
                ragged=ragged,
            )
            aux = add_aux(aux, a)
            new_caches.append(nc if nc is not None else {})
        h = constrain_residual(h, cfg, mesh)
        return (h, aux), tuple(new_caches)

    fn = body
    if cfg.remat and r > 1:
        fn = jax.checkpoint(fn, prevent_cse=False)

    if r == 1:
        (x, aux), emit = fn(
            (x, zero_aux(cfg)),
            (pattern_params, caches),
        )
        new_caches = emit if (caches is not None or update_cache) else None
        return x, new_caches, aux

    xs = (pattern_params, caches if caches is not None else tuple({} for _ in range(p)))
    (x, aux), emitted = jax.lax.scan(fn, (x, zero_aux(cfg)), xs)
    new_caches = emitted if (caches is not None or update_cache) else None
    return x, new_caches, aux
