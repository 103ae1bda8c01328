"""Basic layers: norms, rotary embeddings, gated MLP, embeddings.

Pure-functional: every layer is an ``init_*`` returning a params dict and an
``apply``-style function.  Param leaves carry *logical axis names* via the
parallel ``axes_*`` tree (built in parallel with params) so the launcher can
map them to mesh axes (see ``repro.runtime.sharding``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..launch.jax_compat import resolve_mesh

__all__ = [
    "Initializer",
    "dense_init",
    "embed_init",
    "rms_norm",
    "layer_norm",
    "rope_frequencies",
    "yarn_mscale",
    "rope_attention_scale",
    "apply_rope",
    "swiglu",
    "mlp_init",
    "mlp_apply",
    "cross_entropy_loss",
]


class Initializer:
    """Splits one PRNGKey into a stream of keys (init bookkeeping).

    ``abstract=True`` makes the big initialisers return ShapeDtypeStructs —
    used when only the parameter *structure* is needed (axis-name trees,
    dry-run), avoiding minutes of real RNG for multi-billion-param configs.
    """

    def __init__(self, key: jax.Array, abstract: bool = False):
        self._key = key
        self.abstract = abstract

    def next(self) -> jax.Array:
        if self.abstract:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub


def dense_init(init: Initializer, shape: tuple[int, ...], dtype, scale: float | None = None):
    """Truncated-normal fan-in initialisation."""
    if init.abstract:
        return jax.ShapeDtypeStruct(shape, dtype)
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    std = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(init.next(), -2.0, 2.0, shape, jnp.float32) * std).astype(
        dtype
    )


def embed_init(init: Initializer, vocab: int, d_model: int, dtype):
    if init.abstract:
        return jax.ShapeDtypeStruct((vocab, d_model), dtype)
    return (jax.random.normal(init.next(), (vocab, d_model), jnp.float32) * 0.02).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction for a context ``factor`` times longer."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, head_dim: int, theta: float, positions: int) -> float:
    return head_dim * math.log(positions / (rotations * 2 * math.pi)) / (2 * math.log(theta))


def rope_frequencies(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies for rotary embeddings (half of head_dim).  With
    ``scaling`` (a ``RopeScaling``), YaRN's as DeepSeek-V2's modelling code
    computes them: interpolated by ``factor`` below the dimensions that turn
    ``beta_slow`` times over the original length, left as they are above
    those that turn ``beta_fast`` times, and blended linearly between."""
    exps = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    if scaling is None:
        return 1.0 / (theta ** exps)
    extra = 1.0 / (theta ** exps)
    inter = 1.0 / (scaling.factor * theta ** exps)
    n = scaling.original_max_position
    low = max(math.floor(_yarn_correction_dim(scaling.beta_fast, head_dim, theta, n)), 0)
    high = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, head_dim, theta, n)),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # where the original frequency is kept
    return inter * (1 - keep) + extra * keep


def rope_attention_scale(scaling) -> float:
    """The factor YaRN puts on the softmax scale: ``mscale(factor,
    mscale_all_dim) ** 2`` (1 without scaling)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, scaling=None) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq].  Rotates the
    pairs (i, i + head_dim/2); ``scaling`` is YaRN's (``rope_frequencies``),
    whose cos and sin carry ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``."""
    head_dim = x.shape[-1]
    inv = rope_frequencies(head_dim, theta, scaling)
    angles = positions[..., :, None].astype(jnp.float32) * inv[None, :]  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def mlp_init(init: Initializer, d_model: int, d_ff: int, dtype):
    params = {
        "w_gate": dense_init(init, (d_model, d_ff), dtype),
        "w_up": dense_init(init, (d_model, d_ff), dtype),
        "w_down": dense_init(init, (d_ff, d_model), dtype),
    }
    axes = {
        "w_gate": ("embed", "ff"),
        "w_up": ("embed", "ff"),
        "w_down": ("ff", "embed"),
    }
    return params, axes


def constrain_ff_hidden(h: jax.Array, mesh=None) -> jax.Array:
    """Pin the MLP hidden to [batch->dp, seq, ff->model] (Megatron TP): the
    GSPMD fixpoint sometimes replicates it in rematerialised backward
    regions (8 GB/layer at Jamba scale).  ``mesh`` is an explicit
    Mesh/MeshContext (ambient ``use_mesh`` as fallback); no-op without one."""
    mesh = resolve_mesh(mesh)
    if mesh is None or h.ndim != 3:
        return h
    sizes = mesh.axis_sizes()
    dp = mesh.dp_axes()
    dpn = mesh.dp_size()
    entries = [None, None, None]
    if dp and h.shape[0] % dpn == 0 and h.shape[0] >= dpn:
        entries[0] = dp
    if "model" in sizes and sizes["model"] > 1 and h.shape[2] % sizes["model"] == 0:
        entries[2] = "model"
    if all(e is None for e in entries):
        return h
    return mesh.constrain(h, jax.sharding.PartitionSpec(*entries))


def mlp_apply(params: dict, x: jax.Array, compute_dtype, mesh=None) -> jax.Array:
    w_gate = params["w_gate"].astype(compute_dtype)
    w_up = params["w_up"].astype(compute_dtype)
    w_down = params["w_down"].astype(compute_dtype)
    h = constrain_ff_hidden(swiglu(x @ w_gate, x @ w_up), mesh)
    return h @ w_down


def cross_entropy_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean next-token cross entropy, GSPMD-friendly over a vocab-sharded
    logits tensor: the gold logit is extracted with a one-hot contraction
    (local partial + psum) instead of ``take_along_axis`` (which would
    force an all-gather of the full-vocab logits — 12 GB/device at 152k
    vocab).  fp32 accumulation."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)  # mul+reduce: no transposed dot
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
