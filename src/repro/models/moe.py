"""Mixture-of-Experts layer with CLEX-routed expert parallelism.

Paths:
  * ``moe_local``  — single-device reference: top-k routing, capacity-based
    scatter into per-expert buckets, expert SwiGLU, weighted combine.  This
    is the oracle for the sharded paths and the CPU smoke-test path.
  * ``moe_sharded`` — expert parallelism inside a manual ``shard_map``
    region (entered via ``launch.jax_compat.shard_map``): tokens
    stay sharded over the data axes, experts over the ``model`` axis; the
    dispatch is a `lax.all_to_all` over ``model`` only — the CLEX rule of
    keeping the heavy all-to-all on level-1 (intra-pod, short) links.
    When ``cfg.moe.valiant_shuffle``, tokens are randomly rotated across
    the token dimension first (the paper's "lightweight Valiant trick":
    redistribute inside the level-(1/s - 1) copy) to decorrelate hot
    experts from token position.

The routing math is identical in both paths; tests assert exact agreement.
Per-expert matmuls use a grouped einsum whose Pallas counterpart is
``repro.kernels.moe_gmm``.

A config that names a held share (``MoEConfig.n_held``) takes neither:
``moe_held`` routes over every expert in float32, computes only the
assignments that land on the experts the layer holds, with no capacity and
nothing dropped, and adds the shared experts.  The share is the config's,
not the mesh's: on one chip it stands for one rank of expert parallelism,
whose partial sum goes on to the next layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.moe_gmm import ops as gmm_ops
from ..launch import jax_compat
from .layers import Initializer, dense_init, mlp_apply, mlp_init, swiglu

__all__ = ["moe_init", "moe_apply", "router_topk", "moe_local", "moe_held", "HELD_WEIGHTS"]

# the held share's expert weights: the decode loop hands moe_held their
# whole layer stack, which its kernel reads in place (moe_held's docstring)
HELD_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_init(init: Initializer, cfg: ModelConfig, dtype):
    """Router over all ``n_experts``; expert weights for the held share
    (``n_held``, else every expert); shared experts as one SwiGLU."""
    moe = cfg.moe
    d = cfg.d_model
    f = moe.d_expert_ff
    e = moe.n_held or moe.n_experts
    params = {
        "router": dense_init(init, (d, moe.n_experts), dtype, scale=0.02),
        "w_gate": dense_init(init, (e, d, f), dtype),
        "w_up": dense_init(init, (e, d, f), dtype),
        "w_down": dense_init(init, (e, f, d), dtype),
    }
    axes = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "ff"),
        "w_up": ("experts", "embed", "ff"),
        "w_down": ("experts", "ff", "embed"),
    }
    if moe.n_shared_experts:
        params["shared"], axes["shared"] = mlp_init(init, d, moe.n_shared_experts * f, dtype)
    return params, axes


def router_topk(router_w, x_flat, top_k: int):
    """Returns (weights [T,k], experts [T,k], aux_loss scalar).

    Softmax over all experts, renormalised over the selected k (OLMoE /
    Mixtral convention).  Aux loss is the Switch load-balancing loss.
    """
    logits = (x_flat @ router_w.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    # Switch aux loss: e * sum_e (fraction tokens to e) * (mean router prob e)
    onehot = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(onehot.mean(0) * probs.mean(0))
    return weights, experts, aux


def _dispatch_indices(experts, top_k: int, n_experts: int, capacity: int):
    """Bucket slot for each (token, k) assignment; -1 if dropped.

    slot_within_expert via rank of the assignment among same-expert
    assignments in (token, k) order.
    """
    flat_e = experts.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    inv = jnp.argsort(order, stable=True)
    sorted_e = flat_e[order]
    idx = jnp.arange(flat_e.shape[0])
    seg_start = jnp.where(
        jnp.concatenate([jnp.array([True]), sorted_e[1:] != sorted_e[:-1]]), idx, 0
    )
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    rank_sorted = idx - seg_start
    rank = rank_sorted[inv]  # rank within expert, original order
    slot = jnp.where(rank < capacity, rank, -1)
    return flat_e, slot


def _expert_ffn(params, buckets, compute):
    """buckets [E, C, D] -> [E, C, D] via per-expert SwiGLU (grouped GEMM)."""
    wg = params["w_gate"].astype(compute)
    wu = params["w_up"].astype(compute)
    wd = params["w_down"].astype(compute)
    h = swiglu(jnp.einsum("ecd,edf->ecf", buckets, wg), jnp.einsum("ecd,edf->ecf", buckets, wu))
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_local(params, x_flat, cfg: ModelConfig, *, impl: str = "xla"):
    """Reference MoE on one shard.  x_flat [T, D] -> [T, D], aux loss."""
    moe = cfg.moe
    compute = x_flat.dtype
    t = x_flat.shape[0]
    weights, experts, aux = router_topk(params["router"], x_flat, moe.top_k)
    capacity = max(int(moe.capacity_factor * t * moe.top_k / moe.n_experts), moe.top_k)
    flat_e, slot = _dispatch_indices(experts, moe.top_k, moe.n_experts, capacity)

    token_of = jnp.repeat(jnp.arange(t), moe.top_k)
    keep = slot >= 0
    buckets = jnp.zeros((moe.n_experts, capacity, x_flat.shape[1]), compute)
    buckets = buckets.at[flat_e, jnp.where(keep, slot, 0)].add(
        jnp.where(keep[:, None], x_flat[token_of], 0.0)
    )
    if impl == "pallas":
        out_buckets = gmm_ops.expert_ffn(params, buckets)
    else:
        out_buckets = _expert_ffn(params, buckets, compute)
    gathered = out_buckets[flat_e, jnp.where(keep, slot, 0)]  # [T*k, D]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w = weights.reshape(-1)[:, None].astype(compute)
    out = jnp.zeros_like(x_flat)
    out = out.at[token_of].add(gathered * w)
    return out, aux


def moe_sharded_a2a(params, x_flat, cfg: ModelConfig, *, model_axis: str = "model", key=None):
    """Token-sharded expert parallelism (training / prefill shapes).

    Tokens are partitioned over (dp x model) ranks; experts over ``model``.
    Dispatch: local buckets [E, C, D] -> all_to_all(model) ->
    [E_local, M*C, D] -> grouped FFN -> reverse a2a -> combine.
    The a2a rides only the innermost (cheapest) mesh axis — CLEX level 1.
    x_flat: [T_local, D] with distinct tokens on every rank.
    """
    moe = cfg.moe
    compute = x_flat.dtype
    t = x_flat.shape[0]

    shift = None
    if moe.valiant_shuffle and key is not None:
        # lightweight Valiant: rotate tokens by a random offset so that
        # correlated (positional) expert hotspots spread over buckets
        shift = jax.random.randint(key, (), 0, t)
        x_flat = jnp.roll(x_flat, shift, axis=0)

    weights, experts, aux = router_topk(params["router"], x_flat, moe.top_k)
    capacity = max(int(moe.capacity_factor * t * moe.top_k / moe.n_experts), moe.top_k)
    flat_e, slot = _dispatch_indices(experts, moe.top_k, moe.n_experts, capacity)
    token_of = jnp.repeat(jnp.arange(t), moe.top_k)
    keep = slot >= 0

    buckets = jnp.zeros((moe.n_experts, capacity, x_flat.shape[1]), compute)
    buckets = buckets.at[flat_e, jnp.where(keep, slot, 0)].add(
        jnp.where(keep[:, None], x_flat[token_of], 0.0)
    )
    # CLEX level-1 hop: experts live on the fast inner axis
    buckets = jax.lax.all_to_all(buckets, model_axis, split_axis=0, concat_axis=1, tiled=True)
    out_buckets = _expert_ffn(params, buckets, compute)
    out_buckets = jax.lax.all_to_all(
        out_buckets, model_axis, split_axis=1, concat_axis=0, tiled=True
    )
    gathered = out_buckets[flat_e, jnp.where(keep, slot, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w = weights.reshape(-1)[:, None].astype(compute)
    out = jnp.zeros_like(x_flat)
    out = out.at[token_of].add(gathered * w)

    if shift is not None:
        out = jnp.roll(out, -shift, axis=0)
    return out, aux[None]


def moe_replicated_ep(params, x_flat, cfg: ModelConfig, *, model_axis: str = "model"):
    """Decode-shape fallback: tokens replicated over ``model``; every rank
    runs only its local experts over the full token set and partial outputs
    are psum'ed (one all-reduce, like the TP MLP).  x_flat: [T_dp, D]."""
    moe = cfg.moe
    compute = x_flat.dtype
    t = x_flat.shape[0]
    m = jax.lax.axis_size(model_axis)
    rank = jax.lax.axis_index(model_axis)
    e_local = moe.n_experts // m

    weights, experts, aux = router_topk(params["router"], x_flat, moe.top_k)
    capacity = max(int(moe.capacity_factor * t * moe.top_k / moe.n_experts), moe.top_k)
    flat_e, slot = _dispatch_indices(experts, moe.top_k, moe.n_experts, capacity)
    token_of = jnp.repeat(jnp.arange(t), moe.top_k)
    local_e = flat_e - rank * e_local
    mine = (slot >= 0) & (local_e >= 0) & (local_e < e_local)

    buckets = jnp.zeros((e_local, capacity, x_flat.shape[1]), compute)
    buckets = buckets.at[
        jnp.where(mine, local_e, 0), jnp.where(mine, slot, 0)
    ].add(jnp.where(mine[:, None], x_flat[token_of], 0.0))
    out_buckets = _expert_ffn(params, buckets, compute)
    gathered = out_buckets[jnp.where(mine, local_e, 0), jnp.where(mine, slot, 0)]
    gathered = jnp.where(mine[:, None], gathered, 0.0)
    w = weights.reshape(-1)[:, None].astype(compute)
    partial = jnp.zeros_like(x_flat)
    partial = partial.at[token_of].add(gathered * w)
    return jax.lax.psum(partial, model_axis), aux[None]


def route_dropless(router_w, x_flat, moe):
    """DeepSeek's gate: logits over all experts in float32, softmax, top-k;
    the k weights renormalised (``norm_topk_prob``) or scaled by
    ``routed_scaling_factor``.  Returns (weights [T,k] f32, experts [T,k],
    Switch aux loss)."""
    logits = jnp.dot(x_flat.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, moe.top_k)
    if moe.top_k > 1 and moe.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    else:
        weights = weights * moe.routed_scaling_factor
    onehot = jax.nn.one_hot(experts[:, 0], moe.n_experts, dtype=jnp.float32)
    aux = moe.n_experts * jnp.mean(onehot.mean(0) * probs.mean(0))
    return weights, experts, aux


def moe_held(params, x_flat, cfg: ModelConfig, layer=None):
    """The held share's part of a dropless MoE layer, plus the shared
    experts.  x_flat [T, D] -> ([T, D], aux loss, held assignments, held
    experts with at least one assignment).

    In the decode loop the expert weights arrive as the whole layer stack
    (``[r, held, ...]``) with the loop's ``layer``.  The grouped product is a
    kernel that reads its weights group by group, so it is handed the stack
    as ``r * held`` groups of which only the layer's have rows, and reads the
    layer's experts where they lie: given the layer's slice, the kernel's
    operand would be a copy of every held weight, every layer, every step.

    Every (token, expert) assignment is sorted by the held expert it lands
    on, those on no held expert last; one grouped product a weight
    (``kernels.moe_gmm.ops.ragged_gmm``, group sizes from the sort)
    computes the held rows and nothing else, and each token sums its own k
    rows in a fixed order, so a row's output does not depend on what it is
    batched with.  The kernel reads an expert's weights only where its
    group has rows, hence the count of experts touched."""
    moe = cfg.moe
    compute = x_flat.dtype
    t, d = x_flat.shape
    k, held = moe.top_k, moe.n_held
    with jax.named_scope("moe.route"):
        weights, experts, aux = route_dropless(params["router"], x_flat, moe)
    with jax.named_scope("moe.held"):
        local = experts.reshape(-1) - moe.held_first  # [T*k]
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)  # not held: past the last group
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        rows = x_flat[order // k]
        w_gate, w_up, w_down = (params[name] for name in HELD_WEIGHTS)
        groups = sizes
        if w_gate.ndim == 4:  # [r, held, ...]: the decode loop's stack
            r = w_gate.shape[0]
            w_gate, w_up, w_down = (w.reshape((r * held,) + w.shape[2:])
                                    for w in (w_gate, w_up, w_down))
            groups = jax.lax.dynamic_update_slice(jnp.zeros(r * held, jnp.int32), sizes,
                                                  (layer * held,))

        def grouped(a, w):
            return gmm_ops.ragged_gmm(a, w.astype(compute), groups)

        h = swiglu(grouped(rows, w_gate), grouped(rows, w_up))
        y = grouped(h, w_down)
        # rows past the held groups are never computed: select, not scale
        y = jnp.where(mine[order][:, None], y.astype(jnp.float32), 0.0)
        y = jnp.zeros_like(y).at[order].set(y, unique_indices=True)
        y = (y.reshape(t, k, d) * weights[..., None]).sum(axis=1)
    out = y.astype(compute)
    if moe.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + mlp_apply(params["shared"], x_flat, compute)
    return out, aux, mine.sum(dtype=jnp.int32), (sizes > 0).sum(dtype=jnp.int32)


def moe_apply(params, x, cfg: ModelConfig, *, impl: str = "xla", key=None, mesh=None,
              layer=None):
    """[B, S, D] -> ([B, S, D], aux).  A config with a held expert share
    runs ``moe_held`` whatever the mesh (``layer``: see there), and its aux
    is ``{"balance": loss, "held": assignments computed, "held_experts":
    held experts given rows}``.  Otherwise the
    path follows the mesh threaded in by the caller (explicit
    Mesh/MeshContext; ambient ``use_mesh`` as fallback): token-sharded a2a
    EP when enough tokens, replicated EP for tiny (decode) token counts,
    single-device reference otherwise."""
    P = jax.sharding.PartitionSpec
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    if cfg.moe.n_held:
        out, aux, held, touched = moe_held(params, x_flat, cfg, layer)
        return out.reshape(b, s, d), {"balance": aux, "held": held, "held_experts": touched}
    mesh = jax_compat.resolve_mesh(mesh)
    if mesh is None or "model" not in mesh.axis_names or mesh.model_size() == 1:
        out, aux = moe_local(params, x_flat, cfg, impl=impl)
        return out.reshape(b, s, d), aux

    dp_axes = mesh.dp_axes()
    dp = mesh.dp_size()
    m_size = mesh.model_size()
    tokens = b * s
    names = set(dp_axes) | {"model"}

    if tokens % (dp * m_size) == 0 and tokens // (dp * m_size) >= cfg.moe.top_k:
        token_spec = P((*dp_axes, "model"), None)
        out, aux = jax_compat.shard_map(
            lambda p, xf: moe_sharded_a2a(p, xf, cfg, key=key),
            mesh=mesh,
            in_specs=(_expert_specs(cfg), token_spec),
            out_specs=(token_spec, P((*dp_axes, "model"))),
            axis_names=names,
        )(params, x_flat)
    else:
        shard_tokens = dp > 1 and tokens % dp == 0 and tokens >= dp
        token_spec = P(dp_axes, None) if shard_tokens else P(None, None)
        out, aux = jax_compat.shard_map(
            lambda p, xf: moe_replicated_ep(p, xf, cfg),
            mesh=mesh,
            in_specs=(_expert_specs(cfg), token_spec),
            out_specs=(token_spec, P((*dp_axes, "model"))),
            axis_names=names,
        )(params, x_flat)
    return out.reshape(b, s, d), aux.mean()


def _expert_specs(cfg: ModelConfig):
    P = jax.sharding.PartitionSpec
    return {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
