"""CLEX-inspired hierarchical collectives (docs/ARCHITECTURE.md Sec. 3).

A TPU multi-pod machine is a physical CLEX-like hierarchy: the innermost
mesh axis rides short intra-pod ICI links (the paper's level-1 clique), the
``pod`` axis rides scarce long links (top-level bundles).  The paper's
routing discipline maps onto collective schedules:

* ``hierarchical_all_reduce`` — A(2)-style staged gradient sync:
  reduce-scatter on the low (cheap) axes, all-reduce only shards across the
  top (expensive) axis, all-gather back on the low axes.  Cross-pod bytes
  drop by the low-axis size (16x on the production mesh).
* ``compressed_psum`` — the asymmetric-bandwidth principle taken further:
  int8 error-feedback quantisation applied only to top-level traffic.
* ``two_stage_all_to_all`` — the A(2) recursion itself: route within the
  clique to the gateway (a2a over the low axis grouping by destination
  super-shard), one hop across the bundle (a2a over the high axis), then
  deliver locally.  Used by expert-parallel MoE dispatch when experts span
  more than one mesh axis.

All functions are *manual-collective* primitives: call them inside
``shard_map`` regions (``launch.jax_compat.shard_map`` — version-portable)
whose ``axis_names`` include the axes used.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "hierarchical_all_reduce",
    "compressed_psum",
    "quantize_int8",
    "dequantize_int8",
    "two_stage_all_to_all",
    "CollectiveCostModel",
]


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantisation.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compressed_psum(x: jax.Array, axis: str) -> tuple[jax.Array, jax.Array]:
    """All-reduce over ``axis`` moving int8 + one fp32 scale per shard
    instead of full-precision tensors (4x fewer bytes than fp32, 2x vs
    bf16).  Implemented as quantise -> all_gather -> local dequant-sum,
    which is byte-optimal for the small pod counts where this applies.

    Returns (sum, quantisation_error) — feed the error back into the next
    step's gradients (error feedback) to keep convergence unbiased.
    """
    q, scale = quantize_int8(x)
    err = x - dequantize_int8(q, scale, x.dtype)
    qs = jax.lax.all_gather(q, axis)  # [P, ...] int8
    ss = jax.lax.all_gather(scale, axis)  # [P]
    shape = (-1,) + (1,) * (q.ndim)
    total = jnp.sum(qs.astype(jnp.float32) * ss.reshape(shape), axis=0)
    return total.astype(x.dtype), err


def error_feedback_slots(params, n_low: int):
    """Zero residual slots matching the reduce-scattered shard shapes of
    ``hierarchical_all_reduce`` with one low axis of size ``n_low``."""
    return jax.tree.map(
        lambda p: jnp.zeros((-(-p.size // n_low),), jnp.float32), params
    )


def hierarchical_all_reduce(
    tree,
    low_axes: Sequence[str] = ("data",),
    high_axis: str | None = "pod",
    average: bool = True,
    compress_high: bool = False,
    residuals=None,
):
    """CLEX-staged all-reduce of a gradient pytree.

    reduce-scatter(low) -> [compressed] all-reduce(high) -> all-gather(low).
    Flat equivalent: psum over low+high.  The staged schedule sends
    1/prod(low) of the bytes across ``high_axis`` — the paper's rule of
    pushing traffic down to the cheap levels; ``compress_high`` quantises
    the (already 1/n_low-sized) cross-pod traffic to int8 with error
    feedback: pass the previous step's ``residuals``
    (``error_feedback_slots``) and carry the returned ones forward.

    Returns (reduced_tree, residual_tree).
    """
    denom = 1.0
    for ax in low_axes:
        denom *= jax.lax.axis_size(ax)
    if high_axis is not None:
        denom *= jax.lax.axis_size(high_axis)
    if residuals is None:
        residuals = jax.tree.map(lambda _: None, tree, is_leaf=lambda x: x is None)

    def reduce_leaf(g, res):
        orig_shape = g.shape
        flat = g.reshape(-1).astype(jnp.float32)
        chunk = flat
        for ax in low_axes:
            size = jax.lax.axis_size(ax)
            if chunk.shape[0] % size:
                pad = size - chunk.shape[0] % size
                chunk = jnp.concatenate([chunk, jnp.zeros((pad,), chunk.dtype)])
            chunk = jax.lax.psum_scatter(chunk, ax, scatter_dimension=0, tiled=True)
        err = jnp.zeros_like(chunk)
        if high_axis is not None:
            if compress_high:
                if res is not None:
                    chunk = chunk + res
                chunk, err = compressed_psum(chunk, high_axis)
            else:
                chunk = jax.lax.psum(chunk, high_axis)
        for ax in reversed(low_axes):
            chunk = jax.lax.all_gather(chunk, ax, axis=0, tiled=True)
        total = chunk[: flat.shape[0]].reshape(orig_shape)
        if average:
            total = total / denom
        return total.astype(g.dtype), err

    leaves, treedef = jax.tree.flatten(tree)
    res_leaves = jax.tree.leaves(residuals) if compress_high and residuals else [None] * len(leaves)
    if len(res_leaves) != len(leaves):
        res_leaves = [None] * len(leaves)
    out = [reduce_leaf(g, r) for g, r in zip(leaves, res_leaves)]
    reduced = treedef.unflatten([t for t, _ in out])
    errors = treedef.unflatten([e for _, e in out])
    return reduced, errors


def two_stage_all_to_all(
    x: jax.Array,
    low_axis: str,
    high_axis: str,
    split_axis: int = 0,
    concat_axis: int = 0,
):
    """A(2) as a collective: all-to-all across the product axis
    (low x high) staged as (i) a2a over ``low_axis`` grouping entries by
    destination high-shard (route to the gateway inside the clique), then
    (ii) a2a over ``high_axis`` (the bundle hop).

    ``x`` is split along ``split_axis`` into low*high equal destination
    groups ordered as (high, low) major/minor.  The result concatenates
    source shards along ``concat_axis`` in the same (high, low) order,
    exactly matching a flat ``all_to_all`` over a ("high","low") product
    axis — verified in tests.
    """
    nl, nh = jax.lax.axis_size(low_axis), jax.lax.axis_size(high_axis)
    assert x.shape[split_axis] % (nl * nh) == 0
    # stage 1: within the clique, regroup so each low-rank holds the traffic
    # of its gateway slot for every destination high-shard
    x = _moveaxis_split(x, split_axis, nh * nl)
    # x now [nh*nl, ...]: destination groups, (high, low) order
    x = x.reshape((nh, nl) + x.shape[1:])
    x = jax.lax.all_to_all(x, low_axis, split_axis=1, concat_axis=1, tiled=False)
    # each low-rank now holds [nh, 1, src_low, ...] -> hop across the bundle
    x = jax.lax.all_to_all(x, high_axis, split_axis=0, concat_axis=0, tiled=False)
    # x [nh(src_high), src_low? ...] reorder to (src_high, src_low) flat groups
    x = x.reshape((nh * nl,) + x.shape[2:])
    return _merge_to_axis(x, concat_axis)


def _moveaxis_split(x, split_axis, groups):
    """[... split ...] -> [groups, ... split/groups ...]."""
    shape = x.shape
    new = shape[:split_axis] + (groups, shape[split_axis] // groups) + shape[split_axis + 1 :]
    x = x.reshape(new)
    return jnp.moveaxis(x, split_axis, 0)


def _merge_to_axis(x, concat_axis):
    """[groups, ...] -> merge groups into ``concat_axis``."""
    x = jnp.moveaxis(x, 0, concat_axis)
    shape = x.shape
    new = shape[:concat_axis] + (shape[concat_axis] * shape[concat_axis + 1],) + shape[
        concat_axis + 2 :
    ]
    return x.reshape(new)


@dataclasses.dataclass(frozen=True)
class CollectiveCostModel:
    """Byte/latency model for flat vs hierarchical schedules (used by the
    roofline report and the collective benchmarks).

    ici_bw:  per-link intra-pod bandwidth (bytes/s)
    dcn_bw:  per-chip cross-pod bandwidth (bytes/s) — the scarce level
    """

    ici_bw: float = 50e9  # ~50 GB/s/link ICI (assignment constants)
    dcn_bw: float = 6.25e9  # ~1/8 of ICI: cross-pod links are the slow level
    ici_latency: float = 1e-6  # per-message setup/hop overhead (CLEX's c_h)
    dcn_latency: float = 10e-6
    quant_bw: float = 100e9  # int8 quantise/dequantise throughput (bytes/s)
    # KV-cache memory hierarchy (docs/SERVING.md, tiered pooling): each hop
    # down the hierarchy is slower and farther, like the CLEX levels
    hbm_host_bw: float = 16e9  # device <-> host staging (PCIe-class)
    hbm_host_latency: float = 25e-6
    host_pooled_bw: float = 4e9  # host <-> pooled/far memory (CXL-class)
    host_pooled_latency: float = 150e-6
    prefill_s_per_token: float = 2e-5  # modeled cost of re-prefilling a token

    def degraded(self, dcn_factor: float) -> "CollectiveCostModel":
        """The same machine with the scarce top-level links running at
        ``dcn_factor`` of nominal bandwidth (a top-level bundle fault)."""
        if not 0.0 < dcn_factor:
            raise ValueError(f"dcn_factor must be positive, got {dcn_factor}")
        return dataclasses.replace(self, dcn_bw=self.dcn_bw * dcn_factor)

    def flat_all_reduce(self, bytes_per_chip: float, n_low: int, n_pods: int) -> float:
        """Ring all-reduce over the full (low x pod) group: every byte
        crosses the pod boundary ~once; bottleneck is the slow link."""
        group = n_low * n_pods
        wire = 2.0 * bytes_per_chip * (group - 1) / group
        bw = self.dcn_bw if n_pods > 1 else self.ici_bw
        lat = self.dcn_latency if n_pods > 1 else self.ici_latency
        return wire / bw + 2 * (group - 1) * lat

    def hierarchical_all_reduce(
        self, bytes_per_chip: float, n_low: int, n_pods: int, compress_ratio: float = 1.0
    ) -> float:
        rs = bytes_per_chip * (n_low - 1) / n_low / self.ici_bw + (n_low - 1) * self.ici_latency
        shard = bytes_per_chip / n_low * compress_ratio
        ar_high = (
            2.0 * shard * (n_pods - 1) / n_pods / self.dcn_bw
            + 2 * (n_pods - 1) * self.dcn_latency
            if n_pods > 1
            else 0.0
        )
        ag = bytes_per_chip * (n_low - 1) / n_low / self.ici_bw + (n_low - 1) * self.ici_latency
        return rs + ar_high + ag

    def flat_all_to_all(self, bytes_per_chip: float, n_low: int, n_pods: int) -> float:
        """Direct flows to every peer: (group-1) messages per chip, of which
        (group - n_low) cross the pod boundary individually — the many-small-
        flows regime the CLEX delay analysis penalises."""
        group = n_low * n_pods
        cross = bytes_per_chip * (group - n_low) / group  # bytes leaving the pod
        local = bytes_per_chip * (n_low - 1) / group
        wire = max(cross / self.dcn_bw, local / self.ici_bw) if n_pods > 1 else (
            local / self.ici_bw
        )
        lat = (n_low - 1) * self.ici_latency + (group - n_low) * self.dcn_latency
        return wire + lat

    def two_stage_all_to_all(self, bytes_per_chip: float, n_low: int, n_pods: int) -> float:
        """A(2): aggregate inside the clique, then n_pods-1 large bundle
        hops — same bytes, exponentially fewer cross-pod messages."""
        stage1 = bytes_per_chip * (n_low - 1) / n_low / self.ici_bw + (n_low - 1) * self.ici_latency
        stage2 = (
            bytes_per_chip * (n_pods - 1) / n_pods / self.dcn_bw
            + (n_pods - 1) * self.dcn_latency
            if n_pods > 1
            else 0.0
        )
        return stage1 + stage2

    # ---------------- training-orchestrator hooks (docs/TRAINING.md) ----------

    def grad_sync_cost(
        self,
        bytes_per_chip: float,
        n_low: int,
        n_pods: int,
        compressed: bool = False,
        compress_ratio: float = 0.26,
    ) -> float:
        """Wall-clock seconds for one staged gradient sync.  With
        ``compressed`` the (already reduce-scattered) cross-pod shard moves
        int8+scale (``compress_ratio`` of fp32 bytes) but pays quantise +
        dequantise passes over the shard at ``quant_bw``.  The orchestrator
        prices both tiers with this (on a ``degraded()`` model when a
        top-level link fault is active) and switches to the compressed tier
        only when the plain tier has become markedly more expensive than its
        fault-free cost — int8 spends accuracy headroom, so it is a repair,
        not a default."""
        base = self.hierarchical_all_reduce(
            bytes_per_chip, n_low, n_pods,
            compress_ratio=compress_ratio if compressed else 1.0,
        )
        if not compressed or n_pods <= 1:
            return base
        shard = bytes_per_chip / max(n_low, 1)
        return base + 2.0 * shard / self.quant_bw

    # ---------------- serving-scheduler hooks (docs/SERVING.md) ----------------

    _KV_TIERS = ("hbm", "host", "pooled")

    def tier_transfer_cost(self, nbytes: float, src: str, dst: str) -> float:
        """Seconds to move ``nbytes`` of KV cache between memory tiers.
        Adjacent hops are hbm<->host (staging link) and host<->pooled (far
        memory fabric); a hbm<->pooled move pays both hops — the same
        store-and-forward accounting the CLEX levels use."""
        order = self._KV_TIERS
        if src not in order or dst not in order:
            raise ValueError(f"unknown tier in {src!r} -> {dst!r}; tiers are {order}")
        i, j = order.index(src), order.index(dst)
        lo, hi = min(i, j), max(i, j)
        hop_bw = (self.hbm_host_bw, self.host_pooled_bw)
        hop_lat = (self.hbm_host_latency, self.host_pooled_latency)
        return sum(nbytes / hop_bw[h] + hop_lat[h] for h in range(lo, hi))

    def wakeup_cost(self, nbytes: float, tier: str = "host") -> float:
        """Seconds to page a demoted session's cache row back into HBM."""
        return self.tier_transfer_cost(nbytes, tier, "hbm")

    def cold_prefill_cost(self, prompt_tokens: int) -> float:
        """Modeled seconds to rebuild a cache by re-prefilling from scratch —
        what waking a resident session avoids.  The ``cost_aware`` scheduler
        compares this against :meth:`wakeup_cost` when ordering admission."""
        return max(float(prompt_tokens), 0.0) * self.prefill_s_per_token

    def migration_cost(self, nbytes: float, overhead_s: float = 0.0) -> float:
        """Modeled seconds to migrate ``nbytes`` of live state onto a new
        mesh: a device -> host -> device round trip over the staging link
        (the extract/insert wire path both orchestrators use), plus a flat
        ``overhead_s`` for remesh/recompile.  ``runtime/autoscale.py``
        compares this against the remaining straggler slowdown to decide
        whether a drain is worth its price (docs/TRAINING.md,
        docs/SERVING.md)."""
        return (
            2.0 * (max(nbytes, 0.0) / self.hbm_host_bw + self.hbm_host_latency)
            + max(overhead_s, 0.0)
        )

    def moe_dispatch_cost(
        self,
        tokens: float,
        d_model: int,
        top_k: int,
        n_low: int,
        n_pods: int,
        bytes_per_elem: float = 2.0,
        hierarchical: bool = True,
    ) -> float:
        """Wall-clock seconds for one MoE dispatch (or combine) all-to-all
        moving ``tokens`` activations of width ``d_model`` to ``top_k``
        experts across an (n_low x n_pods) mesh.  The continuous-batching
        scheduler prices admission with this: hierarchical=True is the CLEX
        level-1 rule (stage traffic through the cheap inner axis)."""
        if tokens <= 0 or top_k <= 0:
            return 0.0
        chips = max(n_low, 1) * max(n_pods, 1)
        bytes_per_chip = tokens * top_k * d_model * bytes_per_elem / chips
        fn = self.two_stage_all_to_all if hierarchical else self.flat_all_to_all
        return fn(bytes_per_chip, n_low, n_pods)

    def decode_step_a2a_cost(
        self,
        batch: float,
        d_model: int,
        top_k: int,
        n_moe_layers: int,
        n_low: int,
        n_pods: int,
        bytes_per_elem: float = 2.0,
        hierarchical: bool = True,
    ) -> float:
        """All-to-all seconds for one decode step of ``batch`` co-scheduled
        requests (one token each): dispatch + combine per MoE layer."""
        if n_moe_layers <= 0 or batch <= 0:
            return 0.0
        one = self.moe_dispatch_cost(
            batch, d_model, top_k, n_low, n_pods, bytes_per_elem, hierarchical
        )
        return 2.0 * n_moe_layers * one

    def coschedule_gain(
        self,
        batch: int,
        d_model: int,
        top_k: int,
        n_moe_layers: int,
        n_low: int,
        n_pods: int,
        bytes_per_elem: float = 2.0,
    ) -> float:
        """Per-request seconds saved by batching ``batch`` MoE-heavy requests
        into one decode step instead of ``batch`` separate steps: wire bytes
        scale with the batch but the (n_pods - 1) bundle-hop latencies — the
        term the CLEX delay analysis bounds — amortise across it.  The
        scheduler co-schedules MoE-heavy requests while this gain is
        positive."""
        if batch <= 1 or n_moe_layers <= 0:
            return 0.0
        solo = self.decode_step_a2a_cost(
            1, d_model, top_k, n_moe_layers, n_low, n_pods, bytes_per_elem
        )
        together = (
            self.decode_step_a2a_cost(
                batch, d_model, top_k, n_moe_layers, n_low, n_pods, bytes_per_elem
            )
            / batch
        )
        return solo - together
