"""Continuous-batching serving: RequestQueue -> Scheduler -> KVPool -> decode.

The subsystem replaces the one-shot batch generator with the serving loop a
production deployment needs (docs/SERVING.md):

* ``RequestQueue`` — admission-ordered queue of ragged requests (each with
  its own prompt length, token budget, temperature, arrival time).
* ``KVPool`` — a pooled, slot-indexed KV cache: ``n_slots`` fixed-size cache
  rows allocated per request and evicted/reused on completion, instead of
  rebuilding the whole cache per batch.
* ``TieredKVPool`` — the same pool behind an explicit memory hierarchy
  (HBM slots -> host rows -> a modeled pooled/far tier): a finished
  session's row is *demoted* to host instead of discarded, spilled to the
  pooled tier LRU-first when host fills, and paged back on wakeup so a
  resumed session skips re-prefill entirely.  Transfers are priced by
  ``CollectiveCostModel.tier_transfer_cost`` — the memory hierarchy is
  treated like another CLEX level (docs/SERVING.md, memory hierarchy).
* ``Scheduler`` — decides which queued requests enter free decode slots.
  The ``cost_aware`` policy prices admission with
  ``core.collectives.CollectiveCostModel``: MoE-dispatch-heavy requests are
  co-scheduled into the same decode steps so their expert-parallel
  all-to-all rides the cheap inner mesh axis together (the CLEX level-1
  rule — push traffic down to the cheap level, amortise the scarce
  bundle-hop latency across the batch).
* ``ContinuousBatchingEngine`` — prefill/decode interleaving with
  per-request completion: finished requests free their slot immediately
  (no head-of-line blocking) and the next queued request is prefilled into
  it while the rest of the batch keeps decoding.

``ServingEngine`` (bottom of the file) keeps the seed's one-shot lockstep
``generate()`` unchanged — it is both the backward-compatible API and the
baseline that ``benchmarks/serving_bench.py`` measures continuous batching
against.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import itertools
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.collectives import CollectiveCostModel
from ..models import Model
from ..obs import NULL_SPAN, get_obs, lane
from ..obs.metrics import MetricsRegistry, registry_field

__all__ = [
    "Request",
    "RequestQueue",
    "KVPool",
    "TierConfig",
    "SessionRecord",
    "TieredKVPool",
    "SchedulerConfig",
    "Scheduler",
    "ContinuousBatchingEngine",
    "ServingEngine",
]


# --------------------------------------------------------------------------
# requests
# --------------------------------------------------------------------------


QUEUED, RUNNING, FINISHED = "queued", "running", "finished"
# SHED: rejected at submit (queue over max_queue_depth) or dropped past its
# deadline — never allocated a KV slot, never counted toward goodput
SHED = "shed"

# compiled closures (engine prefill/decode, pool slot-writes) shared across
# instances with the same configuration — a migration or restart that lands
# on a previously-seen configuration pays no recompile
_JIT_CACHE: dict = {}


@dataclasses.dataclass
class Request:
    """One generation request moving through queued -> running -> finished."""

    rid: int
    prompt: np.ndarray  # [L] int32
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    arrival_time: Optional[float] = None  # None = available immediately
    # dispatch_weight: estimated MoE all-to-all bytes per decoded token
    # (0 for dense models); drives cost-aware co-scheduling
    dispatch_weight: float = 0.0
    # session_id: multi-turn identity on a TieredKVPool engine — on finish
    # the cache row is demoted (not discarded) and a later request with the
    # same session_id wakes it up instead of re-prefilling
    session_id: Optional[int] = None
    # deadline: absolute time after which the request is worthless; an
    # unadmitted request past its deadline is dropped (state SHED) and
    # refunded from the queue instead of wasting a slot
    deadline: Optional[float] = None

    state: str = QUEUED
    tokens_out: list = dataclasses.field(default_factory=list)
    deferred: int = 0  # admission rounds the scheduler has deferred this request
    slot: Optional[int] = None
    # stamps on the clock of step()'s ``now``, taken when the work is done
    # on the host: t_admit as the request's prefill starts (or its session
    # row is paged back in), t_first / t_done once its first / last token
    # is on the host
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # sampling identity: a resumed session keeps its original request id and
    # token-index offset inside the sampling stream, so the continuation is
    # bit-identical to a never-demoted run (set at admission from the
    # session record; defaults mean "fresh stream")
    sample_rid: Optional[int] = None
    idx_base: int = 0
    last_token: Optional[int] = None  # last sampled token (pending decode input)
    # wakeup hint refreshed each admission round: which tier this request's
    # session is resident in (None = must cold-prefill), and the row size
    # the scheduler prices the wakeup transfer with
    resume_tier: Optional[str] = None
    resume_bytes: int = 0

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def moe_heavy(self) -> bool:
        return self.dispatch_weight > 0.0

    @property
    def done(self) -> bool:
        return self.state == FINISHED


class RequestQueue:
    """FIFO of queued requests; ``arrived(now)`` filters by arrival time.

    Closed-loop requests (``arrival_time=None``) go straight onto an
    eligible list kept in submission order; open-loop requests wait in a
    min-heap keyed by arrival time and graduate to the eligible list as the
    clock passes them.  ``arrived(now)`` is O(eligible + arrivals·log
    pending) and ``remove`` is amortised O(1) via lazy deletion — the
    previous deque implementation rescanned and rebuilt the whole queue on
    every engine step, O(queue²) over a long open-loop soak."""

    _COMPACT_AT = 64  # lazy-deleted entries tolerated before a sweep

    def __init__(self):
        self._seq = itertools.count()  # submission order, total across both lists
        self._ready: list[tuple[int, Request]] = []  # eligible, sorted by seq
        self._pending: list[tuple[float, int, Request]] = []  # heap by arrival
        self._gone: set[int] = set()  # id()s removed but not yet swept

    def push(self, req: Request) -> None:
        seq = next(self._seq)
        if req.arrival_time is None:
            self._ready.append((seq, req))  # seq is increasing: stays sorted
        else:
            heapq.heappush(self._pending, (req.arrival_time, seq, req))

    def __len__(self) -> int:
        return len(self._ready) + len(self._pending) - len(self._gone)

    def __iter__(self):
        live = [(s, r) for s, r in self._ready if id(r) not in self._gone]
        live += [(s, r) for _, s, r in self._pending if id(r) not in self._gone]
        return iter(r for _, r in sorted(live, key=lambda e: e[0]))

    def _graduate(self, now: float) -> None:
        while self._pending and self._pending[0][0] <= now:
            _, seq, req = heapq.heappop(self._pending)
            if id(req) in self._gone:
                self._gone.discard(id(req))
                continue
            bisect.insort(self._ready, (seq, req))

    def _compact(self) -> None:
        if len(self._gone) < self._COMPACT_AT:
            return
        self._ready = [(s, r) for s, r in self._ready if id(r) not in self._gone]
        still = {id(r) for _, r in self._ready}
        still |= {id(r) for _, _, r in self._pending}
        self._gone &= still  # entries left only in the heap stay lazily dead

    def arrived(self, now: Optional[float]) -> list[Request]:
        """Requests eligible for admission at virtual/wall time ``now``
        (``now=None`` treats every queued request as arrived)."""
        if now is None:
            return list(self)
        self._graduate(now)
        self._compact()
        return [r for _, r in self._ready if id(r) not in self._gone]

    def remove(self, reqs: Sequence[Request]) -> None:
        self._gone.update(id(r) for r in reqs)

    def next_arrival(self) -> Optional[float]:
        """Earliest not-yet-graduated arrival time (the engine only consults
        this when idle, i.e. after ``arrived`` drained everything due)."""
        while self._pending and id(self._pending[0][2]) in self._gone:
            self._gone.discard(id(heapq.heappop(self._pending)[2]))
        return self._pending[0][0] if self._pending else None


# --------------------------------------------------------------------------
# pooled KV cache
# --------------------------------------------------------------------------


ROWS = 1  # the rows (slots) axis of every decode-cache leaf: [r, B, ...]


def merge_slot_caches(pool_caches, one_caches, slot):
    """Write a single-request decode cache (batch dim 1) into row ``slot`` of
    the pooled cache.  Pure — composes into jitted prefill."""

    def write(pool_leaf, one_leaf):
        return jax.lax.dynamic_update_slice_in_dim(
            pool_leaf, one_leaf.astype(pool_leaf.dtype), slot, axis=ROWS,
            allow_negative_indices=False,
        )

    return jax.tree.map(write, pool_caches, one_caches)


class KVPool:
    """``n_slots`` fixed-size KV-cache rows, allocated per request and
    evicted (freed + reused) on completion.

    The pooled cache is the model's native decode layout with batch dim
    ``n_slots``: layer-stacked, rows on axis ``ROWS`` (1) of every leaf (GQA
    K and V side by side, head-major ``[r, n_slots, Kv, L, 2*D]``).  Each slot
    holds ``capacity`` ring entries (sliding-window layers hold
    ``min(capacity, window)`` — same rule as ``Model.prepare_decode_caches``).
    Freed slots are reused LIFO so a hot cache row is recycled immediately.
    """

    tiered = False  # TieredKVPool overrides; engines branch on this
    stacked = True  # every leaf carries a leading layer axis: rows are axis ROWS

    def __init__(self, model: Model, n_slots: int, capacity: int):
        if n_slots < 1:
            raise ValueError("KVPool needs at least one slot")
        self.model = model
        self.n_slots = n_slots
        self.capacity = capacity
        self.caches = model.init_cache(n_slots, capacity)
        self._free: list[int] = list(range(n_slots - 1, -1, -1))  # pop() -> slot 0 first
        self.slot_rid: list[Optional[int]] = [None] * n_slots
        self.n_alloc = 0
        self.n_evict = 0
        self.high_water = 0
        # the slot-write jit is shared across pools of the same layout, so a
        # migrated/rebuilt pool pays no recompile to re-insert its rows
        key = ("kvpool_write", model, n_slots, capacity)
        self._write = _JIT_CACHE.get(key)
        if self._write is None:
            self._write = jax.jit(merge_slot_caches, donate_argnums=0)
            _JIT_CACHE[key] = self._write

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_slots - len(self._free)

    # uniform residency accounting with TieredKVPool: a plain pool only
    # holds sessions while they occupy an HBM slot
    @property
    def resident_sessions(self) -> int:
        return self.n_used

    @property
    def demoted_sessions(self) -> int:
        return 0

    def active_slots(self) -> list[int]:
        return [s for s, r in enumerate(self.slot_rid) if r is not None]

    def allocate(self, rid: int) -> Optional[int]:
        """Claim a free slot for ``rid``; None when the pool is exhausted."""
        if not self._free:
            return None
        slot = self._free.pop()
        self.slot_rid[slot] = rid
        self.n_alloc += 1
        self.high_water = max(self.high_water, self.n_used)
        return slot

    def free(self, slot: int) -> None:
        """Evict ``slot``'s cache row: the slot returns to the free list and
        its contents are dead (fully overwritten by the next prefill write)."""
        if self.slot_rid[slot] is None:
            raise ValueError(f"slot {slot} is not allocated")
        self.slot_rid[slot] = None
        self._free.append(slot)
        self.n_evict += 1

    def write(self, slot: int, one_caches) -> None:
        """Install a prepared single-request decode cache into ``slot``."""
        self.caches = self._write(self.caches, one_caches, jnp.int32(slot))

    # -------- migration primitives (runtime/serving_elastic.py) --------

    def extract(self, slot: int):
        """Copy ``slot``'s live cache row out as a host-side batch-1 cache
        tree — the migration wire format: device-independent, so it can be
        re-inserted into a pool living on any survivor mesh, bit-exact."""
        if self.slot_rid[slot] is None:
            raise ValueError(f"slot {slot} is not allocated")
        return jax.tree.map(lambda c: np.asarray(c[:, slot : slot + 1]), self.caches)

    def insert(self, slot: int, row) -> None:
        """Install an extracted row into (allocated) ``slot`` — the inverse
        of :meth:`extract`; ``extract -> insert`` round-trips bit-exact."""
        if self.slot_rid[slot] is None:
            raise ValueError(f"slot {slot} is not allocated — allocate before insert")
        self.write(slot, row)

    def extract_all(self, slots: Sequence[int]) -> list:
        """Extract many slots with a single device->host sync: one gather of
        every requested row, one ``device_get`` of the gathered tree, then
        host-side slicing into per-slot rows.  Bit-identical to calling
        :meth:`extract` per slot, but a k-slot migration pays one sync
        instead of k — the dominant term in the migration pause."""
        for s in slots:
            if self.slot_rid[s] is None:
                raise ValueError(f"slot {s} is not allocated")
        if not slots:
            return []
        idx = jnp.asarray(list(slots), jnp.int32)
        gathered = jax.device_get(
            jax.tree.map(lambda c: jnp.take(c, idx, axis=ROWS), self.caches)
        )
        return [
            jax.tree.map(lambda c: np.take(c, [i], axis=ROWS), gathered)
            for i in range(len(slots))
        ]

    def insert_all(self, slots: Sequence[int], rows: Sequence) -> None:
        """Install many extracted rows with one host->device dispatch: the
        rows are concatenated host-side and scattered into their slots by a
        single jitted update — the inverse of :meth:`extract_all`."""
        if len(slots) != len(rows):
            raise ValueError(f"{len(slots)} slots but {len(rows)} rows")
        if not slots:
            return
        for s in slots:
            if self.slot_rid[s] is None:
                raise ValueError(f"slot {s} is not allocated — allocate before insert")
        packed = jax.tree.map(lambda *ls: np.concatenate(ls, axis=ROWS), *rows)
        key = ("kvpool_write_many", self.model, self.n_slots, self.capacity, len(slots))
        write_many = _JIT_CACHE.get(key)
        if write_many is None:
            k = len(slots)

            @partial(jax.jit, donate_argnums=0)
            def write_many(pool_caches, packed_rows, slot_idx):
                for i in range(k):
                    row = jax.tree.map(
                        lambda c: jax.lax.slice_in_dim(c, i, i + 1, axis=ROWS), packed_rows
                    )
                    pool_caches = merge_slot_caches(pool_caches, row, slot_idx[i])
                return pool_caches

            _JIT_CACHE[key] = write_many
        self.caches = write_many(
            self.caches, packed, jnp.asarray(list(slots), jnp.int32)
        )

    def check(self) -> None:
        """Slot-accounting invariants (the chaos harness calls this after
        every migration): the free list and the allocated slots partition the
        pool, and no request id owns two slots."""
        free = set(self._free)
        used = {s for s, r in enumerate(self.slot_rid) if r is not None}
        if len(free) != len(self._free):
            raise AssertionError(f"free list has duplicates: {self._free}")
        if free & used or free | used != set(range(self.n_slots)):
            raise AssertionError(
                f"slot accounting corrupt: free={sorted(free)} used={sorted(used)} "
                f"of {self.n_slots} slots"
            )
        rids = [r for r in self.slot_rid if r is not None]
        if len(rids) != len(set(rids)):
            raise AssertionError(f"request id owns two slots: {self.slot_rid}")


# --------------------------------------------------------------------------
# tiered memory hierarchy: HBM slots -> host rows -> modeled pooled tier
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Capacities of the demoted-session tiers (docs/SERVING.md).

    host_sessions    cache rows kept in host memory (real numpy trees —
                     wakeup pays one host->HBM insert)
    pooled_sessions  rows spilled onward to the modeled pooled/far tier
                     (rows stay host-resident in this process; the extra
                     pooled<->host hop is *priced*, not performed)
    """

    host_sessions: int = 64
    pooled_sessions: int = 256

    def __post_init__(self):
        if self.host_sessions < 0 or self.pooled_sessions < 0:
            raise ValueError("tier capacities must be >= 0")


@dataclasses.dataclass
class SessionRecord:
    """A demoted session: everything needed to resume decode bit-exact.

    ``row`` is the :meth:`KVPool.extract` wire format (device-independent
    host tree); ``pos``/``last_token`` restore the ring position and the
    pending decode input; ``sample_rid``/``idx_base`` pin the sampling
    stream so the continuation is identical to a never-demoted run —
    including a cold re-prefill resume after the row was dropped."""

    sid: int
    pos: int
    last_token: int
    sample_rid: int
    idx_base: int
    tier: str = "host"  # "host" | "pooled" | "dropped"
    row: object = None  # None once dropped (metadata-only)
    nbytes: int = 0


class TieredKVPool(KVPool):
    """A :class:`KVPool` whose evictions feed a memory hierarchy instead of
    the void: HBM slots (active decode) -> host rows (demoted sessions,
    LRU) -> a modeled pooled/far tier -> metadata-only (dropped).

    * :meth:`demote` extracts a finishing slot's row through the migration
      wire format and parks it in the host ledger; host overflow spills the
      least-recently-demoted row to the pooled tier, pooled overflow drops
      the row and keeps only the sampling metadata (a later wakeup then
      re-prefills cold, still bit-exact).
    * :meth:`promote` pages a resident row back into a free HBM slot
      (pooled rows pay the extra modeled pooled->host hop first).
    * every transfer is priced by ``CollectiveCostModel.tier_transfer_cost``
      and accumulated in ``modeled_tier_s`` — the hierarchy is a CLEX level
      structure and its hops are billed like any other collective.

    Ledgers hold plain host data, so they survive a mesh collapse untouched:
    ``ContinuousBatchingEngine.migrate`` carries them to the rebuilt pool
    via :meth:`adopt`.
    """

    tiered = True

    def __init__(
        self,
        model: Model,
        n_slots: int,
        capacity: int,
        tiers: TierConfig = TierConfig(),
        cost_model: Optional[CollectiveCostModel] = None,
        obs=None,
    ):
        super().__init__(model, n_slots, capacity)
        self.tiers = tiers
        self.cost_model = cost_model or CollectiveCostModel()
        self._obs = obs if obs is not None else get_obs()
        self.host: OrderedDict[int, SessionRecord] = OrderedDict()
        self.pooled: OrderedDict[int, SessionRecord] = OrderedDict()
        self.dropped: dict[int, SessionRecord] = {}
        self.n_demote = 0
        self.n_promote = 0
        self.n_spill = 0
        self.n_refill = 0
        self.n_drop = 0
        self.modeled_tier_s = 0.0

    # ---------------- residency accounting ----------------

    @property
    def resident_sessions(self) -> int:
        """Sessions whose cache row is held *somewhere* in the hierarchy
        (active slot, host, or pooled) — the capacity headline the tiered
        bench reports per device."""
        return self.n_used + len(self.host) + len(self.pooled)

    @property
    def demoted_sessions(self) -> int:
        return len(self.host) + len(self.pooled)

    def _account(self, nbytes: int, src: str, dst: str) -> None:
        self.modeled_tier_s += self.cost_model.tier_transfer_cost(nbytes, src, dst)

    def session_tier(self, sid: int) -> Optional[str]:
        rec = self.lookup(sid)
        return rec.tier if rec is not None else None

    def lookup(self, sid: int) -> Optional[SessionRecord]:
        return self.host.get(sid) or self.pooled.get(sid) or self.dropped.get(sid)

    # ---------------- demotion / promotion ----------------

    def demote(self, slot: int, rec: SessionRecord) -> SessionRecord:
        """Evict ``slot`` into the hierarchy: extract the row to host (wire
        format), free the slot, and spill LRU-first past the tier caps."""
        obs = self._obs
        t0 = time.monotonic()
        rec.row = self.extract(slot)
        rec.nbytes = int(
            sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(rec.row))
        )
        if obs.enabled:
            # calibration: the hbm->host transfer price the hierarchy bills
            # vs the extract wall it actually took
            obs.calibration.observe(
                obs.calibration.record(
                    "tier_transfer",
                    self.cost_model.tier_transfer_cost(rec.nbytes, "hbm", "host"),
                    note="demote hbm->host",
                ),
                time.monotonic() - t0,
            )
            obs.tracer.instant("demote", "serve", sid=rec.sid, nbytes=rec.nbytes)
        self.free(slot)
        # a re-demoted session id supersedes any stale ledger entry
        self.host.pop(rec.sid, None)
        self.pooled.pop(rec.sid, None)
        self.dropped.pop(rec.sid, None)
        rec.tier = "host"
        self.host[rec.sid] = rec
        self.n_demote += 1
        self._account(rec.nbytes, "hbm", "host")
        while len(self.host) > self.tiers.host_sessions:
            sid, cold = self.host.popitem(last=False)  # least recently demoted
            cold.tier = "pooled"
            self.pooled[sid] = cold
            self.n_spill += 1
            self._account(cold.nbytes, "host", "pooled")
        while len(self.pooled) > self.tiers.pooled_sessions:
            sid, cold = self.pooled.popitem(last=False)
            cold.tier = "dropped"
            cold.row = None
            self.dropped[sid] = cold
            self.n_drop += 1
        return rec

    def promote(self, sid: int, rid: int) -> tuple[int, SessionRecord]:
        """Page session ``sid`` back into a freshly allocated HBM slot for
        request ``rid``; returns (slot, record).  Caller guarantees a free
        slot (admission is gated on ``n_free``)."""
        rec = self.host.pop(sid, None)
        if rec is None:
            rec = self.pooled.pop(sid, None)
            if rec is None:
                raise KeyError(f"session {sid} has no resident row to promote")
            self.n_refill += 1
            self._account(rec.nbytes, "pooled", "host")
        slot = self.allocate(rid)
        if slot is None:
            raise RuntimeError("promote called with no free slot")
        self.insert(slot, rec.row)
        self._account(rec.nbytes, "host", "hbm")
        self.n_promote += 1
        rec.row = None
        rec.tier = "hbm"
        return slot, rec

    def claim_dropped(self, sid: int) -> Optional[SessionRecord]:
        """Take the metadata-only record of a dropped session (cold resume:
        the caller re-prefills but keeps the sampling identity)."""
        return self.dropped.pop(sid, None)

    def adopt(self, old: "TieredKVPool") -> None:
        """Carry the demoted ledgers (and their counters) over from the pool
        being replaced — host rows are device-independent, so a mesh
        collapse must not touch them (``ContinuousBatchingEngine.migrate``)."""
        self.host = old.host
        self.pooled = old.pooled
        self.dropped = old.dropped
        self.n_demote = old.n_demote
        self.n_promote = old.n_promote
        self.n_spill = old.n_spill
        self.n_refill = old.n_refill
        self.n_drop = old.n_drop
        self.modeled_tier_s = old.modeled_tier_s

    def check(self) -> None:
        """Slot invariants plus tier-ledger invariants: a session lives in
        exactly one ledger, resident tiers hold real rows (dropped holds
        none), and no ledger exceeds its configured capacity."""
        super().check()
        sids = list(self.host) + list(self.pooled) + list(self.dropped)
        if len(sids) != len(set(sids)):
            raise AssertionError(f"session in two tiers: {sorted(sids)}")
        for name, ledger in (("host", self.host), ("pooled", self.pooled)):
            for sid, rec in ledger.items():
                if rec.row is None:
                    raise AssertionError(f"{name} session {sid} lost its row")
                if rec.tier != name:
                    raise AssertionError(
                        f"session {sid} in {name} ledger but tagged {rec.tier!r}"
                    )
        for sid, rec in self.dropped.items():
            if rec.row is not None:
                raise AssertionError(f"dropped session {sid} still holds a row")
        if len(self.host) > self.tiers.host_sessions:
            raise AssertionError(
                f"host ledger over capacity: {len(self.host)} > "
                f"{self.tiers.host_sessions}"
            )
        if len(self.pooled) > self.tiers.pooled_sessions:
            raise AssertionError(
                f"pooled ledger over capacity: {len(self.pooled)} > "
                f"{self.tiers.pooled_sessions}"
            )


# --------------------------------------------------------------------------
# scheduler
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Admission knobs (docs/SERVING.md has the full rationale).

    policy           "fcfs" (arrival order) or "cost_aware" (price MoE
                     dispatch with the CollectiveCostModel and co-schedule)
    a2a_budget_s     per-decode-step all-to-all budget: admission stops
                     adding MoE-heavy requests once the predicted step
                     a2a time would exceed this
    min_coschedule   hold MoE-heavy requests until this many can enter the
                     same step (amortise the bundle-hop latency), unless...
    max_defer_steps  ...a request has been deferred this many admission
                     rounds (aging — no starvation)
    work_conserving  never leave a slot idle when anything is queued, even
                     if over budget
    n_low / n_pods   mesh shape priced by the cost model (inner cheap axis
                     x scarce cross-pod axis)
    """

    policy: str = "cost_aware"
    a2a_budget_s: float = 2e-3
    min_coschedule: int = 2
    max_defer_steps: int = 8
    work_conserving: bool = True
    n_low: int = 8
    n_pods: int = 2
    bytes_per_elem: float = 2.0


class Scheduler:
    """Picks which arrived requests enter free decode slots.

    ``cost_aware`` implements the CLEX level-1 rule for serving: expert
    dispatch is the traffic that must ride the cheap inner axis, so requests
    that generate it are batched into the *same* decode steps (one staged
    all-to-all amortised over the co-scheduled group) instead of being
    spread thinly across steps where each would pay the scarce bundle-hop
    latency alone.  Light (dense) requests fill the remaining slots in
    arrival order.
    """

    def __init__(
        self,
        cfg: SchedulerConfig,
        cost_model: Optional[CollectiveCostModel] = None,
        d_model: int = 1024,
        top_k: int = 0,
        n_moe_layers: int = 0,
    ):
        if cfg.policy not in ("fcfs", "cost_aware"):
            raise ValueError(f"unknown policy {cfg.policy!r}")
        self.cfg = cfg
        self.cost_model = cost_model or CollectiveCostModel()
        self.d_model = d_model
        self.top_k = top_k
        self.n_moe_layers = n_moe_layers
        self.last_step_cost = 0.0  # predicted a2a seconds for the last admitted step

    def _step_cost(self, n_heavy: int) -> float:
        return self.cost_model.decode_step_a2a_cost(
            n_heavy,
            self.d_model,
            max(self.top_k, 1),
            max(self.n_moe_layers, 1),
            self.cfg.n_low,
            self.cfg.n_pods,
            self.cfg.bytes_per_elem,
        )

    def admission_cost(self, r: Request) -> float:
        """Seconds to get ``r`` decoding: waking a tier-resident session pays
        the (priced) row transfer; anything else pays a modeled cold
        prefill.  Used to order admission when sessions can be woken."""
        if r.resume_tier is not None:
            return self.cost_model.wakeup_cost(r.resume_bytes, r.resume_tier)
        return self.cost_model.cold_prefill_cost(r.prompt_len)

    def select(
        self,
        candidates: Sequence[Request],
        n_free: int,
        n_heavy_active: int = 0,
    ) -> list[Request]:
        """Choose up to ``n_free`` requests to admit this round.

        ``n_heavy_active`` is the number of MoE-heavy requests already
        decoding (they contribute to the step's all-to-all bill).
        """
        if n_free <= 0 or not candidates:
            return []
        if self.cfg.policy == "fcfs":
            return list(candidates[:n_free])

        heavy = [r for r in candidates if r.moe_heavy]
        light = [r for r in candidates if not r.moe_heavy]
        # tiered pooling: when any candidate can be *woken* (its session is
        # tier-resident), order each class by admission cost so a cheap
        # host-wakeup beats an expensive cold prefill for the scarce free
        # slots.  Stable sort: pure-cold rounds keep exact arrival order.
        if any(r.resume_tier is not None for r in candidates):
            heavy = sorted(heavy, key=self.admission_cost)
            light = sorted(light, key=self.admission_cost)
        picks: list[Request] = []

        aged = any(r.deferred >= self.cfg.max_defer_steps for r in heavy)
        group_ready = len(heavy) + n_heavy_active >= self.cfg.min_coschedule
        admit_heavy = heavy and (group_ready or aged or not light)

        if admit_heavy:
            n_heavy = n_heavy_active
            for r in heavy:
                # aging overrides the budget (no starvation even when a single
                # request busts it, as full-size MoE configs can); every heavy
                # request left behind this round — budget OR slot exhaustion —
                # accrues deferral so the aging clock never silently pauses
                admit = len(picks) < n_free and (
                    self._step_cost(n_heavy + 1) <= self.cfg.a2a_budget_s
                    or r.deferred >= self.cfg.max_defer_steps
                    or (self.cfg.work_conserving and not picks and not light)
                )
                if admit:
                    picks.append(r)
                    n_heavy += 1
                else:
                    r.deferred += 1
            self.last_step_cost = self._step_cost(n_heavy)
        else:
            for r in heavy:
                r.deferred += 1
            self.last_step_cost = self._step_cost(n_heavy_active)

        for r in light:
            if len(picks) >= n_free:
                break
            picks.append(r)

        # work conservation: if budget/grouping admitted nothing but slots
        # are free and requests wait, take the head of the queue anyway
        if not picks and self.cfg.work_conserving:
            picks = list(candidates[:n_free])
        return picks


# --------------------------------------------------------------------------
# continuous-batching engine
# --------------------------------------------------------------------------


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class EngineMetrics:
    """Engine counters as a thin view over a
    :class:`~repro.obs.metrics.MetricsRegistry` (docs/OBSERVABILITY.md):
    each field is a property over the ``serve.engine.*`` metric of the same
    name, so the registry and the legacy fields are one storage cell.
    Zero-arg construction builds a private registry (the serving bench
    resets metrics with ``type(engine.metrics)()``)."""

    _SCALARS = (
        ("steps", 0),
        ("decode_steps", 0),
        ("prefills", 0),
        ("active_slot_steps", 0),
        ("total_slot_steps", 0),
        ("predicted_a2a_s", 0.0),
        # tiered pooling (TieredKVPool engines only)
        ("demotions", 0),  # finished sessions parked in the hierarchy
        ("wakeups", 0),  # resumes served from a resident row (no prefill)
        ("cold_resumes", 0),  # resumes whose row was dropped (re-prefilled)
        # admission-control shedding (docs/SERVING.md, autoscaling): shed
        # work never allocates a KV slot and never counts toward goodput
        ("rejected", 0),  # refused at submit (queue over max_queue_depth)
        ("deadline_drops", 0),  # dropped unadmitted past their deadline
        ("shed_tokens", 0),  # token budget of all shed requests (not served)
        # backend compiles JAX reported during the engine's prefill/decode
        # calls: each is a step that paid for a new program
        ("compiles", 0),
        # (token, expert) assignments the decode steps computed on the
        # layers' held experts, every row of the pool counted, and the
        # (layer, held expert) pairs given at least one of them, whose
        # weights the steps read (models with a held expert share only:
        # MoEConfig.n_held)
        ("moe_held_assignments", 0),
        ("moe_held_experts", 0),
    )

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = MetricsRegistry() if registry is None else registry
        for name, default in self._SCALARS:
            # reset, not just get-or-create: fresh metrics mean zeroed
            # fields even when the registry is shared across runs
            self.registry.counter(f"serve.engine.{name}", default).value = default

    @property
    def slot_utilization(self) -> float:
        return self.active_slot_steps / self.total_slot_steps if self.total_slot_steps else 0.0


for _name, _default in EngineMetrics._SCALARS:
    setattr(EngineMetrics, _name, registry_field(f"serve.engine.{_name}"))
del _name, _default


# the engine's jitted call in flight on this thread, as (engine, program,
# shape), so a backend compile JAX reports is put down to the step that paid
_IN_CALL = threading.local()
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_listening = False


def _on_jax_event(event: str, secs: float, **_) -> None:
    call = getattr(_IN_CALL, "call", None)
    if event != _BACKEND_COMPILE or call is None:
        return
    engine, program, shape = call
    engine.metrics.compiles += 1
    if engine._obs.enabled:
        engine._obs.tracer.instant("compile", "serve", program=program,
                                   shape=list(shape), secs=secs)


def _count_compiles() -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _listening = True


class ContinuousBatchingEngine:
    """Prefill/decode-interleaved serving over a pooled KV cache.

    Per step: (1) the scheduler admits arrived requests into free slots —
    each admission is a batch-1 prefill whose prepared cache is written into
    its slot; (2) one ragged decode step advances every active slot; rows
    finishing (token budget or EOS) free their slot for the next admission.
    No head-of-line blocking: a 4-token request behind a 400-token one
    completes and hands its slot over 396 steps earlier.

    Sampling is deterministic per (seed, request id, token index) — results
    do not depend on slot assignment, pool size, or admission order.
    """

    def __init__(
        self,
        model: Model,
        params,
        n_slots: int = 8,
        max_len: int = 512,
        mesh=None,
        scheduler: Optional[Scheduler] = None,
        cost_model: Optional[CollectiveCostModel] = None,
        policy: str = "cost_aware",
        seed: int = 0,
        pad_id: int = 0,
        min_prompt_bucket: int = 8,
        audit: bool = False,
        tiers: Optional[TierConfig] = None,
        max_queue_depth: Optional[int] = None,
        obs=None,
    ):
        if model.cfg.enc_dec:
            raise NotImplementedError("continuous batching supports decoder-only models")
        self.model = model
        self.params = params
        self.mesh = mesh
        self.pad_id = pad_id
        self.seed = seed
        # observability bundle (docs/OBSERVABILITY.md): NULL_OBS unless the
        # launcher installed one; every hot-path hook hides behind one
        # `enabled` attribute check
        self._obs = obs if obs is not None else get_obs()
        self.queue = RequestQueue()
        # admission control: submissions past this queue depth are rejected
        # (state SHED) instead of building an unbounded backlog; None = admit
        # everything (the pre-autoscaling behaviour)
        self.max_queue_depth = max_queue_depth
        # tiers=TierConfig(...) turns on the memory hierarchy: finished
        # sessions demote to host/pooled and wake up via submit(session_id=)
        self.tiers = tiers
        self._cost_model = cost_model or CollectiveCostModel()
        self.pool = self._make_pool(n_slots, max_len)
        self.metrics = EngineMetrics(
            registry=self._obs.registry if self._obs.enabled else None
        )
        self._rid = itertools.count()
        self.requests: dict[int, Request] = {}
        self._busy_sessions: set[int] = set()  # one in-flight request per session

        cfg = model.cfg
        self._n_moe_layers = sum(cfg.layer_is_moe(i) for i in range(cfg.n_layers))
        self._dispatch_weight = (
            float(cfg.moe.top_k * cfg.d_model * 2 * self._n_moe_layers)
            if cfg.moe is not None
            else 0.0
        )
        if scheduler is None:
            scheduler = Scheduler(
                SchedulerConfig(policy=policy),
                self._cost_model,
                d_model=cfg.d_model,
                top_k=cfg.moe.top_k if cfg.moe else 0,
                n_moe_layers=self._n_moe_layers,
            )
        self.scheduler = scheduler

        # a held expert share counts its decode assignments and experts
        # (EngineMetrics)
        self._counts_held = cfg.moe is not None and bool(cfg.moe.n_held)
        # SSM state has no positional record, so right-padded prefill would
        # advance it through pad tokens — bucket only pure-attention stacks
        self._bucket_prompts = all(cfg.layer_is_attention(i) for i in range(cfg.n_layers))
        self.min_prompt_bucket = min_prompt_bucket

        # migration hooks (runtime/serving_elastic.py): paused admission and
        # the (rid, token index) audit trail the chaos harness checks for
        # monotone, gap-free, never-repeated token production.  The trail
        # grows one tuple per produced token, so it is opt-in (audit=True) —
        # tests enable it; a long-lived server keeps it off
        self._paused = False
        self.audit_enabled = audit
        self.audit: list[tuple[int, int]] = []

        self._reset_slot_state(n_slots)
        self._build_jits()
        self._clock_offset = 0.0  # step()'s ``now`` minus time.monotonic()
        _count_compiles()

    def _make_pool(self, n_slots: int, capacity: int) -> KVPool:
        if self.tiers is not None:
            return TieredKVPool(
                self.model, n_slots, capacity, self.tiers,
                cost_model=self._cost_model, obs=self._obs,
            )
        return KVPool(self.model, n_slots, capacity)

    def _reset_slot_state(self, n_slots: int) -> None:
        S = n_slots
        self._slot_req: list[Optional[Request]] = [None] * S
        self._tokens = np.zeros((S,), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._temps = np.zeros((S,), np.float32)
        self._rids = np.zeros((S,), np.int32)

    def _jit_cache_key(self):
        """Configurations with the same key share compiled executables: a
        migration or restart that lands back on a previously-seen
        (model, mesh, pool) configuration pays no recompile."""
        return (
            self.model, self.mesh, self.pool.n_slots, self.pool.capacity, self.seed,
        )

    def _build_jits(self) -> None:
        """(Re)build the jitted prefill/decode closures against the current
        ``self.mesh`` / pool layout.  Called at construction and again by
        :meth:`migrate` after a remesh.  Closures are cached per
        configuration (:meth:`_jit_cache_key`) so only a *new* configuration
        compiles — that first-visit compile is part of the honest migration
        cost; revisits (fail-back, A/B restarts) are free."""
        cached = _JIT_CACHE.get(self._jit_cache_key())
        if cached is not None:
            self._prefill_into, self._decode = cached
            return
        mesh_ = self.mesh
        m = self.model
        seed = self.seed
        max_len = self.pool.capacity
        counts_held = self._counts_held

        # sampling is deterministic per (seed, request id, token index): the
        # drawn token never depends on slot assignment or admission order
        def sample_one(logits, temp, rid, idx):
            base = jax.random.PRNGKey(seed)
            k = jax.random.fold_in(jax.random.fold_in(base, rid), idx)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            drawn = jax.random.categorical(k, logits / jnp.maximum(temp, 1e-6), axis=-1)
            return jnp.where(temp > 0.0, drawn.astype(jnp.int32), greedy)

        # sampling is fused into the prefill/decode jits: one dispatch per
        # serving step, tokens (not logits) cross the host boundary
        @partial(jax.jit, donate_argnums=(3,))
        def prefill_into(params, tokens, true_len, pool_caches, slots, temps, rids,
                         idx0):
            """Batched admission: prefill G requests together ([G, bucket])
            and write each prepared cache row into its pool slot.  ``idx0``
            is each row's sampling-stream offset — 0 for fresh requests,
            the session's token count so far for a cold (dropped-session)
            resume, so the re-prefilled continuation stays bit-exact."""
            g = tokens.shape[0]
            logits, caches = m.prefill(
                params, {"tokens": tokens}, mesh=mesh_, last_pos=true_len - 1
            )
            caches = m.mask_prompt_cache(caches, true_len)
            caches = m.prepare_decode_caches(caches, capacity=max_len)
            for i in range(g):
                row = jax.tree.map(lambda c: jax.lax.slice_in_dim(c, i, i + 1, axis=ROWS), caches)
                pool_caches = merge_slot_caches(pool_caches, row, slots[i])
            toks = jax.vmap(sample_one)(logits[:, 0], temps, rids, idx0)
            return toks, pool_caches

        @partial(jax.jit, donate_argnums=(1,))
        def decode(params, pool_caches, tokens, pos, temps, rids, idxs):
            """Next token of every row; a model with a held expert share
            appends the step's held assignments and held experts given
            rows, so the counts ride the tokens' copy to the host."""
            logits, pool_caches, aux = m.decode_step(
                params, pool_caches, tokens[:, None], pos, mesh=mesh_, ragged=True, with_aux=True
            )
            toks = jax.vmap(sample_one)(logits[:, 0], temps, rids, idxs)
            if counts_held:
                toks = jnp.concatenate([toks, jnp.stack([aux["held"], aux["held_experts"]])])
            return toks, pool_caches

        self._prefill_into = prefill_into
        self._decode = decode
        _JIT_CACHE[self._jit_cache_key()] = (prefill_into, decode)

    def absorb_pool_metrics(self, registry: Optional[MetricsRegistry] = None) -> None:
        """Refresh ``serve.pool.*`` counters in ``registry`` (default: the
        metrics registry) from the live pool — last write wins, so calling
        again after a migration updates rather than duplicates
        (docs/OBSERVABILITY.md)."""
        reg = registry if registry is not None else self.metrics.registry
        pool = self.pool
        stats = {
            "n_slots": pool.n_slots,
            "n_alloc": pool.n_alloc,
            "n_evict": pool.n_evict,
            "high_water": pool.high_water,
        }
        if pool.tiered:
            stats.update(
                n_demote=pool.n_demote, n_promote=pool.n_promote,
                n_spill=pool.n_spill, n_refill=pool.n_refill,
                n_drop=pool.n_drop, modeled_tier_s=pool.modeled_tier_s,
                resident_sessions=pool.resident_sessions,
                demoted_sessions=pool.demoted_sessions,
            )
        reg.absorb("serve.pool", stats)

    # ---------------- elasticity hooks ----------------

    def pause_admission(self) -> None:
        """Stop admitting queued requests (decode of active slots continues).
        The migration contract: admission is paused for the duration of a
        KV-pool migration so no prefill races the extract/insert window."""
        self._paused = True

    def resume_admission(self) -> None:
        self._paused = False

    def active_requests(self) -> list[Request]:
        return [r for r in self._slot_req if r is not None]

    def migrate(self, params=None, mesh=None, n_slots: Optional[int] = None) -> int:
        """Rebuild the pool and the jitted paths on a new mesh/param
        placement, preserving in-flight decode state bit-exact.

        Every active slot's ring cache is extracted to host, the pool is
        reconstructed at the new size, and each row is re-inserted; the
        per-slot host state is rebuilt from the ``Request`` objects, so
        decode resumes from the last completed step — no token is redone,
        lost, or reordered (the audit trail stays gap-free).  ``mesh=None``
        keeps the current mesh; callers pause admission around this (the
        serving orchestrator does).  Returns the number of migrated slots.
        """
        active = [(s, r) for s, r in enumerate(self._slot_req) if r is not None]
        new_slots = self.pool.n_slots if n_slots is None else int(n_slots)
        if new_slots < len(active):
            raise ValueError(
                f"cannot migrate {len(active)} in-flight requests into "
                f"{new_slots} slots — the survivor pool must hold every live row"
            )
        obs = self._obs
        # one gather + one device->host sync for all live rows (extract_all),
        # not one sync per slot — the dominant term in the migration pause
        with (obs.tracer.span("migrate", "serve", phase="extract")
              if obs.enabled else NULL_SPAN):
            rows = self.pool.extract_all([s for s, _ in active])
        old = self.pool
        for s, _ in active:  # lifetime ledger: every allocate gets its free
            old.free(s)
        with (obs.tracer.span("migrate", "serve", phase="rebuild")
              if obs.enabled else NULL_SPAN):
            if params is not None:
                self.params = params
            if mesh is not None:
                self.mesh = mesh
            self.pool = self._make_pool(new_slots, old.capacity)
            self.pool.n_alloc += old.n_alloc
            self.pool.n_evict += old.n_evict
            self.pool.high_water = old.high_water
            if self.pool.tiered and old.tiered:
                # demoted rows are host-side and device-independent: the
                # ledger outlives the mesh, it just moves to the rebuilt pool
                self.pool.adopt(old)
            self._reset_slot_state(new_slots)
        with (obs.tracer.span("migrate", "serve", phase="insert")
              if obs.enabled else NULL_SPAN):
            new_slot_order = []
            for (_, req), row in zip(active, rows):
                slot = self.pool.allocate(req.rid)
                req.slot = slot
                self._slot_req[slot] = req
                self._tokens[slot] = (
                    req.tokens_out[-1] if req.tokens_out else req.last_token
                )
                self._pos[slot] = req.prompt_len + len(req.tokens_out) - 1
                self._temps[slot] = req.temperature
                self._rids[slot] = (
                    req.sample_rid if req.sample_rid is not None else req.rid
                )
                new_slot_order.append(slot)
            self.pool.insert_all(new_slot_order, rows)
            self._build_jits()
        return len(rows)

    # ---------------- submission ----------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        arrival_time: Optional[float] = None,
        dispatch_weight: Optional[float] = None,
        now: Optional[float] = None,
        session_id: Optional[int] = None,
        deadline: Optional[float] = None,
    ) -> int:
        """Enqueue one request; returns its request id.

        ``session_id`` (tiered engines): a stable caller-chosen identity.
        The first request under a session id creates the session; when it
        finishes, its cache row demotes into the memory hierarchy instead of
        being discarded.  A later request with the same id *resumes* it —
        ``prompt`` must then be the session's full token history (original
        prompt + every generated token), and admission pages the resident
        row back in and skips re-prefill (or re-prefills the history if the
        row was dropped — either way the continuation is bit-exact).  One
        request may be in flight per session at a time.

        ``deadline`` (absolute, same clock as ``arrival_time``): past it an
        unadmitted request is dropped instead of served late.  When the
        engine was built with ``max_queue_depth`` and the queue is already
        that deep, the request is rejected outright: its state is ``SHED``,
        no KV slot is ever allocated, and its id is still returned so the
        caller can observe the rejection (``engine.requests[rid].state``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.pool.capacity:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds pool capacity {self.pool.capacity}"
            )
        if self.max_queue_depth is not None and len(self.queue) >= self.max_queue_depth:
            req = Request(
                rid=next(self._rid),
                prompt=prompt,
                max_new_tokens=int(max_new_tokens),
                temperature=float(temperature),
                eos_id=eos_id,
                arrival_time=arrival_time,
                session_id=session_id,
                deadline=deadline,
                state=SHED,
                t_submit=now if now is not None else time.monotonic(),
            )
            self.requests[req.rid] = req
            self.metrics.rejected += 1
            self.metrics.shed_tokens += req.max_new_tokens
            return req.rid
        if session_id is not None and self.pool.tiered:
            if session_id in self._busy_sessions:
                raise ValueError(
                    f"session {session_id} already has a request in flight"
                )
            rec = self.pool.lookup(session_id)
            if rec is not None and prompt.size != rec.pos + 1:
                raise ValueError(
                    f"resume of session {session_id} must carry its full "
                    f"token history ({rec.pos + 1} tokens), got {prompt.size}"
                )
            self._busy_sessions.add(session_id)
        req = Request(
            rid=next(self._rid),
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature),
            eos_id=eos_id,
            arrival_time=arrival_time,
            dispatch_weight=(
                self._dispatch_weight if dispatch_weight is None else dispatch_weight
            ),
            session_id=session_id,
            deadline=deadline,
            t_submit=now if now is not None else time.monotonic(),
        )
        self.requests[req.rid] = req
        self.queue.push(req)
        return req.rid

    # ---------------- serving loop ----------------

    def _bucket(self, length: int) -> int:
        if not self._bucket_prompts:
            return length
        return min(max(_next_pow2(length), self.min_prompt_bucket), self.pool.capacity)

    def _admission_groups(self, picks: list[Request]) -> list[list[Request]]:
        """Split admitted requests into batched-prefill groups.  Picks are
        grouped by prompt bucket *first* (stable, so arrival order holds
        within a bucket), then each bucket run splits into power-of-two
        group sizes — a group never pads beyond its own bucket, so one long
        prompt can no longer drag a whole group up to its pad width.
        Compiled prefill shapes stay O(buckets * log slots).  Non-bucketing
        (SSM-bearing) models prefill one by one at exact length."""
        if not self._bucket_prompts:
            return [[r] for r in picks]
        by_bucket: dict[int, list[Request]] = {}
        for r in picks:
            by_bucket.setdefault(self._bucket(r.prompt_len), []).append(r)
        groups = []
        for bucket in sorted(by_bucket):
            run, i = by_bucket[bucket], 0
            while i < len(run):
                g = 1 << ((len(run) - i).bit_length() - 1)  # largest pow2 <= rest
                groups.append(run[i : i + g])
                i += g
        return groups

    def _stamp(self) -> float:
        """The present on the clock of the running step()'s ``now``."""
        return time.monotonic() + self._clock_offset

    def _call(self, program: str, shape: tuple, fn, *args):
        """One jitted call, with any backend compile it pays counted."""
        _IN_CALL.call = (self, program, shape)
        try:
            return fn(*args)
        finally:
            _IN_CALL.call = None

    def _admit_group(self, group: list[Request]) -> None:
        obs = self._obs
        g = len(group)
        bucket = max(self._bucket(r.prompt_len) for r in group)
        span = (
            obs.tracer.span("prefill", "serve", group=g, bucket=bucket)
            if obs.enabled else lane("serve.prefill")
        )
        with span:
            t_admit = self._stamp()
            slots = [self.pool.allocate(r.rid) for r in group]
            assert all(s is not None for s in slots)
            for r in group:
                if r.sample_rid is None:
                    r.sample_rid = r.rid
            toks = np.full((g, bucket), self.pad_id, np.int32)
            for i, r in enumerate(group):
                toks[i, : r.prompt_len] = r.prompt
            t0 = time.monotonic()
            firsts, self.pool.caches = self._call(
                "prefill_into", (g, bucket), self._prefill_into,
                self.params,
                jnp.asarray(toks),
                jnp.asarray([r.prompt_len for r in group], jnp.int32),
                self.pool.caches,
                jnp.asarray(slots, jnp.int32),
                jnp.asarray([r.temperature for r in group], jnp.float32),
                jnp.asarray([r.sample_rid for r in group], jnp.int32),
                jnp.asarray([r.idx_base for r in group], jnp.int32),
            )
            self.metrics.prefills += 1
            with obs.tracer.span("sync", "serve") if obs.enabled else lane("serve.sync"):
                firsts = np.asarray(firsts)
            t_first = self._stamp()
        if obs.enabled:
            # calibration: the modeled cold-prefill price of the group vs
            # the batched prefill wall (includes the device sync above)
            obs.calibration.observe(
                obs.calibration.record(
                    "cold_prefill",
                    sum(
                        self.scheduler.cost_model.cold_prefill_cost(r.prompt_len)
                        for r in group
                    ),
                    note=f"group={g}",
                ),
                time.monotonic() - t0,
            )
        for i, (req, slot) in enumerate(zip(group, slots)):
            tok = int(firsts[i])
            req.state = RUNNING
            req.slot = slot
            req.t_admit = t_admit
            req.t_first = t_first
            req.tokens_out.append(tok)
            req.last_token = tok
            if self.audit_enabled:
                self.audit.append((req.rid, 0))
            self._slot_req[slot] = req
            self._tokens[slot] = tok
            self._pos[slot] = req.prompt_len
            self._temps[slot] = req.temperature
            self._rids[slot] = req.sample_rid
            self._maybe_finish(req, tok, t_first)

    def _admit_resume(self, req: Request) -> None:
        """Wake a tier-resident session: page its row into a free slot and
        resume decode where it left off — no prefill at all.  The first new
        token comes from the next decode step (t_first is stamped then)."""
        obs = self._obs
        if obs.enabled:
            # calibration: the wakeup price admission used, vs the cold
            # prefill it displaced; observed closes with the promote wall
            cal = obs.calibration.record(
                "wakeup",
                self.scheduler.cost_model.wakeup_cost(
                    req.resume_bytes, req.resume_tier or "host"
                ),
                alternative_s=self.scheduler.cost_model.cold_prefill_cost(
                    req.prompt_len
                ),
                chosen="wakeup", note=req.resume_tier or "host",
            )
            with obs.tracer.span("wakeup", "serve", sid=req.session_id,
                                 tier=req.resume_tier):
                t0 = time.monotonic()
                slot, rec = self.pool.promote(req.session_id, req.rid)
                obs.calibration.observe(cal, time.monotonic() - t0)
        else:
            slot, rec = self.pool.promote(req.session_id, req.rid)
        req.state = RUNNING
        req.slot = slot
        req.t_admit = self._stamp()
        req.sample_rid = rec.sample_rid
        req.idx_base = rec.idx_base
        req.last_token = rec.last_token
        self._slot_req[slot] = req
        self._tokens[slot] = rec.last_token
        self._pos[slot] = rec.pos
        self._temps[slot] = req.temperature
        self._rids[slot] = rec.sample_rid
        self.metrics.wakeups += 1

    def _maybe_finish(self, req: Request, last_tok: int, t_tok: float) -> None:
        """Finish ``req`` if ``last_tok`` (on the host at ``t_tok``) ends it."""
        hit_eos = req.eos_id is not None and last_tok == req.eos_id
        if hit_eos or len(req.tokens_out) >= req.max_new_tokens:
            req.state = FINISHED
            req.t_done = t_tok
            slot = req.slot
            if req.session_id is not None and self.pool.tiered:
                # park the session in the hierarchy instead of discarding:
                # a wakeup resumes from here without re-prefilling
                self.pool.demote(
                    slot,
                    SessionRecord(
                        sid=req.session_id,
                        pos=int(self._pos[slot]),
                        last_token=int(self._tokens[slot]),
                        sample_rid=req.sample_rid,
                        idx_base=req.idx_base + len(req.tokens_out),
                    ),
                )
                self.metrics.demotions += 1
                self._busy_sessions.discard(req.session_id)
            else:
                self.pool.free(slot)
                if req.session_id is not None:
                    self._busy_sessions.discard(req.session_id)
            self._slot_req[slot] = None
            req.slot = None

    def _shed_queued(self, reqs: list, *, deadline: bool) -> int:
        """Drop still-queued requests: refund them from the queue (lazy
        delete — amortised O(log n) per request), mark them ``SHED``, and
        release any session reservation.  No KV slot was ever allocated for
        a queued request, so there is nothing to free in the pool."""
        victims = [r for r in reqs if r.state == QUEUED]
        if not victims:
            return 0
        self.queue.remove(victims)
        for r in victims:
            r.state = SHED
            self.metrics.shed_tokens += r.max_new_tokens
            if r.session_id is not None:
                self._busy_sessions.discard(r.session_id)
        if deadline:
            self.metrics.deadline_drops += len(victims)
        else:
            self.metrics.rejected += len(victims)
        if self._obs.enabled:
            self._obs.tracer.instant("shed", "serve", n=len(victims),
                                     deadline=deadline)
        return len(victims)

    def shed_queue(self, keep_depth: int, now: Optional[float] = None) -> int:
        """Autoscale actuation (``runtime/autoscale.py``): shed the *newest*
        queued requests until at most ``keep_depth`` remain in the arrived
        backlog — the oldest work has waited longest and is closest to its
        deadline, so the tail is the cheapest to turn away.  ``now=None``
        sheds against the full queue view (pending arrivals included).
        Returns the number shed."""
        backlog = self.queue.arrived(now)  # arrival-ordered
        excess = len(backlog) - max(keep_depth, 0)
        if excess <= 0:
            return 0
        return self._shed_queued(backlog[len(backlog) - excess:], deadline=False)

    def step(self, now: Optional[float] = None) -> int:
        """One scheduling round: admit, then one ragged decode step for all
        active slots.  Returns the number of tokens produced.

        ``now`` (default ``time.monotonic()``) gates arrivals and deadlines;
        request stamps are taken on its clock as the work they mark is done
        (``now`` plus the time since the step began)."""
        t0 = time.monotonic()
        if now is None:
            now = t0
        self._clock_offset = now - t0
        obs = self._obs
        if obs.enabled:
            obs.tracer.step = self.metrics.steps
        with obs.tracer.span("step", "serve") if obs.enabled else lane("serve.step"):
            produced = self._step(now)
        self.metrics.steps += 1
        return produced

    def _step(self, now: float) -> int:
        obs = self._obs
        produced = 0
        with obs.tracer.span("admit", "serve") if obs.enabled else lane("serve.admit"):
            groups = self._admit(now)
        for group in groups:
            self._admit_group(group)
            produced += len(group)

        # ---- one decode step over the pool
        active = [r for r in self._slot_req if r is not None]
        if not active:
            return produced
        with obs.tracer.span("decode", "serve") if obs.enabled else lane("serve.decode"):
            idxs = np.array(
                [
                    r.idx_base + len(r.tokens_out) if r is not None else 0
                    for r in self._slot_req
                ],
                np.int32,
            )
            toks, self.pool.caches = self._call(
                "decode", (self.pool.n_slots,), self._decode,
                self.params,
                self.pool.caches,
                jnp.asarray(self._tokens),
                jnp.asarray(self._pos),
                jnp.asarray(self._temps),
                jnp.asarray(self._rids),
                jnp.asarray(idxs),
            )
            with obs.tracer.span("sync", "serve") if obs.enabled else lane("serve.sync"):
                toks = np.asarray(toks)
            t_tok = self._stamp()
        held = None
        if self._counts_held:
            # the step's counts also ride its emit span onto the profile
            held = {"moe_held_assignments": int(toks[-2]), "moe_held_experts": int(toks[-1])}
            self.metrics.moe_held_assignments += held["moe_held_assignments"]
            self.metrics.moe_held_experts += held["moe_held_experts"]
        self.metrics.decode_steps += 1
        self.metrics.total_slot_steps += self.pool.n_slots
        with (obs.tracer.span("emit", "serve", **(held or {})) if obs.enabled
              else lane("serve.emit", held)):
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                tok = int(toks[slot])
                if self.audit_enabled:
                    self.audit.append((req.rid, len(req.tokens_out)))
                req.tokens_out.append(tok)
                req.last_token = tok
                if req.t_first is None:
                    req.t_first = t_tok  # woken sessions skip prefill
                self._tokens[slot] = tok
                self._pos[slot] += 1
                self.metrics.active_slot_steps += 1
                produced += 1
                self._maybe_finish(req, tok, t_tok)
        return produced

    def _admit(self, now: float) -> list[list[Request]]:
        """Shed expired requests, pick arrived ones for the free slots, wake
        resumed sessions; returns the prefill groups of the rest."""
        # ---- deadline drops: an unadmitted request past its deadline is
        # worthless — refund it from the queue before it wastes a slot
        expired = [
            r for r in self.queue.arrived(now)
            if r.deadline is not None and now > r.deadline
        ]
        if expired:
            self._shed_queued(expired, deadline=True)

        # ---- admission: fill freed slots from the queue
        candidates = (
            [] if self._paused or not self.pool.n_free else self.queue.arrived(now)
        )
        if not candidates:
            return []
        if self.pool.tiered:
            # refresh each session request's wakeup hint — residency can
            # change between rounds as other demotions spill the ledger
            for r in candidates:
                if r.session_id is not None:
                    rec = self.pool.lookup(r.session_id)
                    resident = rec is not None and rec.row is not None
                    r.resume_tier = rec.tier if resident else None
                    r.resume_bytes = rec.nbytes if resident else 0
        n_heavy_active = sum(
            1 for r in self._slot_req if r is not None and r.moe_heavy
        )
        picks = self.scheduler.select(candidates, self.pool.n_free, n_heavy_active)
        self.queue.remove(picks)
        cold: list[Request] = []
        for r in picks:
            if (
                self.pool.tiered
                and r.session_id is not None
                and self.pool.session_tier(r.session_id) in ("host", "pooled")
            ):
                self._admit_resume(r)  # wakeup: no prefill
                continue
            if self.pool.tiered and r.session_id is not None:
                rec = self.pool.claim_dropped(r.session_id)
                if rec is not None:
                    # row was dropped: re-prefill the full history but
                    # keep the sampling identity — still bit-exact
                    r.sample_rid = rec.sample_rid
                    r.idx_base = rec.idx_base
                    self.metrics.cold_resumes += 1
            cold.append(r)
        self.metrics.predicted_a2a_s += self.scheduler.last_step_cost
        return self._admission_groups(cold)

    def run(
        self,
        clock: Optional[Callable[[], float]] = None,
        max_steps: int = 1_000_000,
    ) -> dict[int, np.ndarray]:
        """Drive ``step()`` until queue and slots drain; returns
        {rid: generated tokens}.  ``clock`` gates open-loop arrivals (defaults
        to ``time.monotonic``); closed-loop submissions (``arrival_time=None``)
        are always eligible.  With the default wall clock, an idle engine
        sleeps until the next arrival; a custom (virtual) clock instead
        fast-forwards to it — discrete-event style — since sleeping cannot
        advance simulated time."""
        wall = clock is None
        clock = clock or time.monotonic
        for _ in range(max_steps):
            if not len(self.queue) and not any(
                r is not None for r in self._slot_req
            ):
                break
            made = self.step(clock())
            if made == 0 and not any(r is not None for r in self._slot_req):
                if self._paused:
                    break  # admission paused, nothing active: cannot progress
                nxt = self.queue.next_arrival()
                if nxt is not None and clock() < nxt:
                    if wall:
                        # idle until the next open-loop arrival
                        while clock() < nxt:
                            time.sleep(min(1e-3, max(nxt - clock(), 0.0)))
                    else:
                        self.step(nxt)  # jump virtual time to the arrival
        return {
            rid: np.asarray(r.tokens_out, np.int32)
            for rid, r in self.requests.items()
            if r.done
        }

    def generate(
        self,
        prompts,
        max_new_tokens,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
    ) -> list[np.ndarray]:
        """Closed-loop convenience: submit ``prompts`` (list of 1-D arrays or a
        2-D array), run to completion, return outputs in submission order."""
        if isinstance(prompts, np.ndarray) and prompts.ndim == 2:
            prompts = list(prompts)
        budgets = (
            max_new_tokens
            if isinstance(max_new_tokens, (list, tuple))
            else [max_new_tokens] * len(prompts)
        )
        if len(budgets) != len(prompts):
            raise ValueError(
                f"{len(prompts)} prompts but {len(budgets)} max_new_tokens entries"
            )
        rids = [
            self.submit(p, b, temperature=temperature, eos_id=eos_id)
            for p, b in zip(prompts, budgets)
        ]
        out = self.run()
        return [out[r] for r in rids]


# --------------------------------------------------------------------------
# one-shot lockstep engine (seed API, and the bench baseline)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ServingEngine:
    """One-shot batch generator: a single prefill over a fixed (left-padded)
    batch, then lockstep decode for a fixed token budget.  Kept as the
    backward-compatible ``generate()`` wrapper and as the baseline the
    serving benchmark compares continuous batching against — it has exactly
    the failure modes the pooled engine removes (idle slots after short
    requests finish, head-of-line blocking between batches)."""

    model: Model
    params: object
    max_len: int = 512
    mesh: object | None = None  # Mesh/MeshContext threaded into the model

    def __post_init__(self):
        mesh = self.mesh
        self._prefill = jax.jit(lambda p, b: self.model.prefill(p, b, mesh=mesh))
        self._decode = jax.jit(
            lambda p, c, t, pos: self.model.decode_step(p, c, t, pos, mesh=mesh),
            donate_argnums=(1,),
        )

    def generate(
        self,
        prompts: np.ndarray,  # [B, S] int32 (left-padded with pad_id)
        max_new_tokens: int,
        pad_id: int = 0,
        temperature: float = 0.0,
        seed: int = 0,
    ) -> np.ndarray:
        """Returns generated tokens [B, max_new_tokens]."""
        b, s = prompts.shape
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.model.cfg.enc_dec:
            raise NotImplementedError("use generate_enc_dec for encoder-decoder models")
        logits, caches = self._prefill(self.params, batch)
        caches = self.model.prepare_decode_caches(caches, capacity=self.max_len)
        key = jax.random.PRNGKey(seed)
        pos = jnp.full((b,), s, jnp.int32)
        out = []
        tok = self._sample(logits[:, 0], temperature, key)
        out.append(tok)
        for i in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            logits, caches = self._decode(self.params, caches, tok[:, None], pos + i)
            tok = self._sample(logits[:, 0], temperature, sub)
            out.append(tok)
        return np.stack([np.asarray(t) for t in out], axis=1)

    @staticmethod
    def _sample(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)
