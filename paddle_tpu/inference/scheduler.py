"""Paged serving engine: block-budget admission, preemption by block
eviction, continuous slot refill.

Sits where ContinuousBatchingEngine sits (same model contract:
``model(x, caches=..., time_step=...)`` with per-row int32 positions),
but the cache is a PagedKVCache — sequences reserve pages as they
grow instead of a dense max_len row, so the concurrency limit is the
BLOCK BUDGET, not slots*max_len. Scheduling policy (vLLM-style):

  * admission: a queued request is admitted only when a slot is free
    AND the allocator can cover its prompt's pages plus a watermark;
    prefill STREAMS the prompt straight into the slot's pages in
    fixed-size causal chunks (``chunked_prefill`` below — batch-1
    chunk calls through PagedKVCache.prefill_views), so there is no
    dense ``[2, 1, H, max_len, D]`` scratch allocation and no
    pages<->scratch scatter/gather pass: peak KV memory IS the pool.
  * growth: before each fused step, every active row crossing a block
    boundary allocates its next page (allocate-on-write).
  * preemption: when the pool is exhausted, the YOUNGEST active
    request is evicted — all its pages are freed at once and the
    request goes back to the FRONT of the queue for re-prefill from
    its recorded history (prompt + every decode input), so a later
    re-admission reproduces its cache exactly.
  * refill: releases/preemptions re-run admission, so the batch stays
    full without stopping in-flight rows.
  * prefix caching (``prefix_cache=True``): admission matches the
    prompt's chained block hashes against previously computed pages
    (paged_cache.match_prefix), ``ref``s the hits into the new slot's
    table, and prefills ONLY the uncached suffix — cached prefix
    tokens cost zero prefill FLOPs and zero new blocks. The suffix
    chunk simply ATTENDS over the adopted pages through the chunk
    protocol (no pages->scratch gather). Released pages park
    cached-free (resurrectable) until LRU reclaim; hit accounting
    rides in ``prefix_stats``.
  * mixed prefill/decode steps (``prefill_token_budget=N``,
    Sarathi-style): admission only grants the slot; each ``step``
    first spends up to N prompt tokens advancing pending prefills
    chunk by chunk (oldest first, growing pages under the same
    preemption rules — no max_len block reservation up front), then
    runs the fused decode call for the active rows, so one long
    prompt never stalls the running batch. The admitted event fires
    when the last chunk lands. Without a budget (the default),
    admission runs every chunk synchronously — same external
    behavior as the old scratch path, still scratchless inside.
    Chunk accounting rides in ``prefill_stats`` (PrefillStats).

  * quantized serving (``dtype="int8"``): the pool stores int8 K/V
    pages with per-row scales (paged_cache.py "QUANTIZED SERVING") —
    ~1.88x the blocks at equal HBM, so the block-budget admission
    above admits ~1.88x the concurrent requests. Scheduling is
    completely dtype-blind: admission, growth, preemption, prefix
    adoption, quotas and snapshots all operate on block counts and
    quantized payloads unchanged. Off by default (bit-identity
    suites run on fp pools).

  * failure isolation (inference/resilience.py): requests end in a
    terminal ``RequestOutcome`` — FINISHED, or FAILED_OOM /
    FAILED_NUMERIC / FAILED_DEADLINE / REJECTED_ADMISSION — surfaced
    in ``outcomes``;
    a BlockOOM that survives preemption sheds ONE request instead of
    raising, ``max_preemptions`` bounds the re-prefill retry budget,
    per-request deadlines (steps or wall clock) are enforced each
    step, and an optional numeric guard fails a slot whose hidden
    goes non-finite (its pages are quarantined). A ``FaultInjector``
    can drive all of it deterministically; ``check_invariants``
    audits the pool bookkeeping. Counters ride in
    ``resilience_stats`` (ResilienceStats).

  * multi-tenant isolation (``tenants=`` / ``set_tenant`` /
    ``submit(..., tenant_id=...)``): every request belongs to a
    tenant (the implicit unlimited ``default`` tenant when no id is
    given — bit-identical to the single-tenant engine). Tenants carry
    a block QUOTA (hard cap on the blocks their slots' tables may
    reference — one charge per reference, so a tenant's bill is a
    pure function of its own tables; see PagedKVCache.__init__), a
    RESERVED floor (pool headroom other tenants may never dip into
    while this tenant is below it), and a WEIGHT for admission.
    Admission is weighted fair queuing over one physical queue:
    the tenant with the lowest virtual time admits next (vtime
    advances by 1/weight per admission; start-time bumped to the
    virtual clock on enqueue-from-idle), age-fair within a tenant and
    still preempted-ahead-of-new. A tenant whose head request is
    blocked by its OWN quota (or by others' reserved floors) is
    skipped — its cap is its problem, never its neighbors' — while
    true pool pressure stops the pass head-of-line as before.
    Preemption and shedding are tenant-aware: a quota or floor hit
    evicts the over-budget tenant's OWN youngest (or sheds its
    grower), and a physical pool OOM takes victims from the grower's
    own tenant — a neighbor is only ever preempted when the grower
    is still under its reserved floor and that neighbor is borrowing
    above its own. Health-based admission control REJECTS provably
    unservable requests at submit (quota- or pool-impossible prompt,
    deadline below the prefill lower bound) with a terminal
    ``REJECTED_ADMISSION`` outcome — never an exception. Per-tenant
    accounting (sheds, rejections, quota hits, blocks held, tokens
    served) rides in ``tenant_stats`` (TenantStats).

  * telemetry (``collector=`` — inference/telemetry.py): an opt-in
    ``TraceCollector`` records every request's lifecycle (submitted /
    admitted / prefill chunks / first token / preemptions / rollbacks
    / terminal outcome -> TTFT, TPOT, queue-wait, preemption-stall
    percentiles per tenant) and brackets each step's phases
    (admission / prefill / model / bookkeeping) with per-step pool /
    queue / per-tenant gauges, exportable as Chrome-trace JSON. With
    no collector (default) every hook site is dark — zero clock
    reads, zero allocations, bit-identical streams. The always-on
    ``registry`` (MetricsRegistry) unifies the five stats siblings,
    ``tenant_report`` and the pool/queue gauges behind one flat
    ``as_dict()`` with interval deltas.

  * health monitoring (``monitor=`` — inference/monitor.py): an
    opt-in ``HealthMonitor`` sampled at the end of every completed
    step — windowed time-series over the registry (tokens/step, shed
    rate, pool tiers, queue depth, per-tenant charge, spec
    acceptance), per-tenant SLO tracking off the collector's latency
    histograms, and deterministic threshold-crossing ``Alert`` events
    (pool-pressure-high, shed-spike, queue-growth, ...) keyed to the
    step clock. Same contracts as the collector: zero overhead off,
    passive, derived-not-snapshotted.

Events are surfaced in ``admitted`` / ``finished`` / ``preempted`` /
``outcomes`` lists the caller drains between steps (prefill outputs
ride along so the caller can seed the next input row).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from ..framework import device as _device
from ..framework.autograd import no_grad
from ..framework.tensor import Tensor
from .paged_cache import (BlockOOM, PagedKVCache, chain_block_hashes,
                          model_call)
from .resilience import RequestOutcome
from .serving import (ParallelStats, PrefillStats, PrefixCacheStats,
                      ResilienceStats, TenantStats)
from .telemetry import MetricsRegistry

__all__ = ["PagedRequest", "PagedServingEngine", "Tenant",
           "chunked_prefill", "DEFAULT_TENANT",
           "MIN_PREFILL_SUFFIX_ROWS"]

# the implicit tenant every request without a tenant_id belongs to:
# unlimited quota, no reserved floor, weight 1 — a single-tenant
# engine therefore schedules bit-identically to the pre-tenant one
# (weighted fair queuing over one tenant IS FIFO, and every victim
# policy degenerates to "youngest first")
DEFAULT_TENANT = "default"


class Tenant:
    """One tenant's isolation contract + accounting.

      quota_blocks     hard cap on pool blocks charged to the tenant
                       (one charge per block-table reference its slots
                       hold); None = unlimited. Growth into the cap
                       evicts/sheds WITHIN the tenant, admission past
                       it skips the tenant — neighbors never pay.
      reserved_blocks  guaranteed floor: while this tenant's charge is
                       below it, other tenants' admissions and growth
                       may not dip the free pool below the unmet
                       remainder, and a pool OOM suffered while below
                       it may evict an over-floor borrower.
      weight           weighted-fair-queuing admission share: a
                       tenant's virtual time advances by 1/weight per
                       admission, so a weight-2 tenant admits twice as
                       often under contention.
      vtime            the WFQ virtual-time tag (scheduler state —
                       snapshots round-trip it).
      fifo             this tenant's FIFO SUB-QUEUE (the physical
                       queue is sharded per tenant, so WFQ head
                       selection reads one deque head per tenant —
                       O(tenants) — instead of scanning one global
                       queue per admission, O(queue)). Within a
                       tenant the order is the same global contract
                       as before: preempted requests (by rid) ahead
                       of never-admitted ones (by enqueue order).
                       ``PagedServingEngine.queue`` materializes the
                       merged global view for snapshots/diagnostics.
      queued           live count of this tenant's queued requests
                       (== len(fifo); gauge maintained at every queue
                       mutation and audited by check_invariants;
                       derived state, so restore recomputes it from
                       the queue instead of round-tripping it).
      stats            TenantStats (serving.py).
    """

    __slots__ = ("tid", "quota_blocks", "reserved_blocks", "weight",
                 "vtime", "fifo", "queued", "stats")

    def __init__(self, tid: str, quota_blocks: Optional[int] = None,
                 reserved_blocks: int = 0, weight: float = 1.0):
        self.tid = str(tid)
        if weight <= 0:
            raise ValueError(f"tenant {tid!r}: weight must be > 0")
        if reserved_blocks < 0:
            raise ValueError(
                f"tenant {tid!r}: reserved_blocks must be >= 0")
        if quota_blocks is not None and quota_blocks < reserved_blocks:
            raise ValueError(
                f"tenant {tid!r}: quota_blocks ({quota_blocks}) < "
                f"reserved_blocks ({reserved_blocks})")
        self.quota_blocks = (None if quota_blocks is None
                             else int(quota_blocks))
        self.reserved_blocks = int(reserved_blocks)
        self.weight = float(weight)
        self.vtime = 0.0
        self.fifo: Deque[PagedRequest] = deque()
        self.queued = 0
        self.stats = TenantStats()

# A partial (suffix-only) prefill must recompute at least this many
# trailing prompt rows, even when the prefix cache covers more: a
# 1-row attention lowers to a GEMV whose accumulation order differs
# from the same row computed inside a multi-row prefill, so a 1-row
# suffix would break bit-identity with the cold path (and a fully
# cached prompt still needs its last hidden for the admission event).
# The same floor governs CHUNK boundaries: every prefill chunk keeps
# >= MIN_PREFILL_SUFFIX_ROWS rows (chunking is bit-transparent for
# multi-row calls — per-row sdpa results are invariant to both chunk
# length and masked key extent — but a 1-row tail chunk would take
# the GEMV lowering).
# See tests/test_prefix_cache.py::test_one_row_suffix_regression.
MIN_PREFILL_SUFFIX_ROWS = 2


def _chunk_len(total: int, pos: int, chunk_tokens: int,
               budget: Optional[int] = None) -> int:
    """Next chunk length for a prefill at ``pos`` of ``total`` rows:
    ``chunk_tokens`` capped by the remaining prompt (and the remaining
    step budget, floored at the 2-row minimum), then adjusted so the
    REMAINING tail is never a single row — a 1-row chunk would break
    bit-identity (MIN_PREFILL_SUFFIX_ROWS)."""
    c = min(chunk_tokens, total - pos)
    if budget is not None:
        c = min(c, max(MIN_PREFILL_SUFFIX_ROWS, budget))
    if total - (pos + c) == 1:
        c = c - 1 if c > MIN_PREFILL_SUFFIX_ROWS else c + 1
    return c


def chunked_prefill(model, cache: PagedKVCache, slot: int, rows,
                    *, pos: int = 0, target: Optional[int] = None,
                    chunk_tokens: int = 64, start_block: int = 0,
                    write_start: int = 0, stats: Optional[PrefillStats]
                    = None, on_chunk=None):
    """Stream ``rows[pos:target]`` ([T, d_model] ndarray) into
    ``slot``'s pages in causal chunks: each chunk is one batch-1 model
    call through ``cache.prefill_views`` — K/V append straight into
    the pages, attention runs over them at ``time_step = chunk
    start`` with full-extent masking, so the resulting pages AND the
    final hidden are bit-identical to a dense scratch prefill of the
    whole prompt (asserted in tests/test_paged_cache.py). The ONE
    prefill implementation shared by PagedServingEngine (admission +
    re-prefill + mixed steps) and SpeculativeEngine (draft prefill).

    ``start_block``/``write_start``: adopted prefix-cache pages — the
    chunks attend over them but never rewrite (or COW-split) them.
    The caller must ``ensure`` page coverage only when running under
    its own OOM policy; this helper ensures per chunk and lets
    BlockOOM propagate. ``on_chunk(new_pos)`` fires after every chunk
    lands — the engine uses it to register completed prefix blocks as
    the prompt streams, so a preemption (or crash restore) mid-prefill
    resumes warm instead of recomputing finished pages. Returns
    ``(new_pos, last_hidden)`` — last_hidden is the final chunk's
    trailing row ([1, d_model]), or None when no chunk ran."""
    import paddle_tpu as paddle
    T = rows.shape[0] if target is None else int(target)
    out = None
    views = cache.prefill_views(slot, write_start=write_start)
    while pos < T:
        c = _chunk_len(T, pos, chunk_tokens)
        cache.ensure(slot, pos + c, start_block=start_block,
                     write_from=pos)
        x = paddle.to_tensor(
            np.ascontiguousarray(rows[pos:pos + c], np.float32)[None])
        # serving never backprops (no_grad keeps the tape from pinning
        # pool versions); time_step as a TENSOR scalar routes to the
        # full-extent masked attention — the length-independence that
        # makes chunking and prefix adoption bit-transparent
        with no_grad():
            out, _ = model(x, caches=views,
                           time_step=Tensor(np.int32(pos)))
        pos += c
        if stats is not None:
            stats.chunks += 1
            stats.prefill_tokens += c
            stats.peak_blocks = max(stats.peak_blocks,
                                    cache.blocks_in_use)
        if on_chunk is not None:
            on_chunk(pos)
    return pos, (out[:, -1] if out is not None else None)


def _request_rows(history) -> np.ndarray:
    """The [T, d_model] float32 array a request keeps as its history.
    A float32 C-contiguous array that OWNS its buffer and that its
    giver has frozen (``flags.writeable`` False: the token wrapper does
    that to the rows it has just gathered, speculative.py ``submit``)
    is the request's from here on, with no copy: nobody can write to
    it through that reference any more. Anything else is copied (a
    view, another dtype, and every array its caller can still write
    to: a foreign caller may keep filling its buffer)."""
    if isinstance(history, np.ndarray) and history.dtype == np.float32 \
            and history.flags.owndata and history.flags.c_contiguous \
            and not history.flags.writeable:
        return history
    return np.array(history, np.float32, copy=True)


class PagedRequest:
    """One sequence. ``history`` is every embedding row the model has
    consumed for it (prompt rows + each decode-step input row): exactly
    what a re-prefill needs to rebuild the evicted cache. It is ONE
    growable [T, d_model] ndarray (amortized append), not a list of
    rows — re-admission previously paid an O(T) np.stack on every
    prefill and a per-row list append on every history flush.

    ``keys`` (optional) is one integer a history row — the token id the
    row embeds — in a sequence the request READS and its giver keeps
    APPENDING to as it steps the request (the token wrapper's stream,
    ``_SpecSeq.toks``): it must cover every row of the history whenever
    block hashes are asked for, and may run ahead of it. A request
    that has keys hashes its blocks from them and never touches its
    rows for that; one without (a caller with raw rows only) hashes
    its rows. What the request was handed decides, nothing else."""

    def __init__(self, rid: int, history: np.ndarray, keys=None):
        self.rid = rid
        arr = _request_rows(history)
        if arr.ndim != 2:
            raise ValueError("history must be [T, d_model] rows")
        self._hist = arr
        self._len = arr.shape[0]
        self.keys = None
        if keys is not None:
            self.bind_keys(keys)
        # chain hashes are append-only like the history: memoized and
        # extended in place, never recomputed across re-admissions
        self._hashes: List[bytes] = []
        self.slot: Optional[int] = None
        self.admit_seq = -1
        # global FIFO position among never-admitted requests (the
        # per-tenant sub-queues merge by it — see _queue_key)
        self.enqueue_seq = -1
        self.preemptions = 0
        # multi-tenant isolation: which tenant's quota/weight/floor
        # govern this request (set by submit; DEFAULT_TENANT when the
        # caller gave no tenant_id)
        self.tenant: str = DEFAULT_TENANT
        # resilience knobs (set by the engine at submit): re-prefill
        # retry budget and per-request deadlines — None = unbounded
        self.max_preemptions: Optional[int] = None
        self.deadline_steps: Optional[int] = None
        self.deadline_time: Optional[float] = None   # monotonic clock
        self.submit_step = 0
        # fork-shared parallel decoding (branch groups): ``gid`` is the
        # group id (the LEAD request's rid) for every member, ``branch``
        # the lane index within it. ``group_n`` > 1 marks a lead whose
        # branches have NOT forked yet (submit sets it; the fork clears
        # it, so a post-fork preemption re-prefills a normal request).
        self.gid: Optional[int] = None
        self.branch = 0
        self.group_n = 1

    @property
    def history(self) -> np.ndarray:
        """[T, d_model] view of every consumed row (no copy)."""
        return self._hist[:self._len]

    def bind_keys(self, keys) -> None:
        """Read the keys from ``keys`` from now on (the stream of the
        wrapper that steps this request: a fork, a branch and a
        restored request are bound to THEIR stream, which continues the
        one they were cut from)."""
        if len(keys) < self._len:
            raise ValueError(
                f"keys cover {len(keys)} of the history's {self._len} "
                f"rows")
        self.keys = keys

    @property
    def key_bytes(self) -> int:
        """Bytes of key material the chain reads a history row."""
        return 4 if self.keys is not None else 4 * self._hist.shape[1]

    def keys_held(self) -> Optional[np.ndarray]:
        """The keys of the rows in the history, as an int32 array of
        its own (a fork's and a snapshot's copy), or None."""
        if self.keys is None:
            return None
        return np.array(self.keys[:self._len], np.int32)

    def append_history(self, row) -> None:
        # a frozen array (the gathered prompt rows, kept uncopied) is
        # full by construction; the test holds for one rolled back too
        if self._len == self._hist.shape[0] or \
                not self._hist.flags.writeable:
            grown = np.empty((max(8, 2 * self._hist.shape[0]),
                              self._hist.shape[1]), np.float32)
            grown[:self._len] = self._hist[:self._len]
            self._hist = grown
        self._hist[self._len] = row
        self._len += 1

    def block_hashes(self, block_size: int) -> List[bytes]:
        """Chained hashes of every FULL block of the history (the
        identity the prefix cache indexes by), from the keys where the
        request has them and from the rows where it has not."""
        n_full = self._len // block_size
        have = len(self._hashes)
        if have < n_full:
            lo, hi = have * block_size, n_full * block_size
            if self.keys is None:
                material = self._hist[lo:hi]
            elif len(self.keys) < hi:
                raise ValueError(
                    f"request {self.rid}: keys cover {len(self.keys)} "
                    f"of {self._len} history rows; whoever steps a "
                    f"request it submitted with keys appends a key "
                    f"for every row it hands the engine")
            else:
                material = np.asarray(self.keys[lo:hi], np.int32)
            self._hashes.extend(chain_block_hashes(
                material, block_size,
                parent=self._hashes[-1] if self._hashes else b""))
        return self._hashes[:n_full]

    def truncate_history(self, length: int, block_size: int) -> None:
        """Roll the recorded history back to ``length`` rows
        (speculative rejection): rows past it were consumed
        speculatively and rejected, so a re-prefill must not replay
        them. Memoized chain hashes past the new last full block are
        dropped with them; the keys of the rows that stay are the
        first ``length`` of the key sequence, whose owner never
        appended the rejected ones."""
        if length < 0 or length > self._len:
            raise ValueError(
                f"truncate to {length} outside [0, {self._len}]")
        self._len = length
        del self._hashes[length // block_size:]

    def __len__(self):
        return self._len


class _GroupTable:
    """Engine-side registry of fork-shared branch groups (parallel
    sampling: ``submit(..., n=4)``). One record per live group:

      n         branch count the group was admitted for
      rids      member rids in branch order (rids[0] == gid == the
                lead's rid; branch rids land here AT FORK TIME — they
                are minted from the engine's rid counter then, so a
                journal replay reproduces them exactly)
      live      member rids without a terminal outcome yet (the group
                outcome-aggregation unit: the group is done when this
                empties)
      reserved  slot indices held for the pending branches while the
                lead's prompt streams (token-budget mode only): marked
                ``prefilling`` with no request/prefill state so the
                admission pass cannot hand them out; emptied by the
                fork (or by a lead drop)
      forked    whether the COW fork has run

    The table is ENGINE-BEHAVIORAL state (admission gating, fork
    targets, outcome aggregation), so it snapshots/restores with the
    engine — tools/check_static.py's snapshot-completeness pass audits
    it like any other state holder."""

    def __init__(self):
        self.groups: Dict[int, dict] = {}
        self._by_rid: Dict[int, int] = {}

    def create(self, gid: int, n: int) -> dict:
        g = {"n": int(n), "rids": [gid], "live": [gid],
             "reserved": [], "forked": False}
        self.groups[gid] = g
        self._by_rid[gid] = gid
        return g

    def add_branch(self, gid: int, rid: int) -> None:
        g = self.groups[gid]
        g["rids"].append(rid)
        g["live"].append(rid)
        self._by_rid[rid] = gid

    def gid_of(self, rid: int) -> Optional[int]:
        return self._by_rid.get(rid)

    def group_of(self, rid: int) -> Optional[dict]:
        gid = self._by_rid.get(rid)
        return None if gid is None else self.groups.get(gid)

    def reserved_slots(self) -> set:
        return {s for g in self.groups.values() for s in g["reserved"]}

    def on_terminal(self, rid: int) -> Optional[dict]:
        """Mark a member terminal; drop the record once every member
        is. Returns the (now possibly dead) group record, or None for
        a non-member rid."""
        gid = self._by_rid.get(rid)
        if gid is None:
            return None
        g = self.groups[gid]
        if rid in g["live"]:
            g["live"].remove(rid)
        if not g["live"]:
            for r in g["rids"]:
                self._by_rid.pop(r, None)
            del self.groups[gid]
        return g

    def snapshot(self) -> dict:
        return {"groups": [dict(g, gid=gid, rids=list(g["rids"]),
                                live=list(g["live"]),
                                reserved=list(g["reserved"]))
                           for gid, g in self.groups.items()],
                "by_rid": dict(self._by_rid)}

    def restore(self, rec: dict) -> None:
        self.groups = {}
        for g in rec.get("groups", []):
            self.groups[int(g["gid"])] = {
                "n": int(g["n"]), "rids": list(g["rids"]),
                "live": list(g["live"]),
                "reserved": [int(s) for s in g["reserved"]],
                "forked": bool(g["forked"])}
        self._by_rid = {int(r): int(gid)
                        for r, gid in rec.get("by_rid", {}).items()}


class PagedServingEngine:
    def __init__(self, model, max_batch: int, block_size: int,
                 num_blocks: int, max_blocks_per_seq: Optional[int] = None,
                 dtype: str = "float32", watermark_blocks: int = 0,
                 prefix_cache: bool = False,
                 chunk_tokens: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 injector=None, max_preemptions: Optional[int] = None,
                 numeric_guard: Optional[bool] = None,
                 tenants: Optional[Dict[str, dict]] = None,
                 collector=None, monitor=None, ledger=None,
                 ragged_step: bool = True):
        self.model = model
        # ragged mixed step (token-budget mode): plan the step's
        # prefill chunks, then launch them PACKED with the decode rows
        # as one model call — ONE paged-attention dispatch per layer —
        # instead of one launch per chunk plus one for the decode.
        # Packing engages ON THE KERNEL PATH (TPU / forced kernels),
        # where dispatch count is the cost being collapsed; the CPU
        # jnp fallback keeps the per-phase calls because CPU
        # bit-identity is strict and XLA CPU matmul row results are
        # only row-count-invariant at small shapes (a packed
        # [R, d] projection can differ from the [B, 1, d] call by a
        # ulp at serving widths). ragged_step="force" packs on the
        # CPU fallback too (tests/benches of the packing machinery —
        # bit-identical at test dims, token-identical at bench dims);
        # False keeps the legacy per-chunk launches everywhere.
        self.ragged_step = ragged_step
        self._ragged_plan: Optional[List[dict]] = None
        self.max_batch = int(max_batch)
        self.dtype = dtype
        self.watermark_blocks = int(watermark_blocks)
        self.prefix_cache = bool(prefix_cache)
        self.prefix_stats = PrefixCacheStats()
        self.prefill_stats = PrefillStats()
        # multi-tenant isolation: registration order is the WFQ
        # tie-break, so the dict's insertion order is load-bearing
        # (snapshots preserve it). The implicit default tenant always
        # exists; ``tenants={"a": {"quota_blocks": 8, "weight": 2}}``
        # pre-registers more, set_tenant adds/updates at runtime, and
        # an unknown tenant_id at submit auto-registers with the
        # unlimited defaults.
        self.tenants: Dict[str, Tenant] = {
            DEFAULT_TENANT: Tenant(DEFAULT_TENANT)}
        self._vclock = 0.0
        # resilience layer (inference/resilience.py): per-request
        # terminal outcomes instead of engine crashes, bounded retry,
        # optional deterministic fault injection + numeric guard. The
        # guard (one [B]-bool device->host read per step) defaults ON
        # only when an injector is present; pass numeric_guard=True to
        # run it in production serving too.
        self.injector = injector
        self.max_preemptions = max_preemptions
        self.numeric_guard = (injector is not None
                              if numeric_guard is None
                              else bool(numeric_guard))
        self.resilience_stats = ResilienceStats()
        # fork-shared parallel decoding (branch groups): group/branch
        # counters next to the resilience siblings
        self.parallel_stats = ParallelStats()
        self.outcomes: List[RequestOutcome] = []
        self._step_count = 0
        self._has_deadlines = False
        # telemetry (inference/telemetry.py). collector: the opt-in
        # TraceCollector — per-request lifecycle + step-phase timeline
        # + Chrome-trace export; None (default) keeps every hook site
        # dark (zero clock reads, zero allocations — the FaultInjector
        # pattern). The collector is PASSIVE (never consulted for
        # control flow) and deliberately NOT part of snapshot():
        # wall-clock timestamps stay out of engine-behavioral state;
        # a restored engine gets the caller's collector wired fresh.
        self.collector = collector
        # ledger (inference/accounting.py): the opt-in CostLedger —
        # classifies every token-row of model work as goodput, waste
        # (per-cause: speculative rejection, re-prefill replay, failed
        # requests) or pending, prices it through the analytic
        # WorkModel, and integrates per-tenant block-step billing.
        # Same contracts as the collector: None (default) keeps every
        # hook site dark, the ledger is PASSIVE (counters only, never
        # consulted for control flow, never reads a clock) and never
        # part of snapshot() — ledger state is derived.
        self.ledger = ledger
        # monitor (inference/monitor.py): the opt-in HealthMonitor —
        # windowed time-series over the registry, per-tenant SLO
        # tracking, deterministic threshold alerting. Sampled at the
        # end of every COMPLETED step (_end_step_telemetry); None
        # (default) keeps the hook dark, and like the collector it is
        # PASSIVE (reads only) and never part of snapshot() — monitor
        # state is derived, rebuilt by resampling after a restore.
        self.monitor = monitor
        # registry: the always-on unified metric surface — the five
        # stats siblings, tenant_report and the pool/queue gauges
        # behind ONE as_dict() (flat keys, interval-deltable). Sources
        # are LIVE (read at snapshot time), so attaching here costs
        # the hot path nothing.
        self.registry = MetricsRegistry()
        self.registry.attach("prefix_cache", self.prefix_stats)
        self.registry.attach("prefill", self.prefill_stats)
        self.registry.attach("resilience", self.resilience_stats)
        self.registry.attach("parallel", self.parallel_stats)
        self.registry.attach("tenants", self.tenant_report)
        # tiers_only: the registry's pool namespace is the per-step /
        # per-sample scrape surface (router, HealthMonitor) and must
        # stay O(1) — the per-slot / per-tenant occupancy HISTOGRAMS
        # are an explicit-diagnosis surface (cache.pool_occupancy(),
        # BlockOOM.details, the oom_shed event), not a gauge
        self.registry.attach(
            "pool",
            lambda: dict(self.cache.pool_occupancy(tiers_only=True),
                         peak=self.cache.peak_blocks_used))
        self.registry.attach("queue", self._queue_gauges)
        # sharded cores export their dispatch instrumentation (jit
        # calls, retraces, psums per call) next to allreduce_count —
        # the monitor's recompile-storm alert surface
        if hasattr(model, "sharded_metrics"):
            self.registry.attach("sharded", model.sharded_metrics)
        # MoE cores export per-expert load / overflow / routing totals
        # (moe_serving.MoeServingCore.moe_metrics) — the expert-collapse
        # detector's sampling surface; dense models leave the namespace
        # absent and the detector dark
        if hasattr(model, "moe_metrics"):
            self.registry.attach("moe", model.moe_metrics)
        self.cache = PagedKVCache.for_model(
            model, block_size, num_blocks, max_seqs=max_batch,
            max_blocks_per_seq=max_blocks_per_seq, dtype=dtype,
            prefix_cache=prefix_cache)
        if injector is not None:
            self.cache.allocator.fault_hook = \
                lambda n: injector.on_alloc("target", n)
        self.max_len = self.cache.capacity_per_seq
        for tid, cfg in (tenants or {}).items():
            self.set_tenant(tid, **cfg)
        # prompt chunk size (chunked_prefill): a multiple of the block
        # size by default so most chunk boundaries land on page edges;
        # any value >= MIN_PREFILL_SUFFIX_ROWS is bit-transparent
        if chunk_tokens is None:
            chunk_tokens = 4 * self.cache.block_size
        if chunk_tokens < MIN_PREFILL_SUFFIX_ROWS:
            raise ValueError(
                f"chunk_tokens must be >= {MIN_PREFILL_SUFFIX_ROWS}")
        self.chunk_tokens = int(chunk_tokens)
        # Sarathi-style mixed steps: with a budget, each step() spends
        # ~this many prompt tokens advancing pending prefills before
        # the fused decode call (a chunk may run ONE token past the
        # cap rather than leave a 1-row tail — the GEMV bit-identity
        # floor); admission only grants a slot. None (default):
        # admission prefills synchronously.
        if prefill_token_budget is not None and \
                prefill_token_budget < MIN_PREFILL_SUFFIX_ROWS:
            raise ValueError(
                f"prefill_token_budget must be >= "
                f"{MIN_PREFILL_SUFFIX_ROWS}")
        self.prefill_token_budget = prefill_token_budget
        self.lens = np.zeros(self.max_batch, np.int32)
        self.active = np.zeros(self.max_batch, bool)
        # slots granted but still streaming their prompt (mixed-step
        # mode): they own pages but must not ride the decode call
        self.prefilling = np.zeros(self.max_batch, bool)
        self._prefills: Dict[int, dict] = {}
        self._requests: List[Optional[PagedRequest]] = \
            [None] * self.max_batch
        # the physical queue lives SHARDED in the tenants' FIFO
        # sub-queues (Tenant.fifo) so the WFQ admission pass touches
        # one deque head per tenant; ``queue`` (property below) merges
        # them back into the legacy global order for snapshots,
        # deadline scans and diagnostics. _queue_len is the O(1)
        # depth gauge the hot paths read.
        self._queue_len = 0
        self._next_enqueue_seq = 0
        # decode inputs not yet attributed to request histories:
        # (x, active-mask) per step, materialized to host lazily so the
        # hot decode loop never pays a device->host sync for the
        # (rare) preemption path
        self._pending_history: List[Tuple[Tensor, np.ndarray]] = []
        self._next_rid = 0
        self._next_admit_seq = 0
        # fork-shared parallel decoding: branch-group registry (the
        # group is the unit of admission, fork and outcome
        # aggregation; a forked branch is a NORMAL slot everywhere
        # else — growth, preemption, shed)
        self.groups = _GroupTable()
        # event queues the caller drains
        self.admitted: List[Tuple[int, int, Tensor]] = []
        self.finished: List[Tuple[int, int, int]] = []
        self.preempted: List[int] = []
        # the ledger binds before the monitor so the monitor's
        # baseline registry snapshot already carries the work.* keys
        if ledger is not None:
            ledger.bind(self.registry, model=model,
                        kv_token_bytes=self.cache.kv_bytes_per_token())
        # wire the monitor LAST (its baseline snapshot reads the live
        # registry sources, which need the engine fully built); the
        # rebase pins the interval-delta baseline at the current step
        # so the first sampled step computes a one-interval delta —
        # the same contract PagedServingEngine.restore re-establishes
        if monitor is not None:
            monitor.bind(self.registry, collector=collector)
            monitor.rebase(self._step_count)

    # -- introspection ------------------------------------------------
    @property
    def num_active(self) -> int:
        return int(self.active.sum())

    @property
    def num_prefilling(self) -> int:
        return int(self.prefilling.sum())

    @property
    def free_slots(self) -> int:
        return int((~self.active & ~self.prefilling).sum())

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: the true free list PLUS the cached-free
        second-chance tier (reclaimable on demand)."""
        return self.cache.allocator.num_free

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_stats.hit_rate

    # -- tenants ------------------------------------------------------
    @property
    def tenant_stats(self) -> Dict[str, TenantStats]:
        """{tenant_id: TenantStats} — the noisy-neighbor attribution
        surface (blocks_held gauges refresh at every step top)."""
        return {tid: t.stats for tid, t in self.tenants.items()}

    def set_tenant(self, tenant_id: str, *,
                   quota_blocks: Optional[int] = None,
                   reserved_blocks: int = 0,
                   weight: float = 1.0) -> Tenant:
        """Register or reconfigure a tenant. Refused (ValueError) when
        the quota would fall below the tenant's CURRENT charge (the
        audit asserts charge <= quota, and enforcement only gates new
        growth — a silently over-quota tenant would be a lie) or when
        the reserved floors together exceed the usable pool (an
        unkeepable promise). Stats and the WFQ virtual time survive
        reconfiguration."""
        held = self.cache.tenant_charge(tenant_id)
        if quota_blocks is not None and quota_blocks < held:
            raise ValueError(
                f"tenant {tenant_id!r} already holds {held} block(s); "
                f"a quota of {quota_blocks} would be violated on "
                f"arrival — drain the tenant first")
        existing = self.tenants.get(tenant_id)
        ten = Tenant(tenant_id, quota_blocks=quota_blocks,
                     reserved_blocks=reserved_blocks, weight=weight)
        if existing is not None:
            ten.vtime = existing.vtime
            ten.fifo = existing.fifo
            ten.queued = existing.queued
            ten.stats = existing.stats
        total_reserved = ten.reserved_blocks + sum(
            t.reserved_blocks for tid, t in self.tenants.items()
            if tid != tenant_id)
        usable = self.cache.num_blocks - 1 - self.watermark_blocks
        if total_reserved > usable:
            raise ValueError(
                f"reserved floors total {total_reserved} block(s) but "
                f"only {usable} are usable (pool {self.cache.num_blocks}"
                f" minus trash and watermark) — the guarantee would be "
                f"unkeepable")
        self.tenants[tenant_id] = ten
        return ten

    def _tenant_of(self, req: PagedRequest) -> Tenant:
        return self.tenants[req.tenant]

    @staticmethod
    def _queue_key(req: PagedRequest):
        """Global queue-order key the per-tenant sub-queues merge by:
        preempted requests (sunk compute) ride ahead of never-admitted
        ones, ordered by original submission age among themselves —
        exactly the order the old single physical deque maintained."""
        if req.preemptions > 0:
            return (0, req.rid)
        return (1, req.enqueue_seq)

    @property
    def queue(self) -> List[PagedRequest]:
        """The merged global queue view, in admission-contract order
        (see _queue_key). Built on demand — snapshot, deadline scans,
        audits and external callers read it; the admission hot path
        never does (it reads the per-tenant sub-queue heads)."""
        out: List[PagedRequest] = []
        for ten in self.tenants.values():
            out.extend(ten.fifo)
        out.sort(key=self._queue_key)
        return out

    def _enqueue(self, req: PagedRequest) -> None:
        """Queue a never-admitted request at its tenant's tail."""
        req.enqueue_seq = self._next_enqueue_seq
        self._next_enqueue_seq += 1
        ten = self.tenants[req.tenant]
        ten.fifo.append(req)
        ten.queued += 1
        self._queue_len += 1

    def _dequeue(self, req: PagedRequest) -> None:
        """The one way OFF the queue (the tenant's queued gauge moves
        with the request) — raises ValueError if not queued."""
        ten = self.tenants[req.tenant]
        if ten.fifo and ten.fifo[0] is req:
            ten.fifo.popleft()      # the admission path: O(1)
        else:
            ten.fifo.remove(req)    # rare (failure/release paths)
        ten.queued -= 1
        self._queue_len -= 1

    def _resolve_tenant(self, tenant_id: Optional[str]) -> Tenant:
        tid = DEFAULT_TENANT if tenant_id is None else str(tenant_id)
        ten = self.tenants.get(tid)
        if ten is None:
            ten = self.set_tenant(tid)   # unlimited defaults
        return ten

    def _unmet_floors(self, exclude: str) -> int:
        """Free-pool headroom reserved for OTHER tenants still below
        their floors — blocks the ``exclude`` tenant may not touch."""
        return sum(
            max(0, t.reserved_blocks - self.cache.tenant_charge(tid))
            for tid, t in self.tenants.items()
            if tid != exclude and t.reserved_blocks)

    def _bump_vtime(self, tid: str) -> None:
        """Start-time fairness: a tenant enqueueing from IDLE (nothing
        of it queued) starts at the virtual clock instead of replaying
        service credit it accrued by sitting out."""
        ten = self.tenants[tid]
        if ten.queued == 0 and ten.vtime < self._vclock:
            ten.vtime = self._vclock

    def tenant_report(self) -> Dict[str, dict]:
        """Operator view: per-tenant config + live occupancy/queue +
        stats (the doctor and the bench print this)."""
        active: Dict[str, int] = {}
        for s in np.flatnonzero(self.active | self.prefilling):
            req = self._requests[int(s)]
            if req is not None:
                active[req.tenant] = active.get(req.tenant, 0) + 1
        cost = (self.ledger.tenant_cost()
                if self.ledger is not None else None)
        return {tid: dict({
            "quota_blocks": t.quota_blocks,
            "reserved_blocks": t.reserved_blocks,
            "weight": t.weight,
            "vtime": round(t.vtime, 6),
            "blocks_held": self.cache.tenant_charge(tid),
            "active": active.get(tid, 0),
            "queued": t.queued,
            "stats": t.stats.as_dict(),
        }, **({"cost": cost[tid]} if cost and tid in cost else {}))
            for tid, t in self.tenants.items()}

    # -- admission ----------------------------------------------------
    @property
    def collector(self):
        """The installed ``TraceCollector`` or None. A model core that
        records spans of its own (``DecoderCore``: ``moe``) is handed
        whatever is installed here, whoever installs it."""
        return self._collector

    @collector.setter
    def collector(self, col) -> None:
        self._collector = col
        if hasattr(self.model, "collector"):
            self.model.collector = col
        if col is not None and col.registry is not None:
            # scraped cold when the collector is dumped (never on the
            # hot path): what the block-identity chain read, and the
            # expert layer's device-side counters
            col.registry.attach("prefix_cache", self.prefix_stats)
            if hasattr(self.model, "moe_metrics"):
                col.registry.attach("moe", self.model.moe_metrics)

    def submit(self, prompt, *, max_preemptions: Optional[int] = None,
               deadline_steps: Optional[int] = None,
               deadline_s: Optional[float] = None,
               tenant_id: Optional[str] = None,
               n: int = 1, keys=None) -> int:
        """Queue a prompt ([T, d_model] embeddings) and try to admit.
        Returns the request id; if admission succeeded an
        ``(rid, slot, last_hidden)`` event is in ``admitted``. With
        ``prefill_token_budget`` set, admission only grants a slot —
        the prompt streams during subsequent ``step`` calls and the
        admitted event fires when the last chunk lands.

        ``tenant_id`` attributes the request to a tenant (quota /
        reserved floor / admission weight — see the class docstring);
        None maps to the implicit unlimited ``default`` tenant, and an
        unknown id auto-registers one with unlimited defaults.

        HEALTH-BASED ADMISSION CONTROL: a request that provably can
        never be served — its prompt needs more blocks than its
        tenant's quota, or than the pool minus other tenants' reserved
        floors, or (token-budget mode) its ``deadline_steps`` is below
        the prefill-step lower bound ceil(T / (budget + 1)) — is
        REJECTED at submit with a terminal ``REJECTED_ADMISSION``
        outcome in ``outcomes`` instead of being queued to fail later.
        Rejection is an outcome, never an exception, and depends only
        on deterministic scheduler state, so a journaled replay
        re-rejects identically. (Malformed submissions — empty prompt,
        prompt past the per-seq page capacity — still raise ValueError
        before any engine mutation, as before.)

        Resilience knobs (all optional, None = unbounded):
        ``max_preemptions`` caps the re-prefill retry budget for THIS
        request (overriding the engine default) — exceeding it fails
        the request with FAILED_OOM instead of requeueing, so two long
        prompts can never livelock each other through eviction.
        ``deadline_steps`` / ``deadline_s`` fail the request
        (FAILED_DEADLINE) once that many engine steps / seconds have
        passed since submission, whether it is running, mid-prefill or
        still queued. Terminal outcomes surface in ``outcomes``.

        FORK-SHARED PARALLEL DECODING (``n`` > 1): ONE request is
        queued whose prompt prefills ONCE; when the last chunk lands
        the engine COW-forks n-1 branch slots whose block tables
        reference the same prompt pages (each branch charged per
        reference — the PR 7 policy), every branch gets its own fresh
        rid and its own ``(rid, slot, last_hidden)`` admitted event
        sharing the lead's prefill hidden, and from then on each
        branch is a normal slot (growth COW-splits the written block;
        preemption degrades a branch to an independent re-prefill).
        Admission requires n free slots; the group is the admission
        unit. The return value is the LEAD's rid == the group id.

        ``keys``: one integer a row (the token id it embeds), for a
        caller that has them: the request's block identities are then
        hashed from the keys and the rows are never read for it. It is
        a sequence the caller keeps appending to, a key for every row
        it later hands ``step`` for this request (``PagedRequest``).
        Without keys the rows themselves are hashed. ``prompt`` is
        copied unless its giver froze it (``_request_rows``)."""
        arr = np.asarray(prompt.numpy() if hasattr(prompt, "numpy")
                         else prompt, np.float32)
        if arr.shape[0] == 0:
            raise ValueError("empty prompt")
        if keys is not None and len(keys) < arr.shape[0]:
            raise ValueError(
                f"{len(keys)} keys for {arr.shape[0]} prompt rows")
        if arr.shape[0] > self.max_len:
            raise ValueError(
                f"prompt length {arr.shape[0]} > per-seq page capacity "
                f"{self.max_len}")
        n = int(n)
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if n > self.max_batch:
            raise ValueError(
                f"n={n} branches exceed max_batch={self.max_batch}")
        if n > 1:
            # the branches fork the prompt's pages, and its state
            self.cache._refuse_with_state("fork (n > 1 samples a prompt)")
        ten = self._resolve_tenant(tenant_id)
        req = PagedRequest(self._next_rid, arr, keys)
        self._next_rid += 1
        req.tenant = ten.tid
        if n > 1:
            req.gid = req.rid
            req.group_n = n
        req.max_preemptions = (self.max_preemptions
                               if max_preemptions is None
                               else int(max_preemptions))
        req.submit_step = self._step_count
        if deadline_steps is not None:
            req.deadline_steps = int(deadline_steps)
        if deadline_s is not None:
            req.deadline_time = time.monotonic() + float(deadline_s)
        if self.collector is not None:
            self.collector.on_submit(req.rid, ten.tid, arr.shape[0])
        if self.ledger is not None:
            self.ledger.on_submit(req.rid, ten.tid, arr.shape[0])
        reject = self._admission_health(req, ten)
        if reject:
            self._record(req, RequestOutcome.REJECTED_ADMISSION,
                         reject)
            return req.rid
        if deadline_steps is not None or deadline_s is not None:
            self._has_deadlines = True
        if n > 1:
            self.groups.create(req.rid, n)
            self.parallel_stats.groups += 1
        col = self.collector
        if self.prefix_cache:
            # the prompt's chain hashes, memoized on the request: the
            # admission pass (now or rounds later) probes the prefix
            # index with them. Computed here so that their cost — a
            # hash over the prompt's keys — is the submit's, and has a
            # span of its own, which says what the chain read
            read = self.prefix_stats.hashed_bytes
            if col is not None:
                col.span_begin("submit.hash", rid=req.rid)
            try:
                self._block_hashes(req)
            finally:
                if col is not None:
                    col.span_end(
                        bytes=self.prefix_stats.hashed_bytes - read,
                        keyed="rows" if req.keys is None else "ids")
        self._bump_vtime(ten.tid)
        depth = col.span_depth if col is not None else 0
        if col is not None:
            col.span_begin("submit.admit", rid=req.rid)
        try:
            self._enqueue(req)
            self._try_admit()
        except BaseException:
            if col is not None:
                col.span_unwind(depth, aborted=True)
            raise
        if col is not None:
            col.span_unwind(depth)
        return req.rid

    def _block_hashes(self, req: PagedRequest) -> List[bytes]:
        """``req.block_hashes`` at the pool's block size, with what the
        chain had to read booked in ``prefix_stats``."""
        bs = self.cache.block_size
        have = len(req._hashes)
        hashes = req.block_hashes(bs)
        new = len(req._hashes) - have
        self.prefix_stats.hashed_bytes += new * bs * req.key_bytes
        if req.keys is None:
            self.prefix_stats.row_keyed_blocks += new
        return hashes

    def request_of(self, rid: int) -> Optional[PagedRequest]:
        """The live request ``rid``: in a slot, or queued."""
        for r in self._requests:
            if r is not None and r.rid == rid:
                return r
        for r in self.queue:
            if r.rid == rid:
                return r
        return None

    def _admission_health(self, req: PagedRequest,
                          ten: Tenant) -> str:
        """Reason string when the request provably cannot be served
        from the current configuration (it would only ever burn pool
        and queue time before failing), else ''. Every check is a
        PERMANENT impossibility under the current tenant/pool
        contracts — transient pressure never rejects, it queues."""
        # the horizon every serving path must eventually cover: the
        # prompt PLUS the first decode token's page (the same +1 the
        # synchronous admission gate uses — a prompt ending on a block
        # boundary needs one block more than blocks_needed(T), and a
        # health check one block looser would queue it to stall at the
        # admission gate forever)
        need = self.cache.blocks_needed(min(len(req) + 1, self.max_len))
        # a branch group charges its tenant per REFERENCE (every
        # branch table references the shared prompt blocks), while the
        # PHYSICAL pool holds the prompt once plus each extra branch's
        # COW-split write page — both horizons must be coverable
        charge_need = need * req.group_n
        phys_need = need + max(0, req.group_n - 1)
        if ten.quota_blocks is not None and \
                charge_need > ten.quota_blocks:
            return (f"prompt needs {charge_need} charged block(s) "
                    f"through its first decode token "
                    f"(x{req.group_n} branch references) but tenant "
                    f"{ten.tid!r} quota is {ten.quota_blocks} — can "
                    f"never be admitted")
        # the permanent bound subtracts other tenants' FULL reserved
        # floors, not the currently-unmet remainder: free minus unmet
        # can never exceed usable minus reserved (free <= usable -
        # charge, unmet = max(0, reserved - charge)), so a check built
        # on the momentary unmet would queue a request every admission
        # pass then floor-skips forever once the floor tenant's charge
        # drops back
        reserved_others = sum(
            t.reserved_blocks for tid, t in self.tenants.items()
            if tid != ten.tid)
        room = self.cache.num_blocks - 1 - self.watermark_blocks \
            - reserved_others
        if phys_need > room:
            return (f"prompt needs {phys_need} block(s) through its "
                    f"first decode token but only {room} can ever be "
                    f"available past other tenants' reserved floors "
                    f"and the watermark")
        if req.deadline_steps is not None and \
                self.prefill_token_budget is not None:
            # each mixed step advances at most budget + 1 prompt
            # tokens (the soft cap), so this lower bound is exact
            floor_steps = -(-len(req) // (self.prefill_token_budget
                                          + 1))
            if req.deadline_steps < floor_steps:
                return (f"prefill alone needs >= {floor_steps} "
                        f"step(s) at prefill_token_budget="
                        f"{self.prefill_token_budget} but the "
                        f"deadline is {req.deadline_steps} — cannot "
                        f"be met at any pool pressure")
        return ""

    def _try_admit(self) -> None:
        """One admission pass, then the ``post_admission`` crash
        point (CrashInjector — a no-op without an injector)."""
        self._admit_pass()
        self._crash("post_admission")

    def _admit_pass(self) -> None:
        """Weighted fair admission: while a slot is free, the queued
        tenant with the LOWEST virtual time (ties broken by
        registration order) offers its oldest queued request —
        age-fair within a tenant, and preempted requests still ride
        ahead of never-admitted ones (the physical queue keeps the
        PR 5 ordering; tenancy only picks WHICH tenant's head goes
        next). The block budget must cover the admission horizon plus
        the watermark: the whole prompt (plus the first decode
        token's page) in synchronous mode, only the FIRST chunk in
        token-budget mode — chunked prefill grows the rest page by
        page under the normal preemption rules.

        Isolation semantics of a blocked head: a tenant blocked by
        its OWN quota, or by OTHER tenants' unmet reserved floors, is
        SKIPPED for this pass (its cap must never become its
        neighbors' head-of-line blocker) and its virtual time does
        not advance; true pool pressure — the head does not fit the
        raw free pool — stops the whole pass, the same no-starvation
        head-of-line rule as before (the blocked tenant keeps the
        lowest vtime, so it admits first once space frees)."""
        skipped: set = set()
        order = {tid: i for i, tid in enumerate(self.tenants)}
        while self._queue_len and self.free_slots > 0:
            # head selection is O(tenants): each tenant's oldest
            # queued request IS its sub-queue head (no global scan)
            cands = [tid for tid, t in self.tenants.items()
                     if t.fifo and tid not in skipped]
            if not cands:
                return
            tid = min(cands, key=lambda t: (self.tenants[t].vtime,
                                            order.get(t, len(order))))
            ten = self.tenants[tid]
            req = ten.fifo[0]
            # a branch group admits as ONE unit: the lead's prompt
            # plus a slot per branch — without all n slots the fork at
            # prefill completion could not land, so the group waits
            # head-of-line (same no-starvation rule as pool pressure)
            if req.group_n > self.free_slots:
                return
            if self.prefill_token_budget is None:
                # cover the prompt AND the first decode token's page —
                # admitting with zero headroom would re-preempt a
                # request sitting on a block boundary every step
                # (prefill/evict livelock)
                horizon = min(len(req) + 1, self.max_len)
            else:
                horizon = min(len(req), self.chunk_tokens)
            need = self.cache.blocks_needed(horizon)
            # tenant quota gates the FULL reference count (shared
            # prefix hits are charged per reference — the policy note
            # in PagedKVCache.__init__), unlike the pool draw below;
            # a branch group's fork multiplies every prompt-block
            # reference by n, so the quota gate scales with it
            quota_need = need * req.group_n
            if ten.quota_blocks is not None and \
                    self.cache.tenant_charge(tid) + quota_need \
                    > ten.quota_blocks:
                ten.stats.quota_hits += 1
                skipped.add(tid)
                continue
            if self.prefix_cache:
                # actively shared prefix hits cost no pool draw at all;
                # cached-free hits come out of free_blocks (a resurrect
                # consumes one free unit, same as an alloc) so only the
                # active ones discount `need`
                matched = self.cache.match_prefix(
                    self._block_hashes(req))
                rc = self.cache.allocator.refcount
                need -= sum(1 for b in matched if rc[b] > 0)
            # physical pool draw: the prompt pages land ONCE however
            # many branches will reference them; each extra branch
            # only needs headroom for its first COW-split write page
            draw = max(need, 0) + max(0, req.group_n - 1) \
                + self.watermark_blocks
            if draw > self.free_blocks:
                return  # head-of-line pool pressure blocks the pass
            if draw > self.free_blocks - self._unmet_floors(tid):
                # only other tenants' reservations stand in the way:
                # their entitlement, this tenant's wait
                skipped.add(tid)
                continue
            self._dequeue(req)
            self._vclock = ten.vtime
            ten.vtime += 1.0 / ten.weight
            if self.prefill_token_budget is None:
                try:
                    self._prefill(req)
                except BlockOOM as e:
                    if self.collector is not None:
                        self.collector.on_event("block_oom", dict(
                            e.details, rid=req.rid, tenant=req.tenant,
                            step=self._step_count))
                    # the budget check above said the prompt fits, so
                    # this is an injected fault (or a raced reclaim):
                    # un-admit — drop the partial pages and retry on a
                    # later admission pass, against the retry budget
                    if req.slot is not None:
                        self._drop(req.slot)
                        req.slot = None
                    if self._over_retry_budget(req):
                        self._fail(req, RequestOutcome.FAILED_OOM,
                                   f"admission prefill OOM and retry "
                                   f"budget exhausted: {e}")
                    else:
                        req.preemptions += 1
                        self._tenant_of(req).stats.preemptions += 1
                        self._requeue_preempted(req)
                        self.preempted.append(req.rid)
                    return
            else:
                # grant the slot only; step() streams the chunks
                self._start_prefill(req)

    def _start_prefill(self, req: PagedRequest) -> int:
        """Grant a slot and set up chunked-prefill state: adopt any
        cached prefix pages and compute the recompute start P (the
        suffix keeps at least MIN_PREFILL_SUFFIX_ROWS rows — see the
        constant's comment: 1-row GEMV accumulation breaks
        bit-identity, and the admission event needs a last hidden)."""
        slot = int(np.flatnonzero(~self.active & ~self.prefilling)[0])
        # attribute the slot BEFORE any page lands in it, so adopted
        # prefix blocks and the first chunk's pages charge the right
        # tenant from the first reference
        self.cache.set_seq_tenant(slot, req.tenant)
        T = len(req)
        bs = self.cache.block_size
        hashes: List[bytes] = []
        n_cached = 0
        if self.prefix_cache:
            hashes = self._block_hashes(req)
            n_cached = self.cache.adopt_prefix(slot, hashes)
            self.prefix_stats.lookups += 1
            self.prefix_stats.lookup_blocks += len(hashes)
            self.prefix_stats.hit_blocks += n_cached
        P = max(0, min(n_cached * bs, T - MIN_PREFILL_SUFFIX_ROWS)) \
            if n_cached else 0
        if self.ledger is not None and P:
            # rows [0, P) adopted, never computed: prefix-cache
            # savings (warm-resume savings on a re-prefill)
            self.ledger.on_prefill_skip(req.rid, P)
        self._prefills[slot] = {"pos": P, "start": P,
                                "n_cached": n_cached, "hashes": hashes}
        self.prefilling[slot] = True
        self._requests[slot] = req
        req.slot = slot
        req.admit_seq = self._next_admit_seq
        self._next_admit_seq += 1
        self._tenant_of(req).stats.admitted += 1
        if req.preemptions > 0:
            self.resilience_stats.retried += 1
        if self.collector is not None:
            self.collector.on_admitted(req.rid, slot,
                                       retry=req.preemptions > 0)
        if req.group_n > 1 and self.prefill_token_budget is not None:
            # token-budget mode: the lead's prompt streams over many
            # steps while admission keeps running — hold the branch
            # slots NOW (prefilling, no request/prefill state) so the
            # fork at prefill completion still has its n-1 targets.
            # The admission gate guaranteed free_slots >= group_n.
            g = self.groups.groups[req.gid]
            for _ in range(req.group_n - 1):
                rs = int(np.flatnonzero(~self.active
                                        & ~self.prefilling)[0])
                self.prefilling[rs] = True
                g["reserved"].append(rs)
        return slot

    def _complete_prefill(self, slot: int, last_hidden) -> None:
        """Last chunk landed: the slot turns decodable and the
        admission event fires."""
        st = self._prefills.pop(slot)
        req = self._requests[slot]
        T = len(req)
        if self.prefix_cache:
            self.cache.register_prefix(slot, st["hashes"])
            self.prefix_stats.tokens_computed += T - st["start"]
            self.prefix_stats.tokens_skipped += st["start"]
        self.prefilling[slot] = False
        self.lens[slot] = T
        self.active[slot] = True
        self.admitted.append((req.rid, slot, last_hidden))
        if self.collector is not None:
            # the admitted event's last hidden is what the caller
            # samples the FIRST TOKEN from — TTFT's defining moment
            self.collector.on_first_token(req.rid)
        self._fork_group(slot, last_hidden)
        self._crash("post_prefill")

    def _chunk_registrar(self, slot: int, st: dict):
        """``on_chunk`` hook for chunked_prefill: index every COMPLETED
        prompt block under its chain hash as the stream advances (not
        only at prefill completion), so a preemption or crash-restore
        mid-prefill re-adopts its own finished pages on re-admission
        instead of recomputing them — the pages park cached-free when
        the victim's slot is dropped and resurrect via adopt_prefix.
        Only full blocks below the write frontier are registered;
        their content is final (later chunks write strictly past
        them), so the immutability audit holds."""
        if not self.prefix_cache:
            return None
        last = [0]      # blocks registered so far by THIS registrar —
                        # keeps a C-chunk prefill at O(blocks), not
                        # O(blocks x chunks) re-probes of the prefix

        def register(pos: int) -> None:
            done = pos // self.cache.block_size
            if done > last[0]:
                self.cache.register_prefix(slot, st["hashes"][:done],
                                           start=last[0])
                last[0] = done
        return register

    def _chunk_hook(self, slot: int, st: dict, req: PagedRequest):
        """``on_chunk`` for engine prefills: the prefix registrar
        (above) composed with the telemetry chunk event and the cost
        ledger's chunk accounting — one callback, built only when a
        consumer exists. The ledger sees every computed chunk as a
        [prev, pos) row span (the replay-vs-fresh split happens
        inside the ledger off its per-request high-water mark)."""
        reg = self._chunk_registrar(slot, st)
        col = self.collector
        led = self.ledger
        if col is None and led is None:
            return reg
        rid = req.rid
        prev = [st["pos"]]

        def hook(pos: int) -> None:
            if reg is not None:
                reg(pos)
            if led is not None:
                led.on_prefill(rid, prev[0], pos)
                prev[0] = pos
            if col is not None:
                col.on_prefill_chunk(rid, pos)
        return hook

    def _prefill(self, req: PagedRequest) -> None:
        """Synchronous admission: stream every chunk now (block budget
        for the whole prompt was checked by _try_admit, so the chunk
        ensures cannot OOM). Runs outside the step-phase timeline
        (submit-time admission), so it records its own ``prefill``
        span — admission prefill cost stays visible either way."""
        slot = self._start_prefill(req)
        st = self._prefills[slot]
        col = self.collector
        depth = col.span_depth if col is not None else 0
        if col is not None:
            col.span_begin("prefill", rid=req.rid,
                           tokens=len(req) - st["pos"])
        try:
            _, h = chunked_prefill(
                self.model, self.cache, slot, req.history,
                pos=st["pos"], target=len(req),
                chunk_tokens=self.chunk_tokens,
                start_block=st["n_cached"],
                write_start=st["n_cached"] * self.cache.block_size,
                stats=self.prefill_stats,
                on_chunk=self._chunk_hook(slot, st, req))
            self._complete_prefill(slot, h)
        except BaseException:
            # an injected BlockOOM or EngineCrash mid-prefill unwinds
            # through here (the admission pass un-admits): close the
            # span flagged so the trace shows the tear-down
            if col is not None:
                col.span_unwind(depth, aborted=True)
            raise
        if col is not None:
            col.span_unwind(depth)

    def _advance_prefills(self) -> Tuple[bool, List[int]]:
        """Token-budget mode: spend ``prefill_token_budget`` prompt
        tokens on pending prefills, oldest first (finish what was
        started before newer grants). The cap is soft by one token:
        a chunk never splits below MIN_PREFILL_SUFFIX_ROWS and never
        leaves a 1-row tail, so when the remaining budget and prompt
        collide with that floor the chunk runs one token long rather
        than deferring (a deferral could never clear — the budget is
        identical next step). Page growth preempts the
        youngest request on OOM — possibly a prefilling one, possibly
        the slot being advanced itself (it then re-queues whole).
        Returns (ran, fresh): whether any chunk ran, and the slots
        whose prefill COMPLETED just now — the caller hasn't drained
        their admitted events yet, so they must sit this step's
        decode out."""
        if self.prefill_token_budget is None or \
                self.num_prefilling == 0:
            return False, []
        budget = self.prefill_token_budget
        ran = False
        fresh: List[int] = []
        while budget >= MIN_PREFILL_SUFFIX_ROWS:
            # reserved branch slots (prefilling, no prefill state)
            # hold no prompt to advance — only real prefills qualify
            slots = [int(s) for s in np.flatnonzero(self.prefilling)
                     if int(s) in self._prefills]
            if not slots:
                break
            slot = min(slots,
                       key=lambda s: self._requests[s].admit_seq)
            req = self._requests[slot]
            st = self._prefills[slot]
            T = len(req)
            c = _chunk_len(T, st["pos"], self.chunk_tokens,
                           budget=budget)
            if not self._grow_chunk(slot, req, st, c):
                continue  # the slot was evicted (or shed) growing
            pos, h = chunked_prefill(
                self.model, self.cache, slot, req.history,
                pos=st["pos"], target=st["pos"] + c,
                chunk_tokens=self.chunk_tokens,
                start_block=st["n_cached"],
                write_start=st["n_cached"] * self.cache.block_size,
                stats=self.prefill_stats,
                on_chunk=self._chunk_hook(slot, st, req))
            st["pos"] = pos
            budget -= c
            ran = True
            if pos >= T:
                # the slot, and the branches its completion forked: the
                # caller has drained none of their admitted events yet
                idle = ~self.active
                self._complete_prefill(slot, h)
                fresh.extend(int(s) for s in
                             np.flatnonzero(self.active & idle))
        if ran:
            self.prefill_stats.prefill_steps += 1
        return ran, fresh

    def _grow_chunk(self, slot: int, req: PagedRequest, st: dict,
                    c: int) -> bool:
        """Cover the next ``c`` prompt rows of a streaming prefill
        (``_grow_or_shed`` under a ``grow`` span: the chunk's page
        growth is the paged-cache manager's time, like the decode
        rows')."""
        col = self.collector
        if col is not None:
            col.span_begin("grow", rid=req.rid)
        try:
            return self._grow_or_shed(slot, req, st["pos"] + c,
                                      start_block=st["n_cached"],
                                      write_from=st["pos"])
        finally:
            if col is not None:
                col.span_end()

    def _plan_prefills(self) -> Tuple[bool, List[int]]:
        """RAGGED token-budget mode: spend the prefill budget exactly
        like ``_advance_prefills`` — identical chunk lengths, growth/
        preemption sequence and stats — but RECORD the chunks in
        ``self._ragged_plan`` instead of launching each as its own
        model call; the step's single packed launch
        (``_flush_ragged_plan``) runs them with the decode rows.
        Completed prefills transition slot state HERE (so the step's
        masks and capacity checks match the eager path exactly); the
        admitted event and prefix registration fire post-launch, when
        the pages exist. A drop of a planned slot flushes the pending
        segments first (``_drop``) — in the eager path those chunks
        had already run before any later preemption could fire, so
        registration/warm-resume semantics are unchanged."""
        if self.prefill_token_budget is None or \
                self.num_prefilling == 0:
            return False, []
        plan = self._ragged_plan
        budget = self.prefill_token_budget
        ran = False
        fresh: List[int] = []
        while budget >= MIN_PREFILL_SUFFIX_ROWS:
            # reserved branch slots (prefilling, no prefill state)
            # hold no prompt to advance — only real prefills qualify
            slots = [int(s) for s in np.flatnonzero(self.prefilling)
                     if int(s) in self._prefills]
            if not slots:
                break
            slot = min(slots,
                       key=lambda s: self._requests[s].admit_seq)
            req = self._requests[slot]
            st = self._prefills[slot]
            T = len(req)
            c = _chunk_len(T, st["pos"], self.chunk_tokens,
                           budget=budget)
            if not self._grow_chunk(slot, req, st, c):
                continue  # the slot was evicted (or shed) growing
            seg = plan[-1] if plan and plan[-1]["slot"] == slot \
                else None
            if seg is None:
                seg = {"slot": slot, "req": req, "from": st["pos"],
                       "to": st["pos"],
                       "ws": st["n_cached"] * self.cache.block_size,
                       "bounds": [],
                       "hook": self._chunk_hook(slot, st, req),
                       "complete": False}
                plan.append(seg)
            st["pos"] += c
            seg["to"] = st["pos"]
            seg["bounds"].append(st["pos"])
            # chunk accounting at the same points chunked_prefill hits
            self.prefill_stats.chunks += 1
            self.prefill_stats.prefill_tokens += c
            self.prefill_stats.peak_blocks = max(
                self.prefill_stats.peak_blocks,
                self.cache.blocks_in_use)
            budget -= c
            ran = True
            if st["pos"] >= T:
                seg["complete"] = True
                self.prefilling[slot] = False
                self.lens[slot] = T
                self.active[slot] = True
                fresh.append(slot)
        if ran:
            self.prefill_stats.prefill_steps += 1
        return ran, fresh

    def _flush_ragged_plan(self, x: Optional[Tensor] = None,
                           L: int = 1):
        """Run the pending planned prefill segments — plus, at the
        step's model point, the fused decode rows — as ONE ragged
        model call through ``PagedKVCache.ragged_views``. CPU streams
        stay bit-identical to the per-chunk launches (the view
        decomposes back into the per-phase executables; the packed
        non-attention ops are per-row invariant — the same contract
        chunked prefill rests on), and the kernel path collapses the
        step to one paged-attention dispatch per layer. ``L`` > 1
        packs a MULTI-TOKEN verify alongside the prefill chunks
        (step_multi in token-budget mode): x is [max_batch, L, d] and
        each slot contributes L rows at positions lens .. lens+L-1.
        Returns the decode hidden [max_batch, L, d] when ``x`` rode
        along, else None.

        SHARD-AWARE by construction: a ShardedServingCore model takes
        the same single packed call and fans each layer out over the
        ragged views' ``shard(s)`` accessor — one ragged launch per
        layer PER SHARD on its own pool slice, closed by exactly one
        all-reduce per layer (the mp=N mixed step stays
        one-model-call, and its streams stay bit-identical to the
        single-chip engine's)."""
        plan = self._ragged_plan
        segs = [s for s in plan if s["to"] > s["from"]]
        del plan[:]
        if not segs and x is None:
            return None
        desc: List[tuple] = [
            ("prefill", s["slot"], s["from"], s["to"] - s["from"],
             s["ws"]) for s in segs]
        if x is not None:
            desc.append(("decode", self.lens.copy(), L))
        col = self.collector
        if col is not None:
            # the step's block tables and tile layout, built on the
            # host and uploaded: paged-cache manager time too
            col.span_begin("grow", what="layout")
        try:
            views = self.cache.ragged_views(desc)
            if col is not None:
                # what each layer's launch will cost the kernel's grid:
                # the plan's bound, and the steps it walks (live ones)
                layout = views[0]._layout
                plan = layout.launch_plan()
                series = {"grid_steps": plan.grid_steps,
                          "live_steps": layout.live_steps(plan),
                          "pages_per_step": plan.pages,
                          "heads_per_step": plan.heads}
                windows = [w for w in self.cache.layer_windows if w]
                if windows:
                    # what a sliding layer's launch may skip, from the
                    # layout's lengths (one figure a step: the
                    # sliding layers share a window)
                    (series["pages_in_context"],
                     series["pages_behind_window"]) = \
                        views[0]._layout.window_pages(min(windows))
                if self.cache.v_dim is not None:
                    # a latent pool: the rows' contexts as the bytes of
                    # cache their launches read, over all layers
                    series["latent_bytes_in_context"] = \
                        int(views[0]._layout.kv_lens_np.sum()) \
                        * self.cache.kv_bytes_per_token()
                col.gauge("paged_attn", series)
        finally:
            if col is not None:
                col.span_end()
        import jax.numpy as jnp
        parts = [jnp.asarray(np.ascontiguousarray(
            s["req"].history[s["from"]:s["to"]], np.float32))
            for s in segs]
        if x is not None:
            parts.append(x.data.reshape(self.max_batch * L,
                                        x.shape[-1]))
        xp = Tensor(jnp.concatenate(parts, axis=0)[None])
        with no_grad():
            out = model_call(self.model, xp, views,
                             Tensor(np.int32(0)), col)
        hv = out.data
        lo = 0
        for s in segs:
            n = s["to"] - s["from"]
            if s["hook"] is not None:
                for b in s["bounds"]:
                    s["hook"](b)
            if s["complete"]:
                self._finish_planned_prefill(
                    s["slot"], Tensor(hv[0, lo + n - 1:lo + n]))
            lo += n
        if x is not None:
            return Tensor(hv[0, lo:lo + self.max_batch * L].reshape(
                (self.max_batch, L) + tuple(hv.shape[2:])))
        return None

    def _finish_planned_prefill(self, slot: int, last_hidden) -> None:
        """Post-launch half of prefill completion for the ragged step
        (the state transition already ran at plan time): the pages now
        exist, so register the prefix blocks and fire the admitted
        event — the same sequence ``_complete_prefill`` runs eagerly."""
        st = self._prefills.pop(slot)
        req = self._requests[slot]
        T = len(req)
        if self.prefix_cache:
            self.cache.register_prefix(slot, st["hashes"])
            self.prefix_stats.tokens_computed += T - st["start"]
            self.prefix_stats.tokens_skipped += st["start"]
        self.admitted.append((req.rid, slot, last_hidden))
        if self.collector is not None:
            self.collector.on_first_token(req.rid)
        self._fork_group(slot, last_hidden)
        self._crash("post_prefill")

    def _fork_group(self, slot: int, last_hidden) -> None:
        """COW-fork the branch slots of a freshly prefilled group
        lead: every branch gets a fresh rid, a history COPY (branches
        diverge from the shared prompt on their first decode token), a
        block table REFERENCING the lead's prompt pages
        (``PagedKVCache.fork`` — charged per reference) and its own
        admitted event carrying the SHARED prefill hidden, so the
        caller samples each branch's first token from one prefill.
        The ledger's ``on_fork`` raises the branch's high-water mark
        to the fork length WITHOUT pending rows — the shared prefill
        is priced exactly once, under the lead. Runs BEFORE the
        ``post_prefill`` crash point: a crash there replays with the
        fork already journaled in the step's effects, the mid-group
        recovery case the tests pin."""
        req = self._requests[slot]
        if req is None or req.group_n <= 1:
            return
        g = self.groups.groups.get(req.gid)
        if g is None or g["forked"]:
            return
        n = req.group_n
        T = len(req)
        reserved = list(g["reserved"])
        del g["reserved"][:]
        for i in range(1, n):
            if reserved:
                bslot = reserved.pop(0)
                self.prefilling[bslot] = False
            else:
                bslot = int(np.flatnonzero(~self.active
                                           & ~self.prefilling)[0])
            breq = PagedRequest(self._next_rid, req.history,
                                keys=req.keys_held())
            self._next_rid += 1
            breq.tenant = req.tenant
            breq.gid = req.gid
            breq.branch = i
            breq.max_preemptions = req.max_preemptions
            breq.deadline_steps = req.deadline_steps
            breq.deadline_time = req.deadline_time
            breq.submit_step = req.submit_step
            self.groups.add_branch(req.gid, breq.rid)
            if self.collector is not None:
                self.collector.on_submit(breq.rid, breq.tenant, T)
            if self.ledger is not None:
                self.ledger.on_submit(breq.rid, breq.tenant, T)
                self.ledger.on_fork(breq.rid, T)
            # attribute BEFORE the fork so every shared-page reference
            # charges the branch's tenant from the first reference
            self.cache.set_seq_tenant(bslot, breq.tenant)
            self.cache.fork(slot, bslot, T)
            self._requests[bslot] = breq
            breq.slot = bslot
            breq.admit_seq = self._next_admit_seq
            self._next_admit_seq += 1
            self.lens[bslot] = T
            self.active[bslot] = True
            self._tenant_of(breq).stats.admitted += 1
            if self.collector is not None:
                self.collector.on_admitted(breq.rid, bslot,
                                           retry=False)
            self.admitted.append((breq.rid, bslot, last_hidden))
            if self.collector is not None:
                self.collector.on_first_token(breq.rid)
            self.parallel_stats.branches += 1
            self.parallel_stats.prefill_tokens_saved += T
            self.parallel_stats.shared_blocks += \
                self.cache.blocks_needed(T)
        g["forked"] = True
        # the lead is a normal slot from here: a later preemption
        # re-prefills it alone instead of re-forking
        req.group_n = 1

    def fork_stream(self, rid: int) -> int:
        """Beam/tree primitive: clone a RUNNING stream mid-decode into
        a free slot — history copied, pages COW-shared at the current
        length (the clone's next written block splits), fresh rid
        returned. The source's group grows by the clone (a group is
        created on demand for a previously lone stream), so the group
        audit and outcome aggregation cover beam trees too. Raises
        ValueError when the rid is not active or no slot is free —
        beam scheduling is the caller's policy; the engine only
        provides the fork."""
        slot = None
        for s, r in enumerate(self._requests):
            if r is not None and r.rid == rid:
                slot = s
                break
        if slot is None or not self.active[slot]:
            raise ValueError(f"rid {rid} is not an active stream")
        free = np.flatnonzero(~self.active & ~self.prefilling)
        reserved = self.groups.reserved_slots()
        free = [int(s) for s in free if int(s) not in reserved]
        if not free:
            raise ValueError("no free slot to fork into")
        # buffered decode inputs must reach the history before it is
        # copied, or the clone would re-prefill a truncated stream
        self._flush_history()
        req = self._requests[slot]
        bslot = free[0]
        L = int(self.lens[slot])
        if req.gid is None:
            req.gid = req.rid
            g = self.groups.create(req.rid, 1)
            g["forked"] = True
            self.parallel_stats.groups += 1
        g = self.groups.groups[req.gid]
        breq = PagedRequest(self._next_rid, req.history,
                            keys=req.keys_held())
        self._next_rid += 1
        breq.tenant = req.tenant
        breq.gid = req.gid
        breq.branch = len(g["rids"])
        breq.max_preemptions = req.max_preemptions
        breq.deadline_steps = req.deadline_steps
        breq.deadline_time = req.deadline_time
        breq.submit_step = req.submit_step
        g["n"] += 1
        self.groups.add_branch(req.gid, breq.rid)
        if self.collector is not None:
            self.collector.on_submit(breq.rid, breq.tenant, L)
        if self.ledger is not None:
            self.ledger.on_submit(breq.rid, breq.tenant, L)
            self.ledger.on_fork(breq.rid, L)
        self.cache.set_seq_tenant(bslot, breq.tenant)
        self.cache.fork(slot, bslot, L)
        self._requests[bslot] = breq
        breq.slot = bslot
        breq.admit_seq = self._next_admit_seq
        self._next_admit_seq += 1
        self.lens[bslot] = L
        self.active[bslot] = True
        self._tenant_of(breq).stats.admitted += 1
        if self.collector is not None:
            self.collector.on_admitted(breq.rid, bslot, retry=False)
        self.parallel_stats.branches += 1
        self.parallel_stats.prefill_tokens_saved += L
        self.parallel_stats.shared_blocks += self.cache.blocks_needed(L)
        return breq.rid

    def cancel(self, rid: int) -> bool:
        """Deliberate early stop of one stream (best-of-n loser
        pruning, beam cuts, caller cancel): pages freed through the
        normal drop path (cached-free second chance intact — the
        content is healthy), terminal CANCELLED outcome, pending
        ledger work resolved as ``bestof_pruned`` waste. Works on
        running, mid-prefill and queued (preempted) members alike.
        Returns False for an unknown/already-terminal rid."""
        req = self.request_of(rid)
        if req is None:
            return False
        self._fail(req, RequestOutcome.CANCELLED,
                   "cancelled (early stop)")
        self._try_admit()
        return True

    # -- release / preemption / failure -------------------------------
    def release(self, slot: int) -> None:
        """Caller-side finish (e.g. EOS): free the pages, refill. The
        request's terminal RequestOutcome (FINISHED) lands in
        ``outcomes``."""
        req = self._requests[slot]
        self._drop(slot)
        if req is not None:
            self._record(req, RequestOutcome.FINISHED, "released")
        self._try_admit()

    def _record(self, req: PagedRequest, status: str,
                reason: str) -> None:
        self.outcomes.append(RequestOutcome(
            req.rid, status, reason=reason, tokens=len(req),
            preemptions=req.preemptions, step=self._step_count))
        st = self.resilience_stats
        ts = self._tenant_of(req).stats
        if status == RequestOutcome.FAILED_OOM:
            st.shed += 1
            ts.sheds += 1
        elif status == RequestOutcome.FAILED_NUMERIC:
            st.nan_failed += 1
            ts.nan_failed += 1
        elif status == RequestOutcome.FAILED_DEADLINE:
            st.deadline_failed += 1
            ts.deadline_failed += 1
        elif status == RequestOutcome.REJECTED_ADMISSION:
            st.rejected += 1
            ts.rejections += 1
        elif status == RequestOutcome.CANCELLED:
            st.cancelled += 1
            ts.cancelled += 1
        # group outcome aggregation: a member's terminal verdict
        # retires it from its group's live set (the record drops when
        # the last member lands)
        self.groups.on_terminal(req.rid)
        col = self.collector
        if self.ledger is not None:
            # the terminal verdict resolves the request's pending work
            # (goodput on FINISHED, retroactive waste on failure)
            self.ledger.on_outcome(req.rid, status)
        if col is not None:
            col.on_outcome(req.rid, status, self._step_count,
                           reason=reason)
            if status == RequestOutcome.FAILED_OOM:
                # the structured BlockOOM breakdown as an event: every
                # shed carries WHO held the pool when it fired
                col.on_event("oom_shed", dict(
                    self.cache.pool_occupancy(), rid=req.rid,
                    tenant=req.tenant, step=self._step_count))

    def _fail(self, req: PagedRequest, status: str,
              reason: str) -> None:
        """Terminal failure of ONE request: free its pages (numeric
        failures quarantine them — no cached-free second chance, the
        content is suspect), detach it from slot/queue, record the
        outcome. The engine, and every other request, keeps going."""
        if req.slot is not None:
            self._drop(req.slot,
                       quarantine=status == RequestOutcome.FAILED_NUMERIC)
            req.slot = None
        else:
            try:
                self._dequeue(req)
            except ValueError:
                pass
        self._record(req, status, reason)

    def _over_retry_budget(self, req: PagedRequest) -> bool:
        return req.max_preemptions is not None and \
            req.preemptions >= req.max_preemptions

    def _requeue_preempted(self, req: PagedRequest) -> None:
        """Readmission fairness: preempted requests re-enter the queue
        AHEAD of never-admitted ones (they carry sunk prefill/decode
        compute), ordered among themselves by original submission age
        — NOT plain appendleft, which reverses the order of two
        requests preempted in different engine passes (a re-admitted
        old request holds a fresh admit_seq, so it is evicted first
        and appendleft would then queue it BEHIND its younger peer).
        The insert is into the request's TENANT sub-queue, whose
        internal order follows the same global _queue_key contract."""
        self._bump_vtime(req.tenant)
        ten = self.tenants[req.tenant]
        key = self._queue_key(req)
        i = 0
        for r in ten.fifo:
            if self._queue_key(r) < key:
                i += 1
            else:
                break
        ten.fifo.insert(i, req)
        ten.queued += 1
        self._queue_len += 1

    def _check_deadlines(self) -> None:
        """Fail every request (active, mid-prefill or queued) whose
        per-request deadline has passed. Zero overhead unless some
        submit() actually set a deadline."""
        if not self._has_deadlines:
            return
        now = None
        held = [self._requests[int(s)] for s in
                np.flatnonzero(self.active | self.prefilling)]
        # scan the sub-queues directly: expiry does not care about the
        # merged order, so don't pay the queue property's sort here
        queued = [r for t in self.tenants.values() for r in t.fifo]
        for req in held + queued:
            if req is None:
                continue
            expired = ""
            if req.deadline_steps is not None and \
                    self._step_count - req.submit_step > \
                    req.deadline_steps:
                expired = (f"deadline of {req.deadline_steps} steps "
                           f"exceeded")
            elif req.deadline_time is not None:
                now = time.monotonic() if now is None else now
                if now >= req.deadline_time:
                    expired = "wall-clock deadline exceeded"
            if expired:
                self._fail(req, RequestOutcome.FAILED_DEADLINE, expired)

    def _flush_history(self) -> None:
        """Attribute buffered decode inputs to their requests'
        histories. Must run before any slot->request mapping change
        (drop/preempt), which is the only time histories are read."""
        if not self._pending_history:
            return
        pending, self._pending_history = self._pending_history, []
        for xt, mask in pending:
            xv = np.asarray(xt.numpy(), np.float32)
            for slot in np.flatnonzero(mask):
                req = self._requests[int(slot)]
                if req is not None:
                    # all L rows of a multi-token (speculative) step;
                    # rejected rows are trimmed back by rollback()
                    for row in xv[int(slot)]:
                        req.append_history(row)

    def _drop(self, slot: int, quarantine: bool = False) -> None:
        plan = self._ragged_plan
        if plan and any(s["slot"] == slot for s in plan):
            # ragged step: the eager path had already RUN this slot's
            # chunks before any later preemption could fire — flush
            # the pending segments so its pages are written (and its
            # completed blocks registered) before they are freed
            self._flush_ragged_plan()
        self._flush_history()
        req = self._requests[slot]
        if req is not None and req.group_n > 1 and \
                req.gid is not None:
            # an UNFORKED group lead leaving its slot (preemption /
            # failure / cancel) releases the branch-slot reservation —
            # a re-admission reserves afresh
            g = self.groups.groups.get(req.gid)
            if g is not None and g["reserved"]:
                for rs in g["reserved"]:
                    self.prefilling[rs] = False
                del g["reserved"][:]
        if quarantine:
            self.cache.quarantine_seq(slot)
        else:
            self.cache.free_seq(slot)
        self.active[slot] = False
        self.prefilling[slot] = False
        self._prefills.pop(slot, None)
        self.lens[slot] = 0
        self._requests[slot] = None

    def preempt(self, slot: int) -> None:
        """Evict a running (or mid-prefill) request: free ALL its
        pages and requeue it ahead of never-admitted requests for
        re-prefill from its history (a mid-prefill victim restarts its
        prompt stream on re-admission). A request past its
        ``max_preemptions`` retry budget FAILS (FAILED_OOM outcome)
        instead of requeueing — bounded retry, no re-prefill
        livelock."""
        req = self._requests[slot]
        if req is None:
            raise ValueError(f"slot {slot} not active")
        if self._over_retry_budget(req):
            self._fail(req, RequestOutcome.FAILED_OOM,
                       f"preemption retry budget "
                       f"({req.max_preemptions}) exhausted")
            return
        self._drop(slot)
        req.slot = None
        req.preemptions += 1
        self._tenant_of(req).stats.preemptions += 1
        self._requeue_preempted(req)
        self.preempted.append(req.rid)
        if self.collector is not None:
            self.collector.on_preempted(req.rid)

    def _oom_victims(self, req: PagedRequest) -> List[int]:
        """Eligible eviction victims for a POOL OOM hit while growing
        ``req``: the grower's OWN tenant's slots — pool pressure a
        tenant creates is resolved inside that tenant, never by
        evicting a within-quota neighbor. The one exception is the
        reserved-floor guarantee: a grower still BELOW its floor is
        entitled to the block, so the victims are the slots of tenants
        borrowing ABOVE their own floors (falling back to the grower's
        own if no one is over). With a single (default) tenant both
        branches degenerate to every held slot — the pre-tenant
        youngest-first policy, bit-identical."""
        held = [int(s) for s in
                np.flatnonzero(self.active | self.prefilling)
                if self._requests[int(s)] is not None]
        ten = self._tenant_of(req)
        if ten.reserved_blocks and \
                self.cache.tenant_charge(ten.tid) < ten.reserved_blocks:
            over = [s for s in held
                    if self._over_floor(self._requests[s].tenant)]
            if over:
                return over
        return [s for s in held
                if self._requests[s].tenant == ten.tid]

    def _over_floor(self, tid: str) -> bool:
        t = self.tenants[tid]
        return self.cache.tenant_charge(tid) > t.reserved_blocks

    def _preempt_youngest(self, cands: Optional[List[int]] = None) -> int:
        if cands is None:
            cands = [int(s) for s in
                     np.flatnonzero(self.active | self.prefilling)
                     if self._requests[int(s)] is not None]
        victim = max(cands, key=lambda s: self._requests[s].admit_seq)
        self.preempt(victim)
        return victim

    # -- decode -------------------------------------------------------
    def step(self, x: Tensor):
        """One fused decode step for every active slot. x: [max_batch,
        1, d_model] next-token embeddings (inactive rows: any values —
        they scatter into the trash block). Slots at page capacity are
        auto-released first (reported in ``finished``) so one full
        sequence never stalls the batch; rows crossing a block boundary
        allocate their next page, preempting the youngest request if
        the pool is dry. With ``prefill_token_budget`` set, the step
        FIRST spends the budget advancing pending prefill chunks
        (Sarathi-style mixed step) — and may legally run with zero
        active slots while prompts are still streaming (returns
        None). Returns hidden [max_batch, 1, d_model] (only rows
        active during this step are meaningful), or None if every
        slot finished before the step could run.

        FAILURE ISOLATION: a request that cannot be served — pool dry
        even after preempting every other request, retry budget or
        deadline blown, non-finite hidden in its row — is failed
        individually (RequestOutcome in ``outcomes``, pages freed) and
        the step completes for everyone else; no BlockOOM or fault
        ever escapes this call. Rows of failed/preempted slots in the
        returned hidden are garbage — drain the event lists."""
        idle = self._begin_step()
        ok = False
        try:
            out = self._step_impl(idle, x)
            ok = True
            return out
        finally:
            # balanced even when an injected EngineCrash unwinds the
            # step; a no-op (no clock read) without a collector. The
            # monitor only samples COMPLETED steps (aborted flag): a
            # torn step's mid-crash state is not a step-boundary
            # sample — it either replays after recovery (sampled
            # then) or the engine is abandoned
            self._end_step_telemetry(aborted=not ok)

    def _ragged_active(self) -> bool:
        """Pack this step? — ragged_step on, token-budget mode, and
        the kernel path live (or packing forced; see __init__)."""
        if not self.ragged_step or self.prefill_token_budget is None:
            return False
        if self.ragged_step == "force":
            return True
        # a compiled sharded core amortizes best when the whole mixed
        # batch rides its ONE jitted packed program — take the ragged
        # plan whenever it's legal, kernel or not
        if getattr(self.model, "prefers_packed_step", False):
            return True
        return _device.use_pallas_kernels()

    def _step_impl(self, idle: bool, x: Tensor):
        if not self._ragged_active():
            return self._step_body(idle, x)
        # ragged mixed step: collect the step's prefill chunks into
        # self._ragged_plan and launch them packed with the decode
        # (_flush_ragged_plan) — cleared even when a crash unwinds
        self._ragged_plan = []
        try:
            return self._step_body(idle, x)
        finally:
            self._ragged_plan = None

    def _step_body(self, idle: bool, x: Tensor):
        plan = self._ragged_plan
        col = self.collector
        if col is not None:
            col.phase("prefill")
        if plan is None:
            ran_prefill, fresh = self._advance_prefills()
        else:
            ran_prefill, fresh = self._plan_prefills()
        if col is not None:
            col.phase("bookkeeping")
        if self.num_active == 0:
            if ran_prefill or self.num_prefilling > 0 \
                    or self._queue_len or not idle:
                if plan:
                    self._flush_ragged_plan()
                self._try_admit()
                return None
            raise RuntimeError("step() with no active slots")
        # 1. capacity-finished slots: report + release, keep the rest
        for slot in np.flatnonzero(self.active & (self.lens >=
                                                  self.max_len)):
            req = self._requests[int(slot)]
            self.finished.append((req.rid, int(slot),
                                  int(self.lens[slot])))
            self._drop(int(slot))
            self._record(req, RequestOutcome.FINISHED,
                         "page capacity reached")
        # slots whose prefill completed within THIS step sit the
        # decode out: the caller has not drained their admitted event
        # yet, so their row of x is garbage — they stay masked and
        # their length does not advance
        stepping = self.active.copy()
        for slot in fresh:
            stepping[slot] = False
        if not stepping.any():
            if plan:
                self._flush_ragged_plan()
            self._try_admit()
            return None
        # 2. grow pages (allocate-on-write), preempting on OOM.
        #    Oldest first: under pressure the young yield to the old.
        order = sorted(np.flatnonzero(stepping),
                       key=lambda s: self._requests[s].admit_seq)
        if col is not None:
            col.span_begin("grow")
        try:
            for slot in order:
                slot = int(slot)
                self._grow_or_shed(slot, self._requests[slot],
                                   int(self.lens[slot]) + 1)
        finally:
            if col is not None:
                col.span_end()
        stepping &= self.active     # growth may have evicted some
        if not stepping.any():
            if plan:
                self._flush_ragged_plan()
            self._try_admit()
            return None
        # 3. record the inputs being consumed (re-prefill history) —
        #    a Tensor ref + mask snapshot only; the device->host read
        #    is deferred to _flush_history (next drop/preempt, or the
        #    periodic bound below so long-lived batches don't pin an
        #    unbounded window of input buffers)
        if len(self._pending_history) >= 32:
            self._flush_history()
        # 3.5 sanitize: non-stepping rows may carry ANY caller values —
        #     including the NaN row of a previously failed slot fed
        #     back verbatim. They scatter k/v into the SHARED trash
        #     block, and a NaN there would poison every sequence's
        #     masked attention tail (an additive -1e30 mask cannot
        #     cancel NaN), so they are zeroed on-device first —
        #     unconditionally, to keep the "inactive rows: any
        #     values" contract sound (bitwise no-op for stepping rows)
        x = self._sanitize_masked_rows(x, stepping)
        self._pending_history.append((x, stepping.copy()))
        # 4. fused ragged step over the paged views; mid-prefill and
        #    freshly admitted slots present all-trash tables so the
        #    decode append cannot touch their pages
        masked = self.prefilling | (self.active & ~stepping)
        self.cache.set_decode_mask(masked if masked.any() else None)
        if col is not None:
            col.phase("model")
        if plan:
            # the step's planned prefill chunks and the fused decode
            # rows in ONE packed model call — one paged-attention
            # launch per layer on the kernel path
            out = self._flush_ragged_plan(x=x)
        else:
            # a private COPY: jax may alias a host buffer it is handed
            # (zero-copy on CPU, an in-flight transfer elsewhere), and
            # self.lens advances in place below while the layers of
            # this call are still executing asynchronously
            t = Tensor(np.array(self.lens, np.int32))
            with no_grad():
                out = model_call(self.model, x, self.cache.views, t, col)
        if self.injector is not None:
            out = self.injector.corrupt_hidden(out)
        if col is not None:
            col.phase("bookkeeping")
        self.lens[stepping] += 1
        self._count_tokens_served(stepping, 1)
        if col is not None:
            col.on_decode([self._requests[int(s)].rid
                           for s in np.flatnonzero(stepping)
                           if self._requests[int(s)] is not None], 1)
        if self.ledger is not None:
            # the consumed row's absolute position (pre-increment len)
            self.ledger.on_decode(
                [(self._requests[int(s)].rid, int(self.lens[s]) - 1)
                 for s in np.flatnonzero(stepping)
                 if self._requests[int(s)] is not None], 1)
        self.prefill_stats.decode_steps += 1
        if ran_prefill:
            self.prefill_stats.mixed_steps += 1
        # decode-phase allocate-on-write growth moves the high-water
        # mark too, not just prefill chunks
        self.prefill_stats.peak_blocks = max(
            self.prefill_stats.peak_blocks, self.cache.peak_blocks_used)
        if self.numeric_guard:
            self._guard_numeric(out, stepping)
        # 5. continuous refill
        if col is not None:
            col.phase("admission")
        self._try_admit()
        return out

    # -- speculative decode (multi-token verify + rollback) -----------
    def step_multi(self, x: Tensor):
        """One fused MULTI-TOKEN step for every active slot: row b's L
        tokens are appended at positions lens[b] .. lens[b]+L-1 and
        scored causally in ONE model call — the speculative-decode
        verification step (inference/speculative.py). x: [max_batch,
        L, d_model]. The caller guarantees lens + L <= capacity for
        every active slot (clamp L; slots AT capacity must be released
        first) — unlike ``step`` there is no auto-release here, since
        a capacity-finished slot cannot ride a multi-token call at
        all. Page growth covers all L positions (preempting youngest
        on OOM, as in ``step``); ``rollback`` drops the rejected tail.
        Returns hidden [max_batch, L, d_model].

        COMPOSES with ``prefill_token_budget`` (the PR 10 residual):
        the step first spends the budget advancing pending prefill
        chunks — packed WITH the verify rows into one ragged launch on
        the kernel path (the ragged kernel and ``ragged_views`` speak
        mixed q_lens natively) — and slots mid-prefill, or whose
        prefill completed within this very step, sit the verify out
        exactly as they sit out ``step``'s decode: their rows of x are
        sanitized, their tables present as trash, their lens do not
        advance, and their admitted event fires for the NEXT round's
        pending token. May return None while prompts are still
        streaming with no verifiable slot."""
        L = int(x.shape[1])
        idle = self._begin_step(kind="verify")
        ok = False
        try:
            if not self._ragged_active():
                out = self._step_multi_impl(idle, x, L)
            else:
                self._ragged_plan = []
                try:
                    out = self._step_multi_impl(idle, x, L)
                finally:
                    self._ragged_plan = None
            ok = True
            return out
        finally:
            self._end_step_telemetry(aborted=not ok)

    def _step_multi_impl(self, idle: bool, x: Tensor, L: int):
        col = self.collector
        plan = self._ragged_plan
        # token-budget mode: spend the prefill budget first (eagerly,
        # or into the ragged plan), exactly like _step_body
        if col is not None:
            col.phase("prefill")
        if plan is None:
            ran_prefill, fresh = self._advance_prefills()
        else:
            ran_prefill, fresh = self._plan_prefills()
        if col is not None:
            col.phase("bookkeeping")
        if self.num_active == 0:
            if ran_prefill or self.num_prefilling > 0 \
                    or self._queue_len or not idle:
                # deadline failures can empty the batch mid-stream;
                # the caller sees None + the outcome events, never an
                # exception
                if plan:
                    self._flush_ragged_plan()
                self._try_admit()
                return None
            raise RuntimeError("step_multi() with no active slots")
        # slots whose prefill completed within THIS step sit the
        # verify out (their admitted event is undrained — their rows
        # of x are garbage), same contract as _step_body
        stepping = self.active.copy()
        for slot in fresh:
            stepping[slot] = False
        if not stepping.any():
            if plan:
                self._flush_ragged_plan()
            self._try_admit()
            return None
        over = stepping & (self.lens + L > self.max_len)
        if over.any():
            if plan:
                # the planning pass already transitioned prefill state
                # (positions, stats, completions): flush the recorded
                # chunks so their pages exist before unwinding, or the
                # caller's retry would decode against prompts the
                # scheduler believes were written
                self._flush_ragged_plan()
            raise ValueError(
                f"slots {np.flatnonzero(over).tolist()} cannot take "
                f"{L} tokens within capacity {self.max_len}; clamp L "
                f"or release them first")
        # grow pages to cover the whole write range, oldest first
        order = sorted(np.flatnonzero(stepping),
                       key=lambda s: self._requests[s].admit_seq)
        if col is not None:
            col.span_begin("grow")
        try:
            for slot in order:
                slot = int(slot)
                self._grow_or_shed(slot, self._requests[slot],
                                   int(self.lens[slot]) + L,
                                   write_from=int(self.lens[slot]))
        finally:
            if col is not None:
                col.span_end()
        stepping &= self.active     # growth may have evicted some
        if not stepping.any():
            if plan:
                self._flush_ragged_plan()
            self._try_admit()
            return None
        if len(self._pending_history) >= 32:
            self._flush_history()
        # see step(): a NaN fed for an inactive row must not reach the
        # shared trash block (zeroed unconditionally, bitwise no-op
        # for active rows)
        x = self._sanitize_masked_rows(x, stepping)
        self._pending_history.append((x, stepping.copy()))
        masked = self.prefilling | (self.active & ~stepping)
        self.cache.set_decode_mask(masked if masked.any() else None)
        if col is not None:
            col.phase("model")
        if plan:
            # the step's planned prefill chunks and the L-row verify
            # packed into ONE ragged model call
            out = self._flush_ragged_plan(x=x, L=L)
        else:
            # a private COPY: jax may alias a host buffer it is handed
            # (zero-copy on CPU, an in-flight transfer elsewhere), and
            # self.lens advances in place below while the layers of
            # this call are still executing asynchronously
            t = Tensor(np.array(self.lens, np.int32))
            with no_grad():
                out = model_call(self.model, x, self.cache.views, t, col)
        if self.injector is not None:
            out = self.injector.corrupt_hidden(out)
        if col is not None:
            col.phase("bookkeeping")
        self.lens[stepping] += L
        self._count_tokens_served(stepping, L)
        if col is not None:
            col.on_decode([self._requests[int(s)].rid
                           for s in np.flatnonzero(stepping)
                           if self._requests[int(s)] is not None], L)
        if self.ledger is not None:
            # L verified rows per slot at positions [len-L, len)
            self.ledger.on_decode(
                [(self._requests[int(s)].rid, int(self.lens[s]) - L)
                 for s in np.flatnonzero(stepping)
                 if self._requests[int(s)] is not None], L)
        self.prefill_stats.decode_steps += 1
        if ran_prefill:
            self.prefill_stats.mixed_steps += 1
        self.prefill_stats.peak_blocks = max(
            self.prefill_stats.peak_blocks, self.cache.peak_blocks_used)
        if self.numeric_guard:
            self._guard_numeric(out, stepping)
        if col is not None:
            col.phase("admission")
        self._try_admit()
        return out

    def rollback(self, slot: int, new_len: int) -> None:
        """Roll an active slot back to ``new_len`` consumed tokens
        (speculative rejection): the pages past the boundary are
        released block-table-tail-first (refcount/cached-free aware —
        PagedKVCache.truncate), the recorded history is trimmed so a
        later preempt -> re-prefill replays only ACCEPTED tokens, and
        the slot keeps decoding from ``new_len``."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} not active")
        new_len = int(new_len)
        if new_len < 1 or new_len > int(self.lens[slot]):
            raise ValueError(
                f"rollback of slot {slot} to {new_len} outside "
                f"[1, {int(self.lens[slot])}]")
        rejected = int(self.lens[slot]) - new_len
        if rejected > 0:
            # rows past new_len advanced the state store's rows too
            self.cache._refuse_with_state("rollback of accepted rows")
        # buffered inputs must reach the history BEFORE trimming it
        self._flush_history()
        self._requests[slot].truncate_history(new_len,
                                              self.cache.block_size)
        self.cache.truncate(slot, new_len)
        old_len = new_len + rejected
        self.lens[slot] = new_len
        if self.collector is not None and rejected > 0:
            self.collector.on_rollback(self._requests[slot].rid,
                                       rejected)
        if self.ledger is not None and rejected > 0:
            self.ledger.on_rollback(self._requests[slot].rid,
                                    new_len, old_len)

    # -- resilience ---------------------------------------------------
    def _crash(self, phase: str) -> None:
        """Consult the injector's crash schedule (CrashInjector): a
        scheduled hit raises EngineCrash OUT of the engine, simulating
        process death mid-step — recovery rebuilds from snapshot +
        journal (inference/recovery.py). No-op for a plain
        FaultInjector and zero overhead with no injector at all."""
        if self.injector is not None:
            self.injector.crash_point(phase)

    def _begin_step(self, kind: str = "step") -> bool:
        """Step-top bookkeeping shared by step()/step_multi():
        advance the step counter (the fault injector's clock) and
        enforce per-request deadlines. Returns whether the engine was
        ALREADY empty on entry — that is caller misuse and still
        raises, while an engine emptied by this step's own failures
        returns None to the caller. Opens the telemetry step span
        LAST (after the ``begin`` crash point), so a step that dies
        at its top never leaves a dangling span."""
        self._step_count += 1
        if self.injector is not None:
            self.injector.begin_step(self._step_count)
            self.injector.crash_point("begin")
        idle = self.num_active == 0 and self.num_prefilling == 0 \
            and not self._queue_len
        self._check_deadlines()
        for tid, ten in self.tenants.items():
            ten.stats.blocks_held = self.cache.tenant_charge(tid)
        if self.collector is not None:
            self.collector.begin_step(self._step_count, kind)
        return idle

    def _queue_gauges(self) -> dict:
        """Queue/slot depths — the ONE source feeding both the
        registry's ``queue`` namespace and the per-step gauge track."""
        return {"depth": self._queue_len,
                "active": self.num_active,
                "prefilling": self.num_prefilling}

    def _end_step_telemetry(self, aborted: bool = False) -> None:
        """Close the step span and sample the per-step gauges from
        ground truth (pool tiers, queue/slot depths, per-tenant
        charge), then hand the step to the health monitor. One call,
        in the step's ``finally`` — the timeline stays balanced even
        when a fault or injected crash unwinds the step early
        (``aborted``); the MONITOR skips aborted steps (a torn step
        is not a step-boundary state — it replays after recovery or
        the engine is abandoned, so sampling it would diverge the
        series from an uninterrupted run's)."""
        col = self.collector
        # taken every step, read or not: a sample then holds what was
        # written since the last step ended, whenever a collector (a
        # profile session's) is installed
        writes = self.cache.take_write_stats()
        charges = None
        if not aborted and (col is not None or
                            self.ledger is not None):
            # ONE per-tenant charge walk shared by the collector's
            # gauge track and the ledger's block-step bill. Unlike
            # the occupancy blocks-per-tenant histogram (which drops
            # zeros), this reports every REGISTERED tenant — a
            # charge falling to 0 must emit a 0, not vanish
            charges = {tid: self.cache.tenant_charge(tid)
                       for tid in self.tenants}
        if col is not None:
            if aborted:
                # close the torn step's span flagged; no gauges — the
                # mid-crash state is not a step-boundary sample
                col.end_step(aborted=True)
            else:
                # the ONE tier source, O(1) scalars only — per-step
                # gauges must not pay the occupancy histograms'
                # O(max_seqs) scan
                occ = self.cache.pool_occupancy(tiers_only=True)
                col.end_step({
                    "pool": {"active": occ["active"],
                             "cached_free": occ["cached_free"],
                             "free": occ["free"]},
                    # what the K/V appends (and COW splits) moved:
                    # pages of a donated pool, not the pool
                    "pool_write": writes,
                    "queue": self._queue_gauges(),
                    "tenant_blocks": charges,
                })
        if self.ledger is not None:
            if aborted:
                # a torn step is not a billing boundary: drop its
                # partial work-log sample (event tallies stand)
                self.ledger.on_step_abort()
            else:
                # block-step billing integrates the per-tenant charge
                # at every completed step boundary; the collector's
                # registry rides along so the ledger can pair the
                # step's analytic work with its measured model-span
                # duration (MFU/MBU)
                self.ledger.on_step(
                    self._step_count, charges,
                    span_src=(col.registry if col is not None
                              else None))
        if self.monitor is not None and not aborted:
            self.monitor.on_step(self._step_count)

    def _count_tokens_served(self, stepping: np.ndarray,
                             n: int) -> None:
        """Attribute this fused call's consumed decode tokens to the
        stepping slots' tenants (the per-tenant throughput signal)."""
        for slot in np.flatnonzero(stepping):
            req = self._requests[int(slot)]
            if req is not None:
                self._tenant_of(req).stats.tokens_served += n

    def _grow_or_shed(self, slot: int, req: PagedRequest, length: int,
                      *, start_block: int = 0,
                      write_from: Optional[int] = None) -> bool:
        """Cover ``length`` tokens for ``slot`` (allocate-on-write +
        COW split), preempting the youngest ELIGIBLE request on
        pressure — possibly the grower itself (it then re-queues for
        re-prefill). The ONE eviction/shed policy behind decode
        growth, multi-token growth and chunked-prefill growth;
        returns True when the slot is still alive (and covered).

        Tenant-aware pressure handling, checked in order:

          1. TENANT QUOTA: growth past the tenant's block cap evicts
             the tenant's OWN youngest; with nothing of its own left
             to evict the grower is SHED (FAILED_OOM naming the
             quota) — a neighbor never pays for a flooder's cap.
          2. RESERVED FLOORS: a tenant at-or-over its own floor may
             not dip the free pool below other tenants' unmet floors;
             it evicts within itself, or (sole member) self-evicts
             and waits queued — floor pressure is transient (it
             clears when the entitled tenant charges up), so the
             grower is preempted, not shed.
          3. POOL OOM: victims come from ``_oom_victims`` (the
             grower's own tenant; over-floor borrowers when the
             grower is below its floor). Pool dry with no eligible
             victim but the grower itself -> SHED, as before.
        """
        if not (self.active[slot] or self.prefilling[slot]):
            return False    # already evicted growing an earlier slot
        ten = self._tenant_of(req)
        while self.active[slot] or self.prefilling[slot]:
            need_new = self.cache.blocks_needed(length) \
                - len(self.cache.seq_blocks[slot])
            if need_new > 0 and ten.quota_blocks is not None and \
                    self.cache.tenant_charge(ten.tid) + need_new \
                    > ten.quota_blocks:
                ten.stats.quota_hits += 1
                own = [int(s) for s in
                       np.flatnonzero(self.active | self.prefilling)
                       if self._requests[int(s)] is not None
                       and self._requests[int(s)].tenant == ten.tid]
                if len(own) <= 1:
                    self._fail(req, RequestOutcome.FAILED_OOM,
                               f"tenant {ten.tid!r} block quota "
                               f"({ten.quota_blocks}) exhausted: "
                               f"{self.cache.tenant_charge(ten.tid)} "
                               f"held + {need_new} needed")
                else:
                    self._preempt_youngest(own)
                continue
            if need_new > 0 and \
                    self.cache.tenant_charge(ten.tid) \
                    >= ten.reserved_blocks:
                unmet = self._unmet_floors(exclude=ten.tid)
                if unmet and self.free_blocks - need_new < unmet:
                    own = [int(s) for s in
                           np.flatnonzero(self.active
                                          | self.prefilling)
                           if self._requests[int(s)] is not None
                           and self._requests[int(s)].tenant == ten.tid]
                    # sole member: self-evict and wait queued (the
                    # floor clears when its owner charges up); with
                    # peers, the tenant's youngest yields
                    self._preempt_youngest(own)
                    continue
            try:
                self.cache.ensure(slot, length, start_block=start_block,
                                  write_from=write_from)
                return True
            except BlockOOM as e:
                if self.collector is not None:
                    # every pool OOM is a telemetry instant carrying
                    # the structured occupancy breakdown (who held
                    # the pool when it fired)
                    self.collector.on_event("block_oom", dict(
                        e.details, rid=req.rid, tenant=req.tenant,
                        step=self._step_count))
                # shed only when no victim but the grower itself is
                # left: the below-floor branch of _oom_victims returns
                # over-floor BORROWERS, a list that never contains the
                # grower — a single entry there is still an eviction
                # the floor guarantee promises, not a dead end
                cands = self._oom_victims(req)
                if not any(s != slot for s in cands):
                    self._fail(req, RequestOutcome.FAILED_OOM,
                               f"pool exhausted even after preempting "
                               f"every eligible request: {e}")
                else:
                    self._preempt_youngest(cands)
        return False

    def _sanitize_masked_rows(self, x, stepping: np.ndarray):
        """Zero the rows of ``x`` that are NOT stepping this call, on
        device (one fused where, no host sync). Stepping rows pass
        through BITWISE unchanged; non-stepping rows' (ignored) trash-
        block writes become finite, so one request's NaN can never
        leak into another's masked attention tail."""
        import jax.numpy as jnp
        mask = jnp.asarray(stepping.reshape(-1, 1, 1))
        return Tensor(jnp.where(mask, x.data,
                                jnp.zeros((), x.data.dtype)))

    def _guard_numeric(self, out, stepping: np.ndarray) -> None:
        """Per-slot numeric guard: one [B]-bool reduction on device,
        one small host read. A non-finite value in a slot's output row
        fails THAT request (FAILED_NUMERIC — its K/V pages may be
        poisoned, so they are quarantined: freed with their prefix
        index entries dropped, no cached-free second chance) and the
        step stands for every other slot; attention is per-row, so a
        NaN cannot cross slots inside the fused call."""
        import jax.numpy as jnp
        finite = np.asarray(jnp.isfinite(out.data)
                            .reshape(out.shape[0], -1).all(axis=1))
        bad = stepping & ~finite
        for slot in np.flatnonzero(bad):
            req = self._requests[int(slot)]
            if req is None:
                continue
            self._fail(req, RequestOutcome.FAILED_NUMERIC,
                       f"non-finite hidden in slot {int(slot)} at "
                       f"step {self._step_count}")

    def check_invariants(self) -> bool:
        """Audit engine + pool bookkeeping (see PagedKVCache.
        check_invariants for the pool-level list); raises
        AssertionError on violation. Engine-level: every active or
        prefilling slot maps to a request that points back at it,
        queued requests hold no slot, and every active slot's table
        covers its length. Run it after every step under the test
        suite's ``--audit-invariants`` flag, or from a serving loop's
        debug path."""
        reserved = self.groups.reserved_slots()
        for slot in np.flatnonzero(self.active | self.prefilling):
            if int(slot) in reserved:
                # branch-slot reservation of an unforked group lead:
                # held (prefilling) but deliberately requestless
                assert self.prefilling[int(slot)] and \
                    self._requests[int(slot)] is None and \
                    int(slot) not in self._prefills and \
                    self.lens[int(slot)] == 0, \
                    f"reserved branch slot {int(slot)} inconsistent"
                continue
            req = self._requests[int(slot)]
            assert req is not None and req.slot == int(slot), \
                f"slot {int(slot)} active without a matching request"
        for req in self.queue:
            assert req.slot is None, \
                f"queued request {req.rid} still holds slot {req.slot}"
        assert not (self.active & self.prefilling).any(), \
            "slot both active and prefilling"
        for slot in self._prefills:
            assert self.prefilling[slot], \
                f"prefill state for non-prefilling slot {slot}"
        # tenant layer: every live request's tenant is registered, the
        # cache's slot attribution mirrors the engine's, and no tenant
        # sits past its quota (enforcement gates every growth path;
        # set_tenant refuses quotas below the current charge)
        for slot in np.flatnonzero(self.active | self.prefilling):
            req = self._requests[int(slot)]
            if req is None:        # reserved branch slot (audited above)
                continue
            assert req.tenant in self.tenants, \
                f"slot {int(slot)} request of unknown tenant " \
                f"{req.tenant!r}"
            assert self.cache.seq_tenant[int(slot)] == req.tenant, \
                (f"slot {int(slot)} cache attribution "
                 f"{self.cache.seq_tenant[int(slot)]!r} != request "
                 f"tenant {req.tenant!r}")
        queued_by_tenant: Dict[str, int] = {}
        for r in self.queue:
            assert r.tenant in self.tenants, \
                f"queued request {r.rid} of unknown tenant {r.tenant!r}"
            queued_by_tenant[r.tenant] = \
                queued_by_tenant.get(r.tenant, 0) + 1
        total_q = 0
        for tid, ten in self.tenants.items():
            assert ten.queued == queued_by_tenant.get(tid, 0) \
                == len(ten.fifo), \
                (f"tenant {tid!r} queued gauge {ten.queued} != "
                 f"{queued_by_tenant.get(tid, 0)} request(s) actually "
                 f"queued (sub-queue holds {len(ten.fifo)})")
            total_q += len(ten.fifo)
            # sub-queue internal order follows the global merge key
            # (preempted by rid, then fresh by enqueue order)
            keys = [self._queue_key(r) for r in ten.fifo]
            assert keys == sorted(keys), \
                (f"tenant {tid!r} sub-queue out of admission order: "
                 f"{[r.rid for r in ten.fifo]}")
            assert all(r.tenant == tid for r in ten.fifo), \
                f"foreign request in tenant {tid!r} sub-queue"
            if ten.quota_blocks is not None:
                held = self.cache.tenant_charge(tid)
                assert held <= ten.quota_blocks, \
                    (f"tenant {tid!r} holds {held} block(s) over its "
                     f"quota {ten.quota_blocks}")
        assert self._queue_len == total_q, \
            (f"queue depth gauge {self._queue_len} != {total_q} "
             f"request(s) across the sub-queues")
        self._audit_groups()
        self.cache.check_invariants(lens=self.lens, active=self.active)
        self.resilience_stats.audits += 1
        return True

    def _audit_groups(self) -> None:
        """Fork-shared page audit: for every live group, every pool
        block's MULTIPLICITY across the member slots' block tables is
        covered by the allocator's refcount (each branch-table
        reference holds one count — the one-charge-per-reference
        policy made physical). ``>=`` not ``==``: the prefix cache and
        the cached-free index may hold further legitimate references
        on top of the group's own. Also audits the group records
        themselves: members map back to the group, reserved slots are
        requestless holders, and only unforked groups reserve."""
        by_slot = {r.rid: s for s, r in enumerate(self._requests)
                   if r is not None}
        for gid, g in self.groups.groups.items():
            assert g["rids"][0] == gid, \
                f"group {gid} lead rid mismatch: {g['rids']}"
            assert set(g["live"]) <= set(g["rids"]), \
                f"group {gid} live set exceeds its members"
            if g["forked"]:
                assert not g["reserved"], \
                    f"forked group {gid} still holds reserved slots"
            slots = [by_slot[rid] for rid in g["rids"]
                     if rid in by_slot]   # queued / preempted /
            if not slots:                 # terminal members hold no
                continue                  # table to audit
            rep = self.cache.share_report(slots)
            for b, m in rep["multiplicity"].items():
                assert rep["refcount"][b] >= m, \
                    (f"group {gid}: block {b} referenced by {m} member "
                     f"table(s) but refcount is {rep['refcount'][b]}")

    # -- page migration (disaggregated serving) -----------------------
    def export_request_slice(self, rid: int) -> Optional[dict]:
        """Migration export (inference/router.py): the wire-format
        slice of ``rid``'s finished prefix pages — its chain-hash
        identity paired with the pool blocks that hold them
        (``PagedKVCache.export_slice``). Only pages a different pool
        could ADOPT ride along: full blocks the slot has actually
        computed (an active slot's decoded extent, a mid-prefill
        slot's chunk frontier). Returns None when the request is
        unknown, still queued, or holds no full block yet — the
        router then migrates cold (plain resubmission). A pure read:
        no allocator or scheduler state moves."""
        self._flush_history()
        req = self.request_of(rid)
        if req is None or req.slot is None:
            return None
        slot = int(req.slot)
        if self.prefilling[slot]:
            covered = int(self._prefills[slot]["pos"])
        else:
            covered = int(self.lens[slot])
        n_full = covered // self.cache.block_size
        if n_full <= 0:
            return None
        hashes = self._block_hashes(req)[:n_full]
        if not hashes:
            return None
        return self.cache.export_slice(slot, hashes)

    def import_slice(self, slc: dict) -> int:
        """Adopt a migrated slice into this engine's pool
        (``PagedKVCache.import_slice``): pages land cached-free +
        hash-indexed, so the migrated request's resubmission hits
        them through the normal prefix-cache admission path."""
        return self.cache.import_slice(slc)

    # -- checkpoint / restore -----------------------------------------
    @staticmethod
    def _stats_rec(st) -> dict:
        return {name: getattr(st, name) for name in st.__slots__}

    @staticmethod
    def _stats_set(st, rec: dict) -> None:
        for name, v in rec.items():
            setattr(st, name, v)

    def _req_rec(self, req: PagedRequest, now: float) -> dict:
        """Picklable record of one request. Wall-clock deadlines are
        stored as REMAINING seconds at snapshot time — the monotonic
        clock does not survive a process, so restore re-bases them."""
        return {
            "rid": req.rid,
            "history": np.array(req.history, np.float32, copy=True),
            # a few bytes a row beside 4 x d_model: what the hashes
            # were (and the rest of the chain will be) made from
            "keys": req.keys_held(),
            "hashes": list(req._hashes),
            "slot": req.slot,
            "admit_seq": req.admit_seq,
            "preemptions": req.preemptions,
            "max_preemptions": req.max_preemptions,
            "deadline_steps": req.deadline_steps,
            "deadline_remaining": (None if req.deadline_time is None
                                   else req.deadline_time - now),
            "submit_step": req.submit_step,
            "tenant": req.tenant,
            "gid": req.gid,
            "branch": req.branch,
            "group_n": req.group_n,
        }

    def snapshot(self) -> dict:
        """Checkpoint EVERYTHING a restored engine needs to continue
        bit-identically: the pool snapshot (PagedKVCache.snapshot),
        every live request (queued, mid-prefill and running — history,
        memoized chain hashes, retry/deadline budgets), queue order,
        per-slot state (lens/active/prefilling + mid-chunk prefill
        frontiers), the step clock and admission sequencer, all stats
        siblings, and any undrained event lists. Buffered decode
        inputs are flushed to histories first, so the snapshot is a
        pure host-side read of a step-boundary state. Telemetry
        (``collector``) is deliberately EXCLUDED: its wall-clock
        timestamps are observational, never behavioral, so restore
        wires the caller's collector fresh instead of replaying
        stale clocks into a new process."""
        self._flush_history()
        now = time.monotonic()
        reqs: Dict[int, PagedRequest] = {
            r.rid: r for r in list(self.queue)
            + [q for q in self._requests if q is not None]}
        return {
            "kind": "paged_engine",
            "config": {
                "max_batch": self.max_batch,
                "block_size": self.cache.block_size,
                "num_blocks": self.cache.num_blocks,
                "max_blocks_per_seq": self.cache.max_blocks_per_seq,
                "dtype": self.dtype,
                "watermark_blocks": self.watermark_blocks,
                "prefix_cache": self.prefix_cache,
                "chunk_tokens": self.chunk_tokens,
                "prefill_token_budget": self.prefill_token_budget,
                "max_preemptions": self.max_preemptions,
                "numeric_guard": self.numeric_guard,
                "ragged_step": self.ragged_step,
            },
            "cache": self.cache.snapshot(),
            "requests": [self._req_rec(r, now) for r in reqs.values()],
            "queue": [r.rid for r in self.queue],
            "slot_rids": [None if r is None else r.rid
                          for r in self._requests],
            "lens": self.lens.copy(),
            "active": self.active.copy(),
            "prefilling": self.prefilling.copy(),
            "prefills": {int(s): {"pos": st["pos"], "start": st["start"],
                                  "n_cached": st["n_cached"],
                                  "hashes": list(st["hashes"])}
                         for s, st in self._prefills.items()},
            "counters": {"next_rid": self._next_rid,
                         "next_admit_seq": self._next_admit_seq,
                         "step_count": self._step_count,
                         "has_deadlines": self._has_deadlines},
            "groups": self.groups.snapshot(),
            # tenant isolation state: configs, WFQ virtual times (the
            # list order IS the registration order — the WFQ
            # tie-break), and per-tenant stats; restore rebuilds the
            # registry so quotas/weights/fairness continue exactly
            "tenants": [{"id": t.tid,
                         "quota_blocks": t.quota_blocks,
                         "reserved_blocks": t.reserved_blocks,
                         "weight": t.weight,
                         "vtime": t.vtime,
                         "stats": self._stats_rec(t.stats)}
                        for t in self.tenants.values()],
            "vclock": self._vclock,
            "stats": {"prefix": self._stats_rec(self.prefix_stats),
                      "prefill": self._stats_rec(self.prefill_stats),
                      "resilience":
                          self._stats_rec(self.resilience_stats),
                      "parallel":
                          self._stats_rec(self.parallel_stats)},
            "events": {
                "admitted": [(rid, slot,
                              None if h is None
                              else np.asarray(h.numpy()))
                             for rid, slot, h in self.admitted],
                "finished": list(self.finished),
                "preempted": list(self.preempted),
            },
            "outcomes": [oc.as_dict() for oc in self.outcomes],
        }

    @classmethod
    def restore(cls, model, snap: dict, *, injector=None,
                collector=None, monitor=None, ledger=None,
                num_blocks: Optional[int] = None) -> "PagedServingEngine":
        """Rebuild an engine from a ``snapshot`` around the caller's
        model (weights are the caller's problem — a snapshot holds
        serving state, not parameters). ``num_blocks`` rehomes the
        pool into a different-size target (PagedKVCache.restore).
        The injector is wired fresh (fault schedules stay keyed by
        the RESTORED step clock, so a replayed step re-injects the
        same faults — required for deterministic replay), and so is
        the collector: snapshots carry NO telemetry state (wall-clock
        timestamps never enter engine-behavioral state), the caller's
        collector simply keeps observing the restored engine. Ends
        with a full engine + deep pool audit."""
        cfg = snap["config"]
        nb = cfg["num_blocks"] if num_blocks is None else int(num_blocks)
        # the constructor's cache is discarded two lines down for the
        # restored one — build it with a 2-block placeholder pool so
        # recovery never holds two full pools at once (a production
        # pool is sized near device memory; 2x there would OOM the
        # recovery path itself). Geometry that outlives the swap
        # (max_len) comes from max_blocks_per_seq, which is passed
        # resolved, and is re-derived from the restored cache below.
        eng = cls(model, cfg["max_batch"], cfg["block_size"], 2,
                  max_blocks_per_seq=cfg["max_blocks_per_seq"],
                  dtype=cfg["dtype"],
                  watermark_blocks=cfg["watermark_blocks"],
                  prefix_cache=cfg["prefix_cache"],
                  chunk_tokens=cfg["chunk_tokens"],
                  prefill_token_budget=cfg["prefill_token_budget"],
                  injector=injector, collector=collector,
                  monitor=monitor, ledger=ledger,
                  max_preemptions=cfg["max_preemptions"],
                  numeric_guard=cfg["numeric_guard"],
                  # pre-ragged snapshots restore onto the (equivalent)
                  # ragged default; the "tile_q" / "tile_kv" keys of
                  # older snapshots are ignored (the kernel derives
                  # its sizes from shapes)
                  ragged_step=cfg.get("ragged_step", True))
        # nb may differ from the cache snapshot's geometry (a resized
        # engine config, or the explicit override): the pool restore
        # rehomes content-addressed blocks either way. The MESH WIDTH
        # comes from the CALLER'S MODEL, not the snapshot — the pool
        # payload is canonical (full-head pages), so a snapshot taken
        # on an mp=N fleet restores behind a single-chip model and
        # vice versa (tensor-parallel snapshot portability)
        eng.cache = PagedKVCache.restore(
            snap["cache"], num_blocks=nb,
            mp=getattr(model, "mp", 1),
            shard_devices=getattr(model, "shard_devices", None))
        if injector is not None:
            eng.cache.allocator.fault_hook = \
                lambda n: injector.on_alloc("target", n)
        eng.max_len = eng.cache.capacity_per_seq
        now = time.monotonic()
        # tenant registry (version-gated: pre-tenant snapshots carry
        # no "tenants" key and restore to the implicit default-only
        # registry the constructor already built)
        for trec in snap.get("tenants", []):
            ten = Tenant(trec["id"],
                         quota_blocks=trec["quota_blocks"],
                         reserved_blocks=trec["reserved_blocks"],
                         weight=trec["weight"])
            ten.vtime = trec["vtime"]
            cls._stats_set(ten.stats, trec["stats"])
            eng.tenants[ten.tid] = ten
        eng._vclock = snap.get("vclock", 0.0)
        reqs: Dict[int, PagedRequest] = {}
        for rec in snap["requests"]:
            # a snapshot written before requests had keys has none in
            # its records: such a request goes on hashing its ROWS, as
            # the hashes it memoized and the index this snapshot
            # carries were made
            req = PagedRequest(rec["rid"], rec["history"],
                               keys=rec.get("keys"))
            req._hashes = list(rec["hashes"])
            req.tenant = rec.get("tenant", DEFAULT_TENANT)
            req.slot = rec["slot"]
            req.admit_seq = rec["admit_seq"]
            req.preemptions = rec["preemptions"]
            req.max_preemptions = rec["max_preemptions"]
            req.deadline_steps = rec["deadline_steps"]
            if rec["deadline_remaining"] is not None:
                req.deadline_time = now + rec["deadline_remaining"]
            req.submit_step = rec["submit_step"]
            # pre-group snapshots carry no branch fields: they restore
            # as the lone streams they were
            req.gid = rec.get("gid")
            req.branch = rec.get("branch", 0)
            req.group_n = rec.get("group_n", 1)
            reqs[req.rid] = req
        eng._requests = [None if rid is None else reqs[rid]
                         for rid in snap["slot_rids"]]
        # reconcile the pool's slot attribution with the requests —
        # a no-op for tenant-era snapshots, and the version gate that
        # lifts a pre-tenant snapshot's unattributed slots onto the
        # implicit default tenant (charge moves with them)
        for slot, r in enumerate(eng._requests):
            if r is not None:
                eng.cache.set_seq_tenant(slot, r.tenant)
        # re-shard the snapshot's global queue-order list into the
        # per-tenant FIFO sub-queues: the saved order is merged-key
        # order, so per-tenant suborder is preserved by appending in
        # sequence (enqueue seqs are reassigned monotonically — only
        # their relative order is behavioral)
        for rid in snap["queue"]:
            r = reqs[rid]
            eng._resolve_tenant(r.tenant)   # auto-register if needed
            eng._enqueue(r)
        eng.lens = np.array(snap["lens"], np.int32)
        eng.active = np.array(snap["active"], bool)
        eng.prefilling = np.array(snap["prefilling"], bool)
        eng._prefills = {int(s): dict(st)
                         for s, st in snap["prefills"].items()}
        c = snap["counters"]
        eng._next_rid = c["next_rid"]
        eng._next_admit_seq = c["next_admit_seq"]
        eng._step_count = c["step_count"]
        eng._has_deadlines = c["has_deadlines"]
        # branch groups (version-gated: pre-group snapshots restore
        # to an empty table)
        eng.groups.restore(snap.get("groups", {}))
        cls._stats_set(eng.prefix_stats, snap["stats"]["prefix"])
        cls._stats_set(eng.prefill_stats, snap["stats"]["prefill"])
        cls._stats_set(eng.resilience_stats,
                       snap["stats"]["resilience"])
        cls._stats_set(eng.parallel_stats,
                       snap["stats"].get("parallel", {}))
        ev = snap["events"]
        eng.admitted = [(rid, slot,
                         None if h is None else Tensor(h))
                        for rid, slot, h in ev["admitted"]]
        eng.finished = list(ev["finished"])
        eng.preempted = list(ev["preempted"])
        eng.outcomes = [RequestOutcome(**oc) for oc in snap["outcomes"]]
        eng.check_invariants()
        if monitor is not None:
            # monitor state is DERIVED, never snapshotted: a fresh
            # monitor re-baselines its interval-delta snapshot at the
            # restored step (counters restore exactly, so resampling
            # the replay reproduces the dead incarnation's samples);
            # a monitor that lived through the crash keeps its live
            # history — rebase is a no-op for it
            monitor.rebase(eng._step_count)
        return eng
