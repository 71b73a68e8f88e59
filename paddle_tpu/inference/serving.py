"""Continuous-batching serving engine over the fused decoder stack.

ref: /root/reference/paddle/fluid/operators/fused/
fused_multi_transformer_op.cu.h:835 — the reference decodes a FIXED
batch with per-batch valid lengths (masked mha over cache_kv). This
engine supplies the serving shape the reference leaves to external
stacks (and the PAPERS.md ragged-serving direction): a fixed pool of
cache SLOTS, each an independent sequence at its own position; one
fused decode step advances every active slot (ragged lengths ride the
per-row seq_lens of the flash-decode kernel / per-row mask), and
finished slots are freed and re-filled by new requests WITHOUT
stopping the batch — continuous batching.

The model contract is FusedMultiTransformer's decode protocol:
``model(x, caches=..., time_step=...) -> (hidden, new_caches)`` with
caches shaped [2, B, H, max_len, D] per layer and time_step a per-row
int32 vector. Prefill of a new request runs batch-1 against a fresh
single-row cache and is scattered into the slot, so in-flight slots
never stall.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from ..framework.tensor import Tensor
from .telemetry import StatsBase

__all__ = ["ContinuousBatchingEngine", "ParallelStats",
           "PrefillStats", "PrefixCacheStats", "ResilienceStats",
           "ShardedServingCore", "SpecDecodeStats", "TenantStats"]

# The five stats siblings below share ONE declarative base
# (telemetry.StatsBase): each lists its counter FIELDS, the DERIVED
# properties to export next to them (with rounding), and the REPR
# headline subset — as_dict()/__repr__ are generated, so every stat a
# class declares is export-visible by construction and the engines'
# MetricsRegistry can attach them wholesale.


class PrefixCacheStats(StatsBase):
    """Serving-surface accounting for the cross-request prefix cache
    (PagedServingEngine(prefix_cache=True)): block-level hit rate and
    the prefill work the cache saved. One instance per engine, read by
    benches/dashboards; counters only ever grow.

      lookups         admissions that probed the index
      lookup_blocks   full prompt blocks eligible to hit
      hit_blocks      blocks shared instead of allocated
      tokens_skipped  prompt tokens whose prefill was skipped
      tokens_computed prompt tokens actually prefilled
      hashed_bytes    bytes of key material the block-identity chain
                      read (4 B a token where requests come with token
                      ids; 4 x d_model B a token where they have rows
                      only)
      row_keyed_blocks  blocks whose identity was hashed from embedding
                      rows (0 behind a token wrapper)
    """

    __slots__ = FIELDS = ("lookups", "lookup_blocks", "hit_blocks",
                          "tokens_skipped", "tokens_computed",
                          "hashed_bytes", "row_keyed_blocks")
    DERIVED = {"blocks_saved": None, "hit_rate": 4}
    REPR = ("hit_rate", "blocks_saved", "tokens_skipped")

    @property
    def blocks_saved(self) -> int:
        """Pages neither allocated nor prefilled thanks to sharing."""
        return self.hit_blocks

    @property
    def hit_rate(self) -> float:
        if self.lookup_blocks == 0:
            return 0.0
        return self.hit_blocks / self.lookup_blocks


class PrefillStats(StatsBase):
    """Serving-surface accounting for CHUNKED PAGED PREFILL
    (scheduler.chunked_prefill / PagedServingEngine), sibling of
    PrefixCacheStats and SpecDecodeStats; counters only grow.

      chunks          chunk model calls run (each writes its K/V
                      straight into pages — no dense scratch)
      prefill_tokens  prompt tokens streamed through those chunks
      prefill_steps   engine steps that advanced at least one pending
                      prefill (token-budget mixed-step mode)
      decode_steps    engine steps that ran the fused decode call
      mixed_steps     steps that did BOTH — the Sarathi-style packing
                      signal (prefill riding along instead of
                      stalling the running batch)
      peak_blocks     high-water pool blocks in use (sampled after
                      every chunk AND every decode step's growth) —
                      with the dense scratch retired this IS the peak
                      KV footprint
    """

    __slots__ = FIELDS = ("chunks", "prefill_tokens", "prefill_steps",
                          "decode_steps", "mixed_steps", "peak_blocks")
    DERIVED = {"tokens_per_chunk": 2, "mixed_step_rate": 4,
               "prefill_tokens_per_step": 2}
    REPR = ("chunks", "prefill_tokens", "mixed_step_rate",
            "peak_blocks")

    @property
    def tokens_per_chunk(self) -> float:
        if self.chunks == 0:
            return 0.0
        return self.prefill_tokens / self.chunks

    @property
    def prefill_tokens_per_step(self) -> float:
        """Mean prompt tokens advanced per prefill-carrying step (the
        token-budget utilization signal)."""
        if self.prefill_steps == 0:
            return 0.0
        return self.prefill_tokens / self.prefill_steps

    @property
    def mixed_step_rate(self) -> float:
        """Fraction of steps that packed prefill chunks alongside
        decode rows."""
        total = self.decode_steps + self.prefill_steps \
            - self.mixed_steps
        if total == 0:
            return 0.0
        return self.mixed_steps / total


class ResilienceStats(StatsBase):
    """Serving-surface accounting for the resilience layer
    (inference/resilience.py + the per-request failure isolation in
    scheduler.py), sibling of PrefixCacheStats / PrefillStats /
    SpecDecodeStats; counters only grow.

      shed             requests FAILED_OOM: pool dry even after
                       preempting every other request, or the
                       re-prefill retry budget (max_preemptions)
                       exhausted — the request is failed and its
                       blocks freed, the step completes for everyone
                       else
      retried          re-admissions of previously preempted requests
                       (each one replays its history bit-identically)
      deadline_failed  requests FAILED_DEADLINE (per-request
                       deadline_steps / deadline_s blown, admitted or
                       still queued)
      nan_failed       requests FAILED_NUMERIC (non-finite hidden in
                       the slot's fused-step output row)
      rejected         requests REJECTED_ADMISSION (health-based
                       admission control refused them at submit:
                       quota- or pool-impossible, or the deadline
                       below the prefill-step lower bound)
      cancelled        requests CANCELLED — deliberate early stop
                       (best-of-n loser pruning, beam cuts, caller
                       cancel); NOT counted as a failure
      audits           check_invariants() passes run through the
                       engine surface
    """

    __slots__ = FIELDS = ("shed", "retried", "deadline_failed",
                          "nan_failed", "rejected", "cancelled",
                          "audits")
    DERIVED = {"failed": None}
    REPR = ("shed", "retried", "deadline_failed", "nan_failed",
            "rejected")

    @property
    def failed(self) -> int:
        """Total requests that ended in a failure outcome."""
        return (self.shed + self.deadline_failed + self.nan_failed
                + self.rejected)


class TenantStats(StatsBase):
    """Per-tenant serving accounting (multi-tenant isolation,
    scheduler.py): one instance per tenant in
    ``PagedServingEngine.tenant_stats``, the attribution surface that
    makes a noisy neighbor VISIBLE — which tenant sheds, which tenant
    gets rejected, which tenant holds the pool. Counters only grow
    except ``blocks_held``, a live gauge refreshed at every step top.

      admitted       requests of this tenant granted a slot (including
                     re-admissions after preemption)
      sheds          requests FAILED_OOM — pool or tenant quota dry
      rejections     requests REJECTED_ADMISSION at submit
      quota_hits     growth/admission attempts that ran into THIS
                     tenant's block quota (each may preempt or shed
                     within the tenant, never a neighbor)
      preemptions    evictions charged to this tenant's requests
      deadline_failed / nan_failed / cancelled   per-tenant split of
                     the engine ResilienceStats counters
      blocks_held    pool blocks currently charged to the tenant (one
                     charge per block-table reference its slots hold)
      tokens_served  decode tokens consumed by this tenant's slots
                     through fused steps
    """

    __slots__ = FIELDS = ("admitted", "sheds", "rejections",
                          "quota_hits", "preemptions",
                          "deadline_failed", "nan_failed", "cancelled",
                          "blocks_held", "tokens_served")
    DERIVED = {"failed": None}
    REPR = ("blocks_held", "tokens_served", "sheds", "rejections",
            "quota_hits")

    @property
    def failed(self) -> int:
        return (self.sheds + self.rejections + self.deadline_failed
                + self.nan_failed)


class ParallelStats(StatsBase):
    """Serving-surface accounting for fork-shared parallel decoding
    (branch groups, scheduler.py): one ``submit(n=k)`` prefills the
    prompt ONCE and COW-forks k branch slots over the same prompt
    pages. Sibling of the other stats classes; counters only grow.

      groups                branch groups admitted (submit(n>1) that
                            passed the health gate, plus on-demand
                            groups minted by ``fork_stream``)
      branches              branch slots forked (excludes the lead:
                            a group of n adds n-1 here; every
                            ``fork_stream`` clone adds 1)
      prefill_tokens_saved  prompt tokens whose prefill the fork
                            skipped (branch length at fork time,
                            summed over branches) — the work the
                            shared prefill amortized
      shared_blocks         block-table references the forks added to
                            already-resident pages (each one a page
                            NOT allocated; charged per reference
                            under the PR 7 quota policy)
    """

    __slots__ = FIELDS = ("groups", "branches",
                          "prefill_tokens_saved", "shared_blocks")
    DERIVED = {"branches_per_group": 2}
    REPR = ("groups", "branches", "prefill_tokens_saved")

    @property
    def branches_per_group(self) -> float:
        if self.groups == 0:
            return 0.0
        return self.branches / self.groups


class SpecDecodeStats(StatsBase):
    """Serving-surface accounting for speculative decoding
    (inference/speculative.py), the sibling of PrefixCacheStats. One
    counter bump per (slot, verification step); counters only grow.

      proposed          draft tokens offered to verification
      accepted          draft tokens the target model agreed with
      emitted           tokens actually emitted (accepted + the one
                        bonus/correction token per step)
      target_steps      per-slot target verification steps — the cost
                        unit speculation amortizes
      draft_steps       per-slot draft model forward steps
      rolled_back       rejected tokens rolled back via page-table
                        truncation
      draft_oom_rolls   draft rolls aborted by a draft-pool BlockOOM
                        (the partial roll is rolled back page-wise and
                        the round serves without speculation)
    """

    __slots__ = FIELDS = ("proposed", "accepted", "emitted",
                          "target_steps", "draft_steps", "rolled_back",
                          "draft_oom_rolls")
    DERIVED = {"acceptance_rate": 4, "tokens_per_target_step": 4}
    REPR = ("acceptance_rate", "tokens_per_target_step", "emitted")

    @property
    def acceptance_rate(self) -> float:
        if self.proposed == 0:
            return 0.0
        return self.accepted / self.proposed

    @property
    def tokens_per_target_step(self) -> float:
        """Mean tokens emitted per target-model step — the speculative
        speedup signal (1.0 == plain decode; K+1 == every proposal
        accepted)."""
        if self.target_steps == 0:
            return 0.0
        return self.emitted / self.target_steps


def _make_pad_heads(shard: int, heads_per_shard: int, num_heads: int):
    import jax.numpy as jnp

    def mp_pad_heads(a):
        # a [b, l, H/mp, D] -> [b, l, H, D], zeros outside this
        # shard's contiguous head slice: the shard's DISJOINT-support
        # contribution to the layer all-reduce. Summing the mp padded
        # contributions reconstructs the full-head attention output
        # BITWISE (x + 0.0 is exact in IEEE for every normal x) — the
        # property the sharded path's bit-identity proof rests on.
        out = jnp.zeros(a.shape[:2] + (num_heads,) + a.shape[3:],
                        a.dtype)
        lo = shard * heads_per_shard
        return out.at[:, :, lo:lo + heads_per_shard].set(a)
    return mp_pad_heads


def _uncommitted(arr):
    """Rebind a (possibly committed) jax array as UNCOMMITTED without
    leaving the device: downstream ops stay free to colocate with the
    next committed operand they meet instead of dragging everything to
    this array's device. The ArrayImpl rewrap is a zero-copy metadata
    op (same buffers, committed=False)."""
    from jax._src.array import ArrayImpl
    return ArrayImpl(arr.aval, arr.sharding,
                     [s.data for s in arr.addressable_shards],
                     committed=False)


class ShardedServingCore:
    """Tensor-parallel (head-sharded) serving twin of a
    FusedMultiTransformer core — the model half of sharded paged
    serving (the pool half is ``PagedKVCache(mp=N)``,
    inference/paged_cache.py). Megatron-style partition chosen so the
    CPU-mesh proof can be BIT-IDENTICAL to the single chip:

      * qkv projection: COLUMN-sharded by head — shard s owns
        ``[d, 3 * H/mp * hd]`` (its q/k/v column groups, bias sliced
        alike) — on the KERNEL (TPU) path. On the CPU proof path the
        column-sliced GEMM is NOT bitwise column-stable at serving
        widths (measured: XLA CPU matmul columns shift ~1 ulp with
        the output width at d=256 — the same executable-shape trap
        class as scheduler.MIN_PREFILL_SUFFIX_ROWS and PR 10's
        row-count finding), so there the REPLICATED projection runs
        once per layer — the exact single-chip executable — and each
        shard slices ITS HEADS out of the result (slicing is exact at
        every width). ``qkv_shard`` picks: "auto" (default — weights
        on TPU, activations on CPU), "weights", "activations".
      * attention: shard s appends to and attends over ITS pool slice
        only (heads are independent — per-shard outputs are bitwise
        the head slices of the full launch). One ragged kernel launch
        per layer per shard on TPU; the jnp fallbacks inherit.
      * the layer closes with ONE ALL-REDUCE: each shard contributes
        its attention output zero-padded to full heads (disjoint
        support), the sum reconstructs the full tensor exactly, and
        the out projection + FFN + LayerNorms run REPLICATED on it —
        the same executables, the same bytes, as the single chip.

    That is exactly ``num_layers`` collectives per model call
    (``allreduce_count`` is the acceptance counter), weights sharded
    where memory matters (qkv columns; the KV pool is the real win —
    per-request HBM headroom multiplies by the mesh width) and
    replicated where exactness matters (out/ffn/ln).

    Placement: ``devices`` (default
    ``parallel.mesh.serving_shard_devices(mp)``) commits shard s's
    qkv slices — and, through ``PagedKVCache.for_model``, its pool
    slice — to device s. On a single-device host the shards are
    LOGICAL (numerics and collective schedule identical, placement
    degenerate), which is how tier-1 proves bit-identity in-process;
    a real mesh (e.g. ``XLA_FLAGS=--xla_force_host_platform_device_
    count=N``) places them on N distinct devices and the all-reduce
    performs real cross-device transfers. This host-orchestrated
    collective is the CPU-mesh PROOF vehicle; the TPU deployment leg
    lowers the same schedule to jax.lax.psum under shard_map (ROADMAP
    hardware residual).

    The wrapper speaks the full FusedMultiTransformer serving
    protocol (``model(x, caches=..., time_step=...)``) for PAGED
    caches — decode, multi-token verify, chunked prefill and the
    packed ragged mixed step all ride the per-shard views'
    ``shard(s)`` accessor — so PagedServingEngine, SpeculativeEngine,
    RecoverableServer and the router compose unchanged. Dense
    (non-paged) caches are not served: sharding exists for the paged
    pool. Weights are SNAPSHOTTED at wrap time (like
    ``quantize_weights``): shard after the weights are final."""

    def __init__(self, base, mp: int, devices=None,
                 qkv_shard: str = "auto", compiled_step="auto",
                 out_shard: str = "auto"):
        import jax
        import jax.numpy as jnp
        if getattr(base, "_quantized", False):
            raise ValueError(
                "int8 cores drop their float weights at quantize "
                "time — shard the float core first (int8 core "
                "projections are a ROADMAP follow-up)")
        if hasattr(base, "moe_spec"):
            raise ValueError(
                "MoE cores shard over EXPERTS, not attention heads — "
                "use MoeServingCore.shard_experts(ep) "
                "(inference/moe_serving.py); composing ep x mp is a "
                "ROADMAP follow-up")
        self.base = base
        self.mp = int(mp)
        if self.mp < 1:
            raise ValueError(f"mp must be >= 1, got {mp}")
        if base.num_heads % self.mp:
            raise ValueError(
                f"num_heads {base.num_heads} must divide evenly over "
                f"mp={self.mp} shards")
        if devices is None:
            from ..parallel.mesh import serving_shard_devices
            devices = serving_shard_devices(self.mp)
        if len(devices) < self.mp:
            raise ValueError(f"need {self.mp} shard devices, got "
                             f"{len(devices)}")
        self.shard_devices = list(devices[:self.mp])
        self._distinct = len(set(self.shard_devices)) > 1
        from ..framework.device import on_tpu
        if qkv_shard == "auto":
            # the house rule (PR 10's ragged_step precedent): the
            # memory-sharded executable engages where it wins (TPU);
            # the CPU proof path keeps the decomposition that is
            # bitwise exact at every width (see class docstring)
            qkv_shard = "weights" if on_tpu() else "activations"
        if qkv_shard not in ("weights", "activations"):
            raise ValueError(f"qkv_shard must be 'auto' | 'weights' |"
                             f" 'activations', got {qkv_shard!r}")
        self.qkv_shard = qkv_shard
        E = base.embed_dim
        Hs = self.heads_per_shard
        hd = base.head_dim
        # per-(layer, shard) qkv column slices, committed to the
        # shard's device on a real mesh. Column index set of shard s:
        # the q, k and v blocks' head-group columns — matches the
        # base's split(qkv, 3)-then-reshape head slicing exactly.
        # Built only on the weight-sharded path; the activation path
        # runs the base module's replicated projection.
        self._qkv_w: List[List[Tensor]] = []
        self._qkv_b: List[List[Optional[Tensor]]] = []
        if qkv_shard == "weights":
            cols = {}
            for s in range(self.mp):
                c = np.concatenate(
                    [np.arange(s * Hs * hd, (s + 1) * Hs * hd)
                     + j * E for j in range(3)])
                cols[s] = np.asarray(c, np.int32)
            for blk in base.layers:
                w = blk.qkv.weight.data
                bia = None if blk.qkv.bias is None \
                    else blk.qkv.bias.data
                ws, bs = [], []
                for s in range(self.mp):
                    wsl = jnp.take(w, jnp.asarray(cols[s]), axis=1)
                    bsl = None if bia is None else jnp.take(
                        bia, jnp.asarray(cols[s]), axis=0)
                    if self._distinct:
                        dev = self.shard_devices[s]
                        wsl = jax.device_put(wsl, dev)
                        if bsl is not None:
                            bsl = jax.device_put(bsl, dev)
                    ws.append(Tensor(wsl))
                    bs.append(None if bsl is None else Tensor(bsl))
                self._qkv_w.append(ws)
                self._qkv_b.append(bs)
        # acceptance counter: ONE all-reduce per layer per model call
        # on the sharded path (mp > 1); reset freely from tests
        self.allreduce_count = 0
        # -- compiled step (one jitted shard_map program per call) ----
        if out_shard == "auto":
            # rows = the true Megatron second GEMM (K-split partial
            # sums) — exact only where the GEMM is column-stable
            # (TPU); the CPU proof path psums the zero-padded head
            # sums and runs the out projection replicated, bitwise
            # the single-chip executable
            out_shard = "rows" if on_tpu() else "replicated"
        if out_shard not in ("rows", "replicated"):
            raise ValueError(f"out_shard must be 'auto' | 'rows' | "
                             f"'replicated', got {out_shard!r}")
        self.out_shard = out_shard
        fully_distinct = len(set(self.shard_devices)) == self.mp
        if compiled_step == "auto":
            compiled_step = self.mp > 1 and fully_distinct
        if compiled_step not in (True, False):
            raise ValueError(f"compiled_step must be 'auto' | True | "
                             f"False, got {compiled_step!r}")
        if compiled_step and (self.mp < 2 or not fully_distinct):
            raise ValueError(
                "compiled_step=True needs mp >= 2 distinct shard "
                "devices (a real Mesh); logical same-device shards "
                "serve on the legacy host-staged path")
        self.compiled_step = compiled_step
        self._compiled = None
        if compiled_step:
            from .compiled_step import CompiledStepRunner
            self._compiled = CompiledStepRunner(self)

    # -- geometry delegation (the protocol surface engines read) ------
    @property
    def num_layers(self):
        return self.base.num_layers

    @property
    def num_heads(self):
        return self.base.num_heads

    @property
    def head_dim(self):
        return self.base.head_dim

    @property
    def embed_dim(self):
        return self.base.embed_dim

    @property
    def heads_per_shard(self) -> int:
        return self.base.num_heads // self.mp

    @property
    def layers(self):
        return self.base.layers

    @property
    def normalize_before(self):
        return self.base.normalize_before

    @property
    def _act_name(self):
        return self.base._act_name

    @property
    def activation(self):
        return self.base.activation

    def gen_paged_cache(self, block_size, num_blocks, max_seqs,
                        max_blocks_per_seq=None, dtype="float32",
                        prefix_cache=False):
        """Sharded pool matching this core's mesh layout (the engines
        call PagedKVCache.for_model, which reads the same fields)."""
        from .paged_cache import PagedKVCache
        return PagedKVCache.for_model(
            self, block_size, num_blocks, max_seqs,
            max_blocks_per_seq=max_blocks_per_seq, dtype=dtype,
            prefix_cache=prefix_cache)

    def reset_allreduce_count(self) -> None:
        self.allreduce_count = 0

    @property
    def prefers_packed_step(self) -> bool:
        """Scheduler hint: the compiled step amortizes best when the
        whole mixed batch rides ONE packed ragged program, so the
        scheduler should take the ragged plan whenever it's legal
        rather than only when per-slot staging would be slower."""
        return self._compiled is not None

    def sharded_metrics(self) -> dict:
        """MetricsRegistry source (attached as ``sharded.*`` by the
        scheduler): dispatch-count instrumentation for the compiled
        step next to the legacy all-reduce counter. A recompile storm
        shows up as ``retraces`` growing past the bucket count."""
        out = {"allreduce_count": self.allreduce_count,
               "mp": self.mp,
               "compiled": 1 if self._compiled is not None else 0}
        if self._compiled is not None:
            out.update(self._compiled.metrics())
        else:
            out.update({"jit_calls": 0, "retraces": 0,
                        "dispatches_per_step": 0, "psums_per_call": 0})
        return out

    def _allreduce(self, parts: List[Tensor]) -> Tensor:
        """THE one collective per layer: sum the shards' zero-padded
        head contributions (disjoint support -> exact reconstruction,
        see _make_pad_heads) in shard order. On a multi-device mesh
        every contribution transfers to shard 0's device and the
        reduced tensor is handed back replicated (host-staged here —
        the CPU-proof emulation of reduce+broadcast; the TPU leg is
        jax.lax.psum). Counted only when something actually crosses
        shards (mp > 1)."""
        if len(parts) == 1:
            return parts[0]
        self.allreduce_count += 1
        total = parts[0]
        if self._distinct:
            import jax
            d0 = self.shard_devices[0]
            for p in parts[1:]:
                # the legacy collective IS a transfer: host-staged
                # reduce-to-shard-0 — the compiled path replaces it
                # with an in-program psum
                total = total + Tensor(
                    jax.device_put(p.data, d0))  # lint: ok(compiled-step-purity)
            # uncommitted replicated result: the out/ffn/ln ops that
            # consume it stay free to colocate with the NEXT
            # committed operand they meet (each shard's qkv slice).
            # The rebind stays ON DEVICE — the old np.asarray round-
            # trip was the per-layer host pull the compiled step
            # exists to kill; the legacy path shouldn't pay it either
            return Tensor(_uncommitted(total.data))
        for p in parts[1:]:
            total = total + p
        return total

    def __call__(self, src, attn_mask=None, caches=None,
                 time_step=None, **kwargs):
        return self.forward(src, attn_mask=attn_mask, caches=caches,
                            time_step=time_step, **kwargs)

    def forward(self, src, attn_mask=None, caches=None,
                time_step=None, **kwargs):
        import jax
        import jax.numpy as jnp
        from ..framework.op import apply
        from ..nn import functional as F
        from ..ops.manipulation import reshape, split
        from ..ops.pallas.paged_attention import head_slice
        if caches is None or time_step is None or \
                not getattr(caches[0], "is_paged", False):
            raise NotImplementedError(
                "ShardedServingCore serves the PAGED cache protocol "
                "only (caches=PagedKVCache views + time_step) — "
                "dense caches have no sharded pool to win")
        cache_mp = getattr(caches[0], "_cache", None)
        if cache_mp is None or cache_mp.mp != self.mp:
            raise ValueError(
                f"cache mesh width "
                f"{getattr(cache_mp, 'mp', '?')} != model mp "
                f"{self.mp} — build the pool via "
                f"PagedKVCache.for_model(sharded_core, ...)")
        if self._compiled is not None:
            res = self._compiled.forward(src, caches, time_step)
            if res is not None:
                return res
        x = src
        b, l = x.shape[0], x.shape[1]
        E, Hs, hd = self.embed_dim, self.heads_per_shard, self.head_dim
        t = time_step.data if isinstance(time_step, Tensor) \
            else jnp.asarray(time_step, jnp.int32)
        t = jnp.broadcast_to(t.reshape(-1).astype(jnp.int32), (b,))
        new_caches = []
        for i, blk in enumerate(self.base.layers):
            residual = x
            h = blk.ln(x) if self.normalize_before else x
            qf = kf = vf = None
            if self.qkv_shard == "activations":
                # the replicated projection — the EXACT single-chip
                # executable, run once per layer; shards slice their
                # heads out of the result (exact at every width)
                y = blk.qkv(h)
                qf, kf, vf = split(y, 3, axis=-1)
                qf = reshape(qf, [b, l, self.num_heads, hd])
                kf = reshape(kf, [b, l, self.num_heads, hd])
                vf = reshape(vf, [b, l, self.num_heads, hd])
            parts = []
            for s in range(self.mp):
                if self.qkv_shard == "weights":
                    y = F.linear(h, self._qkv_w[i][s],
                                 self._qkv_b[i][s])
                    q, k, v = split(y, 3, axis=-1)
                    q = reshape(q, [b, l, Hs, hd])
                    k = reshape(k, [b, l, Hs, hd])
                    v = reshape(v, [b, l, Hs, hd])
                else:
                    q = Tensor(head_slice(qf.data, s, self.mp))
                    k = Tensor(head_slice(kf.data, s, self.mp))
                    v = Tensor(head_slice(vf.data, s, self.mp))
                view = caches[i] if self.mp == 1 \
                    else caches[i].shard(s)
                attn_s = view.decode(q, k, v, t)
                if self.mp == 1:
                    parts.append(attn_s)
                else:
                    parts.append(apply(
                        _make_pad_heads(s, Hs, self.num_heads),
                        (attn_s,), op_name="mp_pad_heads"))
            attn = self._allreduce(parts)
            attn = blk.out_proj(reshape(attn, [b, l, E]))
            x = residual + attn
            if not self.normalize_before:
                x = blk.ln(x)
            residual = x
            hh = blk.ffn_ln(x) if self.normalize_before else x
            hh = blk.ffn2(self.activation(blk.ffn1(hh)))
            x = residual + hh
            if not self.normalize_before:
                x = blk.ffn_ln(x)
            new_caches.append(caches[i])
        return x, new_caches


class ContinuousBatchingEngine:
    def __init__(self, model, max_batch: int, max_len: int,
                 dtype: str = "float32"):
        self.model = model
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.dtype = dtype
        self.caches: List[Tensor] = model.gen_cache(self.max_batch,
                                                    self.max_len,
                                                    dtype=dtype)
        self.lens = np.zeros(self.max_batch, np.int32)
        self.active = np.zeros(self.max_batch, bool)
        # persistent single-row prefill scratch, reused across
        # admissions (stale tail positions are masked by time_step, so
        # re-zeroing between prompts is unnecessary)
        self._scratch: Optional[List[Tensor]] = None
        # slots auto-released by step() on reaching max_len
        self.finished: List[int] = []

    # -- slot management ----------------------------------------------------
    @property
    def free_slots(self) -> int:
        return int((~self.active).sum())

    def add_request(self, prompt: Tensor) -> Tuple[int, Tensor]:
        """Admit a prompt ([T, d_model] embeddings). Prefills a fresh
        single-row cache and scatters it into a free slot. Returns
        (slot, last_hidden [1, d_model])."""
        import jax.numpy as jnp
        free = np.flatnonzero(~self.active)
        if free.size == 0:
            raise RuntimeError(
                "ContinuousBatchingEngine: no free slots "
                f"(max_batch={self.max_batch}); release() one first")
        slot = int(free[0])
        T = prompt.shape[0]
        if T > self.max_len:
            raise ValueError(f"prompt length {T} > max_len "
                             f"{self.max_len}")
        from ..framework.autograd import no_grad
        if self._scratch is None:
            self._scratch = self.model.gen_cache(1, self.max_len,
                                                 dtype=self.dtype)
        # serving never backprops: without no_grad the tape would pin
        # every superseded cache version across the decode loop.
        # time_step rides as a TENSOR scalar so prefill attends over
        # the scratch's FULL extent with a validity mask (not the
        # int-t [:T] slice): reductions then have one extent for every
        # prompt length, keeping prefill numerics length-independent —
        # the property cross-request prefix reuse is bit-exact under
        with no_grad():
            out, row_caches = self.model(
                prompt.unsqueeze(0), caches=self._scratch,
                time_step=Tensor(np.int32(0)))
        self._scratch = row_caches  # reuse the buffers next admission
        for c, row in zip(self.caches, row_caches):
            c._data = c.data.at[:, slot].set(row.data[:, 0])
        self.lens[slot] = T
        self.active[slot] = True
        return slot, out[:, -1]

    def release(self, slot: int):
        self.active[slot] = False
        self.lens[slot] = 0

    # -- decode -------------------------------------------------------------
    def step(self, x: Tensor) -> Optional[Tensor]:
        """One fused decode step for ALL slots. x: [max_batch, 1,
        d_model] next-token embeddings (inactive rows: any values —
        their cache rows are fully overwritten on reuse). Returns
        hidden [max_batch, 1, d_model]; only active rows are
        meaningful. Advances every active slot's length.

        Slots that already reached max_len are auto-released and
        recorded in ``finished`` — one full sequence no longer stalls
        the rest of the batch. If that empties the batch, returns None
        (drain ``finished`` and admit new requests)."""
        if int(self.active.sum()) == 0:
            raise RuntimeError("step() with no active slots")
        for slot in np.flatnonzero(self.active &
                                   (self.lens >= self.max_len)):
            self.finished.append(int(slot))
            self.release(int(slot))
        if int(self.active.sum()) == 0:
            return None
        from ..framework.autograd import no_grad
        # a copy: self.lens advances in place while the call may still
        # be executing (see PagedServingEngine._step_body)
        t = Tensor(np.array(self.lens, np.int32))
        with no_grad():
            out, self.caches = self.model(x, caches=self.caches,
                                          time_step=t)
        self.lens[self.active] += 1
        return out
