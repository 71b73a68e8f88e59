"""Paged KV cache: block pool + free-list allocator + cache-protocol views.

The reference serving path (fused_multi_transformer_op.cu.h decode) and
the round-5 ContinuousBatchingEngine both pre-allocate a dense
[2, B, H, max_len, D] cache row per slot, so HBM — not compute — caps
concurrency. Here K/V live in a per-layer POOL of fixed-size blocks
[num_blocks, 2, H, block_size, D] (PAPERS.md "Ragged Paged Attention",
arxiv 2604.15464); each sequence owns a block table (int32 row of pool
indices) and grows allocate-on-write, one block at a time. Blocks are
refcounted so a forked request can share its prefix pages and split
them copy-on-write at the first divergent append. A LATENT pool
(``PagedKVCache(v_dim=, sm_scale=)``: absorbed multi-head latent
attention) is [num_blocks, 1, 1, block_size, width]: ONE row a position,
which is the key and whose leading ``v_dim`` columns are the value;
everything host-side below is the same for both forms.

THE STATE STORE (``layer_state``): a layer may hold NO K/V at all (a gated
short convolution: its memory is the last few rows of its own input,
not a context). Such a layer gets no pool; the manager keeps, for each
state layer, ONE array [max_seqs, rows, width] of per-SLOT rows beside
the pools. It is zeroed when a slot is (re)admitted (``ensure`` on an
empty slot), dead with the slot, carried by ``snapshot`` / ``restore``,
and never paged, hashed or shared: what would need a state at a block
boundary (prefix adoption, ``fork``, slices, ``truncate``) and ``mp`` > 1
are refused by name. Every view hands a state layer its rows through
``mix(u, taps)``: one body (``_conv_mix``) for decode rows, a prompt
chunk and a packed batch, fed by index lists the view builds on the
host from the layout it already holds.

The cache layout is a PROTOCOL, not a tensor shape:
``FusedMultiTransformer.forward(..., caches=..., time_step=...)``
accepts either dense per-layer Tensors or the `PagedLayerCache` views
below (duck-typed via ``is_paged``), so dense and paged serving are
interchangeable — see the shim in incubate/nn/fused_transformer.py.

Block 0 of every pool is reserved as the TRASH block: inactive batch
rows in a fused decode step scatter their (ignored) k/v there, and
block-table entries past a sequence's allocation point at it so the
kernel's gather always reads a valid pool row (masked by length).

Cross-request PREFIX CACHING (``prefix_cache=True``) layers a content
index over the pool: every full prompt block gets a chained hash
``h_i = H(h_{i-1}, tokens_in_block_i)`` (vLLM-style block identity —
the chain makes the hash position- and prefix-dependent, so a match on
h_i proves the whole prefix up to block i is identical). A new
request's prompt is matched block-by-block against the index
(``match_prefix``/``adopt_prefix``) and shares the hit pages by
refcount — the existing copy-on-write split handles later divergence.
Freed blocks whose hash is still indexed don't return to the free
list: they park in a CACHED-FREE second-chance tier
(``release_to_cache``), resurrectable on a later hit, and are
reclaimed least-recently-used only when the free list runs dry. Block
lifecycle: free -> active -> cached-free -> (resurrect -> active |
reclaim -> free).

QUANTIZED SERVING (``dtype="int8"``): K/V pages store int8 payload
with per-(position, head) float32 scales in per-block metadata
arrays (``scales``) that ride next to the pools — quantized at
page-write time inside every append op, dequantized on every read
(in-register on the ragged kernel's scalar-prefetch path, inside
``gather_pages`` on the jnp fallbacks). ~1.88x KV density vs bf16 at
head_dim 64 (4/head_dim scale overhead), which at a fixed HBM budget
~1.88x's the block pool and therefore admission concurrency. The
whole page lifecycle below — COW fork, prefix-hash sharing,
cached-free resurrection, quarantine, tenant charge,
snapshot/restore — operates on quantized payloads unchanged: scales
move with their page through COW copies and snapshots, the deep
audit fingerprints payload + scales, and because each position's
quantized bytes are a pure function of that token's K/V (see
``_quant_rows``), prefix adoption of a quantized page is EXACT.

TENSOR-PARALLEL SHARDING (``mp`` > 1): the pool partitions over
attention heads — shard s stores ``pools[layer * mp + s]`` of shape
``[num_blocks, 2, H/mp, bs, D]`` on its own device (scale pages
sharded identically on int8 pools), while the allocator, block
tables, refcounts, chain-hash index, tenant charges and decode mask
stay host-side and REPLICATED: block ids and lifecycle are
shard-invariant, so admission, quotas, WFQ, prefix caching, COW,
snapshots and the journal run byte-for-byte unchanged at any mesh
width. Each layer's views grow a ``shard(s)`` accessor; the
per-shard model (inference/serving.py ``ShardedServingCore``) drives
shard s with its own head slice of q/k/v and closes each layer with
ONE all-reduce. Snapshots and migration slices stay CANONICAL
(full-head pages): shards concatenate on the head axis going out and
re-slice coming in, which is what makes checkpoints and kv_slices
portable across mesh widths (mp=N <-> mp=1) — the content address of
a page never depends on how it is sharded.

CRASH RECOVERY (``snapshot``/``restore``): because every block is
content-addressed by its chain hash, a pool checkpoint is "serialize
the live + cached-free pages plus the allocator's exact state"
(refcounts, free-list order, cached-free LRU order, hash index). A
same-geometry restore is a perfect round trip — block ids, free-list
order and LRU order are preserved, so the restored pool allocates
bit-identically to the uninterrupted one. A restore into a DIFFERENT
``num_blocks`` pool rehomes the content-addressed blocks under fresh
ids through the same hash index (cached-free blocks are dropped
least-recently-used first when the target is smaller; a live set that
cannot fit raises a precise ``BlockOOM`` with the occupancy
breakdown). Restore re-runs the deep ``check_invariants`` audit
before handing the pool back.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import device as _device
from ..framework import layer_jit as _layer_jit
from ..framework.op import _trace_clean, apply, unwrap
from ..framework.tensor import Tensor
from ..ops.pallas.paged_attention import (gather_pages, launch_plan,
                                          live_steps,
                                          paged_attention_ragged,
                                          resolve_tile_q)

__all__ = ["BlockOOM", "BlockAllocator", "PagedKVCache",
           "PagedLayerCache", "PagedPrefillView", "PagedRaggedView",
           "chain_hash", "chain_block_hashes"]


# A block's identity is hashed from its KEYS: whatever identifies each
# of its rows to the caller. A caller that serves tokens hands token
# ids (int32, 4 B a token); one that has only embedding rows hands the
# rows (float32, 4 x d_model B a token). Which of the two a chain reads
# follows from the dtype of what it is handed, nothing else. An id
# chain's FIRST link is seeded with a tag where a row chain's starts
# from b"" (as it always has: indexes of older snapshots stay valid),
# so an id-keyed and a row-keyed block never share a hash.
ID_CHAIN_TAG = b"token-ids/int32:"


def _key_material(block_tokens) -> Tuple[np.ndarray, bytes]:
    """(C-contiguous key material, the seed of a chain of its kind):
    integer input is a run of token ids, anything else rows."""
    arr = np.asarray(block_tokens)
    if arr.dtype.kind in "iu":
        return np.ascontiguousarray(arr, "<i4"), ID_CHAIN_TAG
    return np.ascontiguousarray(arr, np.float32), b""


def _link(parent: bytes, material: np.ndarray) -> bytes:
    h = hashlib.blake2b(parent, digest_size=16)
    h.update(material)          # read in place: no bytes copy
    return h.digest()


def chain_hash(parent: bytes, block_tokens) -> bytes:
    """One link of the block-identity chain: hash of the parent block's
    chained hash + this block's keys (token ids where ``block_tokens``
    is an integer array, else its rows' float32 bytes). ``parent``
    b"" starts a chain."""
    material, seed = _key_material(block_tokens)
    return _link(parent or seed, material)


def chain_block_hashes(tokens, block_size: int,
                       parent: bytes = b"") -> List[bytes]:
    """Chained hashes for every FULL block of ``tokens``: ``[T]`` token
    ids or ``[T, ...]`` rows. Partial trailing blocks are never indexed
    — their content is not yet block-identity-stable (the owner keeps
    appending into them)."""
    arr = np.asarray(tokens)
    n_full = arr.shape[0] // block_size
    if n_full == 0:
        return []
    material, seed = _key_material(arr[:n_full * block_size])
    out: List[bytes] = []
    h = parent or seed
    for block in material.reshape(n_full, -1):
        h = _link(h, block)
        out.append(h)
    return out


class BlockOOM(RuntimeError):
    """No free blocks in the pool (the scheduler preempts on this).

    ``details`` is the STRUCTURED occupancy breakdown the message
    string is composed from (``PagedKVCache.pool_occupancy()``:
    tier counts, owning-slot histogram, per-tenant blocks-held
    histogram) — machine-readable for telemetry (every shed/OOM
    emits it as an event, inference/telemetry.py) instead of
    regex-mining the message. Injected faults
    (``FaultInjector.on_alloc``) carry ``{"injected": True, ...}``;
    an OOM raised before any pool exists carries ``{}``."""

    def __init__(self, *args, details: Optional[dict] = None):
        super().__init__(*args)
        self.details: dict = dict(details) if details else {}


class BlockAllocator:
    """Free-list allocator over pool rows 1..num_blocks-1 with
    refcounts (row 0 is the reserved trash block). Shared-prefix
    blocks hold refcount > 1 and are split copy-on-write by the
    cache.

    With prefix caching the allocator grows a SECOND-CHANCE tier:
    refcount-0 blocks whose content is still hash-indexed park in
    ``_cached`` (cached-free) instead of the free list. They count as
    free — ``alloc`` drains the true free list first, then reclaims
    cached-free blocks least-recently-used, announcing each reclaim
    through ``on_reclaim`` so the owner drops its index entry. A
    BlockOOM therefore means BOTH tiers are dry (callers preempt)."""

    def __init__(self, num_blocks: int, on_reclaim=None):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.num_blocks = int(num_blocks)
        # pop() from the end -> lowest ids first (stable tests)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        # cached-free tier: insertion order == release order, so
        # popitem(last=False) evicts the least-recently-released block
        self._cached: "OrderedDict[int, bool]" = OrderedDict()
        self.on_reclaim = on_reclaim
        self.reclaimed = 0
        self.refcount = np.zeros(self.num_blocks, np.int32)
        self.refcount[0] = 1  # trash block: never allocated, never freed
        # diagnostics + fault injection, wired by the owning cache:
        #   context()       -> str appended to BlockOOM messages (pool
        #                      occupancy breakdown, owning-slot histogram)
        #   context_data()  -> dict carried on BlockOOM.details (the
        #                      same breakdown, machine-readable —
        #                      telemetry events ride it)
        #   describe(block) -> str appended to ref/free misuse errors
        #                      (who owns the block)
        #   fault_hook(n)   -> may raise BlockOOM (FaultInjector);
        #                      consulted first so a forced OOM fires
        #                      even with free blocks in the pool
        self.context = None
        self.context_data = None
        self.describe = None
        self.fault_hook = None

    def _blurb(self, block: int) -> str:
        if self.describe is None:
            return ""
        return f" ({self.describe(int(block))})"

    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._cached)

    @property
    def num_cached(self) -> int:
        return len(self._cached)

    def alloc(self, n: int = 1) -> List[int]:
        if self.fault_hook is not None:
            self.fault_hook(n)
        if n > self.num_free:
            raise BlockOOM(
                f"need {n} block(s), {self.num_free} free "
                f"({len(self._free)} free-list + {len(self._cached)} "
                f"cached-free reclaimable)"
                + (self.context() if self.context is not None else ""),
                details=dict(
                    self.context_data()
                    if self.context_data is not None else {},
                    blocks_needed=int(n),
                    blocks_free=int(self.num_free)))
        blocks = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                # LRU reclaim from the second-chance tier
                b, _ = self._cached.popitem(last=False)
                self.reclaimed += 1
                if self.on_reclaim is not None:
                    self.on_reclaim(b)
            self.refcount[b] = 1
            blocks.append(b)
        return blocks

    def ref(self, blocks) -> None:
        """Share blocks (forked prefix): one more owner each."""
        for b in blocks:
            if self.refcount[b] <= 0:
                raise ValueError(f"ref of unallocated block {b}"
                                 + self._blurb(b))
            self.refcount[b] += 1

    def free(self, blocks, to_cache: bool = False) -> None:
        """Drop one owner per block. A block reaching refcount 0 goes
        to the free list — or, with ``to_cache``, to the cached-free
        tier (still-indexed content, resurrectable on a prefix hit)."""
        for b in blocks:
            if b == 0:
                raise ValueError("block 0 is reserved")
            if self.refcount[b] <= 0:
                raise ValueError(f"double free of block {b}"
                                 + self._blurb(b))
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                if to_cache:
                    self._cached[int(b)] = True
                else:
                    self._free.append(int(b))

    def resurrect(self, block: int) -> None:
        """cached-free -> active again (a prefix hit adopted it)."""
        if block not in self._cached:
            raise ValueError(f"block {block} is not cached-free")
        del self._cached[block]
        self.refcount[block] = 1


# --- int8 KV quantization (``dtype="int8"`` pools) --------------------
# Symmetric per-position-per-head scales: each written K/V row
# quantizes over its head_dim with its own scale, stored in the pool's
# per-block scale metadata [num_blocks, 2, heads, block_size]. Row
# granularity (not one scalar per block) is load-bearing twice over:
# (1) appends into a partially-filled block never re-quantize earlier
# positions — no read-modify-write on the hot append path, and shared
# / hash-indexed pages stay immutable (the deep audit's contract);
# (2) a position's quantized bytes are a pure function of that
# token's K/V — which chunking cannot change (per-row invariance of
# multi-row calls, the established chunked-prefill contract) — so the
# int8 payload + scales of a full block are a deterministic function
# of the prefix token stream, and prefix-hash adoption of a quantized
# page is EXACT (the adopter shares the very bytes it would have
# written). Scale overhead: 4 bytes per (position, head, K|V) next to
# head_dim int8 payload bytes — 4/head_dim relative (6.25% at
# head_dim 64), leaving ~1.88x density vs bf16 pools.

KV_QMAX = 127.0


def _merge_delta_snapshot(snap: dict, base: dict,
                          referenced: List[int]) -> dict:
    """Reconstitute a FULL snapshot from a delta and the base it was
    taken against: the delta's own (dirty) payload rows plus the
    base's rows for every ``base_blocks`` id. Refuses a mismatched
    base (different geometry, or missing a referenced block) — a
    wrong base would scatter wrong bytes under valid block ids, the
    exact corruption content addressing exists to prevent."""
    if base.get("geometry") != snap["geometry"]:
        raise ValueError("delta snapshot: base geometry mismatch")
    row_of = {int(b): i for i, b in enumerate(base["blocks"])}
    missing = [b for b in referenced if b not in row_of]
    if missing:
        raise ValueError(f"delta snapshot references block(s) "
                         f"{missing} the base does not carry — "
                         f"wrong base checkpoint")
    take = [row_of[b] for b in referenced]
    merged = dict(snap)
    merged["blocks"] = [int(b) for b in snap["blocks"]] + \
        [int(b) for b in referenced]
    merged["payload"] = np.concatenate(
        [np.asarray(snap["payload"]),
         np.asarray(base["payload"])[take]], axis=0)
    if "scale_payload" in snap:
        merged["scale_payload"] = np.concatenate(
            [np.asarray(snap["scale_payload"]),
             np.asarray(base["scale_payload"])[take]], axis=0)
    merged["base_blocks"] = []
    return merged


def _quant_rows(x):
    """x [..., D] float -> (int8 payload [..., D], float32 scale
    [...]): symmetric round-to-nearest at amax/127 per row. All-zero
    rows quantize to zeros with scale 0 (dequantizes to exact 0)."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1) / KV_QMAX
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-30)[..., None]),
                 -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return q, scale


# --- pool writes ----------------------------------------------------
# Every write into a pool is PAGE-GRANULAR and runs on a DONATED pool:
# gather the touched pages, set the rows in that small array, scatter
# the whole pages back. A scatter whose window is a whole page keeps the
# pool's own layout, and with the pool donated it compiles to one
# in-place update of the aliased buffer. A ROW scatter into the pool
# (``pool.at[blk, 0, :, off, :].set(k)``) does not: the TPU compiler
# gives it a layout of its own and copies the whole pool there and
# back, two pool-sized copies a layer, donated or not
# (tests/test_pool_write_hlo.py holds the compiled programs to this).

def _pages_spanned(n_tokens: int, block_size: int) -> int:
    """Most pages ``n_tokens`` consecutive positions can touch,
    wherever they start."""
    return (n_tokens + block_size - 2) // block_size + 1


def _set_rows(arr, pg_ids, slot, off, k, v):
    # arr [NB, 2, H, bs(, D)]: a pool, or an int8 pool's scale array.
    # Rows r of k/v [R, H(, D)] land in page pg_ids[slot[r]] at in-page
    # offset off[r]. pg_ids [P] names every real page once; its other
    # slots hold the trash block 0 (pad slots, and the slots that rows
    # routed to trash write), which the write-back may hit in any order.
    # A LATENT pool [NB, 1, H, bs, D] has one plane: ``v`` is None.
    pages = arr[pg_ids]
    pages = pages.at[slot, 0, :, off].set(k.astype(arr.dtype))
    if v is not None:
        pages = pages.at[slot, 1, :, off].set(v.astype(arr.dtype))
    return arr.at[pg_ids].set(pages)


def _write_rows(pool, scales, pg_ids, slot, off, k, v):
    """THE page-form append: rows k/v [R, H, D] through the routing
    (pg_ids [P], slot [R], off [R]); int8 pools (``scales`` not None)
    quantize here and write their scale pages the same way."""
    if scales is not None:
        (k, ks), (v, vs) = _quant_rows(k), _quant_rows(v)
        scales = _set_rows(scales, pg_ids, slot, off, ks, vs)
    return _set_rows(pool, pg_ids, slot, off, k, v), scales


def _append_rows(block_size, n_tokens, pool, scales, k, v, t, bt,
                 ws=None):
    # k/v [B, n_tokens, H, D] land at positions t[b] .. t[b]+n_tokens-1
    # through the block table bt [B, MB] (the decode step at n_tokens 1,
    # the speculative verify, a batch-1 prefill chunk). Row b owns the
    # slots [b*S, (b+1)*S) of the page list: real pages are one row's
    # alone (the write range is COW-split by precondition), and a
    # masked or inactive row's table is all trash. With ``ws`` (a
    # chunk's write start) positions below it (an adopted prefix, whose
    # pages hold these values already and may be SHARED) go to a trash
    # slot of their own, and a page wholly below it is not in the list.
    B = t.shape[0]
    S = _pages_spanned(n_tokens, block_size)
    pos = t[:, None] + jnp.arange(n_tokens, dtype=t.dtype)[None, :]
    first = t // block_size
    col = first[:, None] + jnp.arange(S, dtype=t.dtype)[None, :]
    real = col <= ((t + n_tokens - 1) // block_size)[:, None]
    slot = (jnp.arange(B, dtype=t.dtype)[:, None] * S
            + pos // block_size - first[:, None])
    if ws is not None:
        real = real & ((col + 1) * block_size > ws)
        slot = jnp.where(pos >= ws, slot, B * S)
    ids = jnp.take_along_axis(bt, jnp.minimum(col, bt.shape[1] - 1),
                              axis=1)
    pg_ids = jnp.where(real, ids, 0).reshape(-1)
    if ws is not None:
        pg_ids = jnp.concatenate([pg_ids, jnp.zeros((1,), pg_ids.dtype)])
    return _write_rows(pool, scales, pg_ids, slot.reshape(-1),
                       (pos % block_size).reshape(-1),
                       k.reshape((-1,) + k.shape[2:]),
                       None if v is None
                       else v.reshape((-1,) + v.shape[2:]))


def _ragged_append(pool, scales, k, v, pg_ids, route):
    # packed mixed-batch append: row r of k/v [1, R, H, D] through the
    # routing _RaggedLayout built on the host (route [2, R]: each row's
    # slot in pg_ids, its in-page offset), every segment's writes
    # (prefill chunks through their slots' tables, decode rows through
    # the masked batch table) in ONE page-form write.
    return _write_rows(pool, scales, pg_ids, route[0], route[1], k[0],
                       None if v is None else v[0])


def _block_copy(pool, scales, src, dst):
    # copy-on-write split: pool[dst[i]] = pool[src[i]], and on
    # quantized pools the page's scales move with its bytes
    if scales is not None:
        scales = scales.at[dst].set(scales[src])
    return pool.at[dst].set(pool[src]), scales


def _set_pages(pool, scales, ids, pages, scale_pages):
    # whole pages [n, 2, H, bs, D] (and their scales) land at block ids
    if scales is not None:
        scales = scales.at[ids].set(scale_pages.astype(scales.dtype))
    return pool.at[ids].set(pages.astype(pool.dtype)), scales


def _prefill_scatter(start_block, n_blocks, block_size, pool, scales,
                     row_cache, blks):
    # row_cache [2, 1, H, S, D] (dense single-row scratch) -> pages
    # [start_block, start_block + n_blocks) of this sequence (a
    # prefix-cache hit skips the shared prefix pages)
    lo = start_block * block_size
    seg = row_cache[:, 0, :, lo:lo + n_blocks * block_size, :]
    two, H, _, D = seg.shape
    seg = seg.reshape(two, H, n_blocks, block_size, D)
    seg = jnp.transpose(seg, (2, 0, 1, 3, 4))      # [n, 2, H, bs, D]
    sseg = None
    if scales is not None:
        seg, sseg = _quant_rows(seg)
    return _set_pages(pool, scales, blks, seg, sseg)


@functools.lru_cache(maxsize=None)
def _pool_program(fn, *static):
    """The jitted program of one pool write (``fn`` with its leading
    ``static`` arguments bound), pool and scales DONATED: one a
    (function, statics) pair, and jax keys the compiled forms by the
    operands' shapes below that."""
    return jax.jit(functools.partial(fn, *static), donate_argnums=(0, 1))


# -- the per-slot state store -----------------------------------------
# A state layer's memory is the last ``n`` rows of its own input ``u``
# (n = kernel - 1 of a causal depthwise convolution), a slot. One call's
# rows and the stored rows are laid side by side (``src``: the store's
# S * n rows, then the call's R rows); two index lists built on the host
# say, for every row, where its j-th predecessor is (an earlier row of
# the same segment, or the slot's stored row) and, for every slot, which
# ``n`` rows of ``src`` are its last ones now (its own, where the call
# did not touch it).

def _conv_mix(state, u, taps, pred, keep):
    """THE short-convolution mix, one body for every view: ``state``
    [S, n, d] (the store), ``u`` [B, L, d] (the call's rows), ``taps``
    [d, n + 1] (column n multiplies the row itself, column n - j its
    j-th predecessor), ``pred`` [n, R] and ``keep`` [S, n] int32 into
    ``src``. Returns (y [B, L, d] float32, the new store). Predecessors
    are read in the STORE's type whether they come from the store or
    from this call, so how a sequence is cut into calls changes no
    number."""
    S, n, d = state.shape
    rows = u.reshape(-1, d).astype(state.dtype)
    src = jnp.concatenate([state.reshape(S * n, d), rows])
    tp = taps.astype(jnp.float32)
    y = rows.astype(jnp.float32) * tp[:, n]
    for j in range(n):
        y = y + src[pred[j]].astype(jnp.float32) * tp[:, n - 1 - j]
    return y.reshape(u.shape), src[keep]


def _state_clear(states, kept):
    """Zero the rows of the slots ``kept`` [S] bool leaves out, in
    every state layer's store (a select, so that a NaN does not
    survive its slot)."""
    return tuple(jnp.where(kept[:, None, None], s, 0) for s in states)


_conv_mix_program = jax.jit(_conv_mix)
_state_clear_program = jax.jit(_state_clear)
# what a plan counts of the segments that write (``slot_state`` gauge)
_STATE_COUNTS = ("rows", "segments", "segments_carried", "prompt_segments",
                 "prompt_segments_carried")


class _StatePlan:
    """One call's index lists for ``_conv_mix`` (host arithmetic, then
    uploaded once for all state layers) and the host counts the
    ``slot_state`` gauge takes."""

    __slots__ = ("pred", "keep", "stats")

    def __init__(self, S: int, n: int, R: int, groups, fresh):
        """``groups``: (slots [G], first rows [G], L, writes [G] bool,
        is_prompt) for G segments of ``L`` rows each; a segment that
        does not write (a masked or empty decode row) reads its own
        slot's rows and leaves them. ``fresh``: slots whose store is
        about to be zeroed (their segment starts a sequence)."""
        pred = np.zeros((n, R), np.int64)
        keep = np.arange(S * n, dtype=np.int64).reshape(S, n)
        stats = dict.fromkeys(_STATE_COUNTS, 0)
        written = np.zeros(S, bool)
        for slots, los, L, writes, is_prompt in groups:
            slots = np.asarray(slots, np.int64)
            los = np.asarray(los, np.int64)
            writes = np.asarray(writes, bool)
            back = np.arange(L)[None, :]                   # [1, L]
            cols = (los[:, None] + back).reshape(-1)
            for j in range(n):
                b = back - (j + 1)
                pred[j, cols] = np.where(
                    b >= 0, S * n + los[:, None] + b,
                    slots[:, None] * n + n + b).reshape(-1)
            ws, wl = slots[writes], los[writes]
            if written[ws].any() or np.unique(ws).shape[0] != ws.shape[0]:
                raise AssertionError(
                    f"state store: slot(s) {ws[written[ws]].tolist()} "
                    f"are written by two segments of one call")
            written[ws] = True
            for m in range(n):
                t = L - n + m
                keep[ws, m] = S * n + wl + t if t >= 0 else ws * n + m + L
            carried = int(ws.shape[0] - np.isin(ws, list(fresh)).sum())
            stats["rows"] += int(ws.shape[0]) * L
            stats["segments"] += int(ws.shape[0])
            stats["segments_carried"] += carried
            if is_prompt:
                stats["prompt_segments"] += int(ws.shape[0])
                stats["prompt_segments_carried"] += carried
        self.pred = jnp.asarray(pred.astype(np.int32))
        self.keep = jnp.asarray(keep.astype(np.int32))
        self.stats = stats


class _StateMixer:
    """What every view adds for a STATE layer: ``mix``. A view is
    bound to its layer's pool (``_pi``) or its layer's store (``_si``),
    never both; ``_state_groups`` is the view's own (see
    ``_StatePlan``)."""

    def _bind(self, cache: "PagedKVCache", layer: int, shard: int):
        self._cache, self._layer, self._shard = cache, layer, int(shard)
        self._pi = cache.pool_index(layer, self._shard)
        self._si = cache.state_index(layer)

    def _need_pool(self):
        if self._pi is None:
            raise ValueError(
                f"layer {self._layer} holds no K/V (a state layer of "
                f"the state store): hand it rows through mix()")

    def _plan_state(self, B: int, L: int) -> _StatePlan:
        c = self._cache
        return _StatePlan(c.max_seqs, c.state[0].shape[1], B * L,
                          self._state_groups(B, L), c._state_fresh)

    def mix(self, u, taps):
        """``u`` [B, L, d] (a jax array, the call's rows in the view's
        own row order) through this layer's short convolution with
        ``taps`` [d, rows + 1]: returns y [B, L, d] float32 and leaves
        each written slot's last rows in the store."""
        c = self._cache
        if self._si is None:
            raise ValueError(f"layer {self._layer} is a K/V layer: it "
                             f"has no rows in the state store")
        if not _trace_clean():
            raise RuntimeError("the state store is not an operand of "
                               "a step program")
        plan = self._plan_state(int(u.shape[0]), int(u.shape[1]))
        if self._si == 0:
            for k, v in plan.stats.items():
                c._state_seen[k] += v
        c._flush_state_resets()
        y, c.state[self._si] = _conv_mix_program(
            c.state[self._si], u, taps, plan.pred, plan.keep)
        return y


def _visible(kpos, qpos, window):
    """The attention mask every fallback path shares: key ``kpos`` is
    visible to query ``qpos`` causally and, on a layer with a sliding
    window W, iff 0 <= qpos - kpos < W."""
    ok = kpos <= qpos
    return ok if window is None else ok & (qpos - kpos < window)


def _group_heads(k_full, v_full, q_heads: int):
    """Gathered K/V [B, S, nkv, D] as the masked sdpa wants them: each
    kv head repeated for the q heads that share it (nothing to do when
    every query head has its own)."""
    g = int(q_heads) // int(k_full.shape[2])
    if g == 1:
        return k_full, v_full
    return (Tensor(jnp.repeat(k_full.data, g, axis=2)),
            Tensor(jnp.repeat(v_full.data, g, axis=2)))


def _gathered(pool, tables, scales, q_heads: int, v_dim=None):
    """What every fallback attends over: the pages ``tables`` names,
    gathered dense (the kernel module's gather, so both paths share one
    layout definition; int8 pages dequantize inside it; a latent page's
    value is its leading ``v_dim`` columns) and grouped for ``q_heads``
    query heads. Returns (k, v, positions gathered)."""
    gargs = (pool, tables) if scales is None else (pool, tables, scales)
    k_full, v_full = apply(gather_pages, gargs,
                           {"v_dim": v_dim} if v_dim else {},
                           op_name="paged_gather")
    k_full, v_full = _group_heads(k_full, v_full, q_heads)
    return k_full, v_full, k_full.shape[1]


def _mask(kpos, qpos, window):
    """The additive float32 mask the sdpa fallbacks pass."""
    return Tensor(jnp.where(_visible(kpos, qpos, window), 0.0, -1e30)
                  .astype(jnp.float32))


def _masked_sdpa(q, k_full, v_full, mask, sm_scale=None):
    """The fallbacks' attention: the dense masked sdpa executable, at
    the cache's own ``sm_scale`` where it has one (a latent pool: the
    scale is the un-absorbed head's, not the row width's)."""
    from ..nn.functional.attention import sdpa_reference
    # what F.scaled_dot_product_attention runs for a masked call
    return sdpa_reference(q, k_full, v_full, mask, scale=sm_scale)


def _attend(q, pool, scales, tables, lens, rows, window, fallback,
            latent=None):
    """THE seam between the model blocks and the paged cache: the one
    place that decides "Pallas kernel or jnp fallback" for a view's
    attention (``device.use_pallas_kernels()``), and the one call of the
    kernel. A view appends its K/V, then hands over what is its own:
    ``q`` [B, L, nh, hd] as the block gave it, the written ``pool`` (and
    int8 ``scales`` or None), its block ``tables`` [n_seq, MB], and its
    ``fallback`` (no arguments), which is what CPU tier-1 runs. ``rows``
    is static: a packed batch gives its ``q_lens`` tuple, and ``lens``
    [n_seq] are then the lengths with those rows in; a uniform call
    (every sequence L rows: decode, verify, a prefill chunk) gives the
    int L and ``lens`` are the rows' START positions, the block's
    ``time_step`` as it came: the program adds L itself, so the view
    pays no dispatch of its own for it. ``latent``: a latent pool's
    ``(v_dim, sm_scale)`` (``PagedKVCache.latent``), else None."""
    if not _device.use_pallas_kernels():
        return fallback()
    launch, shared = _launch(paged_attention_ragged, rows, window, latent)
    args = (pool, q, lens, tables)
    # inside someone's trace (a step program) every layer calls ONE
    # jitted function, so the kernel is traced and lowered to Mosaic
    # once a program instead of once a layer
    return apply(launch if _trace_clean() else shared,
                 args if scales is None else args + (scales,),
                 op_name="paged_attention")


@functools.lru_cache(maxsize=None)
def _launch(kernel, rows, window, latent=None):
    """``_attend``'s call of ``kernel`` for one static ``(rows,
    window, latent)``: the function, and the same function jitted (without
    that, a four-layer step program spends 0.3 s a layer of set-up on
    tracing and lowering the same kernel again, before the compile
    cache is even asked). Named ``fwd`` as every op executable is: the
    launch's name in a profile, ``mosaic:fwd_*``, comes from it."""
    def fwd(p, q_, ln, bta, sc=None):
        if isinstance(rows, int):
            q_lens, kv_lens = (rows,) * bta.shape[0], ln + rows
        else:
            q_lens, kv_lens = rows, ln
        form = {} if latent is None else \
            {"v_dim": latent[0], "sm_scale": latent[1]}
        out = kernel(q_.reshape((-1,) + q_.shape[2:]), p, bta, q_lens,
                     kv_lens, kv_scales=sc, window=window, **form)
        return out.reshape(q_.shape[:-1] + out.shape[-1:])
    return fwd, jax.jit(fwd)


class _LentStep:
    """One model call's cache state, as ``layer_jit.call_with_state``
    takes it (the protocol is written down there): every layer's pool
    (and int8 scale array) DONATED and rebound from the program's
    outputs; ``time_step`` and the arrays every layer's view reads, the
    batch block table of a uniform call or the packed layout's routing
    and descriptors, as plain operands. ``key`` is what the kernel
    launch is keyed by and nothing more: the static ``q_lens`` of a
    packed call, ``(B, L)`` of a uniform one (and the layers' windows,
    which no operand's shape shows). ``writes`` is what one run moves
    (pages, rows; every layer's view appends once), known from the
    call's shapes: a run that hits a program compiled for ANOTHER
    cache of the same geometry (a restored engine, a second engine on
    the model) traces nothing."""

    def __init__(self, x, views, t):
        view = views[0]
        self.views, self.t = views, t
        self.cache = c = view._cache
        if isinstance(view, PagedRaggedView):
            self.holder, rows = view._layout, view._layout.q_lens
            self.names = ("pg_ids", "route", "bt_all", "kv_lens")
        else:
            c.bt_tensor()           # built, so that there is one to lend
            self.holder, rows = c, tuple(x.shape[:2])
            self.names = ("_bt_cached",)
        self.key = (type(view).__name__, rows, c.layer_windows)
        self.writes = len(c.kv_layers) * np.array(
            view._moves(*x.shape[:2]), np.int64)

    def _pools(self):
        c = self.cache
        return (tuple(p.data for p in c.pools),
                tuple(s.data for s in c.scales) if c.quantized else None)

    def rebind(self, donated):
        c = self.cache
        c.pools[:] = [Tensor(p) for p in donated[0]]
        if c.quantized:
            c.scales[:] = [Tensor(s) for s in donated[1]]

    def arrays(self):
        return self._pools(), (unwrap(self.t),) + tuple(
            unwrap(getattr(self.holder, n)) for n in self.names)

    @contextlib.contextmanager
    def lend(self, donated, plain):
        c, h = self.cache, self.holder
        pools, scales = list(c.pools), c.quantized and list(c.scales)
        held = [getattr(h, n) for n in self.names]
        try:
            self.rebind(donated)
            for n, was, a in zip(self.names, held, plain[1:]):
                setattr(h, n, Tensor(a) if isinstance(was, Tensor) else a)
            yield ({"caches": self.views, "time_step": Tensor(plain[0])},
                   self._pools)
        finally:
            c.pools[:] = pools
            if c.quantized:
                c.scales[:] = scales
            for n, was in zip(self.names, held):
                setattr(h, n, was)


def model_call(model, x, views, t, collector=None):
    """ONE model call of the paged engine, ``model(x, caches=views,
    time_step=t)`` -> hidden: the step program where the step is the
    chip's already, the per-op call everywhere else. The step program
    is that same forward traced whole (``layer_jit.call_with_state``):
    all layers, each layer's page-form K/V append and its
    paged-attention launch in one XLA program, the pools donated
    through it (``_LentStep``). Who gets it is what the code can
    observe: the kernel path live (the predicate ``_attend`` asks),
    one shard, and a core whose forward captures whole. Every other
    call, and a shape whose first trace failed, runs per op as before,
    and the ``step_program`` gauge says why (one sample a call:
    ``captured``, ``programs`` compiled so far, and on a 0 the
    reason). A cache with a state store also gives the ``slot_state``
    gauge its sample here (``take_state_stats``)."""
    if not _device.use_pallas_kernels():
        out, why = None, "no_kernel"
    elif views[0]._cache.mp != 1:
        out, why = None, "mp"
    else:
        state = _LentStep(x, views, t)
        out, why = _layer_jit.call_with_state(model, (x,), state)
        if why is None:
            state.cache._written += state.writes
    if out is None:
        out = model(x, caches=views, time_step=t)
    if collector is not None:
        series = {"captured": int(why is None),
                  "programs": _layer_jit.state_programs(model)}
        if why is not None:
            series[why] = 1
        collector.gauge("step_program", series)
        if views[0]._cache.state_layers:
            collector.gauge("slot_state",
                            views[0]._cache.take_state_stats())
    return out[0]


class PagedLayerCache(_StateMixer):
    """One layer's view of the paged cache — the object that rides in
    the ``caches=`` list of FusedMultiTransformer.forward. Duck-typed
    protocol: ``is_paged`` marks it, ``decode(q, k, v, t)`` appends one
    token per row through the block table and returns the attention
    output [B, 1, nh, hd]."""

    is_paged = True

    def __init__(self, cache: "PagedKVCache", layer: int,
                 shard: int = 0):
        self._bind(cache, layer, shard)

    def _state_groups(self, B: int, L: int):
        # one segment a batch row; a row whose table presents as trash
        # (an empty slot, one mid-prefill) leaves its slot's rows alone
        c = self._cache
        live = c.block_tables[:, 0] != 0
        if c._decode_masked is not None:
            live &= ~c._decode_masked
        return [(np.arange(B), np.arange(B) * L, L, live, False)]

    def shard(self, s: int) -> "PagedLayerCache":
        """This layer's view of mp shard ``s`` — the per-shard cache
        object a ShardedServingCore drives with its own head slice of
        q/k/v (replicated metadata, shard-local pages)."""
        return PagedLayerCache(self._cache, self._layer, shard=s)

    @property
    def pool(self) -> Tensor:
        return self._cache.pools[self._pi]

    @property
    def kv_scales(self) -> Optional[Tensor]:
        """Per-page dequantization scales (int8 pools), else None."""
        c = self._cache
        return c.scales[self._pi] if c.quantized else None

    @property
    def shape(self):
        return self.pool.shape

    @property
    def window(self) -> Optional[int]:
        """This layer's sliding window (None: a full layer)."""
        return self._cache.layer_windows[self._layer]

    def positions(self, t, rows: int):
        """Absolute positions int32 [B, rows] of the call's query
        rows: row b's i-th token sits at t[b] + i."""
        return t.reshape(-1, 1) + jnp.arange(rows, dtype=jnp.int32)

    def _moves(self, B: int, L: int) -> Tuple[int, int]:
        """(pages, rows) one ``decode`` of a [B, L] call moves in this
        layer's pool (``_write_pool``'s counts)."""
        return B * _pages_spanned(L, self._cache.block_size), B * L

    def decode(self, q, k, v, t):
        """q/k/v: [B, L, H, D] Tensors (a latent pool: ``k`` is the
        row [B, L, 1, width], ``v`` is None, and the output is
        [B, L, nh, v_dim]; so in every view) (L == 1 is the plain decode
        step; L > 1 is the multi-query speculative-verification step —
        row b's L tokens land at positions t[b] .. t[b]+L-1 and each
        query attends causally up to its own position). t: traced
        int32 [B] per-row START positions (== current length). Appends
        k/v in place (the pool Tensor is rebound) and returns the
        attention output [B, L, nh, hd]. PRECONDITION:
        ``ensure(row, t[row]+L, write_from=t[row])`` for every active
        row — every write position must be covered by the row's block
        table (and shared pages in the write range COW-split).
        ``_attend`` picks the path: the Pallas paged kernel, or a
        pure-jnp gather + the SAME masked-sdpa codepath the dense
        ragged decode uses, so paged and dense CPU decode are
        bit-identical when page capacity == dense max_len."""
        import jax as _jax
        self._need_pool()
        c = self._cache
        B, L = q.shape[0], q.shape[1]
        if B != c.max_seqs:
            raise ValueError(f"batch {B} != cache max_seqs {c.max_seqs}")
        if c.mp > 1 and int(q.shape[2]) != c.heads_per_shard:
            # a full-head call against a sharded pool would scatter
            # H rows into an H/mp page (or, worse, read as GQA in the
            # kernel): fail loudly with the fix
            raise ValueError(
                f"sharded pool (mp={c.mp}) expects the per-shard "
                f"head slice ({c.heads_per_shard} heads), got "
                f"{int(q.shape[2])} — drive a sharded cache through "
                f"a ShardedServingCore (per-shard qkv), not a "
                f"single-chip model")
        if self._pi == 0 and not isinstance(t, _jax.core.Tracer):
            # eager: catch a forgotten ensure() — the write would land
            # in the shared trash block and silently corrupt this
            # row's attention (rows with NO blocks at t == 0 are
            # inactive by convention and write trash on purpose).
            # Layer 0 only: every layer shares t and the tables, and
            # reading t costs a device->host sync on TPU. Under jit t
            # is a tracer and the precondition is the caller's
            # contract.
            tv = np.asarray(t)
            for row in range(B):
                if c._decode_masked is not None and \
                        c._decode_masked[row]:
                    continue  # row presents a trash table this step
                have = len(c.seq_blocks[row])
                pos = int(tv[row])
                if (have and c.blocks_needed(pos + L) > have) or \
                        (not have and pos > 0):
                    raise ValueError(
                        f"decode of {L} token(s) at position {pos} of "
                        f"row {row} is not covered by its {have} "
                        f"allocated block(s); call "
                        f"ensure(row, position+{L}) first")
        bt = c.bt_tensor()
        tt = Tensor(t)
        pages, rows = self._moves(B, L)
        new_pool, new_sc = c._write_pool(
            self._pi, _append_rows, (c.block_size, L), k, v, tt, bt,
            pages=pages, rows=rows)
        return _attend(
            q, new_pool, new_sc, bt, tt, L, self.window,
            lambda: self._sdpa_over_pages(q, t, new_pool, new_sc, bt),
            c.latent)

    def _sdpa_over_pages(self, q, t, pool, scales, bt):
        """The fallback: gather the pages dense, then mirror the dense
        ragged decode branch (same mask, same sdpa op executable). For
        L > 1 the L axis FOLDS INTO THE BATCH axis (virtual rows
        [b*L+i] share slot b's pages, query i at position t[b]+i): the
        sdpa executable then has the exact q-length-1 shape of the
        plain decode step, which is what makes a multi-token
        verification bit-identical to L single steps — an [L, S]
        attention fuses with different reduction grouping than L
        [1, S] attentions (~1 ulp), the same lowering trap as
        scheduler.MIN_PREFILL_SUFFIX_ROWS."""
        W, c = self.window, self._cache
        B, L = q.shape[0], q.shape[1]
        k_full, v_full, S = _gathered(pool, bt, scales, q.shape[2],
                                      c.v_dim)
        if L == 1:
            qpos = (t[:, None, None, None]
                    + jnp.arange(1)[None, None, :, None])
            kpos = jnp.arange(S)[None, None, None, :]
            return _masked_sdpa(q, k_full, v_full, _mask(kpos, qpos, W),
                                c.sm_scale)

        qf = apply(lambda a: a.reshape((B * L, 1) + a.shape[2:]),
                   (q,), op_name="spec_fold_q")
        kf = apply(lambda a: jnp.repeat(a, L, axis=0), (k_full,),
                   op_name="spec_fold_kv")
        vf = apply(lambda a: jnp.repeat(a, L, axis=0), (v_full,),
                   op_name="spec_fold_kv")
        tf = (jnp.repeat(t, L) + jnp.tile(jnp.arange(L, dtype=t.dtype),
                                          B))
        qpos = tf[:, None, None, None]
        kpos = jnp.arange(S)[None, None, None, :]
        out = _masked_sdpa(qf, kf, vf, _mask(kpos, qpos, W), c.sm_scale)
        return apply(lambda a: a.reshape((B, L) + a.shape[2:]),
                     (out,), op_name="spec_unfold")


class PagedPrefillView(_StateMixer):
    """One layer's CHUNKED-PREFILL view of a single slot — the object
    that rides in ``caches=`` for a batch-1 chunk call
    (``PagedKVCache.prefill_views``). Same duck-typed protocol as
    PagedLayerCache (``is_paged`` + ``decode``), but the chunk's C
    rows append STRAIGHT INTO the slot's pages (no dense scratch, no
    scatter pass) and then attend over them with a per-row causal
    mask at absolute positions ``t[0] + i``.

    Numerics contract (what keeps chunked prefill bit-identical to
    dense scratch prefill): the CPU path runs the chunk as ONE
    multi-row masked sdpa — the same executable family as the dense
    prefill branch — and must NOT fold rows into the batch axis the
    way the speculative multi path does: a row computed at q-length 1
    lowers to a GEMV with different accumulation than the same row
    inside a multi-row call (scheduler.MIN_PREFILL_SUFFIX_ROWS), while
    multi-row sdpa results are per-row invariant to BOTH the chunk
    length and the masked key extent. On TPU the ragged kernel serves
    the same contract through the scalar-prefetch block table."""

    is_paged = True

    def __init__(self, cache: "PagedKVCache", layer: int, slot: int,
                 write_start: int = 0, shard: int = 0):
        self._bind(cache, layer, shard)
        self._slot = slot
        # positions below write_start are an adopted (possibly shared)
        # prefix whose pages already hold these exact K/V — recomputed
        # rows there attend but do not write (see _append_rows)
        self._write_start = int(write_start)

    def _state_groups(self, B: int, L: int):
        return [([self._slot], [0], L, [True], True)]

    def shard(self, s: int) -> "PagedPrefillView":
        """This (layer, slot) chunk view of mp shard ``s``."""
        return PagedPrefillView(self._cache, self._layer, self._slot,
                                write_start=self._write_start, shard=s)

    @property
    def pool(self) -> Tensor:
        return self._cache.pools[self._pi]

    @property
    def kv_scales(self) -> Optional[Tensor]:
        c = self._cache
        return c.scales[self._pi] if c.quantized else None

    @property
    def shape(self):
        return self.pool.shape

    @property
    def window(self) -> Optional[int]:
        """This layer's sliding window (None: a full layer)."""
        return self._cache.layer_windows[self._layer]

    def positions(self, t, rows: int):
        """Absolute positions int32 [B, rows] of the call's query
        rows: row b's i-th token sits at t[b] + i."""
        return t.reshape(-1, 1) + jnp.arange(rows, dtype=jnp.int32)

    def decode(self, q, k, v, t):
        """q/k/v: [1, C, H, D] — one prompt chunk for this view's
        slot, starting at absolute position t[0] (traced int32 [1]).
        Appends the chunk's K/V through the slot's block-table row
        (skipping positions below ``write_start``) and returns the
        chunk's attention output [1, C, nh, hd]. PRECONDITION:
        ``ensure(slot, t[0]+C, write_from=t[0], start_block=...)`` —
        every write position covered and COW-split."""
        import jax as _jax
        self._need_pool()
        c = self._cache
        B, C = q.shape[0], q.shape[1]
        if B != 1:
            raise ValueError(
                f"chunk prefill is a batch-1 call, got batch {B}")
        if c.mp > 1 and int(q.shape[2]) != c.heads_per_shard:
            raise ValueError(
                f"sharded pool (mp={c.mp}) expects the per-shard "
                f"head slice ({c.heads_per_shard} heads), got "
                f"{int(q.shape[2])} — drive a sharded cache through "
                f"a ShardedServingCore")
        if self._pi == 0 and not isinstance(t, _jax.core.Tracer):
            pos = int(np.asarray(t).reshape(-1)[0])
            have = len(c.seq_blocks[self._slot])
            if c.blocks_needed(pos + C) > have:
                raise ValueError(
                    f"prefill chunk [{pos}, {pos + C}) of slot "
                    f"{self._slot} is not covered by its {have} "
                    f"allocated block(s); call ensure() first")
        bt = c.bt_row_tensor(self._slot)
        tt = Tensor(t)
        ws = Tensor(jnp.asarray([self._write_start], jnp.int32))
        new_pool, new_sc = c._write_pool(
            self._pi, _append_rows, (c.block_size, C), k, v, tt, bt, ws,
            pages=_pages_spanned(C, c.block_size) + 1, rows=C)
        return _attend(
            q, new_pool, new_sc, bt, tt, C, self.window,
            lambda: self._sdpa_over_pages(q, t, new_pool, new_sc, bt),
            c.latent)

    def _sdpa_over_pages(self, q, t, pool, scales, bt):
        """The fallback: gather the slot's pages dense and run the
        chunk as ONE multi-row masked sdpa (see class docstring; the
        mask mirrors the dense prefill branch's construction)."""
        c = self._cache
        k_full, v_full, S = _gathered(pool, bt, scales, q.shape[2],
                                      c.v_dim)
        qpos = t[0] + jnp.arange(q.shape[1])[:, None]
        kpos = jnp.arange(S)[None, :]
        return _masked_sdpa(q, k_full, v_full,
                            _mask(kpos, qpos, self.window), c.sm_scale)


class _RaggedLayout:
    """Host-side descriptors for ONE mixed ragged model call, shared
    by every layer's PagedRaggedView: the packed append routing
    (blk/off per row), the per-sequence (q_len, kv_len, block-table
    row) descriptors the kernel consumes, and the segment spans the
    CPU path decomposes along. Built once per launch from the cache's
    CURRENT tables — the caller must have ensure()d coverage and set
    the decode mask first."""

    __slots__ = ("segs", "q_lens", "pg_ids", "route", "kv_lens",
                 "bt_all", "total_rows", "n_pages",
                 "blk_np", "off_np", "pos_np", "pg_ids_np", "pg_slot_np",
                 "kv_lens_np", "_pos", "_cache", "decode_live",
                 "state_plan")

    def __init__(self, cache: "PagedKVCache", segments):
        bs = cache.block_size
        tbl = cache.block_tables
        masked_tbl = tbl
        if cache._decode_masked is not None and \
                cache._decode_masked.any():
            masked_tbl = tbl.copy()
            masked_tbl[cache._decode_masked] = 0
        # the decode rows that are some slot's: what the state store
        # lets a decode segment write (the others present trash tables)
        self.decode_live = masked_tbl[:, 0] != 0
        self.state_plan: Optional[_StatePlan] = None
        self.segs: List[tuple] = []
        q_lens: List[int] = []
        kv_lens: List[int] = []
        bt_rows: List[np.ndarray] = []
        blk: List[np.ndarray] = []
        off: List[np.ndarray] = []
        rowpos: List[np.ndarray] = []   # every packed row's position
        lo = 0
        for seg in segments:
            kind = seg[0]
            if kind == "prefill":
                _, slot, start, length, write_start = seg
                pos = np.arange(start, start + length)
                b = tbl[slot][pos // bs]
                # adopted shared-prefix positions route to trash: the
                # pages already hold these exact values and may be
                # shared (same rule as _append_rows)
                blk.append(np.where(pos >= write_start, b, 0))
                off.append(pos % bs)
                rowpos.append(pos)
                q_lens.append(int(length))
                kv_lens.append(int(start) + int(length))
                bt_rows.append(tbl[slot])
                self.segs.append(("prefill", lo, lo + length, slot,
                                  int(start)))
                lo += length
            elif kind == "decode":
                _, lens, L = seg
                if L < 1:
                    raise ValueError("decode segments carry >= 1 "
                                     "query row per slot")
                lens = np.asarray(lens, np.int64)
                B = lens.shape[0]
                # masked rows (mid-prefill / fresh slots riding along
                # at their real lens) may sit at page capacity: clamp
                # their table column — they present all-trash rows, so
                # any in-range column lands the write in block 0, and
                # covered (unmasked) rows are never clamped
                cols = masked_tbl.shape[1]
                if L == 1:
                    b = masked_tbl[np.arange(B),
                                   np.minimum(lens // bs, cols - 1)]
                    blk.append(b)
                    off.append(lens % bs)
                    rowpos.append(lens)
                else:
                    # multi-query verify rows: slot b's L tokens land
                    # at positions lens[b] .. lens[b]+L-1 through the
                    # DECODE-MASKED table (masked rows write trash),
                    # packed row-major as [b*L + i]
                    pos = lens[:, None] + np.arange(L)[None, :]
                    b = masked_tbl[np.arange(B)[:, None],
                                   np.minimum(pos // bs, cols - 1)]
                    blk.append(b.reshape(-1))
                    off.append((pos % bs).reshape(-1))
                    rowpos.append(pos.reshape(-1))
                q_lens.extend([L] * B)
                kv_lens.extend((lens + L).tolist())
                bt_rows.extend(masked_tbl)
                self.segs.append(("decode", lo, lo + B * L,
                                  lens.astype(np.int32), L))
                lo += B * L
            else:
                raise ValueError(f"unknown ragged segment kind {kind!r}")
        self.total_rows = lo
        self.q_lens = tuple(q_lens)
        # host copies of the scatter routing, kept for the compiled
        # sharded step: it re-packs them with bucket-pad rows (routed
        # to the trash block) BEFORE feeding them in as operands, so
        # the padding never touches device data
        self.blk_np = np.concatenate(blk).astype(np.int32)
        self.off_np = np.concatenate(off).astype(np.int32)
        self.pos_np = np.concatenate(rowpos).astype(np.int32)
        self.kv_lens_np = np.asarray(kv_lens, np.int32)
        self._pos = None
        self.pg_ids_np, self.pg_slot_np = self._page_list(bs)
        self.n_pages = int(self.pg_ids_np.shape[0])
        self.pg_ids = jnp.asarray(self.pg_ids_np)
        self.route = jnp.asarray(np.stack([self.pg_slot_np, self.off_np]))
        self.kv_lens = Tensor(jnp.asarray(kv_lens, jnp.int32))
        self.bt_all = Tensor(jnp.asarray(np.stack(bt_rows), jnp.int32))
        self._cache = cache

    def _page_list(self, bs: int):
        """The page form of the append routing: (pg_ids [P], slot [R]).
        Slot 0 is the trash block, where every row routed to trash
        lands; then each sequence owns ``_pages_spanned(q_len)`` slots,
        the real pages its rows write in table order and the trash
        block in the slots it does not need. ``P`` is a function of
        ``q_lens`` alone, so the append's program is keyed by nothing
        the attention launch is not keyed by already. No real page may
        occur twice: its second write-back would undo the first."""
        spans = [_pages_spanned(ql, bs) for ql in self.q_lens]
        base = np.concatenate([[1], 1 + np.cumsum(spans)]).astype(np.int64)
        pg_ids = np.zeros(int(base[-1]), np.int32)
        seq_of_row = np.repeat(np.arange(len(spans)), self.q_lens)
        page = self.pos_np.astype(np.int64) // bs
        first = np.full(len(spans), np.iinfo(np.int64).max)
        real = self.blk_np != 0
        np.minimum.at(first, seq_of_row[real], page[real])
        slot = np.where(real, base[seq_of_row] + page - first[seq_of_row],
                        0)
        pg_ids[slot[real]] = self.blk_np[real]
        ids = pg_ids[pg_ids != 0]
        if np.unique(ids).shape[0] != ids.shape[0]:
            raise AssertionError(
                f"ragged append: a page is written by two sequences of "
                f"one launch (pages {sorted(ids.tolist())}): the write "
                f"range must be COW-split first (ensure())")
        return pg_ids, slot.astype(np.int32)

    def positions(self):
        """int32 [1, total_rows] on the device: every packed row's
        absolute position (uploaded once a layout, on first use — a
        model without a position encoding never asks)."""
        if self._pos is None:
            if not _trace_clean():
                # a constant of THIS layout in a program other layouts
                # of the same q_lens run
                raise RuntimeError("the packed rows' positions are not "
                                   "an operand of the step program")
            self._pos = jnp.asarray(self.pos_np[None])
        return self._pos

    def window_pages(self, window: int) -> Tuple[int, int]:
        """(pages in the rows' contexts, pages of them wholly behind
        ``window``), summed over the layout's sequences: what a
        sliding layer's launch may skip. Host arithmetic over the
        lengths, for the collector's ``paged_attn`` gauge."""
        bs = self._cache.block_size
        kv = self.kv_lens_np.astype(np.int64)
        q = np.asarray(self.q_lens, np.int64)
        first = kv - q                                   # first query
        # a decode row at position 0 is a slot with nothing in it
        live = (first > 0) | (q > 1)
        return (int((-(-kv // bs))[live].sum()),
                int((np.maximum(first - window + 1, 0) // bs)[live].sum()))

    def launch_plan(self):
        """The paged-attention launch this layout makes on the chip, a
        layer and a shard (``launch_plan`` of the kernel module, at one
        query head a kv head as the serving cores have): host
        arithmetic over shapes, for the collector's ``paged_attn``
        gauge."""
        c = self._cache
        g = c.num_heads // c.num_kv_heads
        tile_q = resolve_tile_q(self.q_lens, g=g)
        return launch_plan(
            sum(-(-ql // tile_q) for ql in self.q_lens),
            c.kv_heads_per_shard, tile_q * g, c.max_blocks_per_seq,
            c.block_size, c.head_dim, c.pools[0].data.dtype.itemsize,
            quantized=c.quantized, v_dim=c.v_dim)

    def live_steps(self, plan) -> int:
        """The grid steps ``plan``'s launch walks on a layer WITHOUT a
        window (the kernel module's count of its own work list, on the
        layout's host lengths; a sliding layer walks fewer,
        ``window_pages``). For the ``paged_attn`` gauge."""
        c = self._cache
        return live_steps(plan, self.q_lens, self.kv_lens_np,
                          c.block_size, g=c.num_heads // c.num_kv_heads)


class PagedRaggedView(_StateMixer):
    """One layer's MIXED-BATCH view: the object that rides in
    ``caches=`` for the scheduler's ragged step — prefill chunks of
    several slots AND the fused decode rows packed into one
    [1, total_rows, d] model call. Same duck-typed protocol as
    PagedLayerCache (``is_paged`` + ``decode``): the packed K/V append
    is ONE page-form write through the precomputed routing, and the
    attention is ONE ``paged_attention_ragged`` launch on the kernel path — the
    dispatch-count collapse this view exists for.

    Numerics contract (CPU bit-identity — the folding rules hoisted
    from PagedLayerCache/PagedPrefillView): on the CPU fallback the
    packed batch DECOMPOSES back into exactly the executables the
    per-phase paths run — each prefill segment one multi-row masked
    sdpa over its slot's gathered pages (never folded to 1-row calls:
    the GEMV trap of scheduler.MIN_PREFILL_SUFFIX_ROWS), the decode
    rows one batch-of-1-row sdpa over the masked batch table (the
    plain decode executable) — so a ragged step's streams are
    BIT-IDENTICAL to the per-phase launches. The non-attention ops
    (LN/QKV/FFN) ride the packed row batch, which is safe by the same
    established invariance chunked prefill rests on: per-row results
    of multi-row calls do not depend on how many rows share the
    call."""

    is_paged = True

    def __init__(self, cache: "PagedKVCache", layer: int,
                 layout: _RaggedLayout, shard: int = 0):
        self._bind(cache, layer, shard)
        self._layout = layout

    def _plan_state(self, B: int, L: int) -> _StatePlan:
        # built once a layout: every state layer of the call shares it
        lay = self._layout
        if lay.state_plan is None:
            lay.state_plan = super()._plan_state(B, L)
        return lay.state_plan

    def _state_groups(self, B: int, L: int):
        lay = self._layout
        if (B, L) != (1, lay.total_rows):
            raise ValueError(f"ragged call expects [1, {lay.total_rows}, "
                             f"d], got [{B}, {L}, d]")
        groups = []
        for seg in lay.segs:
            if seg[0] == "prefill":
                groups.append(([seg[3]], [seg[1]], seg[2] - seg[1],
                               [True], True))
            else:
                n = seg[3].shape[0]
                groups.append((np.arange(n), seg[1] + np.arange(n) * seg[4],
                               seg[4], lay.decode_live, False))
        return groups

    def shard(self, s: int) -> "PagedRaggedView":
        """This layer's ragged view of mp shard ``s`` — the SAME
        layout object rides along (the routing descriptors are
        replicated metadata, shard-invariant by construction)."""
        return PagedRaggedView(self._cache, self._layer, self._layout,
                               shard=s)

    @property
    def pool(self) -> Tensor:
        return self._cache.pools[self._pi]

    @property
    def kv_scales(self) -> Optional[Tensor]:
        c = self._cache
        return c.scales[self._pi] if c.quantized else None

    @property
    def shape(self):
        return self.pool.shape

    @property
    def window(self) -> Optional[int]:
        """This layer's sliding window (None: a full layer)."""
        return self._cache.layer_windows[self._layer]

    def positions(self, t, rows: int):
        """Absolute positions int32 [1, R] of the packed rows, from
        the layout (``t`` says nothing about a packed batch)."""
        return self._layout.positions()

    def _moves(self, *_) -> Tuple[int, int]:
        """(pages, rows) one ``decode`` moves in this layer's pool:
        the layout's page list and its packed rows."""
        return self._layout.n_pages, self._layout.total_rows

    def decode(self, q, k, v, t):
        """q/k/v: [1, R, H, D] — the packed mixed batch. ``t`` is
        ignored: the layout carries every row's absolute position.
        PRECONDITION: every segment's write range is covered and
        COW-split (the scheduler's planning pass ensure()s chunk by
        chunk) and the decode mask is set."""
        self._need_pool()
        c = self._cache
        lay = self._layout
        if q.shape[0] != 1 or q.shape[1] != lay.total_rows:
            raise ValueError(
                f"ragged call expects [1, {lay.total_rows}, H, D], "
                f"got {tuple(q.shape)}")
        if c.mp > 1 and int(q.shape[2]) != c.heads_per_shard:
            raise ValueError(
                f"sharded pool (mp={c.mp}) expects the per-shard "
                f"head slice ({c.heads_per_shard} heads), got "
                f"{int(q.shape[2])} — drive a sharded cache through "
                f"a ShardedServingCore")
        pages, rows = self._moves()
        new_pool, new_sc = c._write_pool(
            self._pi, _ragged_append, (), k, v, lay.pg_ids, lay.route,
            pages=pages, rows=rows)
        return _attend(
            q, new_pool, new_sc, lay.bt_all, lay.kv_lens, lay.q_lens,
            self.window,
            lambda: self._sdpa_by_segment(q, new_pool, new_sc), c.latent)

    def _sdpa_by_segment(self, q, pool, scales):
        """The fallback: decompose into the per-phase executables (see
        class docstring) and re-pack the outputs in row order."""
        c = self._cache
        W = self.window
        outs = []
        for seg in self._layout.segs:
            kind, lo, hi = seg[0], seg[1], seg[2]
            if kind == "prefill":
                slot, start = seg[3], seg[4]
                qs = Tensor(q.data[:, lo:hi])
                k_full, v_full, S = _gathered(
                    pool, c.bt_row_tensor(slot), scales, q.shape[2],
                    c.v_dim)
                qpos = start + jnp.arange(hi - lo)[:, None]
                kpos = jnp.arange(S)[None, :]
                out = _masked_sdpa(qs, k_full, v_full,
                                   _mask(kpos, qpos, W), c.sm_scale)
                outs.append(out.data[0])
                continue
            lens, L = seg[3], seg[4]
            k_full, v_full, S = _gathered(pool, c.bt_tensor(), scales,
                                          q.shape[2], c.v_dim)
            tj = jnp.asarray(lens, jnp.int32)
            kpos = jnp.arange(S)[None, None, None, :]
            if L == 1:
                qd = Tensor(q.data[0, lo:hi][:, None])  # [B,1,H,D]
                qpos = (tj[:, None, None, None]
                        + jnp.arange(1)[None, None, :, None])
            else:
                # multi-query verify rows: fold the L axis into the
                # batch axis, exactly the PagedLayerCache L > 1
                # fallback — same q-length-1 sdpa executable, so a
                # packed verify stays bit-identical to the per-phase
                # step_multi call
                B = (hi - lo) // L
                qd = Tensor(q.data[0, lo:hi][:, None])  # [B*L,1,..]
                k_full = Tensor(jnp.repeat(k_full.data, L, axis=0))
                v_full = Tensor(jnp.repeat(v_full.data, L, axis=0))
                tf = (jnp.repeat(tj, L)
                      + jnp.tile(jnp.arange(L, dtype=jnp.int32), B))
                qpos = tf[:, None, None, None]
            out = _masked_sdpa(qd, k_full, v_full, _mask(kpos, qpos, W),
                               c.sm_scale)
            outs.append(out.data[:, 0])
        return Tensor(jnp.concatenate(outs, axis=0)[None])


class PagedKVCache:
    """Per-layer block pools + one block allocator + per-sequence block
    tables. ``views`` is the list consumed as ``caches=`` by the fused
    decoder; allocation/free/fork are host-side (numpy free list), the
    pool writes are page-granular jnp programs on a donated pool
    (``_write_pool``)."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 block_size: int, num_blocks: int, max_seqs: int,
                 max_blocks_per_seq: Optional[int] = None,
                 dtype: str = "float32", prefix_cache: bool = False,
                 mp: int = 1, shard_devices=None,
                 num_kv_heads: Optional[int] = None,
                 layer_windows=None, v_dim: Optional[int] = None,
                 sm_scale: Optional[float] = None, layer_state=None):
        import paddle_tpu as paddle
        self.num_layers = int(num_layers)
        # THE STATE STORE (``layer_state[i]``: None for a K/V layer, or
        # ``(rows, width)`` for a layer that holds NO K/V and keeps its
        # last ``rows`` input rows of ``width`` a slot instead; the
        # module docstring has the rules). ``kv_layers`` are the layers
        # that get a pool, ``state_layers`` those that get a store;
        # views, windows and ``num_layers`` count every layer.
        layer_state = tuple(layer_state or (None,) * self.num_layers)
        if len(layer_state) != self.num_layers:
            raise ValueError(
                f"layer_state has {len(layer_state)} entries for "
                f"{self.num_layers} layers")
        self.layer_state = tuple(
            None if s is None else (int(s[0]), int(s[1]))
            for s in layer_state)
        self.kv_layers = tuple(i for i, s in enumerate(self.layer_state)
                               if s is None)
        self.state_layers = tuple(i for i, s in enumerate(self.layer_state)
                                  if s is not None)
        if self.state_layers:
            forms = {self.layer_state[i] for i in self.state_layers}
            if len(forms) != 1 or min(forms.pop()) < 1:
                raise ValueError(
                    f"the state store holds one (rows, width) >= 1 for "
                    f"all its layers, got {self.layer_state}")
            if not self.kv_layers:
                raise ValueError(
                    "a paged cache needs a layer that holds K/V: the "
                    "block tables are its slots' lengths too")
            if prefix_cache:
                raise ValueError(
                    "prefix_cache with a state store: an adopted prefix "
                    "has no stored state to start from (the state store "
                    "keeps a slot's LAST rows, none at block boundaries)")
            if int(mp) != 1:
                raise ValueError(
                    "the state store is not split over mp shards: "
                    "serve a model with state layers at mp 1")
        self.num_heads = int(num_heads)
        # GROUPED KV HEADS: the pool stores ``num_kv_heads`` heads a
        # position (== num_heads unless the model shares each kv head
        # among num_heads / num_kv_heads query heads). Pools, scales,
        # append ops, the byte model and the snapshot / slice payloads
        # size by it; ``num_heads`` stays the query width the views
        # check incoming q against.
        self.num_kv_heads = int(num_heads if num_kv_heads is None
                                else num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} is not a multiple of "
                f"num_kv_heads {self.num_kv_heads}")
        # SLIDING WINDOWS: ``layer_windows[i]`` is layer i's window W
        # (query i sees key j iff 0 <= i - j < W) or None for a full
        # layer. Every view of a layer masks by it (kernel and
        # fallbacks alike). One block table per slot still serves all
        # layers: pages behind a window are skipped, not freed.
        self.layer_windows = tuple(
            None if w is None else int(w)
            for w in (layer_windows or (None,) * self.num_layers))
        if len(self.layer_windows) != self.num_layers:
            raise ValueError(
                f"layer_windows has {len(self.layer_windows)} entries "
                f"for {self.num_layers} layers")
        self.head_dim = int(head_dim)
        # THE LATENT FORM (``v_dim``; absorbed multi-head latent
        # attention): a page holds ONE row a position and kv head,
        # [num_blocks, 1, H, bs, head_dim] with ``head_dim`` the row's
        # stored width (kv_lora_rank + the shared rope head), which IS
        # the key; its leading ``v_dim`` columns are the value, so there
        # is no V plane, and the views take ``decode(q, row, None, t)``
        # and return ``v_dim`` columns a head. ``sm_scale`` is the
        # attention's scale (the un-absorbed head's, which no shape here
        # shows). Pools, appends, the byte model and the snapshot /
        # slice payloads size by ``planes``; the allocator, tables,
        # refcounts, the chain-hash index and admission never see it.
        self.v_dim = None if v_dim is None else int(v_dim)
        self.sm_scale = None if sm_scale is None else float(sm_scale)
        self.planes = 2 if self.v_dim is None else 1
        if self.v_dim is not None:
            if not 0 < self.v_dim <= self.head_dim or sm_scale is None:
                raise ValueError(
                    f"a latent pool takes 0 < v_dim <= the row width "
                    f"{self.head_dim} and an sm_scale; got v_dim={v_dim}, "
                    f"sm_scale={sm_scale}")
            if str(dtype) == "int8":
                raise ValueError(
                    "int8 latent pages are not built (ROADMAP M3): one "
                    "scale a row would cover the normed latent and the "
                    "rotated rope head alike; use a float kv_dtype")
            if int(mp) != 1:
                raise ValueError(
                    "a latent pool has one kv head and does not split "
                    "over mp shards (ROADMAP M3): serve it at mp 1")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_seqs = int(max_seqs)
        # TENSOR-PARALLEL SHARDING (``mp`` > 1): the pool is
        # partitioned over attention heads — shard s stores
        # [num_blocks, 2, H/mp, bs, D] (pools[layer * mp + s]), the
        # head slice [s*H/mp, (s+1)*H/mp). EVERYTHING ELSE in this
        # class — allocator, block tables, refcounts, chain-hash
        # index, tenant charges, decode mask — is host-side metadata
        # REPLICATED across shards: block ids and lifecycle are
        # shard-invariant, so admission, quotas, WFQ, prefix hashing,
        # COW, snapshots and the journal run byte-for-byte unchanged.
        # Each shard's pages live on its own device
        # (``shard_devices``, parallel/mesh.py serving_shard_devices)
        # and only the per-shard model (ShardedServingCore) writes /
        # reads them, with its own head-slice of q/k/v. The SNAPSHOT
        # and MIGRATION wire formats stay CANONICAL (full-head pages,
        # the mp=1 layout): shards concatenate on the head axis going
        # out and re-slice coming in, which is what makes snapshots
        # and kv_slices portable across mesh widths (mp=N <-> mp=1).
        self.mp = int(mp)
        if self.mp < 1:
            raise ValueError(f"mp must be >= 1, got {mp}")
        if self.num_heads % self.mp or self.num_kv_heads % self.mp:
            raise ValueError(
                f"num_heads {self.num_heads} / num_kv_heads "
                f"{self.num_kv_heads} must divide evenly over "
                f"mp={self.mp} tensor-parallel shards")
        if self.mp > 1 and shard_devices is None:
            from ..parallel.mesh import serving_shard_devices
            shard_devices = serving_shard_devices(self.mp)
        if shard_devices is not None and len(shard_devices) < self.mp:
            raise ValueError(
                f"need {self.mp} shard devices, got "
                f"{len(shard_devices)}")
        self.shard_devices = (list(shard_devices[:self.mp])
                              if shard_devices is not None else None)
        if max_blocks_per_seq is None:
            max_blocks_per_seq = self.num_blocks - 1
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.dtype = dtype
        # QUANTIZED POOLS (``dtype="int8"``): payload pages hold int8
        # and every page carries per-(position, head) dequantization
        # scales in ``self.scales`` — allocator metadata that moves
        # with the page through COW copies, snapshots and restores.
        # Quantization happens at page-write time inside the append
        # writes (_write_rows); every read path dequantizes (the ragged kernel in-register via scalar
        # prefetch, the jnp fallbacks inside gather_pages). See the
        # module-level note above _quant_rows for why scales are
        # per-row: it is what keeps the quantized payload a pure
        # function of the token stream, so chunking cannot change the
        # bytes and prefix-hash adoption stays exact.
        self.quantized = (str(dtype) == "int8")
        self.prefix_cache = bool(prefix_cache)
        # chained-hash block index (prefix caching): both maps stay in
        # lockstep — a block is indexed iff hash_to_block[h] == b and
        # block_hash[b] == h. Reclaim drops both via _on_reclaim.
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        self.allocator = BlockAllocator(self.num_blocks,
                                        on_reclaim=self._on_reclaim)
        # actionable allocator errors: BlockOOM carries the occupancy
        # breakdown (string AND the structured pool_occupancy dict on
        # .details), ref/free misuse names the owning slot(s)
        self.allocator.context = self._pool_context
        self.allocator.context_data = self.pool_occupancy
        self.allocator.describe = self._describe_block
        # content fingerprints for the "never written in place" audit
        # (check_invariants): blocks that must be immutable — shared
        # (refcount >= 2), hash-indexed, or parked cached-free — are
        # hashed at audit time and re-verified while they stay in that
        # state; fork/adopt re-shares drop the entry (fresh epoch)
        self._audit_fp: Dict[int, bytes] = {}
        # pool storage: ``pools[layer * mp + shard]`` — for mp == 1
        # exactly the old one-entry-per-layer list (shape and device
        # placement untouched), for mp > 1 each entry is one shard's
        # head slice committed to its shard device. The flat list
        # keeps every uniform whole-pool pass (COW copy, snapshot
        # pull, deep-audit fingerprint) working unchanged over all
        # layer x shard entries.
        Hs = self.kv_heads_per_shard
        self.pools: List[Tensor] = [
            self._place(paddle.zeros(
                [self.num_blocks, self.planes, Hs, self.block_size,
                 self.head_dim], dtype=dtype), pi)
            for pi in range(len(self.kv_layers) * self.mp)]
        # per-page dequantization scales (int8 pools only):
        # [num_blocks, 2, heads/mp, block_size] float32 per
        # layer x shard — zero-init dequantizes to exact zeros,
        # matching a zeroed pool
        self.scales: Optional[List[Tensor]] = [
            self._place(paddle.zeros(
                [self.num_blocks, 2, Hs, self.block_size],
                dtype="float32"), pi)
            for pi in range(len(self.kv_layers) * self.mp)] \
            if self.quantized else None
        # the state store: one [max_seqs, rows, width] array a state
        # layer, in the pool's type (int8 pools: the rows stay float32),
        # zeros until written; ``_state_fresh`` are the slots admitted
        # since the last model call, whose rows the next ``mix`` zeroes
        # first (``_flush_state_resets``)
        self.state: List = [
            jnp.zeros((self.max_seqs,) + self.layer_state[i],
                      jnp.float32 if self.quantized
                      else self.pools[0].data.dtype)
            for i in self.state_layers]
        self._state_fresh: set = set()
        self._state_seen = dict.fromkeys(_STATE_COUNTS + ("slots_reset",), 0)
        # all entries at the trash block until allocated
        self.block_tables = np.zeros(
            (self.max_seqs, self.max_blocks_per_seq), np.int32)
        self.seq_blocks: List[List[int]] = [[] for _ in
                                            range(self.max_seqs)]
        self.views = [PagedLayerCache(self, i)
                      for i in range(self.num_layers)]
        self._bt_cached: Optional[Tensor] = None
        self._bt_rows_cached: Dict[int, Tensor] = {}
        # rows whose table presents as ALL-TRASH to the fused decode
        # step (mid-prefill slots: they own real pages, but a decode
        # append at lens==0 through them would corrupt position 0)
        self._decode_masked: Optional[np.ndarray] = None
        self.peak_blocks_used = 0
        # pages / rows the pool writes moved since the last
        # take_write_stats() (host counts, from the writes' shapes)
        self._written = np.zeros(2, np.int64)
        # multi-tenant attribution (scheduler.py): which tenant each
        # slot is serving, and the per-tenant block CHARGE. The charge
        # policy is ONE CHARGE PER TABLE REFERENCE — a block shared by
        # k slots charges each sharer's tenant 1 (not 1/k, not
        # owner-only), so a tenant's charge is a pure function of ITS
        # OWN slots' tables: no neighbor's adopt/release/preempt can
        # ever move it (fractional charging would raise your charge
        # when a sharer releases; owner-pays would transfer a block
        # onto you when the owner leaves — both are cross-tenant
        # interference channels). Ground truth audited by
        # check_invariants: charge[t] == sum of len(seq_blocks[s])
        # over slots with seq_tenant[s] == t, and the total equals the
        # allocator's total refcount over usable blocks.
        self.seq_tenant: List[Optional[str]] = [None] * self.max_seqs
        self._tenant_charge: Dict[Optional[str], int] = {}

    # -- construction -------------------------------------------------
    @classmethod
    def for_model(cls, model, block_size, num_blocks, max_seqs,
                  max_blocks_per_seq=None, dtype="float32",
                  prefix_cache=False):
        """Build a pool matching ``model``'s geometry — INCLUDING its
        tensor-parallel layout: a ShardedServingCore carries ``mp``
        and ``shard_devices``, so the engines get a matching sharded
        pool without a single signature change."""
        return cls(model.num_layers, model.num_heads, model.head_dim,
                   block_size, num_blocks, max_seqs,
                   max_blocks_per_seq=max_blocks_per_seq, dtype=dtype,
                   prefix_cache=prefix_cache,
                   mp=getattr(model, "mp", 1),
                   shard_devices=getattr(model, "shard_devices", None),
                   num_kv_heads=getattr(model, "num_kv_heads", None),
                   layer_windows=getattr(model, "layer_windows", None),
                   layer_state=getattr(model, "layer_state", None),
                   **(getattr(model, "latent_cache", None) or {}))

    def _place(self, t: Tensor, pi: int) -> Tensor:
        """Commit a pool/scale entry to its shard's device (mp > 1);
        the mp == 1 path is byte-for-byte the old single-chip one —
        uncommitted, exactly as paddle.zeros made it."""
        if self.mp == 1 or self.shard_devices is None:
            return t
        import jax as _jax
        dev = self.shard_devices[pi % self.mp]
        return Tensor(_jax.device_put(t.data, dev))

    # -- geometry -----------------------------------------------------
    @property
    def heads_per_shard(self) -> int:
        """Query heads each mp shard drives (== num_heads at mp 1);
        shard s holds heads [s*H/mp, (s+1)*H/mp)."""
        return self.num_heads // self.mp

    @property
    def kv_heads_per_shard(self) -> int:
        """K/V heads each mp shard's pool stores."""
        return self.num_kv_heads // self.mp

    @property
    def latent(self) -> Optional[Tuple[Optional[int], float]]:
        """``(v_dim, sm_scale)`` of a latent pool, ``(None, sm_scale)``
        of a K/V pool whose attention scale is not its stored width's
        (two heads a lane row), None of a plain K/V one: what
        ``_attend`` keys the launch by."""
        return None if self.sm_scale is None \
            else (self.v_dim, self.sm_scale)

    def _latent_geometry(self) -> dict:
        """The keys a latent pool adds to a snapshot's or a slice's
        geometry (a K/V pool adds none: its records read as before)."""
        out = {} if self.v_dim is None else {"v_dim": self.v_dim}
        if self.sm_scale is not None:
            out["sm_scale"] = self.sm_scale
        return out

    def pool_index(self, layer: int, shard: int = 0) -> Optional[int]:
        """Index of (layer, shard)'s entry in the flat ``pools`` /
        ``scales`` lists; None for a state layer, which has none."""
        if self.layer_state[layer] is not None:
            return None
        return self.kv_layers.index(layer) * self.mp + shard

    def state_index(self, layer: int) -> Optional[int]:
        """Index of ``layer``'s array in ``state``; None for a K/V
        layer."""
        if self.layer_state[layer] is None:
            return None
        return self.state_layers.index(layer)

    def state_bytes(self) -> int:
        """Bytes of the state store: every state layer's rows of every
        slot, live or not (it is allocated whole)."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self.state)

    def _flush_state_resets(self) -> None:
        """Zero the rows of the slots admitted since the last call, in
        every state layer, in one program (``mix`` calls this first; so
        does ``snapshot``)."""
        if not self._state_fresh:
            return
        kept = np.ones(self.max_seqs, bool)
        kept[sorted(self._state_fresh)] = False
        self._state_seen["slots_reset"] += len(self._state_fresh)
        self._state_fresh.clear()
        self.state[:] = _state_clear_program(tuple(self.state),
                                             jnp.asarray(kept))

    def take_state_stats(self) -> dict:
        """What the state store saw since the last call, and reset:
        host counts for the collector's ``slot_state`` gauge (one
        sample a model call). ``rows`` written through ``mix`` a state
        layer, in ``segments`` (a prompt chunk, a decode row), of which
        ``segments_carried`` began from stored rows (not a fresh
        slot's zeros); the same two for prompt chunks alone;
        ``slots_reset``; and the store's ``state_bytes``."""
        out = dict(self._state_seen, state_bytes=self.state_bytes())
        for k in self._state_seen:
            self._state_seen[k] = 0
        return out

    def _refuse_with_state(self, what: str) -> None:
        if self.state_layers:
            raise ValueError(
                f"{what} with a state store: the state store keeps each "
                f"slot's LAST rows only, so there is no state to share, "
                f"ship or roll back to at a block boundary")

    def _write_pool(self, pi: int, fn, static, *args, pages: int,
                    rows: int = 0) -> Tuple[Tensor, Optional[Tensor]]:
        """Run one pool write, ``fn(*static, pool, scales, *args)``, on
        entry ``pi`` and rebind ``pools[pi]`` (and ``scales[pi]``) to
        what it returns. The pool and its scales are DONATED: the
        arrays that were bound are deleted by the call (on the CPU
        backend too), so whoever reads a pool takes
        ``cache.pools[i].data`` at the moment it reads. Inside someone
        else's trace the write composes into their program instead.
        ``pages`` / ``rows`` are what the write moves, for the
        ``pool_write`` gauge (``take_write_stats``): counted here for a
        write that runs now, and by whoever runs the program for one
        that is traced into it (``model_call``; a trace is not a run)."""
        pool = self.pools[pi].data
        sc = self.scales[pi].data if self.quantized else None
        arrays = tuple(None if a is None else unwrap(a) for a in args)
        if _trace_clean():
            pool, sc = _pool_program(fn, *static)(pool, sc, *arrays)
            self._written[0] += pages
            self._written[1] += rows
        else:
            pool, sc = fn(*static, pool, sc, *arrays)
        new_pool = self.pools[pi] = Tensor(pool)
        new_sc = None
        if self.quantized:
            new_sc = self.scales[pi] = Tensor(sc)
        return new_pool, new_sc

    def take_write_stats(self) -> dict:
        """What the pool writes since the last call moved, summed over
        layers and shards, and reset: pages gathered and written back
        (trash and pad slots of the page lists included), K/V rows set
        in them, the bytes of those pages (payload and scales) and,
        beside them, the bytes of all the pools. One sample a step of
        the collector's ``pool_write`` gauge."""
        pages, rows = (int(n) for n in self._written)
        self._written[:] = 0
        per_page = (self.kv_bytes_per_token() // len(self.kv_layers)
                    * self.block_size)
        return {"pages_written": pages, "rows_written": rows,
                "pool_bytes_written": pages * per_page,
                "pool_bytes": per_page * self.num_blocks * len(self.pools)}

    def rebind_shard_pools(self, layer: int, global_pool,
                           global_scales=None) -> None:
        """Rebind this layer's per-shard pool entries from a GLOBAL
        head-sharded array (the compiled step's donated output on the
        serving ``Mesh(("mp",))``). Zero-copy both directions: the
        global array's addressable shards ARE per-device buffers, so
        unwrapping them back into the flat ``pools`` list hands every
        eager path between compiled calls (COW block splits, prefill
        scatters, snapshot/export readback) ordinary committed
        per-shard arrays — the device-resident pool protocol with
        host readback only at those boundaries. MUST run immediately
        after the compiled call: donation invalidated the previous
        buffers. Shards sort by their head-axis slice start so entry
        ``pool_index(layer, s)`` always holds heads [s*Hs, (s+1)*Hs).
        """
        shards = sorted(global_pool.addressable_shards,
                        key=lambda sh: sh.index[2].start or 0)
        for s, sh in enumerate(shards):
            self.pools[self.pool_index(layer, s)] = Tensor(sh.data)
        if global_scales is not None:
            sshards = sorted(global_scales.addressable_shards,
                             key=lambda sh: sh.index[2].start or 0)
            for s, sh in enumerate(sshards):
                self.scales[self.pool_index(layer, s)] = \
                    Tensor(sh.data)

    @property
    def capacity_per_seq(self) -> int:
        return self.max_blocks_per_seq * self.block_size

    def blocks_needed(self, length: int) -> int:
        return -(-int(length) // self.block_size)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - 1 - self.allocator.num_free

    def pool_bytes(self) -> int:
        """PER-SHARD pool bytes — what ONE device's HBM actually
        holds. At mp == 1 this is the whole pool (unchanged); on a
        sharded pool each device holds 1/mp of the payload (the
        headroom multiplication the sharding buys — a cost report
        that summed all shards would overstate per-chip HBM by mp x;
        ``pool_bytes_total()`` gives the whole-mesh sum).

        itemsize off the array's own dtype: np.dtype(str(...)) has no
        parse for ml_dtypes names, so a bfloat16 pool would raise.
        Quantized pools count the scale metadata too — the honest
        byte model (a stale bf16 model would overstate density ~2x)."""
        return self.pool_bytes_total() // self.mp

    def pool_bytes_total(self) -> int:
        """Pool bytes summed across every mp shard (the whole-mesh
        footprint; == pool_bytes() at mp 1)."""
        n = sum(int(np.prod(p.shape)) * p.data.dtype.itemsize
                for p in self.pools)
        if self.quantized:
            n += sum(int(np.prod(s.shape)) * s.data.dtype.itemsize
                     for s in self.scales)
        return n

    def kv_bytes_per_token(self) -> int:
        """PER-SHARD HBM bytes one token's K/V occupies across every
        layer (2 x kv heads/mp x (head_dim x payload itemsize + scale
        bytes) x layers; ONE plane of the stored row width in the
        latent form) — the KV-traffic unit of the analytic work
        model (inference/accounting.py), per DEVICE: each shard reads
        and writes only its own head slice, so MBU paired against one
        chip's peak bandwidth must price one chip's traffic. int8
        pools carry 4 scale bytes per (position, head, K|V) next to
        the int8 payload."""
        per_head = self.head_dim * self.pools[0].data.dtype.itemsize
        if self.quantized:
            per_head += self.scales[0].data.dtype.itemsize
        return int(self.planes * self.kv_heads_per_shard * per_head
                   * len(self.kv_layers))

    # -- tenant accounting --------------------------------------------
    def _charge(self, slot: int, delta: int) -> None:
        """Move ``slot``'s tenant's block charge by ``delta`` table
        references. Called by every table mutation (alloc growth,
        prefix adoption, fork, truncate, free, quarantine); a COW swap
        is charge-neutral (one reference out, one in)."""
        if delta == 0:
            return
        t = self.seq_tenant[slot]
        self._tenant_charge[t] = self._tenant_charge.get(t, 0) + delta

    def set_seq_tenant(self, slot: int, tenant: Optional[str]) -> None:
        """Attribute ``slot`` to ``tenant`` (None = unattributed). Any
        blocks the slot already holds move their charge with it."""
        old = self.seq_tenant[slot]
        if old == tenant:
            return
        held = len(self.seq_blocks[slot])
        if held:
            self._tenant_charge[old] = \
                self._tenant_charge.get(old, 0) - held
        self.seq_tenant[slot] = tenant
        if held:
            self._tenant_charge[tenant] = \
                self._tenant_charge.get(tenant, 0) + held

    def tenant_charge(self, tenant: Optional[str]) -> int:
        """Blocks currently charged to ``tenant`` (one per table
        reference its slots hold — see the policy note in __init__)."""
        return self._tenant_charge.get(tenant, 0)

    def tenant_blocks_held(self) -> Dict[Optional[str], int]:
        """{tenant: charged blocks}, nonzero entries only — the
        per-tenant occupancy histogram OOM messages and the offline
        doctor print."""
        return {t: n for t, n in self._tenant_charge.items() if n}

    # -- diagnostics ---------------------------------------------------
    def owners_of(self, block: int) -> List[int]:
        """Slots whose table holds ``block`` (error/audit paths only —
        O(max_seqs * blocks_per_seq))."""
        return [s for s in range(self.max_seqs)
                if block in self.seq_blocks[s]]

    def pool_occupancy(self, tiers_only: bool = False) -> dict:
        """STRUCTURED occupancy breakdown — the single source behind
        BlockOOM messages (``_pool_context`` renders it), the
        exception's machine-readable ``details``, the telemetry
        events every shed/OOM emits, and the engines'
        MetricsRegistry pool gauges: tier counts, owning-slot
        histogram, per-tenant blocks-held histogram.
        ``tiers_only`` skips the two histograms (an O(max_seqs) scan)
        — the per-step gauge path wants just the O(1) tier scalars."""
        a = self.allocator
        out = {
            "active": self.num_blocks - 1 - a.num_free,
            "cached_free": a.num_cached,
            "free": len(a._free),
            "usable": self.num_blocks - 1,
        }
        if self.mp > 1:
            # sharded pools report bytes HONESTLY per shard: the
            # metadata above is replicated (every shard sees the same
            # tiers), the payload is divided — a reader summing
            # per-worker reports must not count HBM mp x over
            out["mp"] = self.mp
            out["pool_bytes_per_shard"] = self.pool_bytes()
        if not tiers_only:
            out["blocks_per_slot"] = {
                s: len(bl) for s, bl in enumerate(self.seq_blocks)
                if bl}
            out["blocks_per_tenant"] = {
                t: n for t, n in self._tenant_charge.items()
                if n and t is not None}
        return out

    def _pool_context(self) -> str:
        """Occupancy breakdown appended to BlockOOM messages so an OOM
        report is actionable — ``pool_occupancy()`` rendered: tier
        counts + owning-slot histogram + (multi-tenant serving) the
        per-tenant blocks-held histogram, so the message names WHICH
        TENANT holds the pool."""
        occ = self.pool_occupancy()
        out = (f"; pool: {occ['active']} active / "
               f"{occ['cached_free']} cached-free"
               f" / {occ['free']} free of {occ['usable']}"
               f" usable; blocks per slot: "
               f"{occ['blocks_per_slot'] or '{}'}")
        if occ["blocks_per_tenant"]:
            out += f"; blocks per tenant: {occ['blocks_per_tenant']}"
        return out

    def _describe_block(self, block: int) -> str:
        owners = self.owners_of(block)
        state = ("cached-free" if block in self.allocator._cached
                 else f"refcount {int(self.allocator.refcount[block])}")
        tail = ", hash-indexed" if block in self._block_hash else ""
        own = f"owned by slot(s) {owners}" if owners else "no owner"
        tnts = sorted({self.seq_tenant[s] for s in owners
                       if self.seq_tenant[s] is not None})
        if tnts:
            own += f" of tenant(s) {tnts}"
        return f"{state}, {own}{tail}"

    def _fingerprint(self, block: int, pool_arrs) -> bytes:
        h = hashlib.blake2b(digest_size=16)
        for arr in pool_arrs:
            h.update(np.ascontiguousarray(arr[block]).tobytes())
        return h.digest()

    def check_invariants(self, lens=None, active=None,
                         deep: bool = True) -> bool:
        """Audit the pool's bookkeeping; raises AssertionError naming
        the violated invariant, returns True when clean. Verified:

          1. refcounts == block-table references: every usable block's
             refcount equals the number of slot tables holding it (a
             block appears at most once per table).
          2. partition: free list, cached-free tier and the active set
             (refcount > 0) are pairwise disjoint and together cover
             every usable block exactly once.
          3. trash block 0: refcount pinned at 1, never in a table,
             never in either free tier, never hash-indexed.
          4. device tables mirror host state: block_tables[slot] is
             seq_blocks[slot] then trash.
          5. hash index: _hash_to_block and _block_hash are inverse
             maps, and every indexed block is live (refcount > 0) or
             parked cached-free — the index never points at a
             free-list block.
          6. cached-free blocks are refcount-0 and hash-indexed (the
             second-chance tier exists only for resurrectable content).
          7. with ``lens``/``active`` (the engine's view): every
             active slot's table covers blocks_needed(lens[slot]).
          8. ``deep``: immutable-content audit — blocks that must not
             be written in place (refcount >= 2 shared pages, hash-
             indexed pages, cached-free pages) are content-fingerprinted
             and re-verified against the previous audit while they
             remain in that state; an in-place write to a shared or
             indexed page trips it. (Writers must COW-split first —
             ensure()'s write-range split.)
          9. tenant quota bookkeeping: the incremental per-tenant
             block charges (_tenant_charge) equal the slot tables'
             ground truth (one charge per reference held by each
             tenant's slots) and their total equals the allocator's
             total refcount over usable blocks — a growth path that
             skipped the charge update cannot survive an audit.
        """
        a = self.allocator
        counts: Dict[int, int] = {}
        for slot in range(self.max_seqs):
            blocks = self.seq_blocks[slot]
            assert len(blocks) == len(set(blocks)), \
                f"slot {slot} table holds duplicate blocks: {blocks}"
            assert len(blocks) <= self.max_blocks_per_seq, \
                f"slot {slot} table over capacity"
            assert 0 not in blocks, \
                f"slot {slot} table holds the trash block"
            for b in blocks:
                counts[int(b)] = counts.get(int(b), 0) + 1
            row = self.block_tables[slot]
            assert list(row[:len(blocks)]) == [int(b) for b in blocks] \
                and not row[len(blocks):].any(), \
                f"slot {slot} device table diverges from seq_blocks"
        free_set, cached_set = set(a._free), set(a._cached)
        active_set = {b for b in range(1, self.num_blocks)
                      if a.refcount[b] > 0}
        assert a.refcount[0] == 1 and 0 not in free_set \
            and 0 not in cached_set and 0 not in self._block_hash, \
            "trash block 0 left its reserved state"
        for b in range(1, self.num_blocks):
            assert int(a.refcount[b]) == counts.get(b, 0), \
                (f"block {b} refcount {int(a.refcount[b])} != "
                 f"{counts.get(b, 0)} table reference(s) "
                 f"(slots {self.owners_of(b)})")
        assert not (free_set & cached_set) \
            and not (free_set & active_set) \
            and not (cached_set & active_set), \
            "free / cached-free / active sets overlap"
        assert free_set | cached_set | active_set \
            == set(range(1, self.num_blocks)), \
            "free / cached-free / active sets do not cover the pool"
        for h, b in self._hash_to_block.items():
            assert self._block_hash.get(b) == h, \
                f"hash index asymmetry at block {b}"
            assert b in active_set or b in cached_set, \
                f"hash index points at free-list block {b}"
        for b, h in self._block_hash.items():
            assert self._hash_to_block.get(h) == b, \
                f"block-hash asymmetry at block {b}"
        for b in cached_set:
            assert a.refcount[b] == 0, f"cached-free block {b} has owners"
            assert b in self._block_hash, \
                f"cached-free block {b} is not hash-indexed"
        # 9. tenant quota bookkeeping vs the allocator's ground truth:
        #    the incremental per-tenant charge must equal the table
        #    references actually held by each tenant's slots (one
        #    charge per reference — the policy note in __init__), and
        #    the grand total must equal the allocator's total refcount
        #    over usable blocks (every reference attributed once).
        truth: Dict[Optional[str], int] = {}
        for slot in range(self.max_seqs):
            n = len(self.seq_blocks[slot])
            if n:
                t = self.seq_tenant[slot]
                truth[t] = truth.get(t, 0) + n
        charged = {t: n for t, n in self._tenant_charge.items() if n}
        assert charged == truth, \
            (f"tenant block charges {charged} diverge from the "
             f"tables' ground truth {truth}")
        assert all(n >= 0 for n in self._tenant_charge.values()), \
            f"negative tenant charge: {self._tenant_charge}"
        total_refs = int(a.refcount[1:].sum())
        assert sum(truth.values()) == total_refs, \
            (f"tenant charges cover {sum(truth.values())} references "
             f"but the allocator counts {total_refs}")
        if lens is not None and active is not None:
            lens = np.asarray(lens)
            for slot in np.flatnonzero(np.asarray(active)):
                need = self.blocks_needed(int(lens[slot]))
                assert need <= len(self.seq_blocks[int(slot)]), \
                    (f"active slot {int(slot)} length "
                     f"{int(lens[slot])} not covered by its "
                     f"{len(self.seq_blocks[int(slot)])} block(s)")
        if deep:
            frozen = {b for b in range(1, self.num_blocks)
                      if a.refcount[b] >= 2 or b in self._block_hash
                      or b in cached_set}
            for b in list(self._audit_fp):
                if b not in frozen:
                    del self._audit_fp[b]
            if frozen:
                # ONE device->host pull per pool, shared by every
                # fingerprint (not one whole-pool copy per block).
                # Quantized pools fingerprint the int8 payload AND the
                # scale pages — an in-place scale rewrite corrupts a
                # shared page as surely as a payload write
                arrs = [np.asarray(p.numpy()) for p in self.pools]
                if self.quantized:
                    arrs += [np.asarray(s.numpy()) for s in self.scales]
                for b in frozen:
                    fp = self._fingerprint(b, arrs)
                    old = self._audit_fp.get(b)
                    assert old is None or old == fp, \
                        (f"immutable block {b} was written in place "
                         f"({self._describe_block(b)})")
                    self._audit_fp[b] = fp
        return True

    # -- checkpoint / restore -----------------------------------------
    def snapshot(self, base: Optional[dict] = None) -> dict:
        """Host-side checkpoint of the whole pool: geometry, the
        allocator's EXACT state (refcounts, free-list order,
        cached-free LRU order), block tables, the chain-hash index,
        and the content of every block that is live (refcount > 0) or
        parked cached-free. Free-list blocks carry no content worth
        keeping — a quarantined page, for instance, is already free
        here and therefore never rides a snapshot. ONE device->host
        pull per layer pool, independent of the live-block count.
        The result is a plain picklable dict (numpy + ints + bytes);
        ``restore`` rebuilds an identical pool from it.

        ``base`` (a previous snapshot of the SAME pool geometry)
        makes this a DELTA: pages whose content the base provably
        already carries — the block is chain-hash indexed, the base's
        index binds the same hash to the same block id, and the base
        holds that block's payload row — ride as ``base_blocks`` ids
        only, no bytes. The content address justifies the skip:
        indexed blocks are immutable in place (the deep audit
        enforces it), so same (id, hash) == same bytes. Unhashed
        blocks (open tails, mid-prefill pages) are always dirty and
        always ship. All ALLOCATOR metadata stays complete either
        way — only payload rows are elided — and ``restore(...,
        base=...)`` reconstitutes the full pool."""
        a = self.allocator
        self._flush_state_resets()      # the store as a reader sees it
        cached_order = [int(b) for b in a._cached]
        keep = sorted({b for b in range(1, self.num_blocks)
                       if a.refcount[b] > 0} | set(cached_order))
        geometry = {
            "num_layers": self.num_layers,
            "num_heads": self.num_heads,
            "num_kv_heads": self.num_kv_heads,
            "layer_windows": list(self.layer_windows),
            "head_dim": self.head_dim,
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "max_seqs": self.max_seqs,
            "max_blocks_per_seq": self.max_blocks_per_seq,
            "dtype": self.dtype,
            "prefix_cache": self.prefix_cache,
            # recorded so tooling names the source mesh width; the
            # PAYLOAD is canonical (full heads) regardless, and
            # restore(mp=...) re-slices for any target width
            "mp": self.mp,
            **self._latent_geometry(),
            **({"layer_state": [None if s is None else list(s)
                                for s in self.layer_state]}
               if self.state_layers else {}),
        }
        clean = set()
        if base is not None:
            if base.get("geometry") != geometry:
                raise ValueError(
                    "delta snapshot: base comes from a different "
                    "pool geometry — content addresses do not "
                    "transfer across geometries")
            base_rows = {int(b) for b in base["blocks"]}
            base_index = base.get("hash_index", {})
            for b in keep:
                h = self._block_hash.get(b)
                if h is not None and base_index.get(h) == b \
                        and b in base_rows:
                    clean.add(b)
        dirty = [b for b in keep if b not in clean]
        arrs = [np.asarray(p.numpy()) for p in self.pools]
        if self.mp > 1:
            # CANONICAL wire format: full-head pages, the mp=1 layout
            # — shard slices concatenate back on the head axis, so a
            # snapshot taken at mp=N restores at ANY width (mp=1
            # included) and vice versa; content-addressing stays over
            # the canonical bytes, identical across mesh widths
            arrs = [np.concatenate(
                arrs[i * self.mp:(i + 1) * self.mp], axis=2)
                for i in range(len(self.kv_layers))]
        if dirty:
            # one fancy-index gather per layer, not a Python loop per
            # block — snapshots sit on the serving hot path
            payload = np.stack([arr[dirty] for arr in arrs],
                               axis=1)                 # [n, L, 2, H, bs, D]
        else:
            payload = np.zeros((0, len(self.kv_layers), self.planes,
                                self.num_kv_heads, self.block_size,
                                self.head_dim), arrs[0].dtype)
        scale_payload = None
        if self.quantized:
            # content-addressing over QUANTIZED bytes: the snapshot
            # carries each kept page's int8 payload plus its scales —
            # together they ARE the page's content, so a restore (same
            # or different geometry) reproduces dequantized values
            # bit-exactly
            sarrs = [np.asarray(s.numpy()) for s in self.scales]
            if self.mp > 1:
                sarrs = [np.concatenate(
                    sarrs[i * self.mp:(i + 1) * self.mp], axis=2)
                    for i in range(len(self.kv_layers))]
            if dirty:
                scale_payload = np.stack([a[dirty] for a in sarrs],
                                         axis=1)   # [n, L, 2, H, bs]
            else:
                scale_payload = np.zeros(
                    (0, len(self.kv_layers), 2, self.num_kv_heads,
                     self.block_size), np.float32)
        return {
            "kind": "paged_kv_cache",
            "geometry": geometry,
            "refcount": {int(b): int(a.refcount[b]) for b in keep},
            "free_order": [int(b) for b in a._free],
            "cached_order": cached_order,       # oldest (LRU) first
            "reclaimed": int(a.reclaimed),
            "hash_index": dict(self._hash_to_block),
            "seq_blocks": [[int(b) for b in bl]
                           for bl in self.seq_blocks],
            "seq_tenant": list(self.seq_tenant),
            "peak_blocks_used": int(self.peak_blocks_used),
            "blocks": [int(b) for b in dirty],
            "payload": payload,
            # content the BASE checkpoint already carries (empty on a
            # full snapshot): restore(base=...) pulls these rows from
            # the base instead of the wire
            "base_blocks": sorted(int(b) for b in clean),
            **({"scale_payload": scale_payload}
               if scale_payload is not None else {}),
            # the state store rides whole (every slot's rows of every
            # state layer: a few rows a slot), never as a delta
            **({"state": [np.asarray(a) for a in self.state]}
               if self.state_layers else {}),
        }

    @classmethod
    def restore(cls, snap: dict, *,
                num_blocks: Optional[int] = None,
                mp: Optional[int] = None,
                shard_devices=None,
                base: Optional[dict] = None) -> "PagedKVCache":
        """Rebuild a pool from a ``snapshot`` dict. With the default
        (same ``num_blocks``) every block keeps its id and the
        allocator's free-list and LRU orders round-trip EXACTLY, so
        post-restore allocation behavior is bit-identical to the
        uninterrupted pool. ``num_blocks`` rehomes the
        content-addressed blocks into a larger or smaller pool:
        live blocks move first (oldest ids first), then cached-free
        blocks newest-first — the least-recently-used cached-free
        blocks are DROPPED (their index entries with them) when the
        target cannot hold everything, exactly the LRU-reclaim policy
        the live allocator applies. A live set that cannot fit raises
        ``BlockOOM`` carrying the snapshot's occupancy breakdown.

        ``mp`` retargets the tensor-parallel width: the snapshot's
        payload is canonical (full-head pages) whatever mesh it was
        taken on, so a snapshot from an mp=N fleet restores onto a
        single chip (mp=1) and vice versa — each target shard takes
        its own head slice of every page. Default: the snapshot's
        recorded width. Ends with the deep ``check_invariants``
        audit.

        A DELTA snapshot (non-empty ``base_blocks``; see
        ``snapshot(base=...)``) additionally needs ``base`` — the
        checkpoint it was taken against — to reconstitute the elided
        payload rows; restoring one without its base refuses rather
        than silently dropping pages. Pre-delta snapshots carry no
        ``base_blocks`` key and restore exactly as before."""
        referenced = [int(b) for b in snap.get("base_blocks", ())]
        if referenced:
            if base is None:
                raise ValueError(
                    f"delta snapshot references {len(referenced)} "
                    f"block(s) from its base checkpoint — restore "
                    f"needs base=...")
            snap = _merge_delta_snapshot(snap, base, referenced)
        g = snap["geometry"]
        nb = g["num_blocks"] if num_blocks is None else int(num_blocks)
        mp_t = int(g.get("mp", 1)) if mp is None else int(mp)
        cache = cls(g["num_layers"], g["num_heads"], g["head_dim"],
                    g["block_size"], nb, g["max_seqs"],
                    max_blocks_per_seq=g["max_blocks_per_seq"],
                    dtype=g["dtype"], prefix_cache=g["prefix_cache"],
                    mp=mp_t, shard_devices=shard_devices,
                    num_kv_heads=g.get("num_kv_heads"),
                    layer_windows=g.get("layer_windows"),
                    v_dim=g.get("v_dim"), sm_scale=g.get("sm_scale"),
                    layer_state=g.get("layer_state"))
        refcount = {int(b): int(n) for b, n in snap["refcount"].items()}
        cached = [int(b) for b in snap["cached_order"]]
        live = sorted(b for b, n in refcount.items() if n > 0)
        usable = nb - 1
        if len(live) > usable:
            hist = {s: len(bl) for s, bl in
                    enumerate(snap["seq_blocks"]) if bl}
            raise BlockOOM(
                f"restore needs {len(live)} live block(s) but the "
                f"target pool has only {usable} usable"
                f"; snapshot pool: {len(live)} active / {len(cached)} "
                f"cached-free of {g['num_blocks'] - 1} usable; "
                f"blocks per slot: {hist or '{}'}",
                details={"active": len(live),
                         "cached_free": len(cached),
                         "usable": g["num_blocks"] - 1,
                         "target_usable": usable,
                         "blocks_per_slot": hist})
        # cached-free blocks that fit, newest (most recently released)
        # kept — dropping the LRU end is the reclaim order the live
        # allocator uses
        n_cached = min(len(cached), usable - len(live))
        dropped, kept_cached = (cached[:len(cached) - n_cached],
                                cached[len(cached) - n_cached:])
        a = cache.allocator
        if nb == g["num_blocks"] and not dropped:
            remap = {b: b for b in live + kept_cached}
            a._free = [int(b) for b in snap["free_order"]]
        else:
            order = live + kept_cached   # canonical rehoming order
            remap = {old: new for new, old in enumerate(order, start=1)}
            # fresh-pool free-list convention: pop() from the end
            # hands out the lowest remaining id first
            a._free = list(range(nb - 1, len(order), -1))
        for old, n in refcount.items():
            if old in remap:
                a.refcount[remap[old]] = n
        a._cached = OrderedDict((remap[b], True) for b in kept_cached)
        a.reclaimed = int(snap["reclaimed"]) + len(dropped)
        # pre-PR-7 snapshots carry no tenant attribution: version-gate
        # to an unattributed pool instead of crashing on the old format
        tenants = snap.get("seq_tenant",
                           [None] * g["max_seqs"])
        for slot, blocks in enumerate(snap["seq_blocks"]):
            mapped = [remap[int(b)] for b in blocks]
            cache.seq_tenant[slot] = tenants[slot]
            cache.seq_blocks[slot] = mapped
            cache._charge(slot, len(mapped))
            cache.block_tables[slot, :len(mapped)] = mapped
        for h, b in snap["hash_index"].items():
            b = remap.get(int(b))
            if b is not None:     # dropped cached-free: index entry too
                cache._hash_to_block[h] = b
                cache._block_hash[b] = h
        payload = np.asarray(snap["payload"])
        rows = [i for i, b in enumerate(snap["blocks"])
                if int(b) in remap]             # dropped blocks: no scatter
        if rows:
            ids = jnp.asarray([remap[int(snap["blocks"][i])]
                               for i in rows], jnp.int32)
            payload = payload[rows]
            Hs = cache.kv_heads_per_shard
            spay = (np.asarray(snap["scale_payload"])[rows]
                    if cache.quantized else None)
            for i in range(len(cache.kv_layers)):
                for s in range(cache.mp):
                    # each target shard takes its head slice of the
                    # canonical page (the whole page at mp == 1)
                    heads = slice(s * Hs, (s + 1) * Hs)
                    cache._write_pool(
                        i * cache.mp + s, _set_pages, (), ids,
                        payload[:, i, :, heads],
                        None if spay is None else spay[:, i, :, heads],
                        pages=len(rows))
        if cache.state_layers:
            # slot numbers survive a restore at any ``num_blocks``
            # (tables are rehomed, slots are not), so the rows do too
            cache.state[:] = [jnp.asarray(a, cache.state[0].dtype)
                              for a in snap["state"]]
        cache.peak_blocks_used = int(snap["peak_blocks_used"])
        cache._tables_dirty()
        cache.check_invariants(deep=True)
        return cache

    def bt_tensor(self) -> Tensor:
        """Device copy of the block tables; rebuilt only after a
        host-side table mutation. Rows in the decode mask (slots
        mid-chunked-prefill) present as all-trash so a fused decode
        step cannot write into their half-built pages."""
        if self._bt_cached is None:
            # always a COPY: jax may alias the host buffer it is handed,
            # and block_tables mutates in place while calls that ride
            # this tensor are still executing asynchronously
            tbl = self.block_tables.copy()
            if self._decode_masked is not None and \
                    self._decode_masked.any():
                tbl[self._decode_masked] = 0
            self._bt_cached = Tensor(jnp.asarray(tbl, jnp.int32))
        return self._bt_cached

    def bt_row_tensor(self, slot: int) -> Tensor:
        """Device copy of ONE slot's (unmasked) block-table row
        [1, MB] — the indirection a chunked-prefill call rides;
        invalidated with the full table."""
        t = self._bt_rows_cached.get(slot)
        if t is None:
            t = Tensor(jnp.asarray(
                self.block_tables[slot:slot + 1].copy(), jnp.int32))
            self._bt_rows_cached[slot] = t
        return t

    def set_decode_mask(self, rows: Optional[np.ndarray]) -> None:
        """Mark rows whose pages a fused DECODE step must not touch
        (slots mid-chunked-prefill; see bt_tensor). ``rows``: bool
        [max_seqs] or None to clear."""
        new = None if rows is None or not rows.any() else rows.copy()
        old = self._decode_masked
        if (old is None) != (new is None) or \
                (old is not None and not np.array_equal(old, new)):
            self._decode_masked = new
            self._bt_cached = None

    def _tables_dirty(self):
        self._bt_cached = None
        self._bt_rows_cached.clear()
        self.peak_blocks_used = max(self.peak_blocks_used,
                                    self.blocks_in_use)

    # -- allocation ---------------------------------------------------
    def ensure(self, slot: int, length: int,
               start_block: int = 0,
               write_from: Optional[int] = None) -> None:
        """Grow slot's table to cover ``length`` tokens
        (allocate-on-write) and copy-on-write split every shared block
        the coming write touches. ``write_from``: first position the
        caller will write (defaults to ``length - 1``, the single-token
        append); a multi-token append passes its start position so a
        shared page in the MIDDLE of the write range splits too.
        ``start_block``: table positions below it are adopted prefix
        pages the caller will never write (suffix-only prefill) — the
        COW split is skipped there, so a fully cached prompt keeps its
        last page shared instead of paying a pointless pool copy.
        Raises BlockOOM when the pool is exhausted (callers preempt)
        and ValueError past the per-seq table capacity."""
        if length <= 0:
            return  # nothing to cover (and no write block to COW)
        need = self.blocks_needed(length)
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"sequence length {length} exceeds per-seq capacity "
                f"{self.capacity_per_seq} (max_blocks_per_seq="
                f"{self.max_blocks_per_seq})")
        have = self.seq_blocks[slot]
        if need > len(have):
            new = self.allocator.alloc(need - len(have))
            if not have and self.state_layers:
                # an empty slot's first pages: a request is (re)admitted
                # here, and its state starts from zero
                self._state_fresh.add(int(slot))
            self.block_tables[slot, len(have):need] = new
            have.extend(new)
            self._charge(slot, len(new))
            self._tables_dirty()
        # COW: every block the write range [write_from, length) lands in
        if write_from is None:
            write_from = int(length) - 1
        lo = max(int(write_from), 0) // self.block_size
        hi = (int(length) - 1) // self.block_size
        for bpos in range(max(lo, start_block), hi + 1):
            if self.allocator.refcount[have[bpos]] > 1:
                self._copy_block(slot, bpos)

    def truncate(self, slot: int, length: int) -> None:
        """Roll the slot back to ``length`` tokens (speculative-decode
        rejection): every block past ``blocks_needed(length)`` leaves
        the table, tail-first. Refcount-aware: a fork-shared page just
        drops one owner (the peer keeps it); a hash-indexed page
        reaching refcount 0 parks in the cached-free tier
        (resurrectable by a later ``match_prefix`` hit) instead of
        freeing — the same second-chance path ``free_seq`` takes. The
        kept partial last block is NOT cleared: positions past
        ``length`` are stale but masked by length everywhere, and the
        next append overwrites them (COW-splitting first if the block
        is shared, via ``ensure``'s write-range split)."""
        if length < 0:
            raise ValueError(f"negative truncate length {length}")
        have = self.seq_blocks[slot]
        keep = self.blocks_needed(length)
        if keep >= len(have):
            return  # nothing past the boundary
        self._refuse_with_state("truncate (a rejected draft's rollback)")
        drop = have[keep:]
        self.release_to_cache(drop)
        del have[keep:]
        self._charge(slot, -len(drop))
        self.block_tables[slot, keep:] = 0
        self._tables_dirty()

    def free_seq(self, slot: int) -> None:
        if self.seq_blocks[slot]:
            self.release_to_cache(self.seq_blocks[slot])
            self._charge(slot, -len(self.seq_blocks[slot]))
            self.seq_blocks[slot] = []
            self.block_tables[slot, :] = 0
            self._tables_dirty()
        self.seq_tenant[slot] = None

    def quarantine_seq(self, slot: int) -> None:
        """Free a slot's pages with NO cached-free second chance: used
        when the slot's pool content is suspect (numeric failure — a
        NaN/Inf reached its hidden, so its K/V pages may be poisoned).
        Solely-owned blocks lose their hash-index entry and return to
        the true free list (never resurrectable); blocks shared with
        other slots only drop this owner — a sharer's copy predates
        the corruption (shared pages are never written in place, so
        any poisoned append went to a COW-split private block)."""
        for b in self.seq_blocks[slot]:
            b = int(b)
            if self.allocator.refcount[b] == 1:
                self._on_reclaim(b)   # drop index entry + audit print
            self.allocator.free([b], to_cache=b in self._block_hash)
        self._charge(slot, -len(self.seq_blocks[slot]))
        self.seq_blocks[slot] = []
        self.block_tables[slot, :] = 0
        self._tables_dirty()
        self.seq_tenant[slot] = None

    def fork(self, src: int, dst: int, length: int) -> None:
        """Share src's first ``blocks_needed(length)`` blocks with dst
        (refcounted, including a partial last block — the first
        divergent append splits it copy-on-write)."""
        if self.seq_blocks[dst]:
            raise ValueError(f"dst slot {dst} already allocated")
        self._refuse_with_state("fork")
        shared = self.seq_blocks[src][:self.blocks_needed(length)]
        self.allocator.ref(shared)
        for b in shared:   # fresh share epoch for the content audit
            self._audit_fp.pop(int(b), None)
        self.seq_blocks[dst] = list(shared)
        self._charge(dst, len(shared))
        self.block_tables[dst, :len(shared)] = shared
        self._tables_dirty()

    def share_report(self, slots) -> dict:
        """Fork-sharing introspection for a branch group (or any slot
        set): which pool blocks the given slots' tables reference, how
        many of the slots reference each (``multiplicity``), and the
        allocator's refcount per block. A pure read — the group audit
        (scheduler._audit_groups), the parallel-sampling tests and the
        ``serving_parallel`` bench all read the same numbers:

          shared_blocks   blocks referenced by >= 2 of the slots (the
                          COW-shared prompt pages)
          private_blocks  blocks referenced by exactly one slot (each
                          branch's divergent tail)
          multiplicity    {block: how many of the slots reference it}
          refcount        {block: allocator refcount} (>= multiplicity;
                          the prefix cache may hold more references)
          bytes_saved     whole-mesh pool bytes the sharing avoided
                          allocating: (multiplicity - 1) block copies
                          summed over shared blocks, priced at
                          kv_bytes_per_token() x block_size x mp
        """
        mult: dict = {}
        for slot in slots:
            for b in self.seq_blocks[int(slot)]:
                b = int(b)
                mult[b] = mult.get(b, 0) + 1
        shared = sorted(b for b, m in mult.items() if m >= 2)
        bpb = self.kv_bytes_per_token() * self.block_size * self.mp
        return {
            "shared_blocks": shared,
            "private_blocks": sorted(b for b, m in mult.items()
                                     if m == 1),
            "multiplicity": mult,
            "refcount": {b: int(self.allocator.refcount[b])
                         for b in mult},
            "bytes_saved": sum(mult[b] - 1 for b in shared) * bpb,
        }

    def _copy_block(self, slot: int, bpos: int, copy: bool = True) -> None:
        """Copy-on-write: give slot a private block at table position
        bpos. copy=False skips the pool copy for callers about to
        overwrite the whole block anyway (write_prefill)."""
        old = self.seq_blocks[slot][bpos]
        new = self.allocator.alloc(1)[0]
        if copy:
            # the page's scales are part of its content: on quantized
            # pools they move with its bytes, in the same program
            src = jnp.asarray([old], jnp.int32)
            dst = jnp.asarray([new], jnp.int32)
            for pi in range(len(self.pools)):
                self._write_pool(pi, _block_copy, (), src, dst, pages=1)
        self.release_to_cache([old])
        self.seq_blocks[slot][bpos] = new
        self.block_tables[slot, bpos] = new
        self._tables_dirty()

    # -- prefix caching -----------------------------------------------
    def _on_reclaim(self, block: int) -> None:
        """Allocator reclaimed a cached-free block: its content is
        about to be overwritten, drop the index entry."""
        h = self._block_hash.pop(block, None)
        if h is not None and self._hash_to_block.get(h) == block:
            del self._hash_to_block[h]
        # content legitimately changes from here: new audit epoch
        self._audit_fp.pop(block, None)

    def release_to_cache(self, blocks) -> None:
        """Drop ownership of ``blocks``; indexed blocks reaching
        refcount 0 park in the allocator's cached-free tier
        (resurrectable on a later ``match_prefix`` hit) instead of
        returning to the free list. Unindexed blocks (partial tails,
        decode pages, or any block when ``prefix_cache`` is off) free
        normally."""
        for b in blocks:
            self.allocator.free([b], to_cache=b in self._block_hash)

    def match_prefix(self, hashes) -> List[int]:
        """Longest indexed prefix of the hash chain -> pool block ids
        (a pure lookup: no refcounts move; use ``adopt_prefix`` to take
        ownership). A break in the chain ends the match — later links
        hash over the missing parent, so they cannot be present."""
        out: List[int] = []
        for h in hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            out.append(b)
        return out

    def adopt_prefix(self, slot, hashes) -> int:
        """Take shared ownership of the longest indexed prefix for an
        empty slot: active blocks gain an owner (``ref``), cached-free
        blocks are resurrected. Returns the number of blocks adopted —
        the caller prefills only tokens past ``n * block_size``."""
        if not self.prefix_cache:
            return 0
        if self.seq_blocks[slot]:
            raise ValueError(f"slot {slot} already allocated")
        matched = self.match_prefix(hashes)
        for b in matched:
            if self.allocator.refcount[b] > 0:
                self.allocator.ref([b])
                # new sharer: fresh epoch for the content audit
                self._audit_fp.pop(int(b), None)
            else:
                self.allocator.resurrect(b)
        if matched:
            self.seq_blocks[slot] = list(matched)
            self._charge(slot, len(matched))
            self.block_tables[slot, :len(matched)] = matched
            self._tables_dirty()
        return len(matched)

    def register_prefix(self, slot, hashes,
                        start: int = 0) -> None:
        """Index the slot's blocks ``[start, len(hashes))`` under their
        chain hashes (first writer wins: a hash already indexed keeps
        its original block — both hold identical content, and 1:1
        block<->hash bookkeeping is what reclaim relies on).
        ``start`` lets an incremental caller (per-chunk registration)
        skip the already-indexed prefix instead of re-probing it."""
        if not self.prefix_cache:
            return
        blocks = self.seq_blocks[slot]
        for i in range(start, min(len(hashes), len(blocks))):
            h, b = hashes[i], int(blocks[i])
            if h in self._hash_to_block or b in self._block_hash:
                continue
            self._hash_to_block[h] = b
            self._block_hash[b] = h

    # -- page migration (disaggregated serving) -----------------------
    def export_slice(self, slot: int, hashes) -> Optional[dict]:
        """Wire-format slice of ONE slot's finished prefix pages — the
        page-MIGRATION payload a disaggregated router ships from a
        prefill-heavy pool to a decode pool (inference/router.py).
        ``hashes`` is the slot's chain-hash identity (one per FULL
        block, ``PagedRequest.block_hashes``); the slice carries the
        first ``min(len(hashes), blocks held)`` blocks as
        content-addressed (hash, payload) pairs — exactly the
        snapshot()'s per-block format, sliced to one slot — plus the
        geometry the importer validates against. ONE fancy-index
        gather per layer, no allocator state: export is a pure read.
        Returns None when the slot holds no full indexed-identity
        block yet (nothing migratable)."""
        self._refuse_with_state("export_slice")
        blocks = [int(b) for b in
                  self.seq_blocks[slot][:len(hashes)]]
        if not blocks:
            return None
        # gather ON DEVICE, transfer only the slice: pulling whole
        # pools to host per export would cost O(pool) per migrated
        # slot where the slice is a handful of blocks. Sharded pools
        # emit the CANONICAL full-head page (per-shard gathers
        # concatenated on the head axis) — the wire format is
        # mesh-width-independent, so any pool can adopt any slice
        ids = jnp.asarray(blocks, jnp.int32)
        if self.mp == 1:
            payload = np.stack([np.asarray(p.data[ids])
                                for p in self.pools],
                               axis=1)            # [n, L, 2, H, bs, D]
        else:
            payload = np.stack(
                [np.concatenate(
                    [np.asarray(
                        self.pools[self.pool_index(i, s)].data[ids])
                     for s in range(self.mp)], axis=2)
                 for i in range(self.num_layers)], axis=1)
        out = {
            "kind": "kv_slice",
            "geometry": {
                "num_layers": self.num_layers,
                "num_heads": self.num_heads,
                "num_kv_heads": self.num_kv_heads,
                "head_dim": self.head_dim,
                "block_size": self.block_size,
                "dtype": self.dtype,
                **self._latent_geometry(),
            },
            "hashes": list(hashes[:len(blocks)]),
            "payload": payload,
        }
        if self.quantized:
            if self.mp == 1:
                out["scale_payload"] = np.stack(
                    [np.asarray(s.data[ids]) for s in self.scales],
                    axis=1)                       # [n, L, 2, H, bs]
            else:
                out["scale_payload"] = np.stack(
                    [np.concatenate(
                        [np.asarray(self.scales[
                            self.pool_index(i, s)].data[ids])
                         for s in range(self.mp)], axis=2)
                     for i in range(self.num_layers)], axis=1)
        return out

    def import_slice(self, slc: dict) -> int:
        """Adopt a migrated ``export_slice`` into THIS pool: each
        (hash, page) lands as a CACHED-FREE hash-indexed block — the
        same second-chance tier a released prefix parks in — so the
        next ``adopt_prefix`` over the migrated request's chain
        resurrects them and the suffix prefill skips the work the
        source pool already did. Semantics:

          * a hash already indexed here is SKIPPED (a colliding live
            or cached prefix — by chain-hash identity the pool already
            holds bit-identical content, and 1:1 block<->hash
            bookkeeping must hold);
          * blocks import in PREFIX ORDER and a pool that cannot hold
            the next one stops early (an imported prefix is useful
            exactly up to its first gap — match_prefix ends there);
            allocation may LRU-reclaim older cached-free content,
            the live allocator's normal policy;
          * nothing is charged to any tenant (no table references) and
            no slot state moves: the import is invisible to admission
            until a request adopts it.

        Returns the number of NEW blocks written. Raises ValueError on
        a geometry/dtype mismatch (pages are raw pool rows — a wrong
        shape would corrupt attention silently) or when this pool has
        no prefix index to adopt into."""
        if slc.get("kind") != "kv_slice":
            raise ValueError(f"not a kv_slice: {slc.get('kind')!r}")
        self._refuse_with_state("import_slice")
        if not self.prefix_cache:
            raise ValueError(
                "import_slice needs prefix_cache=True — migrated "
                "pages are adopted through the chain-hash index")
        g = slc["geometry"]
        mine = {"num_layers": self.num_layers,
                "num_heads": self.num_heads,
                "num_kv_heads": self.num_kv_heads,
                "head_dim": self.head_dim,
                "block_size": self.block_size, "dtype": self.dtype,
                "v_dim": self.v_dim}
        # slices written before grouped kv heads carry no such key:
        # their pages hold num_heads heads
        g = dict(g, num_kv_heads=g.get("num_kv_heads", g.get("num_heads")))
        if {k: g.get(k) for k in mine} != mine:
            raise ValueError(
                f"kv_slice geometry {g} does not match pool {mine}")
        payload = np.asarray(slc["payload"])
        if self.quantized and "scale_payload" not in slc:
            raise ValueError(
                "kv_slice carries no scales but this pool is int8 — "
                "corrupt or hand-built slice")
        spay = (np.asarray(slc["scale_payload"])
                if self.quantized else None)
        # resolve the importable set FIRST (collisions skipped, stop
        # at the first allocation failure), then land it as ONE
        # scatter per layer — not one dispatch per (block, layer)
        landing: List[tuple] = []       # (pool block id, slice row)
        for i, h in enumerate(slc["hashes"]):
            if h in self._hash_to_block:
                continue            # colliding prefix: already here
            try:
                b = self.allocator.alloc(1)[0]
            except BlockOOM:
                break               # pool full: keep the clean prefix
            landing.append((b, i))
        if not landing:
            return 0
        ids = jnp.asarray([b for b, _ in landing], jnp.int32)
        rows = [i for _, i in landing]
        Hs = self.kv_heads_per_shard
        for li in range(self.num_layers):
            # ONE fancy-index gather of the layer's canonical
            # full-head pages; each local shard lands a view-slice of
            # it (not mp re-gathers of the whole payload)
            seg_full = payload[rows, li]
            sfull = spay[rows, li] if self.quantized else None
            for s in range(self.mp):
                heads = slice(s * Hs, (s + 1) * Hs)
                self._write_pool(
                    self.pool_index(li, s), _set_pages, (), ids,
                    seg_full[:, :, heads],
                    None if sfull is None else sfull[:, :, heads],
                    pages=len(rows))
        for (b, i) in landing:
            # fresh content: new audit epoch for the fingerprint
            # check, then park cached-free in prefix (oldest-first
            # LRU) order
            self._audit_fp.pop(b, None)
            self._hash_to_block[slc["hashes"][i]] = b
            self._block_hash[b] = slc["hashes"][i]
            self.allocator.free([b], to_cache=True)
        return len(landing)

    # -- mixed ragged step --------------------------------------------
    def ragged_views(self, segments) -> List["PagedRaggedView"]:
        """Per-layer views for ONE mixed ragged model call (the
        scheduler's token-budget step): ``segments`` is an ordered
        list of descriptors —

          ("prefill", slot, start, length, write_start)
              one prompt chunk: rows [start, start+length) of ``slot``
              append through its table (positions below write_start —
              an adopted shared prefix — route to trash) and attend
              causally at their absolute positions;
          ("decode", lens, 1)
              the fused decode rows: one query per batch slot at
              position lens[b], through the DECODE-MASKED batch table
              (mid-prefill/fresh slots write trash), exactly the plain
              fused step.

        The packed input x is [1, sum(rows), d] in segment order; on
        the kernel path each layer is ONE ``paged_attention_ragged``
        launch. Build AFTER ensure()ing coverage and setting the
        decode mask — the layout snapshots the current tables."""
        layout = _RaggedLayout(self, segments)
        return [PagedRaggedView(self, i, layout)
                for i in range(self.num_layers)]

    # -- prefill ------------------------------------------------------
    def prefill_views(self, slot: int,
                      write_start: int = 0) -> List["PagedPrefillView"]:
        """Per-layer chunked-prefill views of one slot — the
        ``caches=`` list for a batch-1 chunk model call. A suffix-only
        (prefix-cache hit) prefill passes ``write_start`` = adopted
        tokens: recomputed rows below it attend over the adopted pages
        but never rewrite them (they may be shared), which is what
        replaced the old pages->scratch gather."""
        return [PagedPrefillView(self, i, slot, write_start=write_start)
                for i in range(self.num_layers)]

    def write_prefill_chunk(self, slot: int, layer: int, k, v,
                            start: int, write_start: int = 0) -> None:
        """Chunk-granular append: write k/v [1, C, H, D] Tensors into
        this slot's pages at positions [start, start + C) (skipping
        positions below ``write_start`` — an adopted shared prefix).
        ``ensure(slot, start + C, write_from=start)`` must have run.
        The model path goes through ``prefill_views`` (append + attend
        in one protocol call); this entry serves callers that already
        hold projected K/V — e.g. migrating a dense cache row into
        pages chunk by chunk."""
        C = int(k.shape[1])
        pi = self.pool_index(layer, 0)
        if self.mp > 1:
            raise ValueError(
                "write_prefill_chunk takes full-head K/V; a sharded "
                "pool's pages are written per shard through the "
                "prefill views (ShardedServingCore)")
        self._write_pool(
            pi, _append_rows, (self.block_size, C), k, v,
            jnp.asarray([start], jnp.int32), self.bt_row_tensor(slot),
            jnp.asarray([write_start], jnp.int32),
            pages=_pages_spanned(C, self.block_size) + 1, rows=C)

    def write_prefill(self, slot: int, row_caches, length: int,
                      start_block: int = 0) -> None:
        """Scatter a dense single-row scratch cache (the per-layer
        [2, 1, H, S, D] Tensors a batch-1 prefill produced) into this
        slot's pages from ``start_block`` on — an ``adopt_prefix`` hit
        passes the number of adopted blocks so the shared prefix pages
        are neither rewritten nor COW-split. ensure(slot, length) must
        have run first."""
        if self.mp > 1:
            raise ValueError(
                "write_prefill consumes dense full-head scratch rows; "
                "a sharded pool streams prompts through prefill_views"
                " / chunked_prefill (per-shard head slices)")
        n = self.blocks_needed(length)
        if n > len(self.seq_blocks[slot]):
            raise ValueError("ensure() the slot before write_prefill")
        # the scatter rewrites every covered block wholesale, so any
        # fork-shared block in range must be split first (no pool copy
        # needed — its contents are about to be replaced) or the peer
        # sequence would read this prefill through the shared page
        for bpos in range(start_block, n):
            if self.allocator.refcount[self.seq_blocks[slot][bpos]] > 1:
                self._copy_block(slot, bpos, copy=False)
        if start_block >= n:
            return  # fully cached prompt: every page already written
        blks = jnp.asarray(self.seq_blocks[slot][start_block:n], jnp.int32)
        for i, rc in enumerate(row_caches):
            self._write_pool(
                i, _prefill_scatter,
                (start_block, n - start_block, self.block_size), rc, blks,
                pages=n - start_block,
                rows=(n - start_block) * self.block_size)
