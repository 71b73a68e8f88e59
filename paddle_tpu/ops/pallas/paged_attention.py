"""ONE ragged paged-attention kernel over a block-paged KV cache.

TPU analog of vLLM's PagedAttention in the layout of PAPERS.md "Ragged
Paged Attention" (arxiv 2604.15464): instead of one dense
[B, max_len, H, D] cache per batch, K/V live in a shared pool of
fixed-size blocks [num_blocks, 2, nkv, block_size, hd]; each sequence
owns an int32 row of block ids (its block table) and a valid length.

Where earlier rounds carried THREE kernels for the three serving
phases — decode (1 query row/seq), multi-query verify (K+1 rows/seq)
and chunked prefill (C rows/seq, query-tiled) — there is now ONE:
``paged_attention_ragged`` takes a PACKED query batch
[total_rows, nh, hd] plus per-sequence descriptors (static ``q_lens``,
traced ``kv_lens``) and processes a MIXED prefill+decode+verify batch
in a single launch over the shared block table. Query i of sequence s
sits at absolute position ``kv_lens[s] - q_lens[s] + i`` and attends
causally over s's pages (positions <= its own), whose K/V — including
the new rows themselves — must already sit in the pool (the
paged-cache protocol appends before attending). The three old entry
points survive as thin wrappers:

  * ``paged_attention``          q_lens = (1,)*B,   tile_q = 1
  * ``paged_attention_multi``    q_lens = (K+1,)*B, tile_q = K+1
  * ``paged_attention_prefill``  q_lens = (C,)*B,   tile_q = min(C,64)

so one body owns the online softmax + page-skip logic that used to be
triplicated, and a mixed engine step costs ONE dispatch per layer
instead of one per phase per slot (inference/paged_cache.py
``ragged_views`` builds the batch; inference/scheduler.py launches it).

Grid layout: each sequence's queries are cut into tiles of ``tile_q``
rows, and a kv step (``P`` pages) whose first position lies past a
tile's LAST real query is never visited (the causal frontier — prefill
work is O(tokens written), not O(page capacity); a decode tile stops
at its one position), nor one wholly behind a sliding layer's window.
The grid has ONE axis, and it walks a WORK LIST (``_work_list``, built
by XLA from the traced lengths in the same program): an item a live
(tile x head group, kv step) pair, tile by tile; the grid's size is
the number of items, a traced scalar. The list rides as
SCALAR-PREFETCH arguments (pltpu.PrefetchScalarGridSpec) beside the
block table: the items, each tile's (first, last real) query positions,
(first, last) live steps and sequence, and the last table entry each
page operand of a tile may name — an index map is five SMEM reads, a
multiply-add and a minimum, and no division. (PR 33, on the chip: a
grid step costs the scalar core an index map and a block comparison
for every page operand, whether the step is live or not; over the
(tiles, ceil(MB / P)) grid this launch had before, with the
block-table arithmetic inside the index maps, that toll was nine
tenths of a decode launch and the two products a twentieth. PERF.md,
PR 33.) One grid step carries ``Hb`` kv heads of ``P`` pages. The q
and output blocks are (1, Hb, rows, hd), the m / l / acc scratch
(Hb, rows, ..), and both products are batched over the head axis. A
sequence's pages are not contiguous in the pool, so the pool is
handed to the call ``P`` times; operand p's BlockSpec
(1, 2, Hb, block_s, hd) index_map names the item's page p, so each
page is DMA'd HBM->VMEM directly from its pool row, every head at once
— the gathered [B, S, H, D] view never materializes — and the body
joins the ``P`` pages into one (Hb, P * block_s, hd) kv tile. Where a
tile's last step runs past its frontier each operand is held at its
last real page, so it re-names the block the pipeline already holds
and no copy is issued for it. WHAT THE PRODUCTS TAKE: float32
copies of q and of the step's pages, made inside the live branch;
scores, mask and the softmax state are float32. Feeding both products
the pages as a 16-bit pool holds them (PR 33 built it) gives the same
bits on the chip (Mosaic feeds the MXU a float32 operand in one bf16
pass) at the same speed, on the old grid and on this one: the copies
are not what a step costs, so there is one form (PERF.md, PR 33).
``Hb`` and ``P`` come from ``launch_plan``: the largest divisor of
nkv, then the most pages, up to the positions STEP_PAGE_BYTES buys,
whose double-buffered blocks, scratch and working set fit
VMEM_BUDGET_BYTES. At chat's shapes (32 kv heads of 128, bf16 pages of
16, 128 table entries) that is Hb 32 and P 8, 2 MB a step (PERF.md,
PR 27, has the sweep over P and Hb on the chip); at trinity's 8 kv
heads P 32 (24 at the mixed launch's 384 rows). There
is ONE ``pallas_call``: off the chip the same call runs with
``interpret=True`` (jax 0.9.0 interprets scalar-prefetch index maps
and a traced grid size), so a kernel test on the CPU runs the chip's
grid, index maps and body. The model-level CPU fallback in
inference/paged_cache.py uses a pure-jnp gather instead so tier-1
serving tests exercise the full protocol without Mosaic.

Tile sizes: ``tile_q`` is the query rows per grid step — more rows
amortize each page DMA across queries but pad decode segments;
``tile_kv`` is the PAGES per kv grid step. ``None``, which is what the
serving path passes, means derived from the shapes: the longest
segment up to DEFAULT_TILE_Q_CAP rows (``resolve_tile_q``), and
``launch_plan``'s ``P``. Only the kernel's own tests set them.

TENSOR-PARALLEL DISPATCH (sharded pools, inference/paged_cache.py
``mp`` > 1): the kernel itself is shard-oblivious — attention is
head-independent, so a mesh shard simply launches it against ITS pool
slice ``[num_blocks, 2, H/mp, bs, hd]`` with its own head slice of the
packed queries (``head_slice``), under the SAME replicated block
table / q_lens / kv_lens descriptors. One launch per layer PER SHARD,
each on its own device; the per-shard outputs are disjoint head
slices that the serving model's single per-layer all-reduce
recombines (inference/serving.py ShardedServingCore). Every fallback
(interpret, jnp reference, the model-level gather) inherits the same
property for free — nothing in this file ever needs to know the mesh
width. The one hazard is a FULL-head q against a sharded pool: nh/nkv
would alias the GQA group ratio and silently misread — the paged
views guard this (they know the mesh width; the kernel only rejects
ratios that are not whole groups).

THE LATENT FORM (``v_dim``; absorbed multi-head latent attention,
inference/decoder.py ``mla``): the pool is [num_blocks, 1, 1,
block_size, width], ONE row a position which is the key, and whose
leading ``v_dim`` columns are the value. Same grid, prefetch, frontier
and page-skip logic and the same body; a page is one (1, 1, Hb,
block_s, width) block, DMA'd once; the output and the accumulator are
``v_dim`` wide; ``sm_scale`` is the caller's (the un-absorbed head's).
At 32 query heads on the one kv head a decode tile is 32 rows, and
``launch_plan`` gives a step 1 024 positions (STEP_PAGE_BYTES over a
row of 1 280 bytes) and a tile at most MAX_TILE_ROWS rows (PERF.md,
PR 33, has the sweep on the chip).

QUANTIZED PAGES (``kv_scales``): an int8 KV pool rides the SAME block
table with a per-page scale array [num_blocks, 2, nkv, block_size]
(symmetric per-position-per-head scales — see
inference/paged_cache.py for why scales are per row, not one scalar
per block: row granularity is what keeps the quantized payload a pure
function of the token stream, so prefix adoption stays exact). Each
scale page is DMA'd next to its int8 page
through the same work-list lookup as a
(1, 2, Hb, block_s) block — the step's heads on the sublane axis,
which is why a quantized launch keeps ``Hb`` whole or a multiple of 8
— and the kernel dequantizes in-register, folding the scales into its
score and probability tiles (int8 page bytes plus the page's scales
over the wire instead of bf16 — the HBM win). The jnp reference
dequantizes inside its page gather.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu

NEG_INF = -1e30

# the derived tile_q: decode segments want 1 (no padding rows), verify
# wants the whole K+1 block (one page sweep scores every position),
# prefill wants wide tiles up to this cap so a long chunk never holds
# every row in VMEM at once.
DEFAULT_TILE_Q_CAP = 64
# ... and a tile never carries more than this many ROWS (tile_q times
# the query heads a kv head serves): every decode row of a mixed launch
# is padded to a whole tile, and at 32 query heads on the one head of a
# latent pool a 64-query tile is 2 048 rows of which a decode row fills
# 32 (on the chip, a mixed launch of long-decode at 512 / 1 024 / 2 048
# rows: 14.8 / 14.7 / 18.0 ms, PERF.md, PR 33; 25.0 / 22.5 / 37.5 under
# PR 32's grid). No K/V cell reaches it (64 x 6 = 384 rows at most).
MAX_TILE_ROWS = 1024

# what one grid step of the scalar-prefetch launch may hold in VMEM
# (launch_plan sizes Hb and P against it) and what Mosaic is told it
# may use, which leaves the compiler room for its own temporaries; a
# v5e core has 128 MiB.
VMEM_BUDGET_BYTES = 24 * 2 ** 20
VMEM_LIMIT_BYTES = 2 * VMEM_BUDGET_BYTES
# ... and the page bytes a step is given: the kv positions of a step
# are the power of two that brings at most this much (never under one
# 128-lane score tile). ONE figure for every page form, because what
# a step costs beside its products is a fixed toll a page operand
# (PERF.md, PR 33): 128 positions at chat's 32 heads of 128 (16 KiB a
# position), 512 at trinity's 8 (4 KiB), 1 024 over a latent row of
# 640 (1.25 KiB), each the best of its sweep on the chip (PR 27: 128;
# PR 33: 64 / 128 / 256 / 512 and 128 ... 2 048).
STEP_PAGE_BYTES = 2 * 2 ** 20

# launch accounting for the dispatch-count acceptance tests: every
# ``paged_attention_ragged`` entry (the launch, or the delegation to
# the reference under an ``mp`` region) bumps the counter ONCE — i.e. once
# per attention launch when the eager op-jit cache is off
# (FLAGS_eager_op_jit=False; with it on, a cached executable replays
# without re-entering this module, so tests disable it to count).
_DISPATCH = {"count": 0}


def dispatch_count() -> int:
    return _DISPATCH["count"]


def reset_dispatch_count() -> None:
    _DISPATCH["count"] = 0


def head_slice(x, shard: int, mp: int, axis: int = -2):
    """Shard ``shard``'s contiguous head slice of ``x`` along
    ``axis`` (default: the nh axis of the kernel's [R, nh, hd]
    packed-query layout). The tensor-parallel dispatch helper: a mesh
    shard feeds the ragged kernel q = head_slice(q_full, s, mp)
    against its pool slice — slicing is exact (each head's attention
    is independent), so per-shard outputs are bitwise the head slices
    of the single-chip launch."""
    H = x.shape[axis]
    if H % mp:
        raise ValueError(f"{H} heads do not divide over mp={mp}")
    hs = H // mp
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(shard * hs, (shard + 1) * hs)
    return x[tuple(idx)]


class LaunchPlan(NamedTuple):
    """What one scalar-prefetch launch does, from its shapes alone."""
    heads: int            # Hb: kv heads a grid step carries
    pages: int            # P: pool pages a kv grid step carries
    grid: Tuple[int, int]
    bytes_per_step: int   # page (and scale) bytes a full step DMAs

    @property
    def grid_steps(self) -> int:
        return self.grid[0] * self.grid[1]


def _vmem_bytes(shape, itemsize: int) -> int:
    """Bytes a buffer of ``shape`` takes in VMEM: the last dim padded
    to 128 lanes, the one before it to the dtype's sublane tile (8
    rows of 32 bits, 16 of bf16, 32 of int8)."""
    *lead, sub, lane = shape
    tile = 32 // itemsize
    return (math.prod(lead) * -(-sub // tile) * tile
            * -(-lane // 128) * 128 * itemsize)


def launch_plan(T: int, nkv: int, rows: int, MB: int, block_s: int,
                hd: int, kv_itemsize: int, *, q_itemsize: int = 4,
                quantized: bool = False,
                tile_kv: Optional[int] = None,
                v_dim: Optional[int] = None) -> LaunchPlan:
    """The scalar-prefetch launch for a shape — pure host arithmetic,
    shared by the kernel wrapper and the telemetry gauge. ``Hb`` is
    the largest divisor of ``nkv`` whose grid step fits
    VMEM_BUDGET_BYTES at one page a step: the pool (and scale) blocks
    and the q / out blocks, all double-buffered by the pipeline, the
    m / l / acc scratch, and the float32 working set the body makes of
    them: the score and probability tiles, the K and V tiles and, in
    the latent form, q. ``P`` is ``tile_kv`` where the caller
    passes one, else the most pages that still fit, up to the
    positions STEP_PAGE_BYTES buys (never more than the table has),
    in whole 128-lane score tiles. A quantized launch keeps ``Hb``
    whole or a multiple of 8: its (2, Hb, block_s) scale block has the
    heads on the sublane axis. ``v_dim`` (the latent form): a page is
    ONE plane whose leading ``v_dim`` columns are the value, the
    output and the accumulator are ``v_dim`` wide. ``grid`` is the
    launch's BOUND, (tiles x head groups, kv steps a tile): the launch
    itself walks one grid step a LIVE pair (``_work_list``)."""
    planes, od = (2, hd) if v_dim is None else (1, int(v_dim))

    def fits(hb, p):
        n = p * block_s
        blocks = p * _vmem_bytes((planes * hb, block_s, hd), kv_itemsize) \
            + _vmem_bytes((hb, rows, hd), q_itemsize) \
            + _vmem_bytes((hb, rows, od), q_itemsize)
        if quantized:
            blocks += p * _vmem_bytes((2, hb, block_s), 4)
        scratch = 2 * _vmem_bytes((hb, rows, 1), 4) \
            + _vmem_bytes((hb, rows, od), 4)
        work = planes * _vmem_bytes((hb, n, hd), 4) \
            + 2 * _vmem_bytes((hb, rows, n), 4)
        if v_dim is not None:     # the body's float32 copy of a wide q
            work += _vmem_bytes((hb, rows, hd), 4)
        return 2 * blocks + scratch + work <= VMEM_BUDGET_BYTES

    def position_bytes(hb):     # page (and scale) bytes a kv position
        return planes * hb * (hd * kv_itemsize + (4 if quantized else 0))

    cands = [h for h in range(nkv, 0, -1) if nkv % h == 0
             and (not quantized or h == nkv or h % 8 == 0)]
    hb = next((h for h in cands if fits(h, 1)), cands[-1])
    if tile_kv is not None:
        p = min(max(1, int(tile_kv)), MB)
    else:
        bought = max(1, STEP_PAGE_BYTES // position_bytes(hb))
        positions = max(128, 1 << bought.bit_length() - 1)
        cap = max(1, min(MB, positions // block_s))
        tile = max(1, 128 // block_s)       # pages a 128-lane score tile
        p = next((n for n in range(cap, 1, -1) if fits(hb, n)
                  and (n == cap or n < tile or n % tile == 0)), 1)
    grid = (T * (nkv // hb), -(-MB // p))
    return LaunchPlan(hb, p, grid, p * block_s * position_bytes(hb))


def _heads_dot(a, b, b_axis: int):
    """a's last axis against ``b``'s axis ``b_axis`` (counted from the
    [.., rows, cols] pair), accumulated in float32, batched over the
    leading head axis."""
    lead = a.ndim - 2
    batch = tuple(range(lead))
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (lead + b_axis,)), (batch, batch)),
        preferred_element_type=jnp.float32)


def _ragged_body(j, first, last, pos0, pos_last, tiles, q_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, block_s, sm_scale, g,
                 window=None, scales=None):
    """Online-softmax update for one grid step, kv step ``j`` of one
    (tile x head-group) — THE paged-attention body, shared by every
    phase. ``first`` / ``last`` are that tile's first and last LIVE kv
    steps (the work list visits no other: see ``_work_list``);
    ``pos0`` is the tile's first query's absolute position and
    ``pos_last`` its LAST REAL query's (all four read out of
    SMEM/prefetch by the kernel; a partial tail tile's pos_last
    excludes the padding rows, so a decode row padded into a wide
    mixed-batch tile still stops at its single position). ``q_ref`` /
    ``o_ref`` view the step's (Hb, rows, hd) of their blocks: row r of
    head h is query r // g of the tile, at position pos0 + r // g,
    masked causally per row. ``tiles()`` gives this step's kv tile, k
    and v each (Hb, block_s, hd) float32 — ``P`` pool pages of ``Hb``
    heads; both products are batched over the head axis. q and the
    pages are read inside the live branch: the one step that is not
    live is a tile's only one where no query of it has a key (a
    length-0 row), and it reads neither and emits zeros.
    ``scales()`` (int8 pages): the tile's per-position dequantization
    scales, k's and v's, as (Hb, 1, block_s) ROW vectors.
    Dequantization folds into the two products — q.(s_k k)^T =
    (q.k^T) s_k and p.(s_v v) = (p s_v).v — so the scales multiply the
    [Hb, rows, block_s] score/probability tiles along their lane axis
    and never have to be turned into a column.
    ``window`` (a sliding layer): key kpos is visible to query qpos
    iff 0 <= qpos - kpos < window; the mask inside the boundary steps
    is per row (the steps wholly behind the tile's first query's
    window are not on the work list)."""
    @pl.when(j == first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_s <= pos_last)
    def _update():
        q = q_ref[...].astype(jnp.float32)      # [Hb, tile_q * g, hd]
        k, v = tiles()
        scores = _heads_dot(q, k, 1) * sm_scale
        k_scale, v_scale = (None, None) if scales is None else scales()
        if k_scale is not None:
            scores = scores * k_scale
        kpos = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, scores.ndim - 1)
        qpos = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, scores.ndim - 2) // g
        valid = kpos <= qpos                    # implies kpos < kv_len
        if window is not None:
            valid = valid & (qpos - kpos < window)
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_scr[...]                     # [Hb, tile_q * g, 1]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # mask the probabilities too: a fully-masked row would
        # otherwise turn exp(NEG_INF - NEG_INF) into ones
        p = jnp.exp(scores - m_new) * valid
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + _heads_dot(
            p if v_scale is None else p * v_scale, v, 0)
        m_scr[...] = m_new

    @pl.when(j == last)
    def _done():
        l = l_scr[...]
        # rows with no valid key (length-0 sequences) emit zeros
        o_ref[...] = (acc_scr[...] /
                      jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _kernel_ragged_prefetch(item_ref, tile_ref, held_ref, bt_ref, q_ref,
                            *refs, n_hb, pages, quantized, v_dim=None, **kw):
    """The kernel. Grid step ``n`` is work item ``n`` (``_work_list``):
    ``item_ref[n]`` = (tile x head-group, kv step), ``tile_ref[t]`` =
    that tile's (first, last real) query positions and (first, last)
    live kv steps; ``held_ref`` and ``bt_ref`` feed the index maps
    only. ``refs``:
    the pool handed in ``pages`` times (one (1, 2, Hb, block_s, hd)
    page block each, see the index map; (1, 1, Hb, block_s, hd) in the
    latent form, ``v_dim``), for int8 pages the scale array as often
    ((1, 2, Hb, block_s): the block already carries the step's heads),
    then the output block and the m / l / acc scratch."""
    del held_ref, bt_ref
    pool_refs, refs = refs[:pages], refs[pages:]
    n = pl.program_id(0)
    t, j = item_ref[0, n] // n_hb, item_ref[1, n]

    def join(plane):      # the step's P pages of one plane: [Hb, P*bs, hd]
        return jnp.concatenate([r[0, plane].astype(jnp.float32)
                                for r in pool_refs], axis=1)

    def tiles():
        k = join(0)
        # the latent form: the value is the row's leading columns
        return k, (join(1) if v_dim is None else k[..., :v_dim])

    scales = None
    if quantized:
        sc_refs, refs = refs[:pages], refs[pages:]

        def scales():
            # [2, Hb, P*bs] -> the (Hb, 1, P*bs) row vectors of the body
            sc = jnp.concatenate([r[0] for r in sc_refs], axis=-1)
            return sc[0][:, None, :], sc[1][:, None, :]
    o_ref, *scratch = refs
    _ragged_body(j, tile_ref[2, t], tile_ref[3, t], tile_ref[0, t],
                 tile_ref[1, t], tiles, q_ref.at[0], o_ref.at[0],
                 *scratch, scales=scales, **kw)


def _live_range(xp, pos0, pos_last, span, window):
    """Each tile's (first, last) LIVE kv step of ``span`` positions:
    from the step that holds the first key inside the tile's first
    query's window (0 without one) to the step of its last real
    query's position — THE count of what the launch walks, in plain
    integer arithmetic on ``xp`` (jnp over the traced lengths in
    ``_work_list``; numpy on the host in ``live_steps``)."""
    last = xp.maximum(pos_last, 0) // span
    if window is None:
        return xp.zeros_like(last), last
    return xp.minimum(xp.maximum(pos0 - window + 1, 0) // span, last), last


def live_steps(plan: LaunchPlan, q_lens, kv_lens, block_s: int,
               g: int = 1, window=None) -> int:
    """The grid steps ``plan``'s launch walks for a packed batch (host
    lengths): the size of ``_work_list``'s list, counted by the same
    ``_live_range`` over the same tiles. For the telemetry gauge;
    ``plan.grid_steps`` is the bound."""
    tile_seq, tile_off, tile_n, _, _ = _tile_layout(
        q_lens, resolve_tile_q(q_lens, g=g))
    pos0 = (np.asarray(kv_lens, np.int64)
            - np.asarray(q_lens, np.int64))[tile_seq] + tile_off
    first, last = _live_range(np, pos0, pos0 + tile_n - 1,
                              plan.pages * block_s, window)
    return int((last - first + 1).sum()) * (plan.grid[0] // len(tile_seq))


def _work_list(pos0, pos_last, tseq, n_hb, steps, P, block_s, window):
    """What the launch's ONE grid axis walks: an item a LIVE (tile x
    head-group, kv step) pair, tile by tile, a tile's steps in order —
    so a grid step is never spent on a kv step past a tile's causal
    frontier or wholly behind its window (each costs the scalar core
    an index map and a block comparison a page operand, live or not:
    PERF.md, PR 33), and the grid's size is the number of live steps,
    handed to the call as a traced scalar. All of it is computed here,
    by XLA, from the traced lengths, elementwise over at most
    N = T * n_hb * steps items (no gather: the block table is read by
    the index maps, out of SMEM). ``pos0`` / ``pos_last`` [T]: each
    tile's first and last real query positions; ``tseq`` [T]: its
    sequence. Returns (count, items [2, N], tiles [5, T], held [T * P]):
    item n is (tile x head-group, kv step); a tile's (pos0, pos_last,
    first live step, last live step, sequence); and ``held[t * P + p]``,
    the LAST table entry page operand p of tile t may name: operand p
    of kv step j reads entry ``min(j * P + p, held)``, so where the
    step's pages run past the tile's frontier it re-names the block
    the pipeline already holds and no copy is issued for it (entry p
    where even its first page lies past it). A tile none of whose
    queries has a key (a length-0 row) keeps one item, which only
    writes its zeros."""
    T = pos0.shape[0]
    first, last = _live_range(jnp, pos0, pos_last, block_s * P, window)
    last_page = jnp.maximum(pos_last, 0) // block_s
    count = jnp.repeat(last - first + 1, n_hb)              # [T * n_hb]
    start = jnp.cumsum(count) - count
    N = T * n_hb * steps
    item_i = jnp.repeat(jnp.arange(T * n_hb, dtype=jnp.int32), count,
                        total_repeat_length=N)
    item_j = first[item_i // n_hb] + jnp.arange(N, dtype=jnp.int32) \
        - start[item_i]
    p = jnp.arange(P, dtype=jnp.int32)
    held = jnp.maximum(last_page[:, None] - p, 0) // P * P + p   # [T, P]
    return (count.sum(), jnp.stack([item_i, item_j]),
            jnp.stack([pos0, pos_last, first, last, tseq]),
            held.reshape(T * P).astype(jnp.int32))


def resolve_tile_q(q_lens, tile_q=None, g: int = 1) -> int:
    """Queries a tile: the caller's, else the longest segment up to
    DEFAULT_TILE_Q_CAP and to MAX_TILE_ROWS rows at ``g`` query heads a
    kv head."""
    if tile_q is None:
        tile_q = min(DEFAULT_TILE_Q_CAP, max(1, MAX_TILE_ROWS // g),
                     max(q_lens))
    return max(1, int(tile_q))


def _tile_layout(q_lens, tile_q):
    """Host-side tile descriptors for a packed ragged batch: returns
    (tile_seq [T], tile_off [T], tile_n [T], pad_idx [T*tile_q],
    out_idx [R]) — which sequence each tile serves, its query offset
    within that sequence, its REAL row count (a partial tail tile's
    causal frontier stops at its last real query, not at tile_q), the
    packed-row index feeding each padded-tile row (pad rows point at
    row 0, their outputs are dropped), and where each packed row's
    output lives in the padded layout."""
    tile_seq, tile_off, tile_n, pad_idx = [], [], [], []
    out_idx = np.empty(sum(q_lens), np.int32)
    r0 = 0
    for s, ql in enumerate(q_lens):
        for off in range(0, ql, tile_q):
            t = len(tile_seq)
            tile_seq.append(s)
            tile_off.append(off)
            n = min(tile_q, ql - off)
            tile_n.append(n)
            pad_idx.extend(range(r0 + off, r0 + off + n))
            pad_idx.extend([0] * (tile_q - n))
            out_idx[r0 + off:r0 + off + n] = \
                np.arange(t * tile_q, t * tile_q + n)
        r0 += ql
    return (np.asarray(tile_seq, np.int32),
            np.asarray(tile_off, np.int32),
            np.asarray(tile_n, np.int32),
            np.asarray(pad_idx, np.int32), out_idx)


def paged_attention_ragged(q, kv_pool, block_tables, q_lens, kv_lens,
                           sm_scale=None, tile_q=None, tile_kv=None,
                           kv_scales=None, window=None, v_dim=None):
    """THE kernel: one launch scores a mixed prefill+decode+verify
    batch. q: [R, nh, hd] — every sequence's query rows packed
    back-to-back (R == sum(q_lens)). q_lens: STATIC per-sequence query
    counts (python ints; the packed shape depends on them, so they are
    compile-time like every other shape). kv_lens: int32 [n_seq] valid
    lengths INCLUDING each sequence's q_lens new rows (whose K/V must
    already sit in the pool). block_tables: int32 [n_seq, MB] — entry
    j is the pool row holding positions [j*bs, (j+1)*bs); entries past
    a sequence's allocation must point at a valid (e.g. reserved)
    block. Query i of sequence s sits at position
    kv_lens[s] - q_lens[s] + i and attends causally (so q_lens[s] == 1
    is a decode row, == K+1 a speculative verify, == C a prefill
    chunk). Zero-length sequences contribute no rows and are skipped.
    ``kv_scales``: per-page dequantization scales
    [num_blocks, 2, nkv, block_size] for an int8 ``kv_pool`` (None =
    the pool holds real values) — see the module docstring.
    ``window`` (static int or None): a sliding layer — query at
    position i sees key j iff 0 <= i - j < window; pages wholly behind
    a tile's first query's window are skipped like pages past the
    causal frontier (no copy issued on the chip).
    ``v_dim`` (static int or None): the LATENT form. The pool holds ONE
    row a position, [num_blocks, 1, nkv, block_size, hd], which is the
    key, and its leading ``v_dim`` columns are the value (absorbed
    multi-head latent attention: nh query heads over one head of
    kv_lora_rank + rope columns). A page is DMA'd once, the output is
    [R, nh, v_dim], and ``sm_scale`` must be handed in: the scale is the
    un-absorbed head's, not ``hd ** -0.5``.
    Returns [R, nh, hd] in packed order ([R, nh, v_dim] in the latent
    form)."""
    q_lens = tuple(int(x) for x in q_lens)
    R, nh, hd = q.shape
    if R != sum(q_lens):
        raise ValueError(f"packed q has {R} rows, q_lens sum to "
                         f"{sum(q_lens)}")
    planes, od = (2, hd) if v_dim is None else (1, int(v_dim))
    if kv_pool.shape[1] != planes:
        raise ValueError(
            f"a pool of {kv_pool.shape[1]} plane(s) a page does not "
            f"match v_dim={v_dim}: the K/V form has 2, the latent form 1")
    if v_dim is not None and (sm_scale is None or kv_scales is not None
                              or not 0 < od <= hd):
        raise ValueError("the latent form takes sm_scale, a v_dim of at "
                         "most the row's width, and no int8 scales")
    if R == 0:
        return q[..., :od]   # nothing to score — no launch, not counted
    from ...parallel.mesh import inside_spmd_region
    if not on_tpu() and inside_spmd_region("mp"):
        # callable under shard_map: the launch builds
        # its tile layout from static host metadata, but the pallas
        # interpreter's emulated grid does not trace under a manual
        # mesh axis — inside an ``mp`` spmd region (the compiled
        # sharded step's body, a training shard_map) the launch
        # delegates to the jnp reference, which is pure traced ops.
        # Counted as a dispatch either way; on TPU the real kernel
        # traces fine and takes the normal path below.
        _DISPATCH["count"] += 1
        return paged_attention_ragged_reference(
            q, kv_pool, block_tables, q_lens, kv_lens,
            sm_scale=sm_scale, kv_scales=kv_scales, window=window,
            v_dim=v_dim)
    _DISPATCH["count"] += 1
    nkv, block_s = kv_pool.shape[2], kv_pool.shape[3]
    MB = block_tables.shape[1]
    if nh % nkv:
        raise ValueError(
            f"query heads {nh} are not a multiple of the pool's kv "
            f"heads {nkv} — neither a GQA group nor a matching "
            f"tensor-parallel head slice (sharded pools take "
            f"head_slice(q, shard, mp), one launch per shard)")
    g = nh // nkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    tile_q = resolve_tile_q(q_lens, tile_q, g=g)
    tile_seq, tile_off, tile_n, pad_idx, out_idx = \
        _tile_layout(q_lens, tile_q)
    T = tile_seq.shape[0]
    rows = tile_q * g

    lens = jnp.asarray(kv_lens, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    qlen_arr = jnp.asarray(q_lens, jnp.int32)
    tseq = jnp.asarray(tile_seq)
    # per-tile (first, LAST REAL) query positions (kv_lens may be
    # traced): the last real one is the causal frontier — a decode
    # row padded into a wide mixed-batch tile keeps its single
    # position, so the page sweep never runs past it
    pos0 = (lens[tseq] - qlen_arr[tseq]
            + jnp.asarray(tile_off)).astype(jnp.int32)
    pos_last = pos0 + jnp.asarray(tile_n) - 1

    # pad + fold: [R, nh, hd] -> [T, nkv, tile_q*g, hd]
    qp = jnp.take(q.reshape(R, nkv, g, hd), jnp.asarray(pad_idx),
                  axis=0)
    qp = jnp.transpose(qp.reshape(T, tile_q, nkv, g, hd),
                       (0, 2, 1, 3, 4)).reshape(T, nkv, rows, hd)

    # a grid step carries Hb heads of P pages (launch_plan). A
    # sequence's pages are not contiguous in the pool, so the pool is
    # handed to the call P times and operand p's index map names the
    # work item's page p
    plan = launch_plan(T, nkv, rows, MB, block_s, hd,
                       kv_pool.dtype.itemsize,
                       q_itemsize=q.dtype.itemsize,
                       quantized=kv_scales is not None,
                       tile_kv=tile_kv, v_dim=v_dim)
    Hb, P = plan.heads, plan.pages
    n_hb = nkv // Hb
    count, items, tiles, held = _work_list(
        pos0, pos_last, tseq, n_hb, plan.grid[1], P, block_s, window)

    def split(i):
        return (i, 0) if n_hb == 1 else (i // n_hb, i % n_hb)

    def q_map(n, item_, tile_, held_, bt_):
        return split(item_[0, n]) + (0, 0)

    def page_map(p, tail):
        def index(n, item_, tile_, held_, bt_):
            t, head = split(item_[0, n])
            entry = jnp.minimum(item_[1, n] * P + p, held_[t * P + p])
            return (bt_[tile_[4, t], entry], 0, head) + tail
        return index

    # the pages straight out of the pool rows the block table names —
    # the whole paged-attention trick
    in_specs = [pl.BlockSpec((1, Hb, rows, hd), q_map)] + [
        pl.BlockSpec((1, planes, Hb, block_s, hd), page_map(p, (0, 0)))
        for p in range(P)]
    operands = [items, tiles, held, bt, qp] + [kv_pool] * P
    if kv_scales is not None:
        # each scale page rides the SAME lookup as its int8 page
        in_specs += [
            pl.BlockSpec((1, 2, Hb, block_s), page_map(p, (0,)))
            for p in range(P)]
        operands += [jnp.asarray(kv_scales)] * P
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # the work list and the block table (SMEM)
        grid=(count,),           # one step a live (tile, kv step)
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hb, rows, od), q_map),
        scratch_shapes=[pltpu.VMEM((Hb, rows, 1), jnp.float32),
                        pltpu.VMEM((Hb, rows, 1), jnp.float32),
                        pltpu.VMEM((Hb, rows, od), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_ragged_prefetch, n_hb=n_hb,
                          pages=P, quantized=kv_scales is not None,
                          v_dim=v_dim,
                          block_s=block_s * P, sm_scale=scale, g=g,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, nkv, rows, od), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=not on_tpu(),    # off the chip: the same call, interpreted
    )(*operands)

    # unfold + unpad back to the packed row order
    out = jnp.transpose(out.reshape(T, nkv, tile_q, g, od),
                        (0, 2, 1, 3, 4)).reshape(T * tile_q, nh, od)
    return jnp.take(out, jnp.asarray(out_idx), axis=0)


# --- the three phase entry points: thin wrappers over the ragged path -

def paged_attention(q, kv_pool, block_tables, seq_lens, sm_scale=None,
                    kv_scales=None, window=None):
    """Decode: q [B, nh, hd] (one query per sequence), seq_lens int32
    [B] valid lengths. A ragged launch with q_lens = (1,)*B and
    tile_q = 1 (no padding rows). Returns [B, nh, hd]."""
    return paged_attention_ragged(
        q, kv_pool, block_tables, (1,) * q.shape[0], seq_lens,
        sm_scale=sm_scale, tile_q=1, kv_scales=kv_scales,
        window=window)


def paged_attention_multi(q, kv_pool, block_tables, seq_lens,
                          sm_scale=None, kv_scales=None, window=None):
    """Multi-query verify (speculative decode): q [B, n_q, nh, hd],
    query i of row b at position seq_lens[b] - n_q + i, masked
    causally. seq_lens INCLUDE the n_q new tokens. A ragged launch
    with q_lens = (n_q,)*B and tile_q = n_q (each sequence is one
    tile, so every page is DMA'd once per sequence*kv-head). Returns
    [B, n_q, nh, hd]."""
    B, n_q, nh, hd = q.shape
    out = paged_attention_ragged(
        q.reshape(B * n_q, nh, hd), kv_pool, block_tables,
        (n_q,) * B, seq_lens, sm_scale=sm_scale, tile_q=n_q,
        kv_scales=kv_scales, window=window)
    return out.reshape(B, n_q, nh, hd)


def paged_attention_prefill(q, kv_pool, block_tables, start_pos,
                            sm_scale=None, tile_q=None,
                            kv_scales=None, window=None):
    """Chunked prefill: q [B, C, nh, hd] holds one prompt chunk per
    sequence, query i of row b at absolute position start_pos[b] + i.
    A ragged launch with q_lens = (C,)*B, kv_lens = start_pos + C and
    a query-tile grid (default tile_q = min(C, 64)) whose pages past
    each tile's causal frontier are skipped — prefill work is
    O(tokens written), not O(page capacity). Returns [B, C, nh, hd]."""
    B, C, nh, hd = q.shape
    if tile_q is None:
        tile_q = min(C, DEFAULT_TILE_Q_CAP)
    lens = jnp.asarray(start_pos, jnp.int32) + C
    out = paged_attention_ragged(
        q.reshape(B * C, nh, hd), kv_pool, block_tables, (C,) * B,
        lens, sm_scale=sm_scale, tile_q=tile_q, kv_scales=kv_scales,
        window=window)
    return out.reshape(B, C, nh, hd)


# --- references: ONE ragged reference, per-phase ones delegate --------

def gather_pages(kv_pool, block_tables, kv_scales=None, v_dim=None):
    """Pure-jnp page gather: materialize the block-table indirection as
    dense K/V. kv_pool: [NB, 2, nkv, bs, hd]; block_tables: int32
    [B, MB]. Returns (k, v) each [B, MB*bs, nkv, hd] — the layout
    decode_attention consumes. Positions past a sequence's length hold
    whatever its (trash/stale) pages hold; callers mask by length.
    ``kv_scales`` ([NB, 2, nkv, bs], int8 pools) dequantizes the
    gathered pages to float32 — the ONE place the fallback layout
    learns quantization, shared by every CPU/jnp serving path.
    ``v_dim``: the latent form, kv_pool [NB, 1, nkv, bs, hd]; v is the
    leading ``v_dim`` columns of k."""
    pages = kv_pool[jnp.asarray(block_tables, jnp.int32)]
    if kv_scales is not None:
        sc = jnp.asarray(kv_scales)[jnp.asarray(block_tables,
                                                jnp.int32)]
        pages = pages.astype(jnp.float32) * sc[..., None]
    # [B, MB, 2, nkv, bs, hd] -> [B, MB, bs, nkv, hd] per K/V
    k = jnp.moveaxis(pages[:, :, 0], 2, 3)
    B, MB, bs, nkv, hd = k.shape
    k = k.reshape(B, MB * bs, nkv, hd)
    if v_dim is not None:
        return k, k[..., :v_dim]
    v = jnp.moveaxis(pages[:, :, 1], 2, 3)
    return k, v.reshape(B, MB * bs, nkv, hd)


def paged_attention_ragged_reference(q, kv_pool, block_tables, q_lens,
                                     kv_lens, sm_scale=None,
                                     kv_scales=None, window=None,
                                     v_dim=None):
    """jnp reference for the ragged kernel — and the ONE place the
    reference semantics live: the per-phase ``*_reference`` functions
    below are thin delegations, so kernel and reference can no longer
    drift apart per phase. Gather pages dense (dequantizing int8
    pages through their scales), then per-sequence masked softmax
    with each query at kv_lens[s] - q_lens[s] + i."""
    q_lens = tuple(int(x) for x in q_lens)
    R, nh, hd = q.shape
    if R == 0:
        return q if v_dim is None else q[..., :v_dim]
    nkv = kv_pool.shape[2]
    g = nh // nkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    k, v = gather_pages(kv_pool, block_tables, kv_scales=kv_scales,
                        v_dim=v_dim)             # [n_seq, S, nkv, hd]
    S = k.shape[1]
    k = jnp.repeat(k, g, axis=2)                 # GQA: broadcast kv heads
    v = jnp.repeat(v, g, axis=2)
    lens = jnp.asarray(kv_lens, jnp.int32)
    outs, r0 = [], 0
    for s, ql in enumerate(q_lens):
        if ql == 0:
            continue
        qs = q[r0:r0 + ql].astype(jnp.float32)   # [ql, nh, hd]
        scores = jnp.einsum("qhd,shd->hqs", qs,
                            k[s].astype(jnp.float32)) * scale
        qpos = (lens[s] - ql) + jnp.arange(ql)[None, :, None]
        kpos = jnp.arange(S)[None, None, :]
        valid = kpos <= qpos
        if window is not None:
            valid = valid & (qpos - kpos < window)
        p = jax.nn.softmax(jnp.where(valid, scores, NEG_INF), axis=-1)
        # rows with no valid key (inactive: qpos < 0) -> zeros
        p = jnp.where(valid & (qpos >= 0), p, 0.0)
        outs.append(jnp.einsum("hqs,shd->qhd", p,
                               v[s].astype(jnp.float32)).astype(q.dtype))
        r0 += ql
    return jnp.concatenate(outs, axis=0)


def paged_attention_reference(q, kv_pool, block_tables, seq_lens,
                              sm_scale=None, kv_scales=None):
    """Decode reference = ragged reference at q_lens all 1."""
    return paged_attention_ragged_reference(
        q, kv_pool, block_tables, (1,) * q.shape[0], seq_lens,
        sm_scale=sm_scale, kv_scales=kv_scales)


def paged_attention_multi_reference(q, kv_pool, block_tables, seq_lens,
                                    sm_scale=None, kv_scales=None):
    """Multi-query reference = ragged reference at uniform q_lens."""
    B, n_q, nh, hd = q.shape
    out = paged_attention_ragged_reference(
        q.reshape(B * n_q, nh, hd), kv_pool, block_tables,
        (n_q,) * B, seq_lens, sm_scale=sm_scale, kv_scales=kv_scales)
    return out.reshape(B, n_q, nh, hd)


def paged_attention_prefill_reference(q, kv_pool, block_tables,
                                      start_pos, sm_scale=None,
                                      kv_scales=None):
    """Prefill reference: a chunk at start S IS a multi-query sweep
    with seq_lens = S + C (its queries sit at lens - n_q + i)."""
    C = q.shape[1]
    lens = jnp.asarray(start_pos, jnp.int32) + C
    return paged_attention_multi_reference(q, kv_pool, block_tables,
                                           lens, sm_scale=sm_scale,
                                           kv_scales=kv_scales)
