"""Paged KV-cache subsystem (inference/paged_cache.py + scheduler.py).

The cache layout is a protocol: the same FusedMultiTransformer decode
must produce BIT-IDENTICAL hiddens through a PagedKVCache (block pool
+ block tables) and through the dense slot cache — including after a
preempt -> re-prefill cycle and after freed blocks are reused by a new
request. The paged engine must also sustain strictly more concurrent
sequences than the dense engine under the same simulated HBM budget
(the whole point of paging)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import layer_jit
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (BlockAllocator, BlockOOM,
                                  ContinuousBatchingEngine,
                                  PagedKVCache, PagedServingEngine)

D, HEADS, FFN, LAYERS = 32, 4, 64, 2
BS, MB = 16, 4            # 16-token pages, 4 pages/seq
MAXLEN = BS * MB          # dense max_len == paged per-seq capacity


def _model():
    paddle.seed(0)
    return FusedMultiTransformer(D, HEADS, FFN, num_layers=LAYERS)


def _prompt(rng, n):
    return paddle.to_tensor(rng.randn(n, D).astype(np.float32))


def _admit(eng, prompt):
    """submit() + drain the admission event -> (slot, last_hidden)."""
    rid = eng.submit(prompt)
    admitted = {r: (s, h) for r, s, h in eng.admitted}
    eng.admitted.clear()
    assert rid in admitted, "expected immediate admission"
    return admitted[rid]


# deterministic greedy readout: hidden -> token -> next embedding.
# identical hiddens => identical token streams.
_RNG = np.random.RandomState(1234)
_VOCAB = 50
_W_OUT = _RNG.randn(D, _VOCAB).astype(np.float32)
_EMBED = _RNG.randn(_VOCAB, D).astype(np.float32)


def _readout(hidden_row):
    tok = int(np.argmax(hidden_row @ _W_OUT))
    return tok, _EMBED[tok]


def _assert_same_to_a_few_ulp(got, want):
    """Hiddens of two CPU runs that BATCH THEIR ROWS DIFFERENTLY (a
    prompt in one call against the same prompt in chunks, with its
    cached prefix skipped, or through the dense engine's prefill
    program against the paged chunk's): XLA-CPU's matmul is not
    row-count invariant, so the same row comes out one float32 ulp
    apart depending on the call it rides in (measured: 22-27 of 32
    floats differ, by at most 9.5e-7 at magnitudes to 16). Compared 8
    ulp wide (rtol 1e-6, and atol 2e-6 for entries near zero), not bit
    for bit; token streams, hit counts, block accounting and step
    counts around these calls stay exact, and so does every comparison
    of a run with a REPLAY of the same program on the same inputs
    (crash-replay, respawn, snapshot-restore: that is safety)."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=2e-6)


class TestBlockAllocator:
    def test_freelist_refcount_oom(self):
        a = BlockAllocator(6)          # block 0 reserved
        assert a.num_free == 5
        b1 = a.alloc(2)
        assert 0 not in b1 and a.num_free == 3
        a.ref(b1)                      # shared prefix: two owners
        a.free(b1)
        assert a.num_free == 3         # still held by the fork
        a.free(b1)
        assert a.num_free == 5
        a.alloc(5)
        with pytest.raises(BlockOOM):
            a.alloc(1)

    def test_trash_block_protected(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError):
            a.free([0])
        assert 0 not in a.alloc(3)  # trash block never handed out

    def test_error_paths(self):
        """Misuse must fail loudly, not corrupt the refcounts: ref of a
        block nobody owns, double free, and freeing after the last
        owner left."""
        a = BlockAllocator(6)
        with pytest.raises(ValueError, match="ref of unallocated"):
            a.ref([3])                 # never allocated
        b = a.alloc(1)
        a.free(b)
        with pytest.raises(ValueError, match="double free"):
            a.free(b)
        with pytest.raises(ValueError, match="ref of unallocated"):
            a.ref(b)                   # freed: no owner to share with
        assert a.num_free == 5         # failed calls changed nothing

    def test_fork_write_prefill_cow_split_rewires_not_copies(self):
        """fork -> write_prefill on the shared block takes the
        copy=False COW split: the writer gets a fresh page (its content
        is about to be fully rewritten, so no pool copy), the peer
        keeps the original, and the refcounts return to 1/1."""
        model = _model()
        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB)
        scratch = model.gen_cache(1, MAXLEN)
        rng = np.random.RandomState(11)
        with paddle.no_grad():
            _, rc = model(_prompt(rng, 10).unsqueeze(0), caches=scratch,
                          time_step=0)
        cache.ensure(0, 10)
        cache.write_prefill(0, rc, 10)
        shared = cache.seq_blocks[0][0]
        cache.fork(0, 1, 10)
        assert cache.allocator.refcount[shared] == 2
        before = np.asarray(cache.pools[0].numpy())[shared].copy()
        with paddle.no_grad():
            _, rc2 = model(_prompt(rng, 9).unsqueeze(0), caches=scratch,
                           time_step=0)
        cache.ensure(1, 9)
        cache.write_prefill(1, rc2, 9)
        new = cache.seq_blocks[1][0]
        assert new != shared
        assert cache.allocator.refcount[shared] == 1   # slot 0 only
        assert cache.allocator.refcount[new] == 1      # slot 1 only
        assert cache.block_tables[1, 0] == new
        # peer's page was never touched by the split or the prefill
        np.testing.assert_array_equal(
            np.asarray(cache.pools[0].numpy())[shared], before)


class TestTruncateRollback:
    """Speculative-decode rollback at the allocator level:
    PagedKVCache.truncate drops the block-table tail refcount- and
    cached-free-aware (inference/speculative.py rolls back rejected
    windows through it every round)."""

    def _cache(self, prefix_cache=False, num_blocks=10):
        return PagedKVCache(1, HEADS, D // HEADS, block_size=BS,
                            num_blocks=num_blocks, max_seqs=2,
                            max_blocks_per_seq=MB,
                            prefix_cache=prefix_cache)

    def test_truncate_across_block_boundary(self):
        """A rollback spanning several pages frees every block past
        the new boundary in one call; the kept partial block stays."""
        cache = self._cache()
        cache.ensure(0, 3 * BS + 5)            # 4 blocks
        assert len(cache.seq_blocks[0]) == 4
        free_before = cache.allocator.num_free
        cache.truncate(0, BS + 3)              # keep 2 blocks
        assert len(cache.seq_blocks[0]) == 2
        assert cache.allocator.num_free == free_before + 2
        assert (cache.block_tables[0, 2:] == 0).all()
        # re-extend reuses the freed blocks (allocate-on-write again)
        cache.ensure(0, 3 * BS)
        assert len(cache.seq_blocks[0]) == 3
        # truncate to an exact boundary drops nothing extra
        cache.truncate(0, 2 * BS)
        assert len(cache.seq_blocks[0]) == 2
        # no-op when nothing lies past the boundary
        cache.truncate(0, 2 * BS - 1)
        assert len(cache.seq_blocks[0]) == 2
        with pytest.raises(ValueError):
            cache.truncate(0, -1)

    def test_truncate_shared_page_derefs_not_frees(self):
        """Truncating into a fork-shared (refcount > 1) page must drop
        ONE owner: the peer keeps the block and its contents."""
        model = _model()
        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB)
        scratch = model.gen_cache(1, MAXLEN)
        rng = np.random.RandomState(21)
        with paddle.no_grad():
            _, rc = model(_prompt(rng, 2 * BS).unsqueeze(0),
                          caches=scratch, time_step=0)
        cache.ensure(0, 2 * BS)
        cache.write_prefill(0, rc, 2 * BS)
        cache.fork(0, 1, 2 * BS)               # both blocks shared
        shared = list(cache.seq_blocks[0])
        assert all(cache.allocator.refcount[b] == 2 for b in shared)
        before = np.asarray(cache.pools[0].numpy())[shared[1]].copy()
        free_before = cache.allocator.num_free
        cache.truncate(1, BS)                  # slot 1 drops block 1
        assert cache.seq_blocks[1] == shared[:1]
        assert cache.allocator.refcount[shared[1]] == 1   # deref'd
        assert cache.allocator.num_free == free_before    # NOT freed
        np.testing.assert_array_equal(
            np.asarray(cache.pools[0].numpy())[shared[1]], before)
        # slot 0 still owns both; truncating IT now really frees
        cache.truncate(0, BS)
        assert cache.allocator.refcount[shared[1]] == 0
        assert cache.allocator.num_free == free_before + 1

    def test_truncate_to_boundary_parks_indexed_block_then_resurrects(self):
        """Truncating a hash-indexed block to its boundary parks it
        CACHED-FREE (not the free list); re-extending the same prefix
        (a new adoption of the same chain) resurrects the very same
        pool block instead of recomputing it."""
        from paddle_tpu.inference import chain_block_hashes
        model = _model()
        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB,
                                      prefix_cache=True)
        scratch = model.gen_cache(1, MAXLEN)
        rng = np.random.RandomState(22)
        prompt = _prompt(rng, 2 * BS)
        with paddle.no_grad():
            _, rc = model(prompt.unsqueeze(0), caches=scratch,
                          time_step=0)
        cache.ensure(0, 2 * BS)
        cache.write_prefill(0, rc, 2 * BS)
        hashes = chain_block_hashes(np.asarray(prompt.numpy()), BS)
        cache.register_prefix(0, hashes)
        b1 = cache.seq_blocks[0][1]
        assert cache.allocator.num_cached == 0
        cache.truncate(0, BS)                  # drop the indexed page
        assert cache.allocator.num_cached == 1  # parked, not freed
        assert cache.match_prefix(hashes) == cache.seq_blocks[0] + [b1]
        # re-extend via adoption on a fresh slot: the parked block
        # resurrects (same id, no recompute, no pool draw)
        n = cache.adopt_prefix(1, hashes)
        assert n == 2
        assert cache.seq_blocks[1][1] == b1
        assert cache.allocator.num_cached == 0
        assert cache.allocator.refcount[b1] == 1

    def test_truncate_then_append_cow_splits_kept_shared_page(self):
        """After a rollback to mid-page of a SHARED page, the next
        append must still COW-split it (ensure's write-range split):
        the peer's view of the page never changes."""
        model = _model()
        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB)
        scratch = model.gen_cache(1, MAXLEN)
        rng = np.random.RandomState(23)
        with paddle.no_grad():
            _, rc = model(_prompt(rng, BS + 8).unsqueeze(0),
                          caches=scratch, time_step=0)
        cache.ensure(0, BS + 8)
        cache.write_prefill(0, rc, BS + 8)
        cache.fork(0, 1, BS + 8)
        shared = cache.seq_blocks[0][1]
        cache.truncate(1, BS + 4)              # keeps the shared page
        assert cache.seq_blocks[1][1] == shared
        before = np.asarray(cache.pools[0].numpy())[shared].copy()
        cache.ensure(1, BS + 5)                # next write: COW fires
        assert cache.seq_blocks[1][1] != shared
        assert cache.allocator.refcount[shared] == 1
        np.testing.assert_array_equal(
            np.asarray(cache.pools[0].numpy())[shared], before)


class TestBf16Pool:
    def test_bf16_pool_bytes_and_decode_smoke(self):
        """pool_bytes crashed on bfloat16 pools (np.dtype(str(...))
        can't parse ml_dtypes names); it must report 2 bytes/elem, and
        the paged append/decode path must run on a bf16 pool (appends
        cast to the pool dtype)."""
        hd = D // HEADS
        cache = PagedKVCache(1, HEADS, hd, block_size=8, num_blocks=4,
                             max_seqs=1, dtype="bfloat16")
        assert cache.pool_bytes() == 4 * 2 * HEADS * 8 * hd * 2
        cache.ensure(0, 1)
        rng = np.random.RandomState(12)
        q, k, v = (paddle.to_tensor(rng.randn(1, 1, HEADS, hd)
                                    .astype(np.float32))
                   for _ in range(3))
        out = cache.views[0].decode(q, k, v,
                                    np.zeros(1, np.int32))
        assert list(out.shape) == [1, 1, HEADS, hd]
        assert np.isfinite(np.asarray(out.numpy())).all()
        assert str(cache.pools[0].dtype) == "bfloat16"


class TestPagedDenseParity:
    def test_bitwise_identical_decode(self):
        """Same prompts, dense slots vs paged blocks: every decode
        hidden must be bit-identical (acceptance criterion), across a
        page boundary, and the greedy token streams must match."""
        model = _model()
        rng = np.random.RandomState(0)
        pa, pb = _prompt(rng, 5), _prompt(rng, 13)

        dense = ContinuousBatchingEngine(model, max_batch=2,
                                         max_len=MAXLEN)
        sa, la = dense.add_request(pa)
        sb, lb = dense.add_request(pb)
        paged = PagedServingEngine(model, max_batch=2, block_size=BS,
                                   num_blocks=9, max_blocks_per_seq=MB)
        psa, pla = _admit(paged, pa)
        psb, plb = _admit(paged, pb)
        np.testing.assert_array_equal(np.asarray(la.numpy()),
                                      np.asarray(pla.numpy()))

        toks_d, toks_p = [], []
        xd = np.zeros((2, 1, D), np.float32)
        xp = np.zeros((2, 1, D), np.float32)
        for (s, h, x, toks) in ((sa, la, xd, None), (sb, lb, xd, None),
                                (psa, pla, xp, None), (psb, plb, xp, None)):
            x[s, 0] = _readout(np.asarray(h.numpy())[0])[1]
        # 6 steps takes pb from 13 -> 19: crosses the 16-token page edge
        for _ in range(6):
            od = np.asarray(dense.step(paddle.to_tensor(xd)).numpy())
            op = np.asarray(paged.step(paddle.to_tensor(xp)).numpy())
            np.testing.assert_array_equal(od[sa], op[psa])
            np.testing.assert_array_equal(od[sb], op[psb])
            for s, toks, x, o in ((sa, toks_d, xd, od), (sb, toks_d, xd, od)):
                tok, emb = _readout(o[s, 0])
                toks.append(tok)
                x[s, 0] = emb
            for s, toks, x, o in ((psa, toks_p, xp, op), (psb, toks_p, xp, op)):
                tok, emb = _readout(o[s, 0])
                toks.append(tok)
                x[s, 0] = emb
        assert toks_d == toks_p
        # growth actually went paged: pb's slot holds 2 pages now
        assert len(paged.cache.seq_blocks[psb]) == 2

    def test_block_reuse_is_exact(self):
        """A finishes and releases; B reuses A's freed blocks. Stale
        page contents must not perturb B (mask underflow is exact)."""
        model = _model()
        rng = np.random.RandomState(2)
        pa, pb = _prompt(rng, 6), _prompt(rng, 5)

        paged = PagedServingEngine(model, max_batch=2, block_size=BS,
                                   num_blocks=5, max_blocks_per_seq=MB)
        psa, pla = _admit(paged, pa)
        xp = np.zeros((2, 1, D), np.float32)
        xp[psa, 0] = np.asarray(pla.numpy())[0]
        for _ in range(3):
            op = np.asarray(paged.step(paddle.to_tensor(xp)).numpy())
            xp = op[:, :1].copy()
        a_blocks = set(paged.cache.seq_blocks[psa])
        paged.release(psa)
        psb, plb = _admit(paged, pb)
        assert set(paged.cache.seq_blocks[psb]) & a_blocks, \
            "B should reuse A's freed blocks"

        dense = ContinuousBatchingEngine(model, max_batch=2,
                                         max_len=MAXLEN)
        sb, lb = dense.add_request(pb)
        np.testing.assert_array_equal(np.asarray(plb.numpy()),
                                      np.asarray(lb.numpy()))
        xp = np.zeros((2, 1, D), np.float32)
        xd = np.zeros((2, 1, D), np.float32)
        xp[psb, 0] = np.asarray(plb.numpy())[0]
        xd[sb, 0] = np.asarray(lb.numpy())[0]
        for _ in range(4):
            op = np.asarray(paged.step(paddle.to_tensor(xp)).numpy())
            od = np.asarray(dense.step(paddle.to_tensor(xd)).numpy())
            np.testing.assert_array_equal(op[psb], od[sb])
            xp, xd = op[:, :1].copy(), od[:, :1].copy()


class TestPreemption:
    def test_preempt_reprefill_cycle(self):
        """Pool pressure evicts the youngest request (pages freed,
        request re-queued); the survivor decodes on bit-identically,
        and after re-admission the victim's re-prefilled decode is
        bit-identical to a dense engine given the same history."""
        model = _model()
        rng = np.random.RandomState(1)
        pa, pb = _prompt(rng, 14), _prompt(rng, 14)

        # 5 usable pages: A+B fit until both need a 3rd page at len 32
        eng = PagedServingEngine(model, max_batch=2, block_size=BS,
                                 num_blocks=6, max_blocks_per_seq=MB)
        sa, ha = _admit(eng, pa)
        sb, hb = _admit(eng, pb)
        # dense shadow of A alone (same 2-row batch shape)
        dense_a = ContinuousBatchingEngine(model, max_batch=2,
                                           max_len=MAXLEN)
        da, dha = dense_a.add_request(pa)
        assert da == sa
        np.testing.assert_array_equal(np.asarray(ha.numpy()),
                                      np.asarray(dha.numpy()))

        x = np.zeros((2, 1, D), np.float32)
        x[sa, 0] = np.asarray(ha.numpy())[0]
        x[sb, 0] = np.asarray(hb.numpy())[0]
        xd = np.zeros((2, 1, D), np.float32)
        xd[da, 0] = np.asarray(dha.numpy())[0]
        preempt_seen = False
        for _ in range(20):
            o = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            od = np.asarray(dense_a.step(paddle.to_tensor(xd)).numpy())
            # A must be untouched by B's presence OR eviction
            np.testing.assert_array_equal(o[sa], od[da])
            x = o[:, :1].copy()
            xd = od[:, :1].copy()
            if eng.preempted:
                assert eng.preempted == [1]  # B (younger) evicted
                eng.preempted.clear()
                preempt_seen = True
                assert [r.rid for r in eng.queue] == [1]
        assert preempt_seen
        req_b = eng.queue[0]
        assert req_b.preemptions == 1
        # B's recorded history covers prompt + every consumed input
        assert len(req_b.history) == 14 + (32 - 14)

        # release A -> continuous refill re-prefills B from history
        eng.release(sa)
        (rid, slot, hb2), = eng.admitted
        eng.admitted.clear()
        assert rid == 1 and eng.lens[slot] == len(req_b.history)

        # dense engine fed B's FULL history as its prompt == the
        # re-prefill contract (preemption is semantically a restart)
        hist = paddle.to_tensor(np.stack(req_b.history))
        dense_b = ContinuousBatchingEngine(model, max_batch=2,
                                           max_len=MAXLEN)
        db, dhb = dense_b.add_request(hist)
        np.testing.assert_array_equal(np.asarray(hb2.numpy()),
                                      np.asarray(dhb.numpy()))
        xp = np.zeros((2, 1, D), np.float32)
        xd = np.zeros((2, 1, D), np.float32)
        xp[slot, 0] = np.asarray(hb2.numpy())[0]
        xd[db, 0] = np.asarray(dhb.numpy())[0]
        for _ in range(4):
            op = np.asarray(eng.step(paddle.to_tensor(xp)).numpy())
            od = np.asarray(dense_b.step(paddle.to_tensor(xd)).numpy())
            np.testing.assert_array_equal(op[slot], od[db])
            xp, xd = op[:, :1].copy(), od[:, :1].copy()

    def test_pool_too_small_sheds_request_not_engine(self):
        """A sequence that cannot grow even with every other request
        evicted is SHED — a FAILED_OOM RequestOutcome, pages freed —
        instead of raising out of step() (resilience layer): the
        engine survives and serves the next request."""
        from paddle_tpu.inference import RequestOutcome
        model = _model()
        rng = np.random.RandomState(3)
        eng = PagedServingEngine(model, max_batch=1, block_size=8,
                                 num_blocks=2, max_blocks_per_seq=4)
        rid, _ = _admit(eng, _prompt(rng, 7)), None
        x = paddle.to_tensor(np.zeros((1, 1, D), np.float32))
        eng.step(x)  # 7 -> 8 still fits the single page
        out = eng.step(x)  # needs a 2nd page, no victim available
        assert out is None                  # shed, not crashed
        (oc,) = eng.outcomes
        assert oc.status == RequestOutcome.FAILED_OOM
        assert "pool exhausted" in oc.reason
        assert eng.resilience_stats.shed == 1
        assert eng.num_active == 0 and not eng.queue
        assert eng.cache.seq_blocks[0] == []    # pages freed
        eng.check_invariants()
        # the engine is still serviceable for a pool-sized request
        eng.outcomes.clear()
        _admit(eng, _prompt(rng, 5))
        assert eng.step(x) is not None
        # a truly empty engine still flags caller misuse
        eng.release(0)
        with pytest.raises(RuntimeError, match="no active slots"):
            eng.step(x)


class TestSchedulerPolicy:
    def test_strictly_more_concurrency_than_dense(self):
        """ACCEPTANCE: under the same simulated HBM budget (identical
        KV-pool bytes), the paged engine sustains strictly more
        concurrent sequences than the dense engine."""
        model = _model()
        rng = np.random.RandomState(4)
        dense = ContinuousBatchingEngine(model, max_batch=2,
                                         max_len=MAXLEN)
        # same token budget: 2 slots * 64 == 8 pages * 16
        paged = PagedServingEngine(model, max_batch=8, block_size=BS,
                                   num_blocks=8, max_blocks_per_seq=MB)
        dense_bytes = sum(
            int(np.prod(c.shape)) * 4 for c in dense.caches)
        assert paged.cache.pool_bytes() <= dense_bytes

        prompts = [_prompt(rng, 7) for _ in range(8)]
        for p in prompts[:2]:
            dense.add_request(p)
        assert dense.free_slots == 0          # dense caps at 2
        for p in prompts:
            paged.submit(p)
        # 7 usable pages -> 7 concurrent 7-token sequences; the 8th
        # waits in the queue under block-budget admission control
        assert paged.num_active == 7
        assert paged.num_active > dense.max_batch  # strict
        assert len(paged.queue) == 1

        x = paddle.to_tensor(np.zeros((8, 1, D), np.float32))
        o = paged.step(x)                     # all 7 advance together
        assert o is not None and list(o.shape) == [8, 1, D]
        assert int(paged.lens[paged.active].min()) == 8

        # releasing one slot refills from the queue (continuous refill)
        victim = int(np.flatnonzero(paged.active)[0])
        paged.release(victim)
        assert paged.num_active == 7 and not paged.queue

    def test_capacity_finish_reported_not_stalling(self):
        """A sequence at page capacity is auto-released + reported;
        the rest of the batch keeps stepping (dense satellite twin)."""
        model = _model()
        rng = np.random.RandomState(5)
        eng = PagedServingEngine(model, max_batch=2, block_size=8,
                                 num_blocks=8, max_blocks_per_seq=2)
        assert eng.max_len == 16
        sa, ha = _admit(eng, _prompt(rng, 12))
        sb, hb = _admit(eng, _prompt(rng, 8))
        x = np.zeros((2, 1, D), np.float32)
        x[sa, 0] = np.asarray(ha.numpy())[0]
        x[sb, 0] = np.asarray(hb.numpy())[0]
        for _ in range(4):                    # A: 12 -> 16 (capacity)
            o = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            x = o[:, :1].copy()
        assert not eng.finished
        out = eng.step(paddle.to_tensor(x))   # A retired, B steps on
        assert out is not None
        assert eng.finished == [(0, sa, 16)]
        assert not eng.active[sa] and eng.active[sb]
        assert eng.lens[sb] == 13
        # freed pages are back in the pool
        assert eng.cache.seq_blocks[sa] == []

    def test_guards(self):
        model = _model()
        rng = np.random.RandomState(6)
        eng = PagedServingEngine(model, max_batch=1, block_size=8,
                                 num_blocks=8, max_blocks_per_seq=2)
        with pytest.raises(RuntimeError):
            eng.step(paddle.to_tensor(np.zeros((1, 1, D), np.float32)))
        with pytest.raises(ValueError):
            eng.submit(_prompt(rng, 17))      # > 2 pages * 8


class TestChunkedPrefill:
    """Chunked paged prefill (scheduler.chunked_prefill +
    PagedKVCache.prefill_views): prompts stream straight into pages in
    causal chunks — no dense [2,1,H,max_len,D] scratch, no scatter
    pass — and every hidden stays BIT-IDENTICAL to the dense engine,
    because multi-row masked sdpa results are per-row invariant to
    chunk length and masked key extent (1-row chunks are the only
    hazard and are engineered away via MIN_PREFILL_SUFFIX_ROWS)."""

    CAP_BS, CAP_MB = 16, 10          # 160-token capacity: well past
    CAPACITY = CAP_BS * CAP_MB       # the old suite's 64-token scratch

    def _no_gen_cache(self, model):
        """Forbid dense KV scratch allocation for the engine's model:
        the memory-regression tripwire for the retired _scratch."""
        def boom(*a, **kw):
            raise AssertionError(
                "dense gen_cache scratch allocated during paged "
                "serving — chunked prefill must be scratchless")
        model.gen_cache = boom

    def test_long_prompt_streams_scratchless_bit_identical(self):
        """ACCEPTANCE: a prompt longer than the old tests' scratch
        capacity serves through multi-chunk prefill with ZERO dense
        scratch allocation, and admission hidden + every decode step
        are the dense engine's to a few ulp (it prefills 150 rows in
        one call, the paged engine 32 a call)."""
        model = _model()
        rng = np.random.RandomState(30)
        prompt = _prompt(rng, 150)           # 150 > 64, 5 chunks of 32
        dense = ContinuousBatchingEngine(model, max_batch=2,
                                         max_len=self.CAPACITY)
        ds, dh = dense.add_request(prompt)
        eng = PagedServingEngine(model, max_batch=2,
                                 block_size=self.CAP_BS,
                                 num_blocks=24,
                                 max_blocks_per_seq=self.CAP_MB,
                                 chunk_tokens=32)
        assert not hasattr(eng, "_scratch")
        self._no_gen_cache(model)
        slot, h = _admit(eng, prompt)
        _assert_same_to_a_few_ulp(h.numpy(), dh.numpy())
        assert eng.prefill_stats.chunks == 5
        assert eng.prefill_stats.prefill_tokens == 150
        x = np.zeros((2, 1, D), np.float32)
        xd = np.zeros((2, 1, D), np.float32)
        x[slot, 0] = xd[ds, 0] = np.asarray(h.numpy())[0]
        for _ in range(6):
            op = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            od = np.asarray(dense.step(paddle.to_tensor(xd)).numpy())
            _assert_same_to_a_few_ulp(op[slot], od[ds])
            x, xd = op[:, :1].copy(), od[:, :1].copy()

    def test_chunk_boundary_not_block_aligned(self):
        """Chunk boundaries need not align to page boundaries: a
        6-token chunk over 16-token pages (boundaries at 6, 12, 18,
        24 inside pages) must be bit-transparent."""
        model = _model()
        rng = np.random.RandomState(31)
        prompt = _prompt(rng, 29)
        dense = ContinuousBatchingEngine(model, max_batch=1,
                                         max_len=MAXLEN)
        ds, dh = dense.add_request(prompt)
        eng = PagedServingEngine(model, max_batch=1, block_size=BS,
                                 num_blocks=6, max_blocks_per_seq=MB,
                                 chunk_tokens=6)
        slot, h = _admit(eng, prompt)
        np.testing.assert_array_equal(np.asarray(dh.numpy()),
                                      np.asarray(h.numpy()))
        # 6,6,6,6 then the 5-token tail in one >=2-row chunk
        assert eng.prefill_stats.chunks == 5
        x = np.zeros((1, 1, D), np.float32)
        x[0, 0] = np.asarray(h.numpy())[0]
        xd = x.copy()
        for _ in range(4):
            op = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            od = np.asarray(dense.step(paddle.to_tensor(xd)).numpy())
            np.testing.assert_array_equal(op, od)
            x, xd = op[:, :1].copy(), od[:, :1].copy()

    def test_one_row_tail_chunk_is_avoided(self):
        """A prompt of chunk_tokens*k + 1 rows must NOT end on a 1-row
        chunk (the GEMV lowering would break bit-identity): the last
        chunk absorbs the leftover row."""
        model = _model()
        rng = np.random.RandomState(32)
        prompt = _prompt(rng, 33)            # 2*16 + 1
        dense = ContinuousBatchingEngine(model, max_batch=1,
                                         max_len=MAXLEN)
        ds, dh = dense.add_request(prompt)
        eng = PagedServingEngine(model, max_batch=1, block_size=BS,
                                 num_blocks=6, max_blocks_per_seq=MB,
                                 chunk_tokens=16)
        slot, h = _admit(eng, prompt)
        np.testing.assert_array_equal(np.asarray(dh.numpy()),
                                      np.asarray(h.numpy()))
        # 16 + 15 + 2: the middle chunk shrinks so the tail keeps
        # MIN_PREFILL_SUFFIX_ROWS rows (never 16 + 16 + 1)
        assert eng.prefill_stats.chunks == 3
        assert eng.prefill_stats.prefill_tokens == 33

    def test_write_prefill_chunk_matches_scratch_scatter(self):
        """The chunk-granular append API: writing projected K/V into
        pages chunk by chunk (incl. a write_start skip region) must
        leave the pool EXACTLY as the dense write_prefill scatter
        does, and never touch the skipped positions' pages."""
        hd = D // HEADS
        rng = np.random.RandomState(36)
        T = 2 * BS + 5
        # reference: the dense scatter path (scratch at max_len extent)
        kv = rng.randn(2, 1, HEADS, MAXLEN, hd).astype(np.float32)
        ref = PagedKVCache(1, HEADS, hd, block_size=BS, num_blocks=6,
                           max_seqs=1, max_blocks_per_seq=MB)
        ref.ensure(0, T)
        ref.write_prefill(0, [paddle.to_tensor(kv)], T)
        # chunked: two unaligned chunks of projected [1, C, H, hd]
        # rows through write_prefill_chunk
        ch = PagedKVCache(1, HEADS, hd, block_size=BS, num_blocks=6,
                          max_seqs=1, max_blocks_per_seq=MB)
        ch.ensure(0, T)
        k_rows = np.transpose(kv[0], (0, 2, 1, 3))[:, :T]  # [1,T,H,hd]
        v_rows = np.transpose(kv[1], (0, 2, 1, 3))[:, :T]
        for start, stop in ((0, 21), (21, T)):
            ch.write_prefill_chunk(0, 0,
                                   paddle.to_tensor(k_rows[:, start:stop]),
                                   paddle.to_tensor(v_rows[:, start:stop]),
                                   start)
        ref_pool = np.asarray(ref.pools[0].numpy())
        ch_pool = np.asarray(ch.pools[0].numpy())
        for bpos, (rb, cb) in enumerate(zip(ref.seq_blocks[0],
                                            ch.seq_blocks[0])):
            lo, hi = bpos * BS, min((bpos + 1) * BS, T)
            np.testing.assert_array_equal(
                ref_pool[rb, :, :, :hi - lo], ch_pool[cb, :, :, :hi - lo])
        # write_start: re-writing a range with the prefix skipped
        # leaves the prefix page untouched (skipped rows route to trash)
        before = ch_pool[ch.seq_blocks[0][0]].copy()
        ch.write_prefill_chunk(0, 0,
                               paddle.to_tensor(k_rows[:, 10:30]),
                               paddle.to_tensor(v_rows[:, 10:30]),
                               10, write_start=BS)
        after = np.asarray(ch.pools[0].numpy())
        np.testing.assert_array_equal(after[ch.seq_blocks[0][0]],
                                      before)

    def test_no_dense_scratch_memory_regression(self):
        """Satellite regression: serving must allocate NO KV beyond
        the preallocated pool — pool_bytes() is the whole KV
        footprint, before and after a capacity-length admission."""
        model = _model()
        rng = np.random.RandomState(33)
        eng = PagedServingEngine(model, max_batch=1, block_size=BS,
                                 num_blocks=6, max_blocks_per_seq=MB)
        self._no_gen_cache(model)
        pool_before = eng.cache.pool_bytes()
        slot, h = _admit(eng, _prompt(rng, MAXLEN))   # full capacity
        assert eng.cache.pool_bytes() == pool_before
        # the pool high-water mark is the prompt's pages, nothing more
        assert eng.cache.peak_blocks_used == MB
        assert eng.prefill_stats.peak_blocks == MB

    def test_mixed_step_budget_long_prompt_does_not_stall_batch(self):
        """prefill_token_budget: a long prompt streams 32 tokens per
        step WHILE the resident request keeps decoding (Sarathi-style
        mixed steps) — no admission-time stall, and both streams stay
        their dense twins' (the long one to a few ulp: its twin
        prefills 150 rows in one call)."""
        model = _model()
        rng = np.random.RandomState(34)
        pshort = _prompt(rng, 6)
        plong = _prompt(rng, 150)
        eng = PagedServingEngine(model, max_batch=2,
                                 block_size=self.CAP_BS,
                                 num_blocks=24,
                                 max_blocks_per_seq=self.CAP_MB,
                                 chunk_tokens=32,
                                 prefill_token_budget=32)
        dense_s = ContinuousBatchingEngine(model, max_batch=2,
                                           max_len=self.CAPACITY)
        ds, dh = dense_s.add_request(pshort)
        rs = eng.submit(pshort)
        assert not eng.admitted          # budget mode: step() admits
        x = np.zeros((2, 1, D), np.float32)
        assert eng.step(paddle.to_tensor(x)) is None  # prefill-only
        (rid, slot, h), = eng.admitted
        eng.admitted.clear()
        assert rid == rs
        _assert_same_to_a_few_ulp(h.numpy(), dh.numpy())
        x[slot, 0] = np.asarray(h.numpy())[0]
        xs = np.zeros((2, 1, D), np.float32)
        xs[ds, 0] = x[slot, 0]
        rl = eng.submit(plong)
        long_slot = dense_l = None
        for i in range(12):
            op = eng.step(paddle.to_tensor(x))
            os_ = np.asarray(dense_s.step(paddle.to_tensor(xs)).numpy())
            assert op is not None        # short row never stalls
            op = np.asarray(op.numpy())
            _assert_same_to_a_few_ulp(op[slot], os_[ds])
            x[slot, 0] = xs[ds, 0] = os_[ds, 0]
            if dense_l is not None:
                ol = np.asarray(dense_l.step(
                    paddle.to_tensor(xl)).numpy())
                _assert_same_to_a_few_ulp(op[long_slot], ol[dl])
                x[long_slot, 0] = xl[dl, 0] = ol[dl, 0]
            for (rr, ss, hh) in eng.admitted:
                assert rr == rl
                long_slot = ss
                dense_l = ContinuousBatchingEngine(
                    model, max_batch=2, max_len=self.CAPACITY)
                dl, dlh = dense_l.add_request(plong)
                _assert_same_to_a_few_ulp(hh.numpy(), dlh.numpy())
                x[ss, 0] = np.asarray(hh.numpy())[0]
                xl = np.zeros((2, 1, D), np.float32)
                xl[dl, 0] = x[ss, 0]
            eng.admitted.clear()
        assert dense_l is not None, "long prompt never admitted"
        st = eng.prefill_stats
        assert st.mixed_steps > 0        # prefill rode along decode
        assert st.chunks >= 5 and st.prefill_tokens == 156

    def test_preempt_mid_prefill_then_reprefill(self):
        """Pool pressure can evict a request MID-PROMPT-STREAM (it is
        the youngest): its pages free, it re-queues whole, the
        resident request is untouched bitwise, and once pressure
        clears the victim re-streams and decodes bit-identically."""
        model = _model()
        rng = np.random.RandomState(35)
        pa = _prompt(rng, 8)
        pb = _prompt(rng, 40)
        # 7 usable blocks of 8: A holds 1-2, B needs 5 + headroom
        eng = PagedServingEngine(model, max_batch=2, block_size=8,
                                 num_blocks=8, max_blocks_per_seq=8,
                                 chunk_tokens=16,
                                 prefill_token_budget=16)
        dense_a = ContinuousBatchingEngine(model, max_batch=2,
                                           max_len=64)
        da, dha = dense_a.add_request(pa)
        ra = eng.submit(pa)
        x = np.zeros((2, 1, D), np.float32)
        assert eng.step(paddle.to_tensor(x)) is None
        (_, sa, ha), = eng.admitted
        eng.admitted.clear()
        np.testing.assert_array_equal(np.asarray(dha.numpy()),
                                      np.asarray(ha.numpy()))
        x[sa, 0] = np.asarray(ha.numpy())[0]
        xa = np.zeros((2, 1, D), np.float32)
        xa[da, 0] = x[sa, 0]
        rb = eng.submit(pb)
        preempted = 0
        for _ in range(10):
            op = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            od = np.asarray(dense_a.step(paddle.to_tensor(xa)).numpy())
            np.testing.assert_array_equal(op[sa], od[da])
            x[sa, 0] = xa[da, 0] = od[da, 0]
            if eng.preempted:
                assert eng.preempted == [rb]   # B, mid-prefill
                preempted += len(eng.preempted)
                eng.preempted.clear()
            eng.admitted.clear()               # B never completes here
        assert preempted > 0, "expected a mid-prefill eviction"
        # pressure clears: A releases, B streams to completion
        eng.release(sa)
        for _ in range(6):
            if eng.admitted:
                break
            assert eng.step(paddle.to_tensor(x)) is None
        (rid, sb, hb), = eng.admitted
        eng.admitted.clear()
        assert rid == rb
        dense_b = ContinuousBatchingEngine(model, max_batch=2,
                                           max_len=64)
        db, dhb = dense_b.add_request(pb)
        np.testing.assert_array_equal(np.asarray(dhb.numpy()),
                                      np.asarray(hb.numpy()))
        x = np.zeros((2, 1, D), np.float32)
        xb = np.zeros((2, 1, D), np.float32)
        x[sb, 0] = xb[db, 0] = np.asarray(hb.numpy())[0]
        for _ in range(4):
            op = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            od = np.asarray(dense_b.step(paddle.to_tensor(xb)).numpy())
            np.testing.assert_array_equal(op[sb], od[db])
            x, xb = op[:, :1].copy(), od[:, :1].copy()


class TestRaggedMixedStep:
    """The ragged mixed step (ragged_step=True, the default): one
    token-budget step packs its prefill chunks AND the fused decode
    rows into ONE model call, which on the kernel path is ONE
    paged-attention launch per layer (the PR's dispatch-count
    acceptance) — with streams BIT-IDENTICAL to the legacy per-chunk
    path (ragged_step=False)."""

    CAP_BS, CAP_MB = 16, 12
    CAPACITY = 16 * 12

    def _drive(self, ragged, steps=7):
        """Mixed workload: a short resident request decoding while a
        long prompt streams in budgeted chunks. Returns (admitted
        hiddens by rid, per-step decode rows by slot) as numpy."""
        model = _model()
        rng = np.random.RandomState(77)
        pshort = _prompt(rng, 6)
        plong = _prompt(rng, 70)
        eng = PagedServingEngine(model, max_batch=2,
                                 block_size=self.CAP_BS,
                                 num_blocks=24,
                                 max_blocks_per_seq=self.CAP_MB,
                                 chunk_tokens=32,
                                 prefill_token_budget=32,
                                 ragged_step=ragged)
        rs = eng.submit(pshort)
        x = np.zeros((2, 1, D), np.float32)
        assert eng.step(paddle.to_tensor(x)) is None
        hiddens, rows = {}, []
        (rid, slot, h), = eng.admitted
        eng.admitted.clear()
        hiddens[rid] = np.asarray(h.numpy())
        x[slot, 0] = hiddens[rid][0]
        eng.submit(plong)
        for _ in range(steps):
            pre = eng.active.copy()      # slots whose row is real
            out = eng.step(paddle.to_tensor(x))
            assert out is not None
            ov = np.asarray(out.numpy())
            # only slots active BEFORE the step stepped; a freshly
            # admitted slot's row is garbage by contract
            rows.append({int(s): ov[s].copy()
                         for s in np.flatnonzero(pre & eng.active)})
            for s in np.flatnonzero(pre & eng.active):
                x[s, 0] = ov[s, 0]
            for (rr, ss, hh) in eng.admitted:
                hiddens[rr] = np.asarray(hh.numpy())
                x[ss, 0] = hiddens[rr][0]
            eng.admitted.clear()
        assert rs in hiddens and len(hiddens) == 2
        return hiddens, rows, eng

    def test_streams_bit_identical_to_legacy_path(self):
        """The acceptance's regression edge: ragged packing is
        numerically invisible — admission hiddens and every decode row
        equal the per-chunk path's BITWISE."""
        # "force" packs on the CPU fallback too (the default True
        # packs only on the kernel path, where dispatch count is the
        # cost; at these test dims the packed CPU call is bit-exact)
        hr, rr_, er = self._drive(ragged="force")
        hl, rl, el = self._drive(ragged=False)
        assert set(hr) == set(hl)
        for rid in hr:
            np.testing.assert_array_equal(hr[rid], hl[rid])
        for a, b in zip(rr_, rl):
            assert set(a) == set(b)
            for s in a:
                np.testing.assert_array_equal(a[s], b[s])
        # same scheduling too: identical chunk accounting either way
        assert er.prefill_stats.chunks == el.prefill_stats.chunks
        assert er.prefill_stats.prefill_tokens == \
            el.prefill_stats.prefill_tokens
        assert er.prefill_stats.mixed_steps == \
            el.prefill_stats.mixed_steps

    def _dispatch_engine(self, ragged):
        # small geometry: interpret-mode Pallas launches run eagerly
        # here (the op-jit cache is off so the counter is exact)
        model = _model()
        # the per-op step: a step program (PR 31) traces the launch all
        # its layers share ONCE, so the wrapper's entries count layers
        # only here (tests/test_step_program_hlo.py counts the
        # program's custom calls)
        layer_jit.mark_unsafe(model)
        rng = np.random.RandomState(78)
        eng = PagedServingEngine(model, max_batch=2, block_size=self.CAP_BS,
                                 num_blocks=12, max_blocks_per_seq=4,
                                 chunk_tokens=32,
                                 prefill_token_budget=32,
                                 ragged_step=ragged)
        eng.submit(_prompt(rng, 6))
        x = np.zeros((2, 1, D), np.float32)
        assert eng.step(paddle.to_tensor(x)) is None
        (rid, slot, h), = eng.admitted
        eng.admitted.clear()
        x[slot, 0] = np.asarray(h.numpy())[0]
        eng.submit(_prompt(rng, 40))
        return eng, x

    def test_mixed_step_is_one_launch_per_layer(self, monkeypatch):
        """THE dispatch-count acceptance: a mixed step (prefill chunk
        + decode rows) on the kernel path issues exactly ONE
        paged-attention launch per layer; the legacy path pays one per
        chunk PLUS one for the decode per layer. Counted with the
        eager op-jit cache off (a cached executable replays without
        re-entering the kernel wrapper) and the kernel path forced —
        interpret-mode Pallas on CPU."""
        from paddle_tpu.flags import set_flags
        from paddle_tpu.framework import device
        pa = _kernel_module()
        monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
        # setup steps run with the op-jit cache ON (fast); only the
        # MEASURED step disables it so every kernel-wrapper entry is a
        # real launch (a cached executable replays without re-entering
        # the wrapper)
        counts = {}
        for ragged in (True, False):
            eng, x = self._dispatch_engine(ragged=ragged)
            set_flags({"FLAGS_eager_op_jit": False})
            try:
                pa.reset_dispatch_count()
                assert eng.step(paddle.to_tensor(x)) is not None
                assert eng.prefill_stats.mixed_steps >= 1
                counts[ragged] = pa.dispatch_count()
            finally:
                set_flags({"FLAGS_eager_op_jit": True})
        assert counts[True] == LAYERS                # ONE per layer
        # legacy, same workload: the step's one 32-token chunk and the
        # decode rows are a launch each per layer
        assert counts[False] == 2 * LAYERS

    def test_prefill_only_ragged_step_packs_multiple_slots(self):
        """Two prompts streaming concurrently: their chunks pack into
        one launch (prefill-only packed call), and the admission
        hiddens stay bit-identical to the legacy path's."""
        def drive(ragged):
            model = _model()
            rng = np.random.RandomState(79)
            pa_, pb = _prompt(rng, 24), _prompt(rng, 24)
            eng = PagedServingEngine(model, max_batch=2,
                                     block_size=self.CAP_BS,
                                     num_blocks=24,
                                     max_blocks_per_seq=self.CAP_MB,
                                     chunk_tokens=16,
                                     prefill_token_budget=64,
                                     ragged_step=ragged)
            ra, rb = eng.submit(pa_), eng.submit(pb)
            x = paddle.to_tensor(np.zeros((2, 1, D), np.float32))
            got = {}
            for _ in range(6):
                eng.step(x)
                for (rr, ss, hh) in eng.admitted:
                    got[rr] = np.asarray(hh.numpy())
                eng.admitted.clear()
                if len(got) == 2:
                    break
            assert set(got) == {ra, rb}
            return got[ra], got[rb]

        (ha, hb), (la, lb) = drive("force"), drive(False)
        np.testing.assert_array_equal(ha, la)
        np.testing.assert_array_equal(hb, lb)


class TestSharedPrefixCOW:
    def test_fork_shares_then_copies_on_write(self):
        """Refcounted shared-prefix pages: a fork shares the prefix
        blocks; the first divergent append splits the shared page
        copy-on-write, and both rows then decode bit-identically to a
        dense engine given the same prompt twice."""
        model = _model()
        rng = np.random.RandomState(7)
        prompt = _prompt(rng, 14)

        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB)
        scratch = model.gen_cache(1, MAXLEN)
        with paddle.no_grad():
            # Tensor time_step == the engines' full-extent prefill
            # convention (length-independent numerics); required for
            # bitwise parity with ContinuousBatchingEngine below
            _, rc = model(prompt.unsqueeze(0), caches=scratch,
                          time_step=paddle.to_tensor(np.int32(0)))
        cache.ensure(0, 14)
        cache.write_prefill(0, rc, 14)
        cache.fork(0, 1, 14)
        shared = cache.seq_blocks[0][0]
        assert cache.seq_blocks[1] == [shared]
        assert cache.allocator.refcount[shared] == 2

        dense = ContinuousBatchingEngine(model, max_batch=2,
                                         max_len=MAXLEN)
        dense.add_request(prompt)
        dense.add_request(prompt)

        lens = np.array([14, 14], np.int32)
        x = np.asarray(rng.randn(2, 1, D), np.float32)  # divergent
        for step in range(4):
            for slot in (0, 1):
                cache.ensure(slot, int(lens[slot]) + 1)
            if step == 0:
                # first divergent write split the shared page
                assert cache.seq_blocks[0][0] != cache.seq_blocks[1][0]
                assert cache.allocator.refcount[shared] == 1
            xt = paddle.to_tensor(x)
            with paddle.no_grad():
                out, _ = model(xt, caches=cache.views,
                               time_step=paddle.to_tensor(lens))
            od = dense.step(xt)
            lens += 1
            np.testing.assert_array_equal(np.asarray(out.numpy()),
                                          np.asarray(od.numpy()))
            x = np.asarray(out.numpy())[:, :1].copy()

    def test_write_prefill_splits_shared_blocks(self):
        """write_prefill rewrites every covered page wholesale, so a
        fork-shared page must be split first — otherwise the prefill
        would leak into the peer sequence through the shared block."""
        model = _model()
        rng = np.random.RandomState(8)
        prompt = _prompt(rng, 14)
        other = _prompt(rng, 10)

        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB)
        scratch = model.gen_cache(1, MAXLEN)
        with paddle.no_grad():
            # Tensor time_step == the engines' full-extent prefill
            # convention (length-independent numerics); required for
            # bitwise parity with ContinuousBatchingEngine below
            _, rc = model(prompt.unsqueeze(0), caches=scratch,
                          time_step=paddle.to_tensor(np.int32(0)))
        cache.ensure(0, 14)
        cache.write_prefill(0, rc, 14)
        cache.fork(0, 1, 14)
        shared = cache.seq_blocks[0][0]
        # re-prefill slot 1 with DIFFERENT content over the shared page
        with paddle.no_grad():
            _, rc2 = model(other.unsqueeze(0), caches=scratch,
                           time_step=paddle.to_tensor(np.int32(0)))
        cache.ensure(1, 10)
        cache.write_prefill(1, rc2, 10)
        assert cache.seq_blocks[1][0] != shared
        assert cache.allocator.refcount[shared] == 1

        # slot 0 must decode as if the fork never happened
        dense = ContinuousBatchingEngine(model, max_batch=2,
                                         max_len=MAXLEN)
        dense.add_request(prompt)
        lens = np.array([14, 10], np.int32)
        x = np.asarray(rng.randn(2, 1, D), np.float32)
        for _ in range(3):
            for slot in (0, 1):
                cache.ensure(slot, int(lens[slot]) + 1)
            xt = paddle.to_tensor(x)
            with paddle.no_grad():
                out, _ = model(xt, caches=cache.views,
                               time_step=paddle.to_tensor(lens))
            od = dense.step(xt)
            lens += 1
            np.testing.assert_array_equal(
                np.asarray(out.numpy())[0], np.asarray(od.numpy())[0])
            x = np.asarray(out.numpy())[:, :1].copy()


class TestSnapshotRestore:
    """PagedKVCache.snapshot()/restore() round-trip property tests for
    the allocator edge states PR 6's crash recovery must preserve:
    exact free-list and cached-free LRU orders (the restored pool must
    ALLOCATE bit-identically to the uninterrupted one), fork-shared
    refcounts, the trash block's reserved state, and the quarantine
    guarantee (suspect pages never ride a snapshot)."""

    def _loaded_cache(self):
        """A pool exercising every block state at once: slot 0 active
        with registered prefix pages, slot 1 fork-sharing slot 0's
        prefix, a retired slot's pages parked cached-free (known LRU
        order), and a few true-free blocks."""
        from paddle_tpu.inference import chain_block_hashes
        cache = PagedKVCache(LAYERS, HEADS, D // HEADS, block_size=4,
                             num_blocks=16, max_seqs=3,
                             max_blocks_per_seq=6, prefix_cache=True)
        rng = np.random.RandomState(7)

        def fill(slot, toks):
            cache.ensure(slot, toks.shape[0], write_from=0)
            for layer in range(LAYERS):
                k = paddle.to_tensor(rng.randn(
                    1, toks.shape[0], HEADS, D // HEADS)
                    .astype(np.float32))
                v = paddle.to_tensor(rng.randn(
                    1, toks.shape[0], HEADS, D // HEADS)
                    .astype(np.float32))
                cache.write_prefill_chunk(slot, layer, k, v, 0)

        t0 = rng.randn(10, D).astype(np.float32)     # 2 full blocks
        fill(0, t0)
        cache.register_prefix(0, chain_block_hashes(t0, 4))
        cache.fork(0, 1, 8)                          # share 2 blocks
        t2 = rng.randn(12, D).astype(np.float32)     # 3 full blocks
        fill(2, t2)
        cache.register_prefix(2, chain_block_hashes(t2, 4))
        cache.free_seq(2)                            # -> cached-free x3
        assert cache.allocator.num_cached == 3
        assert cache.check_invariants()
        return cache

    @staticmethod
    def _assert_state_equal(a, b):
        assert b.seq_blocks == a.seq_blocks
        np.testing.assert_array_equal(b.block_tables, a.block_tables)
        np.testing.assert_array_equal(b.allocator.refcount,
                                      a.allocator.refcount)
        assert list(b.allocator._free) == list(a.allocator._free)
        assert list(b.allocator._cached) == list(a.allocator._cached)
        assert b._hash_to_block == a._hash_to_block
        assert b._block_hash == a._block_hash

    def test_round_trip_preserves_every_allocator_edge_state(self):
        cache = self._loaded_cache()
        out = PagedKVCache.restore(cache.snapshot())
        self._assert_state_equal(cache, out)
        # content round-trips bitwise for every live + cached block
        live = [b for b in range(1, cache.num_blocks)
                if cache.allocator.refcount[b] > 0
                or b in cache.allocator._cached]
        for i in range(LAYERS):
            src = np.asarray(cache.pools[i].numpy())
            dst = np.asarray(out.pools[i].numpy())
            np.testing.assert_array_equal(src[live], dst[live])
        assert out.check_invariants()

    def test_restored_pool_allocates_bit_identically(self):
        """The recovery contract on the allocator: after restore, the
        SAME alloc sequence hands out the SAME block ids — free-list
        order first, then cached-free LRU reclaim order, with the
        reclaimed blocks' index entries dropped in both pools."""
        cache = self._loaded_cache()
        out = PagedKVCache.restore(cache.snapshot())
        n = cache.allocator.num_free            # drain BOTH tiers
        got_a = [cache.allocator.alloc(1)[0] for _ in range(n)]
        got_b = [out.allocator.alloc(1)[0] for _ in range(n)]
        assert got_a == got_b
        assert cache._hash_to_block == out._hash_to_block
        with pytest.raises(BlockOOM):
            out.allocator.alloc(1)

    def test_quarantined_blocks_never_ride_a_snapshot(self):
        """quarantine_seq frees suspect pages to the TRUE free list
        before any snapshot can see them: the snapshot payload must
        not contain them and the restored pool must not index them."""
        cache = self._loaded_cache()
        suspect = list(cache.seq_blocks[0])
        solely_owned = [b for b in suspect
                        if cache.allocator.refcount[b] == 1]
        cache.quarantine_seq(0)
        snap = cache.snapshot()
        for b in solely_owned:
            assert b not in snap["blocks"]
            assert b not in snap["refcount"]
        out = PagedKVCache.restore(snap)
        for b in solely_owned:
            assert out.allocator.refcount[b] == 0
            assert b not in out._block_hash
            assert b not in out.allocator._cached
        assert out.check_invariants()

    def test_trash_block_and_fork_shared_refcounts(self):
        cache = self._loaded_cache()
        snap = cache.snapshot()
        assert 0 not in snap["blocks"]          # trash never serialized
        out = PagedKVCache.restore(snap)
        assert out.allocator.refcount[0] == 1
        assert 0 not in out.allocator._free
        # the fork share survived: slot 0/1's common prefix blocks at
        # refcount 2, and a post-restore write still COW-splits
        shared = out.seq_blocks[0][0]
        assert out.seq_blocks[1][0] == shared
        assert out.allocator.refcount[shared] == 2
        before = np.asarray(out.pools[0].numpy())[shared].copy()
        out.ensure(1, 2, write_from=0)          # write range hits block 0
        assert out.seq_blocks[1][0] != shared   # split, peer untouched
        np.testing.assert_array_equal(
            np.asarray(out.pools[0].numpy())[shared], before)
        assert out.check_invariants()

    def test_rehome_into_larger_pool(self):
        """Restore into a bigger num_blocks: content-addressed blocks
        take fresh ids, tables/refcounts/index remap with them, and
        the pool serves prefix hits as before."""
        cache = self._loaded_cache()
        out = PagedKVCache.restore(cache.snapshot(), num_blocks=32)
        assert out.num_blocks == 32
        assert out.check_invariants()
        assert len(out._hash_to_block) == len(cache._hash_to_block)
        # same chain hashes still hit (ids remapped, content intact)
        for h, old_b in cache._hash_to_block.items():
            new_b = out._hash_to_block[h]
            for i in range(LAYERS):
                np.testing.assert_array_equal(
                    np.asarray(cache.pools[i].numpy())[old_b],
                    np.asarray(out.pools[i].numpy())[new_b])
        assert out.allocator.num_free > cache.allocator.num_free

    def test_rehome_into_smaller_pool_drops_lru_cached_first(self):
        cache = self._loaded_cache()
        # live set = 5 blocks (slot 0's 3 + slot 1's COW tail... it is
        # whatever refcount>0 says), cached-free = 3; shrink so only
        # ONE cached block fits: the two LEAST recently released drop
        live = int((cache.allocator.refcount[1:] > 0).sum())
        out = PagedKVCache.restore(cache.snapshot(),
                                   num_blocks=live + 1 + 1)
        assert out.allocator.num_cached == 1
        kept = list(out.allocator._cached)[0]
        # the survivor is the NEWEST cached-free block's content
        newest_old = list(cache.allocator._cached)[-1]
        h = cache._block_hash[newest_old]
        assert out._hash_to_block[h] == kept
        assert out.check_invariants()

    def test_rehome_live_overflow_raises_precise_oom(self):
        cache = self._loaded_cache()
        live = int((cache.allocator.refcount[1:] > 0).sum())
        with pytest.raises(BlockOOM) as ei:
            PagedKVCache.restore(cache.snapshot(), num_blocks=live)
        msg = str(ei.value)
        assert f"restore needs {live} live block(s)" in msg
        assert "cached-free" in msg and "blocks per slot" in msg


# ---------------------------------------------------------------------
# page-form pool writes on a donated pool (PR 29)
# ---------------------------------------------------------------------

_PW = dict(layers=2, heads=2, hd=8, bs=4, nb=24, seqs=4, mb=5)


def _pw_cache(dtype, lens=(6, 0, 9, 3), extra=4):
    """A small pool with random content in every page (so a write that
    dropped or moved a neighbouring row would show), slots covered to
    ``lens + extra``."""
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import Tensor
    g = _PW
    cache = PagedKVCache(g["layers"], g["heads"], g["hd"], g["bs"],
                         g["nb"], g["seqs"], max_blocks_per_seq=g["mb"],
                         dtype=dtype, prefix_cache=True)
    rng = np.random.RandomState(7)
    for i, p in enumerate(cache.pools):
        if cache.quantized:
            cache.pools[i] = Tensor(jnp.asarray(
                rng.randint(-127, 128, p.shape).astype(np.int8)))
            cache.scales[i] = Tensor(jnp.asarray(
                rng.rand(*cache.scales[i].shape).astype(np.float32)))
        else:
            cache.pools[i] = Tensor(jnp.asarray(
                rng.randn(*p.shape).astype(np.float32)).astype(
                    p.data.dtype))
    for slot, n in enumerate(lens):
        if n:
            cache.ensure(slot, n + extra, write_from=n)
    return cache


def _pw_kv(rng, b, n):
    g = _PW
    mk = lambda: paddle.to_tensor(
        rng.randn(b, n, g["heads"], g["hd"]).astype(np.float32))
    return mk(), mk(), mk()


def _pw_state(cache):
    """Host copies of every pool (and scale array), as raw bytes."""
    # COPIES: a numpy view of a CPU array keeps its buffer alive, and a
    # buffer someone else holds is copied by the next write, not donated
    out = [np.array(p.numpy()) for p in cache.pools]
    if cache.quantized:
        out += [np.array(s.numpy()) for s in cache.scales]
    return out


def _pw_row_scatter(cache, before, layer, k, v, blk, off):
    """The row scatter the page-form write replaced, on host copies:
    row r lands at [blk[r], :, :, off[r]]; rows routed to the trash
    block 0 are dropped (nothing reads it unmasked)."""
    import jax.numpy as jnp
    from paddle_tpu.inference.paged_cache import _quant_rows
    want = [a.copy() for a in before]
    pool = want[layer]
    k = np.asarray(k.numpy()).reshape((-1,) + tuple(k.shape[2:]))
    v = np.asarray(v.numpy()).reshape((-1,) + tuple(v.shape[2:]))
    if cache.quantized:
        import jax
        (k, ks), (v, vs) = (tuple(np.asarray(a) for a in jax.jit(
            _quant_rows)(jnp.asarray(x))) for x in (k, v))
        sc = want[len(cache.pools) + layer]
    else:
        k = np.asarray(jnp.asarray(k).astype(cache.pools[0].data.dtype))
        v = np.asarray(jnp.asarray(v).astype(cache.pools[0].data.dtype))
    for r, (b, o) in enumerate(zip(blk, off)):
        if b == 0:
            continue
        pool[b, 0, :, o], pool[b, 1, :, o] = k[r], v[r]
        if cache.quantized:
            sc[b, 0, :, o], sc[b, 1, :, o] = ks[r], vs[r]
    return want


def _pw_same_bytes(got, want):
    for i, (a, b) in enumerate(zip(got, want)):
        # the trash block takes whatever lands there, in any order
        assert a[1:].tobytes() == b[1:].tobytes(), f"array {i} differs"


def _pw_decode(cache, rng, L):
    lens = np.array([6, 0, 9, 3], np.int32)
    mask = np.array([False, False, False, True])   # row 3 sits out
    cache.set_decode_mask(mask)
    q, k, v = _pw_kv(rng, _PW["seqs"], L)
    before = _pw_state(cache)
    pos = lens[:, None] + np.arange(L)[None, :]
    tbl = cache.block_tables.copy()
    tbl[mask] = 0
    blk = tbl[np.arange(4)[:, None], pos // _PW["bs"]].reshape(-1)
    cache.views[1].decode(q, k, v, np.asarray(lens))
    return before, 1, k, v, blk, (pos % _PW["bs"]).reshape(-1)


def _pw_chunk(cache, rng, via):
    # positions 5 .. 12 of slot 2, the first two adopted (write_start 7
    # lies inside page 1): rows below it must not be written
    start, C, ws, slot = 5, 8, 7, 2
    q, k, v = _pw_kv(rng, 1, C)
    before = _pw_state(cache)
    pos = np.arange(start, start + C)
    blk = np.where(pos >= ws, cache.block_tables[slot][pos // _PW["bs"]],
                   0)
    if via == "view":
        cache.prefill_views(slot, write_start=ws)[1].decode(
            q, k, v, np.asarray([start], np.int32))
    else:
        cache.write_prefill_chunk(slot, 1, k, v, start, write_start=ws)
    return before, 1, k, v, blk, pos % _PW["bs"]


def _pw_ragged(cache, rng, L=1):
    # a prefill chunk of slot 1 (fresh), one of slot 2 whose first rows
    # are an adopted prefix, and the decode rows with slot 3 masked and
    # slots 1, 2 mid-prefill (masked too)
    cache.ensure(1, 6, write_from=0)
    lens = np.array([6, 0, 9, 3], np.int64)
    mask = np.array([False, True, True, True])
    cache.set_decode_mask(mask)
    desc = [("prefill", 1, 0, 6, 0), ("prefill", 2, 5, 7, 8),
            ("decode", lens.copy(), L)]
    views = cache.ragged_views(desc)
    lay = views[0]._layout
    q, k, v = _pw_kv(rng, 1, lay.total_rows)
    before = _pw_state(cache)
    views[0].decode(q, k, v, None)
    return before, 0, k, v, lay.blk_np, lay.off_np


_PW_APPENDS = {
    "decode": lambda c, r: _pw_decode(c, r, 1),
    "verify": lambda c, r: _pw_decode(c, r, 3),
    "chunk_view": lambda c, r: _pw_chunk(c, r, "view"),
    "chunk_call": lambda c, r: _pw_chunk(c, r, "call"),
    "ragged": lambda c, r: _pw_ragged(c, r),
    "ragged_verify": lambda c, r: _pw_ragged(c, r, L=2),
}


class TestPageFormWrite:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("append", sorted(_PW_APPENDS))
    def test_equals_the_row_scatter_byte_for_byte(self, append, dtype):
        cache = _pw_cache(dtype)
        before, layer, k, v, blk, off = _PW_APPENDS[append](
            cache, np.random.RandomState(3))
        assert (np.asarray(blk) != 0).any() and (np.asarray(blk) == 0).any()
        _pw_same_bytes(_pw_state(cache),
                       _pw_row_scatter(cache, before, layer, k, v, blk, off))

    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    @pytest.mark.parametrize("write", sorted(_PW_APPENDS)
                             + ["cow_split", "write_prefill"])
    def test_old_pool_is_deleted_and_every_reader_still_reads(self, write,
                                                              dtype):
        cache = _pw_cache(dtype)
        cache.register_prefix(0, [b"h0"])
        held = [p.data for p in cache.pools] + \
            [s.data for s in (cache.scales or [])]
        view = cache.views[0]
        if write == "cow_split":
            cache.fork(0, 1, 6)
            cache.ensure(1, 7, write_from=6)       # splits the tail page
            touched = range(len(cache.pools))
        elif write == "write_prefill":
            rows = [paddle.to_tensor(np.random.RandomState(5).randn(
                2, 1, _PW["heads"], _PW["bs"] * _PW["mb"],
                _PW["hd"]).astype(np.float32))] * _PW["layers"]
            cache.ensure(1, 8)
            cache.write_prefill(1, rows, 8)
            touched = range(len(cache.pools))
        else:
            _, layer, *_ = _PW_APPENDS[write](cache,
                                              np.random.RandomState(3))
            touched = [layer]
        n = len(cache.pools)
        for pi in touched:
            assert held[pi].is_deleted()
            if cache.quantized:
                assert held[n + pi].is_deleted()
        # whoever reads takes the array at the moment it reads
        assert view.pool.numpy().shape == tuple(view.shape)
        assert cache.check_invariants(deep=True)
        snap = cache.snapshot()
        assert snap["payload"].shape[0] == len(snap["blocks"])
        slc = cache.export_slice(0, [b"h0"])
        assert slc["payload"].shape[0] == 1
        again = PagedKVCache.restore(snap)
        assert again.snapshot()["payload"].tobytes() == \
            snap["payload"].tobytes()
        stats = cache.take_write_stats()
        assert 0 < stats["pool_bytes_written"] < stats["pool_bytes"] \
            == cache.pool_bytes_total()
        assert cache.take_write_stats()["pages_written"] == 0

    def test_layout_lists_a_real_page_once_and_sizes_by_q_lens(self):
        from paddle_tpu.inference.paged_cache import _pages_spanned
        sizes = set()
        for lens, start in (((6, 0, 9, 3), 5), ((1, 0, 14, 8), 2)):
            cache = _pw_cache("float32", lens=lens, extra=2)
            cache.ensure(1, 12, write_from=0)
            cache.ensure(2, start + 7, write_from=start)
            cache.set_decode_mask(np.array([False, True, True, False]))
            lay = cache.ragged_views(
                [("prefill", 1, 0, 6, 0), ("prefill", 2, start, 7, start),
                 ("decode", np.asarray(lens, np.int64), 1)])[0]._layout
            real = lay.pg_ids_np[lay.pg_ids_np != 0]
            assert len(set(real.tolist())) == real.shape[0] > 0
            assert lay.pg_ids_np[0] == 0        # the trash slot
            # every real row finds its page through its slot
            rows = lay.blk_np != 0
            np.testing.assert_array_equal(
                lay.pg_ids_np[lay.pg_slot_np[rows]], lay.blk_np[rows])
            assert (lay.pg_slot_np[~rows] == 0).all()
            assert lay.n_pages == 1 + sum(
                _pages_spanned(q, _PW["bs"]) for q in lay.q_lens)
            sizes.add((lay.q_lens, lay.n_pages))
        assert len(sizes) == 1      # same q_lens, other positions: same P

    def test_layout_refuses_a_page_two_sequences_write(self):
        cache = _pw_cache("float32")
        cache.free_seq(1)
        cache.fork(0, 1, 6)        # slots 0 and 1 share the tail page
        with pytest.raises(AssertionError, match="two sequences"):
            cache.ragged_views([("decode", np.array([6, 6, 9, 3]), 1)])


# ---------------------------------------------------------------------
# the seam: one predicate, one ``decode(q, k, v, t)``, one kernel call
# ---------------------------------------------------------------------

_SEAM = dict(heads=4, kv_heads=2, hd=16, bs=4, nb=40, seqs=3, mb=8)
_SEAM_LENS = (9, 5, 14)


def _kernel_module():
    # the package re-exports a FUNCTION named paged_attention over it
    import importlib
    return importlib.import_module("paddle_tpu.ops.pallas.paged_attention")


def _seam_cache(dtype, window):
    """One layer, two query heads a kv head, random content in every
    page (the contexts are there without a prefill), slots covered ten
    positions past ``_SEAM_LENS``."""
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import Tensor
    g = _SEAM
    cache = PagedKVCache(1, g["heads"], g["hd"], g["bs"], g["nb"],
                         g["seqs"], max_blocks_per_seq=g["mb"],
                         dtype=dtype, num_kv_heads=g["kv_heads"],
                         layer_windows=(window,))
    rng = np.random.RandomState(11)
    if cache.quantized:
        cache.pools[0] = Tensor(jnp.asarray(rng.randint(
            -127, 128, cache.pools[0].shape).astype(np.int8)))
        cache.scales[0] = Tensor(jnp.asarray(
            (rng.rand(*cache.scales[0].shape) / 64).astype(np.float32)))
    else:
        cache.pools[0] = Tensor(jnp.asarray(
            rng.randn(*cache.pools[0].shape).astype(np.float32)))
    for slot, n in enumerate(_SEAM_LENS):
        cache.ensure(slot, n + 10, write_from=n)
    return cache


def _seam_call(cache, kind, rng):
    """One ``decode`` of a view of ``kind``; returns what the kernel's
    reference needs to score the same rows on the pool the call left:
    (out [R, nh, hd], q [R, nh, hd], tables, q_lens, kv_lens)."""
    g = _SEAM
    lens = np.asarray(_SEAM_LENS, np.int32)

    def qkv(b, n):
        mk = lambda h: paddle.to_tensor(
            rng.randn(b, n, h, g["hd"]).astype(np.float32))
        return mk(g["heads"]), mk(g["kv_heads"]), mk(g["kv_heads"])

    if kind in ("decode", "verify"):
        L = 1 if kind == "decode" else 3
        q, k, v = qkv(g["seqs"], L)
        out = cache.views[0].decode(q, k, v, np.asarray(lens))
        tables, q_lens, kv_lens = (cache.bt_tensor().numpy(),
                                   (L,) * g["seqs"], lens + L)
    elif kind == "prefill":
        slot, C = 1, 6
        q, k, v = qkv(1, C)
        out = cache.prefill_views(slot)[0].decode(
            q, k, v, np.asarray([lens[slot]], np.int32))
        tables, q_lens, kv_lens = (cache.bt_row_tensor(slot).numpy(),
                                   (C,), lens[slot:slot + 1] + C)
    else:
        # a chunk of slot 1 packed with the decode rows; slot 1 is mid
        # prefill, so its decode row rides masked (an all-trash table)
        cache.set_decode_mask(np.array([False, True, False]))
        views = cache.ragged_views(
            [("prefill", 1, int(lens[1]), 6, 0),
             ("decode", lens.astype(np.int64), 1)])
        lay = views[0]._layout
        q, k, v = qkv(1, lay.total_rows)
        out = views[0].decode(q, k, v, None)
        tables, q_lens, kv_lens = (lay.bt_all.numpy(), lay.q_lens,
                                   lay.kv_lens_np)
    flat = lambda a: np.asarray(a.numpy()).reshape(
        (-1, g["heads"], g["hd"]))
    return flat(out), flat(q), np.asarray(tables), q_lens, kv_lens


class TestAttentionSeam:
    @pytest.mark.parametrize("tile_q", [None, 2])
    def test_the_layout_counts_the_launchs_grid_steps(self, monkeypatch,
                                                      tile_q):
        """``_RaggedLayout.live_steps`` (the ``paged_attn`` gauge's
        ``live_steps``: the kernel module's own count, on the layout's host
        lengths) is the size of the grid the work list gives the same
        packed launch on a layer without a window; with two-query tiles
        the chunk is three tiles, each with its own frontier."""
        import jax.numpy as jnp
        pa = _kernel_module()
        cache = _seam_cache("float32", None)
        lens = np.asarray(_SEAM_LENS, np.int64)
        cache.set_decode_mask(np.array([False, True, False]))
        lay = cache.ragged_views([("prefill", 1, int(lens[1]), 6, 0),
                                  ("decode", lens, 1)])[0]._layout
        if tile_q is not None:
            monkeypatch.setattr(pa, "DEFAULT_TILE_Q_CAP", tile_q)
        g = _SEAM["heads"] // _SEAM["kv_heads"]
        tq = pa.resolve_tile_q(lay.q_lens, g=g)
        seq, off, n, _, _ = pa._tile_layout(lay.q_lens, tq)
        assert lay.launch_plan().grid[0] == len(seq)
        # the layout's plan carries every page in one step here: count
        # under a plan of one page a step (contexts of 10, 11, 15)
        plan = pa.launch_plan(len(seq), _SEAM["kv_heads"], tq * g,
                              _SEAM["mb"], _SEAM["bs"], _SEAM["hd"], 4,
                              tile_kv=1)
        pos0 = (lay.kv_lens_np - np.asarray(lay.q_lens))[seq] + off
        count = pa._work_list(
            jnp.asarray(pos0, jnp.int32), jnp.asarray(pos0 + n - 1, jnp.int32),
            jnp.asarray(seq), _SEAM["kv_heads"] // plan.heads,
            plan.grid[1], plan.pages, _SEAM["bs"], None)[0]
        assert lay.live_steps(plan) == int(count) < plan.grid_steps
        assert plan.grid == ((4 if tile_q is None else 6), 8)
        if tile_q is None:      # pages of 4: last positions 10, 9, 5, 14
            assert int(count) == 3 + 3 + 2 + 4

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    @pytest.mark.parametrize("kind",
                             ["decode", "verify", "prefill", "mixed"])
    def test_view_decode_on_the_kernel_path(self, monkeypatch, kind,
                                            dtype, window):
        """``decode(q, k, v, t)`` of every view, with the one predicate
        patched on (the kernel runs interpreted), against the kernel
        module's reference on the pool the call left. Window 6 is
        shorter than every context (9, 5 + 6, 14). Tolerance 2e-5, the
        kernel tests' own: float32 on both sides, but the kernel's
        online softmax sums page by page where the reference takes one
        softmax over the whole context."""
        import jax.numpy as jnp
        from paddle_tpu.flags import set_flags
        from paddle_tpu.framework import device
        pa = _kernel_module()
        monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
        cache = _seam_cache(dtype, window)
        # op-jit off: a cached executable would replay without
        # re-entering the kernel wrapper, and the count below is how
        # the test knows the kernel (not the fallback) answered
        set_flags({"FLAGS_eager_op_jit": False})
        try:
            pa.reset_dispatch_count()
            out, q, tables, q_lens, kv_lens = _seam_call(
                cache, kind, np.random.RandomState(5))
            assert pa.dispatch_count() == 1
        finally:
            set_flags({"FLAGS_eager_op_jit": True})
        sc = cache.scales[0].data if cache.quantized else None
        want = pa.paged_attention_ragged_reference(
            jnp.asarray(q), cache.pools[0].data, jnp.asarray(tables),
            q_lens, jnp.asarray(kv_lens, jnp.int32), kv_scales=sc,
            window=window)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, np.asarray(want), atol=2e-5,
                                   rtol=2e-5)

    @pytest.mark.parametrize("kind",
                             ["decode", "verify", "prefill", "mixed"])
    def test_the_fallback_answers_off_the_chip(self, kind):
        """The same call with the predicate as it is here (no TPU): no
        kernel launch, and the view's own sdpa fallback agrees with the
        kernel's reference (float32, 1e-5: two jnp softmaxes over the
        same gathered pages)."""
        import jax.numpy as jnp
        pa = _kernel_module()
        cache = _seam_cache("float32", 6)
        pa.reset_dispatch_count()
        out, q, tables, q_lens, kv_lens = _seam_call(
            cache, kind, np.random.RandomState(5))
        assert pa.dispatch_count() == 0
        want = pa.paged_attention_ragged_reference(
            jnp.asarray(q), cache.pools[0].data, jnp.asarray(tables),
            q_lens, jnp.asarray(kv_lens, jnp.int32), window=6)
        np.testing.assert_allclose(out, np.asarray(want), atol=1e-5,
                                   rtol=1e-5)

    # the three cores that reach the paged views, tiny: (spec for
    # router.build_model_from_spec, attention launches a mixed step)
    _CORES = {
        "gpt3": ({"arch": "gpt3", "d_model": D, "heads": HEADS,
                  "ffn": FFN, "layers": LAYERS, "vocab": 50}, LAYERS),
        # the host-staged sharded core: one launch a layer A SHARD
        "sharded": ({"arch": "gpt3", "d_model": D, "heads": HEADS,
                     "ffn": FFN, "layers": LAYERS, "vocab": 50,
                     "mp": 2}, LAYERS * 2),
        "afmoe": ({"arch": "afmoe", "hidden_size": 32,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 8, "sliding_window": 8,
                   "intermediate_size": 64, "num_experts": 4,
                   "num_experts_per_tok": 2, "num_shared_experts": 1,
                   "moe_intermediate_size": 16, "route_norm": True,
                   "route_scale": 2.0, "rope_theta": 10000,
                   "rms_norm_eps": 1e-5, "mup_enabled": True,
                   "vocab_size": 50, "weight_dtype": "float32",
                   "num_dense_layers": 1,
                   "layer_types": ["sliding_attention",
                                   "full_attention"]}, 2),
    }

    @pytest.mark.parametrize("core", sorted(_CORES))
    def test_one_patch_switches_every_caller(self, monkeypatch, core):
        """The predicate has ONE home (``framework/device.py``) and
        every caller asks it by attribute: one patch there switches the
        scheduler's ``_ragged_active`` and the attention of the GPT-3
        block, of the host-staged sharded core and of the config-driven
        ``afmoe`` block together. (Until PR 30 the predicate lived in a
        model file and ``decoder.py`` bound it by name at import: the
        patch the tests used never reached the ``afmoe`` block.)"""
        from paddle_tpu.flags import set_flags
        from paddle_tpu.framework import device
        from paddle_tpu.inference import SpeculativeEngine
        from paddle_tpu.inference.router import build_model_from_spec
        pa = _kernel_module()
        spec, launches = self._CORES[core]
        spec = dict(spec)
        if spec.get("mp", 1) > 1:
            # shard the eager way (a compiled sharded step never enters
            # the kernel wrapper: it attends inside its one program)
            mp = spec.pop("mp")
            tsm = build_model_from_spec(spec).shard(
                mp, compiled_step=False)
        else:
            tsm = build_model_from_spec(spec)
        if core == "gpt3":
            # counted on the per-op step: its step program (PR 31)
            # enters the wrapper once for all its layers
            layer_jit.mark_unsafe(tsm.core)
        eng = SpeculativeEngine(tsm, k=0, max_batch=2, block_size=4,
                                num_blocks=40, max_blocks_per_seq=12,
                                prefill_token_budget=8)
        # no TPU here: only the afmoe core, which asks for the packed
        # step wherever it is legal (its block needs every row's
        # position), packs without the kernel
        assert eng.engine._ragged_active() == (core == "afmoe")
        monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
        assert eng.engine._ragged_active()
        rng = np.random.RandomState(3)
        first = eng.submit(rng.randint(0, 50, 6).tolist())
        while not eng.generated(first):
            eng.step()
        eng.submit(rng.randint(0, 50, 20).tolist())
        mixed = eng.engine.prefill_stats.mixed_steps
        set_flags({"FLAGS_eager_op_jit": False})
        try:
            pa.reset_dispatch_count()
            eng.step()
            # a chunk of the second prompt packed with the first
            # request's decode row: ONE launch a layer (a shard)
            assert eng.engine.prefill_stats.mixed_steps == mixed + 1
            assert pa.dispatch_count() == launches
        finally:
            set_flags({"FLAGS_eager_op_jit": True})
