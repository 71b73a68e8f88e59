"""Cross-request prefix caching (inference/paged_cache.py +
scheduler.py): chained prompt-hash block index, partial (suffix-only)
prefill, cached-free resurrection, LRU reclaim under pressure.

The acceptance bar: sharing previously computed pages and prefilling
only the uncached suffix is a pure reuse transform, so the prefix-cache
engine emits the no-prefix-cache engine's TOKEN STREAMS exactly, with
exact hit counts and block accounting, including across hit -> diverge
-> copy-on-write split and reclaim-under-pressure -> cold re-prefill.
Its hiddens are the cold engine's to a few float32 ulp where the two
batch their rows differently (``_assert_same_to_a_few_ulp`` says why),
and bit for bit where they run the same calls."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (PagedServingEngine,
                                  chain_block_hashes)
from tests.test_paged_cache import _assert_same_to_a_few_ulp

D, HEADS, FFN, LAYERS = 32, 4, 64, 2
BS, MB = 16, 5            # 16-token pages, up to 5 pages/seq (80 tok)


def _model():
    paddle.seed(0)
    return FusedMultiTransformer(D, HEADS, FFN, num_layers=LAYERS)


def _admit(eng, prompt):
    rid = eng.submit(paddle.to_tensor(prompt))
    admitted = {r: (s, h) for r, s, h in eng.admitted}
    eng.admitted.clear()
    assert rid in admitted, "expected immediate admission"
    return admitted[rid]


# deterministic greedy readout: hidden -> token -> next embedding,
# so identical hiddens also mean identical token streams
_RNG = np.random.RandomState(1234)
_VOCAB = 50
_W_OUT = _RNG.randn(D, _VOCAB).astype(np.float32)
_EMBED = _RNG.randn(_VOCAB, D).astype(np.float32)


def _readout(hidden_row):
    tok = int(np.argmax(hidden_row @ _W_OUT))
    return tok, _EMBED[tok]


def _serve_one(eng, prompt, n_decode):
    """submit -> greedy-decode n_decode steps -> release. Returns
    (admission hidden, per-step hiddens, token stream)."""
    slot, h = _admit(eng, prompt)
    h0 = np.asarray(h.numpy())[0]
    x = np.zeros((eng.max_batch, 1, D), np.float32)
    tok, emb = _readout(h0)
    toks, hiddens = [tok], []
    x[slot, 0] = emb
    for _ in range(n_decode):
        o = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
        hiddens.append(o[slot, 0].copy())
        tok, emb = _readout(o[slot, 0])
        toks.append(tok)
        x[slot, 0] = emb
    eng.release(slot)
    return h0, hiddens, toks


class TestChainHashes:
    def test_chain_is_prefix_dependent(self):
        rng = np.random.RandomState(0)
        a = rng.randn(3 * BS, D).astype(np.float32)
        b = a.copy()
        b[0, 0] += 1.0  # perturb block 0 only
        ha, hb = (chain_block_hashes(t, BS) for t in (a, b))
        assert len(ha) == 3
        # every later link inherits the divergence through the chain
        assert all(x != y for x, y in zip(ha, hb))
        # partial trailing block is never hashed
        assert len(chain_block_hashes(a[:3 * BS - 1], BS)) == 2
        # same content, same chain
        assert chain_block_hashes(a.copy(), BS) == ha


class TestSharedSystemPrompt:
    def test_hit_rate_and_bit_identical_decode(self):
        """ACCEPTANCE: 16 requests share a 3-block system prompt; after
        warmup the block hit rate is >= 80%, measurably fewer prefill
        tokens are computed than the cold path, every token stream is
        the no-prefix-cache engine's and every hidden within a few ulp
        of it (the warm run computes fewer rows a call)."""
        model = _model()
        rng = np.random.RandomState(0)
        sys_prompt = rng.randn(3 * BS, D).astype(np.float32)
        tails = [rng.randn(5, D).astype(np.float32) for _ in range(16)]
        prompts = [np.concatenate([sys_prompt, t]) for t in tails]
        T = 3 * BS + 5

        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB)
        warm = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB,
                                  prefix_cache=True)
        # 12 decode steps: 53 -> 65 crosses a page boundary at 64
        for p in prompts:
            hc, sc, tc = _serve_one(cold, p, 12)
            hw, sw, tw = _serve_one(warm, p, 12)
            _assert_same_to_a_few_ulp(hw, hc)
            for a, b in zip(sc, sw):
                _assert_same_to_a_few_ulp(b, a)
            assert tc == tw

        st = warm.prefix_stats
        assert st.lookups == 16
        assert st.lookup_blocks == 16 * 3
        assert st.hit_blocks == 15 * 3      # every lookup after warmup
        assert st.hit_rate == 45 / 48 >= 0.8
        # prefill FLOPs: cold computed every prompt token, warm only
        # the first prompt plus each request's uncached tail
        cold_prefill_tokens = 16 * T
        assert st.tokens_computed == T + 15 * 5
        assert st.tokens_computed < cold_prefill_tokens
        assert st.tokens_skipped == 15 * 3 * BS
        assert st.blocks_saved == 45
        # released system-prompt pages are parked cached-free, not lost
        assert warm.cache.allocator.num_cached >= 3

    def test_cross_length_adoption_bit_identical(self):
        """Pages computed under ONE prompt length are adopted by
        prompts of DIFFERENT lengths (variable tails, fully-aligned
        duplicates): same token streams, hiddens within a few ulp of
        the cold engine's (serving prefill attends over the scratch's
        full extent, so its reductions are length-independent; the
        matmuls' row counts are not)."""
        model = _model()
        rng = np.random.RandomState(5)
        sys_prompt = rng.randn(3 * BS, D).astype(np.float32)
        tails = (5, 13, 1, 9, 0, 0)  # 0 = the bare aligned system prompt
        prompts = [np.concatenate(
            [sys_prompt, rng.randn(t, D).astype(np.float32)])
            for t in tails]

        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB)
        warm = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB,
                                  prefix_cache=True)
        for p in prompts:
            hc, sc, tc = _serve_one(cold, p, 4)
            hw, sw, tw = _serve_one(warm, p, 4)
            _assert_same_to_a_few_ulp(hw, hc)
            for a, b in zip(sc, sw):
                _assert_same_to_a_few_ulp(b, a)
            assert tc == tw
        st = warm.prefix_stats
        assert st.hit_blocks == 5 * 3 and st.hit_rate == 15 / 18

    def test_partial_match_on_diverging_prompt(self):
        """A prompt sharing only the first 2 of 3 blocks matches
        exactly 2 (the chain breaks at the divergent block), and the
        recomputed suffix still decodes the same tokens (hiddens to a
        few ulp)."""
        model = _model()
        rng = np.random.RandomState(1)
        sys_prompt = rng.randn(3 * BS, D).astype(np.float32)
        p1 = np.concatenate([sys_prompt,
                             rng.randn(4, D).astype(np.float32)])
        p2 = p1.copy()
        p2[2 * BS + 3] += 1.0  # diverge inside block 2

        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB)
        warm = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB,
                                  prefix_cache=True)
        _serve_one(cold, p1, 4)
        _serve_one(warm, p1, 4)
        hc, sc, tc = _serve_one(cold, p2, 4)
        hw, sw, tw = _serve_one(warm, p2, 4)
        _assert_same_to_a_few_ulp(hw, hc)
        for a, b in zip(sc, sw):
            _assert_same_to_a_few_ulp(b, a)
        assert tc == tw
        st = warm.prefix_stats
        assert st.lookup_blocks == 6 and st.hit_blocks == 2


class TestMinSuffixRows:
    def test_one_row_suffix_regression(self):
        """Regression for the hoisted MIN_PREFILL_SUFFIX_ROWS
        constant: a prompt whose uncached tail is ONE row must still
        admit and decode bit-identically. Without the clamp the
        suffix-only prefill would run a 1-row attention, which lowers
        to a GEMV with different accumulation than the same row inside
        a multi-row prefill — the partial prefill keeps at least
        MIN_PREFILL_SUFFIX_ROWS recomputed rows instead."""
        from paddle_tpu.inference import MIN_PREFILL_SUFFIX_ROWS
        assert MIN_PREFILL_SUFFIX_ROWS >= 2
        model = _model()
        rng = np.random.RandomState(9)
        sys_prompt = rng.randn(3 * BS, D).astype(np.float32)
        # 1-token tail: the dangerous shape
        prompt = np.concatenate(
            [sys_prompt, rng.randn(1, D).astype(np.float32)])

        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB)
        warm = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=12, max_blocks_per_seq=MB,
                                  prefix_cache=True)
        _serve_one(cold, prompt, 0)
        _serve_one(warm, prompt, 0)     # registers the 3 prompt pages
        hc, sc, tc = _serve_one(cold, prompt, 6)
        hw, sw, tw = _serve_one(warm, prompt, 6)
        np.testing.assert_array_equal(hc, hw)
        for a, b in zip(sc, sw):
            np.testing.assert_array_equal(a, b)
        assert tc == tw
        st = warm.prefix_stats
        # all 3 blocks hit on the second admission, but the suffix
        # kept MIN_PREFILL_SUFFIX_ROWS rows: skipped tokens stop at
        # T - MIN_PREFILL_SUFFIX_ROWS, not at the 3-block boundary
        T = 3 * BS + 1
        assert st.hit_blocks == 3
        assert st.tokens_skipped == T - MIN_PREFILL_SUFFIX_ROWS


class TestChunkedSuffix:
    """Prefix-cache adoption composed with CHUNKED prefill: hits seed
    nothing — the suffix chunk(s) simply attend over the adopted pages
    through the chunk protocol (the pages->scratch gather is gone)."""

    def test_adoption_then_one_chunk_suffix(self):
        """A long cached prefix (128 tokens — past the old suite's
        64-token scratch shapes) followed by a short tail: the second
        admission adopts every prefix page and runs the suffix as ONE
        chunk, bit-identical to the cold engine."""
        model = _model()
        rng = np.random.RandomState(10)
        sys_prompt = rng.randn(8 * BS, D).astype(np.float32)
        prompt = np.concatenate(
            [sys_prompt, rng.randn(5, D).astype(np.float32)])
        kw = dict(max_batch=1, block_size=BS, num_blocks=24,
                  max_blocks_per_seq=10, chunk_tokens=32)
        cold = PagedServingEngine(model, **kw)
        warm = PagedServingEngine(model, prefix_cache=True, **kw)
        _serve_one(cold, prompt, 0)
        _serve_one(warm, prompt, 0)        # registers 8 prefix pages
        chunks_before = warm.prefill_stats.chunks
        hc, sc, tc = _serve_one(cold, prompt, 6)
        hw, sw, tw = _serve_one(warm, prompt, 6)
        np.testing.assert_array_equal(hc, hw)
        for a, b in zip(sc, sw):
            np.testing.assert_array_equal(a, b)
        assert tc == tw
        st = warm.prefix_stats
        assert st.hit_blocks == 8
        # the 5-token suffix ran as exactly ONE chunk over the pages
        assert warm.prefill_stats.chunks == chunks_before + 1

    def test_partial_hit_multi_chunk_suffix(self):
        """A suffix longer than one chunk after a partial hit: chunks
        continue from the adopted boundary, never rewriting the shared
        pages, still bit-identical."""
        model = _model()
        rng = np.random.RandomState(11)
        sys_prompt = rng.randn(2 * BS, D).astype(np.float32)
        p1 = np.concatenate([sys_prompt,
                             rng.randn(40, D).astype(np.float32)])
        p2 = np.concatenate([sys_prompt,
                             rng.randn(40, D).astype(np.float32)])
        kw = dict(max_batch=2, block_size=BS, num_blocks=24,
                  max_blocks_per_seq=MB, chunk_tokens=16)
        cold = PagedServingEngine(model, **kw)
        warm = PagedServingEngine(model, prefix_cache=True, **kw)
        _serve_one(cold, p1, 2)
        _serve_one(warm, p1, 2)
        hc, sc, tc = _serve_one(cold, p2, 4)
        hw, sw, tw = _serve_one(warm, p2, 4)
        np.testing.assert_array_equal(hc, hw)
        for a, b in zip(sc, sw):
            np.testing.assert_array_equal(a, b)
        assert tc == tw
        st = warm.prefix_stats
        assert st.hit_blocks == 2          # the shared system pages
        # shared pages stayed shared through the suffix chunks: the
        # index still resolves them (no COW split rewrote them)
        from paddle_tpu.inference import chain_block_hashes
        hashes = chain_block_hashes(sys_prompt, BS)
        assert len(warm.cache.match_prefix(hashes)) == 2


class TestHitDivergeCOW:
    def test_fully_cached_prompt_shares_every_page(self):
        """B's prompt fully matches A's 3 registered pages while A is
        still ACTIVE: B shares ALL of them (the suffix-only prefill
        never writes the adopted region, so no page is copied or
        split), recomputes only a 2-row tail for its admission hidden,
        and both rows then diverge into PRIVATE suffix pages and decode
        bit-identically to the cold engine."""
        model = _model()
        rng = np.random.RandomState(2)
        prompt = rng.randn(3 * BS, D).astype(np.float32)  # aligned: 3 pages

        warm = PagedServingEngine(model, max_batch=2, block_size=BS,
                                  num_blocks=16, max_blocks_per_seq=MB,
                                  prefix_cache=True)
        cold = PagedServingEngine(model, max_batch=2, block_size=BS,
                                  num_blocks=16, max_blocks_per_seq=MB)
        sa, ha = _admit(warm, prompt)
        ca, hca = _admit(cold, prompt)
        a_blocks = list(warm.cache.seq_blocks[sa])
        assert len(a_blocks) == 3
        used_after_a = warm.cache.blocks_in_use

        sb, hb = _admit(warm, prompt)
        cb, hcb = _admit(cold, prompt)
        np.testing.assert_array_equal(np.asarray(ha.numpy()),
                                      np.asarray(hca.numpy()))
        np.testing.assert_array_equal(np.asarray(hb.numpy()),
                                      np.asarray(hcb.numpy()))
        st = warm.prefix_stats
        assert st.hit_blocks == 3
        # A's full prompt + B's 2-row tail recompute (the minimum
        # suffix that stays bit-identical — see scheduler._prefill)
        assert st.tokens_computed == 3 * BS + 2
        assert st.tokens_skipped == 3 * BS - 2
        # every page shared with the ACTIVE owner, ZERO new blocks
        rc = warm.cache.allocator.refcount
        assert warm.cache.seq_blocks[sb] == a_blocks
        assert all(rc[b] == 2 for b in a_blocks)
        assert warm.cache.blocks_in_use == used_after_a

        # diverge: per-row different inputs; each row's appends land in
        # its own fresh suffix page, the shared prompt pages stay shared
        x = np.asarray(rng.randn(2, 1, D), np.float32)
        for _ in range(6):
            ow = np.asarray(warm.step(paddle.to_tensor(x)).numpy())
            oc = np.asarray(cold.step(paddle.to_tensor(x)).numpy())
            np.testing.assert_array_equal(ow, oc)
            x = ow[:, :1].copy()
        assert warm.cache.seq_blocks[sa][:3] == a_blocks
        assert warm.cache.seq_blocks[sb][:3] == a_blocks
        assert warm.cache.seq_blocks[sa][3] != warm.cache.seq_blocks[sb][3]

    def test_write_into_adopted_page_cow_splits(self):
        """If a write DOES land inside an adopted shared page (a caller
        extending a sequence mid-page, the fork/ensure contract), the
        copy-on-write split fires: the writer gets a private copy, the
        index and the peer keep the original."""
        model = _model()
        rng = np.random.RandomState(6)
        prompt = rng.randn(2 * BS, D).astype(np.float32)
        cache = model.gen_paged_cache(block_size=BS, num_blocks=10,
                                      max_seqs=2, max_blocks_per_seq=MB,
                                      prefix_cache=True)
        scratch = model.gen_cache(1, MB * BS)
        with paddle.no_grad():
            _, rc_ = model(paddle.to_tensor(prompt).unsqueeze(0),
                           caches=scratch, time_step=0)
        cache.ensure(0, 2 * BS)
        cache.write_prefill(0, rc_, 2 * BS)
        hashes = chain_block_hashes(prompt, BS)
        cache.register_prefix(0, hashes)

        assert cache.adopt_prefix(1, hashes) == 2
        shared = list(cache.seq_blocks[1])
        assert shared == cache.seq_blocks[0]
        # slot 1 "rewinds" into the middle of the last shared page and
        # appends -> the write block is shared -> COW split
        cache.ensure(1, 2 * BS - 4)
        assert cache.seq_blocks[1][1] != shared[1]
        assert cache.seq_blocks[1][0] == shared[0]   # untouched page
        rc = cache.allocator.refcount
        assert rc[shared[1]] == 1 and rc[shared[0]] == 2
        # the index still maps the hash to the ORIGINAL page
        assert cache.match_prefix(hashes) == shared


class TestReclaimUnderPressure:
    def test_lru_reclaim_breaks_chain_then_cold_reprefill(self):
        """A's released pages park cached-free; an unrelated request
        under pool pressure RECLAIMS them LRU-first (dropping their
        index entries); re-serving A's prompt then misses (the chain is
        broken at its reclaimed head) and re-prefills cold — still
        bit-identical."""
        model = _model()
        rng = np.random.RandomState(3)
        p_a = np.concatenate([rng.randn(3 * BS, D).astype(np.float32),
                              rng.randn(5, D).astype(np.float32)])
        p_b = rng.randn(3 * BS + 5, D).astype(np.float32)

        # 6 blocks -> 5 usable: one request's 4 pages never leave room
        # for another's 3 cached pages to survive intact
        warm = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=6, max_blocks_per_seq=MB,
                                  prefix_cache=True)
        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=6, max_blocks_per_seq=MB)
        _serve_one(warm, p_a, 4)
        _serve_one(cold, p_a, 4)
        alloc = warm.cache.allocator
        assert alloc.num_cached == 3          # A's 3 full prompt pages

        # B shares nothing: its 4+ pages must reclaim from the tier
        _serve_one(warm, p_b, 4)
        _serve_one(cold, p_b, 4)
        assert alloc.reclaimed >= 2
        assert warm.prefix_stats.hit_blocks == 0

        # A again: head-of-chain page was the LRU victim, so the match
        # is 0 blocks -> full cold re-prefill, bit-identical
        hits_before = warm.prefix_stats.hit_blocks
        hc, sc, tc = _serve_one(cold, p_a, 4)
        hw, sw, tw = _serve_one(warm, p_a, 4)
        assert warm.prefix_stats.hit_blocks == hits_before
        np.testing.assert_array_equal(hc, hw)
        for a, b in zip(sc, sw):
            np.testing.assert_array_equal(a, b)
        assert tc == tw

    def test_preempted_request_resurrects_its_own_pages(self):
        """Preemption releases pages to the cached-free tier; the
        re-admission's re-prefill matches the request's OWN full-block
        history hashes, so only the uncached tail is recomputed."""
        model = _model()
        rng = np.random.RandomState(4)
        prompt = rng.randn(2 * BS + 2, D).astype(np.float32)

        eng = PagedServingEngine(model, max_batch=1, block_size=BS,
                                 num_blocks=8, max_blocks_per_seq=MB,
                                 prefix_cache=True)
        slot, h = _admit(eng, prompt)
        x = np.zeros((1, 1, D), np.float32)
        x[0, 0] = _readout(np.asarray(h.numpy())[0])[1]
        for _ in range(3):
            o = np.asarray(eng.step(paddle.to_tensor(x)).numpy())
            x[0, 0] = _readout(o[0, 0])[1]
        eng.preempt(slot)
        assert eng.cache.allocator.num_cached == 2  # full prompt pages
        (req,) = eng.queue
        eng._try_admit()
        (rid, slot2, h2), = eng.admitted
        eng.admitted.clear()
        assert rid == req.rid
        # both full blocks of the history hit on re-admission
        st = eng.prefix_stats
        assert st.hit_blocks == 2
        assert st.tokens_skipped == 2 * BS
        # and the re-prefilled engine keeps decoding without error
        o = eng.step(paddle.to_tensor(x))
        assert o is not None


class TestWarmResumeMidPrefill:
    """Satellite (PR 6): prefix blocks are registered AS CHUNKS
    COMPLETE (scheduler._chunk_registrar riding chunked_prefill's
    on_chunk hook), not only when the whole prompt lands — so a long
    prefill preempted mid-stream re-adopts its own finished pages on
    re-admission instead of recomputing them."""

    def test_preempted_mid_prefill_resumes_warm(self):
        model = _model()
        rng = np.random.RandomState(21)
        prompt = rng.randn(3 * BS + 6, D).astype(np.float32)  # 54 rows

        eng = PagedServingEngine(model, max_batch=1, block_size=BS,
                                 num_blocks=10, max_blocks_per_seq=MB,
                                 prefix_cache=True, chunk_tokens=BS,
                                 prefill_token_budget=BS)
        eng.submit(paddle.to_tensor(prompt))
        x = paddle.to_tensor(np.zeros((1, 1, D), np.float32))
        # two budgeted steps stream two chunks = 2 full pages
        eng.step(x)
        eng.step(x)
        assert eng.prefilling[0] and not eng.admitted
        pos = eng._prefills[0]["pos"]
        assert pos >= 2 * BS
        # the completed pages are ALREADY indexed mid-prefill
        assert len(eng.cache._hash_to_block) == pos // BS

        eng.preempt(0)
        eng.preempted.clear()
        # victim's finished pages parked cached-free, resurrectable
        assert eng.cache.allocator.num_cached == pos // BS

        skipped_before = eng.prefix_stats.tokens_skipped
        for _ in range(8):
            eng.step(x)
            if eng.admitted:
                break
        (rid, slot, h), = eng.admitted
        eng.admitted.clear()
        st = eng.prefix_stats
        assert st.tokens_skipped - skipped_before >= 2 * BS, \
            "re-prefill recomputed pages that were already registered"
        assert st.hit_blocks >= 2

        # and the warm resume is transparent: the admission hidden is
        # a cold engine's (no preemption, no budget) to a few ulp
        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=10, max_blocks_per_seq=MB)
        _, hc = _admit(cold, prompt)
        _assert_same_to_a_few_ulp(h.numpy(), hc.numpy())

    def test_sync_admission_oom_retry_resumes_warm(self):
        """The same machinery through SYNCHRONOUS admission: an
        injected OOM mid-admission-prefill un-admits the request, but
        the chunks that landed before the fault stay registered — the
        retry adopts them instead of starting cold."""
        from paddle_tpu.inference import BlockOOM
        model = _model()
        rng = np.random.RandomState(22)
        prompt = rng.randn(3 * BS + 4, D).astype(np.float32)
        eng = PagedServingEngine(model, max_batch=1, block_size=BS,
                                 num_blocks=10, max_blocks_per_seq=MB,
                                 prefix_cache=True, chunk_tokens=BS)
        # let two chunks land, then fail the third page's allocation
        # (the alloc hook is the same entry a FaultInjector drives)
        calls = {"n": 0}

        def hook(n):
            calls["n"] += 1
            if calls["n"] == 3:
                raise BlockOOM("forced admission OOM")
        eng.cache.allocator.fault_hook = hook
        eng.submit(paddle.to_tensor(prompt))
        assert eng.preempted == [0] and not eng.admitted
        assert eng.cache.allocator.num_cached == 2   # landed chunks
        eng.cache.allocator.fault_hook = None

        eng._try_admit()
        (rid, slot, h), = eng.admitted
        eng.admitted.clear()
        assert eng.prefix_stats.tokens_skipped >= 2 * BS
        cold = PagedServingEngine(model, max_batch=1, block_size=BS,
                                  num_blocks=10, max_blocks_per_seq=MB)
        _, hc = _admit(cold, prompt)
        _assert_same_to_a_few_ulp(h.numpy(), hc.numpy())
