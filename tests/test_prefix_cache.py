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


# ---------------------------------------------------------------------
# a block's identity from token ids (PR 36): a request that was handed
# keys hashes them and never its rows; one that has rows only hashes
# the rows; the token wrapper's gathered rows are kept, not copied
# ---------------------------------------------------------------------

from paddle_tpu.inference import (SpeculativeEngine,      # noqa: E402
                                  TokenServingModel, token_chain_hashes)
from paddle_tpu.inference.paged_cache import ID_CHAIN_TAG  # noqa: E402
from paddle_tpu.inference.scheduler import PagedRequest    # noqa: E402

TBS = 4                                  # 4-token pages for these
_TOK_RNG = np.random.RandomState(4321)
_TOK_EMBED = _TOK_RNG.randn(_VOCAB, D).astype(np.float32)
_TOK_HEAD = _TOK_RNG.randn(D, _VOCAB).astype(np.float32)


def _tsm(layers=LAYERS, seed=0):
    paddle.seed(seed)
    return TokenServingModel(
        FusedMultiTransformer(D, HEADS, FFN, num_layers=layers),
        _TOK_EMBED, _TOK_HEAD)


def _token_engine(tsm=None, draft=None, k=0, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_blocks_per_seq", 16)
    return SpeculativeEngine(tsm or _tsm(), draft, k=k, max_batch=2,
                             block_size=TBS, prefix_cache=True, **kw)


def _ids(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, _VOCAB, n)]


def _consumed(eng, rid):
    """(the engine's request, the ids of the rows in its history)."""
    eng.engine._flush_history()
    req = eng.engine.request_of(rid)
    return req, eng.tokens(rid)[:len(req)]


class TestBlockIdentityFromTokenIds:
    def test_same_ids_share_as_rows_did_and_a_second_turn_adopts_decoded_blocks(self):
        tsm = _tsm()
        eng = _token_engine(tsm)
        st = eng.engine.prefix_stats
        p = _ids(1, 10)
        r1 = eng.submit(p)
        for _ in range(9):
            eng.step()
        # blocks that filled during decoding reach the index when the
        # request is prefilled again: preempt it, let it come back
        eng.engine.preempt(eng._by_rid[r1].slot)
        eng._handle_events()
        indexed = len(eng.engine.request_of(r1)) // TBS
        for _ in range(3):
            eng.step()
        req, turn1 = _consumed(eng, r1)
        assert indexed == 4 and req.keys is eng._by_rid[r1].toks
        eng.release(r1)
        # two submits of the same ids share the prompt's full blocks,
        # as two submits of the same ROWS do on a bare engine
        hits = st.hit_blocks
        r2 = eng.submit(p)
        assert st.hit_blocks - hits == len(p) // TBS
        eng.release(r2)
        bare = PagedServingEngine(tsm.core, max_batch=2, block_size=TBS,
                                  num_blocks=64, max_blocks_per_seq=16,
                                  prefix_cache=True)
        for _ in range(2):
            bare.submit(tsm.embed(p))
            (_, slot, _), = bare.admitted
            bare.admitted.clear()
            bare.release(slot)
        assert bare.prefix_stats.hit_blocks == len(p) // TBS
        assert bare.prefix_stats.row_keyed_blocks == 2 * (len(p) // TBS)
        # the second turn: the first prompt, its answer, new tokens
        hits = st.hit_blocks
        r3 = eng.submit(turn1 + _ids(2, 5))
        assert st.hit_blocks - hits == indexed > len(p) // TBS
        assert st.row_keyed_blocks == 0
        assert eng.engine.check_invariants()
        eng.release(r3)

    def test_an_id_keyed_and_a_row_keyed_block_never_share_a_hash(self):
        tsm = _tsm()
        p = _ids(3, 12)
        rows = tsm.embed(p)
        by_ids = PagedRequest(0, rows, keys=p).block_hashes(TBS)
        by_rows = PagedRequest(1, rows).block_hashes(TBS)
        assert len(by_ids) == len(by_rows) == 3
        assert not set(by_ids) & set(by_rows)
        assert by_ids == chain_block_hashes(np.asarray(p), TBS)
        assert by_rows == chain_block_hashes(rows, TBS)
        # the tag alone tells them apart where the BYTES are equal
        # (width-1 rows whose float32 bits are the ids' int32 bits)
        ids = np.arange(100, 108, dtype=np.int32)
        twins = ids.view(np.float32).reshape(-1, 1)
        assert twins.tobytes() == ids.tobytes() and ID_CHAIN_TAG
        assert not set(chain_block_hashes(ids, TBS)) \
            & set(chain_block_hashes(twins, TBS))
        # and on one engine neither adopts the other's pages
        eng = PagedServingEngine(tsm.core, max_batch=2, block_size=TBS,
                                 num_blocks=64, max_blocks_per_seq=16,
                                 prefix_cache=True)
        eng.submit(rows.copy(), keys=list(p))
        eng.submit(rows.copy())
        assert eng.prefix_stats.hit_blocks == 0
        assert eng.prefix_stats.row_keyed_blocks == 3
        assert eng.prefix_stats.hashed_bytes == 4 * 12 + rows.nbytes
        with pytest.raises(ValueError, match="keys for"):
            eng.submit(rows.copy(), keys=p[:5])

    def test_truncate_history_drops_keys_and_memoized_hashes_together(self):
        tsm = _tsm()
        p = _ids(4, 8)
        pend, d1, d2, d3, fix, nxt = _ids(5, 6)
        toks = list(p)
        req = PagedRequest(0, tsm.embed(p), keys=toks)
        h0 = list(req.block_hashes(TBS))
        # a verify round: the pending token's row and three drafts'
        for t in (pend, d1, d2, d3):
            req.append_history(tsm.embed([t])[0])
        # nobody appended the drafts' ids, and no hash is made of rows
        with pytest.raises(ValueError, match="keys cover 8 of 12"):
            req.block_hashes(TBS)
        toks.extend([pend, d1, d2, d3])
        drafted = list(req.block_hashes(TBS))
        assert drafted[:2] == h0 and len(drafted) == 3
        # d2 is rejected: two rows go, the ids after d1 go, the hash
        # of the block the drafts had filled goes
        req.truncate_history(10, TBS)
        del toks[10:]
        toks.extend([fix, nxt])
        assert req.block_hashes(TBS) == h0
        for t in (fix, nxt):
            req.append_history(tsm.embed([t])[0])
        got = req.block_hashes(TBS)
        assert got == chain_block_hashes(
            np.asarray(p + [pend, d1, fix, nxt]), TBS)
        assert got[2] != drafted[2]

    def test_the_stream_after_rejected_drafts_indexes_accepted_tokens_only(self):
        paddle.seed(99)
        draft = TokenServingModel(
            FusedMultiTransformer(D, HEADS, FFN, num_layers=1),
            _TOK_EMBED, _TOK_HEAD)
        eng = _token_engine(draft=draft, k=3)
        rid = eng.submit(_ids(6, 9))
        for _ in range(8):
            eng.step()
        assert eng.stats.rolled_back > 0
        req, consumed = _consumed(eng, rid)
        assert req.keys is eng._by_rid[rid].toks
        assert len(consumed) == len(req) >= 12
        # prefilled again, the request registers the blocks of what
        # was ACCEPTED: the hashes of its ids, no draft among them
        eng.engine.preempt(eng._by_rid[rid].slot)
        eng._handle_events()
        eng.step()
        req, consumed = _consumed(eng, rid)
        want = token_chain_hashes(eng.target, consumed, TBS)
        assert req.block_hashes(TBS) == want
        cache = eng.engine.cache
        assert cache.match_prefix(want) == \
            cache.seq_blocks[req.slot][:len(want)]
        assert eng.engine.prefix_stats.row_keyed_blocks == 0
        assert eng.check_invariants()

    def test_snapshot_restore_and_journal_replay_reproduce_the_hits(
            self, tmp_path):
        from paddle_tpu.inference import RecoverableServer
        tsm = _tsm()
        paths = dict(journal_path=str(tmp_path / "j.wal"),
                     snapshot_path=str(tmp_path / "s.snap"))
        srv = RecoverableServer(_token_engine(tsm), **paths)
        p = _ids(7, 13)
        r1 = srv.submit(p)
        for _ in range(4):
            srv.step()
        srv.release(r1)
        r2 = srv.submit(p + _ids(8, 3))        # hits the first's blocks
        srv.save_snapshot()
        srv.step()
        srv.release(r2)
        r3 = srv.submit(p[:9] + _ids(9, 6))    # after the snapshot
        srv.step()
        live = srv.engine.engine.prefix_stats
        assert live.hit_blocks == 3 + 2 and live.row_keyed_blocks == 0
        want = srv.engine.tokens(r3)
        srv.journal.close()
        back = RecoverableServer.recover(_tsm(), **paths)
        got = back.engine.engine.prefix_stats
        assert (got.hit_blocks, got.lookup_blocks, got.tokens_skipped,
                got.row_keyed_blocks) == \
            (live.hit_blocks, live.lookup_blocks, live.tokens_skipped, 0)
        assert back.engine.tokens(r3) == want
        req = back.engine.engine.request_of(r3)
        assert req.keys is back.engine._by_rid[r3].toks
        assert req.block_hashes(TBS) == token_chain_hashes(
            tsm, want[:len(req)], TBS)
        back.close()

    def test_a_snapshot_without_keys_loads_and_serves(self):
        """The layout of a snapshot written before requests had keys:
        no ``keys`` in a request's record, hashes and index made from
        rows, no ``hashed_bytes`` / ``row_keyed_blocks`` among the
        stats. Its requests go on hashing rows."""
        tsm = _tsm()
        old = _token_engine(tsm)
        submit = old.engine.submit
        old.engine.submit = lambda rows, keys=None, **kw: \
            submit(rows, **kw)                 # as the parent did
        p = _ids(10, 11)
        rid = old.submit(p)
        for _ in range(3):
            old.step()
        snap = old.snapshot()
        for rec in snap["engine"]["requests"]:
            assert rec.pop("keys") is None
            assert rec["hashes"] == chain_block_hashes(
                rec["history"], TBS)[:len(rec["hashes"])]
        for name in ("hashed_bytes", "row_keyed_blocks"):
            del snap["engine"]["stats"]["prefix"][name]
        live = _token_engine(tsm)
        live.submit(p)
        for _ in range(3):
            live.step()
        back = SpeculativeEngine.restore(tsm, None, snap)
        req = back.engine.request_of(rid)
        assert req.keys is None
        for _ in range(4):
            assert back.step() == live.step()
        # prefilled again it adopts its own pages: row hashes, as the
        # index the snapshot carried was made
        hits = back.engine.prefix_stats.hit_blocks
        back.engine.preempt(back._by_rid[rid].slot)
        back._handle_events()
        back.step()
        assert back.engine.prefix_stats.hit_blocks - hits >= len(p) // TBS
        assert back.engine.prefix_stats.row_keyed_blocks > 0
        assert back.check_invariants()
        # a request submitted now is keyed by ids, and shares nothing
        # with the row-keyed pages
        hits = back.engine.prefix_stats.hit_blocks
        back.submit(p)
        assert back.engine.prefix_stats.hit_blocks == hits

    @pytest.mark.parametrize("budget", [None, 8])
    def test_forks_and_branches_read_their_own_streams(self, budget):
        """A branch group's members and a forked stream are requests of
        their own: each reads ITS stream's ids, the rows in its history
        are those ids' rows (a branch forked inside a mixed step sits
        that step's decode out, as its lead does), and a preempted
        member comes back over its own blocks."""
        tsm = _tsm()
        eng = SpeculativeEngine(tsm, None, k=0, max_batch=4,
                                block_size=TBS, num_blocks=80,
                                max_blocks_per_seq=16, prefix_cache=True,
                                prefill_token_budget=budget)
        gid = eng.submit(_ids(13, 10), n=3, seed=5)
        for _ in range(6):
            eng.step()
        rids = list(eng.group(gid)["rids"])
        rids.append(eng.fork_stream(rids[1]))
        for _ in range(3):
            eng.step()
        for rid in rids[1:]:
            eng.engine.preempt(eng._by_rid[rid].slot)
            eng._handle_events()
        for _ in range(5):
            eng.step()
        for rid in rids:
            req, consumed = _consumed(eng, rid)
            assert req.keys is eng._by_rid[rid].toks
            assert len(eng.tokens(rid)) == len(req) + 1    # the pending
            np.testing.assert_array_equal(req.history,
                                          tsm.embed(consumed))
            assert req.block_hashes(TBS) == token_chain_hashes(
                tsm, consumed, TBS)
        assert eng.engine.prefix_stats.row_keyed_blocks == 0
        assert eng.check_invariants()
        back = SpeculativeEngine.restore(tsm, None, eng.snapshot())
        for rid in rids:
            assert back.engine.request_of(rid).keys \
                is back._by_rid[rid].toks
        for _ in range(3):
            assert back.step() == eng.step()

    def test_token_chain_hashes_is_the_engines_chain_and_embeds_nothing(
            self, monkeypatch):
        tsm = _tsm()
        eng = _token_engine(tsm, prefill_token_budget=8)
        ids = _ids(11, 23)
        rid = eng.submit(ids)
        req = eng.engine.request_of(rid)

        def no_embed(*a, **kw):
            raise AssertionError("token_chain_hashes embedded its ids")
        monkeypatch.setattr(tsm, "embed", no_embed)
        got = token_chain_hashes(tsm, ids, TBS)
        assert got == req.block_hashes(TBS) and len(got) == 23 // TBS
        assert got == chain_block_hashes(np.asarray(ids, np.int64), TBS)
        assert token_chain_hashes(tsm, np.asarray(ids[:3]), TBS) == []
        with pytest.raises(ValueError, match="out of range"):
            token_chain_hashes(tsm, [0, _VOCAB], TBS)

    def test_submit_reads_ids_only_and_keeps_the_gathered_rows(
            self, monkeypatch):
        tsm = _tsm()
        T = 4096
        eng = _token_engine(tsm, num_blocks=T // TBS + 8,
                            max_blocks_per_seq=T // TBS + 2,
                            prefill_token_budget=16)
        gathered = []
        embed = tsm.embed

        def spy(ids):
            gathered.append(embed(ids))
            return gathered[-1]
        monkeypatch.setattr(tsm, "embed", spy)
        rid = eng.submit(_ids(12, T))
        st = eng.engine.prefix_stats
        assert st.hashed_bytes == 4 * T and st.row_keyed_blocks == 0
        req = eng.engine.request_of(rid)
        rows, = gathered
        assert rows.shape == (T, D)
        assert req._hist is rows                  # no second [T, d]
        assert np.shares_memory(req.history, rows)
        assert not rows.flags.writeable           # handed over: frozen
        # the first decode row grows the history into an array of the
        # request's own; the frozen one is never written
        before = rows.copy()
        req.append_history(np.ones(D, np.float32))
        assert len(req) == T + 1 and req._hist is not rows
        np.testing.assert_array_equal(rows, before)
        np.testing.assert_array_equal(req.history[:T], before)
        # a foreign caller's rows are copied: it may keep writing
        bare = PagedServingEngine(tsm.core, max_batch=2, block_size=TBS,
                                  num_blocks=64, max_blocks_per_seq=16,
                                  prefix_cache=True,
                                  prefill_token_budget=16)
        mine = np.random.RandomState(0).randn(24, D).astype(np.float32)
        kept = mine.copy()
        for handed in (mine, mine[:20], paddle.to_tensor(mine)):
            r = bare.submit(handed)
            theirs = bare.request_of(r)
            assert not np.shares_memory(theirs.history, mine)
            mine[:] = 0.0
            np.testing.assert_array_equal(theirs.history,
                                          kept[:len(theirs)])
            mine[:] = kept
