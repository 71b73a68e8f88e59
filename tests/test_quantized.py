"""Quantized serving: int8 KV pages + int8 weights.

The test pattern here is BOUNDED DIVERGENCE, not bit-identity: an int8
pool's dequantized values differ from the fp pool's by the per-row
quantization step, so the contracts are (a) a documented per-step
hidden/logit divergence bound, (b) greedy token-stream agreement, and
(c) every page-lifecycle property (COW fork, prefix adoption,
truncate/resurrect, quarantine, tenant charge, snapshot/restore)
EXACT on the quantized payload — the bytes are different from fp, but
they are the same bytes everywhere they are shared, adopted, copied or
restored. Quantization is opt-in (``dtype="int8"`` /
``kv_dtype="int8"`` / ``weight_dtype="int8"``); every fp suite runs
unchanged with it off.

Documented divergence bounds (asserted below, cited in the README
"Quantized serving" table):

  * element-wise dequantization error  <= amax_row / 254
    (half a quantization step at per-(position, head) scales)
  * per-step hidden divergence         max|h_q - h_fp| <= 0.05 * max|h_fp|
    (observed ~2e-3 relative at the test shapes; the bound is the
    contract, the observation is headroom)
  * greedy token agreement             100% at test scale
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (FaultInjector, PagedKVCache,
                                  PagedServingEngine, SpeculativeEngine,
                                  TokenServingModel)
from paddle_tpu.inference.accounting import WorkModel
from paddle_tpu.inference.scheduler import chunked_prefill

pytestmark = pytest.mark.quant

DIM, HEADS, FFN, LAYERS, VOCAB = 64, 4, 128, 2, 50
HEAD_DIM = DIM // HEADS


def make_model():
    paddle.seed(0)
    m = FusedMultiTransformer(DIM, HEADS, FFN, num_layers=LAYERS)
    m.eval()
    return m


def make_tsm(model=None, **kw):
    model = model or make_model()
    emb = np.random.default_rng(0).standard_normal(
        (VOCAB, DIM)).astype(np.float32)
    return TokenServingModel(model, emb, **kw)


def serve_tokens(tsm, *, kv_dtype="float32", n_req=4, prompt_len=7,
                 gen=8, num_blocks=48, max_batch=4, block_size=4,
                 prefix_cache=False, rounds=300, **kw):
    """Greedy token-ID serving loop; returns {rid: generated}."""
    eng = SpeculativeEngine(tsm, k=0, max_batch=max_batch,
                            block_size=block_size,
                            num_blocks=num_blocks, kv_dtype=kv_dtype,
                            prefix_cache=prefix_cache, **kw)
    prompts = np.random.default_rng(1).integers(
        0, VOCAB, (n_req, prompt_len))
    rids = [eng.submit(list(p)) for p in prompts]
    for _ in range(rounds):
        eng.step()
        if all(len(eng.generated(r)) >= gen for r in rids):
            break
    return {r: eng.generated(r)[:gen] for r in rids}, eng


# --------------------------------------------------------------- opt-in

def test_quantization_off_by_default():
    eng = PagedServingEngine(make_model(), max_batch=2, block_size=4,
                             num_blocks=8)
    assert eng.cache.quantized is False
    assert eng.cache.scales is None
    assert str(eng.cache.pools[0].data.dtype) == "float32"
    tsm = make_tsm()
    assert tsm.weight_dtype == "float32"
    assert tsm._head_int8 is None


# -------------------------------------------------- payload + byte model

def test_quantized_pool_roundtrip_error_bound():
    """Dequantized page content is within half a quantization step of
    the written values — the element-wise bound every higher-level
    divergence bound rests on."""
    model = make_model()
    cache = PagedKVCache.for_model(model, block_size=4, num_blocks=16,
                                   max_seqs=1, dtype="int8")
    rng = np.random.default_rng(2)
    k = rng.standard_normal((1, 8, HEADS, HEAD_DIM)).astype(np.float32)
    v = rng.standard_normal((1, 8, HEADS, HEAD_DIM)).astype(np.float32)
    cache.ensure(0, 8, write_from=0)
    cache.write_prefill_chunk(0, 0, paddle.to_tensor(k),
                              paddle.to_tensor(v), start=0)
    from paddle_tpu.ops.pallas.paged_attention import gather_pages
    kg, vg = gather_pages(cache.pools[0].data,
                          cache.block_tables[:1],
                          kv_scales=cache.scales[0].data)
    kg = np.asarray(kg)[0, :8]          # [T, H, D]
    vg = np.asarray(vg)[0, :8]
    for got, ref in ((kg, k[0]), (vg, v[0])):
        step = np.abs(ref).max(axis=-1, keepdims=True) / 127.0
        assert np.all(np.abs(got - ref) <= step / 2 + 1e-6)


def test_quantized_byte_model():
    """kv_bytes_per_token / pool_bytes count int8 payload + scale
    metadata — the honest numbers the ledger binds through."""
    model = make_model()
    fp = PagedKVCache.for_model(model, block_size=4, num_blocks=16,
                                max_seqs=1)
    q = PagedKVCache.for_model(model, block_size=4, num_blocks=16,
                               max_seqs=1, dtype="int8")
    assert fp.kv_bytes_per_token() == 2 * HEADS * HEAD_DIM * 4 * LAYERS
    assert q.kv_bytes_per_token() == 2 * HEADS * (HEAD_DIM + 4) * LAYERS
    assert q.pool_bytes() == LAYERS * 16 * 2 * HEADS * 4 * (HEAD_DIM + 4)
    # density vs a bf16 pool at the same geometry: 2D / (D + 4)
    bf16_per_token = 2 * HEADS * HEAD_DIM * 2 * LAYERS
    assert bf16_per_token / q.kv_bytes_per_token() == pytest.approx(
        2 * HEAD_DIM / (HEAD_DIM + 4))
    # the analytic work model follows the pool's real density
    wm_q = WorkModel.for_model(model,
                               kv_token_bytes=q.kv_bytes_per_token())
    assert wm_q.kv_token_bytes == q.kv_bytes_per_token()
    # int8 weights: 1-byte weight streaming in the MBU denominator
    wm_w8 = WorkModel.for_model(model, weight_itemsize=1)
    assert wm_w8.weight_bytes * 4 == WorkModel.for_model(model).weight_bytes


def test_chunking_invariance_of_quantized_payload():
    """The int8 payload + scales of a block are a pure function of the
    token stream — different chunk boundaries produce BIT-IDENTICAL
    quantized bytes (the property that makes prefix adoption exact)."""
    model = make_model()
    rows = np.random.default_rng(3).standard_normal(
        (23, DIM)).astype(np.float32)

    def fill(chunk):
        c = PagedKVCache.for_model(model, block_size=4, num_blocks=32,
                                   max_seqs=1, dtype="int8")
        _, h = chunked_prefill(model, c, 0, rows, chunk_tokens=chunk)
        return c, np.asarray(h.numpy())

    c1, h1 = fill(8)
    c2, h2 = fill(5)
    assert np.array_equal(h1, h2)
    for layer in range(LAYERS):
        p1 = np.asarray(c1.pools[layer].numpy())
        p2 = np.asarray(c2.pools[layer].numpy())
        s1 = np.asarray(c1.scales[layer].numpy())
        s2 = np.asarray(c2.scales[layer].numpy())
        for b1, b2 in zip(c1.seq_blocks[0], c2.seq_blocks[0]):
            assert np.array_equal(p1[b1], p2[b2])
            assert np.array_equal(s1[b1], s2[b2])


# ------------------------------------------------------ divergence bounds

def test_per_step_hidden_divergence_bound():
    """Feed the SAME inputs through an fp32 and an int8 engine: every
    step's hidden divergence stays inside the documented bound
    max|h_q - h_fp| <= 0.05 * max|h_fp|."""
    model = make_model()
    rng = np.random.default_rng(4)
    prompt = rng.standard_normal((9, DIM)).astype(np.float32)

    def build(dtype):
        eng = PagedServingEngine(model, max_batch=1, block_size=4,
                                 num_blocks=16, dtype=dtype)
        eng.submit(paddle.to_tensor(prompt))
        (_, slot, h) = eng.admitted.pop()
        return eng, slot, np.asarray(h.numpy())

    ef, sf, hf = build("float32")
    eq, sq, hq = build("int8")
    assert np.abs(hq - hf).max() <= 0.05 * np.abs(hf).max()
    for _ in range(12):
        x = rng.standard_normal((1, 1, DIM)).astype(np.float32)
        of = np.asarray(ef.step(paddle.to_tensor(x)).numpy())
        oq = np.asarray(eq.step(paddle.to_tensor(x)).numpy())
        assert np.abs(oq[sf] - of[sf]).max() \
            <= 0.05 * np.abs(of[sf]).max()


def test_greedy_token_agreement():
    tsm = make_tsm()
    fp, _ = serve_tokens(tsm)
    q, eng = serve_tokens(tsm, kv_dtype="int8")
    total = sum(len(v) for v in fp.values())
    agree = sum(int(a == b) for r in fp for a, b in zip(fp[r], q[r]))
    assert total == 4 * 8
    assert agree / total >= 0.99
    assert eng.engine.cache.quantized
    eng.check_invariants()


def test_w8a16_weight_path_divergence():
    """int8 readout head: per-output-channel scales folded into the
    epilogue; logits within 2% of fp, greedy argmax agrees, and the
    stored head is ~3.8x smaller than float32."""
    model = make_model()
    fp = make_tsm(model)
    q8 = make_tsm(model, weight_dtype="int8")
    h = paddle.to_tensor(np.random.default_rng(5).standard_normal(
        (6, DIM)).astype(np.float32))
    lf = np.asarray(fp.logits(h).numpy())
    lq = np.asarray(q8.logits(h).numpy())
    assert np.abs(lq - lf).max() <= 0.02 * np.abs(lf).max()
    assert (lf.argmax(-1) == lq.argmax(-1)).all()
    assert q8.weight_bytes() * 3 < fp.weight_bytes()
    # the quantized-weight serving loop emits the same greedy streams
    sf, _ = serve_tokens(fp)
    sq, _ = serve_tokens(q8, kv_dtype="int8")
    total = sum(len(v) for v in sf.values())
    agree = sum(int(a == b) for r in sf for a, b in zip(sf[r], sq[r]))
    assert agree / total >= 0.99


# --------------------------------------------- lifecycle on int8 payloads

def test_cow_fork_on_quantized_pages():
    """Fork shares int8 pages; the first divergent append COW-splits
    (payload AND scales travel with the copy) and the parent's bytes
    are untouched — proven by the deep immutability audit plus a
    direct byte compare."""
    model = make_model()
    cache = PagedKVCache.for_model(model, block_size=4, num_blocks=32,
                                   max_seqs=2, dtype="int8")
    rows = np.random.default_rng(6).standard_normal(
        (10, DIM)).astype(np.float32)
    chunked_prefill(model, cache, 0, rows, chunk_tokens=8)
    cache.fork(0, 1, 10)
    parent_blocks = list(cache.seq_blocks[0])
    assert cache.seq_blocks[1] == parent_blocks
    p_before = [np.asarray(p.numpy())[parent_blocks].copy()
                for p in cache.pools]
    s_before = [np.asarray(s.numpy())[parent_blocks].copy()
                for s in cache.scales]
    cache.check_invariants(deep=True)
    # divergent append on the child: COW-splits the shared tail block
    cache.ensure(1, 11, write_from=10)
    assert cache.seq_blocks[1][:-1] == parent_blocks[:-1]
    split = cache.seq_blocks[1][-1]
    assert split != parent_blocks[-1]
    # the split copy carries the page's scales with its payload
    lp = np.asarray(cache.pools[0].numpy())
    ls = np.asarray(cache.scales[0].numpy())
    assert np.array_equal(lp[split], lp[parent_blocks[-1]])
    assert np.array_equal(ls[split], ls[parent_blocks[-1]])
    k = np.random.default_rng(7).standard_normal(
        (1, 1, HEADS, HEAD_DIM)).astype(np.float32)
    cache.write_prefill_chunk(1, 0, paddle.to_tensor(k),
                              paddle.to_tensor(k), start=10)
    for layer in range(LAYERS):
        assert np.array_equal(
            np.asarray(cache.pools[layer].numpy())[parent_blocks],
            p_before[layer])
        assert np.array_equal(
            np.asarray(cache.scales[layer].numpy())[parent_blocks],
            s_before[layer])
    cache.check_invariants(deep=True)


def test_prefix_adoption_exact_after_truncate_resurrect():
    """Release parks quantized pages cached-free; a same-prefix
    request resurrects and ADOPTS them, and its greedy stream is
    bit-identical to a cold int8 run — adoption of quantized pages is
    exact because the bytes are chunking-invariant."""
    tsm = make_tsm()
    prompt = list(np.random.default_rng(8).integers(0, VOCAB, 12))

    def serve_one(eng):
        rid = eng.submit(prompt)
        for _ in range(100):
            eng.step()
            if len(eng.generated(rid)) >= 6:
                break
        return eng.generated(rid)[:6]

    cold = SpeculativeEngine(tsm, k=0, max_batch=2, block_size=4,
                             num_blocks=32, kv_dtype="int8",
                             prefix_cache=True)
    s_cold = serve_one(cold)

    warm = SpeculativeEngine(tsm, k=0, max_batch=2, block_size=4,
                             num_blocks=32, kv_dtype="int8",
                             prefix_cache=True)
    first = serve_one(warm)
    assert first == s_cold
    warm.release(list(warm._by_rid)[0])
    hits_before = warm.engine.prefix_stats.hit_blocks
    second = serve_one(warm)
    assert warm.engine.prefix_stats.hit_blocks > hits_before
    assert second == s_cold
    warm.check_invariants()


def test_quarantine_quantized_pages():
    """A numeric failure quarantines the slot's int8 pages (no
    cached-free second chance) and the pool audit stays clean."""
    inj = FaultInjector(nan_at={3: [0]})
    eng = PagedServingEngine(make_model(), max_batch=2, block_size=4,
                             num_blocks=16, dtype="int8",
                             prefix_cache=True, injector=inj)
    rng = np.random.default_rng(9)
    eng.submit(paddle.to_tensor(
        rng.standard_normal((6, DIM)).astype(np.float32)))
    eng.admitted.clear()
    x = paddle.to_tensor(rng.standard_normal(
        (2, 1, DIM)).astype(np.float32))
    for _ in range(3):
        eng.step(x)
    assert eng.resilience_stats.nan_failed == 1
    assert [oc.status for oc in eng.outcomes][-1] == "failed_numeric"
    assert not eng.cache.seq_blocks[0]
    eng.check_invariants()


def test_tenant_charge_on_quantized_pages():
    """The per-tenant block charge counts quantized pages exactly like
    fp pages (one charge per table reference) and quota enforcement
    still gates growth."""
    eng = PagedServingEngine(
        make_model(), max_batch=2, block_size=4, num_blocks=32,
        dtype="int8", tenants={"a": {"quota_blocks": 3}})
    rng = np.random.default_rng(10)
    eng.submit(paddle.to_tensor(
        rng.standard_normal((7, DIM)).astype(np.float32)),
        tenant_id="a")
    assert eng.cache.tenant_charge("a") == len(eng.cache.seq_blocks[0])
    eng.admitted.clear()
    x = paddle.to_tensor(rng.standard_normal(
        (2, 1, DIM)).astype(np.float32))
    for _ in range(8):
        if eng.num_active == 0:
            break
        eng.step(x)
    # growth past 3 blocks (12 tokens) sheds the sole tenant request
    assert eng.tenants["a"].stats.sheds == 1
    assert eng.cache.tenant_charge("a") == 0
    eng.check_invariants()


def test_snapshot_restore_quantized_roundtrip_and_rehoming():
    """A quantized engine snapshot round-trips: the restored pool
    holds the identical int8 payload + scales, allocates identically,
    and the continued greedy stream matches the uninterrupted run;
    rehoming into a different num_blocks survives the deep audit and
    preserves dequantized content."""
    tsm = make_tsm()
    prompt = list(np.random.default_rng(11).integers(0, VOCAB, 9))

    def drive(eng, rid, n):
        for _ in range(100):
            eng.step()
            if len(eng.generated(rid)) >= n:
                break
        return eng.generated(rid)[:n]

    eng = SpeculativeEngine(tsm, k=0, max_batch=2, block_size=4,
                            num_blocks=24, kv_dtype="int8")
    rid = eng.submit(prompt)
    drive(eng, rid, 4)
    snap = eng.snapshot()
    full = drive(eng, rid, 10)

    res = SpeculativeEngine.restore(tsm, None, snap)
    cache = res.engine.cache
    assert cache.quantized
    cont = drive(res, rid, 10)
    assert cont == full

    # same-geometry restore: EXACT allocator state (ids, free-list
    # order) and bit-identical payload + scales — the pool allocates
    # identically to the uninterrupted one
    a = PagedKVCache.restore(snap["engine"]["cache"])
    assert a.allocator._free == [int(b)
                                 for b in snap["engine"]["cache"]
                                 ["free_order"]]
    assert a.seq_blocks[0] == [
        int(b) for b in snap["engine"]["cache"]["seq_blocks"][0]]
    b = PagedKVCache.restore(snap["engine"]["cache"])
    for layer in range(LAYERS):
        assert np.array_equal(np.asarray(a.pools[layer].numpy()),
                              np.asarray(b.pools[layer].numpy()))
        assert np.array_equal(np.asarray(a.scales[layer].numpy()),
                              np.asarray(b.scales[layer].numpy()))

    # rehoming: bigger and smaller targets, deep audit inside restore
    for nb in (40, 12):
        re = PagedKVCache.restore(snap["engine"]["cache"],
                                  num_blocks=nb)
        assert re.num_blocks == nb and re.quantized
        slot_blocks = re.seq_blocks[0]
        src = PagedKVCache.restore(snap["engine"]["cache"])
        sp = np.asarray(src.pools[0].numpy())
        ss = np.asarray(src.scales[0].numpy())
        rp = np.asarray(re.pools[0].numpy())
        rs = np.asarray(re.scales[0].numpy())
        for bs_, bd in zip(src.seq_blocks[0], slot_blocks):
            assert np.array_equal(sp[bs_], rp[bd])
            assert np.array_equal(ss[bs_], rs[bd])


# ----------------------------------------- what the smaller pages buy

def test_equal_pool_bytes_admit_more_requests():
    """At EQUAL pool bytes an int8 pool (payload + per-row scales, the
    bytes ``pool_bytes()`` reports) holds 2 * 64 / (64 + 4) = 1.88x the
    blocks of a bf16 pool at head_dim 64, and a block-bound backlog
    admits >= 1.8x the concurrent requests. Every request reserves its
    full page need at admission (prompt + gen fills exactly 4 blocks),
    so the ceiling is arithmetic, (blocks - 1) // 4, and it is held
    while the queue is nonempty: blocked on admission, nothing else."""
    dim, heads, layers, block, bpr, gen, n_req = 128, 2, 2, 8, 4, 4, 30
    paddle.seed(0)
    model = FusedMultiTransformer(dim, heads, 256, num_layers=layers)
    model.eval()
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((VOCAB, dim)).astype(np.float32)
    prompts = rng.integers(0, VOCAB, (n_req, bpr * block - gen))
    nb16 = 25
    per_block = layers * 2 * heads * block
    budget = nb16 * per_block * (dim // heads) * 2
    nb8 = budget // (per_block * (dim // heads + 4))

    def ceiling(kv_dtype, num_blocks):
        eng = SpeculativeEngine(
            TokenServingModel(model, emb), k=0, max_batch=16,
            block_size=block, num_blocks=int(num_blocks),
            max_blocks_per_seq=bpr, kv_dtype=kv_dtype)
        assert eng.engine.cache.pool_bytes() <= budget
        rids = [eng.submit(list(p)) for p in prompts]
        most = at_backlog = 0
        for _ in range(100 * n_req):
            eng.step()
            live = eng.engine.num_active + eng.engine.num_prefilling
            most = max(most, live)
            if eng.engine._queue_len > 0:
                at_backlog = max(at_backlog, live)
            if all(len(eng.generated(r)) >= gen for r in rids):
                break
        assert all(len(eng.generated(r)) >= gen for r in rids)
        assert most == at_backlog == (int(num_blocks) - 1) // bpr
        return most

    assert ceiling("int8", nb8) / ceiling("bfloat16", nb16) >= 1.8


# ------------------------------------------------------- kernel plumbing

def test_ragged_kernel_quant_parity_interpret():
    """paged_attention_ragged with kv_scales (interpret mode) matches
    the dequantizing jnp reference, including tile_kv > 1."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_ragged, paged_attention_ragged_reference)
    rng = np.random.default_rng(12)
    NB, nkv, bs, hd, nh = 12, 2, 4, 8, 4
    pool_f = rng.standard_normal((NB, 2, nkv, bs, hd)).astype(
        np.float32)
    amax = np.abs(pool_f).max(-1)
    sc = (amax / 127.0).astype(np.float32)
    qp = np.clip(np.round(pool_f / np.maximum(sc, 1e-30)[..., None]),
                 -127, 127).astype(np.int8)
    bt = np.zeros((3, 4), np.int32)
    bt[0, :3] = [1, 2, 3]
    bt[1, :2] = [4, 5]
    bt[2, :4] = [6, 7, 8, 9]
    q_lens = (1, 2, 5)
    kv_lens = jnp.asarray([9, 6, 13], jnp.int32)
    q = jnp.asarray(rng.standard_normal(
        (sum(q_lens), nh, hd)).astype(np.float32))
    ref = paged_attention_ragged_reference(
        q, jnp.asarray(qp), jnp.asarray(bt), q_lens, kv_lens,
        kv_scales=jnp.asarray(sc))
    for tkv in (None, 2):
        out = paged_attention_ragged(
            q, jnp.asarray(qp), jnp.asarray(bt), q_lens, kv_lens,
            kv_scales=jnp.asarray(sc), tile_kv=tkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    # dequantized reference == reference over a dequantized fp pool
    deq = qp.astype(np.float32) * sc[..., None]
    ref_fp = paged_attention_ragged_reference(
        q, jnp.asarray(deq), jnp.asarray(bt), q_lens, kv_lens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref_fp))
