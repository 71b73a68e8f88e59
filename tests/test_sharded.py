"""Tensor-parallel sharded paged serving (inference/serving.py
ShardedServingCore + the mp-sharded PagedKVCache in paged_cache.py).

The acceptance bar is the stack's house standard: a dp=1/mp=2 mesh run
must be BIT-IDENTICAL to the single-chip engine — plain, prefix-cached,
speculative, quantized and token-budget mixed-step serving — with
exactly ``num_layers`` all-reduces per step on the sharded path, and
snapshots/migration slices portable across mesh widths (mp=N <-> mp=1)
through the canonical full-head page format.

These tests run the shards LOGICALLY (serving_shard_devices cycles the
single CI device): numerics and the collective schedule are identical
to a real mesh — the per-shard executables don't know their neighbors
— only placement is degenerate. The REAL 2-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``) is exercised
by the subprocesses of tests/test_sharded_compiled.py (the host-staged
and the compiled path both).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.fused_transformer import FusedMultiTransformer
from paddle_tpu.inference import (PagedKVCache, PagedServingEngine,
                                  ShardedServingCore, SpeculativeEngine,
                                  TokenServingModel)

pytestmark = pytest.mark.sharded

D, H, FFN, LAYERS, VOCAB, BS = 32, 4, 64, 2, 50, 4
PROMPTS = [list(range(5 + i, 12 + i)) for i in range(3)]


def _tsm(seed=0):
    rng = np.random.RandomState(seed)
    m = FusedMultiTransformer(D, H, FFN, num_layers=LAYERS)
    for blk in m.layers:
        for name in ("qkv", "out_proj", "ffn1", "ffn2"):
            lin = getattr(blk, name)
            lin.weight.set_value(paddle.to_tensor(
                (rng.randn(*lin.weight.shape) * 0.1).astype(np.float32)))
            lin.bias.set_value(paddle.to_tensor(
                (rng.randn(*lin.bias.shape) * 0.01).astype(np.float32)))
    emb = (rng.randn(VOCAB, D) * 0.3).astype(np.float32)
    # rolled readout: greedy streams WALK the vocab instead of locking
    # onto the tied readout's fixed point — a sharding bug cannot hide
    # inside a constant stream
    return TokenServingModel(m, emb, lm_head=np.roll(emb, -1, 0).T.copy())


def _run(tsm, steps=8, prompts=None, **kw):
    """Serve PROMPTS for ``steps`` rounds; returns (engine,
    {prompt index: full token stream})."""
    cfg = dict(k=0, max_batch=3, block_size=BS, num_blocks=40)
    cfg.update(kw)
    eng = SpeculativeEngine(tsm, **cfg)
    rids = [eng.submit(p) for p in
            (PROMPTS if prompts is None else prompts)]
    for _ in range(steps):
        eng.step()
    return eng, {i: eng.tokens(r) for i, r in enumerate(rids)}


# streams are a pure function of the workload knobs — compute each
# single-chip baseline once for the whole module
_BASE = {}


def _baseline(**kw):
    key = tuple(sorted(kw.items()))
    if key not in _BASE:
        _BASE[key] = _run(_tsm(), **kw)[1]
    return _BASE[key]


class TestGuards:
    def test_mp_must_divide_heads(self):
        with pytest.raises(ValueError, match="divide"):
            ShardedServingCore(_tsm().core, 3)
        with pytest.raises(ValueError, match="divide"):
            PagedKVCache(LAYERS, H, 8, BS, 10, 2, mp=3)

    def test_dense_caches_refused(self):
        core = ShardedServingCore(_tsm().core, 2)
        with pytest.raises(NotImplementedError, match="PAGED"):
            core(paddle.to_tensor(np.zeros((1, 2, D), np.float32)))

    def test_mesh_width_mismatch_refused(self):
        core = ShardedServingCore(_tsm().core, 2)
        cache = PagedKVCache(LAYERS, H, D // H, BS, 10, 2, mp=1)
        x = paddle.to_tensor(np.zeros((2, 1, D), np.float32))
        with pytest.raises(ValueError, match="mesh width"):
            core(x, caches=cache.views,
                 time_step=paddle.to_tensor(np.zeros(2, np.int32)))

    def test_full_head_call_on_sharded_pool_refused(self):
        """A single-chip model driven at a sharded pool must fail
        loudly — a full-head q against an H/mp pool would otherwise
        be misread as a GQA group."""
        cache = PagedKVCache(LAYERS, H, 8, BS, 10, 2, mp=2)
        cache.ensure(0, 1)
        q = paddle.to_tensor(np.zeros((2, 1, H, 8), np.float32))
        t = np.zeros(2, np.int32)
        with pytest.raises(ValueError, match="ShardedServingCore"):
            cache.views[0].decode(q, q, q, t)


class TestBitIdentity:
    """mp=2 streams byte-equal to the single chip, per serving mode."""

    def test_plain_paged_decode(self):
        base = _baseline()
        eng, toks = _run(_tsm().shard(2))
        assert toks == base
        eng.check_invariants()

    def test_prefix_cache(self):
        base = _baseline(prefix_cache=True)
        eng, toks = _run(_tsm().shard(2), prefix_cache=True)
        assert toks == base
        eng.check_invariants()

    def test_speculative_self_draft(self):
        base = _baseline(k=2)
        eng, toks = _run(_tsm().shard(2), k=2)
        assert toks == base
        # the draft pool sharded alongside the target (self-draft
        # shares the sharded core): both pools split over the mesh
        assert eng.engine.cache.mp == 2
        assert eng.draft_cache.mp == 2
        eng.check_invariants()

    def test_token_budget_mixed_step(self):
        base = _baseline(k=2, prefill_token_budget=8,
                         prefix_cache=True)
        eng, toks = _run(_tsm().shard(2), k=2, prefill_token_budget=8,
                         prefix_cache=True)
        assert toks == base
        eng.check_invariants()

    def test_weight_sharded_qkv_path(self):
        """The TPU-default WEIGHT-sharded qkv (column slices per
        shard) forced on CPU: bit-identical at these dims — column
        slicing is exact below the width where XLA CPU's GEMM tiling
        shifts (the reason the CPU default slices activations
        instead; see ShardedServingCore)."""
        base = _baseline()
        eng, toks = _run(_tsm().shard(2, qkv_shard="weights"))
        assert eng.target.core.qkv_shard == "weights"
        assert len(eng.target.core._qkv_w) == LAYERS
        assert toks == base
        eng.check_invariants()

    def test_int8_pool(self):
        """Per-(position, head) quantization is head-sliced exact, so
        even the QUANTIZED pool's streams match the single chip
        bit-for-bit."""
        base = _baseline(kv_dtype="int8", prefix_cache=True)
        eng, toks = _run(_tsm().shard(2), kv_dtype="int8",
                         prefix_cache=True)
        assert toks == base
        eng.check_invariants()


class TestAllReduceContract:
    def test_exactly_num_layers_allreduces_per_mixed_step(self):
        """The tentpole contract: ONE all-reduce per layer per model
        call — a token-budget mixed step (prefill chunks packed with
        the verify rows) is one model call, so exactly num_layers.
        This is the HOST-STAGED legacy path's contract, so it pins
        compiled_step=False: on a multi-device client the default
        auto-engages the compiled program, whose collectives live
        inside the jitted call (allreduce_count stays 0 there —
        tests/test_sharded_compiled.py owns that contract)."""
        tsm = _tsm().shard(2, compiled_step=False)
        eng = SpeculativeEngine(tsm, k=2, max_batch=3, block_size=BS,
                                num_blocks=40, prefill_token_budget=8)
        rids = [eng.submit(p) for p in PROMPTS]
        for _ in range(4):
            eng.step()
        # steady state: one spec round = K+1 draft forwards on the
        # sharded self-draft core + ONE verify step_multi (the mixed
        # step — ONE model call however many prefill chunks pack into
        # it). Every model call closes each layer with exactly one
        # all-reduce: the count is a whole multiple of num_layers,
        # and the MIXED STEP itself contributes exactly num_layers.
        tsm.core.reset_allreduce_count()
        before = eng.engine._step_count
        eng.step()
        assert eng.engine._step_count - before == 1  # ONE mixed step
        n = tsm.core.allreduce_count
        assert n % LAYERS == 0, (n, LAYERS)
        assert n // LAYERS == eng.k + 2  # k+1 draft fwds + 1 verify
        del rids
        eng.check_invariants()

    def test_plain_decode_one_allreduce_per_layer(self):
        # legacy host-staged path (see docstring above)
        tsm = _tsm().shard(2, compiled_step=False)
        eng = SpeculativeEngine(tsm, k=0, max_batch=3, block_size=BS,
                                num_blocks=40)
        rids = [eng.submit(p) for p in PROMPTS]
        tsm.core.reset_allreduce_count()
        eng.step()     # k=0: ONE engine.step -> ONE model call
        assert tsm.core.allreduce_count == LAYERS
        del rids

    def test_per_shard_bytes_and_occupancy(self):
        c1 = PagedKVCache(LAYERS, H, 8, BS, 20, 3)
        c2 = PagedKVCache(LAYERS, H, 8, BS, 20, 3, mp=2)
        # payload divides over the mesh, metadata replicates
        assert c2.pool_bytes() * 2 == c1.pool_bytes()
        assert c2.pool_bytes_total() == c1.pool_bytes()
        assert c2.kv_bytes_per_token() * 2 == c1.kv_bytes_per_token()
        occ = c2.pool_occupancy()
        assert occ["mp"] == 2
        assert occ["pool_bytes_per_shard"] == c2.pool_bytes()
        assert "mp" not in c1.pool_occupancy()
        # int8: scale metadata divides with its payload
        q1 = PagedKVCache(LAYERS, H, 8, BS, 20, 3, dtype="int8")
        q2 = PagedKVCache(LAYERS, H, 8, BS, 20, 3, dtype="int8", mp=2)
        assert q2.pool_bytes() * 2 == q1.pool_bytes()
        assert q2.kv_bytes_per_token() * 2 == q1.kv_bytes_per_token()


class TestSnapshotPortability:
    """mp=N and mp=1 snapshots restore into each other through the
    canonical full-head page format, continuing bit-identically."""

    def _crossover(self, src_mp, dst_mp, **kw):
        ref = _baseline(**kw)
        src = _tsm().shard(src_mp) if src_mp > 1 else _tsm()
        e1 = SpeculativeEngine(src, max_batch=3, block_size=BS,
                               num_blocks=40, **kw)
        rids = [e1.submit(p) for p in PROMPTS]
        for _ in range(4):
            e1.step()
        snap = e1.snapshot()
        dst = _tsm().shard(dst_mp) if dst_mp > 1 else _tsm()
        e2 = SpeculativeEngine.restore(dst, None, snap)
        assert e2.engine.cache.mp == dst_mp
        for _ in range(4):
            e2.step()
        assert {i: e2.tokens(r) for i, r in enumerate(rids)} == ref
        e2.check_invariants()

    def test_mp2_snapshot_restores_at_mp1(self):
        self._crossover(2, 1, k=2, prefix_cache=True)

    def test_mp1_snapshot_restores_at_mp2(self):
        self._crossover(1, 2, k=2, prefix_cache=True)

    def test_int8_crossover(self):
        self._crossover(2, 1, k=0, kv_dtype="int8")


class TestSliceAcrossWidths:
    def test_slice_exports_canonical_and_imports_any_width(self):
        """Migration slices carry full-head pages whatever the donor's
        mesh width — an mp=2 donor's slice lands in an mp=1 pool and
        vice versa, and the adopter's suffix prefill skips the work."""
        a, _ = _run(_tsm().shard(2), prefix_cache=True)
        b, _ = _run(_tsm(), prefix_cache=True, num_blocks=60)
        rid_a = sorted(a._by_rid)[0]
        slc = a.export_slice(rid_a)
        assert slc is not None
        assert slc["geometry"]["num_heads"] == H      # canonical
        # the identical-prompt prefix already lives in b; a DIFFERENT
        # donor stream still carries fresh decode blocks to adopt
        rid_last = sorted(a._by_rid)[-1]
        slc2 = a.export_slice(rid_last)
        n = b.import_slice(slc2)
        assert n > 0
        b.check_invariants()
        # reverse direction: single-chip slice into the sharded pool
        rid_b = sorted(b._by_rid)[-1]
        back = b.export_slice(rid_b)
        # fresh sharded target with an empty index adopts everything
        # (a block's identity is its token ids: a pool that has served
        # the same prompts holds those blocks already, whatever its
        # weights, so this one has served nothing)
        c, _ = _run(_tsm(seed=1).shard(2), steps=0, prompts=(),
                    prefix_cache=True)
        m = c.import_slice(back)
        assert m == len(back["hashes"])
        c.check_invariants()
