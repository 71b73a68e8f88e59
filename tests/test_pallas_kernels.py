"""Pallas fused-kernel parity tests (interpret mode on CPU; the same
kernels compile via Mosaic on TPU).

Ref kernels being mirrored: fused_layernorm_residual_dropout_bias.h,
fused_adam_kernel.cu, cutlass moe_kernel.cu,
fused_multi_transformer_op.cu.h:835.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention, decode_attention_reference)
from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
from paddle_tpu.ops.pallas.fused_norm import (
    fused_layer_norm, fused_layer_norm_residual, fused_rms_norm,
    fused_rms_norm_residual)
from paddle_tpu.ops.pallas.grouped_gemm import (
    gmm, gmm_reference, make_group_metadata)
from paddle_tpu.ops.pallas.paged_attention import (
    gather_pages, paged_attention, paged_attention_multi,
    paged_attention_multi_reference, paged_attention_prefill,
    paged_attention_prefill_reference, paged_attention_ragged,
    paged_attention_ragged_reference, paged_attention_reference)

# the MODULE (the package re-exports a function named paged_attention
# over it): tests that shrink its VMEM budget or read launch_plan
pa_module = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")

# the kernel suite is selectable in CI like spec/faults/monitor:
#   pytest -m kernels
pytestmark = pytest.mark.kernels

rng = np.random.default_rng(0)


def _rand(*shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _rms_ref(z, w, eps=1e-6):
    return z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + eps) * w


def _ln_ref(z, w, b, eps=1e-5):
    mu = z.mean(-1, keepdims=True)
    xc = z - mu
    return xc * jax.lax.rsqrt((xc * xc).mean(-1, keepdims=True)
                              + eps) * w + b


class TestFusedNorm:
    def test_rms_forward(self):
        x, w = _rand(4, 8, 128), _rand(128)
        np.testing.assert_allclose(
            np.asarray(fused_rms_norm(x, w)),
            np.asarray(_rms_ref(x, w)), atol=1e-5, rtol=1e-5)

    def test_rms_residual_forward(self):
        x, r, w = _rand(4, 8, 128), _rand(4, 8, 128), _rand(128)
        y, z = fused_rms_norm_residual(x, r, w)
        np.testing.assert_allclose(np.asarray(z), np.asarray(x + r),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_rms_ref(x + r, w)),
                                   atol=1e-5, rtol=1e-5)

    def test_rms_grads(self):
        x, r, w = _rand(2, 4, 128), _rand(2, 4, 128), _rand(128)

        def f(x, r, w):
            y, z = fused_rms_norm_residual(x, r, w)
            return (y ** 2).sum() + (z ** 3).sum()

        def ref(x, r, w):
            z = x + r
            return (_rms_ref(z, w) ** 2).sum() + (z ** 3).sum()

        g1 = jax.grad(f, argnums=(0, 1, 2))(x, r, w)
        g2 = jax.grad(ref, argnums=(0, 1, 2))(x, r, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-4)

    def test_layernorm_forward_and_grads(self):
        x, r = _rand(2, 4, 128), _rand(2, 4, 128)
        w, b = _rand(128), _rand(128)
        y, z = fused_layer_norm_residual(x, r, w, b)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ln_ref(x + r, w, b)),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(fused_layer_norm(x, w, b)),
            np.asarray(_ln_ref(x, w, b)), atol=1e-5, rtol=1e-5)

        def f(x, r, w, b):
            y, _ = fused_layer_norm_residual(x, r, w, b)
            return (y ** 2).sum()

        def ref(x, r, w, b):
            return (_ln_ref(x + r, w, b) ** 2).sum()

        g1 = jax.grad(f, argnums=(0, 1, 2, 3))(x, r, w, b)
        g2 = jax.grad(ref, argnums=(0, 1, 2, 3))(x, r, w, b)
        for a, b2 in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       atol=2e-4, rtol=1e-4)

    def test_bf16_io(self):
        x = _rand(4, 4, 128).astype(jnp.bfloat16)
        w = _rand(128).astype(jnp.bfloat16)
        y = fused_rms_norm(x, w)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(y, np.float32),
            np.asarray(_rms_ref(x.astype(jnp.float32),
                                w.astype(jnp.float32))),
            atol=0.05, rtol=0.05)


class TestFusedDropout:
    def test_keep_fraction_and_determinism(self):
        from paddle_tpu.ops.pallas.fused_norm import _fused_dropout
        x = jnp.ones((128, 128), jnp.float32)
        y = _fused_dropout(x, 0.3, seed=7)
        kept = float((np.asarray(y) != 0).mean())
        assert abs(kept - 0.7) < 0.05
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(_fused_dropout(x, 0.3, seed=7)))
        assert not np.array_equal(
            np.asarray(y), np.asarray(_fused_dropout(x, 0.3, seed=8)))

    def test_norm_residual_dropout_grads(self):
        from paddle_tpu.ops.pallas.fused_norm import (
            fused_layer_norm_residual_dropout,
            fused_rms_norm_residual_dropout)
        x, r, w, b = (_rand(2, 8, 128), _rand(2, 8, 128), _rand(128),
                      _rand(128))

        def loss(x, r, w):
            y, z = fused_rms_norm_residual_dropout(
                x, r, w, dropout_rate=0.25, seed=3)
            return (y ** 2).sum()
        g = jax.grad(loss, argnums=(0, 1, 2))(x, r, w)
        assert all(np.isfinite(np.asarray(gi)).all() for gi in g)
        y, z = fused_layer_norm_residual_dropout(
            x, r, w, b, dropout_rate=0.25, seed=3)
        # z = dropout(x) + r: entries where dropout dropped equal r
        dropped = np.isclose(np.asarray(z), np.asarray(r))
        assert 0.1 < dropped.mean() < 0.4

    def test_rate_zero_is_identity(self):
        from paddle_tpu.ops.pallas.fused_norm import (
            fused_rms_norm_residual, fused_rms_norm_residual_dropout)
        x, r, w = _rand(2, 4, 128), _rand(2, 4, 128), _rand(128)
        y0, _ = fused_rms_norm_residual(x, r, w)
        y1, _ = fused_rms_norm_residual_dropout(x, r, w,
                                                dropout_rate=0.0)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1))


class TestFusedAdamW:
    def test_matches_reference_update(self):
        shape = (33, 77)  # ragged: exercises lane padding
        p = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        master = p.astype(jnp.float32)
        g = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        m = _rand(*shape) * 0.1
        v = jnp.abs(_rand(*shape)) * 0.01
        lr, b1, b2, eps, wd, step = 1e-3, 0.9, 0.95, 1e-8, 0.1, 7.0
        np_, nm, nv, nmaster = fused_adamw_update(
            p, g, m, v, master, lr, b1, b2, eps, wd, step)
        g32 = np.asarray(g, np.float32)
        m_r = b1 * np.asarray(m) + (1 - b1) * g32
        v_r = b2 * np.asarray(v) + (1 - b2) * g32 * g32
        upd = (m_r / (1 - b1 ** step)
               / (np.sqrt(v_r / (1 - b2 ** step)) + eps)
               + wd * np.asarray(master))
        master_r = np.asarray(master) - lr * upd
        np.testing.assert_allclose(np.asarray(nm), m_r, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(nv), v_r, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(nmaster), master_r,
                                   rtol=1e-5, atol=1e-6)
        assert np_.dtype == jnp.bfloat16

    def test_traced_scalars_under_jit(self):
        p = _rand(16, 128)
        st = dict(m=jnp.zeros_like(p), v=jnp.zeros_like(p), master=p)

        @jax.jit
        def step(p, g, st, lr, n):
            return fused_adamw_update(p, g, st["m"], st["v"],
                                      st["master"], lr, 0.9, 0.95, 1e-8,
                                      0.0, n)
        out = step(p, _rand(16, 128), st, jnp.float32(1e-3),
                   jnp.float32(1.0))
        assert out[0].shape == p.shape


class TestGroupedGemm:
    def test_matches_per_expert_matmul(self):
        E, K, N, bm = 4, 64, 96, 8
        sizes = [13, 0, 21, 6]
        offsets, block_expert, M = make_group_metadata(sizes, block_m=bm)
        lhs = _rand(M, K)
        rhs = _rand(E, K, N)
        out = gmm(lhs, rhs, block_expert, block_m=bm, block_n=32,
                  block_k=16)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(gmm_reference(lhs, rhs, block_expert, block_m=bm)),
            atol=1e-4, rtol=1e-4)
        for e in range(E):
            lo, hi = offsets[e], offsets[e] + sizes[e]
            if sizes[e]:
                np.testing.assert_allclose(
                    np.asarray(out[lo:hi]), np.asarray(lhs[lo:hi] @ rhs[e]),
                    rtol=1e-4, atol=1e-4)

    def test_metadata(self):
        offsets, be, total = make_group_metadata([5, 8, 0, 1], block_m=8)
        assert total == 24 and list(offsets) == [0, 8, 16, 16, 24]
        assert list(be) == [0, 1, 3]


class TestGroupedGemmExactParity:
    """BIT-EXACT gmm parity — the MoE serving contract
    (inference/moe_serving.py): the grouped-GEMM dispatch path and the
    per-expert reference fold must produce byte-equal streams, which
    holds only if gmm itself is bit-equal to a plain per-expert matmul
    at serving dims. Interpret mode runs the same one-m-block row
    tiling as Mosaic; XLA CPU's row-count-invariant GEMM makes each
    block's dot bitwise equal to the corresponding rows of the full
    matmul — so these asserts are exact, not allclose."""

    def _parity(self, sizes, K=32, N=48, bm=8, seed=0):
        r = np.random.default_rng(seed)
        E = len(sizes)
        offsets, block_expert, M = make_group_metadata(sizes, block_m=bm)
        lhs = jnp.asarray(r.standard_normal((M, K)), jnp.float32)
        rhs = jnp.asarray(r.standard_normal((E, K, N)), jnp.float32)
        out = gmm(lhs, rhs, block_expert, block_m=bm)
        ref = gmm_reference(lhs, rhs, block_expert, block_m=bm)
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        for e in range(E):
            lo, hi = offsets[e], offsets[e] + sizes[e]
            if sizes[e]:
                assert np.array_equal(np.asarray(out[lo:hi]),
                                      np.asarray(lhs[lo:hi] @ rhs[e])), e

    def test_empty_experts(self):
        self._parity([5, 0, 1, 10])
        self._parity([0, 0, 0, 3])

    def test_single_token_groups(self):
        # one row per expert: every m-block is rows [token, padding]
        self._parity([1, 1, 1, 1])

    def test_uniform_full_blocks(self):
        self._parity([8, 8, 8, 8])

    @pytest.mark.parametrize("seed", range(5))
    def test_randomized_group_sizes(self, seed):
        r = np.random.default_rng(100 + seed)
        E = int(r.integers(2, 6))
        sizes = [int(r.integers(0, 17)) for _ in range(E)]
        if not any(sizes):
            sizes[0] = 1
        self._parity(sizes, K=int(r.integers(8, 48)),
                     N=int(r.integers(8, 64)), seed=seed)


class TestPagedAttention:
    """Ragged paged-attention decode: KV pages gathered through a block
    table (PAPERS.md arxiv 2604.15464). Same online softmax as
    decode_attention; the cache axis is indirected through the table."""

    def _pool(self, NB, nkv, bs, hd):
        return _rand(NB, 2, nkv, bs, hd)

    @pytest.mark.parametrize("nh,nkv", [(8, 4), (4, 4)])
    def test_matches_reference(self, nh, nkv):
        B, hd, bs, MB, NB = 3, 32, 16, 4, 12
        q = _rand(B, nh, hd)
        pool = self._pool(NB, nkv, bs, hd)
        bt = jnp.asarray(rng.integers(0, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([5, 64, 17], jnp.int32)  # partial/full/mid
        out = paged_attention(q, pool, bt, lens)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(paged_attention_reference(q, pool, bt, lens)),
            atol=1e-5, rtol=1e-5)

    def test_matches_dense_decode_on_gathered_pages(self):
        """Paged over a table == dense decode over the gathered cache:
        the block indirection must be a pure layout change."""
        B, nh, hd, bs, MB, NB = 2, 4, 16, 8, 4, 9
        q = _rand(B, nh, hd)
        pool = self._pool(NB, nh, bs, hd)
        bt = jnp.asarray(rng.integers(0, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([9, 32], jnp.int32)
        k, v = gather_pages(pool, bt)
        np.testing.assert_allclose(
            np.asarray(paged_attention(q, pool, bt, lens)),
            np.asarray(decode_attention(q, k, v, lens, block_s=bs)),
            atol=1e-5, rtol=1e-5)

    def test_trash_block_rows_masked(self):
        """Table entries past a row's length point at block 0 (the
        reserved trash block); its garbage must not leak into the
        output, and block-boundary lengths must be exact."""
        B, nh, hd, bs, MB, NB = 2, 4, 16, 8, 3, 6
        q = _rand(B, nh, hd)
        pool = self._pool(NB, nh, bs, hd)
        # row 0: one real block then trash; row 1: exactly two blocks
        bt = jnp.asarray([[3, 0, 0], [4, 5, 0]], jnp.int32)
        lens = jnp.asarray([8, 16], jnp.int32)
        out = paged_attention(q, pool, bt, lens)
        k, v = gather_pages(pool, bt)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(decode_attention_reference(q, k, v, lens)),
            atol=1e-5, rtol=1e-5)
        assert np.all(np.isfinite(np.asarray(out)))


class TestPagedAttentionMulti:
    """Multi-query paged decode (speculative-decode verification):
    n_q query tokens per sequence score all their positions in one
    sweep over the pages, each masked causally to its own position."""

    @pytest.mark.parametrize("nh,nkv", [(8, 4), (4, 4)])
    def test_matches_reference(self, nh, nkv):
        B, n_q, hd, bs, MB, NB = 3, 4, 32, 16, 4, 12
        q = _rand(B, n_q, nh, hd)
        pool = _rand(NB, 2, nkv, bs, hd)
        bt = jnp.asarray(rng.integers(0, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([5, 64, 17], jnp.int32)  # incl. the n_q new
        out = paged_attention_multi(q, pool, bt, lens)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(paged_attention_multi_reference(q, pool, bt,
                                                       lens)),
            atol=1e-5, rtol=1e-5)

    def test_nq1_equals_single_query_kernel(self):
        """n_q == 1 must be exactly the plain paged decode."""
        B, nh, hd, bs, MB, NB = 2, 4, 16, 8, 4, 9
        q = _rand(B, 1, nh, hd)
        pool = _rand(NB, 2, nh, bs, hd)
        bt = jnp.asarray(rng.integers(0, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([9, 32], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(paged_attention_multi(q, pool, bt, lens))[:, 0],
            np.asarray(paged_attention(q[:, 0], pool, bt, lens)))

    def test_last_row_equals_single_at_same_length(self):
        """The final query of an n_q sweep sees exactly the window a
        single-query call at the same length sees (to float tolerance:
        the folded [n_q*g, bs] dots group differently than [g, bs] —
        bit-identity is the CPU fallback's contract, not the
        kernel's)."""
        B, n_q, nh, hd, bs, MB, NB = 2, 3, 4, 16, 8, 4, 9
        q = _rand(B, n_q, nh, hd)
        pool = _rand(NB, 2, nh, bs, hd)
        bt = jnp.asarray(rng.integers(0, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([9, 30], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(paged_attention_multi(q, pool, bt, lens))[:, -1],
            np.asarray(paged_attention(q[:, -1], pool, bt, lens)),
            atol=2e-6, rtol=2e-6)

    def test_causal_within_window_and_trash_masked(self):
        """Query i must not see positions past lens-n_q+i (the yet-
        unaccepted speculative tail), and table entries past the
        allocation (trash block 0) must not leak."""
        B, n_q, nh, hd, bs, MB, NB = 1, 3, 4, 16, 8, 3, 6
        q = _rand(B, n_q, nh, hd)
        pool = _rand(NB, 2, nh, bs, hd)
        bt = jnp.asarray([[3, 0, 0]], jnp.int32)   # 1 real page + trash
        lens = jnp.asarray([7], jnp.int32)         # 4 old + 3 new
        out = np.asarray(paged_attention_multi(q, pool, bt, lens))
        # row 0 (position 4): perturbing positions 5.. must not move it
        pool2 = pool.at[3, :, :, 5:8, :].set(123.0)
        out2 = np.asarray(paged_attention_multi(q, pool2, bt, lens))
        np.testing.assert_array_equal(out[:, 0], out2[:, 0])
        # trash-block garbage must not move anything
        pool3 = pool.at[0].set(1e6)
        out3 = np.asarray(paged_attention_multi(q, pool3, bt, lens))
        np.testing.assert_array_equal(out, out3)
        assert np.isfinite(out).all()


class TestPagedAttentionPrefill:
    """Chunked paged prefill: a prompt chunk's queries (positions
    start+i) attend causally over already-written pages through the
    block table, tiled over a query-tile grid axis with pages past a
    tile's causal frontier skipped."""

    @pytest.mark.parametrize("nh,nkv", [(8, 4), (4, 4)])
    def test_matches_reference(self, nh, nkv):
        B, C, hd, bs, MB, NB = 3, 12, 32, 16, 5, 12
        q = _rand(B, C, nh, hd)
        pool = _rand(NB, 2, nkv, bs, hd)
        bt = jnp.asarray(rng.integers(1, NB, (B, MB)), jnp.int32)
        start = jnp.asarray([0, 23, 60], jnp.int32)  # aligned/mid/deep
        out = paged_attention_prefill(q, pool, bt, start)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(paged_attention_prefill_reference(q, pool, bt,
                                                         start)),
            atol=1e-5, rtol=1e-5)

    def test_query_tiling_matches_untiled(self):
        """tile_q smaller than (and not dividing) the chunk must give
        the same result as one tile — padding rows and per-tile page
        skipping are pure work-scheduling."""
        B, C, nh, hd, bs, MB, NB = 2, 13, 4, 16, 8, 6, 10
        q = _rand(B, C, nh, hd)
        pool = _rand(NB, 2, nh, bs, hd)
        bt = jnp.asarray(rng.integers(1, NB, (B, MB)), jnp.int32)
        start = jnp.asarray([4, 19], jnp.int32)
        ref = paged_attention_prefill_reference(q, pool, bt, start)
        for tq in (1, 4, 5, 13):
            np.testing.assert_allclose(
                np.asarray(paged_attention_prefill(q, pool, bt, start,
                                                   tile_q=tq)),
                np.asarray(ref), atol=1e-5, rtol=1e-5)

    def test_equals_multi_kernel_at_same_positions(self):
        """A prefill chunk at start S IS a multi-query sweep with
        seq_lens = S + C — the two kernels must agree (same folded-row
        math, different grids)."""
        B, C, nh, hd, bs, MB, NB = 2, 6, 4, 16, 8, 4, 9
        q = _rand(B, C, nh, hd)
        pool = _rand(NB, 2, nh, bs, hd)
        bt = jnp.asarray(rng.integers(1, NB, (B, MB)), jnp.int32)
        start = jnp.asarray([3, 10], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(paged_attention_prefill(q, pool, bt, start,
                                               tile_q=3)),
            np.asarray(paged_attention_multi(q, pool, bt, start + C)),
            atol=2e-6, rtol=2e-6)

    def test_causal_within_chunk_and_trash_masked(self):
        """Query i must not see positions past start+i (later chunk
        rows), and trash-block entries past the allocation must not
        leak."""
        B, C, nh, hd, bs, MB, NB = 1, 4, 4, 16, 8, 3, 6
        q = _rand(B, C, nh, hd)
        pool = _rand(NB, 2, nh, bs, hd)
        bt = jnp.asarray([[3, 4, 0]], jnp.int32)
        start = jnp.asarray([6], jnp.int32)     # chunk covers 6..9
        out = np.asarray(paged_attention_prefill(q, pool, bt, start))
        # row 0 (position 6): perturbing positions 7.. must not move it
        pool2 = pool.at[3, :, :, 7:, :].set(123.0)
        pool2 = pool2.at[4].set(123.0)
        out2 = np.asarray(paged_attention_prefill(q, pool2, bt, start))
        np.testing.assert_array_equal(out[:, 0], out2[:, 0])
        # trash-block garbage must not move anything (positions <= 9
        # all live in pages 0-1 of the table)
        pool3 = pool.at[0].set(1e6)
        out3 = np.asarray(paged_attention_prefill(q, pool3, bt, start))
        np.testing.assert_array_equal(out, out3)
        assert np.isfinite(out).all()


class TestPagedAttentionRagged:
    """ONE ragged kernel subsumes all three phases: a packed mixed
    batch — decode rows, speculative-verify blocks and prefill chunks
    over the shared block table — in a single launch, with per-phase
    wrappers as thin delegations. Parity contracts:

      * segment independence (element-exact): each sequence's slice of
        a mixed launch equals the same sequence launched alone at the
        same tile_q — the property that makes packing a pure
        dispatch-count optimization;
      * reference parity (float tolerance): mixed launches match the
        shared jnp reference, and each phase's rows match that phase's
        reference kernel;
      * degenerate batches: all-one-phase mixed launches are exactly
        the per-phase wrappers; empty segments and empty batches are
        legal no-ops.
    """

    def _mixed(self, seed=0, nh=4, nkv=4, hd=16, bs=8, MB=5, NB=14):
        r = np.random.default_rng(seed)
        pool = jnp.asarray(r.standard_normal((NB, 2, nkv, bs, hd)),
                           jnp.float32)
        # decode, verify (K+1=3), prefill chunk at a non-block-aligned
        # start, another decode, block-aligned prefill, EMPTY segment
        q_lens = (1, 3, 7, 1, 10, 0)
        kv_lens = jnp.asarray([17, 9, 5 + 7, 33, 10, 0], jnp.int32)
        bt = jnp.asarray(r.integers(1, NB, (len(q_lens), MB)),
                         jnp.int32)
        q = jnp.asarray(r.standard_normal((sum(q_lens), nh, hd)),
                        jnp.float32)
        return q, pool, bt, q_lens, kv_lens

    def test_scalar_prefetch_kernels_interpreted(self):
        """The kernel the CHIP runs — ``_kernel_ragged_prefetch``, over
        bf16/float pages and over int8 pages with their scales, block
        table and tile maps riding as scalar prefetch — interpreted on
        CPU."""
        from paddle_tpu.inference.paged_cache import _quant_rows
        pa = pa_module
        q, pool, bt, q_lens, kv_lens = self._mixed()
        out = pa.paged_attention_ragged(q, pool, bt, q_lens, kv_lens)
        ref = paged_attention_ragged_reference(q, pool, bt, q_lens,
                                               kv_lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        pool_q, scales = _quant_rows(pool)
        out = pa.paged_attention_ragged(q, pool_q, bt, q_lens, kv_lens,
                                        kv_scales=scales)
        ref = paged_attention_ragged_reference(
            q, pool_q, bt, q_lens, kv_lens, kv_scales=scales)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_mixed_matches_shared_reference(self):
        q, pool, bt, q_lens, kv_lens = self._mixed()
        for tq in (None, 4):
            np.testing.assert_allclose(
                np.asarray(paged_attention_ragged(
                    q, pool, bt, q_lens, kv_lens, tile_q=tq)),
                np.asarray(paged_attention_ragged_reference(
                    q, pool, bt, q_lens, kv_lens)),
                atol=1e-5, rtol=1e-5)

    def test_mixed_rows_match_per_phase_references(self):
        """Each phase's rows of one mixed launch agree with that
        phase's reference kernel — the three delegating references
        cannot drift from what the mixed launch computes."""
        q, pool, bt, q_lens, kv_lens = self._mixed()
        out = np.asarray(paged_attention_ragged(q, pool, bt, q_lens,
                                                kv_lens))
        r0 = 0
        for s, ql in enumerate(q_lens):
            if ql == 0:
                continue
            rows = out[r0:r0 + ql]
            if ql == 1:
                ref = paged_attention_reference(
                    q[r0:r0 + 1], pool, bt[s:s + 1], kv_lens[s:s + 1])
            else:
                ref = paged_attention_multi_reference(
                    q[r0:r0 + ql][None], pool, bt[s:s + 1],
                    kv_lens[s:s + 1])[0]
            np.testing.assert_allclose(rows, np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)
            r0 += ql

    @pytest.mark.parametrize("seed", [1, 2])
    def test_randomized_mixed_property(self, seed):
        """Property sweep: random mixed compositions (random segment
        counts/lengths/starts, non-aligned everywhere) hold both
        contracts — reference parity, and SEGMENT INDEPENDENCE
        (element-exact: packing sequences into one launch must not
        move any sequence's output by a single bit vs launching it
        alone at the same tile_q — what makes packing a pure
        dispatch-count optimization)."""
        r = np.random.default_rng(100 + seed)
        nh, hd, bs, MB, NB = 4, 16, 8, 6, 16
        pool = jnp.asarray(r.standard_normal((NB, 2, nh, bs, hd)),
                           jnp.float32)
        n_seq = int(r.integers(2, 6))
        q_lens, kv_lens = [], []
        for _ in range(n_seq):
            kind = r.integers(0, 3)
            if kind == 0:          # decode
                ql = 1
                kv = int(r.integers(1, MB * bs))
            elif kind == 1:        # verify
                ql = int(r.integers(2, 5))
                kv = int(r.integers(ql, MB * bs))
            else:                  # prefill chunk
                ql = int(r.integers(2, 14))
                kv = int(r.integers(ql, MB * bs))
            q_lens.append(ql)
            kv_lens.append(kv)
        q_lens = tuple(q_lens)
        kv_arr = jnp.asarray(kv_lens, jnp.int32)
        bt = jnp.asarray(r.integers(1, NB, (n_seq, MB)), jnp.int32)
        q = jnp.asarray(r.standard_normal((sum(q_lens), nh, hd)),
                        jnp.float32)
        out = np.asarray(paged_attention_ragged(q, pool, bt, q_lens,
                                                kv_arr, tile_q=4))
        np.testing.assert_allclose(
            out,
            np.asarray(paged_attention_ragged_reference(
                q, pool, bt, q_lens, kv_arr)),
            atol=1e-5, rtol=1e-5)
        r0 = 0
        for s, ql in enumerate(q_lens):
            solo = np.asarray(paged_attention_ragged(
                q[r0:r0 + ql], pool, bt[s:s + 1], (ql,),
                kv_arr[s:s + 1], tile_q=4))
            np.testing.assert_array_equal(out[r0:r0 + ql], solo)
            r0 += ql

    def test_all_one_phase_degenerate_batches(self):
        """All-decode == the decode wrapper, all-verify == the multi
        wrapper, all-prefill == the prefill wrapper — element-exact
        (the wrappers ARE ragged launches at those tilings)."""
        r = np.random.default_rng(7)
        nh, hd, bs, MB, NB = 4, 16, 8, 4, 10
        pool = jnp.asarray(r.standard_normal((NB, 2, nh, bs, hd)),
                           jnp.float32)
        bt = jnp.asarray(r.integers(1, NB, (3, MB)), jnp.int32)
        lens = jnp.asarray([5, 17, 32], jnp.int32)
        qd = jnp.asarray(r.standard_normal((3, nh, hd)), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(paged_attention_ragged(qd, pool, bt, (1, 1, 1),
                                              lens, tile_q=1)),
            np.asarray(paged_attention(qd, pool, bt, lens)))
        qm = jnp.asarray(r.standard_normal((3, 4, nh, hd)), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(paged_attention_ragged(
                qm.reshape(12, nh, hd), pool, bt, (4, 4, 4), lens,
                tile_q=4)).reshape(3, 4, nh, hd),
            np.asarray(paged_attention_multi(qm, pool, bt, lens)))
        qp = jnp.asarray(r.standard_normal((3, 6, nh, hd)), jnp.float32)
        start = jnp.asarray([0, 9, 20], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(paged_attention_ragged(
                qp.reshape(18, nh, hd), pool, bt, (6, 6, 6), start + 6,
                tile_q=6)).reshape(3, 6, nh, hd),
            np.asarray(paged_attention_prefill(qp, pool, bt, start)))

    def test_empty_segments_and_empty_batch(self):
        q, pool, bt, q_lens, kv_lens = self._mixed()
        # all-empty batch: legal no-op, shape-preserving
        out = paged_attention_ragged(q[:0], pool, bt[:2], (0, 0),
                                     kv_lens[:2])
        assert out.shape == (0,) + q.shape[1:]
        # a zero-length segment in the middle changes nothing
        ref = np.asarray(paged_attention_ragged(
            q, pool, bt, q_lens, kv_lens, tile_q=2))
        keep = [s for s, ql in enumerate(q_lens) if ql > 0]
        out2 = np.asarray(paged_attention_ragged(
            q, pool, bt[jnp.asarray(keep)],
            tuple(q_lens[s] for s in keep),
            kv_lens[jnp.asarray(keep)], tile_q=2))
        np.testing.assert_array_equal(ref, out2)

    def test_tile_kv_is_pure_scheduling(self):
        """tile_kv groups pages per kv grid step; any grouping
        (dividing MB or not) gives the same
        attention to float tolerance (the online-softmax update order
        changes, values do not)."""
        q, pool, bt, q_lens, kv_lens = self._mixed()
        ref = np.asarray(paged_attention_ragged(
            q, pool, bt, q_lens, kv_lens, tile_q=4, tile_kv=1))
        for tkv in (2,):      # non-dividing: pads MB 5 -> 6 with trash
            np.testing.assert_allclose(
                np.asarray(paged_attention_ragged(
                    q, pool, bt, q_lens, kv_lens, tile_q=4,
                    tile_kv=tkv)),
                ref, atol=1e-5, rtol=1e-5)

    def test_zero_length_sequence_rows_are_zero(self):
        """kv_len 0 with a live query row (an inactive slot's masked
        decode row): zeros out, never NaN."""
        r = np.random.default_rng(9)
        nh, hd, bs, MB, NB = 4, 16, 8, 3, 6
        pool = jnp.asarray(r.standard_normal((NB, 2, nh, bs, hd)),
                           jnp.float32)
        bt = jnp.asarray([[0, 0, 0], [3, 0, 0]], jnp.int32)
        q = jnp.asarray(r.standard_normal((2, nh, hd)), jnp.float32)
        out = np.asarray(paged_attention_ragged(
            q, pool, bt, (1, 1), jnp.asarray([0, 7], jnp.int32)))
        assert np.all(out[0] == 0.0) and np.isfinite(out).all()


def _budget_for_heads(pa, monkeypatch, heads, *shape, **kw):
    """Shrink the module's VMEM budget until the plan for ``shape``
    carries ``heads`` kv heads a grid step."""
    for kib in range(4096, 0, -8):
        monkeypatch.setattr(pa, "VMEM_BUDGET_BYTES", kib * 1024)
        if pa.launch_plan(*shape, **kw).heads == heads:
            return
    raise AssertionError(f"no budget gives {heads} heads a step")


class TestChipKernelInterpreted:
    """The chip's launch at its new grid — a step carries ``Hb`` kv
    heads of ``P`` pages (``launch_plan``), each page operand held at
    its last real page past the tile's frontier — against the shared
    jnp reference, interpreted on the CPU."""

    # name: (q_lens, kv_lens, tile_q, tile_kv, nkv, g, MB)
    CASES = {
        # rows == 1: one M = 1 product a head; P = MB, one kv step
        "decode": ((1,) * 5, (17, 1, 40, 33, 8), 1, None, 4, 1, 5),
        # derived P = 16 of MB = 20 (``_derived_16_pages``): two kv
        # steps, the last one short
        "decode_pages_not_dividing": ((1,) * 3, (150, 129, 5), 1, None,
                                      4, 1, 20),
        "verify": ((3,) * 4, (9, 3, 24, 40), 3, 2, 4, 1, 5),
        "prefill": ((11,), (27,), 4, 2, 4, 1, 5),
        "mixed": ((1, 3, 7, 1, 10, 0), (17, 9, 12, 33, 10, 0), None, 2,
                  4, 1, 5),
        "mixed_one_step": ((1, 3, 7, 1, 10), (17, 9, 12, 33, 10), None,
                           None, 4, 1, 5),
        "tile_kv_not_dividing": ((1, 6, 1), (40, 30, 8), 4, 3, 4, 1, 5),
        # lengths exactly on a page boundary (8, 16, 40 at 8 a page)
        "page_boundary": ((1, 4, 1, 8), (8, 16, 40, 8), 4, 2, 4, 1, 5),
        # kv_len 0 under a live query row: zeros, never NaN
        "length_zero_rows": ((1, 1, 2), (0, 7, 0), None, 2, 4, 1, 5),
        "gqa_decode": ((1,) * 3, (17, 40, 8), 1, 2, 2, 2, 5),
        "gqa_mixed": ((1, 5, 1), (23, 13, 40), 4, 2, 2, 2, 5),
    }

    def _inputs(self, q_lens, kv_lens, nkv, g, MB, hd=16, bs=8,
                seed=0, dtype=jnp.float32):
        r = np.random.default_rng(seed)
        NB = 2 * MB + 4
        pool = jnp.asarray(r.standard_normal((NB, 2, nkv, bs, hd)), dtype)
        bt = jnp.asarray(r.integers(1, NB, (len(q_lens), MB)),
                         jnp.int32)
        q = jnp.asarray(r.standard_normal((sum(q_lens), nkv * g, hd)),
                        dtype)
        return q, pool, bt, jnp.asarray(kv_lens, jnp.int32)

    def _check(self, pa, q, pool, bt, q_lens, kv_lens, kv_scales=None,
               window=None, tol=2e-5, **tiles):
        out = np.asarray(pa.paged_attention_ragged(
            q, pool, bt, q_lens, kv_lens, kv_scales=kv_scales,
            window=window, **tiles).astype(jnp.float32))
        ref = np.asarray(paged_attention_ragged_reference(
            q.astype(jnp.float32), pool, bt, q_lens, kv_lens,
            kv_scales=kv_scales, window=window))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)
        return out

    def _derived_16_pages(self, monkeypatch, case, itemsize):
        """``decode_pages_not_dividing`` runs the DERIVED plan: a step
        is given the bytes of 128 positions (4 heads of 16, two planes),
        so ``launch_plan`` hands it 16 pages of the table's 20."""
        if case != "decode_pages_not_dividing":
            return
        monkeypatch.setattr(pa_module, "STEP_PAGE_BYTES",
                            128 * 2 * 4 * 16 * itemsize)
        plan = pa_module.launch_plan(3, 4, 1, 20, 8, 16, itemsize,
                                     q_itemsize=itemsize)
        assert (plan.heads, plan.pages, plan.grid) == (4, 16, (3, 2))

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_launch_matches_reference(self, monkeypatch, case):
        q_lens, kv_lens, tq, tkv, nkv, g, MB = self.CASES[case]
        q, pool, bt, kvl = self._inputs(q_lens, kv_lens, nkv, g, MB)
        self._derived_16_pages(monkeypatch, case, 4)
        out = self._check(pa_module, q, pool, bt, q_lens, kvl, tile_q=tq,
                          tile_kv=tkv)
        if case == "length_zero_rows":
            assert np.all(out[0] == 0.0) and np.all(out[2:] == 0.0)

    # bf16 q over a bf16 pool, as long-decode and mixed-queue hand the
    # launch: the body copies both to float32 (exactly), so against the
    # reference on the SAME bf16 inputs in float32 what is left off the
    # chip is the output's own rounding to bf16 (2^-9 of values up to
    # 2.6: 5e-3). 1e-2 holds it, and a wrong page, mask or column range
    # is O(1).
    BF16_TOL = 1e-2

    @pytest.mark.parametrize("case,g,window", [
        ("decode", 1, None), ("decode_pages_not_dividing", 1, None),
        ("mixed", 1, None), ("tile_kv_not_dividing", 1, None),
        ("length_zero_rows", 1, None), ("page_boundary", 1, None),
        ("gqa_mixed", 6, 9), ("gqa_decode", 6, 9), ("gqa_mixed", 6, None)])
    def test_launch_over_bf16_matches_reference(self, monkeypatch, case, g,
                                                window):
        """Mixed batches with partial tail tiles, ``MB`` no multiple of
        ``P``, length-0 rows, and the K/V form at six query heads a kv
        head under a window, bf16 q over bf16 pages."""
        q_lens, kv_lens, tq, tkv, nkv, _, MB = self.CASES[case]
        q, pool, bt, kvl = self._inputs(q_lens, kv_lens, nkv, g, MB,
                                        dtype=jnp.bfloat16)
        self._derived_16_pages(monkeypatch, case, 2)
        out = self._check(pa_module, q, pool, bt, q_lens, kvl, tile_q=tq,
                          tile_kv=tkv, window=window, tol=self.BF16_TOL)
        if case == "length_zero_rows":
            assert np.all(out[0] == 0.0) and np.all(out[2:] == 0.0)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, BF16_TOL)])
    @pytest.mark.parametrize("case", ["decode", "mixed", "page_boundary",
                                      "length_zero_rows"])
    def test_two_heads_of_64_a_pool_row(self, case, dtype, tol):
        """Four query heads a KV head at ``hd`` 64, as ``lfm2_moe`` hands
        the launch: the pool keeps TWO heads side by side in a 128-lane
        row (half the heads, twice the width), a query head's operand is
        zero in the other head's half and it takes its own half of the
        output, at the head's scale. Against the reference over the same
        K/V kept one head of 64 each."""
        q_lens, kv_lens, tq, tkv, _, _, MB = self.CASES[case]
        nkv, g, hd, bs = 4, 4, 64, 8
        q, pool, bt, kvl = self._inputs(q_lens, kv_lens, nkv, g, MB, hd=hd,
                                        dtype=dtype)
        NB = pool.shape[0]
        rows = jnp.transpose(pool.reshape(NB, 2, nkv // 2, 2, bs, hd),
                             (0, 1, 2, 4, 3, 5)).reshape(
            NB, 2, nkv // 2, bs, 2 * hd)
        lane = jax.nn.one_hot((jnp.arange(nkv * g) // g) % 2, 2,
                              dtype=jnp.float32)
        wide = (q[:, :, None, :] * lane[None, :, :, None]).astype(
            dtype).reshape(-1, nkv * g, 2 * hd)
        out = pa_module.paged_attention_ragged(
            wide, rows, bt, q_lens, kvl, sm_scale=hd ** -0.5, tile_q=tq,
            tile_kv=tkv)
        assert out.shape == (sum(q_lens), nkv * g, 2 * hd)
        got = np.asarray(jnp.einsum(
            "rhnd,hn->rhd",
            out.astype(jnp.float32).reshape(-1, nkv * g, 2, hd), lane))
        ref = np.asarray(paged_attention_ragged_reference(
            q.astype(jnp.float32), pool, bt, q_lens, kvl))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, atol=tol, rtol=tol)

    def test_plan_at_two_heads_of_64_a_row(self):
        """busy-chat's launches: 32 query heads over 8 KV heads of 64,
        stored as 4 rows of 128: eight query rows a stored row; a step is
        given 2 MiB of pages, 1 024 positions of 2 048 B (mixed: what
        fits VMEM)."""
        assert pa_module.resolve_tile_q((1,) * 192, None, 8) == 1
        decode = pa_module.launch_plan(192, 4, 8, 304, 16, 128, 2,
                                       q_itemsize=2)
        assert (decode.heads, decode.pages, decode.grid) \
            == (4, 64, (192, 5))
        assert decode.bytes_per_step == 64 * 16 * 2 * 4 * 128 * 2 == 2 ** 21
        tile_q = pa_module.resolve_tile_q((1024,) + (1,) * 192, None, 8)
        mixed = pa_module.launch_plan(
            -(-1024 // tile_q) + 192, 4, tile_q * 8, 304, 16, 128, 2,
            q_itemsize=2)
        assert mixed.heads == 4 and 128 <= mixed.pages * 16 <= 1024

    @pytest.mark.parametrize("window", [None, 9])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_live_steps_is_the_work_lists_count(self, case, window):
        """``live_steps`` (host numpy, the ``paged_attn`` gauge's series)
        and ``_work_list`` (jnp, the launch's grid size) count with the
        one ``_live_range``: equal at one page a step and at the derived
        plan, and never over the plan's bound."""
        q_lens, kv_lens, _, _, nkv, g, MB = self.CASES[case]
        tq = pa_module.resolve_tile_q(q_lens, g=g)
        seq, off, n, _, _ = pa_module._tile_layout(q_lens, tq)
        pos0 = (np.asarray(kv_lens) - np.asarray(q_lens))[seq] + off
        for tile_kv in (1, None):
            plan = pa_module.launch_plan(len(seq), nkv, tq * g, MB, 8, 16,
                                         4, tile_kv=tile_kv)
            count = pa_module._work_list(
                jnp.asarray(pos0, jnp.int32),
                jnp.asarray(pos0 + n - 1, jnp.int32), jnp.asarray(seq),
                nkv // plan.heads, plan.grid[1], plan.pages, 8, window)[0]
            live = pa_module.live_steps(plan, q_lens, kv_lens, 8, g=g,
                                        window=window)
            assert len(seq) <= live == int(count) <= plan.grid_steps

    @pytest.mark.parametrize("case", ["decode", "mixed", "verify"])
    def test_head_groups_under_a_small_budget(self, monkeypatch, case):
        """A budget that holds two of the four kv heads: the grid's
        first axis walks (tile, head group) and the blocks carry
        ``Hb < nkv`` heads."""
        q_lens, kv_lens, tq, tkv, nkv, g, MB = self.CASES[case]
        q, pool, bt, kvl = self._inputs(q_lens, kv_lens, nkv, g, MB)
        rows = (tq or max(q_lens)) * g
        _budget_for_heads(pa_module, monkeypatch, 2, 1, nkv, rows, MB, 8,
                          16, 4)
        self._check(pa_module, q, pool, bt, q_lens, kvl, tile_q=tq,
                    tile_kv=tkv)

    @pytest.mark.parametrize("case,heads", [("mixed", None),
                                            ("decode", None),
                                            ("page_boundary", None),
                                            ("mixed", 8)])
    def test_int8_pages(self, monkeypatch, case, heads):
        """int8 pages: each scale page rides its page's lookup as a
        (1, 2, Hb, block_s) block; with 16 kv heads and a small budget
        a step carries 8 of them (the scale block's sublane tile)."""
        from paddle_tpu.inference.paged_cache import _quant_rows
        q_lens, kv_lens, tq, tkv, nkv, g, MB = self.CASES[case]
        if heads is not None:
            nkv = 16
        q, pool, bt, kvl = self._inputs(q_lens, kv_lens, nkv, g, MB)
        if heads is not None:
            rows = (tq or max(q_lens)) * g
            _budget_for_heads(pa_module, monkeypatch, heads, 1, nkv, rows,
                              MB, 8, 16, 1, quantized=True)
        pool_q, scales = _quant_rows(pool)
        self._check(pa_module, q, pool_q, bt, q_lens, kvl,
                    kv_scales=scales, tile_q=tq, tile_kv=tkv)

    @pytest.mark.parametrize("tile_q", [1, 4])
    def test_segment_independence(self, tile_q):
        """Element-exact: a sequence's rows of a packed launch equal
        the same sequence launched alone at the same tile_q."""
        q_lens, kv_lens = (1, 3, 7, 1, 10), (17, 9, 12, 33, 10)
        q, pool, bt, kvl = self._inputs(q_lens, kv_lens, 4, 1, 5)
        out = np.asarray(pa_module.paged_attention_ragged(
            q, pool, bt, q_lens, kvl, tile_q=tile_q))
        r0 = 0
        for s, ql in enumerate(q_lens):
            solo = np.asarray(pa_module.paged_attention_ragged(
                q[r0:r0 + ql], pool, bt[s:s + 1], (ql,), kvl[s:s + 1],
                tile_q=tile_q))
            np.testing.assert_array_equal(out[r0:r0 + ql], solo)
            r0 += ql

    def test_launch_plan_at_the_cells_shapes(self):
        """The serving cells' launches (32 kv heads of 128, bf16 pages
        of 16, 128 table entries): every head of a page in one grid
        step, so at most 4 096 steps of at least 256 KB where the
        one-head one-page grid had 131 072 of 8 KB."""
        plan = pa_module.launch_plan(32, 32, 1, 128, 16, 128, 2)
        assert plan.heads == 32 and plan.grid[0] == 32
        assert plan.grid_steps == plan.grid[0] * plan.grid[1] <= 4096
        assert plan.bytes_per_step >= 256 * 1024
        assert plan.grid[1] == -(-128 // plan.pages)
        mixed = pa_module.launch_plan(36, 32, 64, 128, 16, 128, 2)
        assert mixed.heads == 32 and mixed.grid_steps <= 4608
        # tile_kv, where a caller passes one, is the pages a step
        assert pa_module.launch_plan(36, 32, 64, 128, 16, 128, 2,
                                   tile_kv=1).grid == (36, 128)
        # grouped shards (mp 4) and an int8 pool keep whole blocks
        assert pa_module.launch_plan(32, 8, 1, 128, 16, 128, 2).heads == 8
        q8 = pa_module.launch_plan(36, 32, 64, 128, 16, 128, 1,
                                 quantized=True)
        assert q8.heads % 8 == 0 and 32 % q8.heads == 0


class TestDecodeAttention:
    @pytest.mark.parametrize("nh,nkv", [(8, 4), (4, 4)])
    def test_matches_dense(self, nh, nkv):
        B, S, hd = 3, 64, 32
        q = _rand(B, nh, hd)
        kc, vc = _rand(B, S, nkv, hd), _rand(B, S, nkv, hd)
        lens = jnp.asarray([5, 64, 17], jnp.int32)
        out = decode_attention(q, kc, vc, lens, block_s=16)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(decode_attention_reference(q, kc, vc, lens)),
            atol=1e-5, rtol=1e-5)

    def test_non_dividing_cache_length_pads(self):
        """S that no power-of-two block divides (e.g. 200) must zero-pad
        up to a block multiple instead of collapsing to tiny blocks
        (16x grid blowup measured in the r3 decode bench)."""
        B, S, nh, hd = 2, 50, 4, 16
        q = _rand(B, nh, hd)
        kc, vc = _rand(B, S, nh, hd), _rand(B, S, nh, hd)
        lens = jnp.asarray([50, 13], jnp.int32)
        out = decode_attention(q, kc, vc, lens, block_s=16)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(decode_attention_reference(q, kc, vc, lens)),
            atol=1e-5, rtol=1e-5)

    def test_zero_length_rows_return_zeros(self):
        """seq_lens == 0 must yield a zero row, not the uniform mean of
        the whole (garbage) cache (advisor r2 finding)."""
        B, S, nh, hd = 2, 32, 4, 16
        q = _rand(B, nh, hd)
        kc, vc = _rand(B, S, nh, hd), _rand(B, S, nh, hd)
        lens = jnp.asarray([0, 7], jnp.int32)
        out = np.asarray(decode_attention(q, kc, vc, lens, block_s=8))
        ref = np.asarray(decode_attention_reference(q, kc, vc, lens))
        assert np.all(out[0] == 0.0) and np.all(ref[0] == 0.0)
        assert np.all(np.isfinite(out))
        np.testing.assert_allclose(out[1], ref[1], atol=1e-5, rtol=1e-5)

    def test_traced_time_step_no_retrace(self):
        """Decode forward keeps time_step traced: one jit trace serves
        every decode position (advisor r2 finding — int(time_step)
        forced a host sync + retrace per step)."""
        import paddle_tpu as paddle
        from paddle_tpu.framework.tensor import Tensor
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        paddle.seed(0)
        m = FusedMultiTransformer(embed_dim=32, num_heads=4,
                                  dim_feedforward=64, num_layers=1)
        caches = m.gen_cache(2, 16)
        x0 = paddle.to_tensor(rng.standard_normal((2, 4, 32))
                              .astype(np.float32))
        _, caches = m(x0, caches=caches, time_step=0)
        traces = 0

        def fwd(tok, cache_data, t):
            nonlocal traces
            traces += 1
            o, cs = m(Tensor(tok), caches=[Tensor(c) for c in cache_data],
                      time_step=Tensor(t))
            return o.data, [c.data for c in cs]

        jf = jax.jit(fwd)
        cd = [c.data for c in caches]
        cd_eager = [c.data for c in caches]
        for t in (4, 5, 6):
            tok = jnp.asarray(rng.standard_normal((2, 1, 32)), jnp.float32)
            o, cd = jf(tok, cd, jnp.asarray(t, jnp.int32))
            # eager reference with a static python-int time_step
            o_ref, cs = m(Tensor(tok),
                          caches=[Tensor(c) for c in cd_eager],
                          time_step=t)
            cd_eager = [c.data for c in cs]
            np.testing.assert_allclose(np.asarray(o), o_ref.numpy(),
                                       rtol=1e-5, atol=1e-6)
        assert traces == 1

    def test_fused_transformer_decode_uses_cache_correctly(self):
        """End-to-end: FusedMultiTransformer decode equals the dense
        path (the kernel is TPU-gated; this exercises the jnp fallback +
        the kernel reference on the same cache layout)."""
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        paddle.seed(0)
        m = FusedMultiTransformer(embed_dim=32, num_heads=4,
                                  dim_feedforward=64, num_layers=2)
        caches = m.gen_cache(2, 16)
        x0 = paddle.to_tensor(rng.standard_normal((2, 4, 32))
                              .astype(np.float32))
        out, caches = m(x0, caches=caches, time_step=0)
        x1 = paddle.to_tensor(rng.standard_normal((2, 1, 32))
                              .astype(np.float32))
        out1, caches = m(x1, caches=caches, time_step=4)
        assert out1.shape == [2, 1, 32]
        # kernel parity on the resulting cache layout
        c = caches[0]
        kc = jnp.swapaxes(c.data[0], 1, 2)
        vc = jnp.swapaxes(c.data[1], 1, 2)
        q = _rand(2, 4, 8)
        lens = jnp.asarray([5, 5], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(decode_attention(q, kc, vc, lens, block_s=8)),
            np.asarray(decode_attention_reference(q, kc, vc, lens)),
            atol=1e-5, rtol=1e-5)


class TestLlamaPallasFusedPath:
    def test_fused_block_matches_jnp_block(self):
        """Force the single-chip fused path (interpret mode on CPU) and
        check the trainer's loss + grads match the jnp path."""
        from paddle_tpu.parallel import mesh as mesh_mod
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.llama_spmd import LlamaSpmdTrainer
        mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])
        cfg = LlamaConfig.tiny(vocab=64, hidden=128, layers=2, heads=4,
                               kv_heads=2, inter=128, seq=16)
        ids = rng.integers(0, 64, (2, 16))
        tr = LlamaSpmdTrainer(cfg, remat=False,
                              compute_dtype=jnp.float32, seed=1)
        base = float(tr.loss_fn(tr.params, jnp.asarray(ids),
                                jnp.asarray(ids)))
        tr._pallas_fused = True  # interpret-mode kernels on CPU
        fused = float(tr.loss_fn(tr.params, jnp.asarray(ids),
                                 jnp.asarray(ids)))
        np.testing.assert_allclose(fused, base, rtol=1e-5)
        g1 = jax.grad(tr.loss_fn)(tr.params, jnp.asarray(ids),
                                  jnp.asarray(ids))
        tr._pallas_fused = False
        g2 = jax.grad(tr.loss_fn)(tr.params, jnp.asarray(ids),
                                  jnp.asarray(ids))
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_fused_adamw_train_step(self):
        from paddle_tpu.parallel import mesh as mesh_mod
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.llama_spmd import LlamaSpmdTrainer
        mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])
        cfg = LlamaConfig.tiny(vocab=64, hidden=128, layers=2, heads=4,
                               kv_heads=2, inter=128, seq=16)
        ids = rng.integers(0, 64, (2, 16))
        tr = LlamaSpmdTrainer(cfg, remat=False,
                              compute_dtype=jnp.float32, seed=1)
        tr._pallas_fused = True
        first = float(tr.train_step(ids))
        for _ in range(4):
            last = float(tr.train_step(ids))
        assert last < first


class TestW8A16Matmul:
    def test_matches_float_matmul(self):
        from paddle_tpu.ops.pallas.int8_matmul import w8a16_matmul
        r = np.random.default_rng(0)
        # the last shape has no lane-aligned divisor of N (a 50257-token
        # vocabulary in small): the final column block is partial
        for M, K, N in [(1, 256, 128), (8, 512, 256), (5, 384, 128),
                        (8, 512, 1000)]:
            x = jnp.asarray(r.standard_normal((M, K)), jnp.bfloat16)
            w = jnp.asarray(r.integers(-127, 128, (K, N)), jnp.int8)
            out = w8a16_matmul(x, w)
            assert out is not None and out.shape == (M, N)
            ref = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-2, atol=2e-2)

    def test_returns_none_on_bad_tiling(self):
        from paddle_tpu.ops.pallas.int8_matmul import w8a16_matmul
        x = jnp.zeros((4, 100), jnp.bfloat16)   # K=100: no valid block
        w = jnp.zeros((100, 128), jnp.int8)
        assert w8a16_matmul(x, w) is None

    def test_quantized_matmul_routes_and_matches(self):
        from paddle_tpu.quantization.functional import (quantize,
                                                        quantized_matmul)
        r = np.random.default_rng(1)
        w = jnp.asarray(r.standard_normal((256, 128)), jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0)
        wq = quantize(w, scale, bits=8, axis=-1)
        x = jnp.asarray(r.standard_normal((4, 256)), jnp.float32)
        out = quantized_matmul(x, wq, scale, out_dtype=jnp.float32)
        ref = jnp.matmul(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-2, atol=3e-1)
