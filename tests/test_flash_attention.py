"""Flash-attention kernel parity (ref test model: OpTest check_output
semantics from /root/reference/python/paddle/fluid/tests/unittests/
eager_op_test.py — forward vs dense reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.flash_attention import (
    _flash_fwd_pallas, _mha_jnp, _native_flash_bhtd)


def _dense_ref(q, k, v, causal):
    # [BH, T, D] -> dense attention via the jnp reference path
    return _mha_jnp(q[:, None], k[:, None], v[:, None], causal,
                    1.0 / np.sqrt(q.shape[-1])).reshape(q.shape[0],
                                                        q.shape[1], -1)


@pytest.mark.parametrize("tq,tk,causal", [
    (128, 128, True), (128, 128, False),
    (100, 100, True), (100, 100, False),   # ragged: not multiple of block
    (257, 257, True),                      # ragged, multi-block
    (64, 192, True),                       # cross-length causal (offset)
    (192, 64, False), (37, 129, False), (129, 37, False),
])
def test_flash_fwd_matches_dense(tq, tk, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, tq, 16), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((2, tk, 16), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((2, tk, 16), dtype=np.float32))
    o = _flash_fwd_pallas(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = _dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("tq,tk,causal", [
    (64, 64, True),
    (100, 100, True),    # ragged: padded q/kv tail + mask_tail in bwd
    (257, 257, False),   # multi-block accumulation, non-causal
    (257, 257, True),    # multi-block + causal block skipping
    (64, 192, True),     # cross-length causal (offset, t_k > t_q)
    (129, 37, False),    # ragged cross-length non-causal
])
def test_native_flash_grad_matches_dense(tq, tk, causal):
    import paddle_tpu.ops.pallas.flash_attention as fa
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, tq, 16), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, tk, 16), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, tk, 16), dtype=np.float32))
    sm = 1.0 / np.sqrt(16)

    def loss_flash(q, k, v):
        return jnp.sum(_native_flash_bhtd(q, k, v, jnp.int32(0),
                                          causal, sm, 0.0) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_mha_jnp(q, k, v, causal, sm) ** 2)

    fa._FORCE_INTERPRET = True
    try:
        o_f = _native_flash_bhtd(q, k, v, jnp.int32(0), causal, sm,
                                 0.0)
        o_d = _mha_jnp(q, k, v, causal, sm)
        np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_d),
                                   atol=2e-5)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa._FORCE_INTERPRET = False
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name} ({tq},{tk},{causal})")


class TestFlashDropout:
    """In-kernel attention-probability dropout (the dense path would
    materialize fp32 [B,H,T,T] probs; flash regenerates the mask from a
    position hash in fwd AND both bwd kernels)."""

    def _qkv(self, T=64, D=64):
        import paddle_tpu.ops.pallas.flash_attention as fa
        rng = np.random.default_rng(0)
        mk = lambda: jnp.asarray(
            rng.standard_normal((2, T, 3, D)), jnp.float32)
        return fa, mk(), mk(), mk()

    def test_rate_zero_matches_reference(self):
        from paddle_tpu.flags import set_flags
        fa, q, k, v = self._qkv()
        fa._FORCE_INTERPRET = True
        # the native kernel carries dropout; at rate 0 the default impl is
        # jax's tuned kernel, which only Mosaic can compile
        set_flags({"FLAGS_tpu_flash_impl": "native"})
        try:
            out = fa.flash_attention_blhd(q, k, v, dropout_rate=0.0)
        finally:
            set_flags({"FLAGS_tpu_flash_impl": "jax"})
            fa._FORCE_INTERPRET = False
        ref = jnp.moveaxis(fa._mha_jnp(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
            jnp.moveaxis(v, 1, 2), False, 1 / np.sqrt(64)), 1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_deterministic_and_seed_sensitive(self):
        fa, q, k, v = self._qkv()
        fa._FORCE_INTERPRET = True
        try:
            a = fa.flash_attention_blhd(q, k, v, dropout_rate=0.3,
                                        seed=jnp.int32(42))
            b = fa.flash_attention_blhd(q, k, v, dropout_rate=0.3,
                                        seed=jnp.int32(42))
            c = fa.flash_attention_blhd(q, k, v, dropout_rate=0.3,
                                        seed=jnp.int32(7))
        finally:
            fa._FORCE_INTERPRET = False
        assert bool(jnp.all(a == b))
        assert not bool(jnp.all(a == c))

    def test_grad_matches_finite_difference(self):
        """fwd and bwd kernels must regenerate the IDENTICAL mask — any
        divergence shows up immediately against central differences."""
        fa, q, k, v = self._qkv(T=32, D=64)
        seed = jnp.int32(5)

        def loss(q_, k_, v_):
            return jnp.sum(fa.flash_attention_blhd(
                q_, k_, v_, dropout_rate=0.25, seed=seed) ** 2)

        fa._FORCE_INTERPRET = True
        try:
            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            eps = 1e-3
            for ai, arr in enumerate((q, k, v)):
                idx = (0, 3, 1, 2)
                args = [q, k, v]
                args[ai] = arr.at[idx].add(eps)
                up = loss(*args)
                args[ai] = arr.at[idx].add(-eps)
                dn = loss(*args)
                fd = float((up - dn) / (2 * eps))
                an = float(g[ai][idx])
                assert abs(fd - an) < 5e-2 * max(1.0, abs(fd)), \
                    (ai, fd, an)
        finally:
            fa._FORCE_INTERPRET = False

    def test_keep_fraction(self):
        from paddle_tpu.ops.pallas.flash_attention import _keep_scale
        r = jax.lax.broadcasted_iota(jnp.int32, (256, 256), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (256, 256), 1)
        ks = _keep_scale(r, c, jnp.int32(0), jnp.int32(123), 0.3)
        kept = float(jnp.mean((ks > 0).astype(jnp.float32)))
        assert abs(kept - 0.7) < 0.02
        # kept entries carry the 1/(1-rate) upscale
        assert abs(float(jnp.max(ks)) - 1.0 / 0.7) < 1e-5


class TestAttentionDropoutRouting:
    """scaled_dot_product_attention must apply REAL dropout on every
    route (the dense fallback previously ignored dropout_p silently)."""

    def _qkv(self, T=16):
        import paddle_tpu as paddle
        rng = np.random.default_rng(0)
        mk = lambda: paddle.to_tensor(
            rng.standard_normal((2, T, 3, 8)).astype(np.float32))
        return mk(), mk(), mk()

    def test_dense_path_applies_dropout_in_training(self):
        import paddle_tpu.nn.functional as F
        q, k, v = self._qkv()
        out0 = F.scaled_dot_product_attention(q, k, v, dropout_p=0.0)
        out1 = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5,
                                              training=True)
        assert not np.allclose(np.asarray(out0.numpy()),
                               np.asarray(out1.numpy()))

    def test_eval_mode_disables_dropout(self):
        import paddle_tpu.nn.functional as F
        q, k, v = self._qkv()
        out0 = F.scaled_dot_product_attention(q, k, v, dropout_p=0.0)
        out1 = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5,
                                              training=False)
        np.testing.assert_allclose(np.asarray(out0.numpy()),
                                   np.asarray(out1.numpy()), atol=1e-6)

    def test_rate_one_returns_zeros(self):
        import paddle_tpu.ops.pallas.flash_attention as fa
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((1, 16, 2, 64)), jnp.float32)
        out = fa.flash_attention_blhd(x, x, x, dropout_rate=1.0,
                                      seed=jnp.int32(1))
        assert float(jnp.max(jnp.abs(out))) == 0.0

    def test_cross_length_causal_dense_fallback_drops(self):
        import paddle_tpu.ops.pallas.flash_attention as fa
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 32, 2, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 16, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 16, 2, 64)), jnp.float32)
        out0 = fa.flash_attention_blhd(q, k, v, causal=True)
        out1 = fa.flash_attention_blhd(q, k, v, causal=True,
                                       dropout_rate=0.4, seed=jnp.int32(9))
        assert not np.allclose(np.asarray(out0), np.asarray(out1))
