"""Flash attention on TPU (Pallas/Mosaic).

This is the TPU equivalent of the reference's flash-attention binding
(ref: /root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu dispatching
to the external CUDA flashattn lib via paddle/phi/backends/dynload/
flashattn.cc) and the cutlass memory-efficient attention
(paddle/phi/kernels/fusion/cutlass/memory_efficient_attention.cu).

Two paths:
- `_flash_fwd_pallas`: this repo's own forward kernel — online-softmax over
  KV blocks, fp32 accumulators in VMEM scratch, MXU matmuls. Used directly
  for inference/no-grad and as the fwd of a custom_vjp.
- `flash_attention_blhd`: differentiable entry in paddle's [B, L, H, D]
  layout; by default routes to jax's tuned TPU flash kernels (fwd+bwd) for
  peak MFU, with this repo's kernel selectable via
  FLAGS_tpu_flash_impl=native.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# murmur3-finalizer constants as wrapped int32 (jnp int32 arithmetic is
# two's-complement wraparound under XLA, exactly what a u32 hash needs)
def _hash_mix(x):
    sr = jax.lax.shift_right_logical
    x = x ^ sr(x, 16)
    x = x * jnp.int32(-2048144789)      # 0x85ebca6b
    x = x ^ sr(x, 13)
    x = x * jnp.int32(-1028477387)      # 0xc2b2ae35
    x = x ^ sr(x, 16)
    return x


def _keep_scale(row, col, bh, seed, rate):
    """Deterministic per-POSITION dropout mask (independent of kernel
    blocking, so the fwd and both bwd kernels regenerate the identical
    mask from (position, seed) alone). Returns keep/(1-rate) as f32."""
    h = (row * jnp.int32(-1640531527)
         ^ col * jnp.int32(1013904223)
         ^ bh * jnp.int32(374761393)) + seed
    h = _hash_mix(h)
    u = (h & jnp.int32(0xFFFFFF)).astype(jnp.float32) * (1.0 / (1 << 24))
    return jnp.where(u >= rate, 1.0 / (1.0 - rate), 0.0)


def _block_drop_scale(q_i, kv_i, block_q, block_k, seed_ref, rate):
    """The [block_q, block_k] dropout scale for grid block (q_i, kv_i) —
    ONE derivation shared by the fwd, dq and dkv kernels so their masks
    can never desynchronize (which would silently corrupt gradients)."""
    row = q_i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    col = kv_i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return _keep_scale(row, col, pl.program_id(0), seed_ref[0, 0], rate)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch,
                      l_scratch, acc_scratch, *, kv_steps, sm_scale, causal,
                      block_q, block_k, t_k, causal_offset, mask_tail,
                      dropout_rate=0.0, seed_ref=None):
    """Grid: (batch*heads, q_blocks, kv_blocks). Online softmax: running max
    (m), normalizer (l) and fp32 accumulator live in VMEM scratch across the
    kv_block grid dimension. `t_k` is the un-padded KV length (tail KV blocks
    beyond it are masked out); causal masking offsets the row index by
    t_k - t_q so cross-length attention matches the dense reference."""
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # causal block skipping: a KV block lying entirely above the (offset)
    # diagonal contributes nothing — skip its MXU work. Only safe when
    # t_k >= t_q (causal_offset >= 0), where no q row is fully masked.
    q_i = pl.program_id(1)
    if causal and causal_offset >= 0:
        run = (q_i * block_q + block_q - 1 + causal_offset
               >= kv_i * block_k)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0]                   # [block_q, d]
        k = k_ref[0]                   # [block_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale               # [block_q, block_k]

        pad_valid = None
        if mask_tail:
            col = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            pad_valid = col < t_k
            s = jnp.where(pad_valid, s, NEG_INF)
        if causal:
            row = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            # causal-masked entries get NEG_INF but are NOT force-zeroed
            # below: a fully-masked row then degrades to uniform attention,
            # matching the dense reference (softmax of an all-NEG_INF row)
            # and hence the AD backward of the custom_vjp.
            s = jnp.where(row + causal_offset >= col, s, NEG_INF)

        m_prev = m_scratch[...]        # [block_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if pad_valid is not None:
            # padding columns must contribute exactly 0 even for rows whose
            # running max is still NEG_INF (exp(NEG_INF - NEG_INF) == 1)
            p = jnp.where(pad_valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        # the normalizer l uses the UNDROPPED p (dropout applies to the
        # normalized probabilities: out = (P∘M/(1-r)) V = acc_dropped / l)
        l_new = alpha * l_scratch[...] + jnp.sum(p, axis=1, keepdims=True)
        p_use = p
        if dropout_rate > 0.0:
            p_use = p * _block_drop_scale(q_i, kv_i, block_q, block_k,
                                          seed_ref, dropout_rate)
        acc = acc_scratch[...] * alpha + jax.lax.dot(
            p_use.astype(v.dtype), v, preferred_element_type=jnp.float32)

        m_scratch[...] = m_new
        l_scratch[...] = l_new
        acc_scratch[...] = acc

    @pl.when(kv_i == kv_steps - 1)
    def _finish():
        o_ref[0] = (acc_scratch[...] /
                    jnp.maximum(l_scratch[...], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m_scratch[...] + jnp.log(
                jnp.maximum(l_scratch[...], 1e-30))
            # lane-broadcast layout (jax flash kernel convention): the lse
            # value lives in all 128 lanes of its row
            lse_ref[0] = jnp.broadcast_to(lse, (block_q, 128))


def _flash_fwd_pallas(q, k, v, causal=False, sm_scale=None, block_q=128,
                      block_k=128, interpret=False, return_lse=False,
                      dropout_rate=0.0, seed=None):
    """q,k,v: [BH, T, D] -> o [BH, T, D] (and lse [BH, T] if return_lse).
    Handles sequence lengths that are not multiples of the block size by
    padding + in-kernel masking. dropout_rate > 0 drops attention
    probabilities in-kernel using the position-hash mask (`seed` is a
    traced int32 scalar; no probs tensor ever hits HBM)."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    # block sublane dims must stay tile-aligned for Mosaic (16 covers bf16)
    block_q = min(block_q, -(-t_q // 16) * 16)
    block_k = min(block_k, -(-t_k // 16) * 16)
    t_q_pad = -(-t_q // block_q) * block_q
    t_k_pad = -(-t_k // block_k) * block_k
    if t_q_pad != t_q:
        q = jnp.pad(q, ((0, 0), (0, t_q_pad - t_q), (0, 0)))
    if t_k_pad != t_k:
        k = jnp.pad(k, ((0, 0), (0, t_k_pad - t_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_k_pad - t_k), (0, 0)))
    grid = (bh, t_q_pad // block_q, t_k_pad // block_k)

    base = functools.partial(
        _flash_fwd_kernel, kv_steps=grid[2], sm_scale=sm_scale,
        causal=causal, block_q=block_q, block_k=block_k, t_k=t_k,
        causal_offset=t_k - t_q, mask_tail=(t_k_pad != t_k),
        dropout_rate=dropout_rate)

    out_shapes = [jax.ShapeDtypeStruct((bh, t_q_pad, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0))]
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
    ]
    extra = ()
    if dropout_rate > 0.0:
        # the seed rides as an (8,128) VMEM tile (a (1,1) block would
        # violate Mosaic tiling); kernels read [0, 0]
        extra = (jnp.full((8, 128), jnp.asarray(seed, jnp.int32)),)
        in_specs.append(pl.BlockSpec((8, 128), lambda b, qi, ki: (0, 0)))
        if return_lse:
            def kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref,
                       m_s, l_s, acc_s):
                return base(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s,
                            acc_s, seed_ref=seed_ref)
        else:
            def kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, m_s, l_s,
                       acc_s):
                return base(q_ref, k_ref, v_ref, o_ref, None, m_s, l_s,
                            acc_s, seed_ref=seed_ref)
    elif return_lse:
        kernel = base
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s):
            return base(q_ref, k_ref, v_ref, o_ref, None, m_s, l_s, acc_s)

    if return_lse:
        out_shapes.append(
            jax.ShapeDtypeStruct((bh, t_q_pad, 128), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)))

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if return_lse else out_specs[0],
        out_shape=out_shapes if return_lse else out_shapes[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, *extra)
    out, lse = outs if return_lse else (outs, None)
    if t_q_pad != t_q:
        out = out[:, :t_q]
        lse = lse[:, :t_q] if lse is not None else None
    return (out, lse[:, :, 0]) if return_lse else out


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, kv_steps, sm_scale, causal,
                         block_q, block_k, t_k, causal_offset, mask_tail,
                         dropout_rate=0.0, seed_ref=None):
    """Grid (bh, q_blocks, kv_blocks): accumulate dQ over KV blocks.
    dS = P * (dO V^T - delta); dQ = dS K * scale  (FlashAttention-2 bwd)."""
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_i = pl.program_id(1)
    if causal:
        run = (q_i * block_q + block_q - 1 + causal_offset
               >= kv_i * block_k)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        valid = None
        if mask_tail:
            col = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = col < t_k
        if causal:
            row = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            cm = row + causal_offset >= col
            valid = cm if valid is None else (valid & cm)
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)

        p = jnp.exp(s - lse)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # same position-hash mask as the forward: dS = P∘(M̃∘dP - δ)
            dp = dp * _block_drop_scale(q_i, kv_i, block_q, block_k,
                                        seed_ref, dropout_rate)
        ds = p * (dp - delta) * sm_scale
        dq_acc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(kv_i == kv_steps - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, q_steps,
                          sm_scale, causal, block_q, block_k, t_k,
                          causal_offset, mask_tail, dropout_rate=0.0,
                          seed_ref=None):
    """Grid (bh, kv_blocks, q_blocks): accumulate dK/dV over Q blocks.
    dV = P^T dO; dK = dS^T Q * scale."""
    q_i = pl.program_id(2)

    @pl.when(q_i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    kv_idx = pl.program_id(1)
    if causal:
        run = (q_i * block_q + block_q - 1 + causal_offset
               >= kv_idx * block_k)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        valid = None
        if mask_tail:
            col = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = col < t_k
        if causal:
            row = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            cm = row + causal_offset >= col
            valid = cm if valid is None else (valid & cm)
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)

        p = jnp.exp(s - lse)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        p_v = p
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            ks = _block_drop_scale(q_i, kv_idx, block_q, block_k,
                                   seed_ref, dropout_rate)
            p_v = p * ks              # dV sees the dropped probabilities
            dp = dp * ks
        # dV += (P∘M̃)^T dO
        dv_acc[...] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        # dK += dS^T Q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_i == q_steps - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale, block_q=128,
                      block_k=128, interpret=False, dropout_rate=0.0,
                      seed=None):
    """FlashAttention-2 backward. q,k,v,o,do: [BH, T, D]; lse: [BH, T]."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = min(block_q, -(-t_q // 16) * 16)
    block_k = min(block_k, -(-t_k // 16) * 16)
    t_q_pad = -(-t_q // block_q) * block_q
    t_k_pad = -(-t_k // block_k) * block_k

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    if t_q_pad != t_q:
        pad = ((0, 0), (0, t_q_pad - t_q), (0, 0))
        q = jnp.pad(q, pad)
        do = jnp.pad(do, pad)
        # padded q rows: lse=+inf makes p = exp(s - inf) = 0 everywhere
        lse = jnp.pad(lse, ((0, 0), (0, t_q_pad - t_q)),
                      constant_values=jnp.inf)
        delta = jnp.pad(delta, ((0, 0), (0, t_q_pad - t_q)))
    if t_k_pad != t_k:
        pad = ((0, 0), (0, t_k_pad - t_k), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # lane-broadcast layout for row statistics (see _flash_fwd_kernel)
    lse = jnp.broadcast_to(lse[:, :, None], (bh, t_q_pad, 128))
    delta = jnp.broadcast_to(delta[:, :, None], (bh, t_q_pad, 128))

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, t_k=t_k, causal_offset=t_k - t_q,
                  mask_tail=(t_k_pad != t_k), dropout_rate=dropout_rate)
    seed_extra = ()
    seed_spec = []
    if dropout_rate > 0.0:
        seed_extra = (jnp.full((8, 128), jnp.asarray(seed, jnp.int32)),)
        seed_spec = [pl.BlockSpec((8, 128), lambda b, i, j: (0, 0))]
    cparams = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    grid_dq = (bh, t_q_pad // block_q, t_k_pad // block_k)
    dq_base = functools.partial(_flash_bwd_dq_kernel, kv_steps=grid_dq[2],
                                **common)
    if dropout_rate > 0.0:
        def dq_kernel(q_r, k_r, v_r, do_r, lse_r, dl_r, seed_r, dq_r,
                      dq_a):
            return dq_base(q_r, k_r, v_r, do_r, lse_r, dl_r, dq_r, dq_a,
                           seed_ref=seed_r)
    else:
        dq_kernel = dq_base
    dq = pl.pallas_call(
        dq_kernel,
        grid=grid_dq,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
        ] + seed_spec,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=cparams,
    )(q, k, v, do, lse, delta, *seed_extra)

    grid_dkv = (bh, t_k_pad // block_k, t_q_pad // block_q)
    dkv_base = functools.partial(_flash_bwd_dkv_kernel, q_steps=grid_dkv[2],
                                 **common)
    if dropout_rate > 0.0:
        def dkv_kernel(q_r, k_r, v_r, do_r, lse_r, dl_r, seed_r, dk_r,
                       dv_r, dk_a, dv_a):
            return dkv_base(q_r, k_r, v_r, do_r, lse_r, dl_r, dk_r, dv_r,
                            dk_a, dv_a, seed_ref=seed_r)
    else:
        dkv_kernel = dkv_base
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=grid_dkv,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, ki, qi: (b, qi, 0)),
        ] + seed_spec,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, t_k_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t_k_pad, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        compiler_params=cparams,
    )(q, k, v, do, lse, delta, *seed_extra)

    if t_q_pad != t_q:
        dq = dq[:, :t_q]
    if t_k_pad != t_k:
        dk = dk[:, :t_k]
        dv = dv[:, :t_k]
    return dq, dk, dv


def _mha_jnp(q, k, v, causal, sm_scale, dropout_rate=0.0, seed=None):
    # [B,H,T,D] reference fallback
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and seed is not None:
        # identical position-hash mask as the kernels ([B,H] folds to bh)
        b, h, tq, tk = p.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)[None]
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)[None]
        bh = jnp.arange(b * h, dtype=jnp.int32).reshape(b * h, 1, 1)
        ks = _keep_scale(row, col, bh, jnp.asarray(seed, jnp.int32),
                         dropout_rate).reshape(b, h, tq, tk)
        p = (p * ks).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# tests set this to run the pallas kernel in interpret mode on CPU
_FORCE_INTERPRET = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _native_flash_bhtd(q, k, v, seed, causal, sm_scale, dropout_rate=0.0):
    b, h, t, d = q.shape
    o = _flash_fwd_pallas(q.reshape(b * h, t, d), k.reshape(b * h, -1, d),
                          v.reshape(b * h, -1, d), causal, sm_scale,
                          interpret=_FORCE_INTERPRET,
                          dropout_rate=dropout_rate, seed=seed)
    return o.reshape(b, h, t, d)


def _native_fwd(q, k, v, seed, causal, sm_scale, dropout_rate):
    b, h, t, d = q.shape
    o, lse = _flash_fwd_pallas(
        q.reshape(b * h, t, d), k.reshape(b * h, -1, d),
        v.reshape(b * h, -1, d), causal, sm_scale,
        interpret=_FORCE_INTERPRET, return_lse=True,
        dropout_rate=dropout_rate, seed=seed)
    return o.reshape(b, h, t, d), (q, k, v, o.reshape(b, h, t, d), lse,
                                   seed)


def _native_bwd(causal, sm_scale, dropout_rate, res, do):
    import numpy as np
    q, k, v, o, lse, seed = res
    b, h, t, d = q.shape
    dq, dk, dv = _flash_bwd_pallas(
        q.reshape(b * h, t, d), k.reshape(b * h, -1, d),
        v.reshape(b * h, -1, d), o.reshape(b * h, t, d), lse,
        do.reshape(b * h, t, d), causal, sm_scale,
        interpret=_FORCE_INTERPRET, dropout_rate=dropout_rate, seed=seed)
    dseed = np.zeros((), jax.dtypes.float0)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, -1, d),
            dv.reshape(b, h, -1, d), dseed)


_native_flash_bhtd.defvjp(_native_fwd, _native_bwd)


def flash_attention_blhd(q, k, v, causal=False, sm_scale=None,
                         dropout_rate=0.0, seed=None):
    """Differentiable flash attention, paddle layout [B, L, H, D].
    dropout_rate > 0 applies in-kernel attention-probability dropout
    (needs a traced int32 `seed`; jax's tuned kernel has no dropout, so
    the native kernel carries it)."""
    from ...flags import get_flag
    sm_scale = sm_scale if sm_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    qh = jnp.moveaxis(q, 1, 2)
    kh = jnp.moveaxis(k, 1, 2)
    vh = jnp.moveaxis(v, 1, 2)
    impl = get_flag("FLAGS_tpu_flash_impl", "jax")
    if dropout_rate > 0.0:
        if not 0.0 < dropout_rate < 1.0:
            # rate 1.0 drops every probability: the output is zeros
            return jnp.zeros_like(q)
        if seed is None:
            raise ValueError(
                "flash_attention_blhd: dropout_rate > 0 needs a seed")
        impl = "native"
    if causal and q.shape[1] > k.shape[1]:
        # t_q > t_k causal has fully-masked rows whose forward degrades to
        # uniform attention; the hand-written backward zeroes them instead,
        # so use the dense path where AD matches the primal exactly
        # (applying the SAME position-hash dropout mask as the kernel)
        out = _mha_jnp(qh, kh, vh, True, sm_scale,
                       dropout_rate=dropout_rate,
                       seed=None if dropout_rate == 0.0 else seed)
        return jnp.moveaxis(out, 1, 2)
    if causal and q.shape[1] != k.shape[1]:
        # jax's tuned kernel masks top-left (col <= row, no cross-length
        # offset); our semantics are bottom-right like the dense reference,
        # so cross-length causal (t_k > t_q) must use the native kernel
        impl = "native"
    if impl == "native":
        out = _native_flash_bhtd(
            qh, kh, vh,
            jnp.asarray(seed if seed is not None else 0, jnp.int32),
            causal, sm_scale, dropout_rate)
    else:
        # no fallback: a Mosaic refusal of the tuned kernel must surface,
        # not silently cost the trainer its backward kernel
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash)
        out = jax_flash(qh, kh, vh, causal=causal, sm_scale=sm_scale,
                        block_sizes=_tuned_block_sizes(
                            qh.shape[2], kh.shape[2]))
    return jnp.moveaxis(out, 1, 2)


def _tuned_block_sizes(t_q, t_k):
    """Block sizes for jax's tuned flash kernel, measured on v5e at the
    training shape [12, 32, 2048, 128]: q1024/kM512/k512 runs the
    fwd+bwd in 47ms vs 138ms with the library defaults (a one-off
    probe, since deleted). Clamped so every block divides the
    (padded-to-128) sequence lengths."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    def clamp(b, t):
        b = min(b, t)
        while t % b:
            b //= 2
        return max(b, 128) if t % max(b, 128) == 0 else t
    bq = clamp(1024, t_q)
    bk = clamp(512, t_k)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq)
