"""Cache-KV decode attention (flash-decoding) Pallas kernel.

TPU analog of the reference's fused decoder attention with a preallocated
KV cache (ref: /root/reference/paddle/fluid/operators/fused/
fused_multi_transformer_op.cu.h:835 — masked multihead attention over
cache_kv with per-batch valid lengths). One query step attends over the
cache with an online softmax; positions beyond each row's seq_len are
masked. GQA is handled by folding query head groups onto the kv-head
axis OUTSIDE the kernel, so the inner compute is pure 2-D MXU matmuls
([g, hd] @ [hd, bs] and [g, bs] @ [bs, hd]).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_s, s_steps, sm_scale):
    b_i = pl.program_id(0)
    s_i = pl.program_id(1)

    @pl.when(s_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # [g, hd]
    k = k_ref[0].astype(jnp.float32)            # [block_s, hd]
    v = v_ref[0].astype(jnp.float32)            # [block_s, hd]
    length = len_ref[b_i, 0]                    # whole lens array in SMEM

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # [g, block_s]
    pos = s_i * block_s + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < length, scores, NEG_INF)

    m_prev = m_scr[...]                          # [g, 1]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    # mask the probabilities too: with length == 0 every score is
    # NEG_INF, m_new stays NEG_INF, and exp(scores - m_new) would be a
    # row of ones — the row must contribute nothing instead
    p = jnp.exp(scores - m_new) * (pos < length)  # [g, block_s]
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(s_i == s_steps - 1)
    def _done():
        l = l_scr[...]
        # length-0 rows have l == 0 and acc == 0: emit zeros, not NaN
        o_ref[0] = (acc_scr[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, seq_lens, sm_scale=None,
                     block_s=128):
    """q: [B, nh, hd] (one decode step). k_cache/v_cache:
    [B, S, nkv, hd]. seq_lens: int32 [B] valid cache lengths (the entry
    at seq_lens-1 is the newest token); rows with seq_lens == 0 return
    zeros. Returns [B, nh, hd]."""
    B, nh, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    g = nh // nkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    block_s = min(block_s, S)
    if S % block_s:
        # zero-pad the cache axis up to a block multiple rather than
        # shrinking the block (a 200-long cache would collapse to
        # 8-wide blocks: 16x the grid steps for the same bytes). The
        # in-kernel `pos < length` mask discards the padded zeros.
        S_pad = -(-S // block_s) * block_s
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        S = S_pad
    s_steps = S // block_s

    qg = q.reshape(B, nkv, g, hd).reshape(B * nkv, g, hd)
    kg = jnp.swapaxes(k_cache, 1, 2).reshape(B * nkv, S, hd)
    vg = jnp.swapaxes(v_cache, 1, 2).reshape(B * nkv, S, hd)
    lens = jnp.repeat(jnp.asarray(seq_lens, jnp.int32), nkv
                      ).reshape(B * nkv, 1)

    kernel = functools.partial(_decode_kernel, block_s=block_s,
                               s_steps=s_steps, sm_scale=scale)
    kw = {}
    scratch = [pltpu.VMEM((g, 1), jnp.float32),
               pltpu.VMEM((g, 1), jnp.float32),
               pltpu.VMEM((g, hd), jnp.float32)]
    if on_tpu():
        # the full lens vector rides in SMEM; the kernel indexes it by
        # program_id (a (1,1) block would violate Mosaic tiling rules)
        len_spec = pl.BlockSpec((B * nkv, 1), lambda b, s: (0, 0),
                                memory_space=pltpu.SMEM)
    else:
        len_spec = pl.BlockSpec((B * nkv, 1), lambda b, s: (0, 0))
        kw["interpret"] = True

    out = pl.pallas_call(
        kernel,
        grid=(B * nkv, s_steps),
        in_specs=[
            len_spec,
            pl.BlockSpec((1, g, hd), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nkv, g, hd), q.dtype),
        scratch_shapes=scratch,
        **kw,
    )(lens, qg, kg, vg)
    return out.reshape(B, nkv, g, hd).reshape(B, nh, hd)


def decode_attention_reference(q, k_cache, v_cache, seq_lens,
                               sm_scale=None):
    """jnp reference for tests/micro-bench."""
    B, nh, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    g = nh // nkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, nkv, g, hd)
    scores = jnp.einsum("bngd,bsnd->bngs", qg,
                        k_cache.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, None, None, :] < \
        jnp.asarray(seq_lens)[:, None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    # mask again after softmax so all-masked (length 0) rows yield zeros
    # rather than the uniform mean of the cache
    p = jax.nn.softmax(scores, axis=-1) * mask
    out = jnp.einsum("bngs,bsnd->bngd", p,
                     v_cache.astype(jnp.float32))
    return out.reshape(B, nh, hd).astype(q.dtype)
