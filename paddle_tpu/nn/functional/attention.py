"""Attention functionals.

``scaled_dot_product_attention`` mirrors the flash-attention entry the
reference binds (ref: /root/reference/paddle/phi/kernels/gpu/
flash_attn_kernel.cu, python/paddle/nn/functional/flash_attention.py).
On TPU the fast path is the Pallas flash kernel in
paddle_tpu/ops/pallas/flash_attention.py, selected when shapes/dtypes
qualify and FLAGS_enable_pallas_kernels is on; otherwise a jnp fallback that
XLA fuses well."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework import device
from ...framework.op import apply, unwrap
from ...framework.tensor import Tensor

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdpa_reference"]


def _sdpa_jnp(q, k, v, mask, dropout_p, causal, scale, dropout_key=None):
    # q,k,v: [B, L, H, D] (paddle flash-attn layout)
    qh = jnp.moveaxis(q, 1, 2)  # [B,H,L,D]
    kh = jnp.moveaxis(k, 1, 2)
    vh = jnp.moveaxis(v, 1, 2)
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if causal:
        ql, kl = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        scores = jnp.where(cm, scores, -1e30 if scores.dtype == jnp.float32
                           else -3e4)
    if mask is not None:
        scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        if dropout_p >= 1.0:
            probs = jnp.zeros_like(probs)
        else:
            keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                        probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p),
                              0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.moveaxis(out, 2, 1)  # back to [B,L,H,D]


def sdpa_reference(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                   scale=None):
    """Dense jnp path (also the test reference for the pallas kernel).
    dropout_p > 0 applies real probability dropout (keyed from the
    framework RNG stream)."""
    args = (q, k, v) + ((attn_mask,) if attn_mask is not None else ())
    if dropout_p > 0.0:
        from ...framework import random as _random
        args = args + (_random.next_key(),)

        def impl(qa, ka, va, *rest):
            m = rest[0] if attn_mask is not None else None
            return _sdpa_jnp(qa, ka, va, m, dropout_p, is_causal, scale,
                             dropout_key=rest[-1])
        return apply(impl, args, op_name="flash_attention")

    def impl(qa, ka, va, *rest):
        m = rest[0] if rest else None
        return _sdpa_jnp(qa, ka, va, m, dropout_p, is_causal, scale)
    return apply(impl, args, op_name="flash_attention")


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention.
    Layout [batch, seqlen, num_heads, head_dim] as the reference's
    flash-attention API."""
    rate = float(dropout_p) if training else 0.0
    use_pallas = (
        attn_mask is None
        and query.shape[-1] >= 64
        and query.shape[-1] % 64 == 0
        # ragged lengths are fine: the kernel pads + masks tail blocks
        and device.use_pallas_kernels()
    )
    if use_pallas:
        from ...ops.pallas.flash_attention import flash_attention_blhd
        if rate > 0.0:
            # In-kernel probability dropout: the probs tensor never hits
            # HBM. Only a win once the [B,H,T,T] probs are actually big —
            # at short T the native kernel's serialized (B*H) grid loses
            # to XLA's batched dense matmuls (measured: BERT seq-128 got
            # 18% SLOWER through the kernel; T=1024 is the crossover for
            # speed, with an O(T^2)-probs memory win on top), so gate on
            # T >= 1024.
            if query.shape[1] >= 1024 and key.shape[1] >= 1024:
                from ...framework import random as _random
                rng_key = _random.next_key()

                def impl(qa, ka, va, kk):
                    seed = jax.random.bits(kk, (),
                                           "uint32").astype(jnp.int32)
                    return flash_attention_blhd(
                        qa, ka, va, causal=is_causal, dropout_rate=rate,
                        seed=seed)
                return apply(impl, (query, key, value, rng_key),
                             op_name="flash_attention")
            return sdpa_reference(query, key, value, attn_mask,
                                  rate, is_causal)

        def impl(qa, ka, va):
            return flash_attention_blhd(qa, ka, va, causal=is_causal)
        return apply(impl, (query, key, value), op_name="flash_attention")
    # rate (not raw dropout_p): training=False must disable dropout
    return sdpa_reference(query, key, value, attn_mask, rate, is_causal)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """ref: python/paddle/nn/functional/flash_attention.py — returns
    (out, softmax) tuple like the reference."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, **kw):
    """Varlen API: fall back to dense per-sequence attention."""
    q, k, v = unwrap(query), unwrap(key), unwrap(value)
    cu_q = unwrap(cu_seqlens_q)
    cu_k = unwrap(cu_seqlens_k)
    import numpy as np
    cu_q = np.asarray(cu_q)
    cu_k = np.asarray(cu_k)
    outs = []
    for i in range(len(cu_q) - 1):
        qs = query[int(cu_q[i]):int(cu_q[i + 1])]
        ks = key[int(cu_k[i]):int(cu_k[i + 1])]
        vs = value[int(cu_k[i]):int(cu_k[i + 1])]
        from ...ops.manipulation import unsqueeze, squeeze
        o = sdpa_reference(unsqueeze(qs, 0), unsqueeze(ks, 0),
                           unsqueeze(vs, 0), None, dropout, causal, scale)
        outs.append(squeeze(o, 0))
    from ...ops.manipulation import concat
    return concat(outs, 0), None
