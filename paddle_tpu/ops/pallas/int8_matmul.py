"""Weight-only int8 matmul (w8a16) Pallas kernel.

TPU analog of the reference's int8 weight-only serving GEMMs
(ref: /root/reference/paddle/fluid/operators/fused/
fused_multi_transformer_int8_op.cu + attn_gemm_int8.h). The XLA fallback
(`dequantize W then matmul`) MATERIALIZES the dequantized bf16 weight in
HBM, so the memory traffic is int8-read + bf16-write + bf16-read — worse
than plain bf16. This kernel streams the int8 weight blocks straight into
VMEM, casts in-register, and accumulates on the MXU: weight bytes over
the wire are actually halved, which is the whole point of int8 in the
weight-bound decode regime.

Scale application (per-out-channel) is folded OUTSIDE the kernel: the
[M, N] output is tiny in serving (M = batch), so `out * scale/qmax` is a
free XLA fusion, and the kernel needs no awkward (1, N) scale block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu


def _w8a16_kernel(x_ref, w_ref, o_ref, acc_scr, *, k_steps):
    k_i = pl.program_id(1)

    @pl.when(k_i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)           # [M, bk]
    w = w_ref[...].astype(jnp.float32)           # [bk, bn] <- int8 cast
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == k_steps - 1)
    def _done():
        o_ref[...] = acc_scr[...]


def _pick_block(dim, candidates):
    for c in candidates:
        if dim % c == 0:
            return c
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _w8a16_call(x, w_int8, M_pad, blocks):
    bk, bn = blocks[:2]
    K, N = w_int8.shape
    # N need not divide bn: the last column block is partial (reads past
    # the edge are padding, writes are clipped), and a column of the
    # output depends on its own weight column only. K divides exactly —
    # padding inside the contraction would pollute every sum.
    k_steps, n_steps = K // bk, pl.cdiv(N, bn)
    kernel = functools.partial(_w8a16_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(n_steps, k_steps),
        in_specs=[
            pl.BlockSpec((M_pad, bk), lambda n, k: (0, k)),
            pl.BlockSpec((bk, bn), lambda n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((M_pad, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((M_pad, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((M_pad, bn), jnp.float32)],
        interpret=not on_tpu(),
    )(x, w_int8)


def _w8a16_fwd(x, w_int8, M_pad, blocks):
    return _w8a16_call(x, w_int8, M_pad, blocks), (w_int8,)


def _w8a16_bwd(M_pad, blocks, res, g):
    # the kernel has no JVP rule; backward (QAT paths) runs the plain XLA
    # contraction — the int8 weight is a frozen constant (zero cotangent)
    (w_int8,) = res
    x_dtype = blocks[2]
    gx = (g @ w_int8.astype(jnp.float32).T).astype(x_dtype)
    return gx, jnp.zeros(w_int8.shape, jax.dtypes.float0)


_w8a16_call.defvjp(_w8a16_fwd, _w8a16_bwd)


def w8a16_matmul(x, w_int8, block_k=512, block_n=512):
    """x [M, K] float/bf16 @ w_int8 [K, N] -> f32 [M, N] (UNSCALED:
    multiply by per-channel scale/qmax outside). Returns None when the
    shapes don't fit the kernel's tiling — K without a 32-aligned block
    divisor, or N under one 128-lane block (caller takes the XLA
    contraction).
    Differentiable wrt x via a custom VJP (plain XLA contraction)."""
    if x.ndim != 2 or w_int8.ndim != 2:
        return None
    M, K = x.shape
    K2, N = w_int8.shape
    if K != K2:
        return None
    bk = _pick_block(K, [b for b in (block_k, 512, 256, 128) if b <= K])
    bn = _pick_block(N, [b for b in (block_n, 512, 256, 128) if b <= N])
    if bn is None and N >= 128:
        # no lane-aligned divisor (e.g. a 50257-token vocabulary): keep
        # the block lane-aligned and let the last one be partial
        bn = min(block_n, N // 128 * 128)
    if bk is None or bn is None or bk % 32 or bn % 128:
        return None
    # pad M to the sublane tile for the activation dtype
    m_tile = 16 if x.dtype == jnp.bfloat16 else 8
    M_pad = max(m_tile, -(-M // m_tile) * m_tile)
    if M_pad != M:
        x = jnp.pad(x, [(0, M_pad - M), (0, 0)])
    out = _w8a16_call(x, w_int8, M_pad, (bk, bn, str(x.dtype)))
    return out[:M]
