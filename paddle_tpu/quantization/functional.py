"""Quantization primitives (ref: the fake_quantize_* fluid ops,
/root/reference/paddle/fluid/operators/fake_quantize_op.cc, and the int8
GEMM path /root/reference/paddle/fluid/operators/fused/attn_gemm_int8.h)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import device
from ..framework.op import apply as _apply
from ..framework.tensor import Tensor


def _op(fn, *args, op_name=None):
    return _apply(fn, args, op_name=op_name)


def _unwrap(x):
    return x.data if isinstance(x, Tensor) else jnp.asarray(x)


def quantize(x, scale, bits=8, axis=None):
    """float -> int8 (symmetric): round(x / scale * qmax), clipped."""
    qmax = 2 ** (bits - 1) - 1

    def impl(x_, s):
        if axis is not None:
            shape = [1] * x_.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        q = jnp.round(x_ / s * qmax)
        return jnp.clip(q, -qmax - 1, qmax).astype(jnp.int8)
    return _op(impl, x, scale, op_name="quantize")


def dequantize(q, scale, bits=8, axis=None, dtype=jnp.float32):
    qmax = 2 ** (bits - 1) - 1

    def impl(q_, s):
        if axis is not None:
            shape = [1] * q_.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        return q_.astype(dtype) * (s / qmax)
    return _op(impl, q, scale, op_name="dequantize")


def fake_quant(x, scale, bits=8, axis=None):
    """Quantize-dequantize with a straight-through estimator: forward sees
    the rounded value, backward passes gradients through unchanged (the
    reference's fake_quantize_dequantize ops give QAT the same semantics)."""
    qmax = 2 ** (bits - 1) - 1

    def impl(x_, s):
        if axis is not None:
            shape = [1] * x_.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        s = s / qmax
        qd = jnp.clip(jnp.round(x_ / s), -qmax - 1, qmax) * s
        return x_ + jax.lax.stop_gradient(qd - x_)
    return _op(impl, x, scale, op_name="fake_quant")


def quantized_matmul(x, w_int8, w_scale, x_scale=None, bits=8,
                     out_dtype=jnp.float32):
    """x [., K] @ int8 weight [K, N] -> float [., N].

    If x_scale is given, x is quantized on the fly and the matmul runs
    int8 x int8 -> int32 on the MXU (preferred_element_type=int32 — the
    TPU analog of the reference's cublasLt int8 GEMM, attn_gemm_int8.h);
    otherwise weight-only: dequantize W and run a float matmul (the bf16
    x dequant-int8 path that dominates TPU serving)."""
    qmax = 2 ** (bits - 1) - 1

    if x_scale is None:
        def impl(x_, w_, ws):
            # serving path: the Pallas w8a16 kernel streams int8 weight
            # blocks (halved weight bytes — the point of int8 in the
            # weight-bound decode regime); XLA fallback materializes the
            # dequantized weight, tripling traffic
            if device.use_pallas_kernels() \
                    and x_.ndim >= 2 and w_.ndim == 2:
                from ..ops.pallas.int8_matmul import w8a16_matmul
                lead = x_.shape[:-1]
                x2 = x_.reshape(-1, x_.shape[-1])
                if x2.shape[0] <= 256:       # serving-size M only
                    acc = w8a16_matmul(x2, w_)
                    if acc is not None:
                        out = acc * (ws.astype(jnp.float32) / qmax)
                        return out.astype(out_dtype).reshape(
                            *lead, w_.shape[1])
            # dequantize in f32 (scale precision), matmul in out_dtype
            # so bf16 activations stay bf16 end-to-end
            wf = (w_.astype(jnp.float32) * (ws / qmax)).astype(out_dtype)
            return jnp.matmul(x_.astype(out_dtype), wf)
        return _op(impl, x, w_int8, w_scale, op_name="quantized_matmul")

    def impl(x_, w_, ws, xs):
        xq = jnp.clip(jnp.round(x_ / xs * qmax), -qmax - 1, qmax
                      ).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, w_, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(out_dtype) * (xs / qmax) * (ws / qmax)
    return _op(impl, x, w_int8, w_scale, x_scale,
               op_name="quantized_matmul_int8")
