"""Common functionals: linear / dropout / embedding / interpolate / etc.
(ref: /root/reference/python/paddle/nn/functional/common.py, input.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import random as _random
from ...framework.dtype import convert_dtype, get_default_dtype
from ...framework.op import apply, unwrap
from ...framework.tensor import Tensor
from ...ops._helpers import op
from ...ops.manipulation import pad as _pad_op

__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "alpha_dropout", "pad",
    "interpolate", "upsample", "bilinear", "cosine_similarity", "embedding",
    "one_hot", "label_smooth", "fold", "unfold", "zeropad2d",
]


def linear(x, weight, bias=None, name=None):
    """y = x @ W (+ b); weight layout [in, out] as in the reference
    (ref: python/paddle/nn/functional/common.py linear)."""
    if bias is None:
        return op("linear", lambda a, w: a @ w, x, weight)
    return op("linear", lambda a, w, b: a @ w + b, x, weight, bias)


def _tpu_dropout_ok():
    from ...flags import get_flag
    from ...framework.device import on_tpu
    return bool(get_flag("FLAGS_tpu_fused_dropout", True)) and on_tpu()


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return op("dropout", lambda a: a * (1.0 - p), x)
        return x
    if isinstance(p, Tensor):
        p = float(p.numpy())
    key = _random.next_key()
    if (axis is None and mode == "upscale_in_train" and 0.0 < p < 1.0
            and _tpu_dropout_ok()):
        # p >= 1.0 falls through to the jnp path (all-zeros; the kernel
        # would compute 0/0)
        # one-pass Pallas dropout with the on-core TPU PRNG: the kernel
        # generates the mask in-core instead of a threefry bernoulli
        def impl_fused(a, k):
            from ...ops.pallas.fused_norm import _dropout_via_vjp
            seed = jax.random.randint(k, (), 0, 2 ** 31 - 1)
            return _dropout_via_vjp(a, float(p), seed)
        return op("dropout", impl_fused, x, key)

    def impl(a):
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in [ax % a.ndim for ax in axes] else 1
                     for i, s in enumerate(a.shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)
    return op("dropout", impl, x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    if not training or p == 0.0:
        return x
    key = _random.next_key()
    def impl(a):
        shape = (a.shape[0], a.shape[1], 1, 1) if data_format == "NCHW" \
            else (a.shape[0], 1, 1, a.shape[3])
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
    return op("dropout2d", impl, x)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    if not training or p == 0.0:
        return x
    key = _random.next_key()
    def impl(a):
        shape = (a.shape[0], a.shape[1], 1, 1, 1) if data_format == "NCDHW" \
            else (a.shape[0], 1, 1, 1, a.shape[4])
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
    return op("dropout3d", impl, x)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    key = _random.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    def impl(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        coef_a = ((1 - p) * (1 + p * alpha_p ** 2)) ** -0.5
        coef_b = -coef_a * p * alpha_p
        return (coef_a * jnp.where(keep, a, alpha_p) + coef_b).astype(a.dtype)
    return op("alpha_dropout", impl, x)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    return _pad_op(x, pad, mode=mode, value=value, data_format=data_format)


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return _pad_op(x, padding, mode="constant", value=0.0,
                   data_format=data_format)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    in_shape = tuple(x.shape) if isinstance(x, Tensor) else unwrap(x).shape
    spatial_ndim = len(in_shape) - 2
    if data_format.startswith("N") and data_format[1] == "C":
        spatial = in_shape[2:]
        channel_last = False
    else:
        spatial = in_shape[1:-1]
        channel_last = True
    if size is not None:
        if isinstance(size, Tensor):
            size = size.numpy().tolist()
        out_size = tuple(int(unwrap(s)) if isinstance(s, Tensor) else int(s)
                         for s in (size if isinstance(size, (list, tuple)) else [size]))
    else:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * spatial_ndim
        out_size = tuple(int(s * f) for s, f in zip(spatial, scale_factor))

    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def impl(arr):
        if channel_last:
            target = (arr.shape[0],) + out_size + (arr.shape[-1],)
        else:
            target = arr.shape[:2] + out_size
        if jmode == "nearest":
            return jax.image.resize(arr, target, method="nearest")
        if align_corners and jmode == "linear":
            # jax.image.resize uses half-pixel centers; emulate align_corners
            # with explicit gather-based linear interp per spatial dim
            return _resize_align_corners(arr, target, channel_last)
        return jax.image.resize(arr, target, method=jmode)
    return op("interpolate", impl, x)


def _resize_align_corners(arr, target, channel_last):
    out = arr
    sp_start = 1 if channel_last else 2
    sp_end = out.ndim - 1 if channel_last else out.ndim
    for d in range(sp_start, sp_end):
        in_n, out_n = out.shape[d], target[d]
        if in_n == out_n:
            continue
        if out_n == 1 or in_n == 1:
            idx = jnp.zeros(out_n, jnp.int32)
            out = jnp.take(out, idx, axis=d)
            continue
        pos = jnp.arange(out_n) * (in_n - 1) / (out_n - 1)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, in_n - 1)
        w = (pos - lo).astype(out.dtype)
        shape = [1] * out.ndim
        shape[d] = out_n
        w = w.reshape(shape)
        out = jnp.take(out, lo, axis=d) * (1 - w) + jnp.take(out, hi, axis=d) * w
    return out


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def bilinear(x1, x2, weight, bias=None, name=None):
    def impl(a, b, w, *rest):
        out = jnp.einsum("bn,knm,bm->bk", a, w, b)
        if rest:
            out = out + rest[0]
        return out
    if bias is not None:
        return op("bilinear", impl, x1, x2, weight, bias)
    return op("bilinear", impl, x1, x2, weight)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def impl(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)
    return op("cosine_similarity", impl, x1, x2)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Lookup rows of `weight` (ref: python/paddle/nn/functional/input.py).
    padding_idx rows produce zero gradient."""
    def impl(idx, w):
        out = jnp.take(w, idx, axis=0)
        return out
    if padding_idx is not None:
        n_rows = weight.shape[0]
        if not -n_rows <= padding_idx < n_rows:
            raise ValueError(
                f"padding_idx must be within [-{n_rows}, {n_rows}), got "
                f"{padding_idx}")
        pi = padding_idx if padding_idx >= 0 else n_rows + padding_idx
        def impl(idx, w):  # noqa: F811
            # ref input.py embedding: ids equal to padding_idx produce
            # all-zero OUTPUT rows (hence also zero gradient into w[pi])
            out = jnp.take(w, idx, axis=0)
            return jnp.where((idx == pi)[..., None], jnp.zeros((), w.dtype),
                             out)
    return op("embedding", impl, x, weight)


def one_hot(x, num_classes, name=None):
    def impl(idx):
        return jax.nn.one_hot(idx, num_classes, dtype=get_default_dtype())
    return op("one_hot", impl, x)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def impl(l, *rest):
        k = l.shape[-1]
        if rest:
            return (1 - epsilon) * l + epsilon * rest[0]
        return (1 - epsilon) * l + epsilon / k
    if prior_dist is not None:
        return op("label_smooth", impl, label, prior_dist)
    return op("label_smooth", impl, label)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    from ...ops.manipulation import unfold as _unfold
    return _unfold(x, kernel_sizes, strides, paddings, dilations)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """col2im inverse of unfold. x: [N, C*kh*kw, L] -> [N, C, H, W]."""
    oh, ow = (output_sizes, output_sizes) if isinstance(output_sizes, int) \
        else output_sizes
    kh, kw = (kernel_sizes, kernel_sizes) if isinstance(kernel_sizes, int) \
        else kernel_sizes
    sh, sw = (strides, strides) if isinstance(strides, int) else strides
    dh, dw = (dilations, dilations) if isinstance(dilations, int) else dilations
    if isinstance(paddings, int):
        pt = pb = pl = pr = paddings
    elif len(paddings) == 2:
        pt = pb = paddings[0]
        pl = pr = paddings[1]
    else:
        pt, pl, pb, pr = paddings

    def impl(a):
        n, ckk, L = a.shape
        c = ckk // (kh * kw)
        ph, pw = oh + pt + pb, ow + pl + pr
        nh = (ph - (dh * (kh - 1) + 1)) // sh + 1
        nw = (pw - (dw * (kw - 1) + 1)) // sw + 1
        cols = a.reshape(n, c, kh, kw, nh, nw)
        out = jnp.zeros((n, c, ph, pw), a.dtype)
        for i in range(kh):
            for j in range(kw):
                hi = i * dh
                wj = j * dw
                out = out.at[:, :, hi:hi + nh * sh:sh,
                             wj:wj + nw * sw:sw].add(cols[:, :, i, j])
        return out[:, :, pt:pt + oh, pl:pl + ow]
    return op("fold", impl, x)
