"""Fused RMSNorm / LayerNorm + residual Pallas kernels.

TPU analog of the reference's fused_layernorm_residual_dropout_bias CUDA
kernels (ref: /root/reference/paddle/phi/kernels/fusion/gpu/
fused_layernorm_residual_dropout_bias.h and fused/fused_dropout_helper.h):
one HBM pass computes residual-add + normalization (+ scale) instead of
separate elementwise kernels. Backward is jnp math under custom_vjp
(bandwidth-bound elementwise that XLA fuses; the fwd fusion is where the
extra HBM pass is saved).

All kernels run in interpret mode on CPU (tests) and compile via Mosaic
on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu


def _rms_fwd_kernel(x_ref, res_ref, w_ref, y_ref, newres_ref, *, eps,
                    has_residual):
    x = x_ref[...].astype(jnp.float32)
    if has_residual:
        x = x + res_ref[...].astype(jnp.float32)
        newres_ref[...] = x.astype(newres_ref.dtype)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    w = w_ref[...].astype(jnp.float32)
    y_ref[...] = (x * rstd * w).astype(y_ref.dtype)


def _rms_fwd_kernel_nores(x_ref, w_ref, y_ref, *, eps):
    # no residual: no res read, no newres write — one read + one write
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    w = w_ref[...].astype(jnp.float32)
    y_ref[...] = (x * rstd * w).astype(y_ref.dtype)


def _ln_fwd_kernel(x_ref, res_ref, w_ref, b_ref, y_ref, newres_ref, *,
                   eps, has_residual):
    x = x_ref[...].astype(jnp.float32)
    if has_residual:
        x = x + res_ref[...].astype(jnp.float32)
        newres_ref[...] = x.astype(newres_ref.dtype)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xc * rstd * w + b).astype(y_ref.dtype)


def _ln_fwd_kernel_nores(x_ref, w_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xc * rstd * w + b).astype(y_ref.dtype)


def _row_grid(x, block_rows=None):
    rows, h = x.shape
    if block_rows is None:
        # 4 row-blocks (x, res, y, newres) live in VMEM at once; budget
        # ~8MB fp32 so large-H models don't blow the ~16MB VMEM
        block_rows = max(8, min(256, (2 * 1024 * 1024) // max(h * 4, 1)))
    br = min(block_rows, rows)
    # Mosaic needs the sublane dim divisible by 8 (or the full array):
    # search downward in multiples of 8 for a divisor of rows
    br -= br % 8
    while br >= 8 and rows % br:
        br -= 8
    if br < 8:
        br = rows  # full-array block is always legal
    return rows // br, br, h


def _rms_fwd(x, residual, w, eps):
    orig_shape = x.shape
    h = orig_shape[-1]
    x2 = x.reshape(-1, h)
    has_res = residual is not None
    n_blocks, br, _ = _row_grid(x2)
    spec = pl.BlockSpec((br, h), lambda i: (i, 0))
    wspec = pl.BlockSpec((h,), lambda i: (0,))
    if not has_res:
        y = pl.pallas_call(
            functools.partial(_rms_fwd_kernel_nores, eps=eps),
            grid=(n_blocks,),
            in_specs=[spec, wspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            interpret=not on_tpu(),
        )(x2, w)
        return y.reshape(orig_shape), x
    r2 = residual.reshape(-1, h)
    y, newres = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps, has_residual=True),
        grid=(n_blocks,),
        in_specs=[spec, spec, wspec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(x2.shape, x.dtype)],
        interpret=not on_tpu(),
    )(x2, r2, w)
    return y.reshape(orig_shape), newres.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rms_core(x, residual, w, eps):
    return _rms_fwd(x, residual, w, eps)


def _rms_core_fwd(x, residual, w, eps):
    y, newres = _rms_fwd(x, residual, w, eps)
    return (y, newres), (newres, w)


def _rms_core_bwd(eps, saved, grads):
    z, w = saved  # z = x + residual (the normalized input)
    gy, gres = grads
    z32 = z.astype(jnp.float32)
    gy32 = gy.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    ms = jnp.mean(z32 * z32, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = z32 * rstd
    gw = (gy32 * xhat).sum(tuple(range(z32.ndim - 1)))
    gxhat = gy32 * w32
    h = z32.shape[-1]
    gz = rstd * (gxhat - xhat * jnp.mean(gxhat * xhat, axis=-1,
                                         keepdims=True))
    gz = gz + (0.0 if gres is None else gres.astype(jnp.float32))
    gz = gz.astype(z.dtype)
    return gz, gz, gw.astype(w.dtype)


_rms_core.defvjp(_rms_core_fwd, _rms_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_nores(x, w, eps):
    return _rms_fwd(x, None, w, eps)[0]


def _rms_nores_fwd(x, w, eps):
    return _rms_fwd(x, None, w, eps)[0], (x, w)


def _rms_nores_bwd(eps, saved, gy):
    gz, _, gw = _rms_core_bwd(eps, saved, (gy, None))
    return gz, gw


_rms_nores.defvjp(_rms_nores_fwd, _rms_nores_bwd)


def fused_rms_norm(x, w, eps=1e-6):
    """y = x / sqrt(mean(x^2) + eps) * w — one read + one write."""
    return _rms_nores(x, w, float(eps))


def fused_rms_norm_residual(x, residual, w, eps=1e-6):
    """z = x + residual; y = rmsnorm(z) * w. Returns (y, z) — z feeds the
    next residual branch (the fused_layernorm_residual pattern)."""
    return _rms_core(x, residual, w, float(eps))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ln_core(x, residual, w, b, eps):
    return _ln_fwd_call(x, residual, w, b, eps)


def _ln_fwd_call(x, residual, w, b, eps):
    orig_shape = x.shape
    h = orig_shape[-1]
    x2 = x.reshape(-1, h)
    n_blocks, br, _ = _row_grid(x2)
    spec = pl.BlockSpec((br, h), lambda i: (i, 0))
    wspec = pl.BlockSpec((h,), lambda i: (0,))
    if residual is None:
        y = pl.pallas_call(
            functools.partial(_ln_fwd_kernel_nores, eps=eps),
            grid=(n_blocks,),
            in_specs=[spec, wspec, wspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            interpret=not on_tpu(),
        )(x2, w, b)
        return y.reshape(orig_shape), x
    r2 = residual.reshape(-1, h)
    y, newres = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps, has_residual=True),
        grid=(n_blocks,),
        in_specs=[spec, spec, wspec, wspec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(x2.shape, x.dtype)],
        interpret=not on_tpu(),
    )(x2, r2, w, b)
    return y.reshape(orig_shape), newres.reshape(orig_shape)


def _ln_core_fwd(x, residual, w, b, eps):
    y, newres = _ln_fwd_call(x, residual, w, b, eps)
    return (y, newres), (newres, w)


def _ln_core_bwd(eps, saved, grads):
    z, w = saved
    gy, gres = grads
    z32 = z.astype(jnp.float32)
    gy32 = gy.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    mu = jnp.mean(z32, axis=-1, keepdims=True)
    xc = z32 - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    red = tuple(range(z32.ndim - 1))
    gw = (gy32 * xhat).sum(red)
    gb = gy32.sum(red)
    gxhat = gy32 * w32
    gz = rstd * (gxhat - jnp.mean(gxhat, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gxhat * xhat, axis=-1, keepdims=True))
    gz = gz + (0.0 if gres is None else gres.astype(jnp.float32))
    gz = gz.astype(z.dtype)
    return gz, gz, gw.astype(w.dtype), gb.astype(w.dtype)


_ln_core.defvjp(_ln_core_fwd, _ln_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_nores(x, w, b, eps):
    return _ln_fwd_call(x, None, w, b, eps)[0]


def _ln_nores_fwd(x, w, b, eps):
    return _ln_fwd_call(x, None, w, b, eps)[0], (x, w)


def _ln_nores_bwd(eps, saved, gy):
    gz, _, gw, gb = _ln_core_bwd(eps, saved, (gy, None))
    return gz, gw, gb


_ln_nores.defvjp(_ln_nores_fwd, _ln_nores_bwd)


def fused_layer_norm(x, w, b, eps=1e-5):
    return _ln_nores(x, w, b, float(eps))


def fused_layer_norm_residual(x, residual, w, b, eps=1e-5):
    """z = x + residual; y = layernorm(z) * w + b. Returns (y, z)."""
    return _ln_core(x, residual, w, b, float(eps))


# -- dropout-fused variants (ref fused_layernorm_residual_dropout_bias.h:
# the CUDA kernel applies dropout to x BEFORE the residual add + norm) ---

def _dropout_kernel(seed_ref, x_ref, o_ref, *, rate):
    i = pl.program_id(0)
    pltpu.prng_seed(seed_ref[0] + i)  # distinct stream per row-block
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.int32)
    # uniform in [0, 1): low 24 bits are non-negative in int32 (Mosaic
    # has no uint32->f32 cast)
    u = (bits & 0xFFFFFF).astype(jnp.float32) * (1.0 / (1 << 24))
    keep = (u >= rate).astype(jnp.float32)
    o_ref[...] = (x_ref[...].astype(jnp.float32) * keep
                  / (1.0 - rate)).astype(o_ref.dtype)


def _fused_dropout(x, rate, seed):
    """One-pass inverted dropout with the on-core PRNG."""
    orig_shape = x.shape
    h = orig_shape[-1]
    x2 = x.reshape(-1, h)
    rows = x2.shape[0]
    pad = (-rows) % 8
    if pad:
        # Mosaic sublane rule: pad rows to a multiple of 8 rather than
        # fall into a whole-array block (VMEM blowup for odd big rows)
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n_blocks, br, _ = _row_grid(x2)
    spec = pl.BlockSpec((br, h), lambda i: (i, 0))
    if not on_tpu():
        # interpret mode has no TPU PRNG: jax.random path, same contract
        import jax.random as jrandom
        keep = (jrandom.uniform(jrandom.PRNGKey(seed), x2.shape)
                >= rate).astype(x2.dtype)
        out = x2 * keep / (1.0 - rate)
        return out[:rows].reshape(orig_shape)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_dropout_kernel, rate=float(rate)),
        grid=(n_blocks,),
        in_specs=[sspec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
    )(jnp.asarray([seed], jnp.int32), x2)
    return out[:rows].reshape(orig_shape)


def fused_rms_norm_residual_dropout(x, residual, w, eps=1e-6,
                                    dropout_rate=0.0, seed=0):
    """z = dropout(x) + residual; y = rmsnorm(z) * w — the reference's
    fused_layernorm_residual_dropout pattern with RMS normalization.
    Dropout uses the on-core TPU PRNG (pltpu.prng_random_bits); backward
    treats the dropout mask as part of the saved z (exact, since
    z = dropout(x) + residual is what the vjp differentiates through)."""
    if dropout_rate > 0.0:
        x = _dropout_via_vjp(x, dropout_rate, seed)
    return _rms_core(x, residual, w, float(eps))


def fused_layer_norm_residual_dropout(x, residual, w, b, eps=1e-5,
                                      dropout_rate=0.0, seed=0):
    """z = dropout(x) + residual; y = layernorm(z) * w + b (ref
    fused_layernorm_residual_dropout_bias.h)."""
    if dropout_rate > 0.0:
        x = _dropout_via_vjp(x, dropout_rate, seed)
    return _ln_core(x, residual, w, b, float(eps))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _dropout_via_vjp(x, rate, seed):
    # seed rides as a DIFFERENTIABLE-position arg (float0 cotangent):
    # nondiff_argnums must never receive traced values, and per-step
    # seeds are traced under jit
    return _fused_dropout(x, rate, seed)


def _dropout_fwd(x, rate, seed):
    return _fused_dropout(x, rate, seed), seed


def _dropout_bwd(rate, seed, gy):
    # inverted dropout is elementwise-linear: the cotangent is the SAME
    # kernel applied to gy (the PRNG is deterministic per (seed, shape),
    # so the mask regenerates exactly — no saved HBM buffer, one pass)
    import numpy as _np
    return (_fused_dropout(gy, rate, seed),
            _np.zeros(_np.shape(seed), jax.dtypes.float0))


_dropout_via_vjp.defvjp(_dropout_fwd, _dropout_bwd)
