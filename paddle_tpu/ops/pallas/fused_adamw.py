"""Fused AdamW update — one Pallas kernel per parameter.

TPU analog of the reference's fused/multi-tensor Adam kernels (ref:
/root/reference/paddle/phi/kernels/gpu/adamw_kernel.cu and the
multi_tensor_adam path paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu):
the whole update (moment EMA, bias correction, decoupled weight decay,
master-weight write, dtype cast-down) is one read and one write per buffer
— no intermediate HBM traffic between the update's elementwise stages.

Scalars (lr, beta1, beta2, eps, wd, step) arrive via scalar prefetch so
one compiled kernel serves every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu

_LANES = 1024  # flattened row width (multiple of the 128-lane tile)


def _adamw_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, master_ref,
                  newp_ref, newm_ref, newv_ref, newmaster_ref):
    # bias corrections (1 - beta^step) are precomputed host/XLA-side:
    # a pow inside the kernel is pointless per-block scalar work
    lr = scal_ref[0]
    b1 = scal_ref[1]
    b2 = scal_ref[2]
    eps = scal_ref[3]
    wd = scal_ref[4]
    bc1 = scal_ref[5]
    bc2 = scal_ref[6]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    master = master_ref[...]
    upd = mhat / (jnp.sqrt(vhat) + eps) + wd * master
    new_master = master - lr * upd
    newm_ref[...] = m
    newv_ref[...] = v
    newmaster_ref[...] = new_master
    newp_ref[...] = new_master.astype(newp_ref.dtype)


def fused_adamw_update(p, g, m, v, master, lr, beta1, beta2, eps, wd,
                       step, block_rows=128):
    # 9 row-blocks (5 in + 4 out) live in VMEM: 9 * 128 * 1024 * 4B ≈ 4.7MB
    """One fused AdamW step. p: any shape/dtype; g same shape; m/v/master
    fp32. Returns (new_p, new_m, new_v, new_master)."""
    shape, dtype = p.shape, p.dtype
    n = p.size
    pad = (-n) % _LANES

    def flat(a, dt):
        a = a.reshape(-1).astype(dt)
        if pad:
            a = jnp.concatenate([a, jnp.zeros((pad,), dt)])
        return a.reshape(-1, _LANES)

    p2 = flat(p, dtype)
    g2 = flat(g, g.dtype)
    m2, v2, ma2 = (flat(a, jnp.float32) for a in (m, v, master))
    rows = p2.shape[0]
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    step_f = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - jnp.asarray(beta1, jnp.float32) ** step_f
    bc2 = 1.0 - jnp.asarray(beta2, jnp.float32) ** step_f
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(beta1, jnp.float32),
                      jnp.asarray(beta2, jnp.float32),
                      jnp.asarray(eps, jnp.float32),
                      jnp.asarray(wd, jnp.float32), bc1, bc2])

    spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    f32 = functools.partial(jax.ShapeDtypeStruct, p2.shape)
    if on_tpu():
        # PrefetchScalarGridSpec index maps receive the scalar refs as
        # trailing args after the grid indices
        pspec = pl.BlockSpec((br, _LANES), lambda i, s: (i, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // br,),
            in_specs=[pspec] * 5,
            out_specs=[pspec] * 4,
        )
        outs = pl.pallas_call(
            _adamw_kernel,
            grid_spec=grid_spec,
            out_shape=[f32(dtype), f32(jnp.float32), f32(jnp.float32),
                       f32(jnp.float32)],
        )(scal, p2, g2, m2, v2, ma2)
    else:
        # the CPU branch (written when interpret mode had no scalar
        # prefetch): emulate with a
        # full-array scalar ref
        sspec = pl.BlockSpec((7,), lambda i: (0,))
        outs = pl.pallas_call(
            _adamw_kernel,
            grid=(rows // br,),
            in_specs=[sspec] + [spec] * 5,
            out_specs=[spec] * 4,
            out_shape=[f32(dtype), f32(jnp.float32), f32(jnp.float32),
                       f32(jnp.float32)],
            interpret=True,
        )(scal, p2, g2, m2, v2, ma2)

    def unflat(a):
        a = a.reshape(-1)
        if pad:
            a = a[:n]
        return a.reshape(shape)

    new_p, new_m, new_v, new_master = outs
    return (unflat(new_p), unflat(new_m), unflat(new_v),
            unflat(new_master))
