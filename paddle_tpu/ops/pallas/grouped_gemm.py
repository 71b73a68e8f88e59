"""Grouped GEMM for MoE experts (Pallas).

TPU analog of the reference's cutlass grouped-GEMM MoE kernel (ref:
/root/reference/paddle/phi/kernels/fusion/cutlass/moe_kernel.cu and
moe/moe_kernel_impl.h): tokens sorted by expert, each expert's row-slice
multiplied by its own weight matrix, without materializing a dense
[E, tokens, ...] tensor.

Layout contract (the megablox convention): callers pad each expert's
token group to a multiple of `block_m` (make_group_metadata does this),
so every m-block belongs to exactly ONE expert; the per-block expert id
arrives via scalar prefetch and drives the rhs BlockSpec index map —
weights for expert e stream into VMEM only for e's blocks.

For the fixed-capacity GShard dispatch (incubate/moe.py) a plain batched
einsum is already MXU-optimal; this kernel is for variable-size
(dropless) grouping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu


def _gmm_kernel(block_expert_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                k_steps):
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == k_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def gmm(lhs, rhs, block_expert, block_m=128, block_n=128, block_k=128):
    """lhs: [M, K] tokens grouped by expert and padded so each block_m
    rows share one expert. rhs: [E, K, N] expert weights. block_expert:
    int32 [M // block_m] expert id per m-block. Returns [M, N]."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2 and M % block_m == 0
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    while N % block_n:
        block_n //= 2
    while K % block_k:
        block_k //= 2
    grid = (M // block_m, N // block_n, K // block_k)
    k_steps = grid[2]

    kernel = functools.partial(_gmm_kernel, k_steps=k_steps)
    # PrefetchScalarGridSpec passes scalar refs AFTER the grid indices
    lhs_spec = pl.BlockSpec((block_m, block_k), lambda m, n, k, be: (m, k))
    rhs_spec = pl.BlockSpec(
        (1, block_k, block_n), lambda m, n, k, be: (be[m], k, n))
    out_spec = pl.BlockSpec((block_m, block_n),
                            lambda m, n, k, be: (m, n))
    out_shape = jax.ShapeDtypeStruct((M, N), lhs.dtype)

    if not on_tpu():
        # the CPU branch (written when interpret mode had no scalar
        # prefetch; a different kernel body from _gmm_kernel): emulate
        # the block->expert indirection by pre-gathering rhs per
        # m-block (test path only; jnp gather keeps this traceable
        # under jit)
        rhs_g = rhs[jnp.asarray(block_expert)]  # [M/bm, K, N]
        def kern(l_ref, r_ref, o_ref, acc_ref, *, k_steps):
            k_i = pl.program_id(2)
            @pl.when(k_i == 0)
            def _init():
                acc_ref[...] = jnp.zeros_like(acc_ref)
            acc_ref[...] += jax.lax.dot_general(
                l_ref[...], r_ref[0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            @pl.when(k_i == k_steps - 1)
            def _done():
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        return pl.pallas_call(
            functools.partial(kern, k_steps=k_steps),
            grid=grid,
            in_specs=[pl.BlockSpec((block_m, block_k),
                                   lambda m, n, k: (m, k)),
                      pl.BlockSpec((1, block_k, block_n),
                                   lambda m, n, k: (m, k, n))],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda m, n, k: (m, n)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
            interpret=True,
        )(lhs, rhs_g)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[lhs_spec, rhs_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=out_shape)(
        jnp.asarray(block_expert, jnp.int32), lhs, rhs)


def make_group_metadata(group_sizes, block_m=128):
    """Host-side helper: given per-expert token counts, produce
    (padded_offsets, block_expert, padded_total) for the gmm layout —
    each expert's rows start at a block_m multiple."""
    sizes = np.asarray(group_sizes)
    padded = ((sizes + block_m - 1) // block_m) * block_m
    offsets = np.concatenate([[0], np.cumsum(padded)])
    block_expert = np.repeat(np.arange(len(sizes)), padded // block_m)
    return offsets, block_expert.astype(np.int32), int(offsets[-1])


def gmm_reference(lhs, rhs, block_expert, block_m=128):
    """jnp reference used by tests/micro-bench."""
    be = jnp.asarray(block_expert)
    blocks = lhs.reshape(-1, block_m, lhs.shape[-1])
    out = jnp.einsum("bmk,bkn->bmn", blocks, rhs[be],
                     preferred_element_type=jnp.float32)
    return out.reshape(lhs.shape[0], rhs.shape[-1]).astype(lhs.dtype)
