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


def _gmm_kernel(block_expert_ref, used_ref, lhs_ref, rhs_ref, out_ref,
                acc_ref, *, k_steps):
    del block_expert_ref                      # feeds the index maps only
    m, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(m < used_ref[0])
    def _product():
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


def gmm(lhs, rhs, block_expert, block_m=128, block_n=128, block_k=128,
        blocks_used=None):
    """lhs: [M, K] tokens grouped by expert and padded so each block_m
    rows share one expert. rhs: [E, K, N] expert weights. block_expert:
    int32 [M // block_m] expert id per m-block. Returns [M, N]
    (float32 accumulation, ``lhs.dtype`` out).

    ``blocks_used`` (int32 [1], may be traced) is for a serving step
    whose row count per expert is known only on the device: ``lhs`` is
    then laid out for the WORST case, only its first ``blocks_used``
    m-blocks hold rows, and ``block_expert`` repeats the last real
    block's expert past them. The unused tail costs no copy and no
    product (its index maps name the blocks the pipeline already holds)
    and its rows come back unwritten, whatever the buffer held.

    The grid is (N / block_n, M / block_m, K / block_k): with the
    n-blocks outermost and ``block_k`` the whole of K, consecutive
    m-blocks of one expert name the same weight block, so every expert
    that holds a row has its weights read once a launch and an expert
    without rows never; ``lhs`` is read once an n-block."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2 and M % block_m == 0, (lhs.shape, rhs.shape, block_m)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    while N % block_n:
        block_n //= 2
    while K % block_k:
        block_k //= 2
    m_blocks, k_steps = M // block_m, K // block_k
    if blocks_used is None:
        blocks_used = m_blocks
    used = jnp.asarray(blocks_used, jnp.int32).reshape(1)

    # PrefetchScalarGridSpec passes scalar refs AFTER the grid indices.
    # Past the used blocks every map holds the last block it loaded.
    def live_m(m, used_):
        return jnp.minimum(m, jnp.maximum(used_[0] - 1, 0))

    def live_k(m, k, used_):
        return jnp.where(m < used_[0], k, k_steps - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // block_n, m_blocks, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda n, m, k, be, u: (live_m(m, u),
                                                 live_k(m, k, u))),
            pl.BlockSpec((1, block_k, block_n),
                         lambda n, m, k, be, u: (be[m], live_k(m, k, u),
                                                 n))],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda n, m, k, be, u: (m, n)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=not on_tpu(),    # off the chip: the same body, interpreted
        name="gmm",
    )(jnp.asarray(block_expert, jnp.int32), used, lhs, rhs)


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
