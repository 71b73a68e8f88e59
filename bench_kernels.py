"""Micro-benchmarks: pallas fused kernels vs the jnp/XLA path.

Run on TPU: `python bench_kernels.py`. Prints one JSON line per kernel
with the speedup vs the unfused jnp implementation. (The driver-run
headline bench stays in bench.py; this file is the per-kernel evidence.)

Each timed region ends in a host read of its result.
"""
from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        r = fn(*args)
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    _sync(r)
    return (time.perf_counter() - t0) / iters


def _sync(r):
    leaves = jax.tree_util.tree_leaves(r)
    for leaf in leaves[:1]:
        float(jnp.sum(leaf.astype(jnp.float32)))


def bench_fused_rms(B=8, T=2048, H=4096, dtype=jnp.bfloat16):
    from paddle_tpu.ops.pallas.fused_norm import fused_rms_norm_residual
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, T, H)), dtype)
    r = jnp.asarray(rng.standard_normal((B, T, H)), dtype)
    w = jnp.asarray(rng.standard_normal((H,)), dtype)

    @jax.jit
    def jnp_path(x, r, w):
        z = x + r
        z32 = z.astype(jnp.float32)
        y = z32 * jax.lax.rsqrt(jnp.mean(z32 * z32, -1, keepdims=True)
                                + 1e-6)
        return (y * w.astype(jnp.float32)).astype(x.dtype), z

    fused = jax.jit(lambda x, r, w: fused_rms_norm_residual(x, r, w))
    t_jnp = _timeit(jnp_path, x, r, w)
    t_fused = _timeit(fused, x, r, w)
    return {"kernel": "fused_rms_norm_residual",
            "jnp_ms": round(t_jnp * 1e3, 4),
            "pallas_ms": round(t_fused * 1e3, 4),
            "speedup": round(t_jnp / t_fused, 3)}


def bench_fused_adamw(n=4096 * 4096):
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.standard_normal((n,)), jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal((n,)), jnp.bfloat16)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    master = p.astype(jnp.float32)

    @jax.jit
    def jnp_path(p, g, m, v, master):
        g32 = g.astype(jnp.float32)
        m2 = 0.9 * m + 0.1 * g32
        v2 = 0.95 * v + 0.05 * g32 * g32
        upd = (m2 / (1 - 0.9 ** 7)) / (jnp.sqrt(v2 / (1 - 0.95 ** 7))
                                       + 1e-8) + 0.1 * master
        ma = master - 1e-3 * upd
        return ma.astype(p.dtype), m2, v2, ma

    fused = jax.jit(lambda p, g, m, v, ma: fused_adamw_update(
        p, g, m, v, ma, 1e-3, 0.9, 0.95, 1e-8, 0.1, 7.0))
    t_jnp = _timeit(jnp_path, p, g, m, v, master)
    t_fused = _timeit(fused, p, g, m, v, master)
    return {"kernel": "fused_adamw", "jnp_ms": round(t_jnp * 1e3, 4),
            "pallas_ms": round(t_fused * 1e3, 4),
            "speedup": round(t_jnp / t_fused, 3)}


def bench_gmm(E=8, K=4096, N=4096, rows_per_e=512):
    from paddle_tpu.ops.pallas.grouped_gemm import (gmm, gmm_reference,
                                                    make_group_metadata)
    rng = np.random.default_rng(0)
    sizes = [rows_per_e] * E
    _, block_expert, M = make_group_metadata(sizes, block_m=128)
    lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((E, K, N)), jnp.bfloat16)
    be = jnp.asarray(block_expert)
    fused = jax.jit(functools.partial(gmm, block_m=128, block_n=512,
                                      block_k=512))
    ref = jax.jit(functools.partial(gmm_reference, block_m=128))
    t_ref = _timeit(ref, lhs, rhs, be)
    t_fused = _timeit(fused, lhs, rhs, be)
    return {"kernel": "grouped_gemm", "jnp_ms": round(t_ref * 1e3, 4),
            "pallas_ms": round(t_fused * 1e3, 4),
            "speedup": round(t_ref / t_fused, 3)}


def bench_decode(B=8, S=2048, nh=32, nkv=8, hd=128):
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention, decode_attention_reference)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), jnp.bfloat16)
    lens = jnp.asarray(rng.integers(S // 2, S, (B,)), jnp.int32)
    fused = jax.jit(decode_attention)
    ref = jax.jit(decode_attention_reference)
    t_ref = _timeit(ref, q, kc, vc, lens)
    t_fused = _timeit(fused, q, kc, vc, lens)
    return {"kernel": "decode_attention", "jnp_ms": round(t_ref * 1e3, 4),
            "pallas_ms": round(t_fused * 1e3, 4),
            "speedup": round(t_ref / t_fused, 3)}


def bench_paged_ragged(nh=32, nkv=8, hd=128, bs=16, MB=32, NB=512,
                       n_dec=8, K=4, n_ver=2, n_pre=2, C=128):
    """ONE ragged launch vs the 3-kernel dispatch pattern at EQUAL
    work: a mixed serving batch (n_dec decode rows + n_ver speculative
    verifies of K+1 rows + n_pre prefill chunks of C rows) scored by
    one ``paged_attention_ragged`` call vs one per-phase call each
    (the pre-unification pattern: decode + multi + prefill = 3
    dispatches; a real mixed step paid one per CHUNK, so 3 is the
    baseline's best case). Reports tokens/s and the dispatch counts."""
    import importlib
    # the pallas package re-exports the function under the module's
    # name, so attribute-style import would shadow the module
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((NB, 2, nkv, bs, hd)),
                       jnp.bfloat16)
    n_seq = n_dec + n_ver + n_pre
    bt = jnp.asarray(rng.integers(1, NB, (n_seq, MB)), jnp.int32)
    q_lens = (1,) * n_dec + (K + 1,) * n_ver + (C,) * n_pre
    kv_lens = np.concatenate([
        rng.integers(MB * bs // 2, MB * bs, n_dec),
        rng.integers(K + 1, MB * bs, n_ver),
        rng.integers(C, MB * bs, n_pre)]).astype(np.int32)
    R = sum(q_lens)
    q = jnp.asarray(rng.standard_normal((R, nh, hd)), jnp.bfloat16)
    lens = jnp.asarray(kv_lens)

    ragged = jax.jit(functools.partial(
        pa.paged_attention_ragged, q_lens=q_lens, tile_q=None))

    def one_launch(q, pool, bt, lens):
        return ragged(q, pool, bt, kv_lens=lens)

    d_hi = n_dec + n_ver * (K + 1)

    @jax.jit
    def three_launches(q, pool, bt, lens):
        dec = pa.paged_attention(q[:n_dec], pool, bt[:n_dec],
                                 lens[:n_dec])
        ver = pa.paged_attention_multi(
            q[n_dec:d_hi].reshape(n_ver, K + 1, nh, hd), pool,
            bt[n_dec:n_dec + n_ver], lens[n_dec:n_dec + n_ver])
        pre = pa.paged_attention_prefill(
            q[d_hi:].reshape(n_pre, C, nh, hd), pool,
            bt[n_dec + n_ver:], lens[n_dec + n_ver:] - C)
        return dec, ver, pre

    t_three = _timeit(three_launches, q, pool, bt, lens)
    t_one = _timeit(one_launch, q, pool, bt, lens)
    return {"kernel": "paged_attention_ragged",
            "mixed_batch": {"decode_rows": n_dec,
                            "verify_rows": n_ver * (K + 1),
                            "prefill_rows": n_pre * C},
            "dispatches": {"ragged": 1, "three_kernel": 3},
            "three_kernel_ms": round(t_three * 1e3, 4),
            "ragged_ms": round(t_one * 1e3, 4),
            "tokens_per_sec_ragged": round(R / t_one, 1),
            "speedup": round(t_three / t_one, 3)}


if __name__ == "__main__":
    for bench in (bench_fused_rms, bench_fused_adamw, bench_gmm,
                  bench_decode, bench_paged_ragged):
        try:
            print(json.dumps(bench()))
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": bench.__name__,
                              "error": str(e)[:200]}))
