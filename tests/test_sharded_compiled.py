"""Compiled collectives: one jitted shard_map program per sharded step.

The compiled path (inference/compiled_step.py) replaces the host-staged
per-shard loop of ShardedServingCore.forward with ONE jitted
shard_map(Mesh(("mp",))) program per mixed step: per-shard qkv +
per-shard paged attention inside the mapped body, exactly one
jax.lax.psum per layer (zero-padded disjoint head sums — IEEE-exact,
same addition order as the eager close), pools donated as head-sharded
NamedSharding operands and rebound zero-copy afterwards.

Tier-1 pytest runs on a single CPU device, where the compiled path
auto-disables (shard "devices" are not distinct), so every mesh test
here drives a subprocess with --xla_force_host_platform_device_count
(the tests/test_multiprocess_tp.py idiom;
--xla_cpu_parallel_codegen_split_count=1 pins the measured XLA-CPU
codegen nondeterminism source).
What the subprocesses prove, against the eager single-chip oracle of
tests/test_sharded.py's model:

 - bit-identical greedy streams across plain / speculative /
   token-budget+prefix / int8 serving, for BOTH the legacy host-staged
   path and the compiled path (and the compiled path never calls
   _allreduce — its per-layer psums live inside the program);
 - compile-cache discipline: bounded retraces over a long staggered
   mixed run, exactly num_layers psums per program, ONE dispatch per
   step;
 - mp=4 geometry on a real 4-device mesh; mp=4 logical-on-2 falls back
   to legacy (still exact) and refuses compiled_step=True;
 - snapshots and migration slices stay canonical full-head pages:
   mp2-compiled <-> mp1 crossovers replay bit-identically;
 - the ragged kernel delegates to its jnp reference inside an active
   shard_map region (interpret mode cannot host-transfer there).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.sharded

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Shared prelude: the deterministic serving model + engine driver of
# tests/test_sharded.py, inlined so each subprocess is self-contained.
_PRELUDE = textwrap.dedent("""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.fused_transformer import \\
        FusedMultiTransformer
    from paddle_tpu.inference import SpeculativeEngine, TokenServingModel

    D, H, FFN, LAYERS, VOCAB, BS = 32, 4, 64, 2, 50, 4
    PROMPTS = [list(range(5 + i, 12 + i)) for i in range(3)]

    def _tsm(seed=0):
        rng = np.random.RandomState(seed)
        m = FusedMultiTransformer(D, H, FFN, num_layers=LAYERS)
        for blk in m.layers:
            for name in ("qkv", "out_proj", "ffn1", "ffn2"):
                lin = getattr(blk, name)
                lin.weight.set_value(paddle.to_tensor(
                    (rng.randn(*lin.weight.shape) * 0.1)
                    .astype(np.float32)))
                lin.bias.set_value(paddle.to_tensor(
                    (rng.randn(*lin.bias.shape) * 0.01)
                    .astype(np.float32)))
        emb = (rng.randn(VOCAB, D) * 0.3).astype(np.float32)
        return TokenServingModel(m, emb,
                                 lm_head=np.roll(emb, -1, 0).T.copy())

    def _run(tsm, steps=8, **kw):
        cfg = dict(k=0, max_batch=3, block_size=BS, num_blocks=40)
        cfg.update(kw)
        eng = SpeculativeEngine(tsm, **cfg)
        rids = [eng.submit(p) for p in PROMPTS]
        for _ in range(steps):
            eng.step()
        return eng, {i: eng.tokens(r) for i, r in enumerate(rids)}

    import jax
""")


def _run_script(body, devices=2, timeout=420):
    """Run PRELUDE+body on a forced-N-device CPU client; require the
    ALL OK sentinel (an assert tripping in the child kills it)."""
    script = _PRELUDE + textwrap.dedent(body) + '\nprint("ALL OK")\n'
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": _REPO,
           "XLA_FLAGS": (f"--xla_force_host_platform_device_count="
                         f"{devices} "
                         "--xla_cpu_parallel_codegen_split_count=1")}
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT, timeout=timeout)
    out = r.stdout.decode()
    assert r.returncode == 0, out[-4000:]
    assert "ALL OK" in out, out[-4000:]
    return out


# ------------------------------------------------------- bit-identity
def test_compiled_bit_identity_all_modes():
    """Plain / spec / token-budget+prefix / int8: compiled mp=2 streams
    == legacy mp=2 streams == single-chip streams, and the compiled
    path never goes through the host-staged _allreduce."""
    _run_script("""
        assert len(jax.devices()) >= 2
        modes = [
            ("plain", {}),
            ("spec", dict(k=2)),
            ("budget", dict(k=2, prefill_token_budget=8,
                            prefix_cache=True)),
            ("int8", dict(kv_dtype="int8", prefix_cache=True)),
        ]
        for name, kw in modes:
            base = _run(_tsm(), **kw)[1]
            legacy = _run(_tsm().shard(2, compiled_step=False), **kw)[1]
            tsmc = _tsm().shard(2)
            assert tsmc.core.compiled_step, \\
                "compiled must auto-engage on 2 distinct devices"
            engc, comp = _run(tsmc, **kw)
            assert legacy == base, name
            assert comp == base, name
            m = tsmc.core.sharded_metrics()
            assert m["compiled"] and m["mp"] == 2, m
            assert m["allreduce_count"] == 0, \\
                "compiled path must not _allreduce"
            assert m["dispatches_per_step"] == 1, m
            assert m["psums_per_call"] == LAYERS, m
            engc.check_invariants()
    """)


# ---------------------------------------------- compile-cache discipline
def test_compiled_retrace_bound_mixed_run():
    """Staggered arrivals + spec decoding + budget-split prefills over
    25 steps: retraces stay bounded by the bucket count (static shapes
    only in the cache key), psum count per program == num_layers."""
    _run_script("""
        tsm = _tsm().shard(2)
        eng = SpeculativeEngine(tsm, k=2, max_batch=3, block_size=BS,
                                num_blocks=60, prefill_token_budget=8,
                                prefix_cache=True)
        rids = []
        for i in range(10):
            rids.append(eng.submit(
                [(7 * i + j) % (VOCAB - 1) for j in
                 range(5 + (i % 4))]))
            eng.step()
        for _ in range(15):
            eng.step()
        m = tsm.core.sharded_metrics()
        assert m["retraces"] <= 12, m
        assert m["psums_per_call"] == LAYERS, m
        assert m["dispatches_per_step"] == 1, m
        assert m["jit_calls"] >= 20, m
        eng.check_invariants()
    """)


# ------------------------------------------------------- mp=4 geometry
def test_compiled_mp4_real_mesh():
    _run_script("""
        assert len(jax.devices()) >= 4
        base = _run(_tsm())[1]
        t4 = _tsm().shard(4)
        assert t4.core.compiled_step
        _, toks = _run(t4)
        assert toks == base
        m = t4.core.sharded_metrics()
        assert m["mp"] == 4 and m["psums_per_call"] == LAYERS, m
    """, devices=4)


def test_mp4_logical_on_two_devices_falls_back_to_legacy():
    """mp=4 over 2 physical devices cycles shard placements — NOT
    fully distinct, so auto keeps the legacy host-staged path (still
    bit-identical) and forcing compiled_step=True refuses."""
    _run_script("""
        from paddle_tpu.inference import ShardedServingCore
        try:
            ShardedServingCore(_tsm().core, 4, compiled_step=True)
        except ValueError as e:
            assert "distinct" in str(e)
        else:
            raise SystemExit("mp=4 on 2 devices must refuse compiled")
        t4 = _tsm().shard(4)
        assert not t4.core.compiled_step
        base = _run(_tsm())[1]
        _, toks4 = _run(t4)
        assert toks4 == base
    """)


# ------------------------------------------- snapshots stay canonical
def test_compiled_snapshot_crossover_both_directions():
    _run_script("""
        kw = dict(k=2, prefix_cache=True)
        ref = _run(_tsm(), **kw)[1]

        e1 = SpeculativeEngine(_tsm().shard(2), max_batch=3,
                               block_size=BS, num_blocks=40, **kw)
        assert e1.target.core.compiled_step
        rids = [e1.submit(p) for p in PROMPTS]
        for _ in range(4):
            e1.step()
        snap = e1.snapshot()
        e2 = SpeculativeEngine.restore(_tsm(), None, snap)
        for _ in range(4):
            e2.step()
        assert {i: e2.tokens(r) for i, r in enumerate(rids)} == ref

        e1 = SpeculativeEngine(_tsm(), max_batch=3, block_size=BS,
                               num_blocks=40, **kw)
        rids = [e1.submit(p) for p in PROMPTS]
        for _ in range(4):
            e1.step()
        snap = e1.snapshot()
        e2 = SpeculativeEngine.restore(_tsm().shard(2), None, snap)
        assert e2.target.core.compiled_step
        for _ in range(4):
            e2.step()
        assert {i: e2.tokens(r) for i, r in enumerate(rids)} == ref
        e2.check_invariants()
    """)


def test_compiled_slice_export_import():
    _run_script("""
        a, _ = _run(_tsm().shard(2), prefix_cache=True)
        b, _ = _run(_tsm(), prefix_cache=True, num_blocks=60)
        rid_a = sorted(a._by_rid)[-1]
        slc = a.export_slice(rid_a)
        assert slc["geometry"]["num_heads"] == H
        n = b.import_slice(slc)
        assert n > 0
        b.check_invariants()
        back = b.export_slice(sorted(b._by_rid)[-1])
        # an empty index adopts everything (identity is the token
        # ids: a pool that served the same prompts holds them already)
        c = SpeculativeEngine(_tsm(seed=1).shard(2), k=0, max_batch=3,
                              block_size=BS, num_blocks=40,
                              prefix_cache=True)
        m = c.import_slice(back)
        assert m == len(back["hashes"])
        c.check_invariants()
    """)


# ------------------------------------------------ legacy path contracts
def test_legacy_allreduce_contract_and_uncommitted():
    """compiled_step=False keeps the host-staged path byte-for-byte:
    num_layers _allreduce calls per mixed step, with the all-reduce
    result now an UNCOMMITTED on-device array (no host round-trip)."""
    _run_script("""
        tl = _tsm().shard(2, compiled_step=False)
        engl = SpeculativeEngine(tl, k=0, max_batch=3, block_size=BS,
                                 num_blocks=40)
        for p in PROMPTS:
            engl.submit(p)
        tl.core.reset_allreduce_count()
        engl.step()
        assert tl.core.allreduce_count == LAYERS
        m = tl.core.sharded_metrics()
        assert not m["compiled"] and m["jit_calls"] == 0, m

        from paddle_tpu.inference.serving import _uncommitted
        import jax.numpy as jnp
        arr = jax.device_put(jnp.ones((4, 4)), jax.devices()[1])
        u = _uncommitted(arr)
        assert not u.committed
        assert u.sharding.device_set == arr.sharding.device_set
        np.testing.assert_array_equal(np.asarray(u), np.asarray(arr))
    """)


def test_rows_mode_out_projection():
    """out_shard='rows' (the Megatron row-sharded second GEMM, TPU
    default) engages and serves; CPU does not promise bit-identity
    for this summation order, so only stream shape is asserted."""
    _run_script("""
        base = _run(_tsm())[1]
        tr = _tsm().shard(2, out_shard="rows")
        assert tr.core.out_shard == "rows"
        assert tr.core.compiled_step
        _, toksr = _run(tr)
        assert set(toksr) == set(base)
        for i in toksr:
            assert np.asarray(toksr[i]).shape == \\
                np.asarray(base[i]).shape
    """)


# ------------------------------------------------- kernel spmd guard
def test_paged_attention_ragged_delegates_inside_shard_map():
    """Inside an active shard_map region the interpret-mode Pallas call
    cannot stage host transfers, so paged_attention_ragged must detect
    the region and delegate to its jnp reference — bit-exactly."""
    _run_script("""
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention_ragged, paged_attention_ragged_reference,
            dispatch_count, reset_dispatch_count)

        rng = np.random.RandomState(0)
        NB, Hh, bs, hd = 8, 2, 4, 8
        pool = jnp.asarray(
            rng.randn(NB, 2, Hh, bs, hd).astype(np.float32))
        bt = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
        q = jnp.asarray(rng.randn(3, Hh, hd).astype(np.float32))
        q_lens, kv_lens = (2, 1), jnp.asarray(
            np.array([5, 3], np.int32))
        ref = paged_attention_ragged_reference(q, pool, bt, q_lens,
                                               kv_lens)
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        reset_dispatch_count()

        def body(q_, pool_, bt_, kvl_):
            return paged_attention_ragged(q_, pool_, bt_, q_lens, kvl_)

        out = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P(), P()),
            out_specs=P(), check_rep=False))(q, pool, bt, kv_lens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert dispatch_count() >= 1
    """)


# --------------------------------------- in-process (single-device) ----
def _tsm_local(seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.fused_transformer import \
        FusedMultiTransformer
    from paddle_tpu.inference import TokenServingModel
    rng = np.random.RandomState(seed)
    m = FusedMultiTransformer(32, 4, 64, num_layers=2)
    for blk in m.layers:
        for name in ("qkv", "out_proj", "ffn1", "ffn2"):
            lin = getattr(blk, name)
            lin.weight.set_value(paddle.to_tensor(
                (rng.randn(*lin.weight.shape) * 0.1)
                .astype(np.float32)))
            lin.bias.set_value(paddle.to_tensor(
                (rng.randn(*lin.bias.shape) * 0.01)
                .astype(np.float32)))
    emb = (rng.randn(50, 32) * 0.3).astype(np.float32)
    return TokenServingModel(m, emb, lm_head=np.roll(emb, -1, 0).T.copy())


def test_single_device_auto_disables_compiled():
    """On one device the shard placements are not distinct: auto must
    keep the legacy path, and metrics must say so."""
    import jax
    t = _tsm_local().shard(2)
    if len(jax.devices()) >= 2:
        pytest.skip("host has a multi-device client")
    assert not t.core.compiled_step
    m = t.core.sharded_metrics()
    assert not m["compiled"]
    assert m["allreduce_count"] == 0 and m["jit_calls"] == 0


def test_forced_compiled_without_distinct_devices_raises():
    import jax
    if len(jax.devices()) >= 2:
        pytest.skip("host has a multi-device client")
    from paddle_tpu.inference import ShardedServingCore
    with pytest.raises(ValueError, match="distinct"):
        ShardedServingCore(_tsm_local().core, 2, compiled_step=True)


def test_bad_option_values_raise():
    from paddle_tpu.inference import ShardedServingCore
    with pytest.raises(ValueError, match="out_shard"):
        ShardedServingCore(_tsm_local().core, 2, out_shard="cols")
    with pytest.raises(ValueError, match="compiled_step"):
        ShardedServingCore(_tsm_local().core, 2, compiled_step="yes")


def test_nondivisible_heads_still_refused():
    from paddle_tpu.inference import ShardedServingCore
    with pytest.raises(ValueError, match="divide"):
        ShardedServingCore(_tsm_local().core, 3)
