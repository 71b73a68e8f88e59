"""The config-driven decoder core at arch ``joyai_llm_flash`` (multi-head
latent attention, ABSORBED, over a latent paged cache; ``inference/
decoder.py``, ``paged_cache.py``, ``ops/pallas/paged_attention.py``) against
its plain UN-absorbed reference (``benchmark/reference/joyai_llm_flash.py``)
at a tiny size on the CPU, through ``build_server_from_spec``.

Tiny ``joyai_llm_flash``: d 64, 4 heads of nope 16 / rope 8 / v 16, kv rank
32, q rank 48, 16 experts top 4 + 1 shared, 1 dense + 4 expert layers,
float32. The cached row is 32 + 8 = 40 columns, stored as 128 (whole lane
tiles, as 576 is stored as 640).
"""
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.jobs import serve_latent  # noqa: E402
from benchmark.reference import joyai_llm_flash as ref  # noqa: E402
from paddle_tpu.framework import device  # noqa: E402
from paddle_tpu.inference import decoder  # noqa: E402
from paddle_tpu.inference import paged_cache as pc  # noqa: E402
from paddle_tpu.inference.recovery import RecoverableServer  # noqa: E402
from paddle_tpu.inference.router import build_server_from_spec  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    launch_plan, paged_attention_ragged, paged_attention_ragged_reference,
    resolve_tile_q)

# float32 everywhere and "highest" products (tests/conftest.py): engine and
# reference differ by the order of their sums only
TOL = 1e-4
STORED = 128                      # 40 columns in whole lane tiles
TINY = {
    "model_type": "joyai_llm_flash", "reference": "joyai_llm_flash",
    "hidden_size": 64, "num_attention_heads": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "n_routed_experts": 16,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rope_interleave": True,
    "rope_theta": 32000000, "rms_norm_eps": 1e-6, "vocab_size": 97,
    "weight_dtype": "float32", "layers_run": [0, 1, 2, 3, 4],
    "engine": {"mp": 1, "k": 0, "max_batch": 4, "block_size": 4,
               "num_blocks": 200, "max_blocks_per_seq": 24,
               "prefix_cache": True, "prefill_token_budget": 16,
               "kv_dtype": "float32"},
}
PROBE_TRAFFIC = {"table": [[40, 8]], "clients": 1}


def _spec(workdir, name="s", **changes):
    spec = serve_latent.server_spec(TINY, 7, workdir)
    spec.update(journal_path=os.path.join(workdir, name + ".wal"),
                snapshot_path=os.path.join(workdir, name + ".bin"))
    spec.update(changes)
    return spec


@pytest.fixture(scope="module")
def served():
    """One tiny server, its probe served once: (server, probe)."""
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_latent.build_server(TINY, 7, workdir)
        try:
            probe = serve_latent.probe_engine(server, TINY, PROBE_TRAFFIC, 7)
            yield server, probe
        finally:
            server.close()


# ---- (a) engine logits against the un-absorbed reference ---------------

def test_probe_logits_match_the_reference(served):
    """Prefill in three chunks, then four decode steps through the latent
    pool: absorbed in the engine, decompressed K and V in the reference."""
    server, probe = served
    stats = {}
    err = serve_latent.compare_probe(server.engine.target, TINY, probe,
                                     tol=TOL, stats=stats)
    assert err < 1e-5 and stats["route_flips_outside_margin"] == 0
    assert stats["route_rows"] == 4 * len(probe["tokens"])
    core = server.engine.target.core
    cfg = core.config
    assert (cfg.attention, cfg.residual) == ("mla", "pre_norm")
    assert core.layer_windows == (None,) * 5
    assert core.latent_cache == {"v_dim": 32, "sm_scale": 24 ** -0.5}
    assert (core.num_heads, core.num_kv_heads, core.head_dim) == (4, 1, STORED)


def test_latent_pool_geometry_and_bytes_by_hand(served):
    """ONE row a position a layer, no V plane: 5 layers x 128 stored
    columns x 4 B a token; 200 blocks x 4 positions of that."""
    cache = served[0].engine.engine.cache
    assert (cache.v_dim, cache.planes, cache.num_kv_heads) == (32, 1, 1)
    assert cache.latent == (32, 24 ** -0.5)
    assert tuple(cache.pools[0].shape) == (200, 1, 1, 4, STORED)
    assert len(cache.pools) == 5 and cache.scales is None
    assert cache.kv_bytes_per_token() == 5 * 1 * STORED * 4 == 2560
    assert cache.pool_bytes() == cache.pool_bytes_total() == 200 * 4 * 2560
    stats = cache.take_write_stats()
    assert stats["pool_bytes"] == cache.pool_bytes()
    # the published widths, in bfloat16: 576 columns stored as 640
    big = decoder.DecoderConfig.from_spec(dict(
        arch="joyai_llm_flash", hidden_size=2048, num_attention_heads=32,
        intermediate_size=7168, num_hidden_layers=5, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense_replace=1, n_routed_experts=256,
        num_experts_per_tok=8))
    assert big.kv_width == 640 and big.attn_scale == 192 ** -0.5
    pool = pc.PagedKVCache(5, 32, big.kv_width, 16, 8, 2, dtype="bfloat16",
                           num_kv_heads=1, v_dim=512,
                           sm_scale=big.attn_scale)
    assert pool.kv_bytes_per_token() == 5 * 640 * 2 == 6400
    assert pool.pool_bytes() == 8 * 16 * 6400
    # beside it, a K/V pool of the same model un-absorbed: 16 times more
    kv = pc.PagedKVCache(5, 32, 192, 16, 8, 2, dtype="bfloat16")
    assert kv.planes == 2 and kv.latent is None
    assert kv.kv_bytes_per_token() == 5 * 2 * 32 * 192 * 2


def test_mixed_and_decode_only_steps_match_the_reference(served):
    """Three requests that arrive while others decode: every decode row,
    in steps that carry someone else's prompt chunk and in steps that do
    not, against the reference's full forward of that request."""
    server, _ = served
    tsm = server.engine.target
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=n).tolist() for n in (24, 13, 30)]
    arrive = {0: 0, 4: 1, 7: 2}                  # step -> prompt
    tap, inner, got, rids = [], tsm.logits, {}, {}
    kinds = set()

    def tapped(hidden):
        out = inner(hidden)
        tap.append(out.data)
        return out
    tsm.logits = tapped
    eng = server.engine.engine
    try:
        for step in range(16):
            if step in arrive:
                rids[server.submit(prompts[arrive[step]])] = arrive[step]
            before = {rid: len(server.generated(rid)) for rid in rids}
            chunks = eng.prefill_stats.prefill_tokens
            del tap[:]
            server.step()
            mixed = eng.prefill_stats.prefill_tokens > chunks
            rows = [a for a in tap if a.ndim == 3]
            for rid, n in before.items():
                if n and len(server.generated(rid)) > n and n < 5:
                    slot = server.engine._by_rid[rid].slot
                    got[(rids[rid], n)] = np.asarray(rows[-1][slot, 0])
                    kinds.add(mixed)
        gens = {rids[rid]: server.generated(rid) for rid in rids}
        for rid in rids:
            server.release(rid)
        server.drain_outcomes()
    finally:
        tsm.logits = inner
    assert kinds == {True, False} and len(got) == 12
    weights = ref.weights_of(tsm)
    for i, prompt in enumerate(prompts):
        want = ref.logits(weights, prompt + gens[i][:4])
        for n in range(1, 5):
            r = want[len(prompt) + n - 1]
            err = np.linalg.norm(got[(i, n)] - r) / np.linalg.norm(r)
            assert err < 1e-5, (i, n, err)


def test_the_kernel_path_serves_the_same_logits(served, monkeypatch):
    """The chip's path (packed steps, the ``v_dim`` launch interpreted)
    on a second server of the same weights: the probe again."""
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_latent.build_server(TINY, 7, workdir)
        try:
            probe = serve_latent.probe_engine(server, TINY,
                                              {"table": [[24, 6]]}, 7)
            err = serve_latent.compare_probe(server.engine.target, TINY,
                                             probe, tol=TOL)
        finally:
            server.close()
    assert err < 2e-5


# ---- (b) absorbed equals un-absorbed, one layer -------------------------

def test_absorbed_attention_equals_unabsorbed_on_one_layer(served):
    """``_mla_in`` / ``_attn_out`` around a plain softmax over the cached
    rows, against the reference's decompressed K and V: the same h."""
    core = served[0].engine.target.core
    cfg, p = core.config, core.params[2]
    n = 19
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, n, 64)),
                    jnp.float32)
    pos = jnp.arange(n)[None]
    q_abs, row, v, gate = decoder._attn_in(cfg, False, p, x, pos)
    assert v is None and gate is None
    assert q_abs.shape == (1, n, 4, STORED) and row.shape == (1, n, 1, STORED)
    assert not np.asarray(row[..., 40:]).any()        # the padding is zeros
    assert not np.asarray(q_abs[..., 40:]).any()
    s = jnp.einsum("qhd,kd->hqk", q_abs[0], row[0, :, 0]) * cfg.attn_scale
    s = jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None], s,
                  -jnp.inf)
    attn = jnp.einsum("hqk,kr->qhr", jax.nn.softmax(s, -1),
                      row[0, :, 0, :32])[None]        # [1, n, 4, rank]
    h, _ = decoder._attn_out(cfg, p, x, attn, None)
    q, k, vv = ref._qkv(x[0], p, cfg=cfg, rounding=None, variant=None)
    assert k.shape == (n, 4, 24) and vv.shape == (n, 4, 16)
    want, _ = ref._after_attention(
        x[0], ref._attention(q, k, vv, cfg.attn_scale), p, cfg=cfg,
        rounding=None)
    np.testing.assert_allclose(np.asarray(h[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_rope_interleaved_rotates_pairs():
    x = jnp.asarray(np.arange(8, dtype=np.float32).reshape(1, 1, 1, 8))
    got = np.asarray(decoder.rope_interleaved(x, jnp.asarray([[3]]), 100.0))
    inv = 100.0 ** (-np.arange(4) / 4)
    for i in range(4):
        c, s = np.cos(3 * inv[i]), np.sin(3 * inv[i])
        a, b = 2 * i, 2 * i + 1
        np.testing.assert_allclose(got[0, 0, 0, [a, b]],
                                   [a * c - b * s, b * c + a * s], rtol=1e-5)
    still = decoder.rope_interleaved(x, jnp.asarray([[0]]), 100.0)
    np.testing.assert_array_equal(np.asarray(still), np.asarray(x))


# ---- (c) each mechanism, broken alone, fails the comparison ----------

@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_broken_mechanism_fails_the_comparison(served, variant):
    server, probe = served
    with pytest.raises(AssertionError, match="reference|margin"):
        serve_latent.compare_probe(server.engine.target, TINY, probe,
                                   tol=TOL, variant=variant)


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_broken_mechanism_moves_the_logits_by_a_wide_margin(served,
                                                              variant):
    """Without the routing check's help: the variant's own logits lie
    percent away from the unbroken reference's, float32 rounding 1e-6."""
    server, probe = served
    weights = ref.weights_of(server.engine.target)
    rows = serve_latent.probed_positions(probe)
    good = ref.logits(weights, probe["tokens"], rows=rows)
    bad = ref.logits(weights, probe["tokens"], rows=rows, variant=variant)
    err = np.linalg.norm(bad - good, axis=-1) / np.linalg.norm(good, axis=-1)
    assert err.min() > 5e-3, err


# ---- (d) the kernel, latent form, 32 query heads on one kv head -------

def _latent_case(seed=0, nh=32, hd=24, v_dim=16, bs=4, MB=12, NB=40,
                 dtype=jnp.float32):
    r = np.random.default_rng(seed)
    pool = jnp.asarray(r.standard_normal((NB, 1, 1, bs, hd)), dtype)
    q_lens = (7, 1, 1, 1, 10)
    kv_lens = jnp.asarray([30, 5, 9, 47, 13], jnp.int32)
    bt = jnp.asarray(r.integers(1, NB, (len(q_lens), MB)), jnp.int32)
    q = jnp.asarray(r.standard_normal((sum(q_lens), nh, hd)), dtype)
    return q, pool, bt, q_lens, kv_lens, v_dim


@pytest.mark.parametrize("tiles", [(None, None), (4, 2), (1, 1)])
def test_latent_kernel_matches_the_reference(tiles):
    q, pool, bt, q_lens, kv_lens, v_dim = _latent_case()
    want = paged_attention_ragged_reference(
        q, pool, bt, q_lens, kv_lens, sm_scale=0.2, v_dim=v_dim)
    got = paged_attention_ragged(q, pool, bt, q_lens, kv_lens, sm_scale=0.2,
                                 tile_q=tiles[0], tile_kv=tiles[1],
                                 v_dim=v_dim)
    assert got.shape == (sum(q_lens), 32, v_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# bf16 q over bf16 latent rows, as long-decode hands the launch: the body
# copies both to float32 (exactly), so against the reference on the SAME
# bf16 inputs what is left off the chip is the output's own rounding to
# bf16, 2^-9 of values up to about 2.5; a wrong page, mask or value column
# is O(1).
BF16_TOL = 1e-2


@pytest.mark.parametrize("tiles", [(None, None), (4, 2), (4, 5), (1, 1)])
def test_latent_kernel_over_bf16_matches_the_reference(tiles):
    """32 query heads on the one kv head, a mixed batch with partial tail
    tiles, 12 table entries in steps of 5 pages, and a length-0 row."""
    q, pool, bt, q_lens, kv_lens, v_dim = _latent_case(dtype=jnp.bfloat16)
    q_lens = q_lens + (1,)                           # a slot with nothing in
    q = jnp.concatenate([q, q[:1]])
    kv_lens = jnp.concatenate([kv_lens, jnp.zeros((1,), jnp.int32)])
    bt = jnp.concatenate([bt, jnp.zeros((1, bt.shape[1]), jnp.int32)])
    want = paged_attention_ragged_reference(
        q.astype(jnp.float32), pool, bt, q_lens, kv_lens, sm_scale=0.2,
        v_dim=v_dim)
    got = paged_attention_ragged(q, pool, bt, q_lens, kv_lens, sm_scale=0.2,
                                 tile_q=tiles[0], tile_kv=tiles[1],
                                 v_dim=v_dim)
    assert got.shape == (sum(q_lens), 32, v_dim) and got.dtype == q.dtype
    got = np.asarray(got.astype(jnp.float32))
    np.testing.assert_allclose(got, np.asarray(want), atol=BF16_TOL,
                               rtol=BF16_TOL)
    assert np.all(got[-1] == 0.0)


def test_latent_reference_is_the_masked_softmax_over_one_row():
    q, pool, bt, q_lens, kv_lens, v_dim = _latent_case()
    got = np.asarray(paged_attention_ragged_reference(
        q, pool, bt, q_lens, kv_lens, sm_scale=0.2, v_dim=v_dim))
    rows = np.asarray(pool)[np.asarray(bt)[3]][:, 0, 0].reshape(-1, 24)[:47]
    for head in (0, 31):
        query = np.asarray(q)[9, head]               # sequence 3's one row
        p = jax.nn.softmax(jnp.asarray(rows @ query * 0.2))
        np.testing.assert_allclose(got[9, head], np.asarray(p) @ rows[:, :16],
                                   atol=1e-5)


def test_latent_form_refuses_what_it_cannot_run():
    q, pool, bt, q_lens, kv_lens, v_dim = _latent_case()
    with pytest.raises(ValueError, match="sm_scale"):
        paged_attention_ragged(q, pool, bt, q_lens, kv_lens, v_dim=v_dim)
    with pytest.raises(ValueError, match="plane"):
        paged_attention_ragged(q, pool, bt, q_lens, kv_lens)   # K/V form
    with pytest.raises(ValueError, match="plane"):
        paged_attention_ragged(q, jnp.concatenate([pool, pool], 1), bt,
                               q_lens, kv_lens, sm_scale=0.2, v_dim=v_dim)


def test_launch_plans():
    """A step is given at most 2 MiB of pages, a power of two of positions:
    chat's plan is what it was (128 positions at 32 heads), trinity's takes
    512 (PR 33's sweep), the latent launch 1 024, in tiles of at most 1 024
    rows. ``grid`` is the bound; the launch walks its live steps only."""
    # chat's decode launch (PERF.md, PR 27): float32 q over bf16 pages
    chat = launch_plan(32, 32, 1, 128, 16, 128, 2, q_itemsize=4)
    assert (chat.heads, chat.pages, chat.grid) == (32, 8, (32, 16))
    assert chat.bytes_per_step == 2 * 2 ** 20
    # trinity's decode and mixed launches: bf16 q over bf16 pages
    decode = launch_plan(32, 8, 6, 784, 16, 128, 2, q_itemsize=2)
    assert (decode.heads, decode.pages, decode.grid) == (8, 32, (32, 25))
    trinity = launch_plan(64, 8, 384, 784, 16, 128, 2, q_itemsize=2)
    # 512 positions at 384 rows do not fit the budget: whole score tiles
    assert (trinity.heads, trinity.pages, trinity.grid) == (8, 24, (64, 33))
    assert resolve_tile_q((2048,) + (1,) * 32, None, 6) == 64
    assert resolve_tile_q((256,) + (1,) * 32) == 64
    # the latent launch of joyai-flash.long-decode
    assert resolve_tile_q((1,) * 64, None, 32) == 1
    assert resolve_tile_q((2048,) + (1,) * 64, None, 32) == 32
    decode = launch_plan(64, 1, 32, 896, 16, 640, 2, q_itemsize=2, v_dim=512)
    assert (decode.heads, decode.pages, decode.grid) == (1, 64, (64, 14))
    assert decode.bytes_per_step == 64 * 16 * 640 * 2       # one plane
    mixed = launch_plan(128, 1, 1024, 896, 16, 640, 2, q_itemsize=2,
                        v_dim=512)
    assert (mixed.heads, mixed.pages) == (1, 64)


def test_work_list_walks_the_live_steps_only():
    """``_work_list`` against a plain enumeration: tile by tile, a head
    group after the other, the kv steps from the first inside the window to
    the last real query's, one item for a tile without a key; and the last
    table entry each page operand may name (its last real page, or entry p
    where even its first page lies past the tile's frontier)."""
    import importlib
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    P, bs, steps, n_hb = 2, 4, 6, 2
    pos0 = np.asarray([0, 5, 40, -1, 17], np.int32)
    pos_last = np.asarray([6, 5, 43, -1, 30], np.int32)
    tseq = np.asarray([0, 1, 1, 2, 3], np.int32)
    for window in (None, 9):
        count, items, tiles, held = pa._work_list(
            jnp.asarray(pos0), jnp.asarray(pos_last), jnp.asarray(tseq),
            n_hb, steps, P, bs, window)
        want = []
        for t in range(5):
            last_page = max(int(pos_last[t]), 0) // bs
            last = last_page // P
            first = 0 if window is None else \
                min(max(int(pos0[t]) - window + 1, 0) // (bs * P), last)
            np.testing.assert_array_equal(
                np.asarray(tiles)[:, t],
                [pos0[t], pos_last[t], first, last, tseq[t]])
            np.testing.assert_array_equal(
                np.asarray(held)[t * P:(t + 1) * P],
                [max(last_page - p, 0) // P * P + p for p in range(P)])
            want += [(t * n_hb + h, j) for h in range(n_hb)
                     for j in range(first, last + 1)]
        assert int(count) == len(want)
        np.testing.assert_array_equal(np.asarray(items)[:, :len(want)],
                                      np.asarray(want).T)
    assert items.shape == (2, 5 * n_hb * steps)
    # tile 0 of the last run: pages 0 and 1 are real (positions 0..6)
    assert list(np.asarray(held)[:2]) == [0, 1]


# ---- (e) the latent pool through the cache's life ----------------------

def test_latent_pool_refuses_int8_and_mp():
    with pytest.raises(ValueError, match="int8 latent"):
        pc.PagedKVCache(2, 4, 128, 4, 8, 2, dtype="int8", num_kv_heads=1,
                        v_dim=32, sm_scale=0.2)
    with pytest.raises(ValueError, match="mp"):
        pc.PagedKVCache(2, 4, 128, 4, 8, 2, mp=2, num_kv_heads=2, v_dim=32,
                        sm_scale=0.2)
    with pytest.raises(ValueError, match="sm_scale"):
        pc.PagedKVCache(2, 4, 128, 4, 8, 2, num_kv_heads=1, v_dim=32)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="mp 1"):
            build_server_from_spec(_spec(d, mp=2))
        with pytest.raises(ValueError, match="int8 latent"):
            build_server_from_spec(_spec(d, kv_dtype="int8"))
        with pytest.raises(ValueError, match="unknown arch"):
            build_server_from_spec(_spec(d, arch="deepseek"))
        with pytest.raises(ValueError, match="interleaved"):
            build_server_from_spec(_spec(d, rope_interleave=False))


def test_accounting_refuses_the_core(served):
    from paddle_tpu.inference.accounting import WorkModel
    with pytest.raises(ValueError, match="GPT-3 block only"):
        WorkModel.for_model(served[0].engine.target)


def test_snapshot_restore_and_slices_over_latent_pages(served):
    """A snapshot of a latent pool restores to the same pages and
    geometry; a slice exported from one pool is adopted by another and
    refused by a pool of another form."""
    server, _ = served
    eng = server.engine.engine
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 97, size=22).tolist()
    rid = server.submit(prompt)
    for _ in range(4):
        server.step()
    cache = eng.cache
    snap = cache.snapshot()
    g = snap["geometry"]
    assert g["v_dim"] == 32 and g["sm_scale"] == 24 ** -0.5
    assert snap["payload"].shape[1:] == (5, 1, 1, 4, STORED)
    back = pc.PagedKVCache.restore(snap)
    assert back.latent == cache.latent and back.planes == 1
    for a, b in zip(cache.pools, back.pools):
        keep = snap["blocks"]
        np.testing.assert_array_equal(np.array(a.numpy())[keep],
                                      np.array(b.numpy())[keep])
    assert back.snapshot(base=snap)["base_blocks"]        # a delta: clean
    # a request submitted as tokens is keyed by its ids
    hashes = pc.chain_block_hashes(np.asarray(prompt), 4)
    slot = server.engine._by_rid[rid].slot
    assert eng._requests[slot].block_hashes(4)[:len(hashes)] == hashes
    slc = cache.export_slice(slot, hashes)
    assert slc["geometry"]["v_dim"] == 32
    assert slc["payload"].shape == (len(hashes), 5, 1, 1, 4, STORED)
    other = pc.PagedKVCache(5, 4, STORED, 4, 40, 2, prefix_cache=True,
                            num_kv_heads=1, v_dim=32, sm_scale=24 ** -0.5)
    assert other.import_slice(slc) == len(hashes)
    assert other.match_prefix(hashes) != []
    kv_form = pc.PagedKVCache(5, 4, STORED, 4, 40, 2, prefix_cache=True,
                              num_kv_heads=1)
    with pytest.raises(ValueError, match="geometry"):
        kv_form.import_slice(slc)
    server.release(rid)
    server.drain_outcomes()
    assert server.check_invariants()


def test_prefix_adoption_and_preemption_over_latent_pages():
    """A second prompt adopts the first one's pages (its logits are the
    reference's all the same); a pool that runs dry preempts and the
    streams are those of a roomy pool."""
    rng = np.random.default_rng(4)
    first = rng.integers(0, 97, size=21).tolist()
    prompts = [first, first[:16] + rng.integers(0, 97, size=6).tolist(),
               rng.integers(0, 97, size=30).tolist(),
               rng.integers(0, 97, size=27).tolist()]

    def streams(num_blocks):
        with tempfile.TemporaryDirectory() as d:
            srv = build_server_from_spec(_spec(d, num_blocks=num_blocks,
                                               max_preemptions=8))
            eng = srv.engine.engine
            rids = [srv.submit(prompts[0])]
            for _ in range(3):
                srv.step()
            rids += [srv.submit(p) for p in prompts[1:]]
            done = {}
            for _ in range(120):
                srv.step()
                for r in rids:          # a closed loop: release at 12
                    if r not in done and len(srv.generated(r)) >= 12:
                        done[r] = list(srv.generated(r))[:12]
                        srv.release(r)
                srv.drain_outcomes()
                if len(done) == len(rids):
                    break
            out = [done[r] for r in rids]
            stats = (eng.prefix_stats.tokens_skipped,
                     eng.resilience_stats.retried)
            assert srv.check_invariants()
            weights = ref.weights_of(srv.engine.target)
            srv.close()
        return out, stats, weights

    roomy, (skipped, retried), weights = streams(200)
    assert skipped >= 16 and retried == 0
    tight, (_, retried), _ = streams(30)
    assert retried > 0 and tight == roomy
    # the adopter's first token is the reference's argmax at its prompt's end
    want = ref.logits(weights, prompts[1], rows=[len(prompts[1]) - 1])
    assert int(want[0].argmax()) == roomy[1][0]


def test_a_recovered_server_serves_on_over_latent_pages(tmp_path):
    """Serve, snapshot, die, recover from the files behind a model of the
    same seeds: the streams of a server that never died."""
    d = str(tmp_path)
    prompts = [list(range(9)), list(range(20, 43))]
    whole = build_server_from_spec(_spec(d, "whole"))
    rids = [whole.submit(p) for p in prompts]
    for _ in range(10):
        whole.step()
    want = [list(whole.generated(r)) for r in rids]
    whole.close()

    spec = _spec(d, "dies", snapshot_every=3)
    srv = build_server_from_spec(spec)
    assert [srv.submit(p) for p in prompts] == rids
    for _ in range(5):                  # a snapshot at 3, two rounds on
        srv.step()
    srv.close()
    again = build_server_from_spec(dict(spec, recover=True))
    try:
        assert isinstance(again, RecoverableServer)
        cache = again.engine.engine.cache
        assert cache.latent == (32, 24 ** -0.5) and cache.planes == 1
        for _ in range(5):
            again.step()
        assert [list(again.generated(r)) for r in rids] == want
        assert again.check_invariants()
    finally:
        again.close()


# ---- (f) spans, gauge, counters -----------------------------------------

def test_mla_spans_gauge_and_counters_in_a_traced_session(served,
                                                          monkeypatch):
    from paddle_tpu.inference.telemetry import TraceCollector
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_report
    server, _ = served
    eng, core = server.engine.engine, server.engine.target.core
    col = TraceCollector()
    eng.collector = col
    try:
        assert core.collector is col
        rng = np.random.default_rng(5)
        rids = [server.submit(rng.integers(0, 97, size=40).tolist())]
        for _ in range(4):                  # alone: three chunks, a token
            server.step()
        rids.append(server.submit(rng.integers(0, 97, size=20).tolist()))
        for _ in range(3):                  # chunks beside a decode row
            server.step()
        eng.cache.take_write_stats()
        for rid in rids:
            server.release(rid)
        server.drain_outcomes()
    finally:
        eng.collector = None
    spans = [e for e in col.events if e.get("ph") == "X"]
    mla = [e for e in spans if e["name"] == "mla"]
    moe = [e for e in spans if e["name"] == "moe"]
    # one a layer a model call: five layers, four of them expert layers
    assert len(mla) * 4 == len(moe) * 5 and len(mla) >= 7 * 5
    assert {e["args"]["parent"] for e in mla} == {"model", "bookkeeping"}
    assert {e["args"]["layer"] for e in mla} == {0, 1, 2, 3, 4}
    for child in ("mla.project", "mla.attend", "mla.out"):
        got = [e for e in spans if e["name"] == child]
        assert len(got) == len(mla)
        assert all(e["args"]["parent"] == "mla" for e in got)
    # ``moe`` is ``mla``'s sibling, not its child
    assert {e["args"]["parent"] for e in moe} == {"model", "bookkeeping"}
    gauge = [e["args"] for e in col.events
             if e.get("ph") == "C" and e["name"] == "paged_attn"]
    # packed launches: chunks of 16, 16, 8 of a 40-token prompt, contexts
    # 16, 32, 40 (and four idle decode rows): 2 560 B a token
    assert [g["latent_bytes_in_context"] for g in gauge[:3]] == \
        [16 * 2560, 32 * 2560, 40 * 2560]
    assert all("pages_in_context" not in g for g in gauge)     # no window
    writes = [e["args"] for e in col.events
              if e.get("ph") == "C" and e["name"] == "pool_write"]
    assert writes and all(w["rows_written"] > 0 and
                          w["pool_bytes"] == eng.cache.pool_bytes()
                          for w in writes)
    # the first step wrote 16 rows in each of five layers
    assert writes[0]["rows_written"] == 5 * 16
    dump = col.chrome_trace()
    text = trace_report.summarize(dump)
    assert "model spans" in text and "mla.attend" in text
    assert dump["metadata"]["registry"]["moe.experts_held"] == 16


@pytest.mark.parametrize("pages", ["float32", "bfloat16"])
def test_paged_attn_gauge_counts_the_latent_launchs_live_steps(monkeypatch,
                                                               pages):
    """The ``paged_attn`` gauge on the kernel path, a sample a packed
    launch: ``live_steps`` (what the work list walks) beside ``grid_steps``
    (the plan's bound), over float32 pages as CPU tier-1 has them and over
    bf16 pages as the cells have; the launch is handed the core's float32
    rows and the pool as it is."""
    import importlib
    from paddle_tpu.inference.telemetry import TraceCollector
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    handed = []

    def recording(q, kv_pool, *a, **kw):
        handed.append((str(q.dtype), str(kv_pool.dtype)))
        return pa.paged_attention_ragged(q, kv_pool, *a, **kw)
    monkeypatch.setattr(pc, "paged_attention_ragged", recording)
    config = dict(TINY, engine=dict(TINY["engine"], kv_dtype=pages))
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_latent.build_server(config, 7, workdir)
        col = TraceCollector()
        server.engine.engine.collector = col
        try:
            rid = server.submit(list(range(20)))
            for _ in range(3):                # two chunks, then a token
                server.step()
            server.release(rid)
        finally:
            server.engine.engine.collector = None
            server.close()
    gauge = [e["args"] for e in col.events
             if e.get("ph") == "C" and e["name"] == "paged_attn"]
    # contexts of at most 20 positions: every tile's one step is live
    assert gauge and all(1 <= g["live_steps"] <= g["grid_steps"]
                         for g in gauge)
    assert handed and set(handed) == {("float32", pages)}


# ---- (g) the chip smoke's latent phase, rehearsed ------------------------

def test_chip_smoke_latent_phase_rehearsal():
    """``chip_smoke.latent_phase`` at toy sizes, the kernel interpreted."""
    import chip_smoke
    res = chip_smoke.latent_phase(
        hidden=64, heads=4, q_rank=48, kv_rank=32, nope=16, rope=8, v_dim=16,
        dense_width=128, experts=16, top_k=4, expert_width=32, vocab=97,
        prompt=40, chunk=16, block_size=4, max_batch=4,
        weight_dtype="float32", kv_dtype="float32", expect_kernel=False,
        logits_tol=1e-4, tol=1e-4)
    assert res["probe_rel_l2"] < 1e-5 and len(res["kernels"]) == 1
    assert res["route"]["route_flips_outside_margin"] == 0
