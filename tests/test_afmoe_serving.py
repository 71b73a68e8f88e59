"""The config-driven decoder core (``inference/decoder.py``, arch ``afmoe``)
against its plain reference (``benchmark/reference/afmoe.py``) at a tiny
size on the CPU, through ``build_server_from_spec``: window and full layers
over one paged pool, grouped KV heads, gated attention, sigmoid-routed
dropless experts with a shared expert.

Tiny ``afmoe``: d 64, 4 heads / 2 KV heads of 16, window 8, 16 experts top
2 + 1 shared, 1 dense + 4 expert layers (sliding x4, full), float32.
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

from benchmark.jobs import serve_arch  # noqa: E402
from benchmark.reference import afmoe  # noqa: E402
from paddle_tpu.inference import moe_serving  # noqa: E402
from paddle_tpu.inference.decoder import DecoderConfig  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_attention_ragged, paged_attention_ragged_reference)

# float32 everywhere and "highest" products (tests/conftest.py): engine and
# reference differ by the order of their sums only
TOL = 1e-4
SLIDING, FULL = "sliding_attention", "full_attention"
TINY = {
    "model_type": "afmoe", "reference": "afmoe",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": 8, "intermediate_size": 128,
    "num_experts": 16, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "route_norm": True, "route_scale": 2.448,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "mup_enabled": True,
    "vocab_size": 97, "weight_dtype": "float32", "num_dense_layers": 1,
    "layer_types": [SLIDING] * 4 + [FULL], "layers_run": [0, 1, 2, 3, 4],
    "deployment_cut": {"num_experts_published": 16, "expert_offset": 0},
    "engine": {"mp": 1, "k": 0, "max_batch": 4, "block_size": 4,
               "num_blocks": 200, "max_blocks_per_seq": 24,
               "prefix_cache": True, "prefill_token_budget": 16,
               "kv_dtype": "float32"},
}
# a prompt five windows long, fed 16 tokens a step
PROBE_TRAFFIC = {"table": [[40, 8]], "clients": 1}


@pytest.fixture(scope="module")
def served():
    """One tiny server, its probe served once: (tsm, probe)."""
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_arch.build_server(TINY, 7, workdir)
        try:
            probe = serve_arch.probe_engine(server, TINY, PROBE_TRAFFIC, 7)
            yield server, probe
        finally:
            server.close()


# ---- (a) engine logits against the reference -------------------------

def test_probe_logits_match_the_reference(served):
    """Prefill in three chunks, then four decode steps through the paged
    pool, at contexts five times the window."""
    server, probe = served
    stats = {}
    err = serve_arch.compare_probe(server.engine.target, TINY, probe,
                                   tol=TOL, stats=stats)
    assert err < 1e-5 and stats["route_flips_outside_margin"] == 0
    assert stats["route_rows"] == 4 * len(probe["tokens"])
    core = server.engine.target.core
    assert core.layer_windows == (8, 8, 8, 8, None)
    cache = server.engine.engine.cache
    assert cache.num_kv_heads == 2 and cache.num_heads == 4
    assert tuple(cache.pools[0].shape) == (200, 2, 2, 4, 16)
    assert cache.kv_bytes_per_token() == 5 * 2 * 2 * 16 * 4


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
    weights = afmoe.weights_of(tsm)
    for i, prompt in enumerate(prompts):
        want = afmoe.logits(weights, prompt + gens[i][:4])
        for n in range(1, 5):
            r = want[len(prompt) + n - 1]
            err = np.linalg.norm(got[(i, n)] - r) / np.linalg.norm(r)
            assert err < 1e-5, (i, n, err)


# ---- (b) the shares add up -------------------------------------------

def test_expert_shares_sum_to_the_uncut_layer(served):
    """One expert layer's output over every ``expert_offset`` share of 4
    experts, the shared expert counted once, is the uncut reference
    layer's."""
    server, _ = served
    cfg = server.engine.target.core.config
    p = server.engine.target.core.params[2]
    m = jnp.asarray(np.random.default_rng(1).standard_normal((23, 64)),
                    jnp.float32)
    whole = afmoe._moe(m, p, cfg, None, None, afmoe.TIE_EPS, {}, None)
    shared = moe_serving.swiglu(m, p["shared_gate_up"], p["shared_down"])
    idx, w, _ = moe_serving.sigmoid_route(
        m, p["router"], p["router_bias"], 2, True, 2.448)
    total = shared
    hit = np.zeros(16, np.int64)
    for offset in range(0, 16, 4):
        part, counts = moe_serving.dropless_experts(
            m, idx, w, p["experts_gate_up"][offset:offset + 4],
            p["experts_down"][offset:offset + 4], offset, block_m=16)
        total = total + part
        hit[offset:offset + 4] = np.asarray(counts)
    assert hit.sum() == 23 * 2                       # nothing dropped
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # and a share alone is that share of the reference
    part, _ = moe_serving.dropless_experts(
        m, idx, w, p["experts_gate_up"][8:12], p["experts_down"][8:12], 8,
        block_m=16)
    ref_p = dict(p, experts_gate_up=p["experts_gate_up"][8:12],
                 experts_down=p["experts_down"][8:12])
    share = afmoe._moe(m, ref_p, DecoderConfig.from_spec(dict(
        cfg.__dict__, experts_held=4, expert_offset=8)), None, None,
        afmoe.TIE_EPS, {}, None)
    np.testing.assert_allclose(np.asarray(shared + part), np.asarray(share),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,want", [(32, 16), (2080, 64), (2048, 64),
                                       (8, 16), (100000, 128)])
def test_row_block_suits_decode_and_mixed_steps(rows, want):
    assert moe_serving.expert_row_block(rows, 4, 256) == want


def test_local_groups_layout():
    idx = jnp.asarray([[3, 9], [4, 3], [12, 5], [3, 4]], jnp.int32)
    dest, block_expert, used, counts, rows_pad = moe_serving.local_groups(
        idx, expert_offset=3, experts_held=3, block_m=2)
    # experts 3, 4, 5 hold 3, 2, 1 rows: groups padded to 4, 2, 2
    assert counts.tolist() == [3, 2, 1] and int(used[0]) == 4
    assert rows_pad == 12 and block_expert.tolist()[:4] == [0, 0, 1, 2]
    assert set(block_expert.tolist()[4:]) == {2}       # the tail repeats
    assert dest.tolist() == [[0, 12], [4, 1], [12, 6], [2, 5]]


@pytest.mark.parametrize("block_k", [8, 32])      # K in steps, K whole
def test_gmm_skips_the_unused_tail(block_k):
    """``gmm`` with ``blocks_used``: the live blocks are the per-expert
    products; the worst-case tail is not computed (a NaN there would
    spread through a product) and comes back as the buffer was."""
    from paddle_tpu.ops.pallas.grouped_gemm import gmm, gmm_reference
    rng = np.random.default_rng(0)
    bm, used = 4, 3
    be = jnp.asarray([0, 2, 2, 2, 2], jnp.int32)         # the tail repeats
    lhs = rng.standard_normal((5 * bm, 32)).astype(np.float32)
    lhs[used * bm:] = np.nan
    rhs = jnp.asarray(rng.standard_normal((3, 32, 24)), jnp.float32)
    out = np.asarray(gmm(jnp.asarray(lhs), rhs, be, block_m=bm, block_n=8,
                         block_k=block_k,
                         blocks_used=jnp.asarray([used], jnp.int32)))
    live = used * bm
    np.testing.assert_allclose(
        out[:live], np.asarray(gmm_reference(jnp.asarray(lhs[:live]), rhs,
                                             be[:used], block_m=bm)),
        rtol=1e-5, atol=1e-5)
    full = np.asarray(gmm(jnp.asarray(np.nan_to_num(lhs)), rhs, be,
                          block_m=bm, block_n=8, block_k=block_k))
    np.testing.assert_allclose(full[:live], out[:live], rtol=1e-6)
    assert not full[live:].any()              # zero rows in, zero rows out


# ---- (c) each mechanism, broken alone, fails the comparison ----------

@pytest.mark.parametrize("variant", [v for v in afmoe.VARIANTS if v])
def test_a_broken_mechanism_fails_the_comparison(served, variant):
    server, probe = served
    with pytest.raises(AssertionError, match="reference|margin"):
        serve_arch.compare_probe(server.engine.target, TINY, probe, tol=TOL,
                                 variant=variant)


def test_near_ties_take_the_engines_choice_inside_the_margin_only():
    s = np.array([[.9, .5, .4999, .1], [.9, .5, .3, .1], [.9, .5, .4999, .1]])
    engine = np.array([[0, 2], [0, 2], [1, 2]])
    stats = {}
    got = afmoe._choose(s, 2, engine, 2e-3, stats)
    assert got.tolist() == [[0, 2], [0, 1], [0, 1]]
    assert stats == {"route_rows": 3, "route_ties_taken": 1,
                     "route_flips_outside_margin": 2,
                     "route_widest_gap": pytest.approx(0.4001)}


# ---- (d) the kernel, with a window, at 6 query heads a kv head -------

def _window_case(seed=0, nkv=2, g=6, hd=16, bs=4, MB=12, NB=40):
    r = np.random.default_rng(seed)
    pool = jnp.asarray(r.standard_normal((NB, 2, nkv, bs, hd)), jnp.float32)
    # a prompt chunk that starts past the window, decode rows short of it,
    # on it and far past it, a chunk that straddles it
    q_lens = (7, 1, 1, 1, 10)
    kv_lens = jnp.asarray([30, 5, 9, 47, 13], jnp.int32)
    bt = jnp.asarray(r.integers(1, NB, (len(q_lens), MB)), jnp.int32)
    q = jnp.asarray(r.standard_normal((sum(q_lens), nkv * g, hd)),
                    jnp.float32)
    return q, pool, bt, q_lens, kv_lens


@pytest.mark.parametrize("window", [8, 3, None])
def test_kernel_with_a_window_matches_the_reference(window):
    q, pool, bt, q_lens, kv_lens = _window_case()
    want = paged_attention_ragged_reference(q, pool, bt, q_lens, kv_lens,
                                            window=window)
    for tile_q, tile_kv in ((None, None), (4, 2)):
        got = paged_attention_ragged(q, pool, bt, q_lens, kv_lens,
                                     tile_q=tile_q, tile_kv=tile_kv,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    if window is not None:       # and the window does change the answer
        full = paged_attention_ragged_reference(q, pool, bt, q_lens, kv_lens)
        assert np.abs(np.asarray(full) - np.asarray(want)).max() > 1e-2


def test_window_reference_is_the_masked_softmax():
    q, pool, bt, q_lens, kv_lens = _window_case(nkv=1, g=1)
    got = np.asarray(paged_attention_ragged_reference(
        q, pool, bt, q_lens, kv_lens, window=8))
    keys = np.asarray(pool)[np.asarray(bt)[3]]          # [MB, 2, 1, bs, hd]
    k = keys[:, 0, 0].reshape(-1, 16)[39:47]            # the last 8 of 47
    v = keys[:, 1, 0].reshape(-1, 16)[39:47]
    row = np.asarray(q)[9, 0]                           # sequence 3's query
    p = jax.nn.softmax(jnp.asarray(k @ row / 4.0))
    np.testing.assert_allclose(got[9, 0], np.asarray(p) @ v, atol=1e-5)


# ---- spans and counters ------------------------------------------------

def test_moe_spans_gauge_and_counters_in_a_traced_session(served):
    from paddle_tpu.inference.telemetry import TraceCollector
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_report
    server, _ = served
    eng, core = server.engine.engine, server.engine.target.core
    assert core.collector is None               # off: nothing installed
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
        for rid in rids:
            server.release(rid)
        server.drain_outcomes()
    finally:
        eng.collector = None
    assert core.collector is None
    spans = [e for e in col.events if e.get("ph") == "X"]
    moe = [e for e in spans if e["name"] == "moe"]
    # a step with decode rows makes its model call in the model phase; one
    # that only advances prompts makes it while it plans (bookkeeping)
    assert {e["args"]["parent"] for e in moe} == {"model", "bookkeeping"}
    assert {e["args"]["layer"] for e in moe
            if e["args"]["parent"] == "model"} == {1, 2, 3, 4}
    for child in ("moe.route", "moe.experts"):
        got = [e for e in spans if e["name"] == child]
        assert len(got) == len(moe)
        assert all(e["args"]["parent"] == "moe" for e in got)
    gauge = [e["args"] for e in col.events
             if e.get("ph") == "C" and e["name"] == "paged_attn"]
    # a 40-token prompt in chunks of 16, 16, 8; window 8, blocks of 4: the
    # second starts at 16 with 8 pages in context, (16 - 8 + 1) // 4 = 2
    # of them behind the window; the third at 32 with 10 and 6
    assert [(g["pages_in_context"], g["pages_behind_window"])
            for g in gauge[:3]] == [(4, 0), (8, 2), (10, 6)]
    # the counters stay on the device until the cold scrape
    acc = core._counters["mixed"][1]["acc"]
    assert isinstance(acc, jax.Array) and acc.shape == (18,)
    dump = col.chrome_trace()
    reg = dump["metadata"]["registry"]
    assert reg["moe.experts_held"] == 16 and reg["moe.mixed.calls"] >= 2
    assert reg["moe.mixed.rows_routed_here"] == \
        2 * 4 * reg["moe.mixed.rows"]            # top 2, 4 expert layers
    text = trace_report.summarize(dump)
    for needle in ("model spans:", "moe.experts:", "expert layers: 16 of 16",
                   "pages in context behind the window",
                   "paged_attn.pages_behind_window"):
        assert needle in text, needle


def test_chip_smoke_decoder_phase_rehearsal():
    """``chip_smoke.decoder_phase`` at toy sizes, kernels interpreted."""
    import chip_smoke
    res = chip_smoke.decoder_phase(
        hidden=64, heads=4, kv_heads=2, head_dim=16, window=8,
        dense_width=128, experts=16, held=4, top_k=2, expert_width=32,
        vocab=97, prompt=40, chunk=16, block_size=4, max_batch=4,
        weight_dtype="float32", kv_dtype="float32", expect_kernel=False,
        logits_tol=1e-4, tol=1e-4)
    assert res["probe_rel_l2"] < 1e-5 and len(res["kernels"]) == 3
    assert res["route"]["route_flips_outside_margin"] == 0
    assert res["moe"]["mixed"]["rows_routed_here"] > 0
