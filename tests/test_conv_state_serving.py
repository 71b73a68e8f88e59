"""The config-driven decoder core at arch ``lfm2_moe`` (gated
short-convolution layers whose state lives a SLOT in the paged cache's
state store, beside attention layers over paged K/V two heads a lane row;
``inference/decoder.py``, ``paged_cache.py``) against its plain reference
(``benchmark/reference/lfm2_moe.py``: a whole-sequence convolution, K and V
per head) at a tiny size on the CPU, through ``build_server_from_spec``.

Tiny ``lfm2_moe``: d 256, 4 query / 2 KV heads of 64 (one pool row of 128),
kernel 3, 8 experts of 32 top 2, the published layers 1..9 of a list of
twelve (a dense conv layer, then A c c c A c c c), float32.
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

from benchmark.jobs import serve_conv  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from paddle_tpu.framework import device  # noqa: E402
from paddle_tpu.inference import decoder  # noqa: E402
from paddle_tpu.inference import paged_cache as pc  # noqa: E402
from paddle_tpu.inference.moe_serving import sigmoid_route  # noqa: E402
from paddle_tpu.inference.recovery import RecoverableServer  # noqa: E402
from paddle_tpu.inference.router import build_server_from_spec  # noqa: E402

# float32 everywhere and "highest" products (tests/conftest.py): engine and
# reference differ by the order of their sums only
TOL = 1e-4
PERIOD = ["conv", "conv", "full_attention", "conv"]
TINY = {
    "model_type": "lfm2_moe", "reference": "lfm2_moe",
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 97, "weight_dtype": "float32",
    "layer_types": PERIOD * 3, "layers_run": list(range(1, 10)),
    "engine": {"mp": 1, "k": 0, "max_batch": 4, "block_size": 4,
               "num_blocks": 200, "max_blocks_per_seq": 24,
               "prefix_cache": False, "prefill_token_budget": 16,
               "kv_dtype": "float32"},
}
KINDS = [(PERIOD * 3)[i] for i in range(1, 10)]
CONV_LAYERS = [i for i, k in enumerate(KINDS) if k == "conv"]
PROBE_TRAFFIC = {"table": [[40, 8]], "clients": 1}


def _config(**engine):
    return dict(TINY, engine=dict(TINY["engine"], **engine))


def _spec(workdir, name="s", **changes):
    spec = serve_conv.server_spec(TINY, 7, workdir)
    spec.update(journal_path=os.path.join(workdir, name + ".wal"),
                snapshot_path=os.path.join(workdir, name + ".bin"))
    spec.update(changes)
    return spec


@pytest.fixture(scope="module")
def served():
    """One tiny server, its probe served once: (server, probe)."""
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_conv.build_server(TINY, 7, workdir)
        try:
            probe = serve_conv.probe_engine(server, TINY, PROBE_TRAFFIC, 7)
            yield server, probe
        finally:
            server.close()


# ---- the configuration ---------------------------------------------------

def test_from_spec_on_the_published_keys():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-1chip.json")) as f:
        config = json.load(f)
    with tempfile.TemporaryDirectory() as d:
        cfg = decoder.DecoderConfig.from_spec(
            serve_conv.server_spec(config, 3, d))
    assert (cfg.attention, cfg.residual) == ("gqa", "pre_norm")
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv", "full_attention", "conv", "conv",
                               "conv")
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.kv_pack, cfg.kv_width, cfg.attn_scale) == (2, 128, 0.125)
    assert (cfg.rope_theta, cfg.rms_norm_eps) == (1000000, 1e-5)
    assert (cfg.route_norm, cfg.route_scale, cfg.route_norm_eps) \
        == (True, 1, 1e-6)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.num_shared_experts, cfg.moe_intermediate_size) \
        == (64, 64, 4, 0, 1536)
    assert (cfg.num_dense_layers, cfg.intermediate_size,
            cfg.conv_L_cache) == (1, 11776, 3)
    assert not cfg.attn_gate and cfg.use_expert_bias
    assert all(cfg.rotates(i) for i in range(9))
    assert [cfg.is_moe(i) for i in range(9)] == [False] + [True] * 8


def test_afmoe_keeps_its_own_attention():
    """What ``lfm2_moe`` changed is the arch's: ``afmoe`` still rotates
    its sliding layers only, gates, adds 1e-20 and packs nothing."""
    cfg = decoder.DecoderConfig.from_spec(dict(
        arch="afmoe", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=8, num_dense_layers=2, intermediate_size=32))
    assert [cfg.rotates(i) for i in range(2)] == [True, False]
    assert cfg.attn_gate and cfg.route_norm_eps == 1e-20
    assert (cfg.kv_pack, cfg.kv_width) == (1, 16)


@pytest.mark.parametrize("changes, match", [
    ({"conv_bias": True}, "conv_bias"),
    ({"conv_L_cache": 1}, "conv_L_cache"),
    ({"arch": "afmoe", "head_dim": 64, "sliding_window": 8}, "conv layers"),
    ({"layer_types": ["conv", "scan"]}, "unknown layer types"),
])
def test_from_spec_refuses(changes, match):
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match=match):
            decoder.DecoderConfig.from_spec(_spec(d, **changes))


def test_heads_that_do_not_pair_are_not_packed():
    with tempfile.TemporaryDirectory() as d:
        odd = decoder.DecoderConfig.from_spec(
            _spec(d, num_key_value_heads=1, num_attention_heads=4))
        small = decoder.DecoderConfig.from_spec(
            _spec(d, hidden_size=64))            # heads of 16: 8 a row
    assert (odd.kv_pack, odd.kv_width) == (1, 64)
    assert (small.head_dim, small.kv_pack) == (16, 1)


def test_route_norm_adds_the_archs_epsilon():
    m = jnp.asarray(np.random.default_rng(0).standard_normal((5, 16)),
                    jnp.float32)
    router = jnp.asarray(np.random.default_rng(1).standard_normal((16, 8)),
                         jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)
    idx, w, s = sigmoid_route(m, router, bias, 2, True, 1.0, 0.5)
    chosen = np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / (chosen.sum(-1, keepdims=True) + 0.5),
        rtol=1e-6)
    _, w0, _ = sigmoid_route(m, router, bias, 2, True, 1.0)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 1.0, rtol=1e-6)


# ---- (a) one chunk, any chunks, packed: the reference's rows ------------

def test_probe_logits_match_the_reference(served):
    """Prefill in three chunks (16, 16, 8), then four decode steps: the
    convolution's past crosses six calls."""
    server, probe = served
    stats = {}
    err = serve_conv.compare_probe(server.engine.target, TINY, probe,
                                   tol=TOL, stats=stats)
    assert err < 1e-5 and stats["route_flips_outside_margin"] == 0
    assert stats["route_rows"] == 8 * len(probe["tokens"])
    core = server.engine.target.core
    assert core.layer_windows == (None,) * 9
    assert core.latent_cache == {"sm_scale": 0.125}
    assert (core.num_heads, core.num_kv_heads, core.head_dim) == (4, 1, 128)
    assert core.layer_state == tuple(
        (2, 256) if k == "conv" else None for k in KINDS)


@pytest.mark.parametrize("budget", [64, 11, 7, 5])
def test_any_chunking_gives_the_same_rows(budget):
    """The 40-token prompt in one chunk and in chunks of 11, 7 and 5
    (a last chunk of 7, 5 and 5 rows): each against the reference's full
    forward. (Row by row: the view tests below; the probe's loop gives a
    prompt 13 steps.)"""
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_conv.build_server(
            _config(prefill_token_budget=budget), 7, workdir)
        try:
            probe = serve_conv.probe_engine(server, TINY, PROBE_TRAFFIC, 7)
            err = serve_conv.compare_probe(server.engine.target, TINY,
                                           probe, tol=TOL)
            chunks = server.engine.engine.prefill_stats.prefill_steps
        finally:
            server.close()
    assert err < 1e-5
    assert chunks == -(-40 // budget)


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


def test_the_kernel_path_serves_the_same_logits(monkeypatch):
    """The chip's path (packed steps only, the launch interpreted over two
    heads a pool row) on a second server of the same weights."""
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_conv.build_server(TINY, 7, workdir)
        try:
            probe = serve_conv.probe_engine(server, TINY,
                                            {"table": [[24, 6]]}, 7)
            err = serve_conv.compare_probe(server.engine.target, TINY,
                                           probe, tol=TOL)
        finally:
            server.close()
    assert err < 2e-5


# ---- the mix itself, view by view, against a plain convolution ----------

def _plain_conv(u, taps):
    """u [n, d], taps [d, K]: y_t = sum_j taps[:, K-1-j] u_{t-j}."""
    n, K = u.shape[0], taps.shape[1]
    y = np.zeros_like(u)
    for j in range(K):
        y[j:] += u[:n - j] * taps[:, K - 1 - j]
    return y


def _state_cache(slots=3, rows=2, width=8, **kw):
    """Layer 0 holds K/V, layers 1 and 2 are state layers."""
    return pc.PagedKVCache(3, 2, 16, 4, 40, slots, max_blocks_per_seq=8,
                           layer_state=[None, (rows, width), (rows, width)],
                           **kw)


@pytest.mark.parametrize("cuts", [(13,), (1,) * 13, (2, 1, 5, 1, 1, 3),
                                  (6, 7)])
@pytest.mark.parametrize("kernel", [3, 4, 2])
def test_prefill_view_mix_in_any_cuts_is_the_plain_convolution(cuts,
                                                               kernel):
    rng = np.random.default_rng(kernel)
    u = rng.standard_normal((13, 8)).astype(np.float32)
    taps = rng.standard_normal((8, kernel)).astype(np.float32)
    cache = _state_cache(rows=kernel - 1)
    cache.ensure(1, 13, write_from=0)
    lo, got = 0, []
    for n in cuts:
        view = cache.prefill_views(1)[2]
        got.append(np.asarray(view.mix(jnp.asarray(u[None, lo:lo + n]),
                                       jnp.asarray(taps)))[0])
        lo += n
    np.testing.assert_allclose(np.concatenate(got), _plain_conv(u, taps),
                               rtol=1e-6, atol=1e-6)
    # the slot's last rows, oldest first; the other layer's untouched
    np.testing.assert_array_equal(np.asarray(cache.state[1][1]),
                                  u[13 - (kernel - 1):])
    assert not np.asarray(cache.state[0]).any()
    assert not np.asarray(cache.state[1])[[0, 2]].any()


def test_packed_and_decode_views_mix_as_the_plain_convolution():
    """A packed call: slot 0's second chunk, slot 2's first chunk and the
    decode rows of slots 1 (live) and 0, 2 (masked, mid-prefill); then a
    decode-only call through the batch view."""
    rng = np.random.default_rng(5)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    seq = {s: rng.standard_normal((12, 8)).astype(np.float32)
           for s in range(3)}
    want = {s: _plain_conv(seq[s], taps) for s in range(3)}
    cache = _state_cache()
    layer = 1
    # before: slot 0 has had 5 rows, slot 1 (decoding) 9 rows
    for slot, n in ((0, 5), (1, 9)):
        cache.ensure(slot, n, write_from=0)
        cache.prefill_views(slot)[layer].mix(
            jnp.asarray(seq[slot][None, :n]), jnp.asarray(taps))
    cache.ensure(0, 9, write_from=5)
    cache.ensure(2, 6, write_from=0)
    cache.ensure(1, 10)
    cache.set_decode_mask(np.array([True, False, True]))
    views = cache.ragged_views([("prefill", 0, 5, 4, 0),
                                ("prefill", 2, 0, 6, 0),
                                ("decode", np.array([9, 9, 6]), 1)])
    junk = rng.standard_normal((8,)).astype(np.float32)
    packed = np.concatenate([seq[0][5:9], seq[2][:6],
                             junk[None], seq[1][9:10], junk[None]])
    y = np.asarray(views[layer].mix(jnp.asarray(packed[None]),
                                    jnp.asarray(taps)))[0]
    np.testing.assert_allclose(y[:4], want[0][5:9], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[4:10], want[2][:6], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[11], want[1][9], rtol=1e-6, atol=1e-6)
    state = np.asarray(cache.state[cache.state_index(layer)])
    np.testing.assert_array_equal(state[0], seq[0][7:9])
    np.testing.assert_array_equal(state[1], seq[1][8:10])
    np.testing.assert_array_equal(state[2], seq[2][4:6])
    # a decode-only call, slot 2 still mid-prefill
    cache.ensure(0, 10)
    cache.ensure(1, 11)
    cache.set_decode_mask(np.array([False, False, True]))
    rows = np.stack([seq[0][9], seq[1][10], junk])[:, None]
    y = np.asarray(cache.views[layer].mix(jnp.asarray(rows),
                                          jnp.asarray(taps)))[:, 0]
    np.testing.assert_allclose(y[0], want[0][9], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[1], want[1][10], rtol=1e-6, atol=1e-6)
    state = np.asarray(cache.state[cache.state_index(layer)])
    np.testing.assert_array_equal(state[0], seq[0][8:10])
    np.testing.assert_array_equal(state[2], seq[2][4:6])     # masked: kept


def test_a_verify_call_of_several_rows_a_slot_mixes_in_order():
    """L > 1 rows a slot through the batch view (the shape a verify step
    has): slot b's rows follow each other and its stored rows."""
    rng = np.random.default_rng(8)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    seq = rng.standard_normal((2, 7, 8)).astype(np.float32)
    cache = _state_cache(slots=2)
    for slot in range(2):
        cache.ensure(slot, 7, write_from=0)
    y = np.concatenate([
        np.asarray(cache.views[1].mix(jnp.asarray(seq[:, lo:hi]),
                                      jnp.asarray(taps)))
        for lo, hi in ((0, 3), (3, 4), (4, 7))], 1)
    for slot in range(2):
        np.testing.assert_allclose(y[slot], _plain_conv(seq[slot], taps),
                                   rtol=1e-6, atol=1e-6)


# ---- (b) a slot's state is its own, and a reused slot starts from zero --

def test_other_slots_state_never_reaches_a_row():
    """Every other slot's rows poisoned with NaN before each call: the
    slot's own results do not move, and the poison stays where it was."""
    rng = np.random.default_rng(6)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    u = rng.standard_normal((9, 8)).astype(np.float32)
    cache = _state_cache()
    cache.ensure(1, 9, write_from=0)
    got = []
    for lo, hi in ((0, 4), (4, 5), (5, 9)):
        cache._flush_state_resets()
        cache.state[:] = [s.at[jnp.asarray([0, 2])].set(jnp.nan)
                          for s in cache.state]
        got.append(np.asarray(cache.prefill_views(1)[1].mix(
            jnp.asarray(u[None, lo:hi]), jnp.asarray(taps)))[0])
    np.testing.assert_allclose(np.concatenate(got), _plain_conv(u, taps),
                               rtol=1e-6, atol=1e-6)
    state = np.asarray(cache.state[0])
    assert np.isnan(state[[0, 2]]).all() and np.isfinite(state[1]).all()


def test_a_reused_slot_starts_from_zero():
    rng = np.random.default_rng(7)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    first, second = rng.standard_normal((2, 6, 8)).astype(np.float32)
    cache = _state_cache()
    cache.ensure(0, 6, write_from=0)
    cache.prefill_views(0)[1].mix(jnp.asarray(first[None]),
                                  jnp.asarray(taps))
    assert np.asarray(cache.state[0][0]).any()
    cache.free_seq(0)
    # NaN in a dead slot's rows must not survive its re-admission either
    cache.state[:] = [s.at[0].set(jnp.nan) for s in cache.state]
    cache.ensure(0, 6, write_from=0)
    y = np.asarray(cache.prefill_views(0)[1].mix(jnp.asarray(second[None]),
                                                 jnp.asarray(taps)))[0]
    np.testing.assert_allclose(y, _plain_conv(second, taps), rtol=1e-6,
                               atol=1e-6)
    assert np.isfinite(np.asarray(cache.state[1][0])).all()
    stats = cache.take_state_stats()
    assert stats["slots_reset"] == 2 and stats["segments"] == 2
    assert stats["segments_carried"] == 0
    assert stats["state_bytes"] == 2 * 3 * 2 * 8 * 4 == cache.state_bytes()


def test_requests_served_together_read_as_served_alone():
    """Greedy streams of three requests that share steps and slots with
    each other, and of a fourth that takes over a released slot: each is
    what the same request produces on a server of its own."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 97, size=n).tolist()
               for n in (19, 7, 26, 15)]

    def alone(prompt):
        with tempfile.TemporaryDirectory() as d:
            srv = build_server_from_spec(_spec(d))
            rid = srv.submit(prompt)
            for _ in range(14):
                srv.step()
            out = list(srv.generated(rid))[:8]
            srv.close()
        return out

    with tempfile.TemporaryDirectory() as d:
        srv = build_server_from_spec(_spec(d))
        rids = [srv.submit(p) for p in prompts[:3]]
        for _ in range(12):
            srv.step()
        slot = srv.engine._by_rid[rids[1]].slot
        together = [list(srv.generated(r))[:8] for r in rids]
        srv.release(rids[1])
        srv.drain_outcomes()
        late = srv.submit(prompts[3])
        for _ in range(12):
            srv.step()
        assert srv.engine._by_rid[late].slot == slot
        together.append(list(srv.generated(late))[:8])
        assert srv.check_invariants()
        srv.close()
    assert all(len(t) == 8 for t in together)
    assert together == [alone(p) for p in prompts]


# ---- (c) a preempted request re-prefills to the same tokens -------------

def test_a_preempted_request_re_prefills_to_the_same_tokens():
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 97, size=n).tolist()
               for n in (21, 22, 30, 27)]

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
            for _ in range(160):
                srv.step()
                for r in rids:          # a closed loop: release at 12
                    if r not in done and len(srv.generated(r)) >= 12:
                        done[r] = list(srv.generated(r))[:12]
                        srv.release(r)
                srv.drain_outcomes()
                if len(done) == len(rids):
                    break
            retried = eng.resilience_stats.retried
            assert srv.check_invariants()
            srv.close()
        return [done[r] for r in rids], retried

    roomy, retried = streams(200)
    assert retried == 0
    tight, retried = streams(30)
    assert retried > 0 and tight == roomy


# ---- (d) snapshot, restore, recover --------------------------------------

def test_snapshot_then_restore_carries_the_state_store(served):
    server, _ = served
    rng = np.random.default_rng(9)
    rid = server.submit(rng.integers(0, 97, size=22).tolist())
    for _ in range(4):                   # two chunks, then decode rows
        server.step()
    cache = server.engine.engine.cache
    snap = cache.snapshot()
    assert snap["geometry"]["layer_state"] == [
        [2, 256] if k == "conv" else None for k in KINDS]
    assert snap["geometry"]["sm_scale"] == 0.125
    assert snap["payload"].shape[1:] == (2, 2, 1, 4, 128)   # two K/V layers
    assert len(snap["state"]) == 7
    back = pc.PagedKVCache.restore(snap)
    assert back.layer_state == cache.layer_state
    assert back.kv_layers == (1, 5) and len(back.pools) == 2
    slot = server.engine._by_rid[rid].slot
    for a, b in zip(cache.state, back.state):
        assert np.asarray(a[slot]).any()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(cache.pools, back.pools):
        keep = snap["blocks"]
        np.testing.assert_array_equal(np.array(a.numpy())[keep],
                                      np.array(b.numpy())[keep])
    # into a smaller pool: tables are rehomed, slots and their rows not
    small = pc.PagedKVCache.restore(snap, num_blocks=40)
    np.testing.assert_array_equal(np.asarray(small.state[3]),
                                  np.asarray(cache.state[3]))
    server.release(rid)
    server.drain_outcomes()
    assert server.check_invariants()


def test_a_recovered_server_continues_mid_decode(tmp_path):
    """Serve, snapshot mid-decode, die, recover from the files behind a
    model of the same seeds: the streams of a server that never died."""
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
        assert len(cache.state) == 7 and len(cache.pools) == 2
        for _ in range(5):
            again.step()
        assert [list(again.generated(r)) for r in rids] == want
        assert again.check_invariants()
    finally:
        again.close()


# ---- (e) what would need a state snapshot is refused by name ------------

@pytest.mark.parametrize("changes", [{"prefix_cache": True}, {"k": 2},
                                     {"mp": 2}])
def test_build_refuses_what_needs_a_state_snapshot(changes):
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="state store"):
            build_server_from_spec(_spec(d, **changes))


def test_the_spec_default_prefix_cache_is_refused_too():
    with tempfile.TemporaryDirectory() as d:
        spec = _spec(d)
        del spec["prefix_cache"]          # the server's default is on
        with pytest.raises(ValueError, match="prefix_cache with a state"):
            build_server_from_spec(spec)


def test_slices_fork_and_rollback_are_refused(served):
    server, _ = served
    eng = server.engine.engine
    rid = server.submit(list(range(1, 14)))
    for _ in range(3):
        server.step()
    slot = server.engine._by_rid[rid].slot
    with pytest.raises(ValueError, match="export_slice with a state store"):
        server.export_slice(rid)
    with pytest.raises(ValueError, match="import_slice with a state store"):
        eng.cache.import_slice({"kind": "kv_slice"})
    with pytest.raises(ValueError, match="fork with a state store"):
        eng.cache.fork(slot, (slot + 1) % 4, 8)
    with pytest.raises(ValueError, match="rollback .* state store"):
        eng.rollback(slot, int(eng.lens[slot]) - 1)
    with pytest.raises(ValueError, match="truncate .* state store"):
        eng.cache.truncate(slot, 2)
    eng.rollback(slot, int(eng.lens[slot]))       # nothing rejected: fine
    server.release(rid)
    server.drain_outcomes()
    assert server.check_invariants()


def test_parallel_samples_of_one_prompt_are_refused(served):
    """``n`` > 1 forks the prompt's pages to sibling slots."""
    server, _ = served
    with pytest.raises(ValueError, match="fork .* state store"):
        server.submit(list(range(1, 10)), n=2)
    assert not server.engine._by_rid         # refused at the door
    assert server.check_invariants()


def test_the_cache_refuses_by_name():
    with pytest.raises(ValueError, match="prefix_cache with a state store"):
        _state_cache(prefix_cache=True)
    with pytest.raises(ValueError, match="state store is not split"):
        _state_cache(mp=2)
    with pytest.raises(ValueError, match="one \\(rows, width\\)"):
        pc.PagedKVCache(3, 2, 16, 4, 40, 2,
                        layer_state=[None, (2, 8), (3, 8)])
    with pytest.raises(ValueError, match="needs a layer that holds K/V"):
        pc.PagedKVCache(2, 2, 16, 4, 40, 2, layer_state=[(2, 8), (2, 8)])
    with pytest.raises(ValueError, match="layer_state has 2 entries"):
        pc.PagedKVCache(3, 2, 16, 4, 40, 2, layer_state=[None, (2, 8)])
    cache = _state_cache()
    with pytest.raises(ValueError, match="holds no K/V"):
        cache.views[1].decode(None, None, None, None)
    with pytest.raises(ValueError, match="is a K/V layer"):
        cache.views[0].mix(jnp.zeros((3, 1, 8)), jnp.zeros((8, 3)))
    cache.ensure(0, 8, write_from=0)
    cache.prefill_views(0)[1].mix(jnp.zeros((1, 4, 8)), jnp.zeros((8, 3)))
    with pytest.raises(AssertionError, match="written by two segments"):
        cache.ragged_views([("prefill", 0, 0, 4, 0),
                            ("prefill", 0, 4, 4, 0)])[1].mix(
            jnp.zeros((1, 8, 8)), jnp.zeros((8, 3)))


# ---- geometry and bytes, by hand -----------------------------------------

def test_pools_state_and_bytes_by_hand(served):
    """Nine layers, two pools: 2 planes x 1 row of 128 (two heads of 64)
    x 4 B a token a K/V layer; seven state arrays of 4 slots x 2 rows x
    256."""
    cache = served[0].engine.engine.cache
    assert cache.num_layers == 9 and len(cache.views) == 9
    assert cache.kv_layers == (1, 5)
    assert cache.state_layers == tuple(CONV_LAYERS)
    assert [cache.pool_index(i) for i in range(9)] == \
        [None, 0, None, None, None, 1, None, None, None]
    assert [cache.state_index(i) for i in range(9)] == \
        [0, None, 1, 2, 3, None, 4, 5, 6]
    assert len(cache.pools) == 2
    assert tuple(cache.pools[0].shape) == (200, 2, 1, 4, 128)
    assert (cache.num_heads, cache.num_kv_heads, cache.head_dim) \
        == (4, 1, 128)
    assert cache.latent == (None, 0.125) and cache.planes == 2
    assert cache.kv_bytes_per_token() == 2 * 2 * 1 * 128 * 4 == 2048
    assert cache.pool_bytes() == 200 * 4 * 2048
    assert [tuple(s.shape) for s in cache.state] == [(4, 2, 256)] * 7
    assert cache.state_bytes() == 7 * 4 * 2 * 256 * 4
    stats = cache.take_write_stats()
    assert stats["pool_bytes"] == cache.pool_bytes()
    # the published widths, in bfloat16
    big = pc.PagedKVCache(9, 32, 128, 16, 8, 2, dtype="bfloat16",
                          num_kv_heads=4, sm_scale=0.125,
                          layer_state=[(2, 2048) if k == "conv" else None
                                       for k in KINDS])
    assert big.kv_bytes_per_token() == 4096
    assert big.state_bytes() == 2 * 57344
    assert tuple(big.pools[1].shape) == (8, 2, 4, 16, 128)


# ---- two heads a pool row is the plain attention ------------------------

def test_packed_heads_equal_per_head_attention_on_one_layer(served):
    """``_attn_in`` / ``_attn_out`` around a plain softmax over the packed
    rows, against the reference's attention with K and V per head."""
    core = served[0].engine.target.core
    cfg, p = core.config, core.params[1]
    n = 19
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, n, 256)),
                    jnp.float32)
    pos = jnp.arange(n)[None]
    q, k, v, gate = decoder._attn_in(cfg, True, p, x, pos)
    assert gate is None
    assert q.shape == (1, n, 4, 128) and k.shape == v.shape == (1, n, 1, 128)
    # query heads 0, 1 read KV head 0 (the row's first half), 2, 3 the other
    assert not np.asarray(q[0, :, :2, 64:]).any()
    assert not np.asarray(q[0, :, 2:, :64]).any()
    s = jnp.einsum("qhd,kd->hqk", q[0], k[0, :, 0]) * cfg.attn_scale
    s = jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None], s,
                  -jnp.inf)
    attn = jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, -1),
                      v[0, :, 0])[None]                  # [1, n, 4, 128]
    h, _ = decoder._attn_out(cfg, p, x, attn, None)
    rq, rk, rv = ref._qkv(x[0], p, cfg=cfg, rounding=None, variant=None)
    assert rk.shape == rv.shape == (n, 2, 64)
    want, _ = ref._residual(x[0], ref._attention(rq, rk, rv, None), p,
                            cfg=cfg, rounding=None, w_out="o")
    np.testing.assert_allclose(np.asarray(h[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---- each mechanism, broken alone, fails the comparison -----------------

def _variant_kw(variant):
    # the tiny probe's chunks are 16 long, the cell's 1 024
    return {"variant": variant, "carry_chunk": 16}


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_broken_mechanism_fails_the_comparison(served, variant):
    server, probe = served
    with pytest.raises(AssertionError, match="reference|margin"):
        serve_conv.compare_probe(server.engine.target, TINY, probe,
                                 tol=TOL, **_variant_kw(variant))


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_broken_mechanism_moves_the_logits_by_a_wide_margin(served,
                                                              variant):
    """Without the routing check's help: the variant's own logits lie
    percent away from the unbroken reference's, float32 rounding 1e-6."""
    server, probe = served
    weights = ref.weights_of(server.engine.target)
    rows = serve_conv.probed_positions(probe)
    good = ref.logits(weights, probe["tokens"], rows=rows)
    bad = ref.logits(weights, probe["tokens"], rows=rows,
                     **_variant_kw(variant))
    err = np.linalg.norm(bad - good, axis=-1) / np.linalg.norm(good, axis=-1)
    assert err.min() > 5e-3, err


def test_the_reference_convolution_is_the_plain_one(served):
    core = served[0].engine.target.core
    cfg, p = core.config, core.params[0]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((11, 256)),
                    jnp.float32)
    got = ref._conv(x, p, cfg=cfg, rounding=None, variant=None,
                    carry_chunk=1024)
    a = np.asarray(ref._rms(x, p["in_norm"], cfg.rms_norm_eps))
    b, c, xg = np.split(a @ np.asarray(p["conv_in"]), 3, axis=-1)
    y = _plain_conv(b * xg, np.asarray(p["conv_taps"]))
    np.testing.assert_allclose(np.asarray(got),
                               (c * y) @ np.asarray(p["conv_out"]),
                               rtol=2e-4, atol=2e-5)


# ---- spans, gauge, report -------------------------------------------------

def test_conv_spans_and_the_slot_state_gauge(served):
    from paddle_tpu.inference.telemetry import TraceCollector
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_report
    server, _ = served
    eng, core = server.engine.engine, server.engine.target.core
    col = TraceCollector()
    eng.cache.take_state_stats()     # what earlier tests left uncollected
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
    spans = [e for e in col.events if e.get("ph") == "X"]
    conv = [e for e in spans if e["name"] == "conv"]
    moe = [e for e in spans if e["name"] == "moe"]
    calls = len(moe) // 8
    # one a conv layer a model call: seven of nine layers, eight expert ones
    assert len(conv) == 7 * calls and calls >= 7
    assert {e["args"]["parent"] for e in conv} == {"model", "bookkeeping"}
    assert {e["args"]["layer"] for e in conv} == set(CONV_LAYERS)
    for child in ("conv.project", "conv.mix", "conv.out"):
        got = [e for e in spans if e["name"] == child]
        assert len(got) == len(conv)
        assert all(e["args"]["parent"] == "conv" for e in got)
    assert {e["args"]["parent"] for e in moe} == {"model", "bookkeeping"}
    assert not [e for e in spans if e["name"].startswith("mla")]
    gauge = [e["args"] for e in col.events
             if e.get("ph") == "C" and e["name"] == "slot_state"]
    assert len(gauge) == calls
    # the 40-token prompt alone: chunks of 16, 16, 8; the first starts a
    # slot, the other two are carried
    assert [(g["rows"], g["prompt_segments"], g["prompt_segments_carried"],
             g["slots_reset"]) for g in gauge[:3]] == \
        [(16, 1, 0, 1), (16, 1, 1, 0), (8, 1, 1, 0)]
    # then its decode row alone, and beside the second prompt's chunks
    assert (gauge[3]["rows"], gauge[3]["segments"],
            gauge[3]["segments_carried"], gauge[3]["prompt_segments"]) \
        == (1, 1, 1, 0)
    assert (gauge[4]["rows"], gauge[4]["segments"],
            gauge[4]["segments_carried"], gauge[4]["slots_reset"]) \
        == (17, 2, 1, 1)
    assert all(g["state_bytes"] == eng.cache.state_bytes() for g in gauge)
    assert serve_conv.carried_share(col) == pytest.approx(
        100.0 * sum(g["prompt_segments_carried"] for g in gauge)
        / sum(g["prompt_segments"] for g in gauge))
    text = trace_report.summarize(col.chrome_trace())
    for needle in ("model spans:", "conv.mix:", "slot_state.segments_carried",
                   "state store:", "prompt chunks"):
        assert needle in text, needle


# ---- the chip smoke's conv phase, rehearsed ------------------------------

def test_chip_smoke_conv_phase_rehearsal():
    """``chip_smoke.conv_phase`` at toy sizes, the kernel interpreted."""
    import chip_smoke
    res = chip_smoke.conv_phase(
        hidden=256, heads=4, kv_heads=2, dense_width=96, experts=8, top_k=2,
        expert_width=32, vocab=97, prompt=32, chunk=16, block_size=4,
        max_batch=4, weight_dtype="float32", kv_dtype="float32",
        expect_kernel=False, logits_tol=1e-4, tol=1e-4)
    assert res["probe_rel_l2"] < 1e-5 and len(res["kernels"]) == 1
    assert res["mix_err"] < 1e-6
    assert res["route"]["route_flips_outside_margin"] == 0
