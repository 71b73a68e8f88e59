"""The step program against the per-op step it replaces.

On the chip a model call of the paged engine is ONE compiled program
(``paged_cache.model_call`` -> ``layer_jit.call_with_state``): the
forward of the block that exists, traced whole, the K/V pools donated
through it. Here the kernel predicate is patched to true, so that both
sides take the chip's path (packed steps, the one ``pallas_call``
interpreted), and one seeded schedule is played twice at tiny widths:
once captured, once per op (``layer_jit.mark_unsafe`` on the core, the
rule that keeps a core on today's step). It holds decode-only steps,
mixed steps with two prompt chunks, a prefix-cache hit with an adopted
prefix, a copy-on-write split (a fork group), preemptions with
re-prefill, and rows masked while their prompt is mid-prefill."""
import os
import sys
import tempfile

import jax
import numpy as np
import pytest

from paddle_tpu.framework import device, layer_jit
from paddle_tpu.inference import paged_cache as pc
from paddle_tpu.inference.recovery import RecoverableServer
from paddle_tpu.inference.router import build_server_from_spec
from paddle_tpu.inference.telemetry import TraceCollector

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = dict(d_model=32, heads=4, ffn=64, layers=2, vocab=50, head_roll=1,
            max_batch=4, block_size=4, num_blocks=24, max_blocks_per_seq=12,
            prefill_token_budget=16, kv_dtype="bfloat16", prefix_cache=True)
STEPS = 60
SLOTS_CHAT = 32      # gpt3-6.7b.chat's slots: its decode-only key is (32, 1)
KINDS = ("decode_only", "mixed_two_chunks", "prefix_hit", "cow_split",
         "preempted", "masked_mid_prefill")
# Hidden rows of the two sides, relative to the row's norm. Both run the
# same float32 operations; the captured program lets XLA fuse across what
# were program boundaries (a LayerNorm into its matmul), so float32 sums
# associate differently (1e-6), and a K/V value that lands on the other
# side of a bfloat16 rounding in a page moves one attention score by 2^-8
# of one term (1e-4 of a row). A wrong page, position or mask replaces a
# whole attention output: tens of percent.
HIDDEN_TOL = 2e-3


def _schedule():
    rng = np.random.default_rng(31)

    def ids(n):
        return rng.integers(0, SPEC["vocab"], size=n).tolist()
    first = ids(13)
    return {   # step -> [(prompt, output length, submit keywords)]
        0: [(first, 6, {}), (ids(9), 12, {})],
        4: [(ids(6), 5, {}), (ids(10), 20, {})],       # two chunks a step
        10: [(first[:8] + ids(5), 6, {})],             # adopts two pages
        14: [(ids(6), 8, {"n": 2})],                   # fork: a COW split
        24: [(ids(40), 4, {})],                        # 3 steps of prefill
        26: [(ids(7), 30, {}), (ids(5), 30, {})],      # the pool runs dry
    }


def _play(per_op: bool) -> dict:
    """The schedule on a fresh server; what each step left behind."""
    with tempfile.TemporaryDirectory() as d:
        col = TraceCollector()
        srv = build_server_from_spec(dict(
            SPEC, journal_path=os.path.join(d, "j"),
            snapshot_path=os.path.join(d, "s")))
        tsm, eng = srv.engine.target, srv.engine.engine
        eng.collector = col
        if per_op:
            layer_jit.mark_unsafe(tsm.core)
        hidden, segs, cows = [], [], []
        logits, views, copy = tsm.logits, eng.cache.ragged_views, \
            eng.cache._copy_block
        tsm.logits = lambda h: (hidden.append(np.asarray(h.numpy())),
                                logits(h))[1]
        eng.cache.ragged_views = lambda desc: (
            segs.append([s[0] for s in desc]), views(desc))[1]
        eng.cache._copy_block = lambda slot, bpos, copy_=True: (
            cows.append(copy_), copy(slot, bpos, copy_))[1]
        want, steps = {}, []
        for i in range(STEPS):
            for prompt, out, kw in _schedule().get(i, ()):
                want[srv.submit(prompt, **kw)] = out
            del hidden[:], segs[:], cows[:]
            skipped = eng.prefix_stats.tokens_skipped
            retried = eng.resilience_stats.retried
            srv.step()
            kinds = set()
            if hidden and not segs:
                kinds.add("decode_only")
            for s in segs:
                if s.count("prefill") >= 2 and "decode" in s:
                    kinds.add("mixed_two_chunks")
                if "decode" in s and eng.num_prefilling:
                    kinds.add("masked_mid_prefill")
            if eng.prefix_stats.tokens_skipped > skipped:
                kinds.add("prefix_hit")
            if any(cows):
                kinds.add("cow_split")
            if eng.resilience_stats.retried > retried:
                kinds.add("preempted")
            c = eng.cache
            steps.append({
                "kinds": kinds, "hidden": [h.copy() for h in hidden],
                "streams": {r: list(srv.generated(r)) for r in want},
                "blocks": ([list(b) for b in c.seq_blocks],
                           c.allocator.refcount.tolist(),
                           len(c.allocator._free), c.allocator.num_cached,
                           eng.lens.tolist())})
            for rid, out in list(want.items()):
                if len(srv.generated(rid)) >= out:
                    srv.release(rid)
                    del want[rid]
            srv.drain_outcomes()
        assert srv.check_invariants()
        gauge = [ev["args"] for ev in col.events
                 if ev.get("ph") == "C" and ev["name"] == "step_program"]
        pools = [np.array(p.numpy(), np.float32) for p in eng.cache.pools]
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import trace_report
        report = trace_report.summarize(col.chrome_trace())
        live = all(not p.data.is_deleted() for p in eng.cache.pools)
        srv.close()
    return {"steps": steps, "gauge": gauge, "pools": pools, "live": live,
            "report": report,
            "programs": layer_jit.state_programs(tsm.core)}


@pytest.fixture(scope="module")
def played():
    saved = device.use_pallas_kernels
    device.use_pallas_kernels = lambda: True
    try:
        return {"program": _play(per_op=False), "per_op": _play(per_op=True)}
    finally:
        device.use_pallas_kernels = saved


def test_every_call_of_one_side_is_captured_and_none_of_the_other(played):
    a, b = played["program"], played["per_op"]
    assert a["gauge"] and len(a["gauge"]) == len(b["gauge"])
    assert all(g["captured"] == 1 for g in a["gauge"])
    assert a["gauge"][-1]["programs"] == a["programs"] > 2
    assert all(g == {"captured": 0, "programs": 0, "core": 1}
               for g in b["gauge"])
    assert a["live"] and b["live"]      # concrete pools between calls
    n = len(a["gauge"])
    assert (f"step program: {n} of {n} model call(s) ran as one compiled "
            f"program (100.0 %), {a['programs']} program(s) compiled"
            ) in a["report"]
    assert (f"step program: 0 of {n} model call(s) ran as one compiled "
            f"program (0.0 %), 0 program(s) compiled; per op because: "
            f"core x{n}") in b["report"]


@pytest.mark.parametrize("kind", KINDS)
def test_steps_of_a_kind_agree(played, kind):
    a, b = played["program"]["steps"], played["per_op"]["steps"]
    mine = [i for i, s in enumerate(a) if kind in s["kinds"]]
    assert mine, f"the schedule holds no {kind} step"
    assert mine == [i for i, s in enumerate(b) if kind in s["kinds"]]
    worsts = []
    for i in mine:
        assert a[i]["streams"] == b[i]["streams"]
        assert a[i]["blocks"] == b[i]["blocks"]
        assert len(a[i]["hidden"]) == len(b[i]["hidden"]) > 0
        for ha, hb in zip(a[i]["hidden"], b[i]["hidden"]):
            # (an idle slot's row is zeros on both sides)
            worst = (np.linalg.norm(ha - hb, axis=-1) / np.maximum(
                np.linalg.norm(hb, axis=-1), 1e-6)).max()
            worsts.append(worst)
            assert worst <= HIDDEN_TOL, (i, worst)
    print(f"{kind}: worst relative distance {max(worsts):.2e}")


def test_whole_schedule_agrees(played):
    a, b = played["program"], played["per_op"]
    for sa, sb in zip(a["steps"], b["steps"]):
        assert sa["streams"] == sb["streams"]
        assert sa["blocks"] == sb["blocks"]
        assert sa["kinds"] == sb["kinds"]
    # every page but the trash block: equal within the bfloat16 rounding
    # of one write (8 bits of mantissa: one unit in the last place)
    for pa, pb in zip(a["pools"], b["pools"]):
        np.testing.assert_allclose(pa[1:], pb[1:], rtol=2.0 ** -7,
                                   atol=1e-6)


def test_logits_against_the_plain_reference(monkeypatch):
    """The benchmark's own reference check, at the tiny shape, with the
    step program engaged: engine logits against ``benchmark/reference``
    (plain float32, cache-free), the benchmark's tolerance."""
    sys.path.insert(0, ROOT)
    from benchmark.jobs import serve
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    config = {"reference": "gpt3", "n_vocab": SPEC["vocab"],
              "n_heads": SPEC["heads"]}
    with tempfile.TemporaryDirectory() as d:
        srv = build_server_from_spec(dict(
            SPEC, journal_path=os.path.join(d, "j"),
            snapshot_path=os.path.join(d, "s")))
        try:
            err = serve.check_probe(srv, config, {"table": [[24, 8]]}, 7,
                                    tol=serve.LOGITS_TOL)
            assert layer_jit.state_programs(srv.engine.target.core) >= 2
        finally:
            srv.close()
    assert err < serve.LOGITS_TOL


# ---- as many programs as launch keys, all of them inside the warm-up ----

def _shape_only(monkeypatch):
    """The kernel predicate true and the launch a stand-in of the right
    shape: the schedule and the programs' keys depend on neither."""
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    monkeypatch.setattr(
        pc, "paged_attention_ragged",
        lambda q, pool, bt, q_lens, kv_lens, **kw: q + 0 * pool[0, 0, 0, 0])


def test_chat_compiles_a_program_a_launch_key_and_none_after_warmup(
        monkeypatch):
    """The first ``warmup_steps`` + 200 steps of ``gpt3-6.7b.chat``, at
    the cell's engine sizes and tiny widths: one step program for every
    distinct launch key (the ``q_lens`` of a packed call, ``(B, L)`` of
    a uniform one), every one of them first seen inside the warm-up."""
    import json
    sys.path.insert(0, ROOT)
    from benchmark.jobs import serve
    _shape_only(monkeypatch)
    with open(os.path.join(ROOT, "benchmark/configs/gpt3-6.7b.json")) as f:
        config = json.load(f)
    config.update(n_layers=1, d_model=32, n_heads=2, d_head=16, d_ff=64,
                  n_vocab=211)
    with open(os.path.join(ROOT, "benchmark/traffic/chat.json")) as f:
        traffic = json.load(f)
    keys, inner = [], layer_jit.call_with_state

    def spy(layer, inputs, state, **kw):
        keys.append(state.key)
        return inner(layer, inputs, state, **kw)
    monkeypatch.setattr(layer_jit, "call_with_state", spy)
    with tempfile.TemporaryDirectory() as d:
        server = serve.build_server(config, 11, d)
        core = server.engine.target.core
        try:
            loop = serve.ClosedLoop(server, traffic, config["n_vocab"], 11)
            for _ in range(traffic["warmup_steps"]):
                loop.step()
            warm, programs = len(keys), layer_jit.state_programs(core)
            for _ in range(200):
                loop.step()
            assert not loop.audit()
        finally:
            server.close()
    print(f"chat: {programs} step programs for {warm} calls of the warm-up")
    assert programs == len(set(keys[:warm])) > 4
    assert layer_jit.state_programs(core) == programs
    assert set(keys[warm:]) <= set(keys[:warm])
    kinds = {k[0] for k in keys}
    assert kinds == {"PagedLayerCache", "PagedRaggedView"}   # both call sites
    assert (SLOTS_CHAT, 1) in {k[1] for k in keys}


def test_a_trace_that_raises_leaves_the_pools_and_the_shape_per_op(
        monkeypatch):
    """The first trace of a shape raises: the call runs per op and so
    does every later call of that shape, without another trace; the
    pools stay concrete arrays (nothing was donated, no tracer stays
    bound); other shapes still capture; the gauge says why."""
    _shape_only(monkeypatch)
    launch, traced = pc.paged_attention_ragged, []

    def fragile(q, pool, bt, q_lens, kv_lens, **kw):
        if layer_jit._state.active and len(q_lens) == SPEC["max_batch"]:
            traced.append(q_lens)
            raise RuntimeError("this shape does not trace")
        return launch(q, pool, bt, q_lens, kv_lens, **kw)
    monkeypatch.setattr(pc, "paged_attention_ragged", fragile)
    with tempfile.TemporaryDirectory() as d:
        col = TraceCollector()
        srv = build_server_from_spec(dict(
            SPEC, journal_path=os.path.join(d, "j"),
            snapshot_path=os.path.join(d, "s")))
        eng = srv.engine.engine
        eng.collector = col
        try:
            rid = srv.submit(list(range(9)))
            for _ in range(6):           # a prompt-only step, then decode
                srv.step()
                for p in eng.cache.pools:
                    assert isinstance(p.data, jax.Array)
                    assert not isinstance(p.data, jax.core.Tracer)
                    assert not p.data.is_deleted()
            assert len(srv.generated(rid)) == 6
            assert srv.check_invariants()
        finally:
            srv.close()
    assert len(traced) == 1              # one failed trace, never again
    gauge = [ev["args"] for ev in col.events
             if ev.get("ph") == "C" and ev["name"] == "step_program"]
    assert gauge[0] == {"captured": 1, "programs": 1}    # the prompt alone
    assert gauge[1:] and all(g == {"captured": 0, "programs": 1,
                                   "trace_failed": 1} for g in gauge[1:])


# ---- the programs live on the MODEL and outlive a cache ----
# ``layer_jit`` keeps a program by (layer, signature, state key); the
# cache behind a model changes under it: ``PagedServingEngine.restore``
# builds a fresh ``PagedKVCache``, and two engines may serve one model.
# The second cache of a geometry hits the compiled program and traces
# nothing, so nothing the host books may hang on a trace having run.

def _gauge(col, name):
    return [ev["args"] for ev in col.events
            if ev.get("ph") == "C" and ev["name"] == name]


def _count_traces(monkeypatch):
    traces, lend = [], pc._LentStep.lend

    def counted(self, donated, plain):
        traces.append(self.key)
        return lend(self, donated, plain)
    monkeypatch.setattr(pc._LentStep, "lend", counted)
    return traces


def _spec(d, name, **kw):
    return {**SPEC, "journal_path": os.path.join(d, name + ".j"),
            "snapshot_path": os.path.join(d, name + ".s"), **kw}


def _moved_every_step(col):
    """Every step with a captured model call counted its pool writes:
    what ``pool_write`` reads is booked a RUN, from the call's shapes."""
    calls = _gauge(col, "step_program")
    assert calls and all(g["captured"] == 1 for g in calls)
    writes = [w for w in _gauge(col, "pool_write") if w["rows_written"]]
    assert len(writes) >= len(calls) // 2      # at most two calls a step
    assert all(w["pages_written"] >= SPEC["layers"] for w in writes)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_a_recovered_engine_runs_the_programs_its_model_holds(
        monkeypatch, tmp_path, kv_dtype):
    """Serve, snapshot, die, recover behind the SAME model, serve on:
    the restored engine's fresh cache runs the programs compiled for
    the dead one's (no new trace of a known shape), its pools (and
    int8 scales) are rebound, its writes counted, and the streams are
    those of a server that never died. (Not bfloat16 pages: a snapshot
    of those does not load, ``ml_dtypes.bfloat16`` is no allowed
    global of ``recovery._restricted_loads``; PERF.md section 7.)"""
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    traces = _count_traces(monkeypatch)
    d = str(tmp_path)
    prompts = [list(range(9)), list(range(20, 27))]

    whole = build_server_from_spec(_spec(d, "whole", kv_dtype=kv_dtype))
    rids = [whole.submit(p) for p in prompts]
    for _ in range(10):
        whole.step()
    want = [list(whole.generated(r)) for r in rids]
    whole.close()

    spec = _spec(d, "dies", snapshot_every=3, kv_dtype=kv_dtype)
    srv = build_server_from_spec(spec)
    tsm = srv.engine.target
    assert [srv.submit(p) for p in prompts] == rids
    for _ in range(5):                  # a snapshot at 3, two rounds on
        srv.step()
    srv.close()
    programs, seen = layer_jit.state_programs(tsm.core), set(traces)
    assert programs >= 2
    del traces[:]

    col = TraceCollector()
    again = RecoverableServer.recover(
        tsm, None, journal_path=spec["journal_path"],
        snapshot_path=spec["snapshot_path"], collector=col)
    try:
        assert again.engine.engine.cache is not srv.engine.engine.cache
        for _ in range(5):
            again.step()
            assert all(not p.data.is_deleted()
                       for p in again.engine.engine.cache.pools)
        assert [list(again.generated(r)) for r in rids] == want
        assert again.check_invariants()
    finally:
        again.close()
    assert not set(traces) & seen       # known shapes: compiled already
    assert layer_jit.state_programs(tsm.core) == programs + len(set(traces))
    _moved_every_step(col)


def test_two_engines_behind_one_model_share_its_programs(monkeypatch,
                                                         tmp_path):
    """Two servers, one model, steps interleaved: the second engine's
    calls run the first one's programs against its OWN pools, and each
    serves what it serves alone."""
    from paddle_tpu.inference.speculative import SpeculativeEngine
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    traces = _count_traces(monkeypatch)
    d = str(tmp_path)
    prompts = {"a": list(range(9)), "b": list(range(30, 39))}

    def alone(name):
        srv = build_server_from_spec(_spec(d, name + "-alone"))
        rid = srv.submit(prompts[name])
        for _ in range(6):
            srv.step()
        out = list(srv.generated(rid))
        srv.close()
        return out
    want = {name: alone(name) for name in prompts}
    del traces[:]

    first = build_server_from_spec(_spec(d, "a"))
    tsm = first.engine.target
    spec = _spec(d, "b")
    cols = {"a": TraceCollector(), "b": TraceCollector()}
    second = RecoverableServer(
        SpeculativeEngine(
            tsm, None, k=0, max_batch=spec["max_batch"],
            block_size=spec["block_size"], num_blocks=spec["num_blocks"],
            max_blocks_per_seq=spec["max_blocks_per_seq"],
            prefix_cache=True, kv_dtype=spec["kv_dtype"],
            prefill_token_budget=spec["prefill_token_budget"]),
        journal_path=spec["journal_path"],
        snapshot_path=spec["snapshot_path"])
    servers = {"a": first, "b": second}
    try:
        rid = {}
        for name, srv in servers.items():
            srv.engine.engine.collector = cols[name]
            rid[name] = srv.submit(prompts[name])
        for _ in range(6):
            first.step()
            mine = len(traces)
            second.step()               # the same shapes, a step behind
            assert len(traces) == mine
        for name, srv in servers.items():
            assert list(srv.generated(rid[name])) == want[name]
            assert srv.check_invariants()
            _moved_every_step(cols[name])
    finally:
        first.close()
        second.close()
    assert layer_jit.state_programs(tsm.core) == len(set(traces))


def test_a_run_discarded_as_unsafe_is_not_counted(monkeypatch, tmp_path):
    """A forward that leaves a tracer in the layer is found out only
    after its program ran: the run is discarded (``core``), the per-op
    run repeats the step, and ``pool_write`` reads ONE step's writes,
    what a core that never captures reads."""
    _shape_only(monkeypatch)
    d = str(tmp_path)

    def serve(name, leaky):
        srv = build_server_from_spec(_spec(d, name))
        core, col = srv.engine.target.core, TraceCollector()
        srv.engine.engine.collector = col
        if leaky:
            forward = type(core).forward

            def leaves_a_tracer(self, *a, **kw):
                self._kept = forward(self, *a, **kw)
                return self._kept
            monkeypatch.setattr(type(core), "forward", leaves_a_tracer)
        else:
            layer_jit.mark_unsafe(core)
        try:
            rid = srv.submit(list(range(9)))
            for _ in range(4):
                srv.step()
            return (list(srv.generated(rid)), _gauge(col, "step_program"),
                    _gauge(col, "pool_write"))
        finally:
            srv.close()
            monkeypatch.undo()
            _shape_only(monkeypatch)
    want, calls, writes = serve("never", leaky=False)
    got, leaky_calls, leaky_writes = serve("leaky", leaky=True)
    assert got == want
    assert leaky_calls == calls and all(
        g == {"captured": 0, "programs": 0, "core": 1} for g in calls)
    assert leaky_writes == writes and writes[0]["rows_written"] > 0
