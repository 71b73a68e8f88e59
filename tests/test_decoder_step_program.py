"""The config-driven block's step program against its per-op step.

On the chip a model call of a ``DecoderCore`` is ONE compiled program
(``paged_cache.model_call`` -> ``layer_jit.call_with_state``, the core's
pure forward): all layers, every page-form append and paged-attention
launch, every short-convolution mix through the state store, every router
and grouped GEMM, with the pools, the state store and the expert counters
donated through it. Here the kernel predicate is patched to true, so both
sides take the chip's path (packed steps, the kernels interpreted), and
one seeded schedule is played twice on a tiny stack of each arch: once
captured, once per op (``layer_jit.mark_unsafe`` on the core). It holds
a step of two prompts alone, decode-only steps, and two prompts beside
the decode rows while their own rows are masked mid-prefill. The
captured side then dies and is recovered behind the SAME model.

Tiny stacks, float32, cut from the specs of ``test_afmoe_serving``
(sliding, sliding, full), ``test_mla_serving`` (the latent page form:
dense, expert, expert) and ``test_conv_state_serving`` (conv layers and
the state store: conv, attention, conv).
"""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.jobs import serve_arch, serve_conv, serve_latent  # noqa: E402
from paddle_tpu.framework import device, layer_jit  # noqa: E402
from paddle_tpu.inference import paged_cache as pc  # noqa: E402
from paddle_tpu.inference.recovery import RecoverableServer  # noqa: E402
from paddle_tpu.inference.router import build_server_from_spec  # noqa: E402
from paddle_tpu.inference.telemetry import TraceCollector  # noqa: E402
from tests import test_afmoe_serving as afmoe  # noqa: E402
from tests import test_conv_state_serving as conv  # noqa: E402
from tests import test_mla_serving as mla  # noqa: E402

SLIDING, FULL = "sliding_attention", "full_attention"
ARCHS = {
    "afmoe": (dict(afmoe.TINY, layer_types=[SLIDING, SLIDING, FULL],
                   layers_run=[0, 1, 2]), serve_arch),
    "joyai_llm_flash": (dict(mla.TINY, layers_run=[0, 1, 2]), serve_latent),
    "lfm2_moe": (dict(conv.TINY, layers_run=[1, 2, 3]), serve_conv)}
STEPS = 8
# Float32 on both sides: the program lets XLA fuse across what were
# program boundaries, so sums associate differently (about 1e-6 of a
# value). A wrong page, position, state row or mask moves whole rows.
TOL = 1e-4


def _schedule(vocab):
    rng = np.random.default_rng(41)

    def ids(n):
        return rng.integers(0, vocab, size=n).tolist()
    # step -> [(prompt, output length)]; a step takes 16 prompt rows, so
    # the programs are three: two prompts alone, decode rows alone, and
    # two whole prompts beside the decode rows (their own rows masked)
    return {0: [(ids(12), 12), (ids(4), 12)],    # still served when it dies
            2: [(ids(10), 3), (ids(6), 3)]}


def _spec(arch, workdir, name):
    config, job = ARCHS[arch]
    spec = job.server_spec(config, 7, workdir)
    spec.update(journal_path=os.path.join(workdir, name + ".wal"),
                snapshot_path=os.path.join(workdir, name + ".bin"),
                snapshot_every=3)
    return spec


def _gauge(col, name):
    return [ev["args"] for ev in col.events
            if ev.get("ph") == "C" and ev["name"] == name]


def _play(arch, per_op, workdir) -> dict:
    """The schedule on a fresh server; what it left behind. The captured
    side then recovers behind the same model and serves three steps on
    (``restored``)."""
    spec = _spec(arch, workdir, "per_op" if per_op else "program")
    srv = build_server_from_spec(spec)
    tsm, eng = srv.engine.target, srv.engine.engine
    core, col = tsm.core, TraceCollector()
    eng.collector = col
    if per_op:
        layer_jit.mark_unsafe(core)
    traces, lend = [], pc._LentStep.lend

    def counted(self, donated, plain):
        traces.append(self.key)
        return lend(self, donated, plain)
    pc._LentStep.lend = counted
    core.route_tap = []
    try:
        want, done, taps = {}, {}, []
        for i in range(STEPS):
            for prompt, out in _schedule(ARCHS[arch][0]["vocab_size"]) \
                    .get(i, ()):
                want[srv.submit(prompt)] = out
            del core.route_tap[:]
            srv.step()
            taps.append([(layer, type(view).__name__, view._layer,
                          np.asarray(pos), np.asarray(idx),
                          isinstance(pos, jax.Array)
                          and isinstance(idx, jax.Array))
                         for layer, view, pos, idx in core.route_tap])
            for rid, out in list(want.items()):
                done[rid] = list(srv.generated(rid))
                if len(done[rid]) >= out:
                    srv.release(rid)
                    del want[rid]
            srv.drain_outcomes()
        assert want and srv.check_invariants()
        core.route_tap = None
        c = eng.cache
        got = {
            "streams": done,
            "pools": [np.array(p.numpy(), np.float32) for p in c.pools],
            "state": [np.array(s, np.float32) for s in c.state],
            "moe": core.moe_metrics(),
            "slot_state": _gauge(col, "slot_state"),
            "step_program": _gauge(col, "step_program"),
            "spans": {ev["name"] for ev in col.events if ev.get("ph") == "X"},
            "taps": taps,
            "programs": layer_jit.state_programs(core),
        }
        srv.close()
        if per_op:
            return got
        seen = set(traces)
        del traces[:]
        col = TraceCollector()
        again = RecoverableServer.recover(
            tsm, None, journal_path=spec["journal_path"],
            snapshot_path=spec["snapshot_path"], collector=col)
        try:
            assert again.engine.engine.cache is not c
            for _ in range(3):
                again.step()
            got["restored"] = {
                "seen": seen, "traced": set(traces),
                "streams": [len(again.generated(r)) for r in want],
                "step_program": _gauge(col, "step_program"),
                "programs": layer_jit.state_programs(core),
                "invariants": again.check_invariants()}
        finally:
            again.close()
        return got
    finally:
        pc._LentStep.lend = lend
        core.route_tap = None


@pytest.fixture(scope="module", params=sorted(ARCHS))
def played(request, tmp_path_factory):
    saved = device.use_pallas_kernels
    device.use_pallas_kernels = lambda: True
    try:
        d = str(tmp_path_factory.mktemp(request.param))
        return request.param, {"program": _play(request.param, False, d),
                               "per_op": _play(request.param, True, d)}
    finally:
        device.use_pallas_kernels = saved


def test_the_captured_engine_serves_what_the_per_op_one_does(played):
    arch, got = played
    a, b = got["program"], got["per_op"]
    assert a["streams"] == b["streams"]
    assert len(a["streams"]) == 4 and all(a["streams"].values())
    for pa, pb in zip(a["pools"], b["pools"]):
        np.testing.assert_allclose(pa[1:], pb[1:], rtol=TOL, atol=TOL)
    assert len(a["state"]) == len(b["state"]) == (
        2 if arch == "lfm2_moe" else 0)
    for sa, sb in zip(a["state"], b["state"]):
        np.testing.assert_allclose(sa, sb, rtol=TOL, atol=TOL)
    assert a["moe"] == b["moe"]
    assert a["moe"]["mixed"]["calls"] and a["moe"]["decode"]["calls"]
    # the state store's counts, booked after the run on one side and
    # inside the mix on the other
    assert a["slot_state"] == b["slot_state"]
    if arch == "lfm2_moe":
        assert sum(g["slots_reset"] for g in a["slot_state"]) == 4
        assert sum(g["segments_carried"] for g in a["slot_state"]) > 0


def test_every_call_of_one_side_is_captured_and_none_of_the_other(played):
    arch, got = played
    a, b = got["program"], got["per_op"]
    assert a["step_program"] and len(a["step_program"]) == \
        len(b["step_program"])
    assert all(g["captured"] == 1 for g in a["step_program"])
    assert a["step_program"][-1]["programs"] == a["programs"] >= 3
    assert all(g == {"captured": 0, "programs": 0, "core": 1}
               for g in b["step_program"])
    # the per-layer spans exist on the per-op path only
    mine = {"afmoe": "moe", "joyai_llm_flash": "mla", "lfm2_moe": "conv"}
    assert mine[arch] in b["spans"] and "moe" in b["spans"]
    assert not {"moe", "mla", "conv"} & a["spans"]


def test_route_tap_takes_concrete_tuples_equal_to_the_per_op_calls(played):
    """What ``serve_arch.take_routes`` reads: (layer, view, positions,
    chosen experts) of every expert layer call, concrete arrays, the same
    on both sides."""
    _, got = played
    a, b = got["program"]["taps"], got["per_op"]["taps"]
    assert len(a) == len(b) == STEPS and sum(map(len, a)) > 0
    for step_a, step_b in zip(a, b):
        assert len(step_a) == len(step_b)
        for ta, tb in zip(step_a, step_b):
            assert ta[:3] == tb[:3]            # layer, view kind, its layer
            assert ta[0] == ta[2]
            np.testing.assert_array_equal(ta[3], tb[3])
            np.testing.assert_array_equal(ta[4], tb[4])
            assert ta[5] and tb[5]             # concrete, on the device


def test_a_restored_engine_traces_nothing_new(played):
    """Serve with snapshots, die, recover behind the SAME model and serve
    on: the fresh cache runs the programs compiled for the dead one's
    (no known key is traced again), and every call is captured."""
    _, got = played
    a = got["program"]
    r = a["restored"]
    assert r["seen"] and not r["traced"] & r["seen"]
    assert r["programs"] == a["programs"] + len(r["traced"])
    assert r["streams"] and all(r["streams"]) and r["invariants"]
    assert r["step_program"] and all(g["captured"] == 1
                                     for g in r["step_program"])
