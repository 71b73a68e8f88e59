"""The nine per-layer metrics that read the program's spans: each file loads,
and its reader gives nothing without a profile session and the hand-computed
value on a fabricated one (``conftest.py``)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402
from benchmark.layer_metrics import program_spans  # noqa: E402

from .conftest import FABRICATED_VALUES, fabricated_collector  # noqa: E402

MANIFEST = cells.load_manifest(ROOT)
METRICS = sorted(FABRICATED_VALUES)
TRACED = {"trace": {"steps": 2}}


def test_the_manifest_has_the_nine():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]
               if m["source"] == "program_span"}
    assert sorted(entries) == METRICS
    assert MANIFEST["per_layer"][-9:] == [entries[m["name"]] for m in
                                          MANIFEST["per_layer"][-9:]]
    for m in entries.values():
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m["workloads"] and all(
            w.startswith("gpt3-6.7b.") for w in m["workloads"])
    assert entries["queue_wait_ms_p50"]["moves"] == "ttft_p50_ms"
    assert entries["submit_ms_per_request.doc"]["workloads"] == \
        ["gpt3-6.7b.doc"]


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_loads(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["unit"] == "ms" and spec["reader"] == "program_spans"
    assert spec["args"]["per"] in ("round", "submit", "request")
    assert spec["args"].get("stat", "mean") in ("mean", "p50")


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_the_hand_computed_value(metric, session):
    got = cells.read_layer_metric(metric, TRACED)
    assert got["unit"] == "ms"
    assert got["value"] == pytest.approx(FABRICATED_VALUES[metric])


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_without_a_session(metric, monkeypatch):
    from paddle_tpu.inference import telemetry
    monkeypatch.setattr(telemetry, "_session", None)
    assert cells.read_layer_metric(metric, TRACED) is None
    # ... nor from an untraced run after an earlier session
    monkeypatch.setattr(telemetry, "_session", fabricated_collector())
    assert cells.read_layer_metric(metric, {"trace": None}) is None
    # ... nor from a program that has no accessor (this PR's parent)
    monkeypatch.delattr(telemetry, "last_session_collector")
    assert cells.read_layer_metric(metric, TRACED) is None


def test_rounds_the_profile_stopped_in_are_left_out(session):
    assert sorted(program_spans.whole_rounds(session)) == [1, 2]
    assert program_spans.round_values(session, spans=("model",)) == \
        pytest.approx([18.0, 10.0])
    # a session with no whole round reads as nothing, not as zero
    session.events.clear()
    assert cells.read_layer_metric("dispatch_ms_per_step", TRACED) is None
    assert cells.read_layer_metric("submit_ms_per_request.doc",
                                   TRACED) is None


def test_a_live_session_feeds_the_readers(tmp_path, monkeypatch):
    """End to end on the CPU: a server from ``build_server_from_spec`` under
    a real profile, then every reader finds something."""
    import jax
    import numpy as np
    from paddle_tpu.inference import telemetry
    from paddle_tpu.inference.router import build_server_from_spec
    monkeypatch.setattr(telemetry, "_session", None)
    srv = build_server_from_spec(dict(
        d_model=32, heads=2, ffn=64, layers=1, vocab=50, head_roll=1,
        max_batch=2, block_size=4, num_blocks=40, max_blocks_per_seq=10,
        prefill_token_budget=8,
        journal_path=str(tmp_path / "j"), snapshot_path=str(tmp_path / "s")))
    rng = np.random.default_rng(0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        for _ in range(2):
            srv.submit(rng.integers(0, 50, 11).tolist())
        for _ in range(5):
            srv.step()
    finally:
        jax.profiler.stop_trace()
    srv.step()
    srv.close()
    for metric in METRICS:
        got = cells.read_layer_metric(metric, TRACED)
        assert got is not None and got["value"] >= 0, metric
    rounds = program_spans.whole_rounds(telemetry.last_session_collector())
    assert len(rounds) == 5
    host = cells.read_layer_metric("step_host_ms_p50", TRACED)["value"]
    parts = sum(cells.read_layer_metric(m, TRACED)["value"] for m in (
        "dispatch_ms_per_step", "sched_ms_per_step", "page_grow_ms_per_step",
        "embed_sample_ms_per_step", "journal_ms_per_step"))
    assert 0 < parts <= 5 * host       # the parts are of the order of the whole


def test_the_programs_tool_reproduces_the_harness_on_the_recorded_trace():
    """``paddle_tpu.profiler.idle_gaps_by_span`` repeats ``benchmark/xplane``'s
    interval arithmetic (the program does not import the benchmark): on the
    recorded chip trace, booked on the harness's own spans with no offset, the
    two agree to the nanosecond."""
    from benchmark import xplane
    from paddle_tpu.profiler import idle_gaps_by_span
    fixture = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")
    want = xplane.summarize(fixture)
    got = idle_gaps_by_span(fixture, prefix="bench.", offset_ns=0)
    assert got["offset_pairs"] == 0 and got["estimated_offset_ns"] == 0
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    gaps = dict(got["gap_seconds"])
    assert gaps.pop("(no span)") == pytest.approx(
        want["gap_seconds"][xplane.NO_SPAN])
    assert gaps == pytest.approx(
        {k: v for k, v in want["gap_seconds"].items()
         if k != xplane.NO_SPAN})
    # moved by an offset, the window moves with the spans: time is shifted
    # between neighbours, none is lost
    moved = idle_gaps_by_span(fixture, prefix="bench.", offset_ns=50_000)
    assert moved["window_s"] == pytest.approx(want["window_s"])
    assert moved["idle_s"] + moved["busy_s"] == pytest.approx(
        want["window_s"])
    with pytest.raises(ValueError):
        idle_gaps_by_span(fixture)             # it holds no pt.* span
