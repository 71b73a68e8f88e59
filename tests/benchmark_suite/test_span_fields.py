"""The six per-layer metrics that read what a span carries beside its
duration (``benchmark/layer_metrics/span_fields.py``): each file loads, and its
reader gives the hand-worked value on a fabricated session that has the fields
(``planted_host_clock.py``) and nothing on one that has not."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402
from benchmark.layer_metrics import span_fields  # noqa: E402

from .conftest import fabricated_collector  # noqa: E402
from .planted_host_clock import (PLANTED_VALUES,  # noqa: E402
                                 planted_collector)

MANIFEST = cells.load_manifest(ROOT)
METRICS = sorted(PLANTED_VALUES)
TRACED = {"trace": {"steps": 2}}
CELLS = ["gpt3-6.7b.chat", "gpt3-6.7b.doc", "trinity-large.mixed-queue"]


@pytest.fixture
def planted(monkeypatch):
    from paddle_tpu.inference import telemetry
    col = planted_collector()
    monkeypatch.setattr(telemetry, "_session", col)
    return col


@pytest.mark.parametrize("metric", METRICS)
def test_the_manifest_names_the_metric(metric):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    counted = metric in ("submit_faults_per_request",
                         "host_preempted_per_step")
    assert entry["unit"] == ("count" if counted else "ms")
    assert entry["source"] == ("program_counter" if counted
                               else "program_span")
    assert entry["better"] == "lower" and entry["workloads"] == CELLS
    assert entry["moves"] == "serve_tok_per_s"
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["unit"] == entry["unit"] and spec["reader"] == "span_fields"
    assert spec["args"]["per"] in ("round", "submit")


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_the_hand_worked_value(metric, planted):
    got = cells.read_layer_metric(metric, TRACED)
    assert got["value"] == pytest.approx(PLANTED_VALUES[metric])


def test_each_cpu_twin_takes_its_wall_metrics_picks():
    def args(metric):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               metric + ".json")) as f:
            return json.load(f)["args"]
    for wall, twin in (("dispatch_ms_per_step", "dispatch_cpu_ms_per_step"),
                       ("embed_sample_ms_per_step",
                        "embed_sample_cpu_ms_per_step")):
        assert args(twin) == dict(args(wall), field="cpu")
    # ... and the median's twin is the mean over the median rounds
    assert args("step_host_cpu_ms_p50") == dict(
        args("step_host_ms_p50"), field="cpu", stat="midmean")


def test_midmean_is_the_median_rounds_value_on_a_clock_that_ticks(planted):
    """Twelve rounds of 10 to 21 ms of host time, all of it work, on a CPU
    clock of 10 ms ticks: a round reads 10 or 20 ms. The median of those is a
    tick; the mean over the six rounds in the middle BY WALL TIME is not."""
    planted.events.clear()
    true = [10.0 + i for i in range(12)]
    phase = 0.0
    for r, ms in enumerate(true, 1):
        ticks = int((phase + ms) // 10) - int(phase // 10)
        phase += ms
        planted.events.append({
            "name": "round", "ph": "X", "ts": r * 1.0, "dur": ms / 1e3,
            "args": {"round": r, "cpu": ticks * 0.010}})
    ticked = [1e3 * ev["args"]["cpu"] for ev in planted.events]
    assert set(ticked) == {10.0, 20.0}
    got = span_fields.read(TRACED, "round", "cpu", stat="midmean",
                           spans=["round"])
    # rounds 4 to 9 by wall time: 13 to 18 ms, of 90 ms of ticks
    middle = ticked[3:9]
    assert got == pytest.approx(sum(middle) / 6)
    assert abs(got - 15.5) < 2.0


def test_work_and_wait_sum_to_the_wall_metric(planted):
    read = lambda m: cells.read_layer_metric(m, TRACED)["value"]  # noqa: E731
    # per round: 49.5 = 34.0 + 15.5 and 40.0 = 28.0 + 12.0
    wall = span_fields.read(TRACED, "round", "cpu", spans=["round"],
                            less=["device_wait"]) + \
        read("host_wait_ms_per_step")
    assert wall == pytest.approx((49.5 + 40.0) / 2)
    assert read("step_host_cpu_ms_p50") <= read("step_host_ms_p50")
    assert read("dispatch_cpu_ms_per_step") <= read("dispatch_ms_per_step")
    assert read("embed_sample_cpu_ms_per_step") <= \
        read("embed_sample_ms_per_step")
    # the other fields read through the same picks
    assert span_fields.read(TRACED, "round", "gc", spans=["round"]) == \
        pytest.approx(0.2)
    assert span_fields.read(TRACED, "submit", "wait") == pytest.approx(0.0)
    with pytest.raises(ValueError):
        span_fields.read(TRACED, "request", "cpu")


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_where_there_is_nothing_to_read(metric,
                                                             monkeypatch):
    from paddle_tpu.inference import telemetry
    # a session of a program whose spans lack the fields (this PR's parent)
    monkeypatch.setattr(telemetry, "_session", fabricated_collector())
    assert cells.read_layer_metric(metric, TRACED) is None
    # no session; an untraced run after a session; no whole round
    monkeypatch.setattr(telemetry, "_session", None)
    assert cells.read_layer_metric(metric, TRACED) is None
    col = planted_collector()
    monkeypatch.setattr(telemetry, "_session", col)
    assert cells.read_layer_metric(metric, {"trace": None}) is None
    col.events.clear()
    assert cells.read_layer_metric(metric, TRACED) is None
    # a program that has no accessor
    monkeypatch.delattr(telemetry, "last_session_collector")
    assert cells.read_layer_metric(metric, TRACED) is None


def test_a_live_session_feeds_the_readers(tmp_path, monkeypatch):
    """End to end on the CPU: a server under a real profile, then every
    reader finds its field, and work is no more than the wall time."""
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
    got = {m: cells.read_layer_metric(m, TRACED) for m in METRICS}
    assert all(v is not None and v["value"] >= 0 for v in got.values()), got
    wall = cells.read_layer_metric("step_host_ms_p50", TRACED)["value"]
    assert got["step_host_cpu_ms_p50"]["value"] <= wall + 0.05
    assert got["host_wait_ms_per_step"]["value"] >= -0.05
