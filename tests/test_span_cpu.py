"""Work or wait: what a span of ``TraceCollector`` carries beside its
duration (``inference/telemetry.py``, the module docstring's WORK OR
WAIT clause).

* ``cpu`` on every span, phase and step: the opening thread's CPU time
  between open and close, from the injectable ``cpu_clock``;
* the thread's ``getrusage`` counters on the spans opened with
  ``counters=True`` (the server's ``round`` and ``submit``, and
  ``submit.embed``) and on no other;
* the interpreter's collector: seconds and count on the outermost open
  span, a span ``gc`` for a collection of generation 2;
* none of it without a collector: no second clock, no ``getrusage``, no
  ``gc`` callback in a process before its first collector.
"""
import gc
import json
import os
import resource
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (RecoverableServer, SpeculativeEngine,
                                  TokenServingModel, TraceCollector)
from paddle_tpu.inference import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import trace_report  # noqa: E402

pytestmark = pytest.mark.obs

D, HEADS, FFN, LAYERS, VOCAB = 32, 4, 64, 2, 50
COUNTERS = set(telemetry.USAGE_FIELDS)
COUNTED_SPANS = {"round", "submit", "submit.embed"}


class Clocks:
    """Two clocks a test moves by hand: reading them changes nothing,
    so a collection the interpreter starts in mid-test cannot shift a
    value."""

    def __init__(self):
        self.wall = 0.0
        self.cpu = 0.0

    def run(self, seconds):             # the thread works
        self.wall += seconds
        self.cpu += seconds

    def sleep(self, seconds):           # ... and waits
        self.wall += seconds

    def collector(self):
        return TraceCollector(clock=lambda: self.wall,
                              cpu_clock=lambda: self.cpu)


def _spans(col):
    return [ev for ev in col.events if ev["ph"] == "X"]


def _named(col, name):
    return [ev for ev in _spans(col) if ev["name"] == name]


@pytest.fixture
def no_collections():
    """The interpreter's collector runs only when the test says so."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def _server(tmp_path, collector=None, snapshot_every=3):
    tmp_path.mkdir(exist_ok=True)
    paddle.seed(0)
    embed = np.random.RandomState(1234).randn(VOCAB, D).astype(np.float32)
    tsm = TokenServingModel(
        FusedMultiTransformer(D, HEADS, FFN, num_layers=LAYERS), embed)
    eng = SpeculativeEngine(tsm, None, k=0, max_batch=2, block_size=4,
                            num_blocks=60, max_blocks_per_seq=10,
                            collector=collector)
    return RecoverableServer(eng, journal_path=str(tmp_path / "j"),
                             snapshot_path=str(tmp_path / "s"),
                             snapshot_every=snapshot_every)


def _serve(srv, rounds=6):
    rng = np.random.default_rng(3)
    rids = [srv.submit(rng.integers(0, VOCAB, 7).tolist())
            for _ in range(2)]
    for _ in range(rounds):
        srv.step()
    return {rid: srv.generated(rid) for rid in rids}


# ---------------------------------------------------------------------
# cpu, with injected clocks
# ---------------------------------------------------------------------

class TestCpuOfSpans:
    def test_nested_spans_work_and_wait(self):
        clk = Clocks()
        col = clk.collector()
        col.span_begin("outer")
        clk.run(0.010)
        col.span_begin("inner")
        clk.run(0.002)
        clk.sleep(0.030)
        col.span_end()
        clk.sleep(0.005)
        col.span_end()
        inner, outer = _spans(col)
        assert inner["dur"] == pytest.approx(0.032)
        assert inner["args"]["cpu"] == pytest.approx(0.002)
        assert outer["dur"] == pytest.approx(0.047)
        assert outer["args"]["cpu"] == pytest.approx(0.012)
        # self CPU follows from ``parent`` as self time does
        assert inner["args"]["parent"] == "outer"
        assert outer["args"]["cpu"] - inner["args"]["cpu"] == \
            pytest.approx(0.010)
        # wait is never stored
        assert "wait" not in inner["args"] and "wait" not in outer["args"]

    def test_phases_and_steps(self):
        clk = Clocks()
        col = clk.collector()
        col.begin_step(7, kind="verify")
        clk.run(0.001)                  # bookkeeping
        col.phase("model")
        clk.run(0.004)
        clk.sleep(0.020)                # held by the runtime
        col.phase("admission")
        clk.run(0.002)
        col.end_step()
        cpu = {ev["name"]: ev["args"]["cpu"] for ev in _spans(col)}
        dur = {ev["name"]: ev["dur"] for ev in _spans(col)}
        assert cpu == pytest.approx({"bookkeeping": 0.001, "model": 0.004,
                                     "admission": 0.002, "verify": 0.007})
        assert dur["model"] == pytest.approx(0.024)
        assert dur["verify"] == pytest.approx(0.027)

    def test_a_step_a_crash_left_open_and_an_aborted_unwind(self):
        clk = Clocks()
        col = clk.collector()
        col.begin_step(1)
        clk.run(0.003)
        col.begin_step(2)               # auto-closes step 1, aborted
        clk.run(0.001)
        col.end_step(aborted=True)
        steps = _named(col, "step")
        assert [ev["args"]["cpu"] for ev in steps] == \
            pytest.approx([0.003, 0.001])
        assert all(ev["args"]["aborted"] for ev in steps)
        depth = col.span_depth
        col.span_begin("round", counters=True)
        clk.run(0.002)
        col.span_begin("journal")
        clk.sleep(0.004)
        col.span_unwind(depth, aborted=True)
        journal, rnd = _named(col, "journal")[0], _named(col, "round")[0]
        assert journal["args"]["aborted"] and rnd["args"]["aborted"]
        assert journal["args"]["cpu"] == pytest.approx(0.0)
        assert rnd["args"]["cpu"] == pytest.approx(0.002)
        assert rnd["dur"] == pytest.approx(0.006)

    def test_a_span_closed_on_another_thread_carries_no_cpu(self):
        clk = Clocks()
        col = clk.collector()
        col.span_begin("handed_over", counters=True)
        clk.run(0.001)
        closer = threading.Thread(target=col.span_end)
        closer.start()
        closer.join(timeout=10)
        assert not closer.is_alive()
        (ev,) = _spans(col)
        assert ev["dur"] == pytest.approx(0.001)
        assert "cpu" not in ev["args"]          # ``thread_time`` is per
        assert not COUNTERS & set(ev["args"])   # thread, and so is rusage


class TestCpuOnTheRealClocks:
    def test_a_sleep_is_wait(self):
        col = TraceCollector()
        col.span_begin("asleep")
        time.sleep(0.05)
        col.span_end()
        (ev,) = _spans(col)
        assert ev["dur"] >= 0.05
        assert ev["args"]["cpu"] < 0.010

    def test_a_busy_loop_is_work(self):
        # the OS may take the core away in any one attempt (the suite
        # runs beside other workers): one undisturbed attempt shows it
        ratios = []
        for _ in range(5):
            col = TraceCollector()
            col.span_begin("busy")
            until = time.perf_counter() + 0.05
            while time.perf_counter() < until:
                pass
            col.span_end()
            (ev,) = _spans(col)
            ratios.append(ev["args"]["cpu"] / ev["dur"])
            if 0.8 <= ratios[-1] <= 1.2:
                break
        assert 0.8 <= ratios[-1] <= 1.2, ratios


# ---------------------------------------------------------------------
# the thread's counters
# ---------------------------------------------------------------------

class TestCounters:
    def test_on_round_submit_and_embed_and_on_no_other_span(self,
                                                            tmp_path):
        col = TraceCollector()
        srv = _server(tmp_path, col)
        _serve(srv)
        srv.close()
        names = {ev["name"] for ev in _spans(col)}
        assert COUNTED_SPANS < names and len(names) > 10
        for ev in _spans(col):
            args = ev["args"]
            assert args["cpu"] >= 0, ev
            if ev["name"] in COUNTED_SPANS:
                assert COUNTERS <= set(args), ev
                assert all(isinstance(args[k], int) and args[k] >= 0
                           for k in COUNTERS)
            else:
                assert not COUNTERS & set(args), ev
            # the collector's pauses: on the outermost spans alone
            assert ("gc" in args and "gc_n" in args) == \
                (ev["name"] in ("round", "submit")), ev

    def test_faults_are_counted_where_fresh_pages_are_written(self):
        col = TraceCollector()
        col.span_begin("fresh", counters=True)
        fresh = np.ones(64 << 20, np.uint8)     # 16 384 pages of 4 KiB
        col.span_end()
        (ev,) = _spans(col)
        # 4 KiB a fault, or 2 MiB under transparent huge pages
        assert ev["args"]["faults"] >= fresh.nbytes // (2 << 20)
        assert ev["args"]["faults_major"] == 0

    def test_a_platform_without_thread_usage_has_no_fields(
            self, monkeypatch):
        monkeypatch.setattr(telemetry, "_RUSAGE_THREAD", None)
        col = TraceCollector()
        col.span_begin("round", counters=True)
        col.span_end()
        (ev,) = _spans(col)
        assert not COUNTERS & set(ev["args"]) and "cpu" in ev["args"]


# ---------------------------------------------------------------------
# the interpreter's collector
# ---------------------------------------------------------------------

class TestCollections:
    def test_a_collection_lands_in_the_open_round_and_as_a_span(
            self, no_collections):
        col = TraceCollector()
        gc.collect()                    # outside every span: nowhere
        assert not col.events
        col.span_begin("round", counters=True)
        col.span_begin("spec_round")
        gc.collect()
        col.span_end()
        col.span_end()
        gc.collect()                    # ... and after it: nowhere
        by_name = {ev["name"]: ev for ev in _spans(col)}
        assert sorted(by_name) == ["gc", "round", "spec_round"]
        pause, rnd = by_name["gc"], by_name["round"]
        assert pause["args"]["generation"] == 2
        assert pause["args"]["collected"] >= 0
        assert pause["args"]["parent"] == "spec_round"
        assert 0 <= pause["args"]["cpu"] <= pause["dur"] + 1e-3
        assert rnd["args"]["gc_n"] == 1
        assert rnd["args"]["gc"] == pytest.approx(pause["dur"])
        assert "gc" not in by_name["spec_round"]["args"]
        # the next outermost span starts from nothing
        col.span_begin("submit", counters=True)
        col.span_end()
        assert _named(col, "submit")[0]["args"]["gc_n"] == 0
        assert _named(col, "submit")[0]["args"]["gc"] == 0.0

    def test_a_young_short_collection_is_counted_and_is_no_span(
            self, no_collections, monkeypatch):
        monkeypatch.setattr(TraceCollector, "GC_SPAN_S", 3600.0)
        col = TraceCollector()
        col.span_begin("round")
        gc.collect(0)
        gc.collect(1)
        col.span_end()
        (rnd,) = _spans(col)
        assert rnd["args"]["gc_n"] == 2 and rnd["args"]["gc"] > 0

    def test_a_collection_on_another_thread_is_not_this_spans(
            self, no_collections):
        col = TraceCollector()
        col.span_begin("round")
        other = threading.Thread(target=gc.collect)
        other.start()
        other.join(timeout=30)
        assert not other.is_alive()
        col.span_end()
        (rnd,) = _spans(col)
        assert rnd["args"]["gc_n"] == 0

    def test_one_callback_for_the_process(self):
        TraceCollector()
        TraceCollector()
        assert gc.callbacks.count(telemetry._on_gc) == 1


# ---------------------------------------------------------------------
# nothing without a collector
# ---------------------------------------------------------------------

class CountingResource:
    def __init__(self):
        self.calls = 0

    def getrusage(self, who):
        self.calls += 1
        return resource.getrusage(who)


class TestNothingWithoutACollector:
    def test_no_collector_means_no_cpu_clock_no_getrusage_no_gc_hook(
            self, tmp_path, counting_clock, monkeypatch):
        """The twin of ``test_no_collector_means_zero_clock_reads``
        for what this file is about: a server nobody traces reads
        neither clock, asks the OS nothing and hands the interpreter's
        collector to no one."""
        usage = CountingResource()
        monkeypatch.setattr(telemetry, "resource", usage)
        handed = []
        monkeypatch.setattr(TraceCollector, "on_gc",
                            lambda self, *a: handed.append(a))
        monkeypatch.setattr(telemetry, "_collectors", set())
        # (an engine snapshot reads the monotonic clock, for the
        # deadlines it rebases: here only snapshot 0 of the constructor)
        srv = _server(tmp_path, snapshot_every=0)
        built = counting_clock.calls
        streams = _serve(srv)
        gc.collect()
        srv.close()
        assert all(len(toks) >= 4 for toks in streams.values())
        assert counting_clock.calls == built <= 1
        assert usage.calls == 0 and not handed
        # ... and the counters count: the same server, traced
        col = TraceCollector()
        srv = _server(tmp_path / "traced", col)
        _serve(srv)
        srv.close()
        assert counting_clock.calls > built
        assert usage.calls == 2 * sum(
            ev["name"] in COUNTED_SPANS for ev in _spans(col))

    def test_no_gc_callback_in_a_process_before_its_first_collector(
            self, tmp_path):
        script = (
            "import gc, sys\n"
            f"sys.path.insert(0, {ROOT!r})\n"
            "from tests import test_span_cpu as t\n"
            "from pathlib import Path\n"
            "from paddle_tpu.inference import telemetry\n"
            f"srv = t._server(Path({str(tmp_path)!r}))\n"
            "t._serve(srv)\n"
            "srv.close()\n"
            "assert telemetry._on_gc not in gc.callbacks\n"
            "assert not telemetry._listening\n"
            "telemetry.TraceCollector()\n"
            "assert gc.callbacks.count(telemetry._on_gc) == 1\n"
            "print('ok')\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        done = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        assert done.stdout.strip().endswith("ok")

    def test_tracing_changes_no_token(self, tmp_path):
        want = _serve(_server(tmp_path / "off"))
        got = _serve(_server(tmp_path / "on", TraceCollector()))
        assert got == want


# ---------------------------------------------------------------------
# the operator's reader: tools/trace_report.py
# ---------------------------------------------------------------------

class TestTraceReport:
    def _trace(self, tmp_path):
        col = TraceCollector()
        srv = _server(tmp_path, col)
        _serve(srv)
        srv.close()
        return json.loads(json.dumps(col.chrome_trace(),
                                     default=telemetry._json_default))

    def test_prints_cpu_wait_counters_and_pauses(self, tmp_path):
        trace = self._trace(tmp_path)
        assert not trace_report.validate(trace)
        lines = {ln.split(":")[0].strip(): ln
                 for ln in trace_report.summarize(trace).splitlines()}
        for name in ("round", "submit", "submit.embed"):
            assert ", cpu " in lines[name] and ", wait " in lines[name]
            assert "; faults " in lines[name] and "preempted" in lines[name]
        assert "; gc " in lines["round"] and "; gc " in lines["submit"]
        assert "; gc " not in lines["submit.embed"]
        assert ", self cpu " in lines["model"]
        assert "faults" not in lines["model"]
        spans = trace_report.machine_report(trace)["spans"]
        rnd = spans["round"]
        assert rnd["cpu_s"] + rnd["wait_s"] == \
            pytest.approx(rnd["total_s"], abs=1e-5)
        assert 0 <= rnd["self_cpu_s"] <= rnd["cpu_s"]
        assert {"faults", "faults_major", "preempted", "yields", "gc_n",
                "gc_s"} <= set(rnd)
        assert "faults" not in spans["model"] and "cpu_s" in spans["model"]

    def test_a_trace_saved_before_the_fields_existed_still_prints(
            self, tmp_path):
        trace = self._trace(tmp_path)
        for ev in trace["traceEvents"]:
            for key in ("cpu", "gc", "gc_n", *COUNTERS):
                ev.get("args", {}).pop(key, None)
        path = tmp_path / "old.json"
        path.write_text(json.dumps(trace))
        assert trace_report.main([str(path)]) == 0
        text = trace_report.summarize(trace)
        assert "round: " in text
        assert "cpu" not in text and "faults" not in text
        rnd = trace_report.machine_report(trace)["spans"]["round"]
        assert set(rnd) == {"count", "total_s", "max_s", "self_s"}
