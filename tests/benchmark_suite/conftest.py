"""A fabricated profile-session collector for the readers of the program's
spans (``benchmark/layer_metrics/program_spans.py``).

Those readers take the collector from the program's accessor
(``telemetry.last_session_collector``), not from the run they are handed. So
``test_layer_metric_readers``, which hands every reader a run that has what it
reads and expects a number, needs a session to have been: the fixture below
plants this one for that test's program-span cases, and any test may ask for
it by name.
"""
import json
import os

import pytest

LAYER_METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "layer_metrics")

# (name, start s, duration s, round, parent): two whole rounds, one the
# profile stopped in, and two submits
FABRICATED_SPANS = [
    # round 1: 100 ms, of which 50.5 ms waiting for the device
    ("round", 0.000, 0.100, 1, None),
    ("journal", 0.000, 0.001, 1, "round"),             # the drain flush
    ("spec_round", 0.001, 0.094, 1, "round"),
    ("draft_roll", 0.001, 0.001, 1, "spec_round"),
    ("embed", 0.002, 0.004, 1, "spec_round"),
    ("verify", 0.006, 0.030, 1, "spec_round"),
    ("bookkeeping", 0.006, 0.001, 1, "verify"),
    ("prefill", 0.007, 0.003, 1, "verify"),
    ("grow", 0.008, 0.001, 1, "prefill"),
    ("bookkeeping", 0.010, 0.004, 1, "verify"),
    ("grow", 0.011, 0.002, 1, "bookkeeping"),
    ("model", 0.014, 0.018, 1, "verify"),
    ("grow", 0.014, 0.003, 1, "model"),
    ("bookkeeping", 0.032, 0.001, 1, "verify"),
    ("admission", 0.033, 0.003, 1, "verify"),
    ("sample_verify", 0.036, 0.058, 1, "spec_round"),
    ("device_wait", 0.038, 0.050, 1, "sample_verify"),
    ("device_wait", 0.0945, 0.0005, 1, "spec_round"),  # a first token
    ("journal", 0.095, 0.002, 1, "round"),
    ("snapshot", 0.097, 0.002, 1, "round"),
    # two submits before round 2 (they carry its number); the second
    # prefills at once, and its page growth is no part of round 2
    ("submit", 0.150, 0.030, 2, None),
    ("submit.journal", 0.150, 0.010, 2, "submit"),
    ("submit", 0.185, 0.010, 2, None),
    ("submit.admit", 0.186, 0.008, 2, "submit"),
    ("grow", 0.187, 0.005, 2, "submit.admit"),
    # round 2: 80 ms, 40 ms waiting
    ("round", 0.200, 0.080, 2, None),
    ("spec_round", 0.200, 0.078, 2, "round"),
    ("embed", 0.201, 0.002, 2, "spec_round"),
    ("verify", 0.203, 0.020, 2, "spec_round"),
    ("bookkeeping", 0.203, 0.002, 2, "verify"),
    ("grow", 0.204, 0.001, 2, "bookkeeping"),
    ("model", 0.205, 0.010, 2, "verify"),
    ("admission", 0.215, 0.001, 2, "verify"),
    ("sample_verify", 0.223, 0.050, 2, "spec_round"),
    ("device_wait", 0.224, 0.040, 2, "sample_verify"),
    ("journal", 0.278, 0.001, 2, "round"),
    # round 3: the profile stopped inside it
    ("round", 0.300, 0.050, 3, None),
    ("model", 0.301, 0.040, 3, "verify"),
]
# what the nine metrics read from it, worked by hand (ms)
FABRICATED_VALUES = {
    "step_host_ms_p50": (49.5 + 40.0) / 2,       # 100 - 50.5, 80 - 40
    "dispatch_ms_per_step": (15.0 + 10.0) / 2,   # model less its layout
    "sched_ms_per_step": (9.0 + 2.0) / 2,        # (3-1)+(1+4-2+1)+3; (2-1)+1
    "page_grow_ms_per_step": (6.0 + 1.0) / 2,
    "embed_sample_ms_per_step": (12.0 + 12.0) / 2,   # 4+(58-50); 2+(50-40)
    "journal_ms_per_step": (5.0 + 1.0) / 2,
    "submit_ms_per_request.chat": 20.0,
    "submit_ms_per_request.doc": 20.0,
    "queue_wait_ms_p50": 4.0,
}


def fabricated_collector():
    from paddle_tpu.inference.telemetry import TraceCollector
    col = TraceCollector()
    for name, ts, dur, rnd, parent in FABRICATED_SPANS:
        args = {"round": rnd}
        if parent:
            args["parent"] = parent
        if name == "round" and rnd == 3:
            args["partial"] = True
        col.events.append({"name": name, "ph": "X", "ts": ts, "dur": dur,
                           "args": args})
    col.events.append({"name": "compile", "ph": "i", "ts": 0.21, "s": "t",
                       "args": {"seconds": 0.5, "round": 2}})
    for rid, (submit, admit) in enumerate([(0.150, 0.152), (0.185, 0.189),
                                           (0.186, 0.196), (0.290, None)]):
        col.on_submit(rid, "default", 128)
        rec = col.requests[rid]
        rec.submit_ts, rec.admit_ts = submit, admit
    return col


def reads_program_spans(metric: str) -> bool:
    with open(os.path.join(LAYER_METRICS, metric + ".json")) as f:
        return json.load(f).get("reader") == "program_spans"


@pytest.fixture
def session(monkeypatch):
    """The fabricated collector, planted as the last profile session's."""
    from paddle_tpu.inference import telemetry
    col = fabricated_collector()
    monkeypatch.setattr(telemetry, "_session", col)
    return col


@pytest.fixture(autouse=True)
def _session_for_the_reader_test(request):
    call = getattr(request.node, "callspec", None)
    metric = call.params.get("metric") if call else None
    if getattr(request.node, "originalname", "") == \
            "test_layer_metric_readers" and isinstance(metric, dict) and \
            reads_program_spans(metric["name"]):
        request.getfixturevalue("session")
