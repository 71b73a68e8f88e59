"""A fabricated profile session WITH what a span carries beside its duration
(``cpu``; the thread's counters and the collector's pauses on ``round`` and
``submit``), for the reader ``benchmark/layer_metrics/span_fields.py``, and
what each of its six metrics reads from it, worked by hand.

The spans are ``conftest.py``'s ``FABRICATED_SPANS`` (two whole rounds, two
submits, a round the profile stopped in). A span is pure work (``cpu`` equals
its duration) unless ``PLANTED_FIELDS`` says otherwise; the spans around a span
that waits carry the same wait. ``tests/conftest.py`` plants this session
around ``test_layer_metric_readers`` for the metrics listed in
``PLANTED_VALUES``; ``test_span_fields.py`` holds the reader to the values.
"""
from .conftest import fabricated_collector

# (name, start s): what the span's ``args`` gain; ``cpu`` in seconds
PLANTED_FIELDS = {
    # round 1, 100 ms: the token read waits 49.5 + 0.4 ms, the argmax that
    # ``sample_verify`` hands over before it 5 ms, the ``model`` phase 9 ms
    # in the runtime, the round's journal append 1.5 ms on the disk
    ("device_wait", 0.038): {"cpu": 0.0005},
    ("device_wait", 0.0945): {"cpu": 0.0001},
    ("sample_verify", 0.036): {"cpu": 0.0035},       # self 8 ms, 3 of them work
    ("model", 0.014): {"cpu": 0.009},                # self 15 ms, 6 of them work
    ("verify", 0.006): {"cpu": 0.021},               # 30 less model's 9 of wait
    # 1 + 4 + 21 + 3.5 + 0.1 of its children, 0.5 of its own
    ("spec_round", 0.001): {"cpu": 0.0301},
    ("journal", 0.095): {"cpu": 0.0005},
    # 1 + 30.1 + 0.5 + 2 of its children, 1 of its own
    ("round", 0.000): {"cpu": 0.0346, "faults": 40, "faults_major": 0,
                       "preempted": 3, "yields": 21, "gc": 0.0004,
                       "gc_n": 2},
    # two submits: the first faults in 30 MB of fresh rows, 4 KiB a fault
    ("submit", 0.150): {"faults": 7680, "faults_major": 0, "preempted": 0,
                        "yields": 0, "gc": 0.0, "gc_n": 0},
    ("submit", 0.185): {"faults": 512, "faults_major": 0, "preempted": 1,
                        "yields": 0, "gc": 0.0, "gc_n": 0},
    # round 2, 80 ms: the token read waits 39.6 ms, the argmax 8, model 4
    ("device_wait", 0.224): {"cpu": 0.0004},
    ("sample_verify", 0.223): {"cpu": 0.0024},       # self 10 ms, 2 of them work
    ("model", 0.205): {"cpu": 0.006},
    ("verify", 0.203): {"cpu": 0.016},
    ("spec_round", 0.200): {"cpu": 0.0264},          # 2 + 16 + 2.4, 6 of its own
    ("round", 0.200): {"cpu": 0.0284, "faults": 0, "faults_major": 0,
                       "preempted": 0, "yields": 17, "gc": 0.0, "gc_n": 0},
}
# round 1: CPU 34.6 less the reads' 0.6 = 34.0 of the 49.5 ms the wall metric
# reads, so 15.5 of wait outside the read (5 + 9 + 1.5); round 2: 28.4 - 0.4 =
# 28.0 of 40.0, 12.0 of wait (8 + 4)
PLANTED_VALUES = {
    "step_host_cpu_ms_p50": (34.0 + 28.0) / 2,
    "host_wait_ms_per_step": (15.5 + 12.0) / 2,
    "dispatch_cpu_ms_per_step": (6.0 + 6.0) / 2,         # 9 - grow's 3; 6
    "embed_sample_cpu_ms_per_step": (7.0 + 4.0) / 2,     # 4 + (3.5 - 0.5); 2 + 2
    "submit_faults_per_request": (7680 + 512) / 2,
    "host_preempted_per_step": (3 + 0) / 2,
}


def planted_collector():
    col = fabricated_collector()
    for ev in col.events:
        if ev.get("ph") != "X":
            continue
        fields = PLANTED_FIELDS.get((ev["name"], ev["ts"]), {})
        ev["args"].update({"cpu": ev["dur"], **fields})
    return col
