"""paddle.profiler (ref: /root/reference/python/paddle/profiler/profiler.py
— Profiler with scheduler states :79, chrome export :212; C++ layer
paddle/fluid/platform/profiler/ with HostTracer + CUPTI CudaTracer merged
into chrome traces).

TPU-native: host events via a lightweight thread-local recorder
(RecordEvent), device timeline via jax.profiler (XPlane/TensorBoard and
perfetto), exported together. The ProfilerTarget/scheduler API matches the
reference."""
from __future__ import annotations

import contextlib
import enum
import json
import os
import threading
import time
from typing import Callable, Iterable, Optional

from .idle_gaps import idle_gaps_by_span

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SortedKeys", "SummaryView", "idle_gaps_by_span"]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys(enum.Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    GPUTotal = 3


class SummaryView(enum.Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


class _HostEvents(threading.local):
    def __init__(self):
        self.events = []
        self.enabled = False


_host = _HostEvents()


class RecordEvent:
    """Host-side event span (the reference's platform::RecordEvent emitted
    by every generated ad_func, eager_gen.py:1075)."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def begin(self):
        self._t0 = time.perf_counter_ns()

    def end(self):
        if _host.enabled and self._t0 is not None:
            _host.events.append(
                (self.name, self._t0, time.perf_counter_ns()))


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    cycle = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = (step - skip_first) % max(cycle, 1)
        if repeat and (step - skip_first) // max(cycle, 1) >= repeat:
            return ProfilerState.CLOSED
        if s < closed:
            return ProfilerState.CLOSED
        if s < closed + ready:
            return ProfilerState.READY
        if s == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof.export(dir_name, format="json")
    return handler


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if isinstance(scheduler, tuple):
            lo, hi = scheduler
            self.scheduler = make_scheduler(closed=lo, record=hi - lo)
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._step = 0
        self._jax_active = False
        self._logdir = None
        self._step_times = []
        self._last = None

    def start(self):
        _host.enabled = True
        _host.events.clear()
        self._last = time.perf_counter()
        if not self.timer_only:
            import tempfile
            self._logdir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            try:
                import jax
                jax.profiler.start_trace(self._logdir)
                self._jax_active = True
            except Exception:
                self._jax_active = False

    def stop(self):
        _host.enabled = False
        if self._jax_active:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_active = False
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self._step_times.append(now - self._last)
        self._last = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        arr = np.asarray(self._step_times[-10:])
        return (f"avg step {arr.mean()*1000:.2f} ms "
                f"(min {arr.min()*1000:.2f}, max {arr.max()*1000:.2f})")

    def export(self, path, format="json"):
        os.makedirs(path, exist_ok=True)
        events = []
        for name, t0, t1 in _host.events:
            events.append({
                "name": name, "ph": "X", "pid": 0, "tid": 0,
                "ts": t0 / 1000.0, "dur": (t1 - t0) / 1000.0,
                "cat": "host",
            })
        out = os.path.join(path, "paddle_tpu_trace.json")
        with open(out, "w") as f:
            json.dump({"traceEvents": events,
                       "jax_trace_dir": self._logdir}, f)
        return out

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        from collections import defaultdict
        agg = defaultdict(lambda: [0, 0.0])
        for name, t0, t1 in _host.events:
            agg[name][0] += 1
            agg[name][1] += (t1 - t0) / 1e6
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}"]
        for name, (calls, total) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40}{calls:>8}{total:>12.3f}")
        s = "\n".join(lines)
        print(s)
        return s

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
