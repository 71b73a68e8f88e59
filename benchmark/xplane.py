"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time per operation under short names, program launches, idle gaps by
the benchmark's own host spans, and the time collectives hold the core.

What the v5e's trace looks like under jax 0.9.0 (read by hand, PR 23's and
this PR's chip runs): one plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per program launch) and ``XLA Ops`` (one event
per HLO instruction, named by its whole HLO line; control flow nests its
body), and a plane ``/host:CPU`` whose thread lines carry
``jax.profiler.TraceAnnotation`` spans under their names. The device's clock
runs about a millisecond off the host's; gaps shorter than that can land on
the neighbouring span.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import time
from collections import defaultdict

SPAN_PREFIX = "bench."
NO_SPAN = "_no_bench_span_"
COLLECTIVE = re.compile(
    r"^(mosaic:)?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")
_HLO = re.compile(r"^%([\w\-]+?)(?:\.\d+)* = \(?(\w+)\[([\d,]*)\]")


def short_name(hlo: str) -> str:
    """``%fwd.1 = f32[32,32,1,128]{...} custom-call(... tpu_custom_call ...)``
    -> ``mosaic:fwd_f32_32_32_1_128_``: instruction name without its number,
    element type and dimensions of the (first) result."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:64]
    name, dtype, dims = m.groups()
    prefix = "mosaic:" if "tpu_custom_call" in hlo else ""
    return f"{prefix}{name}_{dtype}_{dims.replace(',', '_')}_"


def _merge(intervals):
    """The union of (start, end) intervals as sorted disjoint [start, end]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """(name, self_ns) per event: its duration less its direct children's
    (an XLA ``while`` or ``conditional`` spans the ops of its body)."""
    out, stack = [], []           # stack of [end_ns, index into out]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def _clip(events, w0, w1):
    return [(n, max(s, w0), min(s + d, w1) - max(s, w0))
            for n, s, d in events if s < w1 and s + d > w0]


def summarize(path: str) -> dict:
    """Reduce one ``.xplane.pb``. The window is the extent of the
    ``bench.*`` host spans; device events are clipped to it. Times are
    seconds, averaged over the device planes."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            spans += [(e.name, e.start_ns, e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    if not devices or not spans:
        raise ValueError(f"{path}: {len(devices)} device planes and "
                         f"{len(spans)} {SPAN_PREFIX}* spans")
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    spans.sort(key=lambda e: e[1])
    n = len(devices)
    op_s, gap_s = defaultdict(float), defaultdict(float)
    busy = launches = 0.0
    for dev in devices:
        ops = _clip(dev["ops"], w0, w1)
        launches += len(_clip(dev["modules"], w0, w1)) / n
        merged = _merge((s, s + d) for _, s, d in ops)
        busy += sum(b - a for a, b in merged) / 1e9 / n
        for name, self_ns in _self_times(ops):
            op_s[short_name(name)] += self_ns / 1e9 / n
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        for name, ns in _gaps_by_span(zip(edges[::2], edges[1::2]), spans):
            gap_s[name] += ns / 1e9 / n
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy,
        "launches": launches,
        "op_seconds": dict(op_s),
        "gap_seconds": dict(gap_s),
        "collective_s": sum(v for k, v in op_s.items()
                            if COLLECTIVE.match(k)),
        "span_counts": {name: sum(1 for s in spans if s[0] == name)
                        for name in {s[0] for s in spans}},
        "devices": n,
    }


def _gaps_by_span(gaps, spans):
    """(span name, ns) for each part of each idle gap (a, b): the part a host
    span overlaps goes to that span, the rest to ``NO_SPAN``. ``spans`` are
    sorted by start and do not overlap one another."""
    starts = [s for _, s, _ in spans]
    for a, b in gaps:
        rest = b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(spans) and spans[i][1] < b:
            name, s, d = spans[i]
            part = min(b, s + d) - max(a, s)
            if part > 0:
                rest -= part
                yield name, part
            i += 1
        if rest > 0:
            yield NO_SPAN, rest


def op_seconds(summary: dict, pattern: str) -> float:
    """Device seconds of the operations whose short name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary["op_seconds"].items() if rx.search(k))


def breakdown(summary: dict, top: int = 10) -> dict:
    def first(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(summary["op_seconds"]),
            "idle_gaps": first(summary["gap_seconds"])}


class Tracer:
    """Profiles the first ``seconds`` of a window into ``outdir`` and marks
    the benchmark's phases with host spans."""

    def __init__(self, outdir: str, seconds: float):
        self.outdir = outdir
        self.seconds = seconds
        self.t_start = None      # host clock when the profile began
        self.steps = None        # steps of the job inside the traced part

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.outdir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def tick(self, steps: int, last: bool = False) -> None:
        """Call after every step of the window with the steps done so far;
        ends the profile once ``seconds`` have passed, or at the last."""
        if self.steps is None and (
                last or time.perf_counter() - self.t_start >= self.seconds):
            import jax
            jax.profiler.stop_trace()
            self.steps = steps

    def summary(self) -> dict:
        found = glob.glob(os.path.join(self.outdir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"{self.outdir}: {len(found)} trace files")
        out = summarize(found[0])
        out["steps"] = self.steps
        return out


class NoTracer:
    """What an untraced run passes around: the same calls, doing nothing."""

    def span(self, name: str):
        return contextlib.nullcontext()

    def start(self) -> None:
        pass

    def tick(self, steps: int, last: bool = False) -> None:
        pass
