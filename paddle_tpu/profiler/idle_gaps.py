"""Idle gaps of the device, booked on the program's own host spans.

A ``jax.profiler`` trace (``.xplane.pb``) taken while a
``TraceCollector`` is installed (inference/telemetry.py) carries the
device's operations on the ``/device:*`` planes and the program's
spans, as ``pt.<name>`` annotations, on ``/host:CPU``. This module
merges each device plane's operations into busy intervals and books
every idle gap between them on the INNERMOST ``pt.*`` span that
overlaps it — "the device waited 4 ms a round while the host built
block tables" — and the rest on ``(no span)``.

The two planes have clocks of their own. Before booking, the offset
is estimated from the trace itself: a ``pt.device_wait`` span ends
when the blocking read of the sampled tokens returns, which is when
the device's last operation of the round ended (plus the read's own
return latency), so host end less the end of the last device
operation before it, median over the rounds, is the offset (an upper
bound by that latency); the host spans are moved by it onto the
device's clock. It differs from one profile to the next (1.1 and
2.2 ms in two profiles of one cell), so it is measured in each.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Iterable, List, Optional, Sequence, Tuple

__all__ = ["NO_SPAN", "merge_intervals", "innermost_segments",
           "book_gaps", "estimate_clock_offset", "idle_gaps_by_span"]

NO_SPAN = "(no span)"
WAIT_SPAN = "pt.device_wait"
# a device_wait that ends later than this after the last busy interval
# is not paired (nothing ran for it: an empty round)
_PAIR_WITHIN_NS = 20_000_000

Span = Tuple[str, float, float]          # (name, start, end)


def merge_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[List[float]]:
    """The union of (start, end) intervals as sorted disjoint
    [start, end] pairs."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def innermost_segments(spans: Sequence[Span]) -> List[Span]:
    """Flatten nested spans into disjoint (name, start, end) segments,
    each named after the innermost span that covers it."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []        # (name, end)
    cur = 0.0

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                out.append((name, cur, end))
                cur = end

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(start)
        if stack and start > cur:
            out.append((stack[-1][0], cur, start))
        cur = max(cur, start) if stack else start
        stack.append((name, end))
    close_until(float("inf"))
    return out


def book_gaps(gaps: Iterable[Tuple[float, float]],
              segments: Sequence[Span]):
    """(name, start, end) for each part of each gap: the part a
    segment overlaps goes to that segment's name, the rest to
    ``NO_SPAN``. ``segments`` are disjoint and sorted by start."""
    starts = [s for _, s, _ in segments]
    for a, b in gaps:
        at = a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segments) and segments[i][1] < b:
            name, s, e = segments[i]
            lo, hi = max(at, s), min(b, e)
            if hi > lo:
                if lo > at:
                    yield NO_SPAN, at, lo
                yield name, lo, hi
                at = hi
            i += 1
        if b > at:
            yield NO_SPAN, at, b


def estimate_clock_offset(busy: Sequence[Sequence[float]],
                          wait_ends: Iterable[float]
                          ) -> Tuple[float, int]:
    """Host clock less device clock, from the end of each
    ``device_wait`` (host) against the end of the last busy interval
    that ends before it (device): (median difference, number of
    pairs). (0.0, 0) when nothing pairs. The LAST end before, not the
    nearest: the next round's first small programs run a millisecond
    or two after the read returns, often nearer than the end that
    released it. So a host clock that runs behind the device's reads
    as an offset near 0, not as a negative one."""
    ends = [b for _, b in busy]
    diffs = []
    for h in wait_ends:
        i = bisect.bisect_right(ends, h) - 1
        if i >= 0 and h - ends[i] <= _PAIR_WITHIN_NS:
            diffs.append(h - ends[i])
    return (statistics.median(diffs), len(diffs)) if diffs else (0.0, 0)


def idle_gaps_by_span(path: str, prefix: str = "pt.",
                      outer: Optional[str] = None,
                      offset_ns: Optional[float] = None) -> dict:
    """Reduce one ``.xplane.pb``: seconds of device idle time by the
    innermost ``prefix`` host span, averaged over the device planes.

    ``outer`` names a second family of host spans (a harness's own,
    say ``"bench."``): the window is then their extent instead of the
    ``prefix`` spans', and ``by_outer`` splits every figure by the
    outer span it fell in. ``offset_ns`` overrides the estimated
    host-less-device clock offset (0 books the planes as stamped)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, inner, outers = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append(merge_intervals(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns,
                            e.start_ns + e.duration_ns)
                    if e.name.startswith(prefix):
                        inner.append(span)
                    elif outer and e.name.startswith(outer):
                        outers.append(span)
    if not devices or not inner:
        raise ValueError(f"{path}: {len(devices)} device planes with "
                         f"operations and {len(inner)} {prefix}* spans")
    estimated, pairs = estimate_clock_offset(
        devices[0], [e for n, _, e in inner if n == WAIT_SPAN])
    offset = estimated if offset_ns is None else float(offset_ns)
    inner = [(n, s - offset, e - offset) for n, s, e in inner]
    outers = [(n, s - offset, e - offset) for n, s, e in outers]
    frame = outers or inner
    w0 = min(s for _, s, _ in frame)
    w1 = max(e for _, _, e in frame)
    inner_segs = innermost_segments(inner)
    outer_segs = innermost_segments(outers)
    n = len(devices)
    gap_s: dict = defaultdict(float)
    by_outer: dict = defaultdict(lambda: defaultdict(float))
    busy_s = 0.0
    for merged in devices:
        clipped = [(max(a, w0), min(b, w1)) for a, b in merged
                   if a < w1 and b > w0]
        busy_s += sum(b - a for a, b in clipped) / 1e9 / n
        edges = [w0] + [t for ab in clipped for t in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        # with no outer spans every gap is one (NO_SPAN, a, b) piece
        for oname, a, b in book_gaps(gaps, outer_segs):
            for name, lo, hi in book_gaps([(a, b)], inner_segs):
                gap_s[name] += (hi - lo) / 1e9 / n
                by_outer[oname][name] += (hi - lo) / 1e9 / n
    out = {"offset_ns": offset, "estimated_offset_ns": estimated,
           "offset_pairs": pairs, "window_s": (w1 - w0) / 1e9,
           "busy_s": busy_s, "idle_s": sum(gap_s.values()),
           "devices": n, "gap_seconds": dict(gap_s)}
    if outers:
        out["by_outer"] = {o: dict(d) for o, d in by_outer.items()}
    return out
