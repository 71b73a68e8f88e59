"""Serving telemetry: per-request lifecycle tracing, an engine
step-phase timeline, and a unified metrics registry with Chrome-trace
export.

The reference tree ships a whole profiler subsystem
(paddle/fluid/platform/profiler/ emits chrome://tracing timelines)
because an industrial serving stack is untunable blind. This module is
that subsystem for the paged serving stack:

* ``StatsBase`` — the one base behind the five serving stats siblings
  (``PrefixCacheStats`` / ``PrefillStats`` / ``ResilienceStats`` /
  ``TenantStats`` / ``SpecDecodeStats``, serving.py): subclasses
  declare ``FIELDS`` (zero-initialized counters/gauges), ``DERIVED``
  (property name -> rounding digits, exported next to the fields) and
  ``REPR`` (the headline subset), and ``as_dict``/``__repr__`` are
  generated — every stat a subclass declares is export-visible by
  construction, no copy-pasted dict/repr bodies to drift.

* ``MetricsRegistry`` — counters / gauges / histograms plus live
  ``attach``ed sources (a stats sibling, or any callable returning a
  dict — ``tenant_report`` rides this). ``as_dict()`` is a flat
  snapshot (nested sources dot-flattened), ``delta_since(prev)``
  turns two snapshots into interval deltas — the time-series sampling
  surface the ROADMAP's disaggregated router needs for its load
  signals (block pressure, shed rate, per-tenant charge).

* ``TraceCollector`` — the opt-in tracing hub the engines call into
  (``PagedServingEngine(collector=...)``). Three data planes:

    - per-REQUEST lifecycle: submitted -> admitted -> prefill-chunk xN
      -> first-token -> decode (counted, not per-event) ->
      preempted / rolled-back / oom-shed -> terminal outcome, with
      derived TTFT / TPOT / queue-wait / preemption-stall per request,
      rolled up into per-tenant percentiles by ``request_summary``;
    - per-STEP timeline: ``begin_step``/``phase``/``end_step`` bracket
      each engine step's phases (admission, prefill, model,
      bookkeeping), ``span_begin``/``span_end`` nest free-form spans
      around and inside them (the server's ``round`` and ``submit``,
      spec rounds, ``embed``, ``grow``, ``device_wait``, journal
      appends, snapshots), and ``end_step`` samples gauges (pool
      tiers, queue depth, per-tenant charge) from engine ground
      truth. Every span records its round number and the name of the
      span that encloses it (``args["round"]`` / ``args["parent"]``),
      so self time — duration less children — can be computed; the
      timeline is a ring of the newest ``max_events``;
    - WORK OR WAIT: every span, phase and step reads a second clock
      at its open and its close, the calling thread's CPU time
      (``cpu_clock``, default ``time.thread_time``), and carries the
      difference as ``args["cpu"]`` (seconds). Wait is ``dur - cpu``
      and is never stored; self CPU follows from ``parent`` as self
      time does. ``cpu`` is CPU time of the CALLING thread only: work
      the runtime or numpy do on other threads reads as wait; time
      the kernel spends for the thread (page faults) reads as work; a
      runtime that spins instead of sleeping reads as work, so the
      ``cpu / dur`` of ``device_wait``, a span that is nothing but
      waiting, says how far ``wait`` can be trusted on a host (near
      0: the runtime sleeps and wait means what it says; large: it
      spins and every wait is a lower bound). The clock's
      resolution is the platform's (``CLOCK_THREAD_CPUTIME_ID``):
      where the kernel books CPU time by timer tick (10 ms on the
      benchmark's chip host), one span's ``cpu`` is a whole number
      of ticks and only a sum over many spans is a reading. A span
      closed on another thread than it was opened on carries no
      ``cpu``. The spans opened with ``counters=True`` (the server's
      ``round`` and ``submit``, and ``submit.embed``) also carry what
      ``getrusage(RUSAGE_THREAD)`` counted between open and close:
      ``faults`` (pages faulted in without I/O), ``faults_major``,
      ``preempted`` (the OS took the core away) and ``yields`` (the
      thread went to sleep of its own accord); absent where the
      platform has no ``RUSAGE_THREAD``, all 0 on a kernel that does
      not count them (the chip host's). The interpreter's collector
      is watched through ONE ``gc.callbacks`` entry for the process
      (appended with the first ``TraceCollector``, never before):
      every collection that runs on the thread of an open span adds
      its duration to the OUTERMOST open span (``args["gc"]``
      seconds, ``args["gc_n"]`` collections: ``round`` / ``submit``
      on a server) and is a ``pt.gc`` annotation; one of generation
      2, or longer than ``GC_SPAN_S``, is also a span ``gc`` under
      the innermost open span (``generation``, ``collected``). A
      collection another thread runs is not counted: the span's
      thread sleeps on the interpreter lock meanwhile, which is wait;
    - the PROFILER'S CLOCK: every span and phase is also a
      ``jax.profiler.TraceAnnotation`` named ``pt.<name>`` for its
      lifetime, so a profile taken while the collector is installed
      carries the program's spans on the ``/host:CPU`` plane of the
      same ``.xplane.pb`` as the device planes
      (``paddle_tpu.profiler.idle_gaps_by_span`` books the device's
      idle gaps on them);
    - export: ``chrome_trace()`` emits the ``trace_events`` JSON
      format (loadable in Perfetto / chrome://tracing) with the
      request records and summaries riding ``metadata``;
      ``as_dict()`` is the flat metrics dump.

  CONTRACTS (tested in tests/test_telemetry.py):

    - DISABLED = ZERO OVERHEAD: with no collector installed the
      engines perform no clock reads and no telemetry allocations —
      every hook site is behind ``if self.collector is not None``,
      the same pattern as ``FaultInjector``.
    - ONE SWITCH, THE PROFILE SESSION: a ``RecoverableServer`` built
      without a collector reads one flag (``profile_recording()``) at
      the top of each round and each submit; while a
      ``jax.profiler`` trace is recording it installs a collector of
      its own (``open_session_collector``) and removes it at the
      first round top after the trace stopped. The removed collector
      stays reachable through ``last_session_collector()`` until the
      next session replaces it. Outside a session such a server
      allocates no telemetry object and reads no clock; a collector
      the caller passed is never removed.
    - PASSIVE: the collector only ever observes; token streams and
      terminal outcomes are bit-identical with tracing on vs off
      across plain / prefix-cached / speculative / recoverable
      serving (collector methods never raise into the engine and
      never touch engine state).
    - RECOVERY-SAFE: all wall-clock timestamps live HERE, never in
      engine-behavioral state — engine snapshots carry no collector
      state, a recovered engine gets the caller's collector installed
      fresh (``RecoverableServer.recover(collector=...)``). During
      journal replay the collector is flipped to replay mode
      (mirroring how ``CrashInjector`` is disarmed): timeline spans
      record flagged ``replay: True``, records observed live by the
      dead incarnation are FROZEN (no double counting), and requests
      first seen during replay are flagged ``replayed`` and excluded
      from latency percentiles (their replay-time stamps are not
      serving latencies).

The injectable ``clock`` (default ``time.perf_counter``) and
``cpu_clock`` (default ``time.thread_time``) keep tests deterministic
and are how the counting-clock tests prove the zero-overhead contract
for both clocks.
"""
from __future__ import annotations

import collections
import gc
import json
import resource
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax.monitoring
import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["StatsBase", "MetricsRegistry", "NetStats",
           "TraceCollector", "percentiles", "profile_recording",
           "open_session_collector", "last_session_collector"]


# ---------------------------------------------------------------------
# stats base (the five serving.py siblings subclass this)
# ---------------------------------------------------------------------

class StatsBase:
    """Declarative counter/gauge bundle: subclasses list ``FIELDS``
    (instance slots, zero-initialized), ``DERIVED`` ({property name:
    rounding digits or None}) and optionally ``REPR`` (the headline
    fields/properties; defaults to FIELDS). ``as_dict`` exports every
    field AND every derived property — a stat that exists is a stat
    that exports, by construction."""

    FIELDS: Tuple[str, ...] = ()
    DERIVED: Dict[str, Optional[int]] = {}
    REPR: Tuple[str, ...] = ()

    __slots__ = ()

    def __init__(self):
        for f in self.FIELDS:
            setattr(self, f, 0)

    def as_dict(self) -> dict:
        out = {f: getattr(self, f) for f in self.FIELDS}
        for name, nd in self.DERIVED.items():
            v = getattr(self, name)
            out[name] = round(v, nd) if nd is not None else v
        return out

    def __repr__(self):
        parts = []
        for name in (self.REPR or self.FIELDS):
            v = getattr(self, name)
            parts.append(f"{name}={v:.4g}" if isinstance(v, float)
                         else f"{name}={v}")
        return f"{type(self).__name__}({', '.join(parts)})"


class NetStats(StatsBase):
    """Session-transport accounting (inference/net.py), one instance
    per ``ResilientTransport``. A fleet supervisor sums these across
    its workers under the ``net.*`` registry namespace — the series
    the monitor's ``network-flapping`` detector watches. Every field
    is deterministic under a seeded ``NetworkFaultInjector`` storm:
    two identical runs report identical counters.

      sessions          session hellos answered (1 + reconnects,
                        counting the initial adoption)
      reconnects        successful reconnect+hello sequences after a
                        transient fault (EOF / torn frame / CRC /
                        op timeout)
      probes            liveness probe attempts (each reconnect try
                        IS a probe: connect + hello; a failed probe
                        escalates to WorkerDied)
      retried_ops       ops resent on a resumed session after a fault
      reply_cache_hits  retried ops the worker answered from its
                        bounded reply cache instead of re-executing
                        (the transport-level idempotency contract)
      frames_rejected   reply frames discarded as torn or
                        CRC-corrupt (never surfaced as data)
      stale_frames      late/duplicate frames for an already-resolved
                        op seq, discarded by the want-seq check
      blackholes        op deadlines that expired with the connection
                        open (a silent peer, recovered via probe)
    """

    __slots__ = FIELDS = (
        "sessions", "reconnects", "probes", "retried_ops",
        "reply_cache_hits", "frames_rejected", "stale_frames",
        "blackholes")
    REPR = ("sessions", "reconnects", "retried_ops",
            "reply_cache_hits", "frames_rejected")


# ---------------------------------------------------------------------
# unified metrics registry
# ---------------------------------------------------------------------

def percentiles(values, qs=(50, 90, 99)) -> dict:
    """{'count', 'mean', 'p50', 'p90', 'p99', 'max'} of a value list
    (empty input -> {'count': 0})."""
    vals = np.asarray([v for v in values if v is not None], np.float64)
    if vals.size == 0:
        return {"count": 0}
    out = {"count": int(vals.size), "mean": float(vals.mean()),
           "max": float(vals.max())}
    for q in qs:
        out[f"p{q}"] = float(np.percentile(vals, q))
    return out


def _flatten(prefix: str, value, out: dict) -> None:
    if isinstance(value, dict):
        for k, v in value.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)
    else:
        out[prefix] = value


class MetricsRegistry:
    """One namespace for every serving metric: explicit counters /
    gauges / histograms plus live ``attach``ed sources read at
    snapshot time. ``as_dict()`` is flat ({'a.b.c': value}) so two
    snapshots diff into interval deltas with ``delta_since`` — the
    sampling loop a router or dashboard runs."""

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self._hists: Dict[str, List[float]] = {}
        # observations trimmed off each series so far: the absolute
        # index of _hists[name][0] — what lets values_since address a
        # window by TOTAL observation count across trims
        self._hist_dropped: Dict[str, int] = {}
        self._sources: Dict[str, Any] = {}

    # -- writes -------------------------------------------------------
    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    # histogram observations are WINDOWED: a long-lived server must
    # not grow O(total requests) — when a series hits 2x the window
    # the older half is dropped, so percentiles reflect the most
    # recent <= 2*window samples (totals belong in counters)
    HIST_WINDOW = 4096

    def observe(self, name: str, value: float) -> None:
        lst = self._hists.setdefault(name, [])
        if len(lst) >= 2 * self.HIST_WINDOW:
            del lst[:self.HIST_WINDOW]
            self._hist_dropped[name] = \
                self._hist_dropped.get(name, 0) + self.HIST_WINDOW
        lst.append(float(value))

    def attach(self, prefix: str, source) -> None:
        """Register a live source exported under ``prefix``: an object
        with ``as_dict()`` (a stats sibling) or a zero-arg callable
        returning a dict (``tenant_report``, pool occupancy)."""
        self._sources[prefix] = source

    # -- reads --------------------------------------------------------
    def histogram(self, name: str) -> dict:
        return percentiles(self._hists.get(name, ()))

    # -- windowed histogram views -------------------------------------
    # ``as_dict``/``histogram`` report percentiles since boot (well,
    # since the retention window) — useless to an SLO tracker or a
    # router scrape that wants "the last interval". These views
    # address observations by their TOTAL count, the histogram
    # equivalent of ``delta_since``: mark now, serve, then ask for
    # everything after the mark.

    def hist_names(self) -> List[str]:
        return list(self._hists)

    def hist_total(self, name: str) -> int:
        """Observations EVER made on ``name`` (monotonic across the
        retention trim — the mark currency of values_since)."""
        return self._hist_dropped.get(name, 0) + \
            len(self._hists.get(name, ()))

    def hist_marks(self) -> Dict[str, int]:
        """{name: hist_total} for every histogram — snapshot before an
        interval, pass to ``percentiles_since`` after it."""
        return {name: self.hist_total(name) for name in self._hists}

    def last_value(self, name: str) -> Optional[float]:
        """Most recent observation on ``name`` (None when empty) —
        how the cost ledger pairs a step's analytic work with the
        step's just-closed ``span.model`` duration."""
        lst = self._hists.get(name)
        return lst[-1] if lst else None

    def values_since(self, name: str, start: int) -> List[float]:
        """Observations on ``name`` from absolute index ``start``
        (a previous ``hist_total``). Observations already trimmed by
        the retention window are gone — the view clamps to what is
        retained rather than failing."""
        lst = self._hists.get(name)
        if not lst:
            return []
        i = max(0, int(start) - self._hist_dropped.get(name, 0))
        return lst[i:]

    def percentiles_since(self, prev: Optional[Dict[str, int]] = None,
                          qs=(50, 90, 99)) -> Dict[str, dict]:
        """Windowed percentiles: for every histogram, the percentile
        dict over observations made AFTER the ``prev`` marks (a
        ``hist_marks()`` snapshot; names absent there count from 0).
        The interval view ``SloTracker`` and a router scrape consume —
        p50/p90/p99 over the last window, not since boot."""
        prev = prev or {}
        return {name: percentiles(
                    self.values_since(name, prev.get(name, 0)), qs)
                for name in self._hists}

    def as_dict(self) -> dict:
        out: Dict[str, Any] = {}
        for name, v in self.counters.items():
            _flatten(name, v, out)
        for name, v in self.gauges.items():
            _flatten(name, v, out)
        for name, vals in self._hists.items():
            _flatten(name, percentiles(vals), out)
        for prefix, src in self._sources.items():
            d = src() if callable(src) else src.as_dict()
            _flatten(prefix, d, out)
        return out

    def scrape(self, prefixes) -> dict:
        """``as_dict()`` filtered to keys under any of ``prefixes`` —
        the per-worker WIRE payload a fleet router samples
        (inference/router.py): the full flat dict drags along
        per-request latency histograms and tenant detail a placement
        decision has no use for, and scrape payloads cross a pipe
        every tick."""
        pref = tuple(str(p) for p in prefixes)
        return {k: v for k, v in self.as_dict().items()
                if k.startswith(pref)}

    def delta_since(self, prev: dict) -> dict:
        """Numeric differences between the current snapshot and a
        previous ``as_dict()`` (keys absent before count from 0);
        non-numeric entries are skipped."""
        cur = self.as_dict()
        out = {}
        for k, v in cur.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            p = prev.get(k, 0)
            if isinstance(p, bool) or not isinstance(p, (int, float)):
                p = 0
            out[k] = v - p
        return out


# ---------------------------------------------------------------------
# trace collector
# ---------------------------------------------------------------------

class _ReqTrace:
    """Lifecycle record of one request (collector-internal; exported
    via ``as_dict``). Timestamps are collector-relative seconds."""

    __slots__ = ("rid", "tenant", "submit_ts", "admit_ts",
                 "first_ts", "last_ts", "tokens", "chunks",
                 "preemptions", "stall_s", "_preempt_ts", "outcome",
                 "outcome_step", "events", "replayed")

    def __init__(self, rid: int, tenant, ts, replayed: bool = False):
        self.rid = rid
        self.tenant = tenant
        self.submit_ts = ts
        self.admit_ts = None
        self.first_ts = None
        self.last_ts = None
        self.tokens = 0            # decode tokens consumed (rollbacks
                                   # subtracted -> emitted tokens)
        self.chunks = 0
        self.preemptions = 0
        self.stall_s = 0.0         # preempted -> re-admitted wall time
        self._preempt_ts = None
        self.outcome = None
        self.outcome_step = None
        self.events: List[tuple] = []   # (ts, name, args or None)
        self.replayed = replayed

    # -- derived latencies (None until the defining events happened) --
    @property
    def queue_wait_s(self):
        if self.submit_ts is None or self.admit_ts is None:
            return None
        return self.admit_ts - self.submit_ts

    @property
    def ttft_s(self):
        if self.submit_ts is None or self.first_ts is None:
            return None
        return self.first_ts - self.submit_ts

    @property
    def tpot_s(self):
        if self.first_ts is None or self.last_ts is None or \
                self.tokens < 2:
            return None
        return (self.last_ts - self.first_ts) / (self.tokens - 1)

    def as_dict(self) -> dict:
        r = lambda v: None if v is None else round(v, 6)  # noqa: E731
        return {"rid": self.rid, "tenant": self.tenant,
                "tokens": self.tokens, "chunks": self.chunks,
                "preemptions": self.preemptions,
                "outcome": self.outcome,
                "outcome_step": self.outcome_step,
                "queue_wait_s": r(self.queue_wait_s),
                "ttft_s": r(self.ttft_s),
                "tpot_s": r(self.tpot_s),
                "stall_s": r(self.stall_s),
                "replayed": self.replayed,
                "events": [(round(ts, 6), name, args)
                           for ts, name, args in self.events]}


class TraceCollector:
    """See the module docstring. Every method is a cheap append — the
    engines call them only when a collector is installed, and the
    collector never reaches back into the engine."""

    LATENCIES = ("ttft_s", "tpot_s", "queue_wait_s", "stall_s")

    # per-request event-log cap (a preemption storm must not grow one
    # record without bound; counters keep counting past it)
    MAX_REQ_EVENTS = 512

    # a collection below generation 2 that is shorter than this is
    # counted on the outermost span and gets no ``gc`` span of its own
    GC_SPAN_S = 1e-3

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 cpu_clock: Optional[Callable[[], float]] = None,
                 max_events: int = 500_000,
                 max_requests: int = 100_000):
        self._clock = time.perf_counter if clock is None else clock
        self._cpu_clock = time.thread_time if cpu_clock is None \
            else cpu_clock
        self._t0 = self._clock()
        self.max_events = int(max_events)
        self.max_requests = int(max_requests)
        self.dropped = 0                   # events the ring overwrote
        self.evicted_requests = 0
        # timeline (chrome-ish dicts, ts in relative seconds): a ring
        # of the NEWEST max_events — a collector switched on and off on
        # a live server must end a session holding its last rounds
        self.events: collections.deque = collections.deque(
            maxlen=self.max_events)
        self.requests: Dict[int, _ReqTrace] = {}
        self.registry = MetricsRegistry()
        self.steps = 0
        self.replayed_steps = 0
        # the server round (RecoverableServer sets it at the top of
        # each round and submit); None on a bare engine
        self.round_no: Optional[int] = None
        self._replay = False
        # open step: (stamp, step_id, kind, span depth at open, parent,
        # annotation); open phase: (stamp, name, annotation); open
        # spans: (stamp, name, args, parent, annotation, thread usage
        # at open or None); a stamp is ``_stamp()`` at the open
        self._step: Optional[tuple] = None
        self._phase: Optional[tuple] = None
        self._spans: List[tuple] = []
        # collections inside the outermost open span: seconds, count,
        # and the one in progress (stamp, annotation)
        self._gc_s = 0.0
        self._gc_n = 0
        self._gc_open: Optional[tuple] = None
        _watch_process(self)

    def now(self) -> float:
        return self._clock() - self._t0

    def _stamp(self) -> tuple:
        """(wall seconds, CPU seconds of the calling thread, that
        thread) where a span, a phase or a step opens or closes: the
        one place the second clock is read."""
        return self.now(), self._cpu_clock(), threading.get_ident()

    # -- low-level emit -----------------------------------------------
    def _emit(self, ev: dict) -> None:
        if self._replay and ev.get("ph") != "C":
            # counter events' args IS the {series: value} map — a
            # replay flag there would chart as a bogus series
            ev.setdefault("args", {})["replay"] = True
        if len(self.events) == self.max_events:
            self.dropped += 1              # the ring drops its oldest
        self.events.append(ev)

    def _annotate(self, name: str, args: Optional[dict] = None):
        """Open ``pt.<name>`` on the profiler's clock, with the round
        number and (spans about one request) the rid as metadata."""
        meta = {}
        if self.round_no is not None:
            meta["round"] = self.round_no
        if args and "rid" in args:
            meta["rid"] = args["rid"]
        ann = TraceAnnotation("pt." + name, **meta)
        ann.__enter__()
        return ann

    def _innermost(self) -> Optional[str]:
        """Name of the innermost open span or phase (the parent of
        whatever opens next)."""
        if self._step is not None and len(self._spans) <= self._step[3]:
            return self._phase[1] if self._phase else self._step[2]
        return self._spans[-1][1] if self._spans else None

    def _span_event(self, name: str, opened: tuple, closed: tuple,
                    args: Optional[dict] = None,
                    parent: Optional[str] = None) -> None:
        (t0, cpu0, thread0), (t1, cpu1, thread1) = opened, closed
        args = dict(args) if args else {}
        if thread0 == thread1:      # ``thread_time`` is per thread
            args["cpu"] = cpu1 - cpu0
        if self.round_no is not None:
            args["round"] = self.round_no
        if parent is not None:
            args["parent"] = parent
        self._emit({"name": name, "ph": "X", "ts": t0, "dur": t1 - t0,
                    "args": args})
        # every span duration also lands in a windowed registry
        # histogram (``span.<name>``): percentiles_since over these is
        # the windowed per-phase step-timing view the health monitor
        # samples (and kernel tile sizing reads). Replayed spans are
        # replay-time, not serving-time — timeline-flagged only.
        if not self._replay:
            self.registry.observe(f"span.{name}", t1 - t0)

    # -- step timeline ------------------------------------------------
    def begin_step(self, step: int, kind: str = "step") -> None:
        """Open the span for one engine step (auto-closing a step a
        crash left dangling) and its first phase."""
        at = self._stamp()
        if self._step is not None:
            self._close_step(at, aborted=True)
        parent = self._innermost()
        self._step = (at, int(step), kind, len(self._spans), parent,
                      self._annotate(kind))
        self._phase = (at, "bookkeeping", self._annotate("bookkeeping"))

    def phase(self, name: str) -> None:
        """Close the current phase span, open the next. No-op outside
        a step (a crash may have torn one down)."""
        if self._step is None:
            return
        at = self._stamp()
        self._close_phase(at)
        self._phase = (at, name, self._annotate(name))

    def _close_phase(self, at: tuple) -> None:
        if self._phase is None:
            return
        opened, name, ann = self._phase
        ann.__exit__(None, None, None)
        self._span_event(name, opened, at, {"step": self._step[1]},
                         parent=self._step[2])
        self._phase = None

    def end_step(self, gauges: Optional[dict] = None,
                 aborted: bool = False) -> None:
        """Close the step span; ``gauges`` ({track: {series: value}})
        are emitted as Chrome counter events and mirrored into the
        registry. ``aborted`` closes a step a crash tore down: the
        span is flagged, counted separately (``steps.aborted``), and
        its gauges are NOT emitted — mid-crash state is not a
        step-boundary sample."""
        if self._step is None:
            return
        at = self._stamp()
        self._close_step(at, aborted=aborted)
        if aborted:
            return
        for track, series in (gauges or {}).items():
            self.gauge(track, series, ts=at[0])

    def gauge(self, track: str, series: dict,
              ts: Optional[float] = None) -> None:
        """One sample of a gauge track ({series: value}): a Chrome
        counter event, mirrored into the registry."""
        self._emit({"name": track, "ph": "C",
                    "ts": self.now() if ts is None else ts,
                    "args": dict(series)})
        for k, v in series.items():
            self.registry.gauge(f"{track}.{k}", v)

    def _close_step(self, at: tuple, aborted: bool = False) -> None:
        self._close_phase(at)
        opened, step, kind, _, parent, ann = self._step
        ann.__exit__(None, None, None)
        args = {"step": step}
        if aborted:
            args["aborted"] = True
        self._span_event(kind, opened, at, args, parent=parent)
        self._step = None
        if aborted:
            # a torn step is not a completed step: it either replays
            # after recovery (counted then) or the engine is abandoned
            self.registry.count("steps.aborted")
        elif self._replay:
            self.replayed_steps += 1
            self.registry.count("steps.replayed")
        else:
            self.steps += 1
            self.registry.count("steps.live")

    # -- free-form spans (rounds, submits, journal, snapshots, ...) ---
    @property
    def span_depth(self) -> int:
        return len(self._spans)

    def span_begin(self, name: str, counters: bool = False,
                   **args) -> None:
        """``counters``: the span also carries the thread's
        ``USAGE_FIELDS`` between open and close (one ``getrusage`` at
        each end, a few microseconds: for the few spans a round and a
        submit have at their top, not for the 40-80 inside a step)."""
        parent = self._innermost()
        if not self._spans:             # the outermost span opens
            self._gc_s, self._gc_n = 0.0, 0
        self._spans.append((self._stamp(), name, args, parent,
                            self._annotate(name, args),
                            _thread_usage() if counters else None))

    def span_end(self, **extra) -> None:
        if not self._spans:
            return
        opened, name, args, parent, ann, usage = self._spans.pop()
        usage_now = None if usage is None else _thread_usage()
        ann.__exit__(None, None, None)
        closed = self._stamp()
        if usage_now is not None and opened[2] == closed[2]:
            extra.update(zip(USAGE_FIELDS, (
                b - a for a, b in zip(usage, usage_now))))
        if not self._spans:             # the outermost span closes
            extra.update(gc=self._gc_s, gc_n=self._gc_n)
        if extra:
            args = dict(args, **extra)
        self._span_event(name, opened, closed, args, parent=parent)

    def span_unwind(self, depth: int, aborted: bool = False) -> None:
        """Close every span above ``depth``. ``aborted=True`` is for
        exception unwinding (an ``EngineCrash`` mid-round must not
        skew the stack, but the trace should say the span was torn
        down); the default closes normally, so a success path may
        unwind instead of matching every ``span_end`` by hand."""
        while len(self._spans) > depth:
            if aborted:
                self.span_end(aborted=True)
            else:
                self.span_end()

    def on_event(self, name: str, args: Optional[dict] = None) -> None:
        """Instant event on the engine track (OOM/shed occupancy
        dumps and ``compile`` ride this), stamped with the round it
        fell in."""
        ev = {"name": name, "ph": "i", "ts": self.now(), "s": "t"}
        if args or self.round_no is not None:
            ev["args"] = dict(args or {})
            if self.round_no is not None:
                ev["args"]["round"] = self.round_no
        self._emit(ev)
        if not self._replay:       # replayed instants are flagged in
            self.registry.count(f"events.{name}")   # the timeline only

    def on_compile(self, seconds: float) -> None:
        """A backend compile jax's monitoring reported (the one
        process-wide listener below calls this): an instant event
        ``compile`` on the round it fell in. Compiles that fall
        outside every span of this collector are somebody else's."""
        if self._spans or self._step is not None:
            self.on_event("compile", {"seconds": float(seconds)})

    def on_gc(self, phase: str, info: dict) -> None:
        """The interpreter's collector started or stopped a collection
        (the one process-wide ``gc.callbacks`` entry below calls
        this). It counts where it ran on the thread of this
        collector's outermost open span; see the module docstring."""
        if not self._spans or \
                self._spans[0][0][2] != threading.get_ident():
            return
        if phase == "start":
            self._gc_open = (self._stamp(), self._annotate("gc"))
        elif self._gc_open is not None:
            opened, ann = self._gc_open
            self._gc_open = None
            ann.__exit__(None, None, None)
            closed = self._stamp()
            seconds = closed[0] - opened[0]
            self._gc_s += seconds
            self._gc_n += 1
            if info["generation"] == 2 or seconds > self.GC_SPAN_S:
                self._span_event("gc", opened, closed, {
                    "generation": info["generation"],
                    "collected": info["collected"]},
                    parent=self._innermost())

    # -- request lifecycle --------------------------------------------
    def _rec_event(self, rec: _ReqTrace, ts: float, name: str,
                   args: Optional[dict] = None) -> None:
        """Bounded per-record event log (counters keep counting past
        the cap — only the log truncates)."""
        if len(rec.events) < self.MAX_REQ_EVENTS:
            rec.events.append((ts, name, args))

    def _req(self, rid: int) -> Optional[_ReqTrace]:
        """The record for ``rid``, or None when this collector never
        saw it submitted (wired onto a restored engine with in-flight
        requests): a request is traced from its submit or not at all —
        synthesizing a half-record here would put tenant-less entries
        (and, via rollback, NEGATIVE token tallies) in the summary."""
        rec = self.requests.get(rid)
        if rec is None or self._frozen(rec):
            return None
        return rec

    def _frozen(self, rec: _ReqTrace) -> bool:
        # during replay, records the dead incarnation observed live
        # hold the truth already — only replay-born records accumulate
        return self._replay and not rec.replayed

    def on_submit(self, rid: int, tenant: str,
                  prompt_tokens: int) -> None:
        if rid in self.requests:        # replayed submit of a known
            return                      # rid: the live record stands
        if len(self.requests) >= self.max_requests:
            # long-lived servers: evict the OLDEST terminal record
            # (dict order == submission order) so memory stays
            # bounded; live records are never evicted
            victim = next((k for k, r in self.requests.items()
                           if r.outcome is not None), None)
            if victim is not None:
                del self.requests[victim]
                self.evicted_requests += 1
        ts = self.now()
        rec = _ReqTrace(rid, tenant, ts, replayed=self._replay)
        rec.events.append((ts, "submitted",
                           {"prompt_tokens": int(prompt_tokens)}))
        self.requests[rid] = rec
        self.registry.count("requests.submitted")

    def on_admitted(self, rid: int, slot: int, retry: bool) -> None:
        rec = self._req(rid)
        if rec is None:
            return
        ts = self.now()
        if rec.admit_ts is None:
            rec.admit_ts = ts
        if rec._preempt_ts is not None:
            rec.stall_s += ts - rec._preempt_ts
            rec._preempt_ts = None
        self._rec_event(rec, ts, "readmitted" if retry else "admitted",
                        {"slot": int(slot)})

    def on_prefill_chunk(self, rid: int, pos: int) -> None:
        rec = self._req(rid)
        if rec is None:
            return
        rec.chunks += 1
        self._rec_event(rec, self.now(), "prefill_chunk",
                        {"pos": int(pos)})

    def on_first_token(self, rid: int) -> None:
        rec = self._req(rid)
        if rec is None:
            return
        if rec.first_ts is None:
            rec.first_ts = self.now()
            self._rec_event(rec, rec.first_ts, "first_token")

    def on_decode(self, rids, n: int) -> None:
        """One fused step consumed ``n`` decode tokens for each rid —
        counted, not evented (the hot path of the hot path). Frozen
        (replayed) records count nowhere: neither their per-request
        tally nor the registry counter — replay must not inflate
        either."""
        ts = self.now()
        counted = 0
        for rid in rids:
            rec = self._req(rid)
            if rec is None:
                continue
            rec.tokens += n
            rec.last_ts = ts
            counted += 1
        if counted:
            self.registry.count("tokens.decoded", n * counted)

    def on_rollback(self, rid: int, rejected: int) -> None:
        rec = self._req(rid)
        if rec is None:
            return
        rec.tokens -= rejected      # consumed-but-rejected rows leave
        self._rec_event(rec, self.now(), "rolled_back",
                        {"rejected": int(rejected)})

    def on_preempted(self, rid: int) -> None:
        rec = self._req(rid)
        if rec is None:
            return
        rec.preemptions += 1
        rec._preempt_ts = self.now()
        self._rec_event(rec, rec._preempt_ts, "preempted")

    def on_outcome(self, rid: int, status: str, step: int,
                   reason: str = "") -> None:
        rec = self._req(rid)
        if rec is None or rec.outcome is not None:
            return                  # terminal exactly once per record
        ts = self.now()
        rec.outcome = status
        rec.outcome_step = int(step)
        # terminal event rides even past the cap: drop a middle entry
        # rather than lose the verdict from the log
        if len(rec.events) >= self.MAX_REQ_EVENTS:
            del rec.events[self.MAX_REQ_EVENTS // 2]
        rec.events.append((ts, status,
                           {"reason": reason[:120]} if reason else None))
        self.registry.count(f"outcomes.{status}")
        if not rec.replayed:
            for name in self.LATENCIES:
                v = getattr(rec, name)
                if v is not None:
                    self.registry.observe(f"latency.{name}", v)
                    # the per-tenant split the SLO tracker windows
                    # over (values_since / percentiles_since)
                    self.registry.observe(
                        f"latency.{name}.tenant.{rec.tenant}", v)

    # -- replay mode --------------------------------------------------
    def set_replay(self, on: bool) -> None:
        """Journal replay bracket (RecoverableServer.recover): spans
        record flagged, live-observed request records freeze — replay
        neither diverges the trace nor double-counts it."""
        self._replay = bool(on)

    # -- summaries / export -------------------------------------------
    def request_summary(self) -> dict:
        """Per-tenant (+ overall) percentiles of TTFT / TPOT /
        queue-wait / preemption-stall over TERMINAL, non-replayed
        requests (a replay-born record's stamps are replay times, not
        serving latencies — excluded)."""
        done = [r for r in self.requests.values()
                if r.outcome is not None and not r.replayed]
        by_tenant: Dict[str, list] = {}
        for r in done:
            by_tenant.setdefault(r.tenant, []).append(r)

        def roll(recs):
            out = {"requests": len(recs),
                   "tokens": sum(r.tokens for r in recs),
                   "preemptions": sum(r.preemptions for r in recs)}
            for name in self.LATENCIES:
                out[name] = percentiles(getattr(r, name)
                                        for r in recs)
            return out

        return {"overall": roll(done),
                "per_tenant": {t: roll(rs)
                               for t, rs in by_tenant.items()}}

    def as_dict(self) -> dict:
        return {"steps": self.steps,
                "replayed_steps": self.replayed_steps,
                "timeline_events": len(self.events),
                "dropped_events": self.dropped,
                "requests": len(self.requests),
                "evicted_requests": self.evicted_requests,
                "registry": self.registry.as_dict(),
                "summary": self.request_summary()}

    def chrome_trace(self) -> dict:
        """The ``trace_events`` JSON object (Chrome/Perfetto): engine
        timeline on pid 1, request lifecycles as async events on
        pid 2, request/summary/registry dumps in ``metadata``."""
        evs: List[dict] = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "engine"}},
            {"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
             "args": {"name": "requests"}},
        ]
        for ev in self.events:
            out = dict(ev)
            out["ts"] = round(out["ts"] * 1e6, 1)
            if "dur" in out:
                out["dur"] = round(out["dur"] * 1e6, 1)
            out.setdefault("pid", 1)
            out.setdefault("tid", 0)
            evs.append(out)
        for rec in self.requests.values():
            if not rec.events:
                continue
            rid = str(rec.rid)
            name = f"req {rec.rid}"
            args = {"tenant": rec.tenant, "replayed": rec.replayed}
            t_first = rec.events[0][0]
            evs.append({"name": name, "cat": "request", "ph": "b",
                        "id": rid, "ts": round(t_first * 1e6, 1),
                        "pid": 2, "tid": 0, "args": args})
            for ts, ev_name, ev_args in rec.events:
                e = {"name": ev_name, "cat": "request", "ph": "n",
                     "id": rid, "ts": round(ts * 1e6, 1),
                     "pid": 2, "tid": 0}
                if ev_args:
                    e["args"] = dict(ev_args)
                evs.append(e)
            t_last = rec.events[-1][0]
            evs.append({"name": name, "cat": "request", "ph": "e",
                        "id": rid, "ts": round(t_last * 1e6, 1),
                        "pid": 2, "tid": 0})
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "metadata": {
                    "requests": {r.rid: r.as_dict()
                                 for r in self.requests.values()},
                    "summary": self.request_summary(),
                    "registry": self.registry.as_dict(),
                    "steps": self.steps,
                    "replayed_steps": self.replayed_steps,
                    "dropped_events": self.dropped}}

    def save_chrome_trace(self, path: str) -> int:
        """Write ``chrome_trace()`` as JSON; returns bytes written."""
        blob = json.dumps(self.chrome_trace(), default=_json_default)
        with open(path, "w") as f:
            f.write(blob)
        return len(blob)


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o).__name__}")


# ---------------------------------------------------------------------
# what the OS counted for the calling thread
# ---------------------------------------------------------------------

USAGE_FIELDS = ("faults", "faults_major", "preempted", "yields")
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)   # Linux


def _thread_usage() -> Optional[tuple]:
    """``USAGE_FIELDS`` of the calling thread so far (None where the
    platform does not count by thread)."""
    if _RUSAGE_THREAD is None:
        return None
    ru = resource.getrusage(_RUSAGE_THREAD)
    return ru.ru_minflt, ru.ru_majflt, ru.ru_nivcsw, ru.ru_nvcsw


# ---------------------------------------------------------------------
# compile events and collections: one process-wide listener each
# ---------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_collectors: "weakref.WeakSet[TraceCollector]" = weakref.WeakSet()
_listening = False


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        for col in list(_collectors):
            col.on_compile(seconds)


def _on_gc(phase: str, info: dict) -> None:
    for col in list(_collectors):
        col.on_gc(phase, info)


def _watch_process(col: TraceCollector) -> None:
    """jax's monitoring listeners cannot be removed, so there is ONE
    for the process, registered with the first collector, and beside
    it ONE ``gc.callbacks`` entry (a process that never made a
    collector has neither); they hand each backend compile and each
    collection to the collectors that are alive."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        gc.callbacks.append(_on_gc)
        _listening = True
    _collectors.add(col)


# ---------------------------------------------------------------------
# the profile session switch (RecoverableServer, inference/recovery.py)
# ---------------------------------------------------------------------

_session: Optional[TraceCollector] = None


def profile_recording() -> bool:
    """Whether a ``jax.profiler`` trace is recording now: one flag
    read, no clock read."""
    return TraceAnnotation.is_enabled()


def open_session_collector() -> TraceCollector:
    """A fresh collector for the profile session that is recording;
    it replaces the last session's as ``last_session_collector()``."""
    global _session
    _session = TraceCollector()
    return _session


def last_session_collector() -> Optional[TraceCollector]:
    """The collector a ``RecoverableServer`` installed for the running
    or the last profile session (None before the first): what a
    reader run after ``server.close()`` reads, and what an operator
    dumps with ``save_chrome_trace``."""
    return _session
