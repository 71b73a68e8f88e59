"""Offline trace summarizer: load a Chrome-trace JSON written by
``TraceCollector.save_chrome_trace`` (inference/telemetry.py),
validate the ``trace_events`` structure, and print the serving story
— span durations by phase (wall, and where the spans carry it the
opening thread's CPU time, the wait that is their difference, page
faults, preemptions and the collector's pauses), gauge tracks,
per-request lifecycles and
per-tenant TTFT / TPOT / queue-wait percentiles — without needing the
engine, the model, or a live process. Sibling of
tools/recovery_check.py (the snapshot doctor); this is the timeline
doctor.

Usage:
  python tools/trace_report.py TRACE.json [--tenant TID] [--requests]
                                          [--slo TARGETS.json]
  python tools/trace_report.py --xplane FILE.xplane.pb [--outer bench.]
                                          [--offset-us US]

``--xplane`` reads a ``jax.profiler`` trace taken while a collector
was installed (on a ``RecoverableServer`` that is: any profile) and
prints the device's idle seconds by the innermost ``pt.*`` program
span that overlaps each gap, after estimating the host-device clock
offset from the trace itself and applying it
(``paddle_tpu.profiler.idle_gaps_by_span``). ``--outer PREFIX`` splits
the figures by a second family of host spans (a harness's own);
``--offset-us`` overrides the estimate (0: the planes as stamped).

``--slo`` evaluates per-tenant SLO compliance against the trace's
request records (the offline twin of the live ``SloTracker``) so CI
can gate on latency regressions from a saved artifact. TARGETS.json:

  {"objective": 0.95,                      # default compliance bar
   "targets": {"ttft_s": 0.5, "tpot_s": 0.1, "queue_wait_s": 1.0},
   "tenants": {"alice": {"objective": 0.99,
                         "targets": {"ttft_s": 0.2}}}}

Top-level targets/objective apply to every tenant; a ``tenants`` entry
overrides both for that tenant. Replayed request records are excluded
(their stamps are replay times, not serving latencies).

Accepts any file whose top level carries a ``traceEvents`` list (the
Perfetto/chrome://tracing interchange format); the request/summary
sections need the ``metadata`` block our collector writes and are
skipped (with a note) for foreign traces. Exit status: 0 clean,
1 structurally invalid trace OR an SLO violation under ``--slo``,
2 unreadable file (trace or targets).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

try:
    from tools._report import envelope, emit_json
except ImportError:      # run as a script: tools/ is sys.path[0]
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools._report import envelope, emit_json

# span names that belong to one engine step (phases), to one server
# round around and inside the step, and to one submit; anything else
# prints under "spans" (a trace written before these existed has none
# of the last two families and no parents: it loads and prints as it
# always did)
_PHASES = ("admission", "prefill", "model", "bookkeeping")
_ROUND = ("round", "spec_round", "draft_roll", "embed", "verify", "step",
          "grow", "sample_verify", "device_wait", "journal", "snapshot",
          "gc")
_SUBMIT = ("submit", "submit.journal", "submit.embed", "submit.hash",
           "submit.admit")
# what a span may carry beside its duration (``TraceCollector``: ``cpu``,
# seconds of the opening thread's CPU time, on every span; the rest on
# ``round`` / ``submit`` / ``submit.embed``): a trace written before
# these existed has none, and its lines end where they always did
_COUNTS = ("faults", "faults_major", "preempted", "yields", "gc", "gc_n")
_MODEL = ("mla", "mla.project", "mla.attend", "mla.out",
          "conv", "conv.project", "conv.mix", "conv.out",
          "moe", "moe.route", "moe.experts")     # inside the model phase


def _fmt_s(us: float) -> str:
    s = us / 1e6
    if s >= 1.0:
        return f"{s:.3f}s"
    return f"{s * 1e3:.2f}ms"


def _pct_line(name: str, p: dict) -> str:
    if not p or p.get("count", 0) == 0:
        return f"    {name}: (no samples)"
    ms = {k: v * 1e3 for k, v in p.items() if k != "count"}
    return (f"    {name}: n={p['count']}"
            + "".join(f", {k}={ms[k]:.2f}ms"
                      for k in ("p50", "p90", "p99", "max")
                      if k in ms))


def validate(trace: dict) -> list:
    """Structural problems with a would-be Chrome trace ([], or a
    list of human-readable complaints)."""
    bad = []
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["top-level 'traceEvents' missing or not a list — "
                "not a Chrome trace"]
    if not evs:
        bad.append("traceEvents is empty")
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            bad.append(f"event {i} is not an object")
            continue
        ph = ev.get("ph")
        if ph is None or "name" not in ev:
            bad.append(f"event {i} lacks 'ph'/'name'")
            continue
        if ph != "M" and "ts" not in ev:
            bad.append(f"event {i} ({ev.get('name')!r}) lacks 'ts'")
        if ph == "X":
            dur = ev.get("dur")
            if dur is None:
                bad.append(f"event {i} ({ev.get('name')!r}): complete "
                           f"event without 'dur'")
            elif dur < 0:
                bad.append(f"event {i} ({ev.get('name')!r}): negative "
                           f"duration {dur}")
        if len(bad) >= 20:
            bad.append("... (further problems suppressed)")
            break
    return bad


def _rollup(evs):
    """ONE aggregation pass over the timeline events, shared by the
    human renderer (``summarize``) and the machine one
    (``machine_report``) so the two can never drift: returns
    (spans {name: (total, count, max)}, gauge tracks {track: {series:
    [samples, sum, min, max, last]}}, instant tallies, replay-flagged
    span count, children {name: total duration of the spans that name
    it as their parent} — a span's self time is its total less its
    children's; host {name: {"cpu": CPU time of the spans that carry
    it, "wall": their duration (wait is the difference), "child_cpu":
    CPU time of the spans that name it as their parent, and the sum of
    each of ``_COUNTS`` the name's spans carry}}, all times in
    microseconds)."""
    spans = {}
    counters = {}
    insts = {}
    replayed = 0
    children = {}
    host = {}

    def add(name, key, value):
        h = host.setdefault(name, {})
        h[key] = h.get(key, 0) + value
    for ev in evs:
        ph = ev.get("ph")
        if ph == "X":
            name = ev["name"]
            tot, n, mx = spans.get(name, (0.0, 0, 0.0))
            d = float(ev.get("dur", 0))
            spans[name] = (tot + d, n + 1, max(mx, d))
            args = ev.get("args") or {}
            if args.get("replay"):
                replayed += 1
            if args.get("parent") is not None:
                children[args["parent"]] = \
                    children.get(args["parent"], 0.0) + d
            if "cpu" in args:
                add(name, "cpu", float(args["cpu"]) * 1e6)
                add(name, "wall", d)
                if args.get("parent") is not None:
                    add(args["parent"], "child_cpu",
                        float(args["cpu"]) * 1e6)
            for key in _COUNTS:
                if key in args:
                    add(name, key, args[key] * (1e6 if key == "gc" else 1))
        elif ph == "C":
            track = counters.setdefault(ev["name"], {})
            for k, v in (ev.get("args") or {}).items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                st = track.setdefault(k, [0, 0.0, v, v, v])
                track[k] = [st[0] + 1, st[1] + v, min(st[2], v),
                            max(st[3], v), v]
        elif ph == "i":
            insts[ev["name"]] = insts.get(ev["name"], 0) + 1
    return spans, counters, insts, replayed, children, host


def _host_columns(h: dict, n: int) -> str:
    """The rest of a span's line: CPU, wait and self CPU where its spans
    carry ``cpu``, then the counters and the collector's pauses."""
    out = ""
    if "cpu" in h:
        out += (f", cpu {_fmt_s(h['cpu'])}, wait "
                f"{_fmt_s(h['wall'] - h['cpu'])}, self cpu "
                f"{_fmt_s(h['cpu'] - h.get('child_cpu', 0.0))}")
    if "faults" in h:
        out += (f"; faults {h['faults']} ({h['faults'] / n:.1f} a span, "
                f"{h['faults_major']} major), preempted {h['preempted']}, "
                f"yields {h['yields']}")
    if "gc_n" in h:
        out += f"; gc {_fmt_s(h['gc'])} in {h['gc_n']} collection(s)"
    return out


def _expert_lines(meta) -> list:
    """The expert layer's counters (``DecoderCore.moe_metrics``, in the
    dump's registry under ``moe.``), by kind of model call."""
    reg = (meta or {}).get("registry") or {} \
        if isinstance(meta, dict) else {}
    if "moe.experts_held" not in reg:
        return []
    lines = [f"  expert layers: {reg['moe.experts_held']} of "
             f"{reg['moe.experts']} experts held from "
             f"{reg['moe.expert_offset']}, top {reg['moe.top_k']}"]
    for kind in ("decode", "mixed"):
        calls = reg.get(f"moe.{kind}.layer_calls", 0)
        if not calls:
            continue
        lines.append(
            f"    {kind}: {reg[f'moe.{kind}.calls']} call(s), "
            f"{reg[f'moe.{kind}.rows']} row(s); routed here "
            f"{reg[f'moe.{kind}.rows_routed_here']}; rows an expert a "
            f"layer call mean "
            f"{reg[f'moe.{kind}.rows_per_expert_mean']:.2f}, max "
            f"{reg[f'moe.{kind}.rows_per_expert_max']}; experts that "
            f"received a row {reg[f'moe.{kind}.experts_hit'] / calls:.1f}"
            f" a layer call")
    return lines


def _submit_hash(evs, meta) -> dict:
    """What the block-identity chain read: the ``bytes`` and ``keyed``
    (``ids`` | ``rows``) that each ``submit.hash`` span carries, and
    the engine's ``prefix_cache.*`` counters from the dump's registry
    (all the chain's reads, re-admissions and slice exports too)."""
    out = {"submits": 0, "bytes": 0, "keyed": {}}
    for ev in evs:
        args = ev.get("args") or {}
        if ev.get("ph") == "X" and ev["name"] == "submit.hash" \
                and "keyed" in args:
            out["submits"] += 1
            out["bytes"] += int(args.get("bytes", 0))
            out["keyed"][args["keyed"]] = \
                out["keyed"].get(args["keyed"], 0) + 1
    reg = (meta or {}).get("registry") or {} \
        if isinstance(meta, dict) else {}
    for name in ("hashed_bytes", "row_keyed_blocks"):
        if f"prefix_cache.{name}" in reg:
            out[name] = reg[f"prefix_cache.{name}"]
    return out


def _submit_hash_lines(evs, meta) -> list:
    got = _submit_hash(evs, meta)
    lines = []
    if got["submits"]:
        keyed = ", ".join(f"{k} x{n}"
                          for k, n in sorted(got["keyed"].items()))
        lines.append(f"    submit.hash read {got['bytes']} B in "
                     f"{got['submits']} submit(s), "
                     f"{got['bytes'] / got['submits']:.0f} B a request; "
                     f"keyed by {keyed}")
    if "hashed_bytes" in got:
        lines.append(f"    prefix cache: hashed_bytes "
                     f"{got['hashed_bytes']:g}, row_keyed_blocks "
                     f"{got.get('row_keyed_blocks', 0):g} (blocks whose "
                     f"identity was hashed from embedding rows)")
    return lines


def summarize(trace: dict, tenant: str = None,
              show_requests: bool = False) -> str:
    evs = trace["traceEvents"]
    lines = []
    spans, counters, insts, replayed, children, host = _rollup(evs)
    lines.append(f"timeline: {len(evs)} event(s), "
                 f"{sum(n for _, n, _ in spans.values())} span(s)"
                 + (f" ({replayed} replay-flagged)" if replayed
                    else ""))
    order = sorted(spans, key=lambda n: -spans[n][0])
    known = _PHASES + _ROUND + _SUBMIT + _MODEL
    for title, names in (
            ("step phases", [n for n in order if n in _PHASES]),
            ("model spans", [n for n in order if n in _MODEL]),
            ("round spans", [n for n in order if n in _ROUND]),
            ("submit spans", [n for n in order if n in _SUBMIT]),
            ("spans", [n for n in order if n not in known])):
        if not names:
            continue
        lines.append(f"  {title}:")
        for name in names:
            tot, n, mx = spans[name]
            lines.append(f"    {name}: {n} x, total {_fmt_s(tot)}, "
                         f"mean {_fmt_s(tot / n)}, max {_fmt_s(mx)}"
                         + (f", self {_fmt_s(tot - children[name])}"
                            if name in children else "")
                         + _host_columns(host.get(name, {}), n))
        if title == "submit spans":
            lines.extend(_submit_hash_lines(evs, trace.get("metadata")))
    if counters:
        lines.append(f"  gauge tracks: {sorted(counters)}")
        for track in sorted(counters):
            for k, (n, tot, lo, hi, last) in sorted(
                    counters[track].items(), key=lambda kv: str(kv[0])):
                lines.append(f"    {track}.{k}: {n} sample(s), mean "
                             f"{tot / n:g}, min {lo:g}, max {hi:g}, "
                             f"last {last:g}")
    window = counters.get("paged_attn", {})
    if "pages_in_context" in window:
        total = window["pages_in_context"][1]
        behind = window["pages_behind_window"][1]
        lines.append(f"  sliding layers: {behind:g} of {total:g} pages in "
                     f"context behind the window "
                     f"({100.0 * behind / max(total, 1):.1f} %): skipped "
                     f"by the launch, not freed")
    if "live_steps" in window:
        live, bound = window["live_steps"][1], window["grid_steps"][1]
        lines.append(f"  paged-attention launch: {live:g} live grid "
                     f"step(s) walked of a bound of {bound:g} "
                     f"({100.0 * live / max(bound, 1):.1f} %; a layer "
                     f"without a window)")
    writes = counters.get("pool_write", {})
    if "pages_written" in writes:
        steps, pages = writes["pages_written"][:2]
        moved = writes["pool_bytes_written"][1] / steps
        pool = writes["pool_bytes"][4]
        lines.append(f"  pool writes: {pages / steps:g} page(s) and "
                     f"{writes['rows_written'][1] / steps:g} row(s) a "
                     f"step over all layers, {moved / 1e6:.2f} MB of a "
                     f"{pool / 1e6:.1f} MB pool "
                     f"({100.0 * moved / max(pool, 1):.3f} %): pages of "
                     f"a donated pool, written in place")
    state = counters.get("slot_state", {})
    if "segments" in state:
        calls_, segs = state["segments"][:2]
        chunks = state["prompt_segments"][1]
        lines.append(f"  state store: {state['rows'][1] / calls_:g} "
                     f"row(s) in {segs / calls_:g} segment(s) a model "
                     f"call a state layer, "
                     f"{state['segments_carried'][1]:g} of {segs:g} "
                     f"carried from stored rows"
                     f" ({state['prompt_segments_carried'][1]:g} of "
                     f"{chunks:g} prompt chunks), "
                     f"{state['slots_reset'][1]:g} slot(s) zeroed, "
                     f"{state['state_bytes'][4] / 1e6:.2f} MB held")
    calls = counters.get("step_program", {})
    if "captured" in calls:
        n, captured = calls["captured"][:2]
        why = ", ".join(f"{k} x{calls[k][0]}" for k in sorted(calls)
                        if k not in ("captured", "programs"))
        lines.append(f"  step program: {captured:g} of {n} model call(s) "
                     f"ran as one compiled program "
                     f"({100.0 * captured / max(n, 1):.1f} %), "
                     f"{calls['programs'][4]:g} program(s) compiled"
                     + (f"; per op because: {why}" if why else ""))
    if insts:
        lines.append(f"  instants: "
                     + ", ".join(f"{k} x{v}"
                                 for k, v in sorted(insts.items())))
    # -- request summary (our metadata block) -------------------------
    meta = trace.get("metadata")
    lines.extend(_expert_lines(meta))
    if not isinstance(meta, dict) or "summary" not in meta:
        lines.append("no collector metadata (foreign trace?) — "
                     "request summary skipped")
        return "\n".join(lines)
    summ = meta["summary"]
    lines.append(f"engine: {meta.get('steps', '?')} step(s) traced"
                 + (f", {meta['replayed_steps']} replayed"
                    if meta.get("replayed_steps") else "")
                 + (f", {meta['dropped_events']} event(s) DROPPED "
                    f"(buffer full)"
                    if meta.get("dropped_events") else ""))
    sections = [("overall", summ.get("overall", {}))]
    per_tenant = summ.get("per_tenant", {})
    if tenant is not None:
        if tenant not in per_tenant:
            lines.append(f"  tenant {tenant!r}: no terminal requests")
        else:
            sections.append((f"tenant {tenant!r}", per_tenant[tenant]))
    else:
        sections.extend((f"tenant {t!r}", s)
                        for t, s in sorted(per_tenant.items(),
                                           key=lambda kv: str(kv[0])))
    for title, s in sections:
        lines.append(f"  {title}: {s.get('requests', 0)} terminal "
                     f"request(s), {s.get('tokens', 0)} token(s), "
                     f"{s.get('preemptions', 0)} preemption(s)")
        for metric in ("ttft_s", "tpot_s", "queue_wait_s", "stall_s"):
            lines.append(_pct_line(metric, s.get(metric, {})))
    if show_requests:
        lines.append("requests:")
        for rid, rec in sorted(meta.get("requests", {}).items(),
                               key=lambda kv: int(kv[0])):
            lines.append(
                f"  rid {rid} [{rec.get('tenant')}]: "
                f"{rec.get('outcome') or 'live'} @ step "
                f"{rec.get('outcome_step')}, {rec.get('tokens')} tok, "
                f"{rec.get('chunks')} chunk(s), "
                f"{rec.get('preemptions')} preemption(s)"
                + (" [replayed]" if rec.get("replayed") else ""))
            for ts, name, args in rec.get("events", []):
                lines.append(f"      {ts * 1e3:10.3f}ms  {name}"
                             + (f"  {args}" if args else ""))
    return "\n".join(lines)


def machine_report(trace: dict) -> dict:
    """The ``--json`` payload: span rollups (totals in seconds),
    instant/counter tallies and the collector metadata summary — the
    same facts ``summarize`` renders (same ``_rollup`` pass), as
    data."""
    spans, counters, insts, replayed, children, host = \
        _rollup(trace["traceEvents"])
    meta = trace.get("metadata")

    def host_fields(name):
        h = host.get(name, {})
        out = {k: h[k] for k in _COUNTS if k in h and k != "gc"}
        if "gc" in h:
            out["gc_s"] = round(h["gc"] / 1e6, 6)
        if "cpu" in h:
            out.update(
                cpu_s=round(h["cpu"] / 1e6, 6),
                wait_s=round((h["wall"] - h["cpu"]) / 1e6, 6),
                self_cpu_s=round(
                    (h["cpu"] - h.get("child_cpu", 0.0)) / 1e6, 6))
        return out
    out = {
        "events": len(trace["traceEvents"]),
        "spans": {name: dict(
                      count=n, total_s=round(tot / 1e6, 6),
                      max_s=round(mx / 1e6, 6),
                      self_s=round(
                          (tot - children.get(name, 0.0)) / 1e6, 6),
                      **host_fields(name))
                  for name, (tot, n, mx) in sorted(spans.items())},
        "replayed_spans": replayed,
        "instants": dict(sorted(insts.items())),
        "gauge_tracks": sorted(counters),
        "gauges": {f"{track}.{k}": {"samples": n, "mean": tot / n,
                                    "min": lo, "max": hi, "last": last}
                   for track in sorted(counters)
                   for k, (n, tot, lo, hi, last)
                   in counters[track].items()},
    }
    hashed = _submit_hash(trace["traceEvents"], meta)
    if hashed["submits"] or "hashed_bytes" in hashed:
        out["submit_hash"] = hashed
    if isinstance(meta, dict) and "summary" in meta:
        out["steps"] = meta.get("steps")
        out["replayed_steps"] = meta.get("replayed_steps")
        out["dropped_events"] = meta.get("dropped_events")
        out["summary"] = meta["summary"]
    return out


_SLO_METRICS = ("ttft_s", "tpot_s", "queue_wait_s")


def slo_check(trace: dict, targets: dict):
    """Evaluate per-tenant SLO compliance over the trace's request
    records. Returns (report lines, ok). A tenant passes a metric
    when the fraction of its terminal, non-replayed requests meeting
    the target is >= the objective; tenants with no applicable target
    (or no measurable requests) are skipped, not failed."""
    meta = trace.get("metadata")
    if not isinstance(meta, dict) or "requests" not in meta:
        return (["no collector metadata — cannot evaluate SLOs "
                 "against a foreign trace"], False)
    default_obj = float(targets.get("objective", 0.99))
    default_tg = dict(targets.get("targets", {}))
    per_tenant_cfg = targets.get("tenants", {})

    by_tenant = {}
    for rec in meta["requests"].values():
        if rec.get("replayed") or rec.get("outcome") is None:
            continue
        by_tenant.setdefault(rec.get("tenant"), []).append(rec)

    lines, ok = [], True
    for tid in sorted(by_tenant, key=str):
        cfg = per_tenant_cfg.get(tid, {})
        obj = float(cfg.get("objective", default_obj))
        tg = dict(default_tg, **cfg.get("targets", {}))
        recs = by_tenant[tid]
        lines.append(f"tenant {tid!r}: {len(recs)} terminal "
                     f"request(s), objective {obj:.0%}")
        for metric in _SLO_METRICS:
            if tg.get(metric) is None:
                continue
            vals = [rec[metric] for rec in recs
                    if rec.get(metric) is not None]
            if not vals:
                lines.append(f"    {metric} <= {tg[metric]}s: "
                             f"(no samples)")
                continue
            good = sum(1 for v in vals if v <= tg[metric])
            comp = good / len(vals)
            passed = comp >= obj
            ok = ok and passed
            lines.append(
                f"    {metric} <= {tg[metric]}s: {comp:.1%} of "
                f"{len(vals)} ({'PASS' if passed else 'FAIL'})")
    if not by_tenant:
        lines.append("no terminal (non-replayed) requests to judge")
    lines.append(f"SLO: {'PASS' if ok else 'FAIL'}")
    return lines, ok


def xplane_report(path: str, outer: str = None,
                  offset_us: float = None) -> str:
    """The ``--xplane`` rendering: device idle seconds by innermost
    program span, with the clock offset that was applied."""
    from paddle_tpu.profiler import idle_gaps_by_span
    got = idle_gaps_by_span(
        path, outer=outer,
        offset_ns=None if offset_us is None else offset_us * 1e3)
    lines = [
        f"host clock less device clock: "
        f"{got['estimated_offset_ns'] / 1e3:.1f} us estimated from "
        f"{got['offset_pairs']} device_wait end(s); applied "
        f"{got['offset_ns'] / 1e3:.1f} us",
        f"window {got['window_s']:.6f} s on {got['devices']} device "
        f"plane(s): busy {got['busy_s']:.6f} s, idle "
        f"{got['idle_s']:.6f} s "
        f"({100 * got['idle_s'] / got['window_s']:.2f} %)",
        "idle seconds by innermost program span:"]

    def table(d, indent):
        for name, v in sorted(d.items(), key=lambda kv: -kv[1]):
            lines.append(f"{indent}{name}: {v:.6f}")

    table(got["gap_seconds"], "  ")
    for oname, d in sorted(got.get("by_outer", {}).items(),
                           key=lambda kv: -sum(kv[1].values())):
        lines.append(f"inside {oname}: {sum(d.values()):.6f}")
        table(d, "    ")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize a serving Chrome-trace JSON offline")
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--xplane", default=None, metavar="FILE.xplane.pb",
                    help="a jax.profiler trace: device idle seconds "
                         "by innermost pt.* program span")
    ap.add_argument("--outer", default=None, metavar="PREFIX",
                    help="with --xplane: also split by the host spans "
                         "of this prefix (e.g. bench.)")
    ap.add_argument("--offset-us", type=float, default=None,
                    help="with --xplane: host-less-device clock offset "
                         "to apply instead of the estimated one")
    ap.add_argument("--tenant", default=None,
                    help="show only this tenant's latency section")
    ap.add_argument("--requests", action="store_true",
                    help="print every request's full event log")
    ap.add_argument("--slo", default=None, metavar="TARGETS.json",
                    help="evaluate per-tenant SLO compliance against "
                         "the trace (exit 1 on violation)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable envelope "
                         "(paddle_tpu.report.v1, shared with "
                         "health_report/cost_report)")
    args = ap.parse_args(argv)
    if (args.trace is None) == (args.xplane is None):
        ap.error("give TRACE.json or --xplane FILE, one of the two")
    if args.xplane is not None:
        if not os.path.isfile(args.xplane):
            print(f"UNREADABLE: no file {args.xplane!r}")
            return 2
        try:
            print(xplane_report(args.xplane, args.outer, args.offset_us))
        except (ValueError, RuntimeError) as e:   # no planes, not a trace
            print(f"UNREADABLE: {e}")
            return 2
        return 0

    try:
        with open(args.trace) as f:
            trace = json.load(f)
    except (OSError, ValueError) as e:
        print(f"UNREADABLE: {e}")
        return 2
    if not isinstance(trace, dict):
        print("UNREADABLE: top level is not a JSON object")
        return 2

    problems = validate(trace)
    if problems:
        if args.json:
            emit_json(envelope("trace_report", False, 1,
                               {"events": 0}, problems))
        else:
            print(f"INVALID trace ({len(problems)} problem(s)):")
            for p in problems:
                print(f"  - {p}")
        return 1

    slo_result = None
    slo_problems: list = []
    if args.slo is not None:
        try:
            with open(args.slo) as f:
                targets = json.load(f)
        except (OSError, ValueError) as e:
            print(f"UNREADABLE targets: {e}")
            return 2
        if not isinstance(targets, dict):
            print("UNREADABLE targets: top level is not a JSON object")
            return 2
        slo_lines, slo_ok = slo_check(trace, targets)
        slo_result = {"ok": slo_ok, "lines": slo_lines}
        if not slo_ok:
            slo_problems.append("SLO violation (see data.slo.lines)")

    if args.json:
        data = machine_report(trace)
        if slo_result is not None:
            data["slo"] = slo_result
        code = 1 if slo_problems else 0
        emit_json(envelope("trace_report", code == 0, code, data,
                           slo_problems))
        return code

    print(f"trace {args.trace}: valid trace_events JSON")
    print(summarize(trace, tenant=args.tenant,
                    show_requests=args.requests))
    if slo_result is not None:
        print("SLO evaluation:")
        for ln in slo_result["lines"]:
            print(f"  {ln}")
        if not slo_result["ok"]:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
