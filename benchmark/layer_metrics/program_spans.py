"""Host time of the program's own spans, from the collector that
``RecoverableServer`` installs for as long as a profile is recording
(``paddle_tpu.inference.telemetry``). Read from the traced run only, over
the rounds that lie wholly inside the profile and the submits and admissions
the collector saw.

``per`` picks the samples, in milliseconds:

- ``"round"``: one value a round: the durations of ``spans`` plus the self
  times (duration less direct children) of ``self_of``, less the durations
  of ``less``, summed over the events inside that round;
- ``"submit"``: the duration of each ``submit`` span;
- ``"request"``: ``field`` (a latency of the request record, seconds) of
  each request admitted inside the session.

``stat`` reduces them: ``mean`` or ``p50``. A program without the accessor
(the parent of the PR that added this file), an untraced run and a session
with no whole round all read as None.
"""
from benchmark.metrics import percentile


def session_collector():
    from paddle_tpu.inference import telemetry
    accessor = getattr(telemetry, "last_session_collector", None)
    return accessor() if accessor else None


def whole_rounds(col):
    """{round number: the round's span event} for the rounds that began and
    ended inside the session and were not torn down."""
    out = {}
    for ev in col.events:
        args = ev.get("args") or {}
        if ev.get("ph") == "X" and ev["name"] == "round" and \
                not args.get("partial") and not args.get("aborted"):
            out[args["round"]] = ev
    return out


def round_values(col, spans=(), self_of=(), less=()):
    rounds = whole_rounds(col)
    value = {r: 0.0 for r in rounds}
    for ev in col.events:
        args = ev.get("args") or {}
        top = rounds.get(args.get("round"))
        if ev.get("ph") != "X" or top is None or ev["ts"] < top["ts"] or \
                ev["ts"] + ev["dur"] > top["ts"] + top["dur"] + 1e-9:
            continue          # a span of a submit, not of the round
        name, r = ev["name"], args["round"]
        if name in spans or name in self_of:
            value[r] += ev["dur"]
        if name in less or args.get("parent") in self_of:
            value[r] -= ev["dur"]
    return [1e3 * value[r] for r in sorted(value)]


def samples(col, per, spans=(), self_of=(), less=(), field=None):
    if per == "round":
        return round_values(col, spans, self_of, less)
    if per == "submit":
        return [1e3 * ev["dur"] for ev in col.events
                if ev.get("ph") == "X" and ev["name"] == "submit"
                and not (ev.get("args") or {}).get("aborted")]
    if per == "request":
        return [1e3 * getattr(rec, field) for rec in col.requests.values()
                if rec.admit_ts is not None and not rec.replayed]
    raise ValueError(f"program_spans: per={per!r}")


def read(run, per, stat="mean", **picks):
    if not run.get("trace"):
        return None
    col = session_collector()
    if col is None:
        return None
    values = samples(col, per, **picks)
    if not values:
        return None
    return percentile(values, 50) if stat == "p50" \
        else sum(values) / len(values)
