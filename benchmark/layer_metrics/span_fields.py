"""What a span of the program carries beside its duration: the CPU time of
the thread that opened it, and on the round, the submit and the gather what
the OS counted for that thread (``paddle_tpu.inference.telemetry``, WORK OR
WAIT). Read as ``program_spans`` reads durations: from the collector of the
profile session, over the rounds that lie wholly inside the profile and the
submits the collector saw.

``field`` picks the value of one span: ``cpu`` (ms), ``wait`` (duration less
``cpu``, ms: the thread asleep or descheduled), ``dur`` (ms), ``gc`` (ms) or
a counter's name (``faults``, ``faults_major``, ``preempted``, ``yields``,
``gc_n``; as counted). ``per`` picks the samples:

- ``"round"``: one value a round: ``field`` of ``spans`` plus the self value
  (the span's less its direct children's) of ``self_of``, less ``field`` of
  ``less``, summed over the events inside that round;
- ``"submit"``: ``field`` of each ``submit`` span.

``stat`` reduces them: ``mean``, or ``midmean``: the mean over the middle
half of the samples RANKED BY THEIR WALL TIME (the same picks on ``dur``), the
median sample's value. A plain median of ``cpu`` is no reading where the
thread's CPU clock ticks coarser than a round lasts: on the chip's host it
ticks at 10 ms, so one round's ``cpu`` reads 0 or 10 ms and only a mean over
many rounds says anything (PERF.md section 6, PR 37). A session whose spans
lack the field (the parent of the PR that added this file), an untraced run
and a session with no whole round all read as None.
"""
from benchmark.layer_metrics.program_spans import (session_collector,
                                                   whole_rounds)

SECONDS = ("cpu", "gc")


def value_of(ev, field):
    """``field`` of one span event, None where the span does not carry it."""
    args = ev.get("args") or {}
    if field == "dur":
        return 1e3 * ev["dur"]
    if field == "wait":
        return 1e3 * (ev["dur"] - args["cpu"]) if "cpu" in args else None
    if field not in args:
        return None
    return 1e3 * args[field] if field in SECONDS else args[field]


def round_values(col, field, spans=(), self_of=(), less=()):
    """One value a whole round, or None where a picked span lacks ``field``."""
    rounds = whole_rounds(col)
    value = {r: 0.0 for r in rounds}
    for ev in col.events:
        args = ev.get("args") or {}
        top = rounds.get(args.get("round"))
        if ev.get("ph") != "X" or top is None or ev["ts"] < top["ts"] or \
                ev["ts"] + ev["dur"] > top["ts"] + top["dur"] + 1e-9:
            continue          # a span of a submit, not of the round
        name = ev["name"]
        sign = (name in spans or name in self_of) \
            - (name in less or args.get("parent") in self_of)
        if sign:
            got = value_of(ev, field)
            if got is None:
                return None
            value[args["round"]] += sign * got
    return [value[r] for r in sorted(value)]


def samples(col, per, field, **picks):
    if per == "round":
        return round_values(col, field, **picks)
    if per == "submit":
        values = [value_of(ev, field) for ev in col.events
                  if ev.get("ph") == "X" and ev["name"] == "submit"
                  and not (ev.get("args") or {}).get("aborted")]
        return None if None in values else values
    raise ValueError(f"span_fields: per={per!r}")


def read(run, per, field, stat="mean", **picks):
    if not run.get("trace"):
        return None
    col = session_collector()
    if col is None:
        return None
    values = samples(col, per, field, **picks)
    if not values:
        return None
    if stat == "midmean":
        wall = samples(col, per, "dur", **picks)
        order = sorted(range(len(values)), key=wall.__getitem__)
        values = [values[i] for i in
                  order[len(order) // 4:len(order) - len(order) // 4]]
    return sum(values) / len(values)
