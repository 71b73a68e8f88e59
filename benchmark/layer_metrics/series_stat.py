"""A statistic of one of the job's per-step series (host clock or counts),
optionally over one of its counters."""
from benchmark.metrics import percentile


def read(run, series, stat, q=None, over=None, scale=1.0):
    values = run["series"].get(series)
    if not values:
        return None
    value = {"mean": lambda v: sum(v) / len(v), "max": max,
             "percentile": lambda v: percentile(v, q)}[stat](values)
    if over is not None:
        value /= run["counters"][over]
    return value * scale
