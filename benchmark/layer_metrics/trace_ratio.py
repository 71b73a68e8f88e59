"""One number of the trace summary over another (see xplane.summarize)."""


def read(run, num, den, scale=1.0):
    trace = run.get("trace")
    if not trace or not trace.get(den):
        return None
    return scale * trace[num] / trace[den]
