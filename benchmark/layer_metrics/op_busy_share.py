"""Device time of the operations whose short name matches ``pattern``, as a
share of the time the device was busy in the traced part of the window."""
from benchmark import xplane


def read(run, pattern):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * xplane.op_seconds(trace, pattern) / trace["busy_s"]
