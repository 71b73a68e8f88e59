"""One of the run's counters, as counted."""


def read(run, counter):
    return run["counters"].get(counter)
