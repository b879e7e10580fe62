"""90th percentile of the wall times of every round in the window, each
from its start to the start of the next (host clock)."""
import statistics

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(run):
    times = [b - a for a, b in zip(run.stamps, run.stamps[1:])]
    if len(times) < 2:
        return None
    return statistics.quantiles(times, n=10, method="inclusive")[8] * 1e3
