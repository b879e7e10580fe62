"""Seconds from process start to the start of the window: imports, data,
weights made from the seed, compile or cache load, the checked rounds."""
UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(run):
    return run.setup_s
