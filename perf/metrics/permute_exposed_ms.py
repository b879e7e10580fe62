"""Device milliseconds per round in collectives during which no other
operation runs on the chip (the exposed part of ``permute_ms``): the trace
summary's ``collective_exposed_s`` over the traced window's rounds."""
UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER, MOVES = "gossip engine", "tokens_per_s"


def read(run):
    if run.trace is None or not run.rounds or not run.trace["collective_s"]:
        return None
    return run.trace["collective_exposed_s"] / run.rounds * 1e3
