"""Host milliseconds per round inside the program's ``batch_fn`` (its
token batcher and the transfer to the device), in the traced window."""
UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"
LAYER, MOVES = "host loop", "tokens_per_s"


def read(run):
    if not run.rounds:
        return None
    spent = run.spans.between("batch_fn", run.stamps[0], run.stamps[-1])
    return sum(spent) / run.rounds * 1e3
