"""Training positions (label positions) of all clients in the rounds the
window completed, over the window's seconds on the host clock."""
UNIT, BETTER, SOURCE = "tokens/s", "higher", "host_clock"


def read(run):
    if not run.rounds:
        return None
    return run.rounds * run.tokens_per_round / run.window_s
