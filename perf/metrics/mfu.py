"""Model FLOPs of the rounds in the traced window (forward and backward of
every local step, from the shapes; no recompute) per second, over the
chips' bf16 peak."""
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "local phase", "tokens_per_s"


def read(run):
    if run.trace is None or not run.rounds:
        return None
    rate = run.rounds * run.flops_per_round / run.trace["window_s"]
    return 100.0 * rate / (run.chips * run.peak("bf16_flops"))
