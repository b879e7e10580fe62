"""The whole step's share of the chips' bf16 peak in the traced window:
``mfu``'s reading (model FLOPs of the rounds from the shapes, no
recompute, over window seconds and chips times peak), for a cell that
``mfu``'s own list does not name."""
from perf import harness

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "local phase", "tokens_per_s"


def read(run):
    return harness.load_module("metrics", "mfu", run.root).read(run)
