"""The gossip mix kernel's share of its roofline in the traced window:
its device time against max(bytes / HBM peak, FLOPs / bf16 peak) of the
same calls, bytes and FLOPs from its operand shapes (perf/roofline.py)."""
from perf import roofline

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(run):
    return roofline.share(run, "gossip_mix_roofline", "gossip_mix")
