"""A Pallas kernel's share of its roofline in a traced window.

The kernel's events are found on each chip's ``XLA Ops`` line by their
instruction name (the ``name=`` of the ``pallas_call``); their summed
device time is set against the least time the chip could take for the
same calls: the larger of bytes over the HBM peak and FLOPs over the bf16
peak, with the bytes and FLOPs of a round's calls computed from their
operand shapes by the cell's configuration module (``KERNELS``). The calls
seen, over the calls a round makes, count the rounds' worth of work seen.

A reader does not learn its cell's name, so it takes the cell of its own
``BENCHMARK.json`` entry whose tokens and FLOPs per round are the run's.
"""
from __future__ import annotations

import re

from perf import harness
from perf import trace as trace_lib


def cell_of(run, metric: str):
    """(configuration module, configuration, traffic) of the one cell that
    ``metric`` lists whose counts are the run's, else None."""
    spec = harness.read_json("BENCHMARK.json", root=run.root)
    entry = {m["name"]: m for m in spec["per_layer"]}[metric]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    found = []
    for w in spec["workloads"]:
        if w["name"] not in entry.get("workloads", []):
            continue
        mod = harness.load_module("configs", w["config"], run.root)
        cfg = harness.read_json(files[w["config"]], root=run.root)
        t = harness.read_json("perf", "traffic", w["traffic"] + ".json",
                              root=run.root)
        if (hasattr(mod, "KERNELS")
                and mod.tokens_per_round(cfg, t) == run.tokens_per_round
                and mod.flops_per_round(cfg, t) == run.flops_per_round):
            found.append((mod, cfg, t))
    return found[0] if len(found) == 1 else None


def kernel_events(flat: dict, pattern: str, chips: int) -> tuple[int, float]:
    """(events, summed nanoseconds) of the matching operations that start
    in the traced window, over the first ``chips`` device planes."""
    lo, hi = trace_lib.window(flat, trace_lib.SPAN_PREFIX + "window")
    rx = re.compile(pattern)
    planes = sorted(flat["device"], key=lambda p: int(p.rsplit(":", 1)[1]))
    n, ns = 0, 0.0
    for plane in planes[:chips]:
        for name, start, dur in flat["device"][plane]:
            if lo <= start < hi and rx.search(name):
                n += 1
                ns += dur
    return n, ns


def share(run, metric: str, kernel: str):
    """Percent of the roofline that ``kernel`` reached in the traced
    window, or None where there is nothing to read."""
    if run.trace is None:
        return None
    cell = cell_of(run, metric)
    if cell is None:
        return None
    mod, cfg, t = cell
    pattern, per_round = mod.KERNELS[kernel]
    calls, nbytes, flops = per_round(cfg, t)
    n, ns = kernel_events(run.trace["flat"], pattern, run.chips)
    if not n or not ns:
        return None
    bound_s = max(nbytes / run.peak("hbm_bytes_per_s"),
                  flops / run.peak("bf16_flops"))
    return 100.0 * (n / calls) * bound_s / (ns * 1e-9)
