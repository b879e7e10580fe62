"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is first flattened to plain data (:func:`flatten`): for each device
plane, its operations as ``[name, start_ns, duration_ns]``, and the host
spans the harness wrote (``jax.profiler.TraceAnnotation`` names that start
with ``perf.``), all on the profiler's one clock. Everything below works on
that flat form, so the tests can check it on a recorded trace without a
chip.

Device operations are read from the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane: one event per HLO operation that ran, a while
loop and the operations of its body both (so a union, not a sum, gives
busy time), named by the HLO instruction (``while.300``,
``collective-permute-start.1``). The ``Async XLA Ops`` line (DMA copies and
asynchronous collectives in flight) is kept apart: it counts towards
collective time, never towards busy time.
"""
from __future__ import annotations

import gzip
import json
import re

SPAN_PREFIX = "perf."
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# HLO collectives whose time is interconnect traffic (async pairs show as
# their start/done halves)
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|\bsend\b|\brecv\b")


def op_name(text: str) -> str:
    """The HLO instruction's name from the event's text
    (``%while.300 = (s32[], ...) while(...)`` -> ``while.300``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def flatten(profile) -> dict:
    """``jax.profiler.ProfileData`` -> {"device": {plane: [[name, start_ns,
    dur_ns], ...]}, "async": {plane: [...]}, "host": [[name, start_ns,
    dur_ns], ...]}."""
    device, async_ops, host = {}, {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {OPS_LINE: [], ASYNC_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events)
            device[plane.name] = sorted(lines[OPS_LINE], key=lambda o: o[1])
            async_ops[plane.name] = sorted(lines[ASYNC_LINE],
                                           key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "async": async_ops,
            "host": sorted(host, key=lambda s: s[1])}


def save(flat: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(flat, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------- intervals
def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` that no interval of the
    disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_intervals(ops, pattern=None):
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [(o[1], o[1] + o[2]) for o in ops
            if rx is None or rx.search(o[0])]


# ---------------------------------------------------------------- reductions
def window(flat: dict, span: str) -> tuple[float, float]:
    """[start, end) in ns of the named host span (the traced window)."""
    hits = [s for s in flat["host"] if s[0] == span]
    if not hits:
        raise ValueError(f"trace holds no host span {span!r}")
    return hits[0][1], hits[0][1] + hits[0][2]


def busy_ns(ops, lo, hi) -> float:
    """Nanoseconds of [lo, hi) in which some operation ran."""
    return length(clip(merge(op_intervals(ops)), lo, hi))


def kernel_ns(ops, pattern, lo, hi) -> float:
    """Summed device time of the operations whose name matches."""
    return sum(e - s for s, e in clip(op_intervals(ops, pattern), lo, hi))


def collective_ns(ops, async_ops, lo, hi) -> tuple[float, float]:
    """(time in collectives, the part of it in which no other operation
    runs on the device): the union of collective intervals, synchronous and
    asynchronous, and what is left of it after the union of all other
    synchronous operations is taken away."""
    comm = clip(merge(op_intervals(ops, COLLECTIVE)
                      + op_intervals(async_ops, COLLECTIVE)), lo, hi)
    other = merge([(o[1], o[1] + o[2]) for o in ops
                   if not COLLECTIVE.search(o[0])])
    return length(comm), length(subtract(comm, other))


def top_ops(ops, lo, hi, n=10) -> list[list]:
    """The n operation names with the most device time, [name, seconds]."""
    tot: dict[str, float] = {}
    for name, s, d in ops:
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            tot[name] = tot.get(name, 0.0) + (e2 - s2)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(ops, host, lo, hi, n=10, other="host_outside_spans",
              window_span=SPAN_PREFIX + "window"):
    """The n longest idle gaps of the device in [lo, hi), each
    [label, seconds], labelled by the harness span (other than the window's
    own) that overlaps the gap most, the shorter of two that overlap it as
    much (``other`` where none does)."""
    busy = clip(merge(op_intervals(ops)), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    spans = [(s[0][len(SPAN_PREFIX):], s[1], s[1] + s[2]) for s in host
             if s[1] + s[2] > lo and s[1] < hi and s[0] != window_span]
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover, best_len = other, 0.0, float("inf")
        for name, ss, se in spans:
            c = min(ge, se) - max(gs, ss)
            if c > cover or (c == cover and c > 0 and se - ss < best_len):
                best, cover, best_len = name, c, se - ss
        out.append([best, (ge - gs) * 1e-9])
    return out


def summarize(flat: dict, chips: int = 1,
              span: str = SPAN_PREFIX + "window") -> dict:
    """Device numbers of the traced window, averaged over the first
    ``chips`` devices (those the cell uses): busy and window seconds,
    collective seconds and their exposed part, and the breakdown (top
    operations of the busiest device, its longest idle gaps)."""
    lo, hi = window(flat, span)
    planes = sorted(flat["device"], key=lambda p: int(p.rsplit(":", 1)[1]))
    per_dev = {p: (flat["device"][p], busy_ns(flat["device"][p], lo, hi))
               for p in planes[:chips]}
    if not any(b > 0 for _, b in per_dev.values()):
        raise ValueError("no device operation ran in the traced window")
    n = len(per_dev)
    comm = [collective_ns(ops, flat.get("async", {}).get(plane, []), lo, hi)
            for plane, (ops, _) in per_dev.items()]
    busiest = max(per_dev.values(), key=lambda v: v[1])[0]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(b for _, b in per_dev.values()) / n * 1e-9,
        "devices": n,
        "collective_s": sum(c[0] for c in comm) / n * 1e-9,
        "collective_exposed_s": sum(c[1] for c in comm) / n * 1e-9,
        "breakdown": {"device_ops": top_ops(busiest, lo, hi),
                      "idle_gaps": idle_gaps(busiest, flat["host"], lo, hi)},
    }
