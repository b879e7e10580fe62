"""The program's own scopes and host spans in a JAX profiler trace.

The program names its layers with ``jax.named_scope``: ``dfl.local`` (the
local phase), ``dfl.gossip`` (the gossip engine) and, inside it, ``pack``
and ``unpack``. Its host spans are one ``dfl.round`` per round (a step span
whose step number is the round index) around the leaf spans ``dfl.batch``,
``dfl.operands``, ``dfl.dispatch``, ``dfl.sync`` and ``dfl.record``.

A TPU trace's operation events carry no scope: their text is the HLO
instruction without its metadata, and the profile's metadata plane holds
nothing that ``ProfileData`` reads. An operation's scope path is therefore
the ``op_name`` metadata of its instruction in the compiled text of its
program (:func:`op_names`), found by the ``XLA Modules`` event that
encloses the operation and the instruction's name.

:func:`flatten` adds two keys to :func:`perf.trace.flatten`'s flat form:
``scopes`` (per device plane, each operation's scope path or None, aligned
with ``device``) and ``program`` (the ``dfl.`` host spans as ``[name,
start_ns, dur_ns, round]``). :func:`split` reduces them over a window.
"""
from __future__ import annotations

import functools
import re

from perf import trace

PREFIX = "dfl."
ROUND_SPAN = PREFIX + "round"
SYNC_SPAN = PREFIX + "sync"
OTHER = "host_outside_spans"
MODULES_LINE = "XLA Modules"
SCOPES = ("local", "gossip", "gossip.pack", "gossip.unpack")
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')
WRAPPER = re.compile(r"^[\w.-]*\((.*)\)$")


# ---------------------------------------------------------------- flatten
def op_names(hlo_text: str) -> dict:
    """{module: {instruction: op_name}} of a compiled program's text."""
    out: dict[str, dict[str, str]] = {}
    names: dict[str, str] = {}
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            names = out.setdefault(line.split()[1].rstrip(","), {})
            continue
        m = INSTRUCTION.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return out


def scope_paths(ops, modules, names: dict) -> list:
    """Each operation's scope path (its instruction's ``op_name``), or None:
    ``ops`` and ``modules`` are one device's sorted ``[name, start_ns,
    dur_ns]`` events, ``names`` is :func:`op_names`' map."""
    out, j = [], 0
    for name, start, _ in ops:
        while j < len(modules) and modules[j][1] + modules[j][2] <= start:
            j += 1
        inside = j < len(modules) and modules[j][1] <= start
        out.append(names.get(modules[j][0], {}).get(name) if inside
                   else None)
    return out


def program_spans(events) -> list[list]:
    """The ``dfl.`` spans of one host thread as [name, start_ns, dur_ns,
    round]: a ``dfl.round`` span's round is its step number, a leaf span's
    that of the ``dfl.round`` span around it (None where there is none)."""
    spans = [[e.name, float(e.start_ns), float(e.duration_ns),
              dict(e.stats).get("step_num")] for e in events
             if e.name.startswith(PREFIX)]
    rounds = [s for s in spans if s[0] == ROUND_SPAN]
    for s in rounds:
        s[3] = None if s[3] is None else int(s[3])
    for s in spans:
        if s[0] != ROUND_SPAN:
            outer = [r[3] for r in rounds
                     if r[1] <= s[1] and s[1] + s[2] <= r[1] + r[2]]
            s[3] = outer[0] if outer else None
    return spans


def flatten(profile, names: dict | None = None) -> dict:
    """:func:`perf.trace.flatten` with ``scopes`` and ``program`` added;
    ``names`` (:func:`op_names`) gives the scope paths, without it every
    path is None."""
    flat = trace.flatten(profile)
    flat["scopes"], program = {}, []
    for plane in profile.planes:
        if plane.name in flat["device"]:
            modules = sorted(
                ([m.name.split("(", 1)[0], float(m.start_ns),
                  float(m.duration_ns)]
                 for line in plane.lines if line.name == MODULES_LINE
                 for m in line.events), key=lambda m: m[1])
            flat["scopes"][plane.name] = scope_paths(
                flat["device"][plane.name], modules, names or {})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(program_spans(line.events))
    flat["program"] = sorted(program, key=lambda s: s[1])
    return flat


# ---------------------------------------------------------------- reduce
@functools.lru_cache(maxsize=None)
def scope_label(path: str | None) -> str | None:
    """The innermost of the program's scopes in a scope path, as one of
    :data:`SCOPES`, or None. Path components are matched after stripping
    transform wrappers (``vmap(dfl.local)`` is ``dfl.local``); ``pack``
    and ``unpack`` count only under ``dfl.gossip``. Of the paths that XLA
    joins with ``;`` when it merges instructions, the first counts."""
    label = None
    for part in (path or "").split(";", 1)[0].split("/"):
        while (m := WRAPPER.match(part)):
            part = m.group(1)
        if part == PREFIX + "local":
            label = "local"
        elif part == PREFIX + "gossip":
            label = "gossip"
        elif part in ("pack", "unpack") and label in ("gossip",
                                                      "gossip.pack",
                                                      "gossip.unpack"):
            label = "gossip." + part
    return label


def scope_ns(ops, labels, scope: str, lo, hi) -> float:
    """Nanoseconds of [lo, hi) in which an operation labelled ``scope``
    (:func:`scope_label`) ran: the union of their intervals."""
    return trace.length(trace.clip(trace.merge(
        (o[1], o[1] + o[2]) for o, lab in zip(ops, labels) if lab == scope),
        lo, hi))


def device_scopes(ops, paths, lo, hi) -> dict | None:
    """{scope: ns} for each of :data:`SCOPES`, and ``unscoped``: busy time
    of [lo, hi) that no scoped operation covers. None where no operation of
    [lo, hi) has a scope."""
    labels = [scope_label(p) for p in paths]
    if not any(lab and o[1] < hi and o[1] + o[2] > lo
               for o, lab in zip(ops, labels)):
        return None
    out = {scope: scope_ns(ops, labels, scope, lo, hi) for scope in SCOPES}
    scoped = trace.merge((o[1], o[1] + o[2])
                         for o, lab in zip(ops, labels) if lab)
    busy = trace.clip(trace.merge(trace.op_intervals(ops)), lo, hi)
    out["unscoped"] = trace.length(trace.subtract(busy, scoped))
    return out


def host_self_ns(program, lo, hi) -> float | None:
    """Host time of [lo, hi) inside ``dfl.round`` spans and outside their
    ``dfl.sync`` children, in which the host waits for the device. None
    where no round span overlaps [lo, hi)."""
    rounds = trace.clip(trace.merge(
        (s[1], s[1] + s[2]) for s in program if s[0] == ROUND_SPAN), lo, hi)
    if not rounds:
        return None
    syncs = trace.merge((s[1], s[1] + s[2]) for s in program
                        if s[0] == SYNC_SPAN)
    return trace.length(trace.subtract(rounds, syncs))


def idle_by_span(ops, program, lo, hi) -> dict | None:
    """{span: ns} of the device's idle time in [lo, hi): each idle instant
    goes to the innermost (shortest) ``dfl.`` leaf span around it, or to
    ``host_outside_spans``. None where no leaf span overlaps [lo, hi)."""
    leaves = [(s[0], s[1], s[1] + s[2]) for s in program
              if s[0] != ROUND_SPAN and s[1] < hi and s[1] + s[2] > lo]
    if not leaves:
        return None
    busy = trace.clip(trace.merge(trace.op_intervals(ops)), lo, hi)
    out: dict[str, float] = {}
    for gs, ge in trace.subtract([(lo, hi)], busy):
        near = [sp for sp in leaves if sp[1] < ge and sp[2] > gs]
        cuts = sorted({gs, ge} | {t for sp in near for t in sp[1:]
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            around = [sp for sp in near if sp[1] <= a and b <= sp[2]]
            name = (min(around, key=lambda sp: sp[2] - sp[1])[0] if around
                    else OTHER)
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def split(flat: dict, rounds: int, chips: int = 1,
          span: str = trace.SPAN_PREFIX + "window") -> dict:
    """The window's device time by scope and idle time by span, and the
    per-round numbers that read them: ``local_ms`` and ``gossip_ms`` (pack
    and unpack included), device ms per round in the scope, averaged over
    the first ``chips`` devices; ``host_ms``, the host's ms per round in
    its rounds outside ``dfl.sync``; ``idle_ms``, the busiest device's idle
    ms per round. Each is None where the trace lacks what it reads."""
    lo, hi = trace.window(flat, span)
    planes = sorted(flat["device"],
                    key=lambda p: int(p.rsplit(":", 1)[1]))[:chips]
    busiest = max(planes, key=lambda p: trace.busy_ns(flat["device"][p],
                                                      lo, hi))
    ops = flat["device"][busiest]
    per_dev = [device_scopes(flat["device"][p], flat["scopes"][p], lo, hi)
               for p in planes]
    scopes = None
    if all(per_dev):
        scopes = {k: sum(d[k] for d in per_dev) / len(per_dev) * 1e-9
                  for k in per_dev[0]}
    idle = idle_by_span(ops, flat["program"], lo, hi)
    host = host_self_ns(flat["program"], lo, hi)

    def per_round(seconds):
        return None if seconds is None else seconds / rounds * 1e3

    return {
        "local_ms": per_round(scopes and scopes["local"]),
        "gossip_ms": per_round(scopes and scopes["gossip"]
                               + scopes["gossip.pack"]
                               + scopes["gossip.unpack"]),
        "host_ms": per_round(None if host is None else host * 1e-9),
        "idle_ms": per_round((hi - lo - trace.busy_ns(ops, lo, hi)) * 1e-9),
        "device_scopes": scopes,
        "idle_by_span": None if idle is None else {
            k: v * 1e-9 for k, v in idle.items()},
    }
