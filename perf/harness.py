"""Finds a cell's parts by name and runs the cell once.

``BENCHMARK.json`` names each cell's configuration and traffic. The parts
are files found by those names, so a later change adds a cell, a
configuration or a metric by adding files:

* ``perf/configs/<config>.json``: the configuration's sizes and source;
  ``perf/configs/<config>.py``: ``build(cfg, traffic, seed32, spans)``,
  which returns the cell (the system under test, set up from the seed, and
  its comparison with the plain reference beside it);
* ``perf/traffic/<traffic>.json``: the job's parameters and the limits of
  the numbers compared;
* ``perf/metrics/<metric>.py``: ``read(run)`` for one metric, end-to-end or
  per-layer, returning None where it finds nothing to read;
* ``perf/peaks.json``: the chips' published peaks, by ``device_kind``.

A cell has ``tokens_per_round``, ``flops_per_round``, ``setup()``,
``rounds(on_round)`` (runs rounds until ``on_round``, called as each round
starts, raises), ``release()`` and ``check()`` (the numbers compared).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_json(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, root=ROOT):
    """``perf/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "perf", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Parts:
    workload: dict
    config: dict
    traffic: dict
    cell_module: object
    end_to_end: list
    per_layer: list


def parts(workload: str, root=ROOT) -> Parts:
    spec = read_json("BENCHMARK.json", root=root)
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    files = {c["name"]: c["file"] for c in spec["configs"]}
    cfg = read_json(files[wl["config"]], root=root)

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Parts(wl, cfg, read_json("perf", "traffic", wl["traffic"] + ".json",
                                    root=root),
                 load_module("configs", wl["config"], root=root),
                 [m for m in spec["end_to_end"] if applies(m)],
                 [m for m in spec["per_layer"] if applies(m)])


def peaks(device_kind: str, root=ROOT) -> dict:
    table = read_json("perf", "peaks.json", root=root)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       "perf/peaks.json")
    return table[device_kind]


def seed32(seed: int) -> int:
    """A 32-bit seed for JAX keys (which keep only the low 32 bits of a
    Python int) that still differs for every whole-number seed."""
    import numpy as np
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def cache_dir(root=ROOT) -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))


class Spans:
    """Host spans of the harness: each is timed on the host clock and, when
    a trace is on, written into it as ``perf.<name>``."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("perf." + name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def between(self, name: str, lo: float, hi: float) -> list[float]:
        return [e - s for n, s, e in self.records
                if n == name and lo <= s < hi]


class CompileWatch:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class StopWindow(Exception):
    pass


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    chips: int
    device_kind: str
    setup_s: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)
    spans: Spans = dataclasses.field(default_factory=Spans)
    tokens_per_round: float = 0.0
    flops_per_round: float = 0.0
    trace: dict | None = None        # perf.trace.summarize of a traced run
    root: str = ROOT

    @property
    def rounds(self) -> int:
        return max(len(self.stamps) - 1, 0)

    @property
    def window_s(self) -> float:
        return self.stamps[-1] - self.stamps[0] if self.rounds else 0.0

    def peak(self, key: str) -> float:
        return float(peaks(self.device_kind, self.root)[key])


def window(cell, seconds: float, run: Run) -> None:
    """Runs rounds for ``seconds``: the window spans whole rounds, from the
    start of its first to the end of its last (the start of the next)."""
    import jax
    ann = jax.profiler.TraceAnnotation("perf.window")

    def on_round():
        t = time.perf_counter()
        if not run.stamps:
            ann.__enter__()
        run.stamps.append(t)
        if t - run.stamps[0] >= seconds:
            ann.__exit__(None, None, None)
            raise StopWindow

    try:
        cell.rounds(on_round)
    except StopWindow:
        return
    raise RuntimeError("the cell's rounds ended before the window closed")


def check_devices(chips: int):
    """The first ``chips`` TPU devices; raises where JAX finds fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"perf: the cell needs {chips} TPU chip(s); JAX "
                         f"finds {len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def enable_cache(root=ROOT) -> str:
    """Every compile of this process into the persistent cache, short ones
    too, at $JAX_COMPILATION_CACHE_DIR or a fixed path in the checkout."""
    import jax
    path = cache_dir(root)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def trace_window(cell, seconds: float, run: Run, tmp: str) -> None:
    """The window under the profiler, reduced by perf.trace."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from perf import trace as trace_lib

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        window(cell, seconds, run)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    flat = trace_lib.flatten(ProfileData.from_file(path))
    run.trace = trace_lib.summarize(flat, run.chips)
    run.trace["flat"] = flat


def read_metrics(entries, run: Run) -> dict:
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"], run.root).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(compared: dict, limits: dict) -> dict:
    """{name: {value, limit, ok}}: a number passes at or under its limit; a
    number that is not finite fails and is printed as null."""
    out = {}
    for k, v in compared.items():
        ok = isinstance(v, (int, float)) and math.isfinite(v)
        out[k] = {"value": v if ok else None, "limit": limits[k],
                  "ok": bool(ok and v <= limits[k])}
    return out


def execute(workload: str, seed: int, seconds: float, traced: bool,
            t_start: float, require_tpu: bool = True, root=ROOT,
            cache: bool = True) -> dict:
    """One run of one cell; returns the result object (the last line).
    ``require_tpu`` and ``cache`` are off only in the CPU tests."""
    import tempfile

    import jax

    p = parts(workload, root)
    chips = p.workload["chips"]
    devs = check_devices(chips) if require_tpu else jax.devices()[:chips]
    if cache:
        enable_cache(root)
    watch = CompileWatch()
    run = Run(chips, devs[0].device_kind, root=root)
    cell = p.cell_module.build(p.config, p.traffic, seed32(seed), run.spans)
    run.tokens_per_round = cell.tokens_per_round
    run.flops_per_round = cell.flops_per_round
    cell.setup()
    setup = {"compiles": watch.compiles, "compile_s": watch.compile_s,
             "cache_hits": watch.cache_hits, "cache_dir": cache_dir(root)}
    if traced:
        seconds = min(seconds, p.traffic["trace_seconds"])
        with tempfile.TemporaryDirectory(prefix="perf-trace-") as tmp:
            trace_window(cell, seconds, run, tmp)
    else:
        window(cell, seconds, run)
    run.setup_s = run.stamps[0] - t_start
    print_slowest(run)
    compiles_in_window = watch.compiles - setup["compiles"]
    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    cell.release()
    numbers = judge(cell.check(), p.traffic["limits"])
    numbers["compiles_in_window"] = {"value": compiles_in_window, "limit": 0,
                                     "ok": compiles_in_window == 0}
    failed = sum(1 for v in numbers.values() if not v["ok"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    result = {"correct": failed == 0, "attempted": run.rounds,
              "failed": failed,
              "metrics": read_metrics(p.per_layer if traced
                                      else p.end_to_end, run),
              "device": device}
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    phases: dict = {}
    for name, t0, t1 in run.spans.records:
        if name.startswith("setup."):
            phases[name[6:]] = phases.get(name[6:], 0.0) + t1 - t0
    result["setup"] = dict(setup, setup_s=run.setup_s, phases=phases)
    result["compared"] = numbers
    return result


def print_slowest(run: Run, n: int = 5) -> None:
    """The window's n slowest rounds on standard error, [index, ms]."""
    times = [(i, (b - a) * 1e3) for i, (a, b) in
             enumerate(zip(run.stamps, run.stamps[1:]))]
    slow = sorted(times, key=lambda t: -t[1])[:n]
    print(f"slowest rounds of {len(times)}: "
          + ", ".join(f"#{i} {ms:.1f} ms" for i, ms in slow), file=sys.stderr)


def print_result(result: dict) -> None:
    for name, v in result["compared"].items():
        print(f"compared {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
