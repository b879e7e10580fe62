"""BENCHMARK.json against its files, parts found by name, and the refusal
to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from perf import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.read_json("BENCHMARK.json")


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "perf/run.py"]
    assert spec["paths"] == ["perf"]
    assert 1 <= spec["run_seconds"] <= 51


def test_configs_files_and_names(spec):
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"] == f"perf/configs/{c['name']}.json"
        cfg = harness.read_json(c["file"])
        assert cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(ROOT, "perf", "configs",
                                           c["name"] + ".py"))


def test_workloads_have_traffic_limits_and_metrics(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    four = 0
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        four += w["chips"] == 4
        t = harness.read_json("perf", "traffic", w["traffic"] + ".json")
        assert t["limits"] and t["trace_seconds"] > 0
        p = harness.parts(w["name"])
        names = {m["name"] for m in p.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert p.per_layer
        assert all(m["moves"] in names for m in p.per_layer)
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_every_metric_has_a_reader_that_agrees(spec):
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            r = harness.load_module("metrics", m["name"])
            assert (r.UNIT, r.BETTER, r.SOURCE) == (
                m["unit"], m["better"], m["source"])
            if kind == "per_layer":
                assert (r.LAYER, r.MOVES) == (m["layer"], m["moves"])
            else:
                assert 0.01 <= m["bound"] <= 0.25
                assert m["source"] in ("host_clock", "device_trace")


# ------------------------------------------------------- parts by name
TOY_CELL = '''
import jax.numpy as jnp


class Cell:
    def __init__(self, cfg, traffic, seed32, spans):
        self.n, self.spans = cfg["width"], spans
        self.tokens_per_round = traffic["rows"]
        self.flops_per_round = 2.0 * traffic["rows"] * self.n ** 2

    def setup(self):
        self.x = jnp.ones((self.n, self.n))
        self.x = ((self.x @ self.x) / self.n).block_until_ready()  # warm-up

    def rounds(self, on_round):
        while True:
            on_round()
            with self.spans.span("toy_step"):
                self.x = (self.x @ self.x) / self.n
            self.x.block_until_ready()

    def release(self):
        self.x = None

    def check(self):
        return {"toy_gap": 0.0}


def build(cfg, traffic, seed32, spans):
    return Cell(cfg, traffic, seed32, spans)
'''

TOY_METRIC = '''
UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"
LAYER, MOVES = "toy layer", "tokens_per_s"


def read(run):
    spent = run.spans.between("toy_step", run.stamps[0], run.stamps[-1])
    return sum(spent) / max(run.rounds, 1) * 1e3
'''


def copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "perf"), os.path.join(dst, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))


def snapshot(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perf")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), root)] = fh.read()
    return out


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, and named in new entries of BENCHMARK.json, run without an edit
    to any file the benchmark already has."""
    copy_benchmark(tmp_path)
    before = snapshot(tmp_path)
    perf = tmp_path / "perf"
    (perf / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "width": 8, "reduced": []}))
    (perf / "configs" / "toy.py").write_text(TOY_CELL)
    (perf / "traffic" / "toy.mix.json").write_text(json.dumps(
        {"rows": 8, "trace_seconds": 1, "limits": {"toy_gap": 0.0}}))
    (perf / "metrics" / "toy_step_ms.py").write_text(TOY_METRIC)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "https://example.org",
                            "file": "perf/configs/toy.json", "reduced": [],
                            "why": "toy"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy.mix", "chips": 1, "why": "toy"})
    spec["per_layer"].append({"name": "toy_step_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "toy layer", "moves": "tokens_per_s",
                              "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    result = harness.execute("toy.cell", 3, 0.3, False, time.perf_counter(),
                             require_tpu=False, root=str(tmp_path),
                             cache=False)
    assert result["correct"] and result["attempted"] > 0
    assert {"tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert "round_ms_p90" not in result["metrics"]  # not listed for toy.cell
    assert list(result)[-1] == "compared"
    p = harness.parts("toy.cell", root=str(tmp_path))
    assert [m["name"] for m in p.per_layer] == ["toy_step_ms"]
    run = harness.Run(1, "cpu", stamps=[0.0, 1.0],
                      spans=harness.Spans(), root=str(tmp_path))
    run.spans.records.append(("toy_step", 0.5, 0.75))
    assert harness.read_metrics(p.per_layer, run) == {
        "toy_step_ms": {"value": 250.0, "unit": "ms"}}
    after = snapshot(tmp_path)
    assert {k: after[k] for k in before} == before


def test_seed32_takes_any_whole_seed():
    seeds = [0, 1, 2**31 - 1, 2**31 + 5, 2**33 + 5, 5]
    got = [harness.seed32(s) for s in seeds]
    assert len(set(got)) == len(seeds)
    assert all(0 <= g < 2**32 for g in got)
    assert harness.seed32(2**33 + 5) == got[4]


def run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "lstm128.f32.k3",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    r = run_cli(ROOT)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert not r.stdout.strip()


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    copy_benchmark(tmp_path)
    r = run_cli(tmp_path)
    assert r.returncode != 0
    assert not r.stdout.strip()
