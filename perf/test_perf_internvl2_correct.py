"""The InternVL2-1B cell's comparison at a size a CPU holds: the cell on a
4-client ring of CPU devices, at ``registry.reduced("internvl2-1b")``
widths in float32, runs correct through the harness, and each fault
planted in the reference fails a limit."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from perf import harness

WORKLOAD = "internvl2_1b.ring4.f32"
SMALL_TRAFFIC = {"seq": 24, "batch": 4, "grad_accum": 2}

_RUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys, time
    root = sys.argv[1]
    sys.path[:0] = [root, os.path.join(root, "src")]
    from perf import harness
    w = "internvl2_1b.ring4.f32"
    run = harness.execute(w, 2**33 + 17, 0.5, False, time.perf_counter(),
                          require_tpu=False, root=root, cache=False)
    p = harness.parts(w, root=root)
    cell = p.cell_module.build(p.config, p.traffic, harness.seed32(5),
                               harness.Spans())
    cell.setup()
    cell.release()
    want = cell.reference()
    faults = {f: harness.judge(cell.gaps(cell.reference(fault=f), want),
                               p.traffic["limits"])
              for f in ("half_batch", "no_mix", "no_qkv_bias")}
    print(json.dumps({"run": run["compared"], "correct": run["correct"],
                      "attempted": run["attempted"], "faults": faults}))
""")


def small_root(tmp_path):
    """The benchmark's files with the cell cut to the reduced widths in
    float32 and a short sequence; the program from this checkout."""
    from repro.configs import registry

    root = harness.ROOT
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    os.symlink(os.path.join(root, "src"), tmp_path / "src")
    r = registry.reduced("internvl2-1b")
    path = tmp_path / "perf" / "configs" / "internvl2_1b.json"
    c = json.loads(path.read_text())
    c.update(n_layers=r.n_layers, d_model=r.d_model, n_heads=r.n_heads,
             n_kv_heads=r.n_kv_heads, head_dim=r.head_dim, d_ff=r.d_ff,
             vocab=r.vocab, image_tokens=r.stub_prefix,
             vision_feature_dim=r.vision_feature_dim, dtype="float32")
    path.write_text(json.dumps(c))
    path = tmp_path / "perf" / "traffic" / "ring4.f32.k2.json"
    t = json.loads(path.read_text())
    t.update(SMALL_TRAFFIC)
    path.write_text(json.dumps(t))
    return str(tmp_path)


def test_sound_run_is_correct_and_planted_faults_fail(tmp_path):
    out = subprocess.run([sys.executable, "-c", _RUN, small_root(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"], r["run"]
    assert r["attempted"] >= 1
    for fault, judged in r["faults"].items():
        assert not all(v["ok"] for v in judged.values()), (fault, judged)
