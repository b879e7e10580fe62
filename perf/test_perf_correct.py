"""The comparison that decides ``correct``, at a size a CPU test can hold:
a sound run passes; the control (the reference in bfloat16, put in the
program's place) and each fault the char-LSTM cell can have, planted in
the program underneath a whole run, fail."""
import json
import os
import shutil
import time

import pytest

from perf import harness

ROOT = harness.ROOT
WORKLOAD = "lstm128.f32.k3"
SMALL = {"clients": 8, "batch": 4, "seq": 16}


@pytest.fixture
def small_root(tmp_path):
    """The benchmark's files with the cell's traffic cut to SMALL."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    name = harness.parts(WORKLOAD).workload["traffic"]
    path = tmp_path / "perf" / "traffic" / f"{name}.json"
    t = json.loads(path.read_text())
    t.update(SMALL)
    path.write_text(json.dumps(t))
    return str(tmp_path)


def run(root):
    return harness.execute(WORKLOAD, 2**33 + 17, 0.5, False,
                           time.perf_counter(), require_tpu=False, root=root,
                           cache=False)


def test_sound_run_is_correct(small_root):
    r = run(small_root)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0


def test_control_fails(small_root):
    p = harness.parts(WORKLOAD, root=small_root)
    cell = p.cell_module.build(p.config, p.traffic, harness.seed32(5),
                               harness.Spans())
    cell.setup()
    cell.release()
    judged = harness.judge(
        cell.gaps(cell.reference("bfloat16"), cell.reference()),
        p.traffic["limits"])
    assert not all(v["ok"] for v in judged.values()), judged


def test_round_that_returns_its_state_unchanged_fails(small_root,
                                                      monkeypatch):
    from repro.launch import train
    build = train.SimTrainer._build

    def broken(self, spec):
        fn = build(self, spec)

        def round_fn(params, *args):
            _, losses, metrics = fn(params, *args)
            return params, losses, metrics
        return round_fn

    monkeypatch.setattr(train.SimTrainer, "_build", broken)
    r = run(small_root)
    assert not r["correct"]
    assert r["compared"]["step1_client_change_gap"]["value"] == pytest.approx(
        1.0)


def test_half_the_batch_left_out_fails(small_root, monkeypatch):
    from repro.models import lstm
    whole = lstm.loss_fn

    def half(params, batch):
        rows = batch["tokens"].shape[0] // 2
        return whole(params, {k: v[:rows] for k, v in batch.items()})

    monkeypatch.setattr(lstm, "loss_fn", half)
    r = run(small_root)
    assert not r["correct"], r["compared"]


def test_gossip_exchange_left_out_fails(small_root, monkeypatch):
    from repro.core import engine
    build = engine.build_gossip_executor

    class NoExchange:
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def __call__(self, params, **_):
            return params

    monkeypatch.setattr(engine, "build_gossip_executor",
                        lambda *a, **k: NoExchange(build(*a, **k)))
    r = run(small_root)
    assert not r["correct"], r["compared"]


def test_reference_draws_the_cells_overlay_as_the_program_does():
    from perf.configs import char_lstm, char_lstm_ref
    from repro.core import topology

    t = harness.parts(WORKLOAD).traffic
    ov = topology.expander_overlay(t["clients"], t["degree"],
                                   seed=t["overlay_seed"])
    got = char_lstm.schedule_adjacency(ov.schedules, t["clients"])
    want = char_lstm_ref.overlay_adjacency(
        t["topology"], t["clients"], t["degree"], t["overlay_seed"])
    assert (got == want).all()
    assert (want.sum(axis=1) == t["degree"]).all()


def test_overlay_drawn_from_another_seed_fails(small_root, monkeypatch):
    from repro.core import topology
    draw = topology.expander_overlay

    def other(n, d, seed=0, **kw):
        return draw(n, d, seed=seed + 1, **kw)

    monkeypatch.setattr(topology, "expander_overlay", other)
    r = run(small_root)
    assert not r["correct"]
    assert r["compared"]["overlay_edges_differing"]["value"] > 0


def test_change_norms_are_taken_client_by_client():
    import jax.numpy as jnp
    import numpy as np

    from perf import compare

    p0 = {"a": jnp.zeros((3, 2, 2)), "b": jnp.zeros((3, 4))}
    p = {"a": jnp.arange(12.0).reshape(3, 2, 2), "b": jnp.ones((3, 4))}
    got = compare.leaf_diff_norms(p, p0)
    want = [np.linalg.norm(np.arange(12.0).reshape(3, 4), axis=1),
            np.full(3, 2.0)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the worst client's leaf, over the larger of its own norm and the
    # median client-leaf's
    assert compare.worst_leaf_gap([1.0, 2.0, 4.0], [1.0, 2.0, 5.0],
                                  [True] * 3) == 0.2
