"""The program's scopes and spans in a trace: a hand-made profile counted
by hand, two rounds of the simulator traced on the CPU, and a recorded chip
trace."""
import os

import pytest

from perf import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))

# one device running two programs: the round [1000, 7000) and the loss mean
# [7200, 7300). The round's operations: its local phase (a while loop
# [1000, 4000) around a fusion [1500, 2500)), a copy without metadata
# [4000, 4500), then the gossip engine's pack [4600, 5600), mix
# [5600, 6200) and unpack [6200, 6800). The mean's one operation shares an
# instruction name with the round's. Host: the window [500, 9500); round 7
# [400, 7400) with batch [400, 800), operands [800, 900), dispatch
# [900, 1000), sync [1000, 7350), record [7350, 7400); round 8
# [7500, 9600) with batch [7500, 9000), operands [9000, 9100), dispatch
# [9100, 9200), sync [9200, 9600).
PROFILE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 3600000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 4600000 duration_ps: 600000 }
    events { metadata_id: 6 offset_ps: 5200000 duration_ps: 600000 }
    events { metadata_id: 2 offset_ps: 6200000 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 8 offset_ps: 6200000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while()" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "copy.3" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.4" } }
  event_metadata { key: 5 value { id: 5 name: "fusion.5" } }
  event_metadata { key: 6 value { id: 6 name: "slice.6" } }
  event_metadata { key: 7 value { id: 7 name: "jit_round_fn(123)" } }
  event_metadata { key: 8 value { id: 8 name: "jit__mean(456)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 400000 duration_ps: 7000000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 400000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 800000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 900000 duration_ps: 100000 }
    events { metadata_id: 6 offset_ps: 1000000 duration_ps: 6350000 }
    events { metadata_id: 7 offset_ps: 7350000 duration_ps: 50000 }
    events { metadata_id: 2 offset_ps: 7500000 duration_ps: 2100000
             stats { metadata_id: 1 int64_value: 8 } }
    events { metadata_id: 3 offset_ps: 7500000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 9100000 duration_ps: 100000 }
    events { metadata_id: 6 offset_ps: 9200000 duration_ps: 400000 }
    events { metadata_id: 8 offset_ps: 7600000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "perf.window" } }
  event_metadata { key: 2 value { id: 2 name: "dfl.round" } }
  event_metadata { key: 3 value { id: 3 name: "dfl.batch" } }
  event_metadata { key: 4 value { id: 4 name: "dfl.operands" } }
  event_metadata { key: 5 value { id: 5 name: "dfl.dispatch" } }
  event_metadata { key: 6 value { id: 6 name: "dfl.sync" } }
  event_metadata { key: 7 value { id: 7 name: "dfl.record" } }
  event_metadata { key: 8 value { id: 8 name: "perf.batch_fn" } }
  stat_metadata { key: 1 value { id: 1 name: "step_num" } }
}
'''

# the round's compiled text: op_name metadata on the entry's instructions
# and on an instruction of a fused computation, none on the copy
HLO = '''HloModule jit_round_fn, is_scheduled=true

%fused_computation (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %multiply.9 = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(round_fn)/dfl.gossip/mul"}
}

ENTRY %main.10 (p: f32[4]) -> f32[4] {
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(round_fn)/vmap(dfl.local)/while" stack_frame_id=3}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(round_fn)/vmap(dfl.local)/while/body/closed_call/jvp()/mul"}
  %copy.3 = f32[4]{0} copy(%p)
  %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(round_fn)/dfl.gossip/vmap(pack)/concatenate"}
  %fusion.5 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(round_fn)/dfl.gossip/mul;jit(round_fn)/dfl.gossip/mul"}
  ROOT %slice.6 = f32[4]{0} slice(%p), slice={[0:4]}, metadata={op_name="jit(round_fn)/dfl.gossip/vmap(unpack)/slice"}
}
'''


@pytest.fixture(scope="module")
def flat():
    from jax.profiler import ProfileData
    return scopes.flatten(ProfileData.from_text_proto(PROFILE),
                          scopes.op_names(HLO))


def test_op_names_of_compiled_text():
    names = scopes.op_names(HLO)
    assert list(names) == ["jit_round_fn"]
    assert names["jit_round_fn"]["while.1"] == \
        "jit(round_fn)/vmap(dfl.local)/while"
    assert names["jit_round_fn"]["multiply.9"] == \
        "jit(round_fn)/dfl.gossip/mul"
    assert "copy.3" not in names["jit_round_fn"]


def test_scope_labels():
    label = scopes.scope_label
    assert label("jit(round_fn)/vmap(dfl.local)/while") == "local"
    assert label("transpose(jvp(dfl.local))/dot_general") == "local"
    assert label("jit(f)/dfl.gossip/vmap(pack)/jit(_pad)/pad") == \
        "gossip.pack"
    assert label("jit(f)/dfl.gossip/vmap(unpack)/slice") == "gossip.unpack"
    assert label("jit(f)/dfl.gossip/mul;jit(f)/vmap(dfl.local)/add") == \
        "gossip"
    # pack and unpack count only under dfl.gossip; the innermost scope wins
    assert label("jit(f)/vmap(unpack)/slice") is None
    assert label("jit(f)/dfl.local/pack/x") == "local"
    assert label("jit(f)/dfl.local/dfl.gossip/x") == "gossip"
    assert label("params['embed']") is None and label(None) is None


def test_flatten_adds_scopes_and_program_spans(flat):
    # the existing keys as perf.trace.flatten gives them
    assert [s[0] for s in flat["host"]] == ["perf.window", "perf.batch_fn"]
    paths = flat["scopes"]["/device:TPU:0"]
    assert len(paths) == len(flat["device"]["/device:TPU:0"])
    assert [scopes.scope_label(p) for p in paths] == [
        "local", "local", None, "gossip.pack", "gossip", "gossip.unpack",
        None]
    assert paths[2] is None            # the copy carries no metadata
    assert paths[6] is None            # fusion.2 of the mean, not the round
    prog = flat["program"]
    assert [(s[0], s[3]) for s in prog] == [
        ("dfl.round", 7), ("dfl.batch", 7), ("dfl.operands", 7),
        ("dfl.dispatch", 7), ("dfl.sync", 7), ("dfl.record", 7),
        ("dfl.round", 8), ("dfl.batch", 8), ("dfl.operands", 8),
        ("dfl.dispatch", 8), ("dfl.sync", 8)]
    assert prog[0][1:3] == [400.0, 7000.0]


def test_flatten_without_names_has_no_scopes():
    from jax.profiler import ProfileData
    f = scopes.flatten(ProfileData.from_text_proto(PROFILE))
    assert set(f["scopes"]["/device:TPU:0"]) == {None}
    lo, hi = trace.window(f, "perf.window")
    assert scopes.device_scopes(f["device"]["/device:TPU:0"],
                                f["scopes"]["/device:TPU:0"], lo, hi) is None


def test_scope_time_by_hand(flat):
    ops = flat["device"]["/device:TPU:0"]
    labels = [scopes.scope_label(p) for p in flat["scopes"]["/device:TPU:0"]]
    # the while loop and the fusion inside it: a union, not a sum
    assert scopes.scope_ns(ops, labels, "local", 0, 1e9) == 3000.0
    assert scopes.scope_ns(ops, labels, "local", 0, 2000) == 1000.0
    got = scopes.device_scopes(ops, flat["scopes"]["/device:TPU:0"],
                               500, 9500)
    # busy 5800: the copy (500) and the mean (100) are unscoped
    assert got == {"local": 3000.0, "gossip": 600.0, "gossip.pack": 1000.0,
                   "gossip.unpack": 600.0, "unscoped": 600.0}
    assert sum(got.values()) == trace.busy_ns(ops, 500, 9500)


def test_host_self_time_by_hand(flat):
    # rounds in the window: [500, 7400) and [7500, 9500); less the syncs
    # [1000, 7350) and [9200, 9500): 500 + 50 + 1700
    assert scopes.host_self_ns(flat["program"], 500, 9500) == 2250.0
    assert scopes.host_self_ns([], 500, 9500) is None


def test_idle_by_span_by_hand(flat):
    ops = flat["device"]["/device:TPU:0"]
    got = scopes.idle_by_span(ops, flat["program"], 500, 9500)
    # idle: [500, 1000) batch 300, operands 100, dispatch 100;
    # [4500, 4600) and [6800, 7200) sync; [7300, 9500): sync 50, record
    # 50, between the rounds 100, batch 1500, operands 100, dispatch 100,
    # sync 300
    assert got == {"dfl.batch": 1800.0, "dfl.operands": 200.0,
                   "dfl.dispatch": 200.0, "dfl.sync": 850.0,
                   "dfl.record": 50.0, "host_outside_spans": 100.0}
    assert sum(got.values()) == 9000 - trace.busy_ns(ops, 500, 9500)
    assert scopes.idle_by_span(ops, [], 500, 9500) is None


def test_split_by_hand(flat):
    s = scopes.split(flat, rounds=2)
    assert s["local_ms"] == pytest.approx(3000e-9 / 2 * 1e3)
    assert s["gossip_ms"] == pytest.approx(2200e-9 / 2 * 1e3)
    assert s["host_ms"] == pytest.approx(2250e-9 / 2 * 1e3)
    assert s["idle_ms"] == pytest.approx(3200e-9 / 2 * 1e3)
    assert s["device_scopes"]["unscoped"] == pytest.approx(600e-9)
    assert s["idle_by_span"]["dfl.batch"] == pytest.approx(1800e-9)


def test_split_of_a_trace_without_the_programs_scopes_or_spans():
    """The recorded trace of a program without scopes or round spans
    (``lstm128_trace.json.gz``): the window's numbers, none of the
    program's."""
    flat = trace.load(os.path.join(HERE, "testdata", "lstm128_trace.json.gz"))
    flat["scopes"] = {p: [None] * len(o) for p, o in flat["device"].items()}
    flat["program"] = []
    s = scopes.split(flat, rounds=2)
    assert s["idle_ms"] > 0
    assert s["local_ms"] is s["gossip_ms"] is s["host_ms"] is None
    assert s["device_scopes"] is s["idle_by_span"] is None


def test_simulator_rounds_traced_on_the_cpu(tmp_path):
    """Two rounds of SimTrainer.run under the profiler: one dfl.round per
    round with the round index as its step number, and the five leaf spans
    in order inside it."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core import dfedavg
    from repro.core.topology import expander_overlay
    from repro.launch.train import SimTrainer

    n = 8
    tr = SimTrainer(overlay=expander_overlay(n, 4, seed=0),
                    loss_fn=lambda p, b: (
                        jnp.mean(jnp.square(p["w"] - b["t"])), {}),
                    dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.3,
                                                momentum=0.5))
    params = {"w": jnp.zeros((n, 3))}

    def batch_fn(rnd):
        return {"t": jnp.full((n, 2, 3), float(rnd))}

    params, _ = tr.run(params, batch_fn, 1, lambda r: 0.3)  # compile
    with jax.profiler.trace(str(tmp_path)):
        _, hist = tr.run(params, batch_fn, 3, lambda r: 0.3, start_round=1)
    assert [h["round"] for h in hist] == [1, 2]
    assert all(set(h) == {"round", "train_loss"} for h in hist)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    prog = scopes.flatten(ProfileData.from_file(path))["program"]
    rounds = [s for s in prog if s[0] == "dfl.round"]
    assert [s[3] for s in rounds] == [1, 2]
    for r in rounds:
        leaves = [s for s in prog if s[0] != "dfl.round" and s[3] == r[3]]
        assert [s[0] for s in leaves] == [
            "dfl.batch", "dfl.operands", "dfl.dispatch", "dfl.sync",
            "dfl.record"]
        assert all(r[1] <= s[1] and s[1] + s[2] <= r[1] + r[2]
                   for s in leaves)
