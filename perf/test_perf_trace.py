"""The trace reduction, on a hand-made profile and on a recorded chip trace."""
import os

import pytest

from perf import trace

HERE = os.path.dirname(os.path.abspath(__file__))

# one device: a fusion [1000, 3000) ns, a collective [2500, 5000) that the
# fusion hides in part, a copy [6000, 7000); host: the window span
# [500, 9500) and a batch_fn span [5200, 5900) inside it
PROFILE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "collective-permute-start.1" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_round_fn" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 5200000 duration_ps: 700000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "perf.window" } }
  event_metadata { key: 2 value { id: 2 name: "perf.batch_fn" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } }
}
'''


@pytest.fixture(scope="module")
def flat():
    from jax.profiler import ProfileData
    return trace.flatten(ProfileData.from_text_proto(PROFILE))


def test_flatten_keeps_device_ops_and_harness_spans(flat):
    assert list(flat["device"]) == ["/device:TPU:0"]
    assert [o[0] for o in flat["device"]["/device:TPU:0"]] == [
        "fusion.1", "collective-permute-start.1", "copy.2"]
    assert [s[0] for s in flat["host"]] == ["perf.window", "perf.batch_fn"]


def test_interval_algebra():
    assert trace.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert trace.length([(0, 3), (5, 6)]) == 4


def test_summary_by_hand(flat):
    s = trace.summarize(flat)
    assert s["window_s"] == pytest.approx(9000e-9)
    # busy: [1000, 5000) and [6000, 7000)
    assert s["busy_s"] == pytest.approx(5000e-9)
    # the collective runs [2500, 5000); the fusion covers [2500, 3000)
    assert s["collective_s"] == pytest.approx(2500e-9)
    assert s["collective_exposed_s"] == pytest.approx(2000e-9)
    top = s["breakdown"]["device_ops"]
    assert top[0] == ["collective-permute-start.1", pytest.approx(2500e-9)]
    gaps = s["breakdown"]["idle_gaps"]
    # idle: [500, 1000), [5000, 6000) (batch_fn covers 700 of it),
    # [7000, 9500)
    assert gaps[0] == ["host_outside_spans", pytest.approx(2500e-9)]
    assert gaps[1] == ["batch_fn", pytest.approx(1000e-9)]
    assert len(gaps) == 3


def test_kernel_time(flat):
    ops = flat["device"]["/device:TPU:0"]
    assert trace.kernel_ns(ops, r"^fusion", 0, 1e9) == 2000.0
    # clipped to the window's end
    assert trace.kernel_ns(ops, r"^copy", 0, 6500) == 500.0


def test_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize({"device": {"/device:TPU:0": []},
                         "host": [["perf.window", 0.0, 10.0]]})


def test_recorded_chip_trace():
    """A few rounds of the char-LSTM cell traced on a TPU v5e, trimmed:
    the reduction finds the window, device work inside it, and an idle
    share between 0 and 1."""
    flat = trace.load(os.path.join(HERE, "testdata", "lstm128_trace.json.gz"))
    s = trace.summarize(flat)
    assert s["window_s"] == pytest.approx(0.837259471)
    assert s["busy_s"] == pytest.approx(0.812504898)
    assert s["devices"] == 1 and s["collective_s"] == 0.0
    # the LSTM scans are the while loops; the device waits on the host's
    # batch_fn between rounds
    assert s["breakdown"]["device_ops"][0][0] == "while.300"
    assert [g[0] for g in s["breakdown"]["idle_gaps"][:2]] == ["batch_fn"] * 2
