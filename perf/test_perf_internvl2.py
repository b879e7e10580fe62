"""The InternVL2-1B cell: found by name, its counts against hand counts at
the published widths, and each of its readers on a hand-made trace."""
import pytest

from perf import harness
from perf import trace as trace_lib

WORKLOAD = "internvl2_1b.ring4.f32"
READERS = ["permute_ms", "permute_exposed_ms", "gossip_mix_roofline",
           "sgdm_roofline", "step_mfu"]


@pytest.fixture(scope="module")
def parts():
    return harness.parts(WORKLOAD)


def test_cell_found_by_name(parts):
    assert parts.workload["chips"] == 4
    assert parts.config["name"] == "internvl2_1b"
    assert parts.traffic["clients"] == 4 and parts.traffic["topology"] == "ring"
    assert {"tokens_per_s", "setup_s"} <= {m["name"] for m in parts.end_to_end}
    assert set(READERS) <= {m["name"] for m in parts.per_layer}
    assert callable(parts.cell_module.build)


def test_flops_and_tokens_by_hand(parts):
    mod, cfg, t = parts.cell_module, parts.config, parts.traffic
    seqs = 4 * 2 * 4                        # clients x K x batch
    block = (896 * (896 + 2 * 128)          # q, k, v
             + 896 * 896                     # o
             + 3 * 896 * 4864)               # gate, up, down
    assert block == 14_909_440
    by_hand = (6 * 24 * block * seqs * 1024          # every position
               + 6 * 896 * 151_655 * seqs * 768      # head, label positions
               + 6 * (4096 * 896 + 896 * 896) * seqs * 256   # projector
               + 3 * 2 * 1024 ** 2 * 896 * 24 * seqs)        # attention
    assert by_hand == 94_937_485_934_592
    assert mod.flops_per_round(cfg, t) == by_hand
    assert by_hand / 4 == pytest.approx(2.3735e13, rel=1e-4)   # per chip
    assert mod.tokens_per_round(cfg, t) == 24_576


# one client's leaves at the published widths, elements each
LEAVES = {"embed": 151_680 * 896, "head": 151_680 * 896, "final_norm": 896,
          "ln1": 24 * 896, "ln2": 24 * 896, "wq": 24 * 896 * 14 * 64,
          "wk": 24 * 896 * 2 * 64, "wv": 24 * 896 * 2 * 64,
          "wo": 24 * 14 * 64 * 896, "bq": 24 * 14 * 64, "bk": 24 * 2 * 64,
          "bv": 24 * 2 * 64, "w_gate": 24 * 896 * 4864,
          "w_up": 24 * 896 * 4864, "w_down": 24 * 4864 * 896,
          "ln_scale": 4096, "ln_bias": 4096, "w1": 4096 * 896, "b1": 896,
          "w2": 896 * 896, "b2": 896}


def tiles(n):      # elements padded to 256 x 128 tiles
    return -(-n // 32768) * 32768


def test_param_count_and_kernel_counts_by_hand(parts):
    mod, cfg, t = parts.cell_module, parts.config, parts.traffic
    assert sum(LEAVES.values()) == cfg["params_per_client_padded"]
    assert sorted(mod.client_elements(cfg)) == sorted(LEAVES.values())
    # mix: one packed bf16 buffer, 3 read + 1 written, f32 weights and alive
    packed = tiles(634_191_488)
    assert packed == 634_191_872
    assert mod.mix_kernel_round(cfg, t) == (
        1, 4 * packed * 2 + 2 * 3 * 4, 2 * 3 * packed)
    # sgdm: per leaf and step, w, v, g read and w, v written (bf16), the
    # (1, 2) f32 scalars, 4 FLOPs an element
    padded = sum(tiles(n) for n in LEAVES.values())
    assert padded == 634_486_784
    assert mod.sgdm_kernel_round(cfg, t) == (
        2 * 21, 2 * (10 * padded + 21 * 8), 2 * 4 * padded)


def hand_trace():
    """Four chips, two rounds of 0.5 s in a 1 s window. A round on each chip:
    a local fusion [0, 300) ms, 42 fused_sgdm calls of 0.5 ms from 300 ms,
    a permute in flight [330, 360) ms with a fusion [350, 360) beside it,
    then the mix kernel [370, 378) ms."""
    ms = 1e6
    device, async_ops = {}, {}
    for c in range(4):
        ops, flight = [], []
        for r in range(2):
            t0 = r * 500 * ms
            ops.append(["fusion.1", t0, 300 * ms])
            ops += [[f"fused_sgdm.{14 + k % 21}", t0 + 300 * ms + k * 0.5 * ms,
                     0.5 * ms] for k in range(42)]
            ops.append(["fusion.2", t0 + 350 * ms, 10 * ms])
            ops.append(["gossip_mix_alive.1", t0 + 370 * ms, 8 * ms])
            flight.append(["collective-permute-start.1", t0 + 330 * ms,
                           30 * ms])
        device[f"/device:TPU:{c}"] = sorted(ops, key=lambda o: o[1])
        async_ops[f"/device:TPU:{c}"] = flight
    return {"device": device, "async": async_ops,
            "host": [["perf.window", 0.0, 1000 * ms]]}


def traced_run(parts, flat=None):
    mod, cfg, t = parts.cell_module, parts.config, parts.traffic
    run = harness.Run(4, "TPU v5 lite", stamps=[0.0, 0.5, 1.0],
                      tokens_per_round=mod.tokens_per_round(cfg, t),
                      flops_per_round=mod.flops_per_round(cfg, t))
    if flat is not None:
        run.trace = trace_lib.summarize(flat, 4)
        run.trace["flat"] = flat
    return run


def test_readers_on_a_hand_made_trace(parts):
    run = traced_run(parts, hand_trace())
    got = {m: harness.load_module("metrics", m).read(run) for m in READERS}
    assert got["permute_ms"] == pytest.approx(30.0)
    assert got["permute_exposed_ms"] == pytest.approx(20.0)
    # mix: 5,073,535,000 bytes / 819 GB/s = 6.1948 ms against 8 ms a call
    assert got["gossip_mix_roofline"] == pytest.approx(
        100 * 5_073_535_000 / 819e9 / 8e-3)
    # sgdm: 12,689,736,016 bytes / 819 GB/s a round against 42 x 0.5 ms
    assert got["sgdm_roofline"] == pytest.approx(
        100 * 12_689_736_016 / 819e9 / 21e-3)
    # two rounds of 94.94 TFLOP in 1 s over 4 x 197 TFLOP/s
    assert got["step_mfu"] == pytest.approx(
        100 * 2 * 94_937_485_934_592 / (4 * 197e12))
    assert 0 < got["gossip_mix_roofline"] < 100
    assert 0 < got["sgdm_roofline"] < 100


def test_readers_find_nothing_without_the_kernels(parts):
    flat = hand_trace()
    for ops in flat["device"].values():
        ops[:] = [o for o in ops if o[0].startswith("fusion")]
    for ops in flat["async"].values():
        ops.clear()
    for run in (traced_run(parts), traced_run(parts, flat)):
        for m in ["permute_ms", "permute_exposed_ms", "gossip_mix_roofline",
                  "sgdm_roofline"]:
            assert harness.load_module("metrics", m).read(run) is None, m


def test_roofline_readers_ignore_a_run_of_another_cell(parts):
    run = traced_run(parts, hand_trace())
    run.flops_per_round += 1.0
    for m in ["gossip_mix_roofline", "sgdm_roofline"]:
        assert harness.load_module("metrics", m).read(run) is None
