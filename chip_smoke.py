#!/usr/bin/env python3
"""Chip smoke test: the DFL round end to end on a TPU, checked against
plain references.

    python chip_smoke.py               # one chip: the char-LSTM main path
    python chip_smoke.py --four-chips  # a 2x2 host: the multi-chip substrates

One chip runs ``repro.launch.train``'s path (``build_char_lm`` ->
``SimTrainer``, stacked substrate) for the paper's char-LSTM at its own
widths (2 layers, 256 hidden, embed 128, vocab 53) on the bundled
Shakespeare corpus: 128 clients on a degree-4 expander, K = 3 local steps,
batch 8, seq 64. Two engine cells:

* ``f32`` with telemetry — the HIGHEST-precision mix einsum plus the
  ``sqnorms_2d`` kernel;
* ``int8_block`` — ``quantize_2d_blockwise`` under vmap.

Four chips run only what exists across chips:

* ``shard_map`` — ``launch.steps.build_train_step`` on the device mesh,
  one ``internvl2-1b`` client (published widths, 4 x 1024 positions, the
  first 256 of them 4096-wide vision features through the projector) per
  chip on a ring, ``f32`` and ``int8_block``: d collective-permutes per round
  into ``gossip_mix_2d`` / ``dequant_accumulate_2d_blockwise``;
* ``blocked`` — 512 char-LSTM clients, 128 per chip (``--gossip-block
  128``), whole-block permutes between chips.

Every phase checks, and fails on any miss:

* the device is a TPU, and each Pallas kernel on the path compiled as a
  Mosaic kernel (``tpu_custom_call``), none in interpret mode;
* the loss is finite (and, where the phase trains, descends);
* one gossip round matches a float64 NumPy ``W @ x`` from the overlay's
  Chow weights, elementwise within ``F32_TOL * max|x|`` — plus, per
  receiver, the int8 quantization bound ``sum_j |W_ij| * amax_j / 254``
  for ``int8_block`` and half a bf16 ulp per rounding for bf16
  parameters (see ``mix_vs_reference``). The round is
  the trainer's own compiled round run at lr = 0, which makes the local
  phase an exact no-op;
* on four chips, every device holds its own clients' shard.

The run stays in this one process (no child ever needs the chip), keeps
JAX's compile cache where ``repro.launch.compile_cache`` says, and prints
one JSON line per phase; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
It prints no speed. Without a TPU it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# f32 mixing error allowed, relative to the largest |x| of the round (a
# (d+1)-term f32 weighted sum is off by a few ulp; 1e-6 is ~8 ulp)
F32_TOL = 1e-6
BF16_HALF_ULP = 2.0 ** -8      # round-to-nearest bf16 output, relative
MAX_COLS = 1 << 16             # reference columns compared per leaf
SEED = 0


# ------------------------------------------------------------------ helpers
class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache loads
    included) and the persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.cache_hits


def pallas_eqns(jaxpr):
    """Every pallas_call equation in a (closed) jaxpr, nested ones too."""
    from jax.extend import core as jcore
    out = []
    todo = [jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr]
    while todo:
        jp = todo.pop()
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        todo.append(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        todo.append(sub)
    return out


def kernel_checks(traced, compiled_text, expect_kernels: bool) -> tuple:
    """The compiled round holds Mosaic kernels exactly where Pallas calls
    are on the path, and none of them runs in interpret mode."""
    eqns = pallas_eqns(traced.jaxpr)
    n_custom = compiled_text.count("tpu_custom_call")
    checks = {"no_interpret_kernels":
              all(not e.params.get("interpret") for e in eqns)}
    if expect_kernels:
        checks["pallas_on_path"] = len(eqns) > 0
        checks["tpu_custom_call_compiled"] = n_custom > 0
    else:
        checks["no_pallas_expected"] = len(eqns) == 0
    return checks, {"pallas_calls": len(eqns), "tpu_custom_calls": n_custom}


def sample_cols(tree, n):
    """Each leaf as (n, <= MAX_COLS) evenly strided columns, on the host.
    Mixing acts column by column, so a column subset is checked exactly."""
    import jax
    import numpy as np

    def one(x):
        flat = x.reshape(n, -1)
        stride = max(1, math.ceil(flat.shape[1] / MAX_COLS))
        return flat[:, ::stride]

    return [np.asarray(jax.device_get(c), np.float64)
            for c in jax.tree.leaves(jax.jit(lambda t: jax.tree.map(one, t))(
                tree))]


def client_amax(tree):
    """(n,) largest |x| of each client over all its leaves (bounds every
    per-block int8 scale of its packed buffers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f(t):
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(x.astype(jnp.float32)).reshape(x.shape[0], -1),
                    axis=1) for x in jax.tree.leaves(t)]), axis=0)

    return np.asarray(jax.jit(f)(tree), np.float64)


def mix_vs_reference(x_cols, y_cols, w, amax, *, codec,
                     bf16_roundings=0) -> tuple:
    """Elementwise check of one round y = mix(x) against float64 W @ x.

    Tolerance per element: F32_TOL * max|x|, plus for ``int8_block`` the
    receiver's quantization bound sum_j |W_ij| * amax_j / 254, plus for
    bf16 parameters half a bf16 ulp of |W| @ |x| (which bounds every
    partial sum) per rounding the reduce makes."""
    import numpy as np
    aw = np.abs(w)
    off = aw * (1.0 - np.eye(w.shape[0]))
    quant = (off @ amax / 254.0 if codec == "int8_block"
             else np.zeros_like(amax))
    floor = F32_TOL * float(amax.max())
    worst = worst_id = -np.inf
    err_max = 0.0
    for x, y in zip(x_cols, y_cols):
        ref = w @ x
        tol = (floor + quant[:, None] * (1 + 1e-6)
               + bf16_roundings * BF16_HALF_ULP * (aw @ np.abs(x)))
        worst = max(worst, float(np.max(np.abs(y - ref) - tol)))
        # a do-nothing round (y = x) must fail the same check
        worst_id = max(worst_id, float(np.max(np.abs(x - ref) - tol)))
        err_max = max(err_max, float(np.max(np.abs(y - ref))))
    checks = {"mix_matches_f64_reference": worst <= 0.0,
              "reference_discriminates": worst_id > 0.0}
    metrics = {"mix_max_abs_err": err_max,
               "mix_max_rel_err": err_max / float(amax.max()),
               "tol_f32_rel": F32_TOL,
               "tol_quant_max": float(np.max(quant)),
               "bf16_roundings": bf16_roundings}
    return checks, metrics


# ------------------------------------------------------------------ phases
def char_lm_phase(*, codec, telemetry, n_clients=128, rounds=4, block=0,
                  expect_kernels=True):
    """The train CLI's path: build_char_lm -> SimTrainer.run, then one
    lr = 0 round of the same compiled round against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import train
    from repro.overlay import plan as overlay_plan

    app = train.build_char_lm(n_clients=n_clients, topology="expander",
                              degree=4, local_steps=3, batch=8, seq=64,
                              seed=SEED, gossip_codec=codec,
                              gossip_block=block, telemetry=telemetry)
    trainer = app.trainer
    params, hist = app.run(rounds)
    # the initial weights of every client sit on device 0; the blocked
    # round's temporaries need that room on four chips
    app.params = None
    losses = [h["train_loss"] for h in hist]
    checks = {"loss_finite": all(math.isfinite(v) for v in losses),
              "loss_descends": losses[-1] < losses[0]}
    metrics = {"losses": losses}

    gates = jnp.asarray(overlay_plan.gates_for(trainer.plan, rounds,
                                               trainer.spec.degree))
    args = (params, app.batch_fn(rounds), jnp.asarray(0.0, jnp.float32),
            jnp.ones(n_clients, jnp.float32), gates, None, None)
    traced = trainer.round_fn.trace(*args)
    lowered = traced.lower()
    kc, km = kernel_checks(traced, lowered.compile().as_text(),
                           expect_kernels)
    checks.update(kc)
    metrics.update(km)
    metrics["collective_permutes"] = lowered.as_text().count(
        "collective_permute")

    x_cols = sample_cols(params, n_clients)
    amax = client_amax(params)
    mixed, round_losses, _ = trainer.round_fn(*args)
    checks["loss_finite"] &= bool(np.all(np.isfinite(
        np.asarray(round_losses))))
    y_cols = sample_cols(mixed, n_clients)
    w = np.asarray(trainer.overlay.mixing_matrix(), np.float64)
    mc, mm = mix_vs_reference(x_cols, y_cols, w, amax, codec=codec)
    checks.update(mc)
    metrics.update(mm)
    if codec == "f32" and not block:
        metrics["default_precision_rel_err"] = default_precision_error(
            params, w, amax)
    if block:
        sc, sm = placement_checks(mixed, n_devices=n_clients // block)
        checks.update(sc)
        metrics.update(sm)
    return checks, metrics


def default_precision_error(params, w, amax):
    """What the same W @ x gives through an einsum at DEFAULT precision on
    this device (the gossip einsums pass HIGHEST): max relative error on
    the largest leaf. Reported, not checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaf = max(jax.tree.leaves(params), key=lambda x: x.size)
    n = leaf.shape[0]
    y = jax.jit(lambda m, x: jnp.einsum("cd,df->cf", m, x.reshape(n, -1)))(
        jnp.asarray(w, jnp.float32), leaf)
    ref = w @ np.asarray(leaf, np.float64).reshape(n, -1)
    return float(np.max(np.abs(np.asarray(y, np.float64) - ref))
                 / amax.max())


def placement_checks(tree, n_devices):
    """Each of the first n_devices devices holds exactly its own slice of
    the client axis of every leaf."""
    import jax
    devs = set(jax.devices()[:n_devices])
    ok = True
    for x in jax.tree.leaves(tree):
        shards = x.addressable_shards
        rows = {s.device: (s.index[0].start or 0) for s in shards}
        ok &= (set(rows) == devs and len(set(rows.values())) == n_devices
               and all(s.data.shape[0] == x.shape[0] // n_devices
                       for s in shards))
    return ({"state_on_every_device": bool(ok)},
            {"devices_holding_state": len(devs)})


def shard_map_phase(*, codec, arch="internvl2-1b", seq=1024, per_client=4,
                    local_steps=2, grad_accum=4):
    """launch.steps.build_train_step on the device mesh, one client per
    chip on a ring: one lr = 0 step against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.configs import registry
    from repro.configs.base import DFLConfig, ParallelConfig, ShapeConfig
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps
    from repro.models import params as params_lib
    from repro.models.api import ModelAPI

    cfg = registry.get(arch)
    mesh = mesh_lib.make_production_mesh()
    n = mesh.shape["data"]
    shape = ShapeConfig("smoke", seq, per_client * n, "train")
    par = ParallelConfig(clients_per_pod=n, tp=1, local_steps=local_steps,
                         grad_accum=grad_accum, gossip_codec=codec)
    setup = steps.build_train_step(cfg, shape, mesh, par,
                                   DFLConfig(topology="ring", seed=SEED))
    p_shard, b_shard = setup.in_shardings[0], setup.in_shardings[1]

    struct1 = ModelAPI(cfg).param_struct()
    params = jax.jit(jax.vmap(lambda k: params_lib.init_params(struct1, k)),
                     out_shardings=p_shard)(
        jax.random.split(jax.random.key(SEED), n))

    bspec = setup.input_specs["batch"]

    def make_batch(key):
        out = {}
        for i, (name, s) in enumerate(sorted(bspec.items())):
            k = jax.random.fold_in(key, i)
            if jnp.issubdtype(s.dtype, jnp.integer):
                out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab,
                                               s.dtype)
            else:
                out[name] = jax.random.normal(k, s.shape).astype(s.dtype)
        return out

    batch = jax.jit(make_batch, out_shardings=b_shard)(
        jax.random.key(SEED + 1))
    repl = NamedSharding(setup.dfl_mesh, jax.sharding.PartitionSpec())
    lr = jax.device_put(jnp.asarray(0.0, jnp.float32), repl)
    alive = jax.device_put(jnp.ones(n, jnp.float32), repl)
    gates = jax.device_put(jnp.ones(setup.gossip_spec.degree, jnp.float32),
                           repl)

    traced = setup.step_fn.trace(params, batch, lr, alive, gates)
    lowered = traced.lower()
    compiled = lowered.compile()
    checks, metrics = kernel_checks(traced, compiled.as_text(), True)
    permutes = lowered.as_text().count("collective_permute")
    checks["d_collective_permutes"] = permutes == setup.gossip_spec.degree
    mem = compiled.memory_analysis()
    metrics.update(collective_permutes=permutes,
                   degree=setup.gossip_spec.degree,
                   params_per_client=params_lib.count_params(struct1),
                   device_temp_bytes=int(mem.temp_size_in_bytes),
                   device_argument_bytes=int(mem.argument_size_in_bytes))

    x_cols = sample_cols(params, n)
    amax = client_amax(params)
    new_params, step_metrics = compiled(params, batch, lr, alive, gates)
    loss = float(step_metrics["loss"])
    checks["loss_finite"] = math.isfinite(loss)
    metrics["loss"] = loss
    y_cols = sample_cols(new_params, n)
    w = np.asarray(setup.overlay.mixing_matrix(), np.float64)
    # bf16 parameters: the f32 codec's kernel rounds once at its output;
    # the int8 codec accumulates self + d wires in the parameter dtype
    d = setup.gossip_spec.degree
    mc, mm = mix_vs_reference(x_cols, y_cols, w, amax, codec=codec,
                              bf16_roundings=1 if codec == "f32" else d + 1)
    checks.update(mc)
    metrics.update(mm)
    sc, sm = placement_checks(new_params, n_devices=n)
    checks.update(sc)
    metrics.update(sm)
    return checks, metrics


ONE_CHIP = {
    "char_lm_f32_telemetry": lambda: char_lm_phase(codec="f32",
                                                   telemetry=True),
    "char_lm_int8_block": lambda: char_lm_phase(codec="int8_block",
                                                telemetry=False),
}
FOUR_CHIPS = {
    "shard_map_internvl2_1b_f32": lambda: shard_map_phase(codec="f32"),
    "shard_map_internvl2_1b_int8_block":
        lambda: shard_map_phase(codec="int8_block"),
    "blocked_char_lm_512x128": lambda: char_lm_phase(
        codec="f32", telemetry=False, n_clients=512, rounds=3, block=128,
        expect_kernels=False),
}


def run_phases(phases, clock) -> bool:
    ok = True
    for name, fn in phases.items():
        c0, h0 = clock.snapshot()
        t0 = time.perf_counter()
        try:
            checks, metrics = fn()
        except Exception:
            traceback.print_exc()
            checks, metrics = {"ran": False}, {}
        c1, h1 = clock.snapshot()
        passed = all(checks.values())
        ok &= passed
        print(json.dumps({"phase": name, "passed": passed, "checks": checks,
                          "compile_seconds": c1 - c0,
                          "compile_cache_hits": h1 - h0,
                          "phase_seconds": time.perf_counter() - t0,
                          **metrics}), flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip substrates (2x2 host)")
    args = ap.parse_args()

    import jax

    from repro.launch import compile_cache

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s), JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    clock = CompileClock()
    print(json.dumps({"device_kind": devices[0].device_kind,
                      "devices": len(devices), "compile_cache": cache_dir}),
          flush=True)
    if not run_phases(FOUR_CHIPS if args.four_chips else ONE_CHIP, clock):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
