import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=16"
                           ).strip()
# ^ MUST precede the first jax import (jax locks the device count on init),
# which is why this smoke is a standalone module instead of a benchmarks.run
# suite: run.py imports jax before any suite can set the flag. Appended (not
# setdefault) so a pre-exported XLA_FLAGS keeps its flags without dropping
# the fake device count this smoke requires.

"""Pipelined + quantized engine smoke — the CI guard for the composition.

Builds the PRODUCTION train step (launch.steps.build_train_step, fully-
manual shard_map island) on a 16-fake-device (4, 4) mesh with
``gossip_impl="ppermute_packed_async"``, ``gossip_delay=1``,
``gossip_codec="int8_block"`` and hard-asserts the engine acceptance
criteria on every push:

  * the lowered HLO ships exactly **d** collective-permutes per round and
    every one of them carries the **int8 wire buffer** (quantize + fold
    happened before the wire, scales ride inside);
  * the donated in-flight snapshot is the int8 wire (4x smaller state);
  * the async impl at ``gossip_delay=0`` still lowers to HLO *textually
    identical* to ``ppermute_packed`` (no drift from the codec plumbing);
  * executing rounds under straggler churn + rotating one-peer gates reuses
    ONE executable (``_cache_size() == 1`` — alive/gates/snapshot are step
    data, never trace structure);
  * the **sparse EF** cell (``gossip_codec="topk_ef"``): same d-collective
    count with the lane-folded int8 top-k wire, per-round wire bytes <= 10%
    of the dense f32 build, the EF residual threading the donated
    ``codec_state`` operand (nonzero after one round), and the same
    one-executable guard under churn + gate rotation;
  * the **Chebyshev** cell (``gossip_sub_rounds=2``): exactly 2*d
    collective-permutes in the lowered step, the ``gossip_sub_rounds=1``
    build lowering to HLO *textually identical* to the default packed
    build (the sub-round plumbing is invisible at k=1), and ONE executable
    across rounds that vary the traced Chebyshev coefficients alongside
    churn + gate rotation.

Usage (CI bench-smoke lane):
    PYTHONPATH=src python -m benchmarks.bench_engine_smoke
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit


def main() -> None:
    from repro.configs import registry
    from repro.configs.base import DFLConfig, ParallelConfig, ShapeConfig
    from repro.launch import steps
    from repro.launch.mesh import make_mesh
    from repro.models import params as params_lib

    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = registry.reduced("qwen2.5-3b")  # single-dtype smoke tree
    shape = ShapeConfig("t", 64, 8, "train")
    dfl = DFLConfig(degree=2, round_plan="one_peer")

    texts = {}
    setups = {}
    for key, delay, codec in (("packed", 0, "auto"),
                              ("async_sync", 0, "auto"),
                              ("async_quant", 1, "int8_block")):
        par = ParallelConfig(clients_per_pod=4, local_steps=2, grad_accum=2,
                             gossip_impl=("ppermute_packed" if key == "packed"
                                          else "ppermute_packed_async"),
                             gossip_delay=delay, gossip_codec=codec)
        setup = steps.build_train_step(cfg, shape, mesh, par, dfl)
        args = [params_lib.shape_structs(setup.param_struct),
                setup.input_specs["batch"], setup.input_specs["lr"],
                setup.input_specs["alive"], setup.input_specs["gates"]]
        if "inflight" in setup.input_specs:
            args.append(setup.input_specs["inflight"])
        texts[key] = setup.step_fn.lower(*args).as_text()
        setups[key] = setup

    # --- d collectives, all of them int8 wire, snapshot dtype int8
    setup = setups["async_quant"]
    d = setup.gossip_spec.degree
    perms = [ln for ln in texts["async_quant"].splitlines()
             if "collective_permute" in ln]
    assert len(perms) == d, (len(perms), d)
    assert all("xi8>" in ln for ln in perms), "non-int8 wire on a permute"
    assert all(str(s.dtype) == "int8"
               for s in setup.input_specs["inflight"])
    # --- delay=0 bit-identity anchor survives the codec plumbing
    assert texts["async_sync"] == texts["packed"], \
        "async delay=0 no longer lowers identically to ppermute_packed"

    # --- execute: churn + one-peer gate rotation must reuse ONE executable
    r = np.random.default_rng(0)
    structs = params_lib.shape_structs(setup.param_struct)
    params = jax.tree.map(
        lambda s, sh: jax.device_put(
            jnp.asarray(r.standard_normal(s.shape) * 0.02, s.dtype), sh),
        structs, setup.in_shardings[0])
    batch = {k: jnp.zeros(v.shape, v.dtype)
             for k, v in setup.input_specs["batch"].items()}
    inflight = setup.init_inflight(params)
    n, d = setup.n_clients, setup.gossip_spec.degree
    t0 = time.perf_counter()
    rounds = 3
    for rnd in range(rounds):
        alive = (r.random(n) > 0.3).astype(np.float32)
        if alive.sum() < 2:
            alive[:] = 1.0
        gates = np.zeros(d, np.float32)
        gates[rnd % d] = 1.0
        params, _m, inflight = setup.step_fn(
            params, batch, jnp.float32(0.01), jnp.asarray(alive),
            jnp.asarray(gates), inflight)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    from repro.telemetry import TraceCounter
    n_traces = TraceCounter.cache_size(setup.step_fn)
    assert n_traces == 1, f"pipelined+quant step retraced: {n_traces}"
    for leaf in jax.tree.leaves(params):
        assert bool(jnp.isfinite(jnp.asarray(leaf, jnp.float32)).all())

    emit("engine_smoke/async_quant/4x4", dt * 1e6 / rounds,
         f"d_collectives={len(perms)};int8_wire=1;n_traces={n_traces};"
         f"rounds={rounds};delay0_identity=1")

    # --- sparse EF cell: topk_ef through the SAME production step
    par_s = ParallelConfig(clients_per_pod=4, local_steps=2, grad_accum=2,
                           gossip_impl="ppermute_packed",
                           gossip_codec="topk_ef")
    s_t = steps.build_train_step(cfg, shape, mesh, par_s, dfl)
    args = [params_lib.shape_structs(s_t.param_struct),
            s_t.input_specs["batch"], s_t.input_specs["lr"],
            s_t.input_specs["alive"], s_t.input_specs["gates"],
            s_t.input_specs["codec_state"]]
    sperms = [ln for ln in s_t.step_fn.lower(*args).as_text().splitlines()
              if "collective_permute" in ln]
    assert len(sperms) == d, (len(sperms), d)
    assert all("xi8>" in ln for ln in sperms), "non-int8 top-k wire"
    # wire accounting rides the telemetry builds (wire_bytes_per_round is
    # the executor's exact wire-struct sum, populated when telemetry is on)
    wire = {}
    for codec in ("f32", "topk_ef"):
        par_w = ParallelConfig(clients_per_pod=4, local_steps=2,
                               grad_accum=2, gossip_impl="ppermute_packed",
                               gossip_codec=codec, gossip_telemetry=True)
        wire[codec] = steps.build_train_step(
            cfg, shape, mesh, par_w, dfl).wire_bytes_per_round
    ratio = wire["topk_ef"] / wire["f32"]
    assert ratio <= 0.10, f"topk_ef wire ratio vs f32: {ratio}"

    cstate = s_t.init_codec_state(params)
    t0 = time.perf_counter()
    for rnd in range(rounds):
        alive = (r.random(n) > 0.3).astype(np.float32)
        if alive.sum() < 2:
            alive[:] = 1.0
        gates = np.zeros(d, np.float32)
        gates[rnd % d] = 1.0
        params, _m, cstate = s_t.step_fn(
            params, batch, jnp.float32(0.01), jnp.asarray(alive),
            jnp.asarray(gates), cstate)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    s_traces = TraceCounter.cache_size(s_t.step_fn)
    assert s_traces == 1, f"sparse EF step retraced: {s_traces}"
    resid = sum(float(jnp.sum(jnp.abs(c))) for c in cstate)
    assert resid > 0, "EF residual stayed zero — error feedback inert"
    for leaf in jax.tree.leaves(params):
        assert bool(jnp.isfinite(jnp.asarray(leaf, jnp.float32)).all())
    emit("engine_smoke/sparse_ef/4x4", dt * 1e6 / rounds,
         f"d_collectives={len(sperms)};wire_ratio_vs_f32={ratio:.4f};"
         f"n_traces={s_traces};rounds={rounds};residual_mass={resid:.3e}")

    # --- Chebyshev cell: sub_rounds=2 through the SAME production step
    par_c1 = ParallelConfig(clients_per_pod=4, local_steps=2, grad_accum=2,
                            gossip_impl="ppermute_packed",
                            gossip_sub_rounds=1)
    c1 = steps.build_train_step(cfg, shape, mesh, par_c1, dfl)
    args = [params_lib.shape_structs(c1.param_struct),
            c1.input_specs["batch"], c1.input_specs["lr"],
            c1.input_specs["alive"], c1.input_specs["gates"]]
    assert c1.cheby_coeffs is None and "cheby" not in c1.input_specs
    assert c1.step_fn.lower(*args).as_text() == texts["packed"], \
        "sub_rounds=1 no longer lowers identically to the packed build"

    par_c2 = ParallelConfig(clients_per_pod=4, local_steps=2, grad_accum=2,
                            gossip_impl="ppermute_packed",
                            gossip_sub_rounds=2)
    c2 = steps.build_train_step(cfg, shape, mesh, par_c2, dfl)
    om = np.asarray(c2.cheby_coeffs)
    assert om.shape == (2,) and om[0] == 1.0, om
    assert c2.input_specs["cheby"].shape == (2,)
    args = [params_lib.shape_structs(c2.param_struct),
            c2.input_specs["batch"], c2.input_specs["lr"],
            c2.input_specs["alive"], c2.input_specs["gates"],
            c2.input_specs["cheby"]]
    cperms = [ln for ln in c2.step_fn.lower(*args).as_text().splitlines()
              if "collective_permute" in ln]
    assert len(cperms) == 2 * d, (len(cperms), d)

    t0 = time.perf_counter()
    for rnd in range(rounds):
        alive = (r.random(n) > 0.3).astype(np.float32)
        if alive.sum() < 2:
            alive[:] = 1.0
        gates = np.zeros(d, np.float32)
        gates[rnd % d] = 1.0
        # coefficients are step DATA: vary them every round, expect 1 trace
        cheby = jnp.asarray([1.0, float(om[1]) * (1.0 + 0.05 * rnd)],
                            jnp.float32)
        params, _m = c2.step_fn(
            params, batch, jnp.float32(0.01), jnp.asarray(alive),
            jnp.asarray(gates), cheby)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    c_traces = TraceCounter.cache_size(c2.step_fn)
    assert c_traces == 1, f"chebyshev step retraced: {c_traces}"
    for leaf in jax.tree.leaves(params):
        assert bool(jnp.isfinite(jnp.asarray(leaf, jnp.float32)).all())
    emit("engine_smoke/chebyshev_k2/4x4", dt * 1e6 / rounds,
         f"kd_collectives={len(cperms)};n_traces={c_traces};"
         f"rounds={rounds};k1_identity=1")
    print("ENGINE_SMOKE_OK")


if __name__ == "__main__":
    main()
