"""Telemetry subsystem tests (ISSUE 8).

Covers the three layers and their composition:

* in-graph round metrics (`repro.telemetry.metrics` + the engine/step
  plumbing): consensus-residual oracle checks, byte-identical mixed outputs
  with telemetry on vs off, wire-byte accounting;
* the event stream (`repro.telemetry.events` / `log`): TraceCounter
  semantics, JSONL round-trip, event ordering under attack -> suspicion ->
  quarantine-splice repair;
* the report layer (`repro.telemetry.report`): bench-dir + run-log merge.

The slow lane asserts the PR's acceptance on the PRODUCTION step, in
lowered HLO: telemetry ON ships exactly d collective-permutes and zero
additional collectives of any kind vs OFF (f32 AND int8_block), executes
>= 3 rounds of straggler churn + one-peer gate rotation + active-cohort
rotation on ONE executable, and the step's params output is bitwise
independent of the telemetry flag.
"""
import json
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dfedavg, engine, failures as failures_lib, gossip, \
    topology
from repro.launch.elastic import ElasticTrainer
from repro.overlay import plan as plan_lib
from repro.telemetry import (TelemetryConfig, TelemetryLogger, TraceCounter,
                             read_jsonl)
from repro.telemetry import events as tel_events
from repro.telemetry import metrics as tel_metrics
from repro.telemetry import report as tel_report


def _tree(n, seed=0, shapes=((6, 5), (11,))):
    r = np.random.default_rng(seed)
    return {f"p{i}": jnp.asarray(r.standard_normal((n,) + s), jnp.float32)
            for i, s in enumerate(shapes)}


def _quad_loss(p, b):
    return jnp.mean(jnp.square(p["w"] - b["t"])), {}


# ------------------------------------------------------------ TraceCounter
class TestTraceCounter:
    def test_hit_counts_traces_not_calls(self):
        tc = TraceCounter("t")

        @jax.jit
        @tc.wrap
        def f(x):
            return x * 2

        for i in range(5):
            f(jnp.float32(i))
        assert tc.count == 1
        f(jnp.arange(3.0))  # new shape => one new trace
        assert tc.count == 2
        assert TraceCounter.cache_size(f) == 2

    def test_expect_raises_with_context(self):
        tc = TraceCounter("guard")
        tc.hit()
        tc.expect(1)
        with pytest.raises(AssertionError, match="guard.*expected 2"):
            tc.expect(2, what="churn must be data")

    def test_hits_emit_compile_events(self, tmp_path):
        log = TelemetryLogger(tmp_path / "t.jsonl")
        tc = TraceCounter("round", logger=log)
        tc.hit()
        tc.hit()
        log.close()
        recs = [r for r in read_jsonl(tmp_path / "t.jsonl")
                if r["kind"] == "compile"]
        assert [r["count"] for r in recs] == [1, 2]
        assert all(r["counter"] == "round" for r in recs)


# ------------------------------------------------------------ event stream
class TestEventStream:
    def test_jsonl_round_trip_and_validation(self, tmp_path):
        path = tmp_path / "run.jsonl"
        with TelemetryLogger(path, run="unit", n_clients=4) as log:
            log.event("note", msg="hello")
            with log.phase("gossip"):
                pass
            log.round(0, loss=1.5)
            log.repair({"dead": [2], "spliced": True, "n_after": 3})
        recs = read_jsonl(path)
        for r in recs:
            tel_events.validate_event(r)
        assert [r["kind"] for r in recs] == ["run", "note", "round", "repair"]
        assert [r["seq"] for r in recs] == list(range(len(recs)))
        rnd = recs[2]
        assert rnd["loss"] == 1.5 and "gossip" in rnd["phases"]

    def test_unknown_kind_rejected(self, tmp_path):
        with TelemetryLogger(tmp_path / "x.jsonl") as log:
            with pytest.raises(ValueError, match="kind"):
                log.event("bogus")

    def test_ordering_under_attack_and_quarantine_splice(self, tmp_path):
        """The ISSUE's event-ordering acceptance: one run where a scripted
        attacker activates, gets clipped (suspicion), is quarantined via the
        splice repair, and the re-jit lands as a compile event — all in
        stream order, with round records interleaved once per step."""
        n = 12
        path = tmp_path / "run.jsonl"
        logger = TelemetryLogger(path, run="quarantine", n_clients=n)
        atk = failures_lib.AttackPlan(
            n_clients=n, events=((2, (3,), "scale", 50.0),))
        tr = ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=_quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2, momentum=0.9),
            engine=engine.GossipEngineConfig(
                substrate="stacked", screen="norm_clip", clip_tau=3.0,
                telemetry=TelemetryConfig()),
            quarantine_rounds=2, attack_plan=atk, logger=logger)
        params = _tree(n, shapes=((64,),))
        params = {"w": params["p0"]}
        for rnd in range(6):
            m = tr.overlay.n
            params, _, _ = tr.observe_heartbeats(np.ones(m, np.float32),
                                                 params)
            batch = {"t": jnp.zeros((tr.overlay.n, 2, 64), jnp.float32)}
            params, _ = tr.step(params, batch, 0.2)
        logger.close()

        recs = read_jsonl(path)
        kinds = [r["kind"] for r in recs]
        assert kinds.count("round") == 6
        # the attacker was evicted by the quarantine splice: exactly one
        # repair, and therefore exactly two compiles (init + re-jit)
        assert kinds.count("repair") == 1 and kinds.count("compile") == 2
        assert tr.n_traces == 2
        seq_of = {k: [r["seq"] for r in recs if r["kind"] == k]
                  for k in set(kinds)}
        # activation precedes the first clip, which precedes the repair,
        # which precedes the re-jit — the stream tells the story in order
        assert seq_of["attack"][0] < seq_of["suspicion"][0] \
            < seq_of["repair"][0] < seq_of["compile"][1]
        repair = [r for r in recs if r["kind"] == "repair"][0]
        assert repair["quarantined"] == [3] and repair["spliced"]
        # round records carry the metric summaries
        rnd0 = [r for r in recs if r["kind"] == "round"][0]
        assert {"loss", "resid_sqnorm", "in_degree_mean",
                "phases"} <= set(rnd0)


# --------------------------------------------------------- engine metrics
class TestEngineMetrics:
    def _spec(self, n=10, d=4, seed=2):
        return gossip.make_gossip_spec(topology.expander_overlay(n, d,
                                                                 seed=seed))

    def test_stacked_consensus_residual_matches_oracle(self):
        spec = self._spec()
        x = _tree(10, seed=5)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      telemetry=TelemetryConfig()), spec)
        alive = jnp.asarray(np.r_[np.ones(7), 0, 1, 1], jnp.float32)
        mixed, met = ex(x, alive=alive)
        # oracle: contrib-weighted squared distance to each mixed-in source
        _, contrib = gossip.raw_contrib_tables(spec, alive, None)
        w = np.asarray(contrib)                    # (n, 1 + S)
        flat = np.concatenate(
            [np.asarray(v).reshape(10, -1) for v in x.values()], axis=1)
        resid = np.zeros(10)
        for s, rf in enumerate(spec.recv_from):
            src = flat[np.asarray(rf)]
            resid += w[:, 1 + s] * np.sum((src - flat) ** 2, axis=1)
        np.testing.assert_allclose(np.asarray(met["resid_sqnorm"]), resid,
                                   rtol=1e-5)
        # in-degree drops for receivers of the dead client only
        np.testing.assert_allclose(np.asarray(met["in_degree"]),
                                   w[:, 1:].sum(axis=1), rtol=1e-6)
        # telemetry must not perturb the mixed output by a single bit
        ex0 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        plain = ex0(x, alive=alive)
        for k in x:
            assert np.array_equal(np.asarray(mixed[k]), np.asarray(plain[k]))

    @pytest.mark.parametrize("codec", ["f32", "int8_block"])
    def test_delayed_cells_mixed_output_bit_identical(self, codec):
        spec = self._spec()
        x = _tree(10, seed=7)
        mk = lambda tel: engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", delay=1,
                                      codec=codec, telemetry=tel), spec)
        ex_t, ex_0 = mk(TelemetryConfig()), mk(None)
        st_t, st_0 = ex_t.init_state(x), ex_0.init_state(x)
        for _ in range(2):
            out = ex_t(x, state=st_t)
            mixed_t, st_t, met = out
            mixed_0, st_0 = ex_0(x, state=st_0)
            for k in x:
                assert np.array_equal(np.asarray(mixed_t[k]),
                                      np.asarray(mixed_0[k]))
            x = mixed_t
        assert float(met["resid_sqnorm"].sum()) >= 0.0
        assert np.isfinite(np.asarray(met["resid_sqnorm"])).all()

    def test_wire_bytes_per_round_counts_codec_bytes(self):
        spec = self._spec()
        x = _tree(10)
        from repro.core import packing
        pack = packing.make_stacked_pack_spec(
            jax.tree.map(lambda v: v[0], x))
        wires = {}
        for codec in ("f32", "int8_block"):
            ex = engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="shard_map", codec=codec),
                spec, axis_names="client", pack_spec=pack)
            wires[codec] = ex.wire_bytes_per_round()
        assert wires["f32"] > 0
        # int8 payload: ~4x smaller, plus the per-tile scale rows
        assert wires["f32"] / 4 <= wires["int8_block"] < wires["f32"] / 2
        # dense has no packed wire; per_leaf refuses the accounting
        exd = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="dense"), spec)
        assert exd.wire_bytes_per_round() == 0
        exl = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="per_leaf"), spec,
            axis_names="client")
        with pytest.raises(ValueError):
            exl.wire_bytes_per_round()

    def test_summarize_metrics_shapes(self):
        spec = self._spec()
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      telemetry=TelemetryConfig()), spec)
        _, met = ex(_tree(10))
        met = dict(met)
        met["wire_bytes"] = jnp.float32(1234.0)
        met["attack_energy"] = jnp.float32(0.0)
        s = tel_metrics.summarize_metrics(met, n_clients=10)
        assert s["wire_bytes"] == 1234 and s["attack_energy"] == 0.0
        assert s["in_degree_mean"] == pytest.approx(4.0)
        assert len(s["sched_mass"]) == spec.degree
        assert tel_metrics.summarize_metrics(None) == {}
        assert tel_metrics.summarize_metrics({}) == {}


# ------------------------------------------------- elastic runtime guards
class TestElasticTelemetry:
    def test_zero_retraces_under_churn_gates_cohorts(self):
        n = 12
        tr = ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=_quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.9),
            plan=plan_lib.OnePeerPlan(),
            active_plan=plan_lib.RandomKActiveSet(k=8, seed=0),
            engine=engine.GossipEngineConfig(substrate="stacked",
                                             telemetry=TelemetryConfig()))
        params = {"w": _tree(n, shapes=((32,),))["p0"]}
        r = np.random.default_rng(0)
        for rnd in range(4):
            alive = (r.random(n) > 0.2).astype(np.float32)
            params, _, _ = tr.observe_heartbeats(alive, params)
            batch = {"t": jnp.zeros((n, 2, 32), jnp.float32)}
            params, _ = tr.step(params, batch, 0.2)
        assert tr.n_traces == 1  # churn + gates + cohorts are all data
        assert tr.last_metrics is not None
        assert set(tr.last_metrics) == {"resid_sqnorm", "in_degree",
                                        "sched_contrib"}

    def test_telemetry_off_keeps_metrics_none(self):
        n = 8
        tr = ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=_quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.9))
        params = {"w": _tree(n, shapes=((16,),))["p0"]}
        params, _, _ = tr.observe_heartbeats(np.ones(n, np.float32), params)
        params, _ = tr.step(params,
                            {"t": jnp.zeros((n, 2, 16), jnp.float32)}, 0.2)
        assert tr.last_metrics is None and tr.n_traces == 1

    def test_validation_rejects_unsupported_compositions(self):
        ov = topology.expander_overlay(8, 4, seed=0)
        dcfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.9)
        # blocked + telemetry is now a supported (metrics-only) cell, but
        # Chebyshev sub-rounds still don't ride the blocked substrate
        with pytest.raises(ValueError, match="sub_rounds > 1"):
            ElasticTrainer(overlay=ov, loss_fn=_quad_loss, dcfg=dcfg,
                           engine=engine.GossipEngineConfig(
                               substrate="blocked", block=8, sub_rounds=2))
        with pytest.raises(ValueError, match="TelemetryConfig"):
            engine.GossipEngineConfig(substrate="stacked", telemetry=True)


# ---------------------------------------------- blocked-substrate metrics
class TestBlockedTelemetry:
    """Satellite: the metrics-only blocked telemetry cell. Consensus
    residual + in-degree are measured on the device-local (B,)-leading rows
    the blocked round already gathers; the island's P("clients") out_spec
    concatenates them back to the (n,)-stacked layout. Validated against
    the stacked-telemetry oracle, with the zero-extra-collectives contract
    asserted in lowered HLO (slow lane)."""

    def _blocked_island(self, spec, block, tel):
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import shard_map

        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="blocked", block=block,
                                      telemetry=tel),
            spec, axis_names="clients")
        mesh = Mesh(np.asarray(jax.devices()[:spec.n_clients // block]),
                    ("clients",))

        def body(t, a, g):
            return ex(t, alive=a, gates=g)

        out_specs = ((P("clients"), P("clients")) if tel is not None
                     else P("clients"))
        return jax.jit(shard_map(body, mesh,
                                 in_specs=(P("clients"), P(), P()),
                                 out_specs=out_specs))

    def test_blocked_metrics_match_stacked_oracle(self):
        n = 12
        spec = gossip.make_gossip_spec(topology.expander_overlay(n, 4,
                                                                 seed=0))
        x = _tree(n, seed=3)
        stacked = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      telemetry=TelemetryConfig()), spec)
        fn = self._blocked_island(spec, n, TelemetryConfig())
        for t in range(3):
            alive = (np.random.default_rng(t).random(n) > 0.3
                     ).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            gates = np.zeros(spec.degree, np.float32)
            gates[t % spec.degree] = 1.0
            ref_mixed, ref = stacked(x, alive=jnp.asarray(alive),
                                     gates=jnp.asarray(gates))
            got_mixed, met = fn(x, jnp.asarray(alive), jnp.asarray(gates))
            for k in x:   # telemetry-on blocked round == stacked round
                np.testing.assert_array_equal(np.asarray(got_mixed[k]),
                                              np.asarray(ref_mixed[k]))
            assert met["resid_sqnorm"].shape == (n,)
            assert met["sched_contrib"].shape == (n, spec.degree)
            np.testing.assert_allclose(np.asarray(met["resid_sqnorm"]),
                                       np.asarray(ref["resid_sqnorm"]),
                                       rtol=2e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(met["in_degree"]),
                                       np.asarray(ref["in_degree"]),
                                       rtol=1e-6)
            np.testing.assert_allclose(np.asarray(met["sched_contrib"]),
                                       np.asarray(ref["sched_contrib"]),
                                       rtol=1e-6)

    def test_trainer_blocked_telemetry_zero_retraces(self):
        n = 8
        tr = ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=_quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.9),
            plan=plan_lib.OnePeerPlan(),
            engine=engine.GossipEngineConfig(
                substrate="blocked", block=n,
                telemetry=TelemetryConfig()))
        params = {"w": _tree(n, shapes=((16,),))["p0"]}
        r = np.random.default_rng(0)
        for rnd in range(4):
            alive = (r.random(n) > 0.2).astype(np.float32)
            params, _, _ = tr.observe_heartbeats(alive, params)
            params, _ = tr.step(
                params, {"t": jnp.zeros((n, 2, 16), jnp.float32)}, 0.2)
        assert tr.n_traces == 1  # metrics + churn + gates are all data
        met = tr.last_metrics
        assert set(met) == {"resid_sqnorm", "in_degree", "sched_contrib"}
        assert met["resid_sqnorm"].shape == (n,)
        assert met["in_degree"].shape == (n,)
        assert met["sched_contrib"].shape == (n, tr.overlay.degree)
        for v in met.values():
            assert np.isfinite(np.asarray(v)).all()

    @pytest.mark.slow
    def test_blocked_telemetry_ships_zero_extra_collectives(self):
        """Acceptance, in lowered HLO on a real 4-device blocked layout:
        telemetry ON ships exactly the same count of EVERY collective kind
        as OFF (the cross-block permutes included), and the cross-device
        metrics still match the stacked oracle."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import Mesh, PartitionSpec as P
            from repro.core import engine, gossip, topology
            from repro.launch.mesh import shard_map
            from repro.telemetry import TelemetryConfig

            n, b = 16, 4
            spec = gossip.make_gossip_spec(
                topology.expander_overlay(n, 4, seed=0))
            r = np.random.default_rng(0)
            tree = {"a": jnp.asarray(r.standard_normal((n, 6, 5)),
                                     jnp.float32),
                    "b": jnp.asarray(r.standard_normal((n, 11)),
                                     jnp.float32)}
            alive = jnp.asarray((np.random.default_rng(1).random(n) > 0.25)
                                .astype(np.float32))
            gates = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)
            mesh = Mesh(np.asarray(jax.devices()[: n // b]), ("clients",))
            texts, outs = {}, {}
            for tel in (False, True):
                ex = engine.build_gossip_executor(
                    engine.GossipEngineConfig(
                        substrate="blocked", block=b,
                        telemetry=TelemetryConfig() if tel else None),
                    spec, axis_names="clients")
                def body(t, a, g, ex=ex):
                    return ex(t, alive=a, gates=g)
                out_specs = ((P("clients"), P("clients")) if tel
                             else P("clients"))
                fn = jax.jit(shard_map(body, mesh,
                                       in_specs=(P("clients"), P(), P()),
                                       out_specs=out_specs))
                texts[tel] = fn.lower(tree, alive, gates).as_text()
                outs[tel] = fn(tree, alive, gates)
            KINDS = ("collective-permute", "all-reduce", "all-gather",
                     "reduce-scatter", "all-to-all")
            counts = {tel: {k: texts[tel].count(k) for k in KINDS}
                      for tel in (False, True)}
            assert counts[True] == counts[False], counts
            perms = [l for l in texts[True].splitlines()
                     if "collective_permute" in l]
            assert len(perms) > 0  # the expander DOES cross blocks
            mixed_t, met = outs[True]
            for k in tree:
                assert np.array_equal(np.asarray(mixed_t[k]),
                                      np.asarray(outs[False][k]))
            ex_s = engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="stacked",
                                          telemetry=TelemetryConfig()),
                spec)
            _, ref = ex_s(tree, alive=alive, gates=gates)
            np.testing.assert_allclose(np.asarray(met["resid_sqnorm"]),
                                       np.asarray(ref["resid_sqnorm"]),
                                       rtol=2e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(met["in_degree"]),
                                       np.asarray(ref["in_degree"]),
                                       rtol=1e-6)
            print("BLOCKED_TEL_OK n_perms=", len(perms))
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "BLOCKED_TEL_OK" in out.stdout, out.stdout + out.stderr


# ------------------------------------------------------------- the report
class TestReport:
    def test_build_summary_merges_benches_and_runs(self, tmp_path):
        bench = tmp_path / "bench"
        bench.mkdir()
        (bench / "engine.json").write_text(json.dumps(
            {"bench": "engine", "rounds_per_sec": 12.5, "n_traces": 1}))
        (bench / "telemetry.json").write_text(json.dumps(
            {"bench": "telemetry",
             "wire_bytes": {"f32": 262144, "int8_block": 65792},
             "cells": [{"label": "on", "rounds_per_sec": 10.0}]}))
        log = tmp_path / "run.jsonl"
        with TelemetryLogger(log, run="demo") as lg:
            lg.round(0, loss=2.0, resid_sqnorm=9.0)
            lg.round(1, loss=1.0, resid_sqnorm=4.0)
            lg.repair({"dead": [1], "spliced": True, "n_after": 7})
        out = tmp_path / "summary.json"
        summary = tel_report.build_summary(bench_dir=str(bench),
                                           logs=[str(log)], out=str(out))
        assert summary["wire_bytes_per_round"] == {"f32": 262144,
                                                   "int8_block": 65792}
        assert summary["retraces"]["engine/engine"] == 1
        assert any(v["rounds_per_sec"] == 12.5
                   for v in summary["rounds_per_sec"].values())
        run = summary["runs"][0]
        assert run["rounds"] == 2 and run["repairs"] == 1
        assert run["consensus"] == [[0, 9.0], [1, 4.0]]
        assert json.loads(out.read_text()) == summary


# ---------------------------------------- acceptance on the production step
class TestProductionStepTelemetry:
    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr
        return out.stdout

    @pytest.mark.slow
    def test_on_ships_d_collectives_and_zero_extra(self):
        """Acceptance, in lowered HLO, f32 AND int8_block: with telemetry
        ON the step still ships exactly d collective-permutes and the count
        of EVERY collective kind equals the telemetry-OFF build — the
        metrics are free-riding on values the round already moves."""
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
            import sys; sys.path.insert(0, "src")
            import jax
            from repro.configs import registry
            from repro.configs.base import ShapeConfig, ParallelConfig, DFLConfig
            from repro.launch import steps
            from repro.models import params as P

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 4), ("data", "model"))
            cfg = registry.reduced("qwen2.5-3b")
            shape = ShapeConfig("t", 64, 8, "train")
            KINDS = ("collective-permute", "all-reduce", "all-gather",
                     "reduce-scatter", "all-to-all")
            for gi, delay, codec in (("ppermute_packed", 0, "auto"),
                                     ("ppermute_packed_async", 1,
                                      "int8_block")):
                texts = {}
                for tel in (False, True):
                    par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                         grad_accum=2, gossip_impl=gi,
                                         gossip_delay=delay,
                                         gossip_codec=codec,
                                         gossip_telemetry=tel)
                    setup = steps.build_train_step(cfg, shape, mesh, par,
                                                   DFLConfig(degree=2))
                    args = [P.shape_structs(setup.param_struct),
                            setup.input_specs["batch"],
                            setup.input_specs["lr"],
                            setup.input_specs["alive"],
                            setup.input_specs["gates"]]
                    if "inflight" in setup.input_specs:
                        args.append(setup.input_specs["inflight"])
                    texts[tel] = setup.step_fn.lower(*args).as_text()
                    if tel:
                        assert setup.wire_bytes_per_round > 0
                d = setup.gossip_spec.degree
                counts = {tel: {k: texts[tel].count(k) for k in KINDS}
                          for tel in (False, True)}
                assert counts[True] == counts[False], (gi, codec, counts)
                for tel in (False, True):
                    perms = [l for l in texts[tel].splitlines()
                             if "collective_permute" in l]
                    assert len(perms) == d, (gi, codec, tel, len(perms), d)
            print("TELEMETRY_HLO_OK")
        """)

    @pytest.mark.slow
    def test_one_executable_and_bitwise_params_over_rounds(self):
        """Acceptance, executed: >= 3 rounds of straggler churn + one-peer
        gate rotation + active-cohort rotation reuse ONE executable with
        telemetry ON (f32 and int8_block), the metrics arrive finite with
        the exact static wire-byte constant, and the params trajectory is
        BITWISE identical to the telemetry-OFF run."""
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from repro.configs import registry
            from repro.configs.base import ShapeConfig, ParallelConfig, DFLConfig
            from repro.launch import steps
            from repro.models import params as P
            from repro.telemetry import TraceCounter

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 4), ("data", "model"))
            cfg = registry.reduced("qwen2.5-3b")
            shape = ShapeConfig("t", 16, 4, "train")
            dfl = DFLConfig(degree=2, round_plan="one_peer")

            def drive(codec, delay, tel, rounds=4):
                par = ParallelConfig(clients_per_pod=4, local_steps=1,
                                     grad_accum=1,
                                     gossip_impl="ppermute_packed_async",
                                     gossip_delay=delay, gossip_codec=codec,
                                     gossip_telemetry=tel)
                setup = steps.build_train_step(cfg, shape, mesh, par, dfl)
                r = np.random.default_rng(0)
                structs = P.shape_structs(setup.param_struct)
                params = jax.tree.map(
                    lambda s, sh: jax.device_put(
                        jnp.asarray(r.standard_normal(s.shape) * 0.02,
                                    s.dtype), sh),
                    structs, setup.in_shardings[0])
                inflight = (setup.init_inflight(params)
                            if "inflight" in setup.input_specs else None)
                batch = {k: jnp.zeros(v.shape, v.dtype)
                         for k, v in setup.input_specs["batch"].items()}
                n = setup.gossip_spec.n_clients
                d = setup.gossip_spec.degree
                mets = []
                for rnd in range(rounds):
                    alive = (r.random(n) > 0.2).astype(np.float32)
                    alive *= (np.arange(n) % 2 == rnd % 2)  # cohorts
                    if alive.sum() < 2:
                        alive[:] = 1.0
                    gates = np.zeros(d, np.float32)
                    gates[rnd % d] = 1.0                    # one-peer
                    args = [params, batch, jnp.float32(0.01),
                            jnp.asarray(alive), jnp.asarray(gates)]
                    if inflight is not None:
                        args.append(inflight)
                    out = setup.step_fn(*args)
                    params, metrics = out[0], out[1]
                    if inflight is not None:
                        inflight = out[2]
                    mets.append(metrics)
                assert TraceCounter.cache_size(setup.step_fn) == 1, codec
                return setup, params, mets

            for codec, delay in (("auto", 0), ("int8_block", 1)):
                setup, p_on, mets = drive(codec, delay, True)
                _, p_off, mets_off = drive(codec, delay, False)
                for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
                    assert np.array_equal(np.asarray(a), np.asarray(b))
                assert all("telemetry" not in m for m in mets_off)
                tel = mets[-1]["telemetry"]
                assert int(np.asarray(tel["wire_bytes"]).max()) \\
                    == setup.wire_bytes_per_round
                for k in ("resid_sqnorm", "in_degree", "sched_contrib"):
                    assert np.isfinite(np.asarray(tel[k])).all(), (codec, k)
            print("TELEMETRY_STEP_EXEC_OK")
        """)


class TestRoundSampling:
    """TelemetryLogger(round_every=k): sampled round records."""

    def test_default_stream_unchanged(self):
        a = TelemetryLogger(run="a")
        b = TelemetryLogger(run="b", round_every=1)
        for rnd in range(4):
            a.round(rnd, loss=float(rnd))
            b.round(rnd, loss=float(rnd))
        strip = lambda recs: [{k: v for k, v in r.items() if k != "ts"}
                              for r in recs if r["kind"] == "round"]
        assert strip(a.records) == strip(b.records)

    def test_round_every_samples_and_peeks(self):
        log = TelemetryLogger(round_every=3)
        assert [log.wants_round(r) for r in range(6)] == [
            True, False, False, True, False, False]
        for rnd in range(7):
            log.round(rnd, loss=float(rnd))
        rounds = [r["round"] for r in log.of_kind("round")]
        assert rounds == [0, 3, 6]

    def test_off_rounds_accumulate_phases_into_the_next_record(self):
        log = TelemetryLogger(round_every=2)
        for rnd in range(1, 3):           # rnd 1 skipped, rnd 2 emitted
            with log.phase("work"):
                pass
            log.round(rnd, loss=0.0)
        (rec,) = log.of_kind("round")
        assert rec["round"] == 2
        assert "work" in rec["phases"]    # both rounds' seconds folded in

    def test_round_every_validated(self):
        with pytest.raises(ValueError, match="round_every"):
            TelemetryLogger(round_every=0)


# ------------------------------------------------ program scopes and spans
def _scoped_loss(p, b):
    return (jnp.mean(jnp.square(p["w"] - b["t"]))
            + jnp.mean(jnp.square(p["b"])), {})


def _host_spans(trace_dir, prefix):
    """[name, start_ns, end_ns, step_num] of the host spans in a trace."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([e.name, e.start_ns, e.end_ns,
                            dict(e.stats).get("step_num")]
                           for e in line.events if e.name.startswith(prefix))
    return sorted(out, key=lambda s: s[1])


LEAF_SPANS = ["dfl.operands", "dfl.dispatch", "dfl.sync", "dfl.record"]


class TestScopesAndSpans:
    def test_round_fn_carries_the_layer_scopes(self):
        """The compiled round names its layers in every instruction's
        op_name: the local phase's while loop under dfl.local, the mix
        under dfl.gossip/mix, and the packing copies under dfl.gossip/pack
        in a round whose wire needs the packed layout. The f32 round mixes
        the leaves where they are: it enters no pack or unpack scope."""
        from repro.launch.train import SimTrainer
        n = 8

        def compiled_ops(codec):
            tr = SimTrainer(overlay=topology.expander_overlay(n, 4, seed=0),
                            loss_fn=_scoped_loss,
                            dcfg=dfedavg.DFedAvgMConfig(local_steps=2,
                                                        lr=0.3, momentum=0.5),
                            engine=engine.GossipEngineConfig(
                                substrate="stacked", codec=codec))
            params = {"w": jnp.zeros((n, 5)), "b": jnp.ones((n, 3))}
            text = tr.round_fn.lower(
                params, {"t": jnp.ones((n, 2, 5))}, jnp.float32(0.1),
                jnp.ones(n, jnp.float32), tr._gates(0), None,
                None).compile().as_text()
            return dict(re.findall(
                r'^\s*(?:ROOT )?%([\w.-]+) = .*op_name="([^"]*)"', text,
                re.M))

        for codec in ("f32", "int8_block"):
            ops = compiled_ops(codec)
            whiles = [v for k, v in ops.items() if k.startswith("while")]
            assert whiles and all("dfl.local" in v for v in whiles)
            assert not any("dfl.gossip" in v for v in whiles)
            assert any("dfl.gossip/mix/" in v for v in ops.values()), codec
            packed = [v for v in ops.values()
                      if "dfl.gossip/vmap(pack)/" in v
                      or "dfl.gossip/vmap(unpack)/" in v]
            if codec == "f32":
                assert not packed
            else:
                assert any("/vmap(pack)/" in v for v in packed)
                assert any("/vmap(unpack)/" in v for v in packed)

    def test_elastic_round_spans(self, tmp_path):
        """Each ElasticTrainer.step is a dfl.round step span (step number =
        round index) around its leaf spans, in order; the logger's phase
        is a span too and still times the JSONL phase."""
        n, dim = 8, 3
        trainer = ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=lambda p, b: (jnp.mean(jnp.square(p["w"] - b["t"])), {}),
            dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.3, momentum=0.5),
            logger=TelemetryLogger())
        params = {"w": jnp.zeros((n, dim))}
        batch = {"t": jnp.ones((n, 2, dim))}
        params, _ = trainer.step(params, batch, 0.3)   # compile outside
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(2):
                params, _ = trainer.step(params, batch, 0.3)
        spans = _host_spans(tmp_path, "dfl.")
        rounds = [s for s in spans if s[0] == "dfl.round"]
        assert [int(s[3]) for s in rounds] == [1, 2]
        for r in rounds:
            inside = [s[0] for s in spans if s[0] != "dfl.round"
                      and r[1] <= s[1] and s[2] <= r[2]]
            assert inside == LEAF_SPANS
        assert len(_host_spans(tmp_path, "round")) == 2    # the phase
        assert all("round" in r["phases"]
                   for r in trainer.logger.of_kind("round"))

    def test_phase_is_a_span(self, tmp_path):
        log = TelemetryLogger()
        with jax.profiler.trace(str(tmp_path)):
            with log.phase("gossip"):
                pass
        assert [s[0] for s in _host_spans(tmp_path, "gossip")] == ["gossip"]
        assert "gossip" in log.round(0, loss=0.0)["phases"]
