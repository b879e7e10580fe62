"""Sparse top-k gossip with error feedback — the stateful WireCodec
contract, the codec registry, and the trainers' ``engine=`` front door.

Covers the PR's acceptance criteria:
  * TopKEFCodec.encode is ``ef_compress`` (the simulator oracle) on the
    packed buffer, bitwise, with the residual threading across rounds;
  * the EF residual (codec state) rides the SAME old2new splice-repair
    remap as the params and the in-flight snapshot, byte-exact;
  * churn x cohorts x gates never retrace the sparse round;
  * the production shard_map step ships exactly d collectives, all of them
    the folded int8 top-k wire, at <= 10% of the dense f32 wire bytes;
  * ``engine=GossipEngineConfig(...)`` is bitwise-equivalent to the legacy
    per-knob spelling, which now warns;
  * ``register_codec`` makes a custom codec a first-class engine citizen.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, dfedavg, engine, gossip, packing, \
    topology
from repro.launch.elastic import ElasticTrainer
from repro.overlay.plan import OnePeerPlan, RandomKActiveSet


def _quad_loss(params, batch):
    return jnp.mean(jnp.square(params["w"] - batch["target"])), {}


def _batches(targets, k=1):
    return {"target": jnp.broadcast_to(
        targets[:, None], (targets.shape[0], k) + targets.shape[1:])}


def _trainer(n, **kw):
    kw.setdefault("overlay", topology.ring_overlay(n))
    kw.setdefault("loss_fn", _quad_loss)
    kw.setdefault("dcfg", dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2,
                                                 momentum=0.9))
    return ElasticTrainer(**kw)


class TestTopKEFCodec:
    def test_encode_matches_ef_compress_oracle_multi_round(self):
        """The codec on a pad-free packed buffer IS ef_compress: decoded
        payload and carried residual match the oracle bitwise, three rounds
        deep (the residual is what makes round r depend on round r-1)."""
        rows = 16
        codec = engine.get_codec("topk_ef")
        r = np.random.default_rng(0)
        state = codec.init_state(
            jax.ShapeDtypeStruct((rows, packing.LANE), jnp.float32))
        oracle = compression.ErrorFeedbackState.init(
            {"b": jnp.zeros((rows, packing.LANE), jnp.float32)})
        for rnd in range(3):
            buf = jnp.asarray(r.standard_normal((rows, packing.LANE)),
                              jnp.float32)
            wire, state = codec.encode(buf, n_blocks=1, block_rows=rows,
                                       impl="ref", state=state)
            dense = codec.decode(wire, jnp.float32, n_blocks=1,
                                 block_rows=rows)
            want, oracle = compression.ef_compress(
                {"b": buf}, oracle, codec.k_fraction)
            np.testing.assert_array_equal(np.asarray(dense),
                                          np.asarray(want["b"]))
            np.testing.assert_array_equal(
                np.asarray(state), np.asarray(oracle.residual["b"]))

    def test_wire_is_at_most_a_tenth_of_f32(self):
        """ISSUE acceptance: the k=1% wire ships <= 10% of the dense f32
        bytes for a realistically sized buffer."""
        struct = jax.ShapeDtypeStruct((4096, packing.LANE), jnp.float32)
        topk = engine.get_codec("topk_ef").wire_struct(struct, 1)
        f32 = engine.get_codec("f32").wire_struct(struct, 1)
        ratio = ((np.prod(topk.shape) * topk.dtype.itemsize)
                 / (np.prod(f32.shape) * f32.dtype.itemsize))
        assert ratio <= 0.10, ratio

    def test_stateful_codec_rejects_screens_and_per_leaf(self):
        with pytest.raises(ValueError, match="stateful codec"):
            engine.GossipEngineConfig(substrate="per_leaf", codec="topk_ef")
        with pytest.raises(ValueError, match="stateful codec"):
            engine.GossipEngineConfig(substrate="stacked", codec="topk_ef",
                                      screen="norm_clip")


class TestCodecRegistry:
    def test_unknown_codec_is_a_clear_error(self):
        with pytest.raises(ValueError, match="unknown codec"):
            engine.get_codec("definitely_not_registered")

    def test_registered_codec_is_first_class_in_the_front_door(self):
        """register_codec -> the name works in GossipEngineConfig and the
        trainer's engine= front door with zero executor special-casing."""
        if "topk_ef_test_k5" not in engine.CODECS:
            engine.register_codec(
                "topk_ef_test_k5",
                engine.TopKEFCodec(0.05, name="topk_ef_test_k5"))
        assert "topk_ef_test_k5" in engine.CODECS
        n, dim = 6, 256
        trainer = _trainer(n, engine=engine.GossipEngineConfig(
            substrate="stacked", codec="topk_ef_test_k5"))
        r = np.random.default_rng(0)
        params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
        targets = jnp.zeros((n, dim), jnp.float32)
        for _ in range(2):
            params, losses = trainer.step(params, _batches(targets), 0.2)
        assert bool(jnp.isfinite(losses).all())
        assert trainer._codec_state is not None
        assert trainer.n_traces == 1


class TestEngineFrontDoor:
    def test_engine_config_bitwise_equals_legacy_default(self):
        """The default engine and an explicit stacked/f32 engine drive the
        exact same round: params agree bitwise after three rounds."""
        n, dim = 8, 64
        r = np.random.default_rng(1)
        p0 = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
        targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
        default = _trainer(n)
        front = _trainer(n, engine=engine.GossipEngineConfig(
            substrate="stacked", codec="f32"))
        pa = pb = p0
        for _ in range(3):
            pa, _ = default.step(pa, _batches(targets), 0.1)
            pb, _ = front.step(pb, _batches(targets), 0.1)
        np.testing.assert_array_equal(np.asarray(pa["w"]),
                                      np.asarray(pb["w"]))


class TestCodecStateElastic:
    def test_residual_survives_splice_repair_byte_exact(self):
        """The EF residual rides repair_and_remap with the params and the
        in-flight wire: surviving rows are byte-identical post-splice."""
        # dim large enough that k = 1% of the packed buffer is smaller than
        # the payload — below that, top-k captures every nonzero entry and
        # the residual is legitimately all-zero
        n, dim = 12, 1 << 16
        r = np.random.default_rng(2)
        targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
        trainer = _trainer(n, straggler_rounds=1, failure_rounds=2,
                           engine=engine.GossipEngineConfig(
                               substrate="stacked", codec="topk_ef",
                               delay=1))
        params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
        params, _ = trainer.step(params, _batches(targets), 0.1)
        alive = np.ones(n)
        alive[5] = 0
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is None                    # straggler, not dead yet
        params, _ = trainer.step(params, _batches(targets), 0.1)
        pre_state = [np.asarray(b) for b in trainer._codec_state]
        pre_wire = [np.asarray(b) for b in trainer._inflight]
        assert sum(float(np.abs(b).sum()) for b in pre_state) > 0
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is not None and old2new[5] == -1
        survivors = np.arange(n) != 5
        for b_pre, b_post in zip(pre_state, trainer._codec_state):
            assert str(np.asarray(b_post).dtype) == "float32"
            np.testing.assert_array_equal(np.asarray(b_post),
                                          b_pre[survivors])
        for b_pre, b_post in zip(pre_wire, trainer._inflight):
            np.testing.assert_array_equal(np.asarray(b_post),
                                          b_pre[survivors])
        surv_targets = jnp.concatenate([targets[:5], targets[6:]])
        params, _ = trainer.step(params, _batches(surv_targets), 0.1)
        assert params["w"].shape[0] == n - 1
        assert bool(jnp.isfinite(params["w"]).all())
        assert trainer.n_traces == 2              # one re-jit per membership

    def test_churn_cohorts_gates_never_retrace_the_sparse_round(self):
        """Straggler churn x random-k cohorts x one-peer gate rotation with
        the stateful codec: alive/gates/state are data, ONE executable."""
        n, dim = 10, 128
        trainer = _trainer(n, straggler_rounds=2, failure_rounds=10**9,
                           plan=OnePeerPlan(),
                           active_plan=RandomKActiveSet(k=6, seed=3),
                           engine=engine.GossipEngineConfig(
                               substrate="stacked", codec="topk_ef"))
        r = np.random.default_rng(3)
        params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
        targets = jnp.zeros((n, dim), jnp.float32)
        for rnd in range(6):
            alive = (r.random(n) > 0.3).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1.0
            params, _, old2new = trainer.observe_heartbeats(alive, params)
            assert old2new is None
            params, _ = trainer.step(params, _batches(targets), 0.2)
        assert trainer.n_traces == 1, trainer.n_traces
        assert bool(jnp.isfinite(params["w"]).all())


class TestProductionStepSparse:
    @pytest.mark.slow
    def test_hlo_d_collectives_state_remap_and_zero_retrace(self):
        """The full shard_map production step with gossip_codec="topk_ef":
        exactly d collective-permutes (each the folded int8 wire), wire
        bytes <= 10% of the dense f32 build, the codec state donated and
        threading (nonzero residual after a round), one executable under
        churn + gate rotation, and the state's global layout row-remappable
        exactly like the params."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
            import sys; sys.path.insert(0, "src")
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import registry
            from repro.configs.base import ShapeConfig, ParallelConfig, DFLConfig
            from repro.launch import steps
            from repro.models import params as P
            from repro.telemetry import TraceCounter

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 4), ("data", "model"))
            cfg = registry.reduced("qwen2.5-3b")
            shape = ShapeConfig("t", 64, 8, "train")
            dfl = DFLConfig(degree=2, round_plan="one_peer")

            def build(codec, telemetry=False):
                par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                     grad_accum=2,
                                     gossip_impl="ppermute_packed",
                                     gossip_codec=codec,
                                     gossip_telemetry=telemetry)
                return steps.build_train_step(cfg, shape, mesh, par, dfl)

            setup = build("topk_ef")
            assert setup.init_codec_state is not None
            assert "codec_state" in setup.input_specs
            args = [P.shape_structs(setup.param_struct),
                    setup.input_specs["batch"], setup.input_specs["lr"],
                    setup.input_specs["alive"], setup.input_specs["gates"],
                    setup.input_specs["codec_state"]]
            text = setup.step_fn.lower(*args).as_text()
            d = setup.gossip_spec.degree
            perms = [l for l in text.splitlines()
                     if "collective_permute" in l]
            assert len(perms) == d, (len(perms), d)
            assert all("xi8>" in l for l in perms), "non-int8 top-k wire"

            wire = {c: build(c, telemetry=True).wire_bytes_per_round
                    for c in ("f32", "topk_ef")}
            ratio = wire["topk_ef"] / wire["f32"]
            assert ratio <= 0.10, ratio

            r = np.random.default_rng(0)
            structs = P.shape_structs(setup.param_struct)
            params = jax.tree.map(
                lambda s, sh: jax.device_put(
                    jnp.asarray(r.standard_normal(s.shape) * 0.02, s.dtype),
                    sh),
                structs, setup.in_shardings[0])
            batch = {k: jnp.zeros(v.shape, v.dtype)
                     for k, v in setup.input_specs["batch"].items()}
            cstate = setup.init_codec_state(params)
            n = setup.n_clients
            for rnd in range(3):
                alive = (r.random(n) > 0.3).astype(np.float32)
                if alive.sum() < 2:
                    alive[:] = 1.0
                gates = np.zeros(d, np.float32)
                gates[rnd % d] = 1.0
                params, _m, cstate = setup.step_fn(
                    params, batch, jnp.float32(0.01), jnp.asarray(alive),
                    jnp.asarray(gates), cstate)
            jax.block_until_ready(params)
            assert TraceCounter.cache_size(setup.step_fn) == 1
            resid = sum(float(jnp.sum(jnp.abs(c))) for c in cstate)
            assert resid > 0, "EF residual stayed zero"
            # the global codec-state layout leads with the device axes, the
            # per-client rows inside — a host-side old2new row take (the
            # splice-repair remap) is well-formed and byte-exact
            for spec, buf in zip(setup.input_specs["codec_state"], cstate):
                assert str(spec.dtype) == "float32"
                host = np.asarray(buf)
                perm = np.arange(host.shape[0])[::-1]
                np.testing.assert_array_equal(host[perm][perm], host)
            print("SPARSE_STEP_OK d=", d, "ratio=", round(ratio, 4))
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "SPARSE_STEP_OK" in out.stdout, out.stdout + out.stderr
