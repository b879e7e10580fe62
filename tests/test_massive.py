"""Massive-client substrate tests: active-set round plans + blocked gossip.

Covers the two halves of the client-count/device-count decoupling:

* :class:`repro.overlay.plan.ActiveSetPlan` — round-level client subsampling
  shipped as step data (participation-as-data: zero retraces across cohort
  rotations, never visible to the HealthTracker);
* the ``blocked`` engine substrate (`repro.core.gossip.BlockedSpec`) — B
  simulated clients per device, intra-block edges as stacked gathers and
  cross-block schedule parts as whole-block ppermutes, bit-compatible with
  the stacked substrate.
"""
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dfedavg, engine as engine_lib, failures as failures_lib, \
    gossip, topology
from repro.launch.elastic import ElasticTrainer
from repro.overlay import plan as plan_lib


# ------------------------------------------------------------ active sets
class TestActiveSetPlans:
    def test_full_plan_is_inert(self):
        """No plan and the "full" plan are the same non-engagement: ones
        vector, is_subsampling False (the predicate the step builders key
        their signature on)."""
        assert not plan_lib.is_subsampling(None)
        assert not plan_lib.is_subsampling(plan_lib.FullActiveSet())
        assert plan_lib.is_subsampling(plan_lib.RandomKActiveSet(k=2))
        np.testing.assert_array_equal(plan_lib.active_for(None, 3, 7),
                                      np.ones(7, np.float32))
        np.testing.assert_array_equal(
            plan_lib.active_for(plan_lib.FullActiveSet(), 3, 7),
            np.ones(7, np.float32))

    def test_random_k_count_and_determinism(self):
        plan = plan_lib.RandomKActiveSet(k=5, seed=3)
        for rnd in range(6):
            a = plan.active(rnd, 16)
            assert a.sum() == 5 and set(np.unique(a)) <= {0.0, 1.0}
            np.testing.assert_array_equal(a, plan.active(rnd, 16))
        # cohorts rotate (not the same set every round)
        assert any(not np.array_equal(plan.active(0, 16), plan.active(r, 16))
                   for r in range(1, 6))

    def test_shards_cover_everyone_exactly_once(self):
        plan = plan_lib.ShardActiveSet(n_shards=4)
        total = np.zeros(12)
        for rnd in range(4):
            a = plan.active(rnd, 12)
            assert a.sum() == 3  # 12 clients / 4 shards
            total += a
        np.testing.assert_array_equal(total, np.ones(12))

    def test_stratified_every_stratum_represented(self):
        plan = plan_lib.StratifiedActiveSet(k=4, n_strata=4, seed=0)
        for rnd in range(5):
            a = plan.active(rnd, 16)
            # strata are contiguous quarters; each must send >= 1 client
            for j in range(4):
                assert a[4 * j:4 * (j + 1)].sum() >= 1
            np.testing.assert_array_equal(a, plan.active(rnd, 16))

    def test_factory_names_and_validation(self):
        assert plan_lib.make_active_set("full").name == "full"
        assert plan_lib.make_active_set("random_k", k=3).k == 3
        assert plan_lib.make_active_set("shards", n_shards=5).n_shards == 5
        st = plan_lib.make_active_set("stratified", k=4, n_shards=2)
        assert st.n_strata == 2
        with pytest.raises(ValueError, match="unknown active-set plan"):
            plan_lib.make_active_set("typo")
        assert set(plan_lib.ACTIVE_SET_NAMES) == {
            "full", "random_k", "shards", "stratified"}


# ------------------------------------------------------------ blocked spec
class TestBlockedSpec:
    def test_block_equals_n_is_intra_only(self):
        """B = n: one device holds everyone — every schedule is intra-block
        (no transfers) and the gather table degenerates to recv_from."""
        ov = topology.expander_overlay(12, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        bs = gossip.make_blocked_spec(spec, 12)
        assert bs.n_devices == 1 and bs.n_transfers == 0
        assert bs.cross_schedules == 0
        for s, rf in enumerate(spec.recv_from):
            np.testing.assert_array_equal(bs.gather_flat[s], rf)

    def test_ring_two_devices(self):
        """Ring on 2 devices: each direction schedule has exactly one
        cross-block partial permutation (the {0->1, 1->0} swap)."""
        ov = topology.ring_overlay(8)
        spec = gossip.make_gossip_spec(ov)
        bs = gossip.make_blocked_spec(spec, 4)
        assert bs.n_devices == 2
        assert bs.cross_schedules == len(spec.recv_from)
        # on 2 devices a schedule's cross demand is always one swap
        assert bs.n_transfers == bs.cross_schedules
        for part in bs.transfers:
            assert set(part) <= {(0, 1), (1, 0)}

    @pytest.mark.parametrize("n,d,b", [(12, 4, 3), (16, 4, 4), (12, 4, 6),
                                       (16, 2, 8)])
    def test_gather_table_reconstructs_recv_from(self, n, d, b):
        """Brute-force replay of the blocked round's data movement with
        client ids as payload: applying the transfers and then the flat
        gather must reproduce each schedule's recv_from exactly."""
        ov = topology.expander_overlay(n, d, seed=1)
        spec = gossip.make_gossip_spec(ov)
        bs = gossip.make_blocked_spec(spec, b)
        device_wire = [np.arange(dev * b, (dev + 1) * b)
                       for dev in range(bs.n_devices)]
        for s, rf in enumerate(spec.recv_from):
            for dev in range(bs.n_devices):
                cand = [device_wire[dev]]
                for part in bs.transfers:
                    srcs = [sd for (sd, dd) in part if dd == dev]
                    # ppermute: a device outside the partial permutation
                    # receives zeros; -1 sentinel catches a bad slot
                    cand.append(device_wire[srcs[0]] if srcs
                                else np.full(b, -1))
                flat = np.concatenate(cand)
                for row in range(b):
                    i = dev * b + row
                    assert flat[bs.gather_flat[s][i]] == rf[i], (s, i)

    def test_partial_permutation_invariant(self):
        """No device sends or receives twice within one transfer (the
        condition for a single ppermute to carry the whole part)."""
        ov = topology.expander_overlay(16, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        bs = gossip.make_blocked_spec(spec, 2)
        for part in bs.transfers:
            srcs = [s for s, _ in part]
            dsts = [d for _, d in part]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)

    def test_invalid_block_rejected(self):
        ov = topology.expander_overlay(12, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        with pytest.raises(ValueError, match="dividing n_clients"):
            gossip.make_blocked_spec(spec, 5)
        with pytest.raises(ValueError, match="dividing n_clients"):
            gossip.make_blocked_spec(spec, 0)


# ------------------------------------------------- engine config validation
class TestBlockedConfigValidation:
    def test_delay_on_blocked_rejected_names_supported_cells(self):
        with pytest.raises(ValueError) as e:
            engine_lib.GossipEngineConfig(substrate="blocked", block=4,
                                          delay=1)
        msg = str(e.value)
        assert "shard_map | stacked" in msg and "blocked" in msg

    def test_screen_on_blocked_rejected_names_supported_cells(self):
        for screen in ("norm_clip", "trimmed_mean"):
            with pytest.raises(ValueError) as e:
                engine_lib.GossipEngineConfig(substrate="blocked", block=4,
                                              screen=screen)
            msg = str(e.value)
            assert "shard_map | stacked" in msg and "blocked" in msg

    def test_blocked_needs_block(self):
        with pytest.raises(ValueError, match="block >= 1"):
            engine_lib.GossipEngineConfig(substrate="blocked")

    def test_block_on_other_substrates_rejected(self):
        with pytest.raises(ValueError):
            engine_lib.GossipEngineConfig(substrate="stacked", block=4)

    def test_blocked_needs_axis_names(self):
        ov = topology.expander_overlay(8, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        with pytest.raises(ValueError, match="axis_names"):
            engine_lib.build_gossip_executor(
                engine_lib.GossipEngineConfig(substrate="blocked", block=4),
                spec)


# ------------------------------------------------ single-device blocked
def _island(executor, mesh):
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import shard_map

    def body(t, a, g):
        return executor(t, alive=a, gates=g)

    return jax.jit(shard_map(body, mesh, in_specs=(P("clients"), P(), P()),
                             out_specs=P("clients")))


class TestBlockedParityOneDevice:
    """block = n on the single local device: the blocked round must be
    BITWISE identical to the stacked round (the same f32 multiply-add over
    the same rows)."""

    def test_bitwise_vs_stacked_with_alive_and_gates(self):
        from jax.sharding import Mesh
        n = 12
        ov = topology.expander_overlay(n, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        r = np.random.default_rng(0)
        tree = {"a": jnp.asarray(r.standard_normal((n, 6, 5)), jnp.float32),
                "b": jnp.asarray(r.standard_normal((n, 11)), jnp.float32)}
        stacked = engine_lib.build_gossip_executor(
            engine_lib.GossipEngineConfig(substrate="stacked"), spec)
        blocked = engine_lib.build_gossip_executor(
            engine_lib.GossipEngineConfig(substrate="blocked", block=n),
            spec, axis_names="clients")
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
        fn = _island(blocked, mesh)
        for t in range(3):
            alive = (np.random.default_rng(t).random(n) > 0.3
                     ).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            gates = np.zeros(spec.degree, np.float32)
            gates[t % spec.degree] = 1.0
            ref = stacked(tree, alive=jnp.asarray(alive),
                          gates=jnp.asarray(gates))
            got = fn(tree, jnp.asarray(alive), jnp.asarray(gates))
            for k in tree:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(ref[k]))


# --------------------------------------------- trainer-level composition
def _quad_loss(p, b):
    pred = b["x"] @ p["w"]
    return jnp.mean((pred - b["y"]) ** 2), {}


def _quad_setup(n, seed=0):
    r = np.random.default_rng(seed)
    params = {"w": jnp.asarray(r.standard_normal((n, 5, 3)), jnp.float32)}

    def batches(rnd, m=n):  # m: current client count (shrinks after splice)
        rr = np.random.default_rng(1000 + rnd)
        return {"x": jnp.asarray(rr.standard_normal((m, 8, 5)), jnp.float32),
                "y": jnp.asarray(rr.standard_normal((m, 8, 3)), jnp.float32)}

    return params, batches


def _make_trainer(n, **kw):
    ov = topology.expander_overlay(n, 4, seed=0)
    dcfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.05, momentum=0.9)
    return ElasticTrainer(overlay=ov, loss_fn=_quad_loss, dcfg=dcfg, **kw)


class TestElasticActiveSetComposition:
    """Satellite: active-set plan x alive churn x one-peer gates x
    AttackPlan, on both the stacked and (1-device) blocked substrates —
    zero retraces across >= 3 cohort rotations, identical results."""

    def _run(self, n, rounds, gossip_block):
        t = _make_trainer(
            n,
            plan=plan_lib.make_plan("one_peer"),
            active_plan=plan_lib.make_active_set("random_k", k=n // 2,
                                                 seed=1),
            attack_plan=failures_lib.sample_attackers(n, 2, seed=3),
            engine=engine_lib.GossipEngineConfig(
                substrate="blocked" if gossip_block else "stacked",
                block=gossip_block))
        params, batches = _quad_setup(n)
        r = np.random.default_rng(7)
        for rnd in range(rounds):
            hb = (r.random(n) > 0.2).astype(np.float32)  # straggler churn
            params, _, o2n = t.observe_heartbeats(hb, params)
            assert o2n is None  # churn below failure_rounds: no repair
            params, _ = t.step(params, batches(rnd), 0.05)
        return t, params

    def test_zero_retraces_and_blocked_parity(self):
        n, rounds = 12, 5  # >= 3 distinct cohorts from the random_k plan
        t_stacked, p_stacked = self._run(n, rounds, gossip_block=0)
        t_blocked, p_blocked = self._run(n, rounds, gossip_block=n)
        assert t_stacked.n_traces == 1
        assert t_blocked.n_traces == 1
        # distinct cohorts actually happened (rotation, not repetition)
        cohorts = {tuple(t_stacked.active_for_round(r)) for r in range(rounds)}
        assert len(cohorts) >= 3
        np.testing.assert_array_equal(np.asarray(p_stacked["w"]),
                                      np.asarray(p_blocked["w"]))

    def test_active_set_never_feeds_health_tracker(self):
        """Inactive clients are resting, not failing: with every heartbeat
        present, a rotating active set must leave the tracker pristine —
        no stragglers, no dead, no repairs."""
        n = 8
        t = _make_trainer(n, active_plan=plan_lib.ShardActiveSet(n_shards=4))
        params, batches = _quad_setup(n)
        for rnd in range(6):
            params, _, _ = t.observe_heartbeats(np.ones(n, np.float32),
                                                params)
            params, _ = t.step(params, batches(rnd), 0.05)
        assert t.health.missed.sum() == 0
        assert len(t.health.stragglers()) == 0 and len(t.health.dead()) == 0
        assert t.repairs == [] and t.n_traces == 1

    def test_inactive_clients_mix_as_identity(self):
        """One gossip round: an alive-but-inactive client keeps its
        post-local-step params (identity row), and its neighbors mix
        without it — the dead-client semantics, minus the health cost."""
        n = 10
        ov = topology.expander_overlay(n, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        r = np.random.default_rng(0)
        x = {"w": jnp.asarray(r.standard_normal((n, 7)), jnp.float32)}
        active = np.ones(n, np.float32)
        active[[2, 5]] = 0.0
        got = gossip.mix_packed_stacked(x, spec, alive=jnp.asarray(active))
        ref = gossip.mix_dense_masked(x, ov.mixing_matrix(), active)
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.asarray(ref["w"]), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_array_equal(np.asarray(got["w"])[[2, 5]],
                                      np.asarray(x["w"])[[2, 5]])

    def test_byte_exact_remap_through_splice_repair(self):
        """A permanent failure under an active-set plan: the splice must
        remap the surviving rows byte-exactly (pure row gather, no math)
        and cost exactly one retrace."""
        n = 12
        t = _make_trainer(
            n, plan=plan_lib.make_plan("one_peer"),
            active_plan=plan_lib.make_active_set("shards", n_shards=3),
            failure_rounds=2)
        params, batches = _quad_setup(n)
        for rnd in range(2):
            params, _, _ = t.observe_heartbeats(np.ones(n, np.float32),
                                                params)
            params, _ = t.step(params, batches(rnd), 0.05)
        assert t.n_traces == 1
        before = np.asarray(params["w"])
        hb = np.ones(n, np.float32)
        hb[4] = 0.0
        old2new = None
        rnd = 2
        while old2new is None:
            params, _, old2new = t.observe_heartbeats(hb, params)
            if old2new is None:
                params, _ = t.step(params, batches(rnd), 0.05)
                before = np.asarray(params["w"])
                rnd += 1
        survivors = np.asarray(
            [i for i in range(n) if np.asarray(old2new)[i] >= 0])
        np.testing.assert_array_equal(np.asarray(params["w"]),
                                      before[survivors])
        assert t.repairs[-1]["spliced"] is True
        params, _ = t.step(params, batches(rnd, t.overlay.n), 0.05)
        assert t.n_traces == 2  # exactly one re-jit, from the repair

    def test_blocked_masking_repair_never_rejits(self):
        """Blocked layout, survivor count not divisible by block: the dead
        client is permanently masked instead of spliced — repairs records
        spliced=False and the executable never retraces."""
        n = 12
        t = _make_trainer(n, failure_rounds=2,
                          engine=engine_lib.GossipEngineConfig(
                              substrate="blocked", block=n))
        params, batches = _quad_setup(n)
        hb = np.ones(n, np.float32)
        hb[3] = 0.0
        for rnd in range(4):
            params, _, o2n = t.observe_heartbeats(hb, params)
            assert o2n is None  # masking is not a membership change
            params, _ = t.step(params, batches(rnd), 0.05)
        assert t.repairs and t.repairs[-1]["spliced"] is False
        assert t.repairs[-1]["masked"] == [3]
        assert t.overlay.n == n and t.n_traces == 1

    def test_blocked_validation(self):
        with pytest.raises(ValueError, match="divisor"):
            _make_trainer(12, engine=engine_lib.GossipEngineConfig(
                substrate="blocked", block=5))
        with pytest.raises(ValueError, match="devices"):
            # 12 devices on a 1-CPU host
            _make_trainer(12, engine=engine_lib.GossipEngineConfig(
                substrate="blocked", block=1))


# -------------------------------------------------- multi-device (slow)
class TestBlockedMultiDevice:
    """Real cross-device blocked gossip on fake-device meshes (subprocess:
    the device count must be pinned before jax initializes)."""

    @pytest.mark.slow
    def test_two_device_parity_and_collective_count(self):
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import Mesh, PartitionSpec as P
            from repro.core import engine as engine_lib, gossip, topology
            from repro.launch.mesh import shard_map

            n, b = 8, 4
            ov = topology.expander_overlay(n, 4, seed=0)
            spec = gossip.make_gossip_spec(ov)
            bs = gossip.make_blocked_spec(spec, b)
            r = np.random.default_rng(0)
            tree = {"a": jnp.asarray(r.standard_normal((n, 6, 5)), jnp.float32),
                    "w": jnp.asarray(r.standard_normal((n, 11)), jnp.float32)}
            alive = jnp.asarray(
                np.array([1, 1, 0, 1, 1, 1, 1, 0], np.float32))
            gates = jnp.asarray(np.array([1, 0, 1, 1], np.float32))
            mesh = Mesh(np.asarray(jax.devices()), ("clients",))

            def island(executor):
                def body(t, a, g):
                    return executor(t, alive=a, gates=g)
                return jax.jit(shard_map(
                    body, mesh, in_specs=(P("clients"), P(), P()),
                    out_specs=P("clients")))

            for codec, exact in (("f32", True), ("int8", False)):
                stacked = engine_lib.build_gossip_executor(
                    engine_lib.GossipEngineConfig(substrate="stacked",
                                                  codec=codec), spec)
                blocked = engine_lib.build_gossip_executor(
                    engine_lib.GossipEngineConfig(substrate="blocked",
                                                  codec=codec, block=b),
                    spec, axis_names="clients")
                fn = island(blocked)
                hlo = fn.lower(tree, alive, gates).as_text()
                n_perm = hlo.count("collective_permute")
                # cross-device edge count in HLO == the schedule partition:
                # on 2 devices, one swap per cross-block schedule
                assert n_perm == bs.n_transfers == bs.cross_schedules, (
                    codec, n_perm, bs.n_transfers)
                ref = stacked(tree, alive=alive, gates=gates)
                got = fn(tree, alive, gates)
                for k in tree:
                    a_ref, a_got = np.asarray(ref[k]), np.asarray(got[k])
                    if exact:
                        np.testing.assert_array_equal(a_got, a_ref)
                    else:  # int8: same codec path, tiny tolerance
                        np.testing.assert_allclose(a_got, a_ref,
                                                   rtol=1e-5, atol=1e-5)
            print("BLOCKED_PARITY_OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "BLOCKED_PARITY_OK" in out.stdout, out.stdout + out.stderr

    @pytest.mark.slow
    def test_blocked_trainer_splice_on_four_devices(self):
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import dfedavg, engine as engine_lib, topology
            from repro.launch.elastic import ElasticTrainer
            from repro.overlay import plan as plan_lib

            n, b = 16, 4

            def loss_fn(p, batch):
                pred = batch["x"] @ p["w"]
                return jnp.mean((pred - batch["y"]) ** 2), {}

            t = ElasticTrainer(
                overlay=topology.expander_overlay(n, 4, seed=0),
                loss_fn=loss_fn,
                dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.05,
                                            momentum=0.9),
                active_plan=plan_lib.make_active_set("shards", n_shards=2),
                engine=engine_lib.GossipEngineConfig(substrate="blocked",
                                                     block=b),
                failure_rounds=2)
            r = np.random.default_rng(0)
            params = {"w": jnp.asarray(r.standard_normal((n, 5, 3)),
                                       jnp.float32)}

            def batches(rnd, m):
                rr = np.random.default_rng(1000 + rnd)
                return {"x": jnp.asarray(rr.standard_normal((m, 8, 5)),
                                         jnp.float32),
                        "y": jnp.asarray(rr.standard_normal((m, 8, 3)),
                                         jnp.float32)}

            for rnd in range(2):
                params, _, _ = t.observe_heartbeats(np.ones(n, np.float32),
                                                    params)
                params, _ = t.step(params, batches(rnd, n), 0.05)
            assert t.n_traces == 1

            # kill 4 clients: survivors 12 = 3 blocks -> splice to 3 devices
            hb = np.ones(n, np.float32)
            hb[[1, 6, 9, 14]] = 0.0
            before = old2new = None
            rnd = 2
            while old2new is None:
                before = np.asarray(params["w"])
                params, _, old2new = t.observe_heartbeats(hb, params)
                if old2new is None:
                    params, _ = t.step(params, batches(rnd, n), 0.05)
                    rnd += 1
            survivors = np.asarray([i for i in range(n)
                                    if np.asarray(old2new)[i] >= 0])
            np.testing.assert_array_equal(np.asarray(params["w"]),
                                          before[survivors])
            assert t.repairs[-1]["spliced"] is True
            assert t.overlay.n == 12
            params, _ = t.step(params, batches(rnd, 12), 0.05)
            assert t.n_traces == 2  # exactly one re-jit for the repair
            print("BLOCKED_SPLICE_OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "BLOCKED_SPLICE_OK" in out.stdout, out.stdout + out.stderr
