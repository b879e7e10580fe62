"""Overlay-lab tests: graph-family registry, graph -> overlay conversion,
and time-varying round plans on the packed gossip engine.

Acceptance (ISSUE 3): gated time-varying gossip (one-peer rotation over a
precompiled d-schedule pool) runs with ZERO retraces across rounds and
matches the dense gated-mixing oracle bit-for-bit in f32; `convert.py`
round-trips an arbitrary connected graph into a valid schedule-based
Overlay executable by `ppermute_mix_packed`.
"""
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # optional dep (requirements-dev.txt): property tests degrade, not error
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.configs.base import DFLConfig
from repro.core import dfedavg, gossip, spectral, topology
from repro.launch.elastic import ElasticTrainer
from repro.launch.steps import build_overlay
from repro.overlay import convert, plan as plan_lib, registry


# ----------------------------------------------------------------- registry
class TestRegistry:
    @pytest.mark.parametrize("family,n,expect_scheds", [
        ("ring", 16, 2),
        ("expander", 16, 4),
        ("complete", 12, 11),
        ("torus", 24, 4),
        ("hypercube", 16, 4),
        ("random_regular", 16, 4),
        ("onepeer_exp", 12, 6),   # shifts +-1, +-2, +-4
        ("onepeer_exp", 16, 7),   # shifts +-1, +-2, +-4, 8 (+8 == -8)
        ("erdos_renyi", 30, None),
    ])
    def test_family_builds_valid_connected(self, family, n, expect_scheds):
        ov, meta = registry.build(family, n, degree=4, seed=0)
        assert ov.n == n
        assert meta["connected"] and meta["spectral_gap"] > 0
        if expect_scheds is not None:
            assert meta["n_schedules"] == expect_scheds
        for s in ov.schedules:  # valid permutation schedules
            assert np.array_equal(np.sort(s), np.arange(n))
        ov.mixing_matrix()      # Chow weights well-defined

    def test_unknown_family_rejected(self):
        with pytest.raises(ValueError, match="unknown overlay family"):
            registry.build("moebius", 16)

    def test_torus_is_wraparound_grid(self):
        ov, meta = registry.build("torus", 24)  # 4 x 6
        adj = ov.simple_adjacency()
        assert (adj.sum(1) == 4).all()
        assert adj[0, 6] == 1 and adj[0, 18] == 1   # row wrap (r=4, c=6)
        assert adj[0, 1] == 1 and adj[0, 5] == 1    # col wrap

    def test_hypercube_needs_power_of_two(self):
        with pytest.raises(ValueError):
            registry.build("hypercube", 12)
        ov, meta = registry.build("hypercube", 32)
        assert meta["n_schedules"] == 5
        assert (ov.simple_adjacency().sum(1) == 5).all()

    def test_dflconfig_selects_registry_families(self):
        """`DFLConfig.topology` reaches every registered family through the
        production `build_overlay` entry point."""
        for family, n in [("torus", 16), ("hypercube", 16),
                          ("random_regular", 16), ("onepeer_exp", 16),
                          ("expander", 16), ("complete", 8)]:
            ov = build_overlay(n, DFLConfig(topology=family, degree=4))
            assert ov is not None and ov.n == n
            assert ov.spectral_report().connected

    def test_meta_ranks_families_by_gap(self):
        """The sweepable claim: metadata orders families the way the paper's
        theory says (complete > hypercube > ring at equal n)."""
        gaps = {f: registry.build(f, 16)[1]["spectral_gap"]
                for f in ("complete", "hypercube", "ring")}
        assert gaps["complete"] > gaps["hypercube"] > gaps["ring"]


# ------------------------------------------------------------------ convert
def _random_connected_adj(n, p, seed):
    rng = np.random.default_rng(seed)
    for _ in range(64):
        u = rng.random((n, n))
        a = np.triu((u < p).astype(np.int64), k=1)
        adj = a + a.T
        if spectral.is_connected(adj):
            return adj
    return None


def _check_conversion(n, p, seed):
    adj = _random_connected_adj(n, p, seed)
    if adj is None:
        return
    maxd = int(adj.sum(1).max())
    ov = convert.overlay_from_adjacency(adj)
    # lossless: the schedule multigraph IS the input graph
    np.testing.assert_array_equal(ov.multigraph_adjacency(), adj)
    # schedule count: Delta + 1 (Vizing) below the Euler-split cutoff; the
    # split path trades a few extra colors for near-linear time above it
    bound = maxd + (1 if maxd <= convert._EULER_CUTOFF else 8)
    assert len(ov.schedules) <= bound, (len(ov.schedules), maxd)
    if maxd > convert._EULER_CUTOFF:
        # pure Misra-Gries (no split) must still meet the Vizing bound
        ov_mg = convert.overlay_from_adjacency(adj, euler_cutoff=maxd)
        np.testing.assert_array_equal(ov_mg.multigraph_adjacency(), adj)
        assert len(ov_mg.schedules) <= maxd + 1, (len(ov_mg.schedules), maxd)
    for s in ov.schedules:
        assert np.array_equal(np.sort(s), np.arange(n))
        assert np.array_equal(np.argsort(s), s)
    # executable: Chow mixing matrix exists and is row-stochastic
    m = ov.mixing_matrix()
    np.testing.assert_allclose(m.sum(1), 1.0, atol=1e-9)


class TestConvert:
    def test_structured_graphs_round_trip(self):
        ring = topology.ring_overlay(12).simple_adjacency().astype(np.int64)
        for adj in (ring, topology.erdos_renyi_adjacency(20, seed=3
                                                         ).astype(np.int64)):
            ov = convert.overlay_from_adjacency(adj)
            np.testing.assert_array_equal(ov.multigraph_adjacency(), adj)

    def test_euler_split_high_degree(self):
        """Complete graphs force the Euler-tour divide path; the split costs
        a few extra colors but stays lossless."""
        for n in (16, 21):
            adj = np.ones((n, n), np.int64) - np.eye(n, dtype=np.int64)
            ov = convert.overlay_from_adjacency(adj)
            np.testing.assert_array_equal(ov.multigraph_adjacency(), adj)
            assert len(ov.schedules) <= (n - 1) + 8  # Delta + O(log Delta)

    def test_euler_split_halves_degrees(self):
        adj = _random_connected_adj(20, 0.5, 0)
        left, right = convert.euler_split(adj)
        np.testing.assert_array_equal(left + right, adj)
        deg = adj.sum(1)
        for half in (left, right):
            assert (np.abs(half.sum(1) - deg / 2.0) <= 1.0).all()

    def test_disconnected_rejected(self):
        adj = np.zeros((6, 6), np.int64)
        adj[0, 1] = adj[1, 0] = 1
        adj[2, 3] = adj[3, 2] = 1
        with pytest.raises(ValueError, match="disconnected"):
            convert.overlay_from_adjacency(adj)

    def test_invalid_adjacency_rejected(self):
        with pytest.raises(ValueError):  # asymmetric
            convert.overlay_from_adjacency(np.triu(np.ones((4, 4)), 1))
        with pytest.raises(ValueError):  # self loops
            convert.overlay_from_adjacency(np.ones((4, 4), np.int64))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(4, 32), p=st.floats(0.15, 0.7),
           seed=st.integers(0, 1000))
    def test_conversion_properties(n, p, seed):
        _check_conversion(n, p, seed)
else:
    @pytest.mark.parametrize("n,p,seed", [
        (6, 0.5, 0), (12, 0.3, 7), (20, 0.2, 42), (32, 0.15, 9),
        (15, 0.6, 3), (9, 0.4, 11),
    ])
    def test_conversion_properties(n, p, seed):
        _check_conversion(n, p, seed)


# ------------------------------------------------------- spectral sanity
def _check_alon_boppana(n, d, seed):
    """Random d-regular matching unions are near-Ramanujan (Friedman): the
    largest nontrivial adjacency eigenvalue sits within half the
    Alon-Boppana-to-trivial gap of the 2 sqrt(d-1) bound."""
    ov = registry.random_regular_overlay(n, d, seed)
    adj = ov.simple_adjacency()
    assert (adj.sum(1) == d).all()
    ev = np.linalg.eigvalsh(adj)
    mu = max(abs(ev[0]), abs(ev[-2]))
    bound = 2.0 * np.sqrt(d - 1.0)
    assert mu <= bound + 0.5 * (d - bound), (n, d, seed, mu, bound)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=st.sampled_from([16, 32, 48, 64]), d=st.sampled_from([4, 6]),
           seed=st.integers(0, 150))
    def test_random_regular_spectral_gap(n, d, seed):
        _check_alon_boppana(n, d, seed)
else:
    @pytest.mark.parametrize("n,d,seed", [
        (16, 4, 0), (32, 4, 17), (64, 4, 123), (32, 6, 5), (64, 6, 77),
    ])
    def test_random_regular_spectral_gap(n, d, seed):
        _check_alon_boppana(n, d, seed)


# -------------------------------------------------------------- round plans
class TestRoundPlans:
    def test_one_peer_rotation_covers_pool(self):
        p = plan_lib.OnePeerPlan()
        seen = np.zeros(5)
        for rnd in range(5):
            g = p.gates(rnd, 5)
            assert g.sum() == 1.0 and g.dtype == np.float32
            seen += g
        np.testing.assert_array_equal(seen, 1.0)  # each schedule exactly once

    def test_random_subset_size_and_determinism(self):
        p = plan_lib.RandomSubsetPlan(k=2, seed=3)
        for rnd in range(6):
            g = p.gates(rnd, 6)
            assert g.sum() == 2.0
            np.testing.assert_array_equal(g, p.gates(rnd, 6))  # stateless

    def test_throttle_fraction_rotates(self):
        p = plan_lib.ThrottlePlan(fraction=0.5)
        seen = np.zeros(6)
        for rnd in range(4):
            g = p.gates(rnd, 6)
            assert g.sum() == 3.0
            seen += g
        assert (seen > 0).all()  # rotation reaches the whole pool

    def test_make_plan_factory(self):
        assert plan_lib.make_plan("one_peer").gates(1, 4)[1] == 1.0
        assert plan_lib.make_plan("static").gates(0, 3).sum() == 3.0
        with pytest.raises(ValueError):
            plan_lib.make_plan("fourier")


# ------------------------------------------------- gated mixing (stacked)
def _tree(n, seed=0):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.standard_normal((n, 6, 5)), jnp.float32),
            "b": jnp.asarray(r.standard_normal((n, 11)), jnp.float32)}


class TestGatedMixing:
    def test_gated_matrix_row_stochastic_and_composes_with_alive(self):
        ov = topology.expander_overlay(12, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        r = np.random.default_rng(0)
        for t in range(4):
            g = (r.random(4) > 0.5).astype(np.float32)
            alive = (r.random(12) > 0.3).astype(np.float32)
            m = np.asarray(gossip.gated_mixing_matrix(
                spec, jnp.asarray(g), jnp.asarray(alive)))
            np.testing.assert_allclose(m.sum(1), 1.0, atol=1e-5)
            for i in np.nonzero(alive == 0)[0]:  # dead receivers: identity
                assert m[i, i] == pytest.approx(1.0)

    def test_stacked_gated_matches_dense_oracle(self):
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(10, seed=5)
        r = np.random.default_rng(1)
        for t in range(4):
            g = (r.random(4) > 0.4).astype(np.float32)
            alive = (r.random(10) > 0.25).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            got = gossip.mix_packed_stacked(x, spec, jnp.asarray(alive),
                                            gates=jnp.asarray(g))
            ref = gossip.mix_dense_gated(x, spec, jnp.asarray(g),
                                         jnp.asarray(alive))
            for k in x:
                np.testing.assert_allclose(got[k], ref[k],
                                           rtol=2e-5, atol=2e-5)

    def test_all_gates_zero_is_identity(self):
        ov = topology.expander_overlay(8, 4, seed=1)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(8)
        got = gossip.mix_packed_stacked(x, spec, gates=jnp.zeros(4))
        for k in x:
            np.testing.assert_allclose(got[k], x[k], rtol=1e-6)

    def test_all_gates_one_matches_ungated(self):
        ov = topology.expander_overlay(8, 4, seed=1)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(8, seed=2)
        got = gossip.mix_packed_stacked(x, spec, gates=jnp.ones(4))
        ref = gossip.mix_dense(x, ov.mixing_matrix())
        for k in x:
            np.testing.assert_allclose(got[k], ref[k], rtol=2e-5, atol=2e-5)

    def test_gates_on_converted_overlay_with_fixed_points(self):
        """Gate semantics under fixed points (matching schedules leave nodes
        uncovered): the full-permutation convention keeps rows stochastic."""
        adj = topology.erdos_renyi_adjacency(12, seed=1).astype(np.int64)
        ov = convert.overlay_from_adjacency(adj)
        spec = gossip.make_gossip_spec(ov)
        x = {"w": jnp.asarray(
            np.random.default_rng(0).standard_normal((12, 7)), jnp.float32)}
        g = (np.random.default_rng(2).random(spec.degree) > 0.4
             ).astype(np.float32)
        got = gossip.mix_packed_stacked(x, spec, gates=jnp.asarray(g))
        ref = gossip.mix_dense_gated(x, spec, jnp.asarray(g))
        np.testing.assert_allclose(got["w"], ref["w"], rtol=2e-5, atol=2e-5)


# ---------------------------------- acceptance: packed executor + retraces
class TestGatedPackedShardMap:
    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    def test_one_peer_rotation_bitwise_and_zero_retrace(self):
        """ISSUE 3 acceptance: one-peer rotation over the precompiled
        d-schedule pool — zero retraces across rounds, bit-for-bit equal to
        the dense gated oracle in f32 (gates+alive composed)."""
        self._run("""
            import os
            # bitwise needs both sides rounded alike: on a host with FMA,
            # XLA:CPU fuses a multiply-add on one side only, so cap the ISA
            os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8"
                                       " --xla_cpu_max_isa=AVX")
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, topology
            from repro.launch.mesh import make_mesh, shard_map
            from repro.overlay.plan import OnePeerPlan
            from repro.telemetry import TraceCounter

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=0)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(0)
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            specs = jax.tree.map(lambda _: P("client"), x)
            xs = jax.device_put(x, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), x))

            tracer = TraceCounter("one_peer")
            @tracer.wrap
            def body(t, a, g):
                local = jax.tree.map(lambda v: v[0], t)
                out = gossip.ppermute_mix_packed(local, spec, "client",
                                                 alive=a, gates=g)
                return jax.tree.map(lambda v: v[None], out)
            fn = jax.jit(shard_map(body, mesh, in_specs=(specs, P(), P()),
                                   out_specs=specs))
            plan = OnePeerPlan()
            for rnd in range(10):
                g = plan.gates(rnd, spec.degree)
                alive = np.ones(8, np.float32)
                if rnd >= 5:
                    alive[rnd % 3] = 0.0   # compose with straggler masking
                got = fn(xs, jnp.asarray(alive), jnp.asarray(g))
                ref = gossip.mix_dense_gated(x, spec, jnp.asarray(g),
                                             jnp.asarray(alive))
                for k in x:   # bit-for-bit in f32
                    np.testing.assert_array_equal(np.asarray(got[k]),
                                                  np.asarray(ref[k]))
            tracer.expect(1, what="one-peer gates are data")
            print("ONE_PEER_BITWISE_OK traces=%d" % tracer.count)
        """)

    def test_converted_overlay_executable_by_ppermute_mix_packed(self):
        """ISSUE 3 acceptance: an arbitrary connected graph, converted to
        schedules, executes on the packed engine and matches the oracle."""
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, spectral, topology
            from repro.launch.mesh import make_mesh, shard_map
            from repro.overlay import convert

            rng = np.random.default_rng(7)
            while True:   # arbitrary connected 8-node graph
                u = rng.random((8, 8))
                a = np.triu((u < 0.4).astype(np.int64), 1)
                adj = a + a.T
                if spectral.is_connected(adj):
                    break
            ov = convert.overlay_from_adjacency(adj)
            np.testing.assert_array_equal(ov.multigraph_adjacency(), adj)
            spec = gossip.make_gossip_spec(ov)

            mesh = make_mesh((8,), ("client",))
            x = {"w": jnp.asarray(rng.standard_normal((8, 6, 5)),
                                  jnp.float32)}
            specs = jax.tree.map(lambda _: P("client"), x)
            xs = jax.device_put(x, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), x))

            def body(t):
                local = jax.tree.map(lambda v: v[0], t)
                out = gossip.ppermute_mix_packed(local, spec, "client")
                return jax.tree.map(lambda v: v[None], out)
            fn = jax.jit(shard_map(body, mesh, in_specs=(specs,),
                                   out_specs=specs))
            got = fn(xs)
            ref = gossip.mix_dense(x, ov.mixing_matrix())
            np.testing.assert_allclose(np.asarray(got["w"]),
                                       np.asarray(ref["w"]),
                                       rtol=2e-5, atol=2e-5)
            print("CONVERTED_EXEC_OK schedules=%d" % spec.degree)
        """)


# ------------------------------------------------------- elastic + plans
def quad_loss(params, batch):
    return jnp.mean(jnp.square(params["w"] - batch["target"])), {}


def _batches(targets, k):
    return {"target": jnp.broadcast_to(
        targets[:, None], (targets.shape[0], k, targets.shape[1]))}


class TestElasticWithPlan:
    def test_one_peer_plan_zero_retrace_and_oracle_parity(self):
        """Time-varying rounds through the elastic trainer: rotating gates
        (+ straggler churn) reuse ONE executable, and every round matches a
        manual local-step + dense gated-mixing oracle loop."""
        n, dim = 10, 4
        r = np.random.default_rng(0)
        targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
        cfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.3, momentum=0.5)
        overlay = topology.expander_overlay(n, 4, seed=3)
        trainer = ElasticTrainer(overlay=overlay, loss_fn=quad_loss,
                                 dcfg=cfg, straggler_rounds=1,
                                 failure_rounds=99,
                                 plan=plan_lib.OnePeerPlan())
        spec = trainer.spec

        params = {"w": jnp.zeros((n, dim))}
        ref = {"w": jnp.zeros((n, dim))}

        def local(p, b):
            def client(pc, bc):
                v = jax.tree.map(jnp.zeros_like, pc)
                pc, _, loss = dfedavg.local_round(pc, v, bc, quad_loss, cfg,
                                                  lr=0.3)
                return pc, loss
            return jax.vmap(client)(p, b)

        rng = np.random.default_rng(1)
        for rnd in range(8):
            mask = np.ones(n, np.float32)
            if rnd in (3, 5):
                mask[rng.integers(n)] = 0.0
            gates = trainer.gates_for_round(rnd)
            params, _, _ = trainer.observe_heartbeats(mask, params)
            batches = _batches(targets, 2)
            params, _ = trainer.step(params, batches, 0.3)
            ref, _ = local(ref, batches)
            ref = gossip.mix_dense_gated(ref, spec, gates, jnp.asarray(mask))
            np.testing.assert_allclose(np.asarray(params["w"]),
                                       np.asarray(ref["w"]),
                                       rtol=2e-5, atol=2e-5)
        assert trainer.n_traces == 1, trainer.n_traces

    def test_static_plan_is_bitwise_equal_to_no_plan(self):
        """Regression: a StaticPlan must be inert. On overlays whose Chow
        self-weight is negative (onepeer_exp at n=32: w0 < 0), all-ones
        gates are NOT a no-op (the gated branch clamps w0) — so the gate
        pathway must stay off for static plans, matching plan=None
        bit-for-bit."""
        n, dim = 32, 5
        overlay, _ = registry.build("onepeer_exp", n)
        spec = gossip.make_gossip_spec(overlay)
        assert min(spec.self_weights) < 0  # the case that used to diverge
        r = np.random.default_rng(0)
        targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
        cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0)
        outs = []
        for plan in (None, plan_lib.StaticPlan(),
                     plan_lib.make_plan("static")):
            trainer = ElasticTrainer(overlay=overlay, loss_fn=quad_loss,
                                     dcfg=cfg, straggler_rounds=1,
                                     failure_rounds=99, plan=plan)
            params = {"w": jnp.zeros((n, dim))}
            for _ in range(3):
                trainer.observe_heartbeats(np.ones(n), params)
                params, _ = trainer.step(params, _batches(targets, 1), 0.2)
            outs.append(np.asarray(params["w"]))
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])

    def test_plan_survives_repair(self):
        """A membership change rebuilds the spec (new schedule count); the
        stateless plan keeps issuing valid gates and training continues."""
        n, dim = 12, 3
        targets = jnp.zeros((n, dim))
        cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0)
        trainer = ElasticTrainer(overlay=topology.expander_overlay(n, 4,
                                                                   seed=0),
                                 loss_fn=quad_loss, dcfg=cfg,
                                 straggler_rounds=1, failure_rounds=2,
                                 plan=plan_lib.OnePeerPlan())
        params = {"w": jnp.ones((n, dim))}
        alive = np.ones(n)
        for _ in range(2):
            params, _, _ = trainer.observe_heartbeats(alive, params)
            params, _ = trainer.step(params, _batches(targets, 1), 0.2)
        alive[4] = 0
        params, _, _ = trainer.observe_heartbeats(alive, params)
        params, _ = trainer.step(params, _batches(targets, 1), 0.2)
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is not None and trainer.n_clients == n - 1
        targets2 = jnp.zeros((n - 1, dim))
        for _ in range(4):
            params, _, _ = trainer.observe_heartbeats(np.ones(n - 1), params)
            params, _ = trainer.step(params, _batches(targets2, 1), 0.2)
        assert trainer.n_traces == 2          # one per membership
        assert bool(jnp.isfinite(params["w"]).all())
        assert trainer.gates_for_round().shape == (trainer.spec.degree,)
