"""Gossip executor equivalence + convergence-to-consensus tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # optional dep (requirements-dev.txt): property tests degrade, not error
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import gossip, topology


def _tree(n, seed=0):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.standard_normal((n, 6, 5)), jnp.float32),
            "b": jnp.asarray(r.standard_normal((n, 11)), jnp.float32)}


class TestExecutorEquivalence:
    @pytest.mark.parametrize("n,d", [(8, 2), (16, 4), (12, 3)])
    def test_schedules_match_dense(self, n, d):
        ov = topology.expander_overlay(n, d, seed=0)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(n)
        dense = gossip.mix_dense(x, ov.mixing_matrix())
        sched = gossip.mix_schedules(x, spec)
        for k in x:
            np.testing.assert_allclose(dense[k], sched[k], rtol=2e-5, atol=2e-5)

    def test_gossip_preserves_mean(self):
        """Doubly-stochastic mixing: the client-mean is invariant."""
        ov = topology.expander_overlay(16, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(16, seed=3)
        y = gossip.mix_schedules(x, spec)
        for k in x:
            np.testing.assert_allclose(jnp.mean(x[k], 0), jnp.mean(y[k], 0),
                                       rtol=1e-4, atol=1e-5)

    def test_consensus_rate_matches_lambda(self):
        """Disagreement contracts at rate lambda per round (spectral theory)."""
        n = 32
        ov = topology.expander_overlay(n, 4, seed=1)
        spec = gossip.make_gossip_spec(ov)
        lam = spec.lam
        r = np.random.default_rng(0)
        x = {"w": jnp.asarray(r.standard_normal((n, 40)), jnp.float32)}
        def disagreement(t):
            mean = jnp.mean(t["w"], 0, keepdims=True)
            return float(jnp.linalg.norm(t["w"] - mean))
        d0 = disagreement(x)
        for _ in range(10):
            x = gossip.mix_schedules(x, spec)
        d10 = disagreement(x)
        assert d10 <= d0 * (lam ** 10) * 1.05  # within 5% of the bound

    def test_expander_mixes_faster_than_ring(self):
        n = 32
        r = np.random.default_rng(0)
        x0 = np.asarray(r.standard_normal((n, 20)), np.float32)
        outs = {}
        for name, ov in [("ring", topology.ring_overlay(n)),
                         ("exp", topology.expander_overlay(n, 4, seed=0))]:
            spec = gossip.make_gossip_spec(ov)
            x = {"w": jnp.asarray(x0)}
            for _ in range(8):
                x = gossip.mix_schedules(x, spec)
            mean = jnp.mean(x["w"], 0, keepdims=True)
            outs[name] = float(jnp.linalg.norm(x["w"] - mean))
        assert outs["exp"] < outs["ring"] * 0.5


class TestShardMapGossip:
    """ppermute path == stacked-gather path, on real (fake-device) meshes."""

    def test_ppermute_matches_schedules(self):
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=0)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(0)
            x = jnp.asarray(r.standard_normal((8, 16, 3)), jnp.float32)

            ref = gossip.mix_schedules({"w": x}, spec)["w"]

            def body(t):
                local = jax.tree.map(lambda a: a[0], t)
                out = gossip.ppermute_mix(local, spec, "client")
                return jax.tree.map(lambda a: a[None], out)

            fn = shard_map(body, mesh, in_specs=(P("client"),),
                           out_specs=P("client"))
            got = jax.jit(fn)(jax.device_put(
                {"w": x}, NamedSharding(mesh, P("client"))))["w"]
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            print("PPERMUTE_OK")
        """)
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, cwd=".")
        assert "PPERMUTE_OK" in out.stdout, out.stdout + out.stderr


class TestFailureAdjustedGossip:
    def test_alive_weight_table_matches_masked_matrix(self):
        """The traced-argument weight table rebuilds mix_dense_masked's
        effective matrix row-for-row (the packed engine's masking math)."""
        ov = topology.expander_overlay(12, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        alive = np.ones(12, np.float32)
        alive[[2, 7]] = 0
        table = np.asarray(gossip.alive_weight_table(spec, jnp.asarray(alive)))
        # scatter the table back into an n x n matrix
        m = np.zeros((12, 12))
        m[np.arange(12), np.arange(12)] += table[:, 0]
        for s, rf in enumerate(spec.recv_from):
            for i, j in enumerate(rf):
                m[i, j] += table[i, 1 + s] if i != j else 0.0
        np.testing.assert_allclose(m.sum(1), 1.0, atol=1e-5)
        assert m[2, 2] == pytest.approx(1.0) and m[7, 7] == pytest.approx(1.0)
        alive_idx = [i for i in range(12) if alive[i]]
        assert np.all(np.abs(m[np.ix_(alive_idx, [2, 7])]) < 1e-7)

    def test_mix_packed_stacked_matches_dense_masked(self):
        """Stacked packed executor (the elastic round's mixing path) ==
        mix_dense_masked for random masks; unmasked == mix_dense."""
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        m = ov.mixing_matrix()
        x = _tree(10, seed=5)
        got = gossip.mix_packed_stacked(x, spec)
        ref = gossip.mix_dense(x, m)
        for k in x:
            np.testing.assert_allclose(got[k], ref[k], rtol=2e-5, atol=2e-5)
        r = np.random.default_rng(0)
        for t in range(4):
            alive = (r.random(10) > 0.3).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            got = gossip.mix_packed_stacked(x, spec, jnp.asarray(alive))
            ref = gossip.mix_dense_masked(x, m, alive)
            for k in x:
                np.testing.assert_allclose(got[k], ref[k],
                                           rtol=2e-5, atol=2e-5)


class TestDelayedGossip:
    """Pipelined (one-round-delayed) mixing: the stacked delayed executor
    against the mix_dense_delayed oracle, and the delay=0 anchors."""

    def test_delayed_stacked_matches_dense_delayed(self):
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        fresh = _tree(10, seed=5)
        prev = _tree(10, seed=6)
        snap = gossip.pack_state_stacked(prev)
        got, new_snap = gossip.mix_packed_stacked_delayed(fresh, snap, spec)
        ref = gossip.mix_dense_delayed(fresh, prev, spec)
        for k in fresh:
            np.testing.assert_allclose(got[k], ref[k], rtol=2e-5, atol=2e-5)
        # the new in-flight state is this round's packed fresh tree
        want = gossip.pack_state_stacked(fresh)
        for a, b in zip(new_snap, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_delayed_composes_with_alive_and_gates(self):
        ov = topology.expander_overlay(12, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        fresh, prev = _tree(12, seed=7), _tree(12, seed=8)
        snap = gossip.pack_state_stacked(prev)
        r = np.random.default_rng(0)
        for t in range(3):
            alive = (r.random(12) > 0.3).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            gates = np.zeros(spec.degree, np.float32)
            gates[t % spec.degree] = 1.0  # one-peer round
            got, _ = gossip.mix_packed_stacked_delayed(
                fresh, snap, spec, jnp.asarray(alive),
                gates=jnp.asarray(gates))
            ref = gossip.mix_dense_delayed(fresh, prev, spec,
                                           jnp.asarray(gates),
                                           jnp.asarray(alive))
            for k in fresh:
                np.testing.assert_allclose(got[k], ref[k],
                                           rtol=2e-5, atol=2e-5)

    def test_self_snapshot_is_bitwise_sync(self):
        """delay=0 anchor: feeding the CURRENT tree as the snapshot must
        reproduce the synchronous stacked executor bit-for-bit (the same
        f32 multiply-add over the same rows)."""
        ov = topology.expander_overlay(8, 4, seed=1)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(8, seed=9)
        got, _ = gossip.mix_packed_stacked_delayed(
            x, gossip.pack_state_stacked(x), spec)
        sync = gossip.mix_packed_stacked(x, spec)
        for k in x:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(sync[k]))

    def test_dense_delayed_with_fresh_equals_sync_oracle(self):
        ov = topology.expander_overlay(10, 4, seed=3)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(10, seed=10)
        got = gossip.mix_dense_delayed(x, x, spec)
        ref = gossip.mix_dense(x, ov.mixing_matrix())
        for k in x:
            np.testing.assert_allclose(got[k], ref[k], rtol=2e-5, atol=2e-5)

    def test_delayed_recursion_reaches_consensus(self):
        """One-round staleness slows mixing but still contracts to
        consensus (the convergence story of asynchronous gossip)."""
        n = 16
        ov = topology.expander_overlay(n, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        r = np.random.default_rng(0)
        x = {"w": jnp.asarray(r.standard_normal((n, 24)), jnp.float32)}
        y = x

        def disagreement(t):
            mean = jnp.mean(t["w"], 0, keepdims=True)
            return float(jnp.linalg.norm(t["w"] - mean))

        d0 = disagreement(x)
        for _ in range(20):
            x, y = gossip.mix_dense_delayed(x, y, spec), x
        assert disagreement(x) < 0.05 * d0


def _check_executors_agree(n, d, seed):
    ov = topology.expander_overlay(n, d, seed=seed)
    spec = gossip.make_gossip_spec(ov)
    x = _tree(n, seed=seed)
    dense = gossip.mix_dense(x, ov.mixing_matrix())
    sched = gossip.mix_schedules(x, spec)
    for k in x:
        np.testing.assert_allclose(dense[k], sched[k], rtol=3e-5, atol=3e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=st.sampled_from([8, 12, 16]), d=st.sampled_from([2, 3, 4]),
           seed=st.integers(0, 500))
    def test_gossip_executors_agree_property(n, d, seed):
        _check_executors_agree(n, d, seed)
else:
    @pytest.mark.parametrize("n,d,seed", [(8, 2, 0), (12, 3, 7), (16, 4, 123),
                                          (16, 2, 31), (12, 4, 255)])
    def test_gossip_executors_agree_property(n, d, seed):
        _check_executors_agree(n, d, seed)
