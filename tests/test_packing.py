"""Packed-gossip subsystem tests: PackSpec round-trips, packed executor parity
vs the dense oracle under shard_map, and the d-collectives-per-round claim
checked in lowered HLO."""
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import gossip, packing, topology

try:  # optional dep (requirements-dev.txt): property tests degrade, not error
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _odd_tree(seed=0):
    """Multi-leaf, odd-shaped, nested — nothing lane-aligned."""
    r = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(r.standard_normal((6, 5)), jnp.float32),
        "b": jnp.asarray(r.standard_normal((11,)), jnp.float32),
        "nested": {"k": jnp.asarray(r.standard_normal((3, 129)), jnp.float32),
                   "scalar": jnp.asarray(float(r.standard_normal()), jnp.float32)},
    }


class TestPackRoundTrip:
    def test_round_trip_exact(self):
        tree = _odd_tree()
        spec = packing.make_pack_spec(tree)
        back = packing.unpack_tree(packing.pack_tree(tree, spec), spec)
        assert jax.tree.structure(back) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_buffers_lane_aligned_and_tiled(self):
        spec = packing.make_pack_spec(_odd_tree())
        for b in range(spec.n_buffers):
            rows, lane = spec.buffer_shape(b)
            assert lane == packing.LANE
            assert rows % spec.block_rows == 0
        assert spec.payload_elements == sum(
            x.size for x in jax.tree.leaves(_odd_tree()))
        assert spec.padded_elements >= spec.payload_elements

    def test_one_buffer_per_dtype(self):
        tree = {"a": jnp.ones((7, 3), jnp.float32),
                "b": jnp.ones((5,), jnp.bfloat16),
                "c": jnp.ones((2, 2), jnp.float32)}
        spec = packing.make_pack_spec(tree)
        assert sorted(spec.buffer_dtypes) == ["bfloat16", "float32"]
        bufs = packing.pack_tree(tree, spec)
        assert [str(x.dtype) for x in bufs] == list(spec.buffer_dtypes)
        back = packing.unpack_tree(bufs, spec)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_spec_static_hashable_and_jittable(self):
        tree = _odd_tree()
        spec = packing.make_pack_spec(tree)
        assert hash(spec) == hash(packing.make_pack_spec(tree))
        # spec closes over a jitted fn (what the train step does)
        fn = jax.jit(lambda t: packing.unpack_tree(
            packing.pack_tree(t, spec), spec))
        back = fn(tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_spec_from_shape_structs_works_on_arrays(self):
        tree = _odd_tree()
        structs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        spec = packing.make_pack_spec(structs)
        back = packing.unpack_tree(packing.pack_tree(tree, spec), spec)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mismatched_tree_rejected(self):
        spec = packing.make_pack_spec(_odd_tree())
        bad = {"only": jnp.ones((4,), jnp.float32)}
        with pytest.raises(ValueError):
            packing.pack_tree(bad, spec)


def _check_round_trip(shapes, seed):
    r = np.random.default_rng(seed)
    tree = {f"l{i}": jnp.asarray(r.standard_normal(s), jnp.float32)
            for i, s in enumerate(shapes)}
    spec = packing.make_pack_spec(tree)
    back = packing.unpack_tree(packing.pack_tree(tree, spec), spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(shapes=st.lists(
        st.lists(st.integers(1, 17), min_size=0, max_size=3).map(tuple),
        min_size=1, max_size=6), seed=st.integers(0, 100))
    def test_pack_round_trip_property(shapes, seed):
        _check_round_trip(shapes, seed)
else:
    @pytest.mark.parametrize("shapes,seed", [
        ([(3, 5), (7,), ()], 0),
        ([(1,), (17, 17, 2), (128,), (129,)], 1),
        ([(8, 16)], 2),
        ([(2, 3, 4), (5,), (6, 1), (1, 1, 1)], 3),
    ])
    def test_pack_round_trip_property(shapes, seed):
        _check_round_trip(shapes, seed)


class TestPackedGossipParity:
    """Packed ppermute executors == mix_dense oracle, on fake-device meshes."""

    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    def test_packed_matches_dense(self):
        self._run("""
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
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32),
                 "n": {"k": jnp.asarray(r.standard_normal((8, 3, 129)),
                                        jnp.float32)}}
            ref = gossip.mix_dense(x, ov.mixing_matrix())

            def body(t):
                local = jax.tree.map(lambda a: a[0], t)
                out = gossip.ppermute_mix_packed(local, spec, "client")
                return jax.tree.map(lambda a: a[None], out)

            specs = jax.tree.map(lambda _: P("client"), x)
            fn = shard_map(body, mesh, in_specs=(specs,), out_specs=specs)
            got = jax.jit(fn)(jax.device_put(
                x, jax.tree.map(lambda _: NamedSharding(mesh, P("client")), x)))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-5, atol=2e-5)
            print("PACKED_PARITY_OK")
        """)

    def test_packed_quantized_within_int8_tolerance(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=1)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(3)
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            ref = gossip.mix_dense(x, ov.mixing_matrix())

            def body(t):
                local = jax.tree.map(lambda a: a[0], t)
                out = gossip.ppermute_mix_packed_quantized(local, spec, "client")
                return jax.tree.map(lambda a: a[None], out)

            specs = jax.tree.map(lambda _: P("client"), x)
            fn = shard_map(body, mesh, in_specs=(specs,), out_specs=specs)
            got = jax.jit(fn)(jax.device_put(
                x, jax.tree.map(lambda _: NamedSharding(mesh, P("client")), x)))
            # int8 error enters via d received payloads, each scaled by the
            # edge weight; scale is per-buffer (buffer-wide amax / 127)
            amax = max(float(jnp.max(jnp.abs(v)))
                       for v in jax.tree.leaves(x))
            bound = 2 * spec.degree * spec.edge_weight * amax / 127.0 + 1e-6
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
                err = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                assert err <= bound, (err, bound)
            print("PACKED_QUANT_OK")
        """)

    def test_packed_matches_per_leaf_on_sharded_leaves(self):
        """Full-manual island semantics: mixing local shards == mixing the
        full tree, with leaves additionally sharded over a second axis."""
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, packing, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((4, 2), ("client", "fsdp"))
            ov = topology.expander_overlay(4, 2, seed=0)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(0)
            x = {"w": jnp.asarray(r.standard_normal((4, 16, 6)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((4, 11)), jnp.float32)}
            ref = gossip.mix_dense(x, ov.mixing_matrix())
            pspecs = {"w": P("client", "fsdp", None), "b": P("client", None)}
            locals_ = {"w": jax.ShapeDtypeStruct((8, 6), jnp.float32),
                       "b": jax.ShapeDtypeStruct((11,), jnp.float32)}
            pack_spec = packing.make_pack_spec(locals_)

            def body(t):
                local = jax.tree.map(lambda a: a[0], t)
                out = gossip.ppermute_mix_packed(local, spec, "client",
                                                 pack_spec=pack_spec)
                return jax.tree.map(lambda a: a[None], out)

            fn = shard_map(body, mesh, in_specs=(pspecs,), out_specs=pspecs)
            got = jax.jit(fn)(jax.device_put(
                x, {k: NamedSharding(mesh, s) for k, s in pspecs.items()}))
            for k in x:
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(ref[k]),
                                           rtol=2e-5, atol=2e-5)
            print("SHARDED_PARITY_OK")
        """)


class TestPackedAliveMaskParity:
    """Failure-aware packed executors == mix_dense_masked oracle, under
    shard_map with the alive mask as a traced argument (f32 + quantized)."""

    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    def test_packed_alive_matches_dense_masked(self):
        self._run("""
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
            m = ov.mixing_matrix()
            r = np.random.default_rng(0)
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            specs = jax.tree.map(lambda _: P("client"), x)
            xs = jax.device_put(x, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), x))

            def body(t, a):
                local = jax.tree.map(lambda v: v[0], t)
                out = gossip.ppermute_mix_packed(local, spec, "client",
                                                 alive=a)
                return jax.tree.map(lambda v: v[None], out)

            fn = jax.jit(shard_map(body, mesh, in_specs=(specs, P()),
                                   out_specs=specs))
            masks = [np.ones(8, np.float32)]  # all-alive: == unmasked mixing
            for t in range(4):                # random masks (>= 2 alive)
                mask = (np.random.default_rng(t).random(8) > 0.35
                        ).astype(np.float32)
                if mask.sum() >= 2:
                    masks.append(mask)
            dead_one = np.ones(8, np.float32); dead_one[3] = 0.0
            masks.append(dead_one)
            for mask in masks:
                ref = gossip.mix_dense_masked(x, m, mask)
                got = fn(xs, jnp.asarray(mask))
                for k in x:
                    np.testing.assert_allclose(np.asarray(got[k]),
                                               np.asarray(ref[k]),
                                               rtol=2e-5, atol=2e-5)
            # all-alive must equal the plain (unmasked) mixing matrix
            ref = gossip.mix_dense(x, m)
            got = fn(xs, jnp.ones(8))
            for k in x:
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(ref[k]),
                                           rtol=2e-5, atol=2e-5)
            # a dead client's row must keep its own params exactly
            got = fn(xs, jnp.asarray(dead_one))
            for k in x:
                np.testing.assert_allclose(np.asarray(got[k][3]),
                                           np.asarray(x[k][3]), rtol=1e-6)
            print("ALIVE_PARITY_OK")
        """)

    def test_packed_quantized_alive_within_int8_tolerance(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=1)
            spec = gossip.make_gossip_spec(ov)
            m = ov.mixing_matrix()
            r = np.random.default_rng(3)
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            specs = jax.tree.map(lambda _: P("client"), x)
            xs = jax.device_put(x, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), x))

            def body(t, a):
                local = jax.tree.map(lambda v: v[0], t)
                out = gossip.ppermute_mix_packed_quantized(
                    local, spec, "client", alive=a)
                return jax.tree.map(lambda v: v[None], out)

            fn = jax.jit(shard_map(body, mesh, in_specs=(specs, P()),
                                   out_specs=specs))
            amax = max(float(jnp.max(jnp.abs(v)))
                       for v in jax.tree.leaves(x))
            # int8 error enters via <= d received payloads; renormalization
            # can scale each weight up to ~2x the unmasked edge weight
            bound = 4 * spec.degree * spec.edge_weight * amax / 127.0 + 1e-6
            mask = np.ones(8, np.float32); mask[2] = 0.0; mask[5] = 0.0
            for alive in (np.ones(8, np.float32), mask):
                ref = gossip.mix_dense_masked(x, m, alive)
                got = fn(xs, jnp.asarray(alive))
                for k in x:
                    err = float(np.max(np.abs(np.asarray(got[k])
                                              - np.asarray(ref[k]))))
                    assert err <= bound, (k, err, bound)
            # dead rows are exact (the identity path never dequantizes)
            got = fn(xs, jnp.asarray(mask))
            for k in x:
                np.testing.assert_allclose(np.asarray(got[k][2]),
                                           np.asarray(x[k][2]), rtol=1e-6)
            print("ALIVE_QUANT_OK")
        """)


class TestBlockScaleQuant:
    """Per-row-block quant scales for the packed wire buffer (the PR-1
    follow-up): fold/split round trip, per-block amax semantics, and the
    error win over the per-buffer scale on heterogeneous buffers."""

    def _hetero_buffer(self, n_blocks=3, small_block=1):
        r = np.random.default_rng(0)
        rows = n_blocks * packing.PACK_BLOCK_ROWS
        buf = np.asarray(r.standard_normal((rows, packing.LANE)), np.float32)
        lo = small_block * packing.PACK_BLOCK_ROWS
        buf[lo:lo + packing.PACK_BLOCK_ROWS] *= 1e-3  # tiny-magnitude tile
        return jnp.asarray(buf)

    def test_fold_split_round_trip_exact(self):
        from repro.kernels.quant_gossip import ops as qops
        buf = self._hetero_buffer()
        q, scales = qops.quantize_packed_blockwise(buf)
        n_blocks = buf.shape[0] // packing.PACK_BLOCK_ROWS
        wire = qops.fold_scales_into_wire(q, scales)
        assert wire.shape == (buf.shape[0] + packing.scale_rows(n_blocks),
                              packing.LANE)
        rq, rs = qops.split_wire_blockwise(wire, n_blocks)
        np.testing.assert_array_equal(np.asarray(rq), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(scales))

    def test_scales_are_per_block_amax(self):
        from repro.kernels.quant_gossip import ops as qops
        buf = self._hetero_buffer()
        _, scales = qops.quantize_packed_blockwise(buf)
        per_block = np.abs(np.asarray(buf)).reshape(
            -1, packing.PACK_BLOCK_ROWS * packing.LANE).max(axis=1) / 127.0
        np.testing.assert_allclose(np.asarray(scales), per_block, rtol=1e-6)
        # the small block's scale must NOT inherit the buffer-wide amax
        assert scales[1] < 1e-2 * scales[0]

    def test_blockwise_chain_parity_and_error_win(self):
        """quantize -> fold -> ship -> split -> dequant-accumulate must
        reconstruct within the per-BLOCK int8 bound; on the small-magnitude
        tile that bound is ~1e3x tighter than the per-buffer scale's."""
        from repro.kernels.quant_gossip import ops as qops
        buf = self._hetero_buffer()
        n_blocks = buf.shape[0] // packing.PACK_BLOCK_ROWS
        acc = jnp.zeros_like(buf)

        q, scales = qops.quantize_packed_blockwise(buf)
        rq, rs = qops.split_wire_blockwise(
            qops.fold_scales_into_wire(q, scales), n_blocks)
        out_block = qops.dequant_accumulate_packed_blockwise(rq, rs, 1.0, acc)
        per_row_bound = np.repeat(np.asarray(scales), packing.PACK_BLOCK_ROWS)
        err = np.abs(np.asarray(out_block) - np.asarray(buf))
        assert (err <= per_row_bound[:, None] * 0.5 + 1e-9).all()

        qb, sb = qops.quantize_packed(buf)
        out_buf = qops.dequant_accumulate_packed(
            *qops.split_wire(qops.fold_scale_into_wire(qb, sb)), 1.0, acc)
        lo = packing.PACK_BLOCK_ROWS
        small = slice(lo, lo + packing.PACK_BLOCK_ROWS)
        err_small_block = err[small].max()
        err_small_buf = np.abs(np.asarray(out_buf) - np.asarray(buf))[small].max()
        assert err_small_block < 1e-2 * err_small_buf, \
            (err_small_block, err_small_buf)

    def test_blockwise_alive_weight_folds_in(self):
        from repro.kernels.quant_gossip import ops as qops
        buf = self._hetero_buffer()
        acc = jnp.asarray(np.random.default_rng(1).standard_normal(
            buf.shape), jnp.float32)
        q, scales = qops.quantize_packed_blockwise(buf)
        got = qops.dequant_accumulate_packed_blockwise(q, scales, 0.25, acc,
                                                       alive=0.5)
        ref = qops.dequant_accumulate_packed_blockwise(q, scales, 0.125, acc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-7)


class TestPackedDelayedGossip:
    """Pipelined shard_map executor == mix_dense_delayed oracle, and its
    delay=0 anchor (self snapshot == synchronous executor, bitwise)."""

    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    def test_delayed_matches_dense_delayed(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, packing, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=0)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(0)
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            prev = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                    "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            locals_ = {"w": jax.ShapeDtypeStruct((6, 5), jnp.float32),
                       "b": jax.ShapeDtypeStruct((11,), jnp.float32)}
            pack_spec = packing.make_pack_spec(locals_)
            snap = gossip.pack_state_stacked(prev, pack_spec)
            specs = jax.tree.map(lambda _: P("client"), x)
            state_specs = tuple(P("client", None, None) for _ in snap)

            def body(t, s, a, g):
                local = jax.tree.map(lambda v: v[0], t)
                s_local = tuple(b[0] for b in s)
                mixed, new_s = gossip.ppermute_mix_packed_delayed(
                    local, s_local, spec, "client", pack_spec=pack_spec,
                    alive=a, gates=g)
                return (jax.tree.map(lambda v: v[None], mixed),
                        tuple(b[None] for b in new_s))

            fn = jax.jit(shard_map(body, mesh,
                                   in_specs=(specs, state_specs, P(), P()),
                                   out_specs=(specs, state_specs)))
            xs = jax.device_put(x, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), x))
            snap_s = jax.device_put(snap, tuple(
                NamedSharding(mesh, P("client")) for _ in snap))
            alive = jnp.asarray([1., 1., 1., 1., 1., 1., 0., 1.], jnp.float32)
            gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
            got, new_state = fn(xs, snap_s, alive, gates)
            ref = gossip.mix_dense_delayed(x, prev, spec, gates, alive)
            for k in x:
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(ref[k]),
                                           rtol=2e-5, atol=2e-5)
            # the emitted state is the fresh pack of this round's tree
            np.testing.assert_array_equal(
                np.asarray(new_state[0]),
                np.asarray(gossip.pack_state_stacked(x, pack_spec)[0]))
            print("DELAYED_PARITY_OK")
        """)

    def test_self_snapshot_is_bitwise_sync(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import gossip, packing, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=1)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(3)
            x = {"w": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32)}
            locals_ = {"w": jax.ShapeDtypeStruct((6, 5), jnp.float32)}
            pack_spec = packing.make_pack_spec(locals_)
            snap = gossip.pack_state_stacked(x, pack_spec)
            specs = jax.tree.map(lambda _: P("client"), x)
            state_specs = tuple(P("client", None, None) for _ in snap)

            def body_delayed(t, s):
                local = jax.tree.map(lambda v: v[0], t)
                mixed, _ = gossip.ppermute_mix_packed_delayed(
                    local, tuple(b[0] for b in s), spec, "client",
                    pack_spec=pack_spec)
                return jax.tree.map(lambda v: v[None], mixed)

            def body_sync(t):
                local = jax.tree.map(lambda v: v[0], t)
                mixed = gossip.ppermute_mix_packed(local, spec, "client",
                                                   pack_spec=pack_spec)
                return jax.tree.map(lambda v: v[None], mixed)

            xs = jax.device_put(x, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), x))
            snap_s = jax.device_put(snap, tuple(
                NamedSharding(mesh, P("client")) for _ in snap))
            got = jax.jit(shard_map(body_delayed, mesh,
                                    in_specs=(specs, state_specs),
                                    out_specs=specs))(xs, snap_s)
            ref = jax.jit(shard_map(body_sync, mesh, in_specs=(specs,),
                                    out_specs=specs))(xs)
            np.testing.assert_array_equal(np.asarray(got["w"]),
                                          np.asarray(ref["w"]))
            print("SELF_SNAPSHOT_OK")
        """)


class TestPackedCollectiveCount:
    @pytest.mark.slow
    def test_packed_train_step_issues_d_permutes(self):
        """The tentpole claim, in lowered HLO: the packed train step issues
        exactly d collective-permutes per gossip round, independent of the
        number of parameter leaves; the per-leaf path issues d x n_leaves.
        The pipelined step (async, delay=1) also ships exactly d — the
        in-flight snapshot replaces the fresh buffer on the wire, it never
        adds collectives — and the async impl at delay=0 must lower to HLO
        *identical* to the synchronous packed step (the bit-identity
        regression anchor)."""
        code = textwrap.dedent("""
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
            cfg = registry.reduced("qwen2.5-3b")  # single-dtype param tree
            shape = ShapeConfig("t", 64, 8, "train")
            counts, texts = {}, {}
            for gi, delay in (("ppermute_packed", 0),
                              ("ppermute_packed_quant", 0),
                              ("ppermute", 0),
                              ("ppermute_packed_async", 0),
                              ("ppermute_packed_async", 1)):
                par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                     grad_accum=2, gossip_impl=gi,
                                     gossip_delay=delay)
                setup = steps.build_train_step(cfg, shape, mesh, par,
                                               DFLConfig(degree=2))
                args = [P.shape_structs(setup.param_struct),
                        setup.input_specs["batch"], setup.input_specs["lr"],
                        setup.input_specs["alive"],
                        setup.input_specs["gates"]]
                if "inflight" in setup.input_specs:
                    args.append(setup.input_specs["inflight"])
                text = setup.step_fn.lower(*args).as_text()
                counts[(gi, delay)] = text.count("collective_permute")
                texts[(gi, delay)] = text
            n_leaves = len(jax.tree.leaves(
                P.shape_structs(setup.param_struct)))
            d = setup.gossip_spec.degree
            assert counts[("ppermute_packed", 0)] == d, counts
            # quant path: the per-block f32 scales are folded into the int8
            # wire buffer, so it too ships exactly d collectives
            assert counts[("ppermute_packed_quant", 0)] == d, counts
            assert counts[("ppermute", 0)] == d * n_leaves, (counts, n_leaves)
            assert counts[("ppermute_packed_async", 1)] == d, counts
            assert (texts[("ppermute_packed_async", 0)]
                    == texts[("ppermute_packed", 0)]), \
                "async delay=0 must lower identically to ppermute_packed"
            print("PERMUTE_COUNT_OK", counts, "d=", d, "leaves=", n_leaves)
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "PERMUTE_COUNT_OK" in out.stdout, out.stdout + out.stderr

    @pytest.mark.slow
    def test_async_train_step_executes_delayed_semantics(self):
        """End-to-end on fake devices: the pipelined production step, run
        with lr=0 (local steps are exact no-ops), must follow the
        mix_dense_delayed recursion over two rounds — round 0 mixes the
        primed snapshot (the initial params), round 1 mixes round 0's."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from repro.configs import registry
            from repro.configs.base import ShapeConfig, ParallelConfig, DFLConfig
            from repro.launch import steps
            from repro.core import gossip
            from repro.models import params as P

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 4), ("data", "model"))
            cfg = registry.reduced("qwen2.5-3b")
            shape = ShapeConfig("t", 64, 8, "train")
            par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                 grad_accum=2,
                                 gossip_impl="ppermute_packed_async",
                                 gossip_delay=1)
            setup = steps.build_train_step(cfg, shape, mesh, par,
                                           DFLConfig(degree=2))
            spec = setup.gossip_spec
            r = np.random.default_rng(0)
            structs = P.shape_structs(setup.param_struct)
            params = jax.tree.map(
                lambda s, sh: jax.device_put(
                    jnp.asarray(r.standard_normal(s.shape) * 0.02, s.dtype),
                    sh), structs, setup.in_shardings[0])
            batch = {k: jnp.zeros(v.shape, v.dtype)
                     for k, v in setup.input_specs["batch"].items()}
            inflight = setup.init_inflight(params)
            x = [jnp.asarray(np.asarray(l, np.float32))
                 for l in jax.tree.leaves(params)]
            y = x
            for t in range(2):
                params, _m, inflight = setup.step_fn(
                    params, batch, jnp.float32(0.0),
                    jnp.ones(setup.n_clients, jnp.float32),
                    jnp.ones(spec.degree, jnp.float32), inflight)
                x, y = gossip.mix_dense_delayed(x, y, spec), x
            got = jax.tree.leaves(params)
            for g, refl in zip(got, x):
                np.testing.assert_allclose(np.asarray(g, np.float32),
                                           np.asarray(refl, np.float32),
                                           rtol=2e-2, atol=2e-2)
            print("ASYNC_EXEC_OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "ASYNC_EXEC_OK" in out.stdout, out.stdout + out.stderr
