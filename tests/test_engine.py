"""GossipEngine (codec x timing x substrate) tests.

The tentpole claims under test:

* every legacy ``gossip_impl`` string parses to exactly one engine cell and
  every legacy executor entry point resolves through
  ``engine.build_gossip_executor`` (no per-variant mixing bodies left);
* the free composition — pipelined + quantized (``delay=1 x int8``) — is
  correct against a ``mix_dense_delayed`` + quantize oracle (incl. alive
  masks and round-plan gates), carries its snapshot in the int8 wire
  format through splice repair, retraces nothing under churn + active
  plans, and ships exactly d int8 collectives per round in lowered HLO.
"""
import re
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, gossip, packing, topology


def _tree(n, seed=0):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.standard_normal((n, 6, 5)), jnp.float32),
            "b": jnp.asarray(r.standard_normal((n, 11)), jnp.float32)}


def _quantize_roundtrip_stacked(tree, codec_name):
    """What the int8 wire does to a snapshot: per-client pack -> quantize ->
    fold -> split -> dequantize -> unpack (the delayed-quant oracle input)."""
    codec = engine.get_codec(codec_name)
    ps = gossip._stacked_pack_spec(tree)
    bufs = jax.vmap(lambda t: packing.pack_tree(t, ps))(tree)
    deq = tuple(
        jax.vmap(lambda x, b=b: codec.decode(
            codec.encode(x, n_blocks=ps.buffer_blocks(b),
                         block_rows=ps.block_rows, impl="auto"),
            x.dtype, n_blocks=ps.buffer_blocks(b),
            block_rows=ps.block_rows))(buf)
        for b, buf in enumerate(bufs))
    return jax.vmap(lambda bs: packing.unpack_tree(bs, ps))(deq)


class TestEngineConfig:
    def test_legacy_impl_alias_table(self):
        """Every legacy gossip_impl string parses to exactly one engine
        cell (the documented alias table)."""
        expect = {
            "dense": ("dense", "f32"),
            "ppermute": ("per_leaf", "f32"),
            "ppermute_quant": ("per_leaf", "int8"),
            "ppermute_packed": ("shard_map", "f32"),
            "ppermute_packed_quant": ("shard_map", "int8_block"),
            "ppermute_packed_async": ("shard_map", "f32"),
        }
        for impl, (substrate, codec) in expect.items():
            cfg = engine.parse_gossip_impl(impl)
            assert (cfg.substrate, cfg.codec, cfg.delay) == (substrate,
                                                             codec, 0)
        # async + delay=1 is the only delayed alias; codec override is how
        # pipelined+quantized is spelled
        cfg = engine.parse_gossip_impl("ppermute_packed_async", 1,
                                       "int8_block")
        assert (cfg.substrate, cfg.codec, cfg.delay) == ("shard_map",
                                                         "int8_block", 1)
        # delay=0 async == ppermute_packed: the SAME hashable config (the
        # textual-HLO-identity anchor is this equality)
        assert (engine.parse_gossip_impl("ppermute_packed_async", 0)
                == engine.parse_gossip_impl("ppermute_packed", 0))

    def test_invalid_cells_rejected(self):
        with pytest.raises(ValueError):
            engine.parse_gossip_impl("nope")
        with pytest.raises(ValueError):
            engine.parse_gossip_impl("ppermute_packed", 1)  # delay needs async
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="per_leaf", delay=1)
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="per_leaf",
                                      codec="int8_block")
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="dense", codec="int8")
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(codec="int7")
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="mesh")

    def test_shard_map_substrate_needs_axis_names(self):
        spec = gossip.make_gossip_spec(topology.ring_overlay(4))
        with pytest.raises(ValueError):
            engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="shard_map"), spec)

    def test_delayed_executor_requires_state(self):
        spec = gossip.make_gossip_spec(topology.ring_overlay(4))
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", delay=1), spec)
        with pytest.raises(ValueError):
            ex(_tree(4))


class TestLegacyEntryPointsResolveThroughEngine:
    """The seven pre-engine executors are aliases of engine cells: stacked
    cells bitwise, and the wrappers carry no mixing bodies of their own."""

    def test_stacked_sync_is_engine_cell(self):
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(10, seed=5)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", codec="f32"),
            spec)
        got = gossip.mix_packed_stacked(x, spec)
        ref = ex(x)
        for k in x:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]))

    def test_stacked_delayed_is_engine_cell(self):
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        fresh, prev = _tree(10, seed=5), _tree(10, seed=6)
        snap = gossip.pack_state_stacked(prev)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", codec="f32",
                                      delay=1), spec)
        got, gsnap = gossip.mix_packed_stacked_delayed(fresh, snap, spec)
        ref, rsnap = ex(fresh, state=snap)
        for k in fresh:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]))
        for a, b in zip(gsnap, rsnap):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_init_state_matches_pack_state_stacked_for_f32(self):
        ov = topology.expander_overlay(8, 4, seed=1)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(8, seed=7)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", delay=1), spec)
        for a, b in zip(ex.init_state(x), gossip.pack_state_stacked(x)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_no_copy_paste_mixing_bodies_left_in_gossip(self):
        """Source-level guard on the refactor's acceptance criterion: the
        seven legacy entry points in core/gossip.py contain no ppermute /
        stack / einsum mixing bodies — they delegate to the engine."""
        import ast
        import inspect
        import textwrap as tw

        for fn in (gossip.ppermute_mix, gossip.ppermute_mix_quantized,
                   gossip.ppermute_mix_packed,
                   gossip.ppermute_mix_packed_quantized,
                   gossip.ppermute_mix_packed_delayed,
                   gossip.mix_packed_stacked,
                   gossip.mix_packed_stacked_delayed):
            fndef = ast.parse(tw.dedent(inspect.getsource(fn))).body[0]
            if (fndef.body and isinstance(fndef.body[0], ast.Expr)
                    and isinstance(fndef.body[0].value, ast.Constant)):
                fndef.body = fndef.body[1:]  # drop the docstring
            src = ast.unparse(fndef)
            assert "build_gossip_executor" in src, fn.__name__
            for marker in ("lax.ppermute", "jnp.stack", "jnp.einsum",
                           "quantize_packed", "dequant_accumulate"):
                assert marker not in src, (fn.__name__, marker)


class TestStackedLeafwiseRound:
    """The stacked f32 sync round gathers and mixes the leaves where they
    are: an f32 multiply-add over each leaf's gathered neighbour rows, with
    no packed buffer and no (n, d+1, ...) stack."""

    @staticmethod
    def _case(n, d, masked, seed=0):
        spec = gossip.make_gossip_spec(topology.expander_overlay(n, d,
                                                                 seed=seed))
        if not masked:
            return spec, {}
        r = np.random.default_rng(seed + n + d)
        alive = (r.random(n) > 0.3).astype(np.float32)
        alive[:2] = 1.0
        gates = np.ones(spec.degree, np.float32)
        gates[r.integers(spec.degree)] = 0.0
        return spec, {"alive": jnp.asarray(alive),
                      "gates": jnp.asarray(gates)}

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("d", [2, 4])
    @pytest.mark.parametrize("n", [8, 12, 32])
    def test_matches_dense_gated_oracle(self, n, d, masked, dtype):
        spec, kw = self._case(n, d, masked)
        x = jax.tree.map(lambda v: v.astype(dtype), _tree(n, seed=n + d))
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        got = jax.jit(lambda t, **k: ex(t, **k))(x, **kw)
        ref = gossip.mix_dense_gated(x, spec, kw.get("gates"),
                                     kw.get("alive"))
        # the oracle sums over senders, the round in schedule order: f32
        # agrees to a few ulp; bf16 to one ulp of the final rounding
        tol = 2e-5 if dtype == "float32" else 1e-2
        for k in x:
            assert got[k].dtype == x[k].dtype
            np.testing.assert_allclose(
                np.asarray(got[k], np.float32), np.asarray(ref[k], np.float32),
                rtol=tol, atol=tol)

    @staticmethod
    def _out_shapes(jaxpr):
        """Every equation's output shape, nested jaxprs included."""
        out = []
        for eqn in jaxpr.eqns:
            out.extend(tuple(v.aval.shape) for v in eqn.outvars)
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        out.extend(TestStackedLeafwiseRound._out_shapes(
                            inner))
        return out

    @pytest.mark.parametrize("masked", [False, True])
    def test_builds_no_stack_and_no_packed_state(self, masked):
        """Structural guard on the traced round: no array of shape
        (n, d+1, ...) and no (n, rows, 128) pack buffer of the state. The
        delayed round, which carries the packed snapshot, shows the guard
        sees such a buffer where one is built."""
        n, d = 12, 4
        spec, kw = self._case(n, d, masked)
        x = _tree(n)

        def shapes(delay):
            ex = engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="stacked", delay=delay),
                spec)
            if delay:
                fn = lambda t, s, **k: ex(t, state=s, **k)  # noqa: E731
                args = (x, ex.init_state(x))
            else:
                fn, args = (lambda t, **k: ex(t, **k)), (x,)
            return self._out_shapes(jax.make_jaxpr(fn)(*args, **kw).jaxpr)

        def packed(s):
            return len(s) == 3 and s[0] == n and s[2] == packing.LANE

        sync = shapes(0)
        assert not [s for s in sync if len(s) > 2 and s[:2] == (n, d + 1)]
        assert not [s for s in sync if packed(s)]
        assert [s for s in shapes(1) if packed(s)]
        text = jax.jit(lambda t, **k: engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)(t, **k)
        ).lower(x, **kw).as_text(debug_info=True)
        assert "dfl.gossip/mix/" in text
        assert not re.search(r'dfl\.gossip/[^"]*pack', text)


class TestStackedQuantCells:
    """int8 codecs on the stacked substrate (the elastic/simulator path)."""

    @pytest.mark.parametrize("codec", ["int8", "int8_block"])
    def test_sync_quant_within_int8_tolerance(self, codec):
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        x = _tree(10, seed=5)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", codec=codec),
            spec)
        got = ex(x)
        ref = gossip.mix_dense(x, ov.mixing_matrix())
        amax = max(float(jnp.max(jnp.abs(v))) for v in jax.tree.leaves(x))
        bound = 2 * spec.degree * spec.edge_weight * amax / 127.0 + 1e-6
        for k in x:
            err = float(np.max(np.abs(np.asarray(got[k])
                                      - np.asarray(ref[k]))))
            assert err <= bound, (k, err, bound)

    @pytest.mark.parametrize("codec", ["int8", "int8_block"])
    def test_delayed_quant_matches_dense_delayed_oracle(self, codec):
        """THE free-composition parity: delayed x int8 == mix_dense_delayed
        on the quantize-roundtripped snapshot (the wire is the only lossy
        element, and it only touches the delayed neighbor payloads)."""
        ov = topology.expander_overlay(10, 4, seed=2)
        spec = gossip.make_gossip_spec(ov)
        fresh, prev = _tree(10, seed=5), _tree(10, seed=6)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", codec=codec,
                                      delay=1), spec)
        state = ex.init_state(prev)
        assert all(str(s.dtype) == "int8" for s in state)
        got, new_state = ex(fresh, state=state)
        prev_deq = _quantize_roundtrip_stacked(prev, codec)
        ref = gossip.mix_dense_delayed(fresh, prev_deq, spec)
        for k in fresh:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(ref[k]),
                                       rtol=2e-5, atol=2e-5)
        # the emitted state is the encoded fresh tree (next round's wire)
        for a, b in zip(new_state, ex.init_state(fresh)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_delayed_quant_composes_with_alive_and_gates(self):
        ov = topology.expander_overlay(12, 4, seed=0)
        spec = gossip.make_gossip_spec(ov)
        fresh, prev = _tree(12, seed=7), _tree(12, seed=8)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      codec="int8_block", delay=1), spec)
        state = ex.init_state(prev)
        prev_deq = _quantize_roundtrip_stacked(prev, "int8_block")
        r = np.random.default_rng(0)
        for t in range(3):
            alive = (r.random(12) > 0.3).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            gates = np.zeros(spec.degree, np.float32)
            gates[t % spec.degree] = 1.0  # one-peer round
            got, _ = ex(fresh, state=state, alive=jnp.asarray(alive),
                        gates=jnp.asarray(gates))
            ref = gossip.mix_dense_delayed(fresh, prev_deq, spec,
                                           jnp.asarray(gates),
                                           jnp.asarray(alive))
            for k in fresh:
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(ref[k]),
                                           rtol=2e-5, atol=2e-5)

    def test_blockwise_beats_per_buffer_on_heterogeneous_tree(self):
        """The int8_block codec's reason to exist, at engine level: a tiny-
        magnitude leaf mixed next to a large one keeps its precision."""
        ov = topology.expander_overlay(8, 4, seed=1)
        spec = gossip.make_gossip_spec(ov)
        r = np.random.default_rng(3)
        # "big" fills the first two (256, 128) tiles exactly, so "small"
        # (~1e-3 magnitudes, a norm-gain run) owns its own tile and its
        # block scale cannot inherit big's amax
        x = {"big": jnp.asarray(r.standard_normal((8, 512, 128)),
                                jnp.float32),
             "small": jnp.asarray(r.standard_normal((8, 256, 128)) * 1e-3,
                                  jnp.float32)}
        ref = gossip.mix_dense(x, ov.mixing_matrix())
        errs = {}
        for codec in ("int8", "int8_block"):
            ex = engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="stacked", codec=codec),
                spec)
            got = ex(x)
            errs[codec] = float(np.max(np.abs(np.asarray(got["small"])
                                              - np.asarray(ref["small"]))))
        assert errs["int8_block"] < 1e-2 * errs["int8"], errs


class TestPipelinedQuantElastic:
    """The composition on the elastic runtime: zero retraces under churn +
    active plans, and the int8 snapshot follows survivors through repair."""

    def _trainer(self, n, **kw):
        from repro.core import dfedavg
        from repro.launch.elastic import ElasticTrainer

        def quad_loss(params, batch):
            return jnp.mean(jnp.square(params["w"] - batch["target"])), {}

        return ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0),
            **kw)

    @staticmethod
    def _batches(targets, k):
        return {"target": jnp.broadcast_to(
            targets[:, None], (targets.shape[0], k, targets.shape[1]))}

    def test_pipelined_quant_zero_retrace_under_churn_and_plan(self):
        from repro.overlay.plan import OnePeerPlan

        n, dim = 10, 3
        trainer = self._trainer(n, straggler_rounds=1, failure_rounds=99,
                                engine=engine.GossipEngineConfig(
                                    substrate="stacked", codec="int8_block",
                                    delay=1),
                                plan=OnePeerPlan())
        params = {"w": jnp.ones((n, dim))}
        targets = jnp.zeros((n, dim))
        rng = np.random.default_rng(0)
        for rnd in range(8):
            alive = (rng.random(n) > 0.3).astype(np.float32)
            if rnd == 3:
                alive[:] = 1.0
            params, _, old2new = trainer.observe_heartbeats(alive, params)
            assert old2new is None
            params, _ = trainer.step(params, self._batches(targets, 1), 0.2)
        assert trainer.n_traces == 1, trainer.n_traces
        assert all(str(b.dtype) == "int8" for b in trainer._inflight)

    def test_int8_snapshot_survives_repair_remap(self):
        """repair_and_remap compacts the int8 wire snapshot by the same
        old2new row permutation as the params (byte-exact rows)."""
        n, dim = 12, 4
        r = np.random.default_rng(1)
        targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
        trainer = self._trainer(n, straggler_rounds=1, failure_rounds=2,
                                engine=engine.GossipEngineConfig(
                                    substrate="stacked", codec="int8_block",
                                    delay=1))
        params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
        params, _ = trainer.step(params, self._batches(targets, 1), 0.1)
        alive = np.ones(n)
        alive[5] = 0
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is None                    # straggler, not dead yet
        params, _ = trainer.step(params, self._batches(targets, 1), 0.1)
        pre = [np.asarray(b) for b in trainer._inflight]
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is not None and old2new[5] == -1
        survivors = np.arange(n) != 5
        for b_pre, b_post in zip(pre, trainer._inflight):
            assert str(np.asarray(b_post).dtype) == "int8"
            np.testing.assert_array_equal(np.asarray(b_post),
                                          b_pre[survivors])
        surv_targets = jnp.concatenate([targets[:5], targets[6:]])
        params, _ = trainer.step(params, self._batches(surv_targets, 1), 0.1)
        assert params["w"].shape[0] == n - 1
        assert bool(jnp.isfinite(params["w"]).all())
        assert trainer.n_traces == 2              # one re-jit per membership

    def test_pipelined_quant_tracks_f32_pipeline(self):
        """Convergence sanity: delayed int8 follows delayed f32 to the same
        consensus neighborhood (the wire error is bounded by the scales)."""
        n, dim = 10, 16
        r = np.random.default_rng(2)
        targets = jnp.zeros((n, dim))
        finals = {}
        for codec in ("f32", "int8_block"):
            trainer = self._trainer(n, straggler_rounds=1,
                                    failure_rounds=99,
                                    engine=engine.GossipEngineConfig(
                                        substrate="stacked", codec=codec,
                                        delay=1))
            params = {"w": jnp.asarray(r.standard_normal((n, dim)),
                                       jnp.float32)}
            for _ in range(12):
                params, _, _ = trainer.observe_heartbeats(np.ones(n), params)
                params, _ = trainer.step(params, self._batches(targets, 2),
                                         0.3)
            finals[codec] = float(jnp.mean(jnp.square(params["w"])))
        assert finals["int8_block"] <= 4 * finals["f32"] + 1e-4, finals


class TestShardMapPipelinedQuant:
    """The production composition under shard_map on fake devices."""

    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    def test_delayed_quant_matches_dense_delayed_oracle(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import engine, gossip, packing, topology
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
            ex = engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="shard_map",
                                          codec="int8_block", delay=1),
                spec, axis_names="client", pack_spec=pack_spec)
            specs = jax.tree.map(lambda _: P("client"), x)
            sspecs = tuple(P("client", None, None)
                           for _ in ex.state_structs())

            def init_body(t):
                local = jax.tree.map(lambda a: a[0], t)
                return tuple(b[None] for b in ex.init_state(local))

            def body(t, s, a, g):
                local = jax.tree.map(lambda v: v[0], t)
                mixed, new_s = ex(local, state=tuple(b[0] for b in s),
                                  alive=a, gates=g)
                return (jax.tree.map(lambda v: v[None], mixed),
                        tuple(b[None] for b in new_s))

            put = lambda t: jax.device_put(t, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), t))
            snap = jax.jit(shard_map(init_body, mesh, in_specs=(specs,),
                                     out_specs=sspecs))(put(prev))
            assert all(str(b.dtype) == "int8" for b in snap)
            alive = jnp.asarray([1., 1., 1., 1., 1., 1., 0., 1.], jnp.float32)
            gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
            fn = jax.jit(shard_map(body, mesh,
                                   in_specs=(specs, sspecs, P(), P()),
                                   out_specs=(specs, sspecs)))
            got, new_state = fn(put(x), snap, alive, gates)

            # oracle: mix_dense_delayed on the quantize-roundtripped snapshot
            codec = ex.codec
            ps = gossip._stacked_pack_spec(prev)
            bufs = jax.vmap(lambda t: packing.pack_tree(t, ps))(prev)
            deq = tuple(jax.vmap(lambda z, b=b: codec.decode(
                codec.encode(z, n_blocks=ps.buffer_blocks(b),
                             block_rows=ps.block_rows, impl="auto"),
                z.dtype, n_blocks=ps.buffer_blocks(b),
                block_rows=ps.block_rows))(buf)
                for b, buf in enumerate(bufs))
            prev_deq = jax.vmap(lambda bs: packing.unpack_tree(bs, ps))(deq)
            ref = gossip.mix_dense_delayed(x, prev_deq, spec, gates, alive)
            for k in x:
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(ref[k]),
                                           rtol=2e-5, atol=2e-5)
            print("SHARD_MAP_DELAYED_QUANT_OK")
        """)


class TestProductionPipelinedQuantStep:
    @pytest.mark.slow
    def test_async_quant_step_ships_d_int8_collectives(self):
        """Acceptance, in lowered HLO: gossip_impl='ppermute_packed_async' +
        gossip_delay=1 + gossip_codec='int8_block' ships exactly d
        collective-permutes per round and every one of them carries the int8
        wire buffer; the in-flight donated state is the int8 wire; and the
        sync f32 async config still lowers textually identical to
        ppermute_packed (no drift from the codec plumbing)."""
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
            cfg = registry.reduced("qwen2.5-3b")
            shape = ShapeConfig("t", 64, 8, "train")
            texts = {}
            for gi, delay, codec in (("ppermute_packed", 0, "auto"),
                                     ("ppermute_packed_async", 0, "auto"),
                                     ("ppermute_packed_async", 1, "int8"),
                                     ("ppermute_packed_async", 1, "int8_block")):
                par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                     grad_accum=2, gossip_impl=gi,
                                     gossip_delay=delay, gossip_codec=codec)
                setup = steps.build_train_step(cfg, shape, mesh, par,
                                               DFLConfig(degree=2))
                args = [P.shape_structs(setup.param_struct),
                        setup.input_specs["batch"], setup.input_specs["lr"],
                        setup.input_specs["alive"],
                        setup.input_specs["gates"]]
                if "inflight" in setup.input_specs:
                    args.append(setup.input_specs["inflight"])
                    assert all(str(s.dtype) == "int8"
                               for s in setup.input_specs["inflight"])
                texts[(gi, delay, codec)] = setup.step_fn.lower(
                    *args).as_text()
            d = setup.gossip_spec.degree
            for key, text in texts.items():
                perms = [l for l in text.splitlines()
                         if "collective_permute" in l]
                assert len(perms) == d, (key, len(perms), d)
                if key[2] in ("int8", "int8_block"):
                    # every shipped buffer is the int8 wire
                    assert all("xi8>" in l for l in perms), key
            assert (texts[("ppermute_packed_async", 0, "auto")]
                    == texts[("ppermute_packed", 0, "auto")]), \\
                "async delay=0 must still lower identically to ppermute_packed"
            print("ASYNC_QUANT_HLO_OK d=", d)
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert "ASYNC_QUANT_HLO_OK" in out.stdout, out.stdout + out.stderr


class TestByzantineScreens:
    """The fourth engine layer: screen ("none" | "norm_clip" |
    "trimmed_mean") composes with codec x timing x substrate through the
    config alone — no new executors, the screen="none" paths byte-identical
    to the pre-screen engine."""

    def test_screen_config_validation(self):
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(screen="median")
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="dense", screen="norm_clip")
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="per_leaf",
                                      screen="trimmed_mean")
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(screen="norm_clip", clip_tau=0.0)
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(screen="trimmed_mean", trim_f=-1)
        cfg = engine.parse_gossip_impl("ppermute_packed", screen="norm_clip",
                                       clip_tau=2.5)
        assert (cfg.screen, cfg.clip_tau) == ("norm_clip", 2.5)

    def test_telemetry_needs_packed_substrate(self):
        from repro.telemetry import TelemetryConfig
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="dense",
                                      telemetry=TelemetryConfig())
        with pytest.raises(ValueError):
            engine.GossipEngineConfig(substrate="per_leaf",
                                      telemetry=TelemetryConfig())
        # the metrics-only blocked cell is legal (TELEMETRY_SUBSTRATES)
        cfg = engine.GossipEngineConfig(substrate="blocked", block=2,
                                        telemetry=TelemetryConfig())
        assert cfg.telemetry is not None
        cfg = engine.parse_gossip_impl("ppermute_packed",
                                       telemetry=TelemetryConfig())
        assert cfg.telemetry == TelemetryConfig()

    def test_norm_clip_identity_at_large_tau_is_bitwise(self):
        """When no sender exceeds tau x the receiver's own norm, every clip
        factor is 1.0 and the screened stacked f32 round is BITWISE equal
        to the unscreened one (incl. alive + gates)."""
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        ex0 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        exc = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      screen="norm_clip", clip_tau=1e6), spec)
        for kw in ({},
                   {"alive": jnp.asarray(np.r_[np.ones(7), 0, 1, 1],
                                         jnp.float32),
                    "gates": jnp.asarray([1., 0., 1., 1.], jnp.float32)}):
            a0, ac = ex0(x, **kw), exc(x, **kw)
            for k in x:
                np.testing.assert_array_equal(np.asarray(a0[k]),
                                              np.asarray(ac[k]))

    def test_norm_clip_screens_attacker_and_counts_clips(self):
        """A huge sender is rescaled to tau x the receiver's own norm
        (whole-model norms, all pack buffers) and the per-sender clip
        telemetry counts exactly its live receivers."""
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        xa = jax.tree.map(lambda v: v.at[3].mul(1e4), x)
        ex0 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        from repro.telemetry import metrics as telemetry_metrics
        exc = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      screen="norm_clip", clip_tau=3.0,
                                      telemetry=telemetry_metrics.clip_only()),
            spec)
        got, stats = exc(xa)
        plain = ex0(xa)
        # the attacker's OWN row keeps its huge self-term by design —
        # screens defend receivers, not the attacker
        others = np.arange(10) != 3
        mx_scr = max(float(jnp.max(jnp.abs(got[k][others]))) for k in x)
        mx_pl = max(float(jnp.max(jnp.abs(plain[k][others]))) for k in x)
        assert mx_scr < mx_pl / 50, (mx_scr, mx_pl)
        counts = np.asarray(stats["clipped"])
        in_deg = sum((np.asarray(rf) == 3) & np.asarray(m).astype(bool)
                     for rf, m in zip(spec.recv_from, spec.live_masks))
        assert counts[3] == int(np.sum(in_deg)), (counts, np.sum(in_deg))
        assert counts.sum() == counts[3], counts

    def test_trimmed_f0_is_renormalized_mean(self):
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        ex0 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        ext = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      screen="trimmed_mean", trim_f=0), spec)
        gt, pl = ext(x), ex0(x)
        for k in x:
            np.testing.assert_allclose(np.asarray(gt[k]), np.asarray(pl[k]),
                                       rtol=3e-6, atol=3e-6)

    def test_trimmed_matches_ref_oracle_with_alive_and_gates(self):
        """Engine trimmed cell == vmapped ref.trimmed_mix over the packed
        stack with the raw/contrib weight tables (dead senders and gated
        schedules excluded from the order statistics)."""
        from repro.kernels.gossip_mix import ref as mix_ref
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        alive = jnp.asarray(np.r_[np.ones(7), 0, 1, 1], jnp.float32)
        gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
        ext = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      screen="trimmed_mean", trim_f=1), spec)
        gt = ext(x, alive=alive, gates=gates)
        ps = gossip._stacked_pack_spec(x)
        bufs = jax.vmap(lambda t: packing.pack_tree(t, ps))(x)
        raw, contrib = gossip.raw_contrib_tables(spec, alive, gates)
        u = jnp.maximum(raw, 0.0) * contrib
        lv = (contrib > 0.0).astype(jnp.float32)
        outs = []
        for buf in bufs:
            stack = jnp.stack([buf] + [jnp.take(buf, jnp.asarray(rf), axis=0)
                                       for rf in spec.recv_from], axis=1)
            outs.append(jax.vmap(
                lambda st, uu, ll: mix_ref.trimmed_mix(st, uu, ll, 1)
            )(stack, u, lv))
        ref = jax.vmap(lambda bs: packing.unpack_tree(bs, ps))(tuple(outs))
        for k in x:
            np.testing.assert_allclose(np.asarray(gt[k]), np.asarray(ref[k]),
                                       rtol=1e-6, atol=1e-6)

    def test_trimmed_neutralizes_sign_flip_where_mean_is_poisoned(self):
        """Deviation-from-clean-round on receivers whose attacker
        in-multiplicity is <= trim: the trimmed cell stays near the clean
        round while the plain mean is dragged by the attacker. (A receiver
        fed the same attacker on two schedules needs trim >= 2 — the
        order-statistics contract, asserted via the multiplicity filter.)"""
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        xa = jax.tree.map(lambda v: v.at[3].mul(-50.0), x)
        ex0 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        ext = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      screen="trimmed_mean", trim_f=1), spec)
        mult = sum(((np.asarray(rf) == 3) & np.asarray(m).astype(bool))
                   .astype(int)
                   for rf, m in zip(spec.recv_from, spec.live_masks))
        recv = np.where(mult == 1)[0]
        err_t = max(float(jnp.max(jnp.abs(ext(xa)[k][recv] - ext(x)[k][recv])))
                    for k in x)
        err_p = max(float(jnp.max(jnp.abs(ex0(xa)[k][recv] - ex0(x)[k][recv])))
                    for k in x)
        assert err_t < err_p / 10, (err_t, err_p)

    @pytest.mark.parametrize("codec", ["int8", "int8_block"])
    def test_int8_trimmed_decodes_within_quant_tolerance(self, codec):
        """The dequant-side trimmed kernel (int8 wire decoded inside the
        fused trim pass) tracks the f32 trimmed cell within the wire's
        quantization error."""
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        alive = jnp.asarray(np.r_[np.ones(7), 0, 1, 1], jnp.float32)
        gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
        exf = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked",
                                      screen="trimmed_mean", trim_f=1), spec)
        exq = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", codec=codec,
                                      screen="trimmed_mean", trim_f=1), spec)
        gf = exf(x, alive=alive, gates=gates)
        gq = exq(x, alive=alive, gates=gates)
        for k in x:
            np.testing.assert_allclose(np.asarray(gq[k]), np.asarray(gf[k]),
                                       rtol=5e-2, atol=5e-2)

    def test_screens_compose_with_delay(self):
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        alive = jnp.asarray(np.r_[np.ones(7), 0, 1, 1], jnp.float32)
        for codec in ("f32", "int8_block"):
            for screen, kw in (("norm_clip", dict(clip_tau=3.0)),
                               ("trimmed_mean", dict(trim_f=1))):
                ex = engine.build_gossip_executor(
                    engine.GossipEngineConfig(substrate="stacked",
                                              codec=codec, delay=1,
                                              screen=screen, **kw), spec)
                st = ex.init_state(_tree(10, seed=6))
                mixed, new_st = ex(x, state=st, alive=alive)
                assert all(bool(jnp.isfinite(v).all())
                           for v in mixed.values()), (codec, screen)


class TestShardMapScreens:
    """Screened cells on the production shard_map substrate, vs their
    stacked twins (whole-model norm_clip needed a two-phase shard_map
    round; trimmed excludes fixed-point deliveries, which arrive as zeros
    on the wire there)."""

    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    @pytest.mark.slow
    def test_shard_map_screens_match_stacked_twins(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import engine, gossip, packing, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=0)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(9)
            x = {"a": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            xa = jax.tree.map(lambda v: v.at[2].mul(-30.0), x)  # attacker
            alive = jnp.asarray([1., 1., 1., 0., 1., 1., 1., 1.], jnp.float32)
            gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
            locals_ = {"a": jax.ShapeDtypeStruct((6, 5), jnp.float32),
                       "b": jax.ShapeDtypeStruct((11,), jnp.float32)}
            pspec = packing.make_pack_spec(locals_)
            specs = jax.tree.map(lambda _: P("client"), x)
            put = lambda t: jax.device_put(t, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), t))
            for codec in ("f32", "int8_block"):
                for screen, kw in (("norm_clip", dict(clip_tau=3.0)),
                                   ("trimmed_mean", dict(trim_f=1))):
                    exs = engine.build_gossip_executor(
                        engine.GossipEngineConfig(substrate="shard_map",
                                                  codec=codec, screen=screen,
                                                  **kw),
                        spec, axis_names="client", pack_spec=pspec)
                    exst = engine.build_gossip_executor(
                        engine.GossipEngineConfig(substrate="stacked",
                                                  codec=codec, screen=screen,
                                                  **kw), spec)

                    def body(t, a, g):
                        local = jax.tree.map(lambda v: v[0], t)
                        mixed = exs(local, alive=a, gates=g)
                        return jax.tree.map(lambda v: v[None], mixed)

                    fn = jax.jit(shard_map(body, mesh,
                                           in_specs=(specs, P(), P()),
                                           out_specs=specs))
                    got = fn(put(xa), alive, gates)
                    ref = exst(xa, alive=alive, gates=gates)
                    tol = 1e-6 if codec == "f32" else 5e-2
                    for k in x:
                        np.testing.assert_allclose(
                            np.asarray(got[k]), np.asarray(ref[k]),
                            rtol=tol, atol=tol)
            print("SHARD_MAP_SCREENS_OK")
        """)

    @pytest.mark.slow
    def test_screened_byzantine_step_ships_d_collectives(self):
        """Acceptance, in lowered HLO: every screened cell of the
        production step — with the Byzantine attack operands threaded —
        still ships exactly d collective-permutes per round."""
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
            for gi, screen in (("ppermute_packed", "norm_clip"),
                               ("ppermute_packed", "trimmed_mean"),
                               ("ppermute_packed_quant", "norm_clip"),
                               ("ppermute_packed_quant", "trimmed_mean")):
                par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                     grad_accum=2, gossip_impl=gi,
                                     gossip_screen=screen, gossip_trim_f=1)
                setup = steps.build_train_step(cfg, shape, mesh, par,
                                               DFLConfig(degree=2,
                                                         byzantine=True))
                assert "attack" in setup.input_specs
                args = [P.shape_structs(setup.param_struct),
                        setup.input_specs["batch"], setup.input_specs["lr"],
                        setup.input_specs["alive"],
                        setup.input_specs["gates"],
                        setup.input_specs["attack"],
                        setup.input_specs["attack_key"]]
                text = setup.step_fn.lower(*args).as_text()
                perms = [l for l in text.splitlines()
                         if "collective_permute" in l]
                d = setup.gossip_spec.degree
                assert len(perms) == d, (gi, screen, len(perms), d)
            print("SCREENED_STEP_HLO_OK")
        """)


class TestChebyshevMultiRound:
    """Chebyshev-accelerated multi-round gossip (sub_rounds = k > 1, the
    second timing axis): config validation, the traced (k,) coefficient
    operand contract, the stacked cell vs the dense ``chebyshev_mix``
    oracle (incl. alive masks + gates + dead-client identity), consensus
    acceleration over plain repetition on the ring, k-fold wire
    accounting, zero retraces under varying coefficients x churn x gates,
    and — in the slow lane — the shard_map twin plus the production-step
    anchors (exactly k*d collective-permutes; sub_rounds=1 lowers
    textually identical to the sync engine)."""

    def test_cheby_config_validation(self):
        with pytest.raises(ValueError, match="sub_rounds"):
            engine.GossipEngineConfig(sub_rounds=0)
        with pytest.raises(ValueError, match="sub_rounds"):
            engine.GossipEngineConfig(sub_rounds=1.5)
        for substrate, kw in (("dense", {}), ("per_leaf", {}),
                              ("blocked", dict(block=4))):
            with pytest.raises(ValueError, match="sub_rounds > 1"):
                engine.GossipEngineConfig(substrate=substrate,
                                          sub_rounds=2, **kw)
        with pytest.raises(ValueError, match="synchronous"):
            engine.GossipEngineConfig(substrate="stacked", delay=1,
                                      sub_rounds=2)
        for screen in ("norm_clip", "trimmed_mean"):
            with pytest.raises(ValueError, match="screen"):
                engine.GossipEngineConfig(substrate="stacked",
                                          screen=screen, sub_rounds=2)
        with pytest.raises(ValueError, match="stateful"):
            engine.GossipEngineConfig(substrate="stacked", codec="topk_ef",
                                      sub_rounds=2)
        # the same cells stay legal at k=1 (the sync engine) and the
        # stateless codecs compose at k>1
        engine.GossipEngineConfig(substrate="stacked", screen="norm_clip")
        engine.GossipEngineConfig(substrate="stacked", codec="topk_ef")
        engine.GossipEngineConfig(substrate="stacked", codec="int8_block",
                                  sub_rounds=3)

    def test_cheby_operand_contract(self):
        spec = gossip.make_gossip_spec(topology.ring_overlay(8))
        x = _tree(8)
        ex2 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", sub_rounds=2),
            spec)
        with pytest.raises(ValueError, match="cheby"):
            ex2(x)  # k > 1 needs the (k,) coefficient operand
        ex1 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        with pytest.raises(ValueError, match="cheby"):
            ex1(x, cheby=jnp.ones((1,), jnp.float32))  # k = 1 must not
        om = ex2.cheby_coeffs()
        assert om.shape == (2,) and om.dtype == np.float32
        assert om[0] == 1.0  # the first sub-round IS the plain mix

    @pytest.mark.parametrize("k", [2, 3])
    def test_stacked_cheby_matches_dense_oracle(self, k):
        from repro.core import mixing
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=5)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", sub_rounds=k),
            spec)
        om = ex.cheby_coeffs()
        alive = jnp.asarray(np.r_[np.ones(7), 0, 1, 1], jnp.float32)
        gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
        for kw in ({}, {"alive": alive}, {"alive": alive, "gates": gates}):
            got = ex(x, cheby=jnp.asarray(om), **kw)
            m = np.asarray(gossip.gated_mixing_matrix(
                spec, kw.get("gates"), kw.get("alive")))
            for key in x:
                ref = mixing.chebyshev_mix(np.asarray(x[key]), m, om)
                np.testing.assert_allclose(np.asarray(got[key]), ref,
                                           rtol=2e-5, atol=2e-5)
        # a dead client's identity row survives the whole recurrence
        # bit-for-bit: y == x^(j) makes every x^(j+1) collapse to x^(0)
        got = ex(x, cheby=jnp.asarray(om), alive=alive)
        for key in x:
            np.testing.assert_array_equal(np.asarray(got[key][7]),
                                          np.asarray(x[key][7]))

    def test_cheby_beats_plain_repetition_on_the_ring(self):
        from repro.core import spectral
        spec = gossip.make_gossip_spec(topology.ring_overlay(8))
        x = _tree(8, seed=1)
        ex1 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked"), spec)
        ex2 = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", sub_rounds=2),
            spec)
        # theory: 1/T_2(1/lam) < lam^2 whenever 0 < lam < 1
        assert spectral.chebyshev_lambda(spec.lam, 2) < spec.lam ** 2

        def resid(t):
            return sum(float(jnp.sum(jnp.square(
                v - v.mean(axis=0, keepdims=True)))) for v in t.values())

        cheb = ex2(x, cheby=jnp.asarray(ex2.cheby_coeffs()))
        plain = ex1(ex1(x))  # same wire budget: two plain applications
        assert resid(cheb) < resid(plain) < resid(x)

    def test_wire_bytes_multiply_by_sub_rounds(self):
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10)
        pack = packing.make_stacked_pack_spec(
            jax.tree.map(lambda v: v[0], x))
        wires = {}
        for k in (1, 2, 3):
            ex = engine.build_gossip_executor(
                engine.GossipEngineConfig(substrate="shard_map",
                                          sub_rounds=k),
                spec, axis_names="client", pack_spec=pack)
            wires[k] = ex.wire_bytes_per_round()
        assert wires[1] > 0
        assert wires[2] == 2 * wires[1] and wires[3] == 3 * wires[1]

    def test_varying_coefficients_churn_gates_zero_retraces(self):
        from repro.telemetry import TraceCounter
        spec = gossip.make_gossip_spec(
            topology.expander_overlay(10, 4, seed=2))
        x = _tree(10, seed=3)
        ex = engine.build_gossip_executor(
            engine.GossipEngineConfig(substrate="stacked", sub_rounds=2),
            spec)
        fn = jax.jit(lambda t, a, g, c: ex(t, alive=a, gates=g, cheby=c))
        r = np.random.default_rng(0)
        for t in range(4):
            alive = (r.random(10) > 0.3).astype(np.float32)
            if alive.sum() < 2:
                alive[:] = 1
            gates = (np.arange(4) != t % 4).astype(np.float32)
            cheby = jnp.asarray([1.0, 1.0 + 0.1 * t], jnp.float32)
            x = fn(x, jnp.asarray(alive), jnp.asarray(gates), cheby)
        assert TraceCounter.cache_size(fn) == 1
        assert all(bool(jnp.isfinite(v).all()) for v in x.values())

    def test_elastic_trainer_sub_rounds_composes_with_telemetry(self):
        from repro.core import dfedavg
        from repro.launch.elastic import ElasticTrainer
        from repro.overlay import plan as plan_lib
        from repro.telemetry import TelemetryConfig
        n = 12
        tr = ElasticTrainer(
            overlay=topology.expander_overlay(n, 4, seed=0),
            loss_fn=lambda p, b: (jnp.mean(jnp.square(p["w"] - b["t"])),
                                  {}),
            dcfg=dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2,
                                        momentum=0.9),
            plan=plan_lib.OnePeerPlan(),
            engine=engine.GossipEngineConfig(
                substrate="stacked", sub_rounds=2,
                telemetry=TelemetryConfig()))
        params = {"w": jnp.asarray(
            np.random.default_rng(1).standard_normal((n, 16)), jnp.float32)}
        r = np.random.default_rng(0)
        for rnd in range(4):
            alive = (r.random(n) > 0.2).astype(np.float32)
            params, _, _ = tr.observe_heartbeats(alive, params)
            params, _ = tr.step(
                params, {"t": jnp.zeros((n, 2, 16), jnp.float32)}, 0.2)
        assert tr.n_traces == 1  # coefficients + churn + gates are data
        # telemetry composes: metrics measure the FIRST sub-round only, so
        # they stay comparable across the sub_rounds axis
        assert set(tr.last_metrics) == {"resid_sqnorm", "in_degree",
                                        "sched_contrib"}
        assert np.isfinite(np.asarray(params["w"])).all()


class TestChebyshevSlowLane:
    def _run(self, code):
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, cwd=".")
        assert "OK" in out.stdout, out.stdout + out.stderr

    @pytest.mark.slow
    def test_shard_map_cheby_matches_oracle_and_ships_kd_permutes(self):
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import engine, gossip, mixing, packing, topology
            from repro.launch.mesh import make_mesh, shard_map

            mesh = make_mesh((8,), ("client",))
            ov = topology.expander_overlay(8, 4, seed=0)
            spec = gossip.make_gossip_spec(ov)
            r = np.random.default_rng(9)
            x = {"a": jnp.asarray(r.standard_normal((8, 6, 5)), jnp.float32),
                 "b": jnp.asarray(r.standard_normal((8, 11)), jnp.float32)}
            alive = jnp.asarray([1., 1., 1., 0., 1., 1., 1., 1.], jnp.float32)
            gates = jnp.asarray([1., 0., 1., 1.], jnp.float32)
            locals_ = {"a": jax.ShapeDtypeStruct((6, 5), jnp.float32),
                       "b": jax.ShapeDtypeStruct((11,), jnp.float32)}
            pspec = packing.make_pack_spec(locals_)
            specs = jax.tree.map(lambda _: P("client"), x)
            put = lambda t: jax.device_put(t, jax.tree.map(
                lambda _: NamedSharding(mesh, P("client")), t))
            for k in (2, 3):
                ex = engine.build_gossip_executor(
                    engine.GossipEngineConfig(substrate="shard_map",
                                              sub_rounds=k),
                    spec, axis_names="client", pack_spec=pspec)
                om = ex.cheby_coeffs()

                def body(t, a, g, c):
                    local = jax.tree.map(lambda v: v[0], t)
                    mixed = ex(local, alive=a, gates=g, cheby=c)
                    return jax.tree.map(lambda v: v[None], mixed)

                fn = jax.jit(shard_map(body, mesh,
                                       in_specs=(specs, P(), P(), P()),
                                       out_specs=specs))
                args = (put(x), alive, gates, jnp.asarray(om))
                got = fn(*args)
                m = np.asarray(gossip.gated_mixing_matrix(spec, gates,
                                                          alive))
                for key in x:
                    ref = mixing.chebyshev_mix(np.asarray(x[key]), m, om)
                    np.testing.assert_allclose(np.asarray(got[key]), ref,
                                               rtol=2e-5, atol=2e-5)
                text = fn.lower(*args).as_text()
                perms = [l for l in text.splitlines()
                         if "collective_permute" in l]
                assert len(perms) == k * spec.degree, (k, len(perms))
            print("SHARD_MAP_CHEBY_OK")
        """)

    @pytest.mark.slow
    def test_production_step_ships_kd_permutes_and_k1_identity(self):
        """Acceptance, in lowered HLO on the production step: sub_rounds=k
        ships exactly k*d collective-permutes, wire accounting multiplies
        by k, the (k,) cheby operand threads as one more donated traced
        input, and sub_rounds=1 lowers TEXTUALLY IDENTICAL to the default
        sync engine (zero-cost axis)."""
        self._run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax
            from repro.configs import registry
            from repro.configs.base import ShapeConfig, ParallelConfig, DFLConfig
            from repro.launch import steps
            from repro.models import params as P

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 4), ("data", "model"))
            cfg = registry.reduced("qwen2.5-3b")
            shape = ShapeConfig("t", 64, 8, "train")
            texts, wires = {}, {}
            for k in (1, 2, 3):
                par = ParallelConfig(clients_per_pod=4, local_steps=2,
                                     grad_accum=2,
                                     gossip_impl="ppermute_packed",
                                     gossip_sub_rounds=k)
                setup = steps.build_train_step(cfg, shape, mesh, par,
                                               DFLConfig(degree=2))
                args = [P.shape_structs(setup.param_struct),
                        setup.input_specs["batch"],
                        setup.input_specs["lr"],
                        setup.input_specs["alive"],
                        setup.input_specs["gates"]]
                if k > 1:
                    om = np.asarray(setup.cheby_coeffs)
                    assert om.shape == (k,) and om[0] == 1.0
                    assert setup.input_specs["cheby"].shape == (k,)
                    args.append(setup.input_specs["cheby"])
                else:
                    assert setup.cheby_coeffs is None
                    assert "cheby" not in setup.input_specs
                texts[k] = setup.step_fn.lower(*args).as_text()
                wires[k] = setup.wire_bytes_per_round
                d = setup.gossip_spec.degree
                perms = [l for l in texts[k].splitlines()
                         if "collective_permute" in l]
                assert len(perms) == k * d, (k, len(perms), d)
            assert wires[2] == 2 * wires[1] and wires[3] == 3 * wires[1]
            # the k=1 cell IS the sync engine, byte for byte
            par0 = ParallelConfig(clients_per_pod=4, local_steps=2,
                                  grad_accum=2,
                                  gossip_impl="ppermute_packed")
            setup0 = steps.build_train_step(cfg, shape, mesh, par0,
                                            DFLConfig(degree=2))
            args0 = [P.shape_structs(setup0.param_struct),
                     setup0.input_specs["batch"],
                     setup0.input_specs["lr"],
                     setup0.input_specs["alive"],
                     setup0.input_specs["gates"]]
            assert texts[1] == setup0.step_fn.lower(*args0).as_text()
            print("CHEBY_STEP_HLO_OK")
        """)
