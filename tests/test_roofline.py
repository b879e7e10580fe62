"""Roofline tooling tests: scan-aware HLO cost analyzer vs ground truth."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis, hw
from repro.roofline.hlo_cost import analyze_hlo


def _hlo(f, *specs):
    return jax.jit(f).lower(*specs).compile().as_text()


class TestHloCost:
    def test_plain_matmul_exact(self):
        t = _hlo(lambda x, w: x @ w,
                 jax.ShapeDtypeStruct((128, 256), jnp.float32),
                 jax.ShapeDtypeStruct((256, 512), jnp.float32))
        assert analyze_hlo(t, 1).flops == 2 * 128 * 256 * 512

    def test_scan_multiplies_trip_count(self):
        """The reason this module exists: cost_analysis counts scan bodies once."""
        f = lambda x, w: jax.lax.scan(lambda h, _: (h @ w, None), x, None,
                                      length=10)[0]
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        compiled = jax.jit(f).lower(x, w).compile()
        ours = analyze_hlo(compiled.as_text(), 1).flops
        xla = compiled.cost_analysis().get("flops", 0.0)
        assert ours == 10 * 2 * 64 * 64 * 64
        assert xla < ours / 5  # documents the undercount

    def test_nested_scan(self):
        def f(x, w):
            def outer(h, _):
                return jax.lax.scan(lambda g, __: (g @ w, None), h, None,
                                    length=5)[0], None
            return jax.lax.scan(outer, x, None, length=3)[0]
        t = _hlo(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
                 jax.ShapeDtypeStruct((32, 32), jnp.float32))
        c = analyze_hlo(t, 1)
        assert c.flops == 15 * 2 * 32 * 32 * 32
        assert sorted(c.while_trip_counts) == [3, 5]

    def test_batched_einsum(self):
        t = _hlo(lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
                 jax.ShapeDtypeStruct((4, 8, 16), jnp.float32),
                 jax.ShapeDtypeStruct((4, 16, 8), jnp.float32))
        assert analyze_hlo(t, 1).flops == 2 * 4 * 8 * 16 * 8

    def test_matches_cost_analysis_without_scans(self):
        def f(x, w1, w2):
            return jax.nn.relu(x @ w1) @ w2
        specs = [jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in [(32, 64), (64, 128), (128, 16)]]
        compiled = jax.jit(f).lower(*specs).compile()
        ours = analyze_hlo(compiled.as_text(), 1).flops
        xla = compiled.cost_analysis().get("flops", 0.0)
        # dot flops dominate; ours counts only dots, so ours <= xla <= ours+eps
        dots = 2 * 32 * 64 * 128 + 2 * 32 * 128 * 16
        assert ours == dots
        assert xla >= dots


class TestRooflineTerms:
    def test_terms_and_dominance(self):
        f = lambda x, w: x @ w
        specs = [jax.ShapeDtypeStruct((256, 256), jnp.float32)] * 2
        compiled = jax.jit(f).lower(*specs).compile()
        roof = analysis.roofline(compiled.cost_analysis(), compiled.as_text(), 1)
        assert roof.compute_s == pytest.approx(
            2 * 256**3 / hw.PEAK_FLOPS_BF16)
        assert roof.dominant in ("compute", "memory", "collective")
        # a tiny matmul is memory-bound on v5e
        assert roof.dominant == "memory"

    def test_model_flops_formulas(self):
        assert analysis.model_flops_train(1e9, 1000) == 6e12
        assert analysis.model_flops_prefill(1e9, 1000) == 2e12
        assert analysis.model_flops_decode(1e9, 8) == 16e9


class TestCollectiveParsing:
    def test_ppermute_bytes_counted(self):
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.roofline.hlo_cost import analyze_hlo
            from repro.launch.mesh import make_mesh, shard_map
            mesh = make_mesh((4,), ("x",))
            def f(a):
                return jax.lax.ppermute(a, "x", [(i, (i+1) % 4) for i in range(4)])
            fn = shard_map(f, mesh, in_specs=P("x"), out_specs=P("x"))
            t = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((4, 1024), jnp.float32)).compile().as_text()
            c = analyze_hlo(t, 4)
            # per-device shard is (1, 1024) f32 = 4096 bytes on the wire
            assert c.collective_bytes["collective-permute"] == 4096, c
            print("PPERMUTE_BYTES_OK")
        """)
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, cwd=".")
        assert "PPERMUTE_BYTES_OK" in out.stdout, out.stdout + out.stderr
