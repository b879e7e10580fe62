"""Small-mesh dry-run integration tests: the same step builders as the
production 512-chip dry-run, on an 16-fake-device world (subprocess, because
the device count must be fixed before jax initializes)."""
import json
import subprocess
import sys
import textwrap

import pytest

_HARNESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys; sys.path.insert(0, "src")
    import json
    import jax
    from repro.configs import registry
    from repro.configs.base import ShapeConfig, ParallelConfig, DFLConfig
    from repro.launch import steps
    from repro.models import params as P
    from repro.roofline import analysis
    from repro.launch.mesh import make_mesh

    arch, kind, gossip = sys.argv[1], sys.argv[2], sys.argv[3]
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = registry.reduced(arch)
    if kind == "train":
        shape = ShapeConfig("t", 64, 8, "train")
        par = ParallelConfig(clients_per_pod=4, local_steps=2, grad_accum=2,
                             gossip_impl=gossip)
        setup = steps.build_train_step(cfg, shape, mesh, par, DFLConfig(degree=2))
        lowered = setup.step_fn.lower(P.shape_structs(setup.param_struct),
                                      setup.input_specs["batch"],
                                      setup.input_specs["lr"],
                                      setup.input_specs["alive"],
                                      setup.input_specs["gates"])
    else:
        shape = ShapeConfig("s", 64, 8, kind)
        setup = steps.build_serve_step(cfg, shape, mesh)
        lowered = setup.step_fn.lower(P.shape_structs(setup.param_struct),
                                      setup.input_specs)
    compiled = lowered.compile()
    roof = analysis.roofline(compiled.cost_analysis(), compiled.as_text(), 16)
    print("RESULT " + json.dumps({
        "flops": roof.flops, "wire": roof.wire_bytes,
        "permutes": roof.collective_counts["collective-permute"],
        "dominant": roof.dominant}))
""")


def _run(arch, kind, gossip="ppermute"):
    out = subprocess.run([sys.executable, "-c", _HARNESS, arch, kind, gossip],
                         capture_output=True, text=True, cwd=".")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"harness failed:\n{out.stdout}\n{out.stderr}")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "rwkv6-1.6b", "zamba2-2.7b"])
def test_train_step_compiles_small_mesh(arch):
    res = _run(arch, "train")
    assert res["flops"] > 0
    # gossip must lower to collective-permutes (2 schedules x param leaves)
    assert res["permutes"] > 0


@pytest.mark.slow
def test_gossip_impl_changes_collectives():
    """The paper's point, visible in compiled HLO: schedule-decomposed
    ppermute gossip moves fewer wire bytes than naive dense mixing (which
    effectively all-gathers every client's parameters)."""
    res_pp = _run("qwen2.5-3b", "train", "ppermute")
    res_dense = _run("qwen2.5-3b", "train", "dense")
    assert res_pp["permutes"] > 0
    assert res_dense["wire"] > res_pp["wire"]


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_serve_steps_compile_small_mesh(kind):
    res = _run("gemma2-2b", kind)
    assert res["flops"] > 0


def test_production_mesh_is_the_visible_devices():
    """A 2x2 host (4 devices) gets a (4, 1) production mesh with Auto axes,
    the DFL mesh puts one client on each device, and the shard_map train
    step built on it ships d collective-permutes on a ring."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax
        from jax.sharding import AxisType
        from repro.configs import registry
        from repro.configs.base import DFLConfig, ParallelConfig, ShapeConfig
        from repro.launch import mesh as mesh_lib, steps
        from repro.models import params as P

        mesh = mesh_lib.make_production_mesh()
        assert dict(mesh.shape) == {"data": 4, "model": 1}, mesh.shape
        assert set(mesh.axis_types) == {AxisType.Auto}, mesh.axis_types
        dmesh = mesh_lib.derive_dfl_mesh(mesh, clients_per_pod=4, tp=1)
        assert dict(dmesh.shape) == {"client": 4, "fsdp": 1, "dp": 1,
                                     "tp": 1}, dmesh.shape
        setup = steps.build_train_step(
            registry.reduced("internvl2-1b"), ShapeConfig("t", 32, 8, "train"),
            mesh, ParallelConfig(clients_per_pod=4, tp=1, grad_accum=1),
            DFLConfig(topology="ring"))
        assert setup.n_clients == 4
        text = setup.step_fn.lower(
            P.shape_structs(setup.param_struct), setup.input_specs["batch"],
            setup.input_specs["lr"], setup.input_specs["alive"],
            setup.input_specs["gates"]).as_text()
        assert text.count("collective_permute") == setup.gossip_spec.degree
        print("PRODUCTION_MESH_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".")
    assert "PRODUCTION_MESH_OK" in out.stdout, out.stdout + out.stderr
