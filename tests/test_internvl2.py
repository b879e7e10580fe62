"""InternVL2-1B against its plain reference (``tests/ref_internvl2.py``).

At ``registry.reduced("internvl2-1b")`` sizes, in float32 on the CPU, on
seeded weights whose QKV biases, norm scales and projector biases are
non-zero: the model's logits, loss and gradients (projector included), and
one DFL round through ``launch.steps.build_train_step`` on a 4-client ring
of CPU devices. The full entry's widths and parameter count are the
published model's.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import params as params_lib
from repro.models import transformer
from repro.models.api import ModelAPI

sys.path.insert(0, os.path.dirname(__file__))
import ref_internvl2 as ref  # noqa: E402

NONZERO = {"bq", "bk", "bv", "b1", "b2", "ln_bias"}
SCALES = {"ln1", "ln2", "final_norm", "ln_scale"}


def seeded_params(cfg, key):
    """The program's init, with every bias and norm scale drawn away from
    its zero or one, so that a reference that drops one of them differs."""
    p = ModelAPI(cfg).init_params(key)

    def move(path, x):
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % 997)
        noise = 0.2 * jax.random.normal(k, x.shape, jnp.float32)
        if name in NONZERO:
            return noise.astype(x.dtype)
        if name in SCALES:
            return (1.0 + noise).astype(x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(move, p)


def small_cfg():
    return dataclasses.replace(registry.reduced("internvl2-1b"),
                               dtype="float32")


def batch_for(cfg, key, b, s):
    k1, k2 = jax.random.split(key)
    seq = jax.random.randint(k1, (b, s + 1), 0, cfg.vocab)
    return {"tokens": seq[:, :-1], "labels": seq[:, 1:],
            "prefix_embeds": jax.random.normal(
                k2, (b, cfg.stub_prefix, cfg.vision_feature_dim))}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_published_widths_and_param_count():
    cfg = registry.get("internvl2-1b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab) == (
        24, 896, 14, 2, 64, 4864, 151655)
    assert cfg.qkv_bias and cfg.rope_theta == 1_000_000.0
    assert cfg.norm == "rms" and cfg.act == "silu" and not cfg.tie_embeddings
    assert (cfg.frontend, cfg.stub_prefix, cfg.vision_feature_dim) == (
        "vision_stub", 256, 4096)
    struct = transformer.param_struct(cfg)
    proj = params_lib.count_params(struct["vision_proj"])
    assert proj == 4_482_816
    assert cfg.param_count() == 634_146_688          # published, unpadded
    assert cfg.param_count() - proj == 629_663_872   # Qwen2-0.5B
    assert cfg.padded_vocab == 151_680
    assert ModelAPI(cfg).param_count() == 634_191_488


def test_input_specs_carry_the_vision_features():
    from repro.configs.base import ShapeConfig
    cfg = registry.get("internvl2-1b")
    specs = ModelAPI(cfg).input_specs(ShapeConfig("t", 1024, 4, "train"))
    assert specs["prefix_embeds"].shape == (4, 256, 4096)


@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_and_grads_match_reference(remat):
    cfg = small_cfg()
    api = ModelAPI(cfg)
    p = seeded_params(cfg, jax.random.key(11))
    batch = batch_for(cfg, jax.random.key(12), 2, 24)
    args = (cfg.vocab, cfg.rope_theta)

    got = api.forward(p, batch["tokens"], prefix_embeds=batch["prefix_embeds"])
    want = ref.logits(p, batch["tokens"], batch["prefix_embeds"], *args)
    assert rel(got[..., :cfg.vocab], want) < 1e-5

    (loss, _), g = jax.value_and_grad(api.loss_fn, has_aux=True)(
        p, batch, remat=remat)
    want_loss, want_g = jax.value_and_grad(ref.loss)(p, batch, *args)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    gaps = jax.tree.map(rel, g, want_g)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    assert all(float(jnp.linalg.norm(x)) > 0
               for x in jax.tree.leaves(g["vision_proj"]))

    # the comparison sees the biases: without them the reference is far off
    no_bias = ref.logits(p, batch["tokens"], batch["prefix_embeds"], *args,
                         qkv_bias=False)
    assert rel(got[..., :cfg.vocab], no_bias) > 1e-3


def test_audio_stub_keeps_d_model_prefix():
    cfg = registry.reduced("musicgen-medium")
    assert cfg.prefix_width == cfg.d_model
    assert "vision_proj" not in transformer.param_struct(cfg)


_ROUND = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path[:0] = ["src", "tests"]
    import json
    import jax, jax.numpy as jnp, numpy as np
    import ref_internvl2 as ref
    from test_internvl2 import batch_for, seeded_params, small_cfg
    from repro.configs.base import DFLConfig, ParallelConfig, ShapeConfig
    from repro.kernels.fused_sgdm import kernel as sgdm_k, ops as sgdm_ops
    from repro.launch import mesh as mesh_lib, steps

    if sys.argv[1:] == ["pallas_interpret"]:   # the kernel, not its jnp ref
        sgdm_ops._on_tpu = lambda: True
        kernel = sgdm_k.sgdm_2d
        sgdm_k.sgdm_2d = lambda *a, **k: kernel(*a, **{**k, "interpret": True})
    cfg = small_cfg()
    n, per_client, seq, k_steps, accum = 4, 4, 24, 2, 2
    lr, beta = 0.05, 0.9
    mesh = mesh_lib.make_production_mesh()
    setup = steps.build_train_step(
        cfg, ShapeConfig("t", seq, n * per_client, "train"), mesh,
        ParallelConfig(clients_per_pod=n, tp=1, local_steps=k_steps,
                       grad_accum=accum),
        DFLConfig(topology="ring", lr=lr, momentum=beta))
    p1 = seeded_params(cfg, jax.random.key(21))
    p0 = jax.tree.map(lambda a: jnp.stack([a] * n), p1)
    b = batch_for(cfg, jax.random.key(22), n * k_steps * per_client, seq)
    batch = jax.tree.map(
        lambda a: a.reshape((n, k_steps, per_client) + a.shape[1:]), b)
    args = [jax.device_put(p0, setup.in_shardings[0]),
            jax.device_put(batch, setup.in_shardings[1]),
            jnp.float32(lr), jnp.ones(n, jnp.float32),
            jnp.ones(setup.gossip_spec.degree, jnp.float32)]
    got, met = setup.step_fn(*args)
    m = np.asarray(setup.overlay.mixing_matrix(), np.float64)
    want, losses = ref.dfl_round(p0, batch, jnp.asarray(m, jnp.float32),
                                 cfg.vocab, cfg.rope_theta, lr, beta, accum)
    gap = {}
    for (path, g), w, a in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree.leaves(want), jax.tree.leaves(p0)):
        dg = np.asarray(g, np.float64) - np.asarray(a, np.float64)
        dw = np.asarray(w, np.float64) - np.asarray(a, np.float64)
        gap[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(dg - dw) / np.linalg.norm(dw))
    print(json.dumps({"change_gap": gap,
                      "loss": float(met["loss"]),
                      "ref_loss": float(jnp.mean(losses)),
                      "mixing_offdiag": sorted(set(np.round(
                          m[m > 0].ravel(), 6).tolist()))}))
""")


def _round_against_reference(*argv):
    import json
    out = subprocess.run([sys.executable, "-c", _ROUND, *argv],
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_dfl_round_matches_reference():
    """One round of build_train_step's step_fn (4 clients on 4 CPU devices,
    a ring, K = 2, grad_accum 2, fused sgdm) against the reference round:
    each leaf's change over the round within 1e-3 of the reference's. The
    change is a difference of float32 weights, each rounded to 2**-24 of a
    weight about a thousand times the change, so round-off alone puts
    ~1e-4 on it; a missing bias or mix moves it by order one."""
    r = _round_against_reference()
    assert max(r["change_gap"].values()) < 1e-3, r["change_gap"]
    assert abs(r["loss"] - r["ref_loss"]) < 1e-5 * r["ref_loss"]
    assert r["mixing_offdiag"] == [0.333333]  # Chow weights on a 4-ring


def test_dfl_round_with_the_sgdm_kernel_matches_reference():
    """The same round with the fused sgdm Pallas kernel itself (in
    interpret mode) inside the step, vmapped over clients under its
    shard_map, where the CPU otherwise takes the kernel's jnp ref."""
    r = _round_against_reference("pallas_interpret")
    assert max(r["change_gap"].values()) < 1e-3, r["change_gap"]
    assert abs(r["loss"] - r["ref_loss"]) < 1e-5 * r["ref_loss"]
