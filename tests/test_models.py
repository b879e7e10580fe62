"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
shape + finiteness assertions, prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models.api import ModelAPI

RNG = jax.random.key(0)


def _batch(cfg, b=2, s=32):
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.stub_prefix:
        batch["prefix_embeds"] = jnp.zeros(
            (b, cfg.stub_prefix, cfg.prefix_width), jnp.dtype(cfg.dtype))
    return batch


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
class TestArchSmoke:
    def test_train_step(self, arch):
        """One forward+backward+update on the reduced config: shapes + no NaNs."""
        cfg = registry.reduced(arch)
        api = ModelAPI(cfg)
        params = api.init_params(RNG)
        batch = _batch(cfg)

        def step(p, b):
            (loss, aux), grads = jax.value_and_grad(api.loss_fn, has_aux=True)(p, b)
            p = jax.tree.map(lambda w, g: w - 0.01 * g.astype(w.dtype), p, grads)
            return p, loss

        p2, loss = jax.jit(step)(params, batch)
        assert np.isfinite(float(loss))
        # params changed and stayed finite
        moved = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b_.astype(jnp.float32)))), params, p2)
        assert max(jax.tree.leaves(moved)) > 0
        for leaf in jax.tree.leaves(p2):
            assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())

    def test_forward_shapes(self, arch):
        cfg = registry.reduced(arch)
        api = ModelAPI(cfg)
        params = api.init_params(RNG)
        batch = _batch(cfg, b=2, s=32)
        logits = api.forward(params, batch["tokens"],
                             **({"prefix_embeds": batch["prefix_embeds"]}
                                if cfg.stub_prefix else {}))
        assert logits.shape == (2, 32, cfg.padded_vocab)
        assert bool(jnp.isfinite(logits).all())
        # padded vocab columns masked to -inf
        if cfg.padded_vocab > cfg.vocab:
            assert float(logits[..., cfg.vocab:].max()) < -1e29


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma2-2b", "rwkv6-1.6b",
                                  "zamba2-2.7b", "musicgen-medium"])
def test_decode_matches_forward(arch):
    """Greedy decode with a cache == teacher forcing (f32, high capacity)."""
    cfg = dataclasses.replace(registry.reduced(arch), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    api = ModelAPI(cfg)
    params = api.init_params(RNG)
    B, S = 2, 24
    toks = jax.random.randint(jax.random.key(2), (B, S + 1), 0, cfg.vocab)
    pe = (jnp.zeros((B, cfg.stub_prefix, cfg.prefix_width), jnp.float32)
          if cfg.stub_prefix else None)

    full = api.forward(params, toks, **({"prefix_embeds": pe} if pe is not None else {}))
    _, cache = api.prefill(params, toks[:, :S], prefix_embeds=pe)
    cache = {k: (jnp.pad(v, [(0, 0), (0, 0), (0, 8)] + [(0, 0)] * (v.ndim - 3))
                 if k in ("k", "v") and v.ndim >= 3 and v.shape[2] == S else v)
             for k, v in cache.items()}
    ld, _ = api.decode_step(params, cache, toks[:, S], jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(ld),
                               rtol=1e-3, atol=2e-4)


def test_gqa_grouping():
    """GQA: permuting tokens permutes logits consistently (sanity)."""
    cfg = registry.reduced("qwen2-72b")
    api = ModelAPI(cfg)
    params = api.init_params(RNG)
    toks = jax.random.randint(jax.random.key(3), (2, 16), 0, cfg.vocab)
    out = api.forward(params, toks)
    out_swap = api.forward(params, toks[::-1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_swap[::-1]),
                               rtol=2e-2, atol=2e-2)


def test_gemma2_local_window_masks_far_context():
    """A local-attention-only config must be insensitive to tokens farther
    back than the window at the final position."""
    cfg = registry.reduced("gemma2-2b")
    cfg = dataclasses.replace(cfg, dtype="float32", local_window=8,
                              n_layers=2)
    api = ModelAPI(cfg)
    params = api.init_params(RNG)
    toks = jax.random.randint(jax.random.key(4), (1, 64), 0, cfg.vocab)
    toks2 = toks.at[:, :8].set((toks[:, :8] + 7) % cfg.vocab)  # far past
    # layer pattern = local, global: the global layer sees everything, so
    # compare against a both-local config by setting pattern "global" off:
    cfg_local = dataclasses.replace(cfg, layer_pattern="global")
    # in "global" pattern our code applies window only when local_window set
    api_local = ModelAPI(cfg_local)
    out1 = api_local.forward(params, toks)
    out2 = api_local.forward(params, toks2)
    np.testing.assert_allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]),
                               rtol=1e-4, atol=1e-4)


def test_moe_routes_to_multiple_experts():
    from repro.models import moe as moe_lib
    from repro.configs.base import MoEConfig
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=32, capacity_factor=2.0)
    r = jax.random.key(5)
    d = 16
    x = jax.random.normal(r, (2, 8, d), jnp.float32)
    router = jax.random.normal(jax.random.key(6), (d, 4), jnp.float32)
    wg = jax.random.normal(jax.random.key(7), (4, d, 32), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(8), (4, d, 32), jnp.float32) * 0.1
    wd = jax.random.normal(jax.random.key(9), (4, 32, d), jnp.float32) * 0.1
    out = moe_lib.moe_ffn(x, router, wg, wu, wd, cfg, "silu")
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    aux = moe_lib.moe_aux_loss(x, router, cfg)
    assert float(aux) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz, = 1 if balanced


def test_rwkv_chunked_equals_stepwise():
    """WKV chunked evaluation == token-by-token recurrence."""
    from repro.models import rwkv as rwkv_mod
    b, s, h, hd = 2, 12, 3, 4
    r0 = np.random.default_rng(0)
    mk = lambda: jnp.asarray(r0.standard_normal((b, s, h, hd)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    logw = -jnp.asarray(r0.uniform(0.05, 1.0, (b, s, h, hd)), jnp.float32)
    u = jnp.asarray(r0.standard_normal((h, hd)), jnp.float32)
    st = jnp.zeros((b, h, hd, hd), jnp.float32)
    out_c, st_c = rwkv_mod.wkv_chunked(r, k, v, logw, u, st, chunk=4)
    outs = []
    st2 = st
    for t in range(s):
        o, st2 = rwkv_mod.wkv_step(r[:, t], k[:, t], v[:, t], logw[:, t], u, st2)
        outs.append(o)
    out_s = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st2),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunked_equals_stepwise():
    """Mamba2 SSD chunked == token-by-token recurrence."""
    from repro.models import ssm
    b, s, h, p, n = 2, 12, 3, 4, 5
    r0 = np.random.default_rng(1)
    xh = jnp.asarray(r0.standard_normal((b, s, h, p)), jnp.float32)
    bm = jnp.asarray(r0.standard_normal((b, s, n)), jnp.float32)
    cm = jnp.asarray(r0.standard_normal((b, s, n)), jnp.float32)
    dt = jnp.asarray(r0.uniform(0.1, 1.0, (b, s, h)), jnp.float32)
    la = -jnp.asarray(r0.uniform(0.05, 1.0, (b, s, h)), jnp.float32)
    st = jnp.zeros((b, h, p, n), jnp.float32)
    y_c, st_c = ssm.ssd_chunked(xh, bm, cm, la, dt, st, chunk=4)
    ys = []
    st2 = st
    for t in range(s):
        y, st2 = ssm.ssd_step(xh[:, t], bm[:, t], cm[:, t], la[:, t], dt[:, t], st2)
        ys.append(y)
    y_s = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st2),
                               rtol=1e-4, atol=1e-4)


def test_init_params_same_in_every_process():
    """``--seed`` fixes the weights: two processes with different string-
    hash salts (PYTHONHASHSEED) init the same struct to identical leaves."""
    import os
    import subprocess
    import sys

    code = ("import hashlib, jax, numpy as np\n"
            "from repro.models import lstm, params\n"
            "p = params.init_params(lstm.param_struct(vocab=53),"
            " jax.random.key(0))\n"
            "h = hashlib.sha256()\n"
            "for leaf in jax.tree.leaves(p): h.update(np.asarray(leaf)"
            ".tobytes())\n"
            "print(h.hexdigest())\n")
    digests = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(["src"] + sys.path))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        digests.append(out.stdout.strip().splitlines()[-1])
    assert digests[0] == digests[1], digests
