"""Plain reference of InternVL2-1B's trainable part and its DFL round.

InternVL2-1B (hf ``OpenGVLab/InternVL2-1B``; arXiv:2404.16821): a frozen
InternViT-300M whose 256 pixel-shuffled features per 448x448 tile (4096
wide) go through the trainable projector ``mlp1`` (LayerNorm -> Linear ->
GELU -> Linear) into the first 256 positions of Qwen2-0.5B-Instruct, a
decoder of pre-norm blocks:

    h = x + Wo attn(rope(RMSNorm(x) Wq + bq), rope(RMSNorm(x) Wk + bk),
                    RMSNorm(x) Wv + bv)
    x' = h + W_down (silu(RMSNorm(h) W_gate) * RMSNorm(h) W_up)

with grouped-query attention (query head i reads key/value head
i // (heads / kv_heads)), causal softmax attention scaled by 1/sqrt(hd),
rotary positions over the two halves of each head with base ``theta``, a
final RMSNorm and an untied head. The loss is the mean cross-entropy over
the text positions (those after the image's).

One DFL round for every client: momentum reset, K heavy-ball steps
(v' = beta v - lr g, w' = w + v'), each step's gradient the mean over its
micro-batches, then the gossip step w_i' = sum_j M_ij w_j.

Everything is plain ``jax.numpy`` in float32 at ``Precision.HIGHEST``.
Departure: the parameters are held in the configuration's dtype after each
update and after the mix (bfloat16 for the published model, which stores
them so); the momentum stays float32. ``qkv_bias=False`` plants the fault
of a block without the Qwen2 biases.

Parameters use the program's tree layout (``transformer.param_struct``),
whose vocabulary is padded: the reference reads the first ``vocab`` rows
of the embedding and columns of the head only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def rms_norm(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, theta):
    """x (B, S, H, hd): rotate the halves of each head by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def projector(p, feats):
    h = layer_norm(feats, p["ln_scale"], p["ln_bias"])
    h = jax.nn.gelu(_mm("bpf,fd->bpd", h, p["w1"]) + p["b1"],
                    approximate=False)
    return _mm("bpd,de->bpe", h, p["w2"]) + p["b2"]


def attention(q, k, v):
    """q (B, S, H, hd), k/v (B, S, KV, hd): causal grouped-query attention."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = q.shape[1]
    scores = _mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return _mm("bhqk,bkhd->bqhd", probs, v)


def block(x, p, theta, qkv_bias=True):
    h = rms_norm(x, p["ln1"])
    q = _mm("bsd,dhk->bshk", h, p["wq"])
    k = _mm("bsd,dhk->bshk", h, p["wk"])
    v = _mm("bsd,dhk->bshk", h, p["wv"])
    if qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    a = attention(rope(q, theta), rope(k, theta), v)
    x = x + _mm("bshk,hkd->bsd", a, p["wo"])
    h = rms_norm(x, p["ln2"])
    ff = jax.nn.silu(_mm("bsd,df->bsf", h, p["w_gate"])) \
        * _mm("bsd,df->bsf", h, p["w_up"])
    return x + _mm("bsf,fd->bsd", ff, p["w_down"])


def hidden(p, tokens, feats, theta, qkv_bias=True):
    """tokens (B, S) int, feats (B, P, f) -> final-normed states (B, S, d)
    in float32; the image's P positions come first."""
    p = jax.tree.map(lambda a: a.astype(F32), p)
    n_img = feats.shape[1]
    x = p["embed"][tokens]
    x = x.at[:, :n_img].set(projector(p["vision_proj"], feats.astype(F32)))
    for i in range(p["blocks"]["wq"].shape[0]):
        x = block(x, jax.tree.map(lambda a: a[i], p["blocks"]), theta,
                  qkv_bias)
    return rms_norm(x, p["final_norm"])


def logits(p, tokens, feats, vocab, theta, qkv_bias=True):
    x = hidden(p, tokens, feats, theta, qkv_bias)
    return _mm("bsd,dv->bsv", x, p["head"][:, :vocab].astype(F32))


def loss(p, batch, vocab, theta, qkv_bias=True):
    """Mean cross-entropy over the text positions."""
    n_img = batch["prefix_embeds"].shape[1]
    lg = logits(p, batch["tokens"], batch["prefix_embeds"], vocab, theta,
                qkv_bias)[:, n_img:]
    lp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(lp, batch["labels"][:, n_img:, None], -1)
    return -jnp.mean(gold)


def client_round(p, batches, vocab, theta, lr, beta, grad_accum,
                 qkv_bias=True):
    """K heavy-ball steps of one client from a zeroed momentum; batches
    hold (K, B, ...) and each step's B splits into ``grad_accum``
    micro-batches. Returns (params, mean loss)."""
    dt = jax.tree.leaves(p)[0].dtype
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p)
    grad = jax.value_and_grad(loss)
    total, k_steps = 0.0, batches["tokens"].shape[0]
    for k in range(k_steps):
        step = jax.tree.map(lambda a: a[k], batches)
        mb = step["tokens"].shape[0] // grad_accum
        g_sum = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p)
        for m in range(grad_accum):
            micro = jax.tree.map(lambda a: a[m * mb:(m + 1) * mb], step)
            val, g = grad(p, micro, vocab, theta, qkv_bias)
            g_sum = jax.tree.map(lambda a, b: a + b.astype(F32), g_sum, g)
            total = total + val / grad_accum
        v = jax.tree.map(lambda v, g: beta * v - lr * g / grad_accum, v, g_sum)
        p = jax.tree.map(lambda w, v: (w.astype(F32) + v).astype(dt), p, v)
    return p, total / k_steps


def dfl_round(params, batches, mixing, vocab, theta, lr, beta, grad_accum,
              qkv_bias=True):
    """Every client's round, then the mix; params and batches carry the
    clients on axis 0, ``mixing`` is (n, n). Returns (params, (n,) losses)."""
    n = mixing.shape[0]
    outs = [client_round(jax.tree.map(lambda a: a[i], params),
                         jax.tree.map(lambda a: a[i], batches), vocab, theta,
                         lr, beta, grad_accum, qkv_bias) for i in range(n)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *[o[0] for o in outs])
    dt = jax.tree.leaves(params)[0].dtype
    mixed = jax.tree.map(
        lambda a: jnp.einsum("ij,j...->i...", mixing.astype(F32),
                             a.astype(F32), precision=HI).astype(dt), stacked)
    return mixed, jnp.stack([o[1] for o in outs])
