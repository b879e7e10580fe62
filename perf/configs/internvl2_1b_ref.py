"""Plain reference of InternVL2-1B's trainable part and its DFL round,
independent of the program (no ``repro`` import).

InternVL2-1B (hf ``OpenGVLab/InternVL2-1B``; arXiv:2404.16821): the frozen
InternViT-300M's 256 pixel-shuffled features per 448x448 tile (4096 wide)
go through the trainable projector ``mlp1``

    LayerNorm(4096, eps 1e-5, with bias) -> Linear 4096->896 -> GELU (exact)
    -> Linear 896->896

into the first 256 positions of Qwen2-0.5B-Instruct: 24 pre-norm blocks

    h = x + Wo attn(rope(RMSNorm(x) Wq + bq), rope(RMSNorm(x) Wk + bk),
                    RMSNorm(x) Wv + bv)
    x' = h + W_down (silu(RMSNorm(h) W_gate) * RMSNorm(h) W_up)

(RMSNorm eps 1e-6; 14 query heads of 64 over 2 key/value heads, query
head i reading key/value head i // 7; causal softmax attention scaled by
1/8; rotary positions over the two halves of each head, base 1e6), a final
RMSNorm and an untied head. The loss is the mean cross-entropy over the
text positions (those after the image's).

One DFL round for every client (DFedAvgM): momentum reset, K heavy-ball
steps v' = beta v - lr g, w' = w + v', each step's gradient the mean over
its micro-batches, then the gossip step w_i' = sum_j M_ij w_j with the
mixing matrix the caller draws.

Plain ``jax.numpy`` in float32 at ``Precision.HIGHEST``. Departures, none
of which changes what is computed: the parameters are stored in the
configuration's dtype (bfloat16 as published) after each update and after
the mix, because the configuration stores them so (the momentum stays
float32; every computation reads them as float32); each block's
activations are
recomputed in the backward pass (``jax.checkpoint``), each micro-batch's
gradient is added to the momentum as it is computed (v' = beta v -
sum_m (lr / M) g_m, which is beta v - lr g with g the mean of the M
micro-batches' gradients), and the clients run one micro-batch at a time,
each client on its own device when there are as many, so that one
client's float32 weights, momentum, gradient and activations fit on one
chip. Parameters use the program's tree layout,
whose vocabulary is padded: only the first ``vocab`` rows of the embedding
and columns of the head are read.

Planted faults (``fault``): ``half_batch`` (each step's loss and gradient
over the first half of its micro-batches), ``no_mix`` (the gossip step left
out), ``no_qkv_bias`` (the blocks without the Qwen2 biases). ``low=True``
is the control below the configuration's precision: the attention softmax
and the cross-entropy computed in bfloat16 (every intermediate rounded to
bfloat16 with ``lax.reduce_precision``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FAULTS = ("half_batch", "no_mix", "no_qkv_bias")


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


def bf16(x):
    """x rounded to bfloat16 values. An explicit rounding: a float32 ->
    bfloat16 -> float32 round trip may be left out by the compiler (XLA
    may keep excess precision on the TPU)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def log_softmax(x, low):
    """In float32, or (``low``) with every intermediate rounded to
    bfloat16."""
    if not low:
        return jax.nn.log_softmax(x, axis=-1)
    x = bf16(x)
    z = bf16(x - jnp.max(x, -1, keepdims=True))
    return bf16(z - bf16(jnp.log(bf16(jnp.sum(bf16(jnp.exp(z)), -1,
                                                keepdims=True)))))


def softmax(x, low):
    return jnp.exp(log_softmax(x, low)) if low else jax.nn.softmax(x, -1)


def projector(p, feats):
    h = layer_norm(feats, p["ln_scale"], p["ln_bias"])
    h = jax.nn.gelu(_mm("bpf,fd->bpd", h, p["w1"]) + p["b1"],
                    approximate=False)
    return _mm("bpd,de->bpe", h, p["w2"]) + p["b2"]


def attention(q, k, v, low):
    """q (B, S, H, hd), k/v (B, S, KV, hd): causal grouped-query attention."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = q.shape[1]
    scores = _mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = softmax(jnp.where(causal, scores, -jnp.inf), low)
    return _mm("bhqk,bkhd->bqhd", probs, v)


def block(x, p, theta, qkv_bias, low):
    h = rms_norm(x, p["ln1"])
    q = _mm("bsd,dhk->bshk", h, p["wq"])
    k = _mm("bsd,dhk->bshk", h, p["wk"])
    v = _mm("bsd,dhk->bshk", h, p["wv"])
    if qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    a = attention(rope(q, theta), rope(k, theta), v, low)
    x = x + _mm("bshk,hkd->bsd", a, p["wo"])
    h = rms_norm(x, p["ln2"])
    ff = jax.nn.silu(_mm("bsd,df->bsf", h, p["w_gate"])) \
        * _mm("bsd,df->bsf", h, p["w_up"])
    return x + _mm("bsf,fd->bsd", ff, p["w_down"])


def loss(p, batch, vocab, theta, qkv_bias=True, low=False):
    """Mean cross-entropy over the text positions; p in the program's
    layout, batch one micro-batch (tokens, labels, prefix_embeds)."""
    p = jax.tree.map(lambda a: a.astype(F32), p)
    feats = batch["prefix_embeds"].astype(F32)
    n_img = feats.shape[1]
    x = p["embed"][batch["tokens"]]
    x = x.at[:, :n_img].set(projector(p["vision_proj"], feats))

    @jax.checkpoint
    def one(x, pb):
        return block(x, pb, theta, qkv_bias, low), None

    x, _ = jax.lax.scan(one, x, p["blocks"])
    x = rms_norm(x[:, n_img:], p["final_norm"])
    logits = _mm("bsd,dv->bsv", x, p["head"][:, :vocab])
    lp = log_softmax(logits, low)
    gold = jnp.take_along_axis(lp, batch["labels"][:, n_img:, None], -1)
    return -jnp.mean(gold)


@functools.lru_cache(maxsize=None)
def _fns(vocab, theta, qkv_bias, low):
    @functools.partial(jax.jit, donate_argnums=(1,))
    def descend(p, v, micro, rate):
        """v - rate x the micro-batch's gradient, and the micro-batch's
        loss; the gradient is taken at p's values in float32."""
        val, g = jax.value_and_grad(loss)(
            jax.tree.map(lambda a: a.astype(F32), p), micro, vocab, theta,
            qkv_bias, low)
        return jax.tree.map(lambda v, g: v - rate * g, v, g), val

    @functools.partial(jax.jit, donate_argnums=(0,))
    def advance(p, v):
        return jax.tree.map(lambda w, v: (w.astype(F32) + v).astype(w.dtype),
                            p, v)

    return descend, advance


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(v, beta):
    return jax.tree.map(lambda x: beta * x, v)


@jax.jit
def _zeros(p):
    return jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p)


@jax.jit
def _mix(row, *ps):
    """sum_j row[j] p_j in float32, stored in the parameters' dtype."""
    return jax.tree.map(
        lambda *xs: sum(row[j] * x.astype(F32) for j, x in enumerate(xs)
                        ).astype(xs[0].dtype), *ps)


@jax.jit
def _leaf_norms(a, b=None):
    f = lambda t: [x.astype(F32) for x in jax.tree.leaves(t)]  # noqa: E731
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x if b is None else x - y)))
                      for x, y in zip(f(a), f(a if b is None else b))])


def run(p0_fn, batches, mixing, rounds, vocab, theta, lr, beta, grad_accum,
        fault=None, low=False):
    """The first ``rounds`` rounds of every client.

    p0_fn(device): one client's initial params (the same draw for every
    client) on that device, in the dtype the parameters are stored in;
    batches[r]: round r's host arrays with
    clients on axis 0, then K, then B; mixing (n, n). Client i runs on
    device i % (devices), the clients' steps dispatched side by side.
    Returns {"losses": [mean over clients per round], "grads0": (leaves, n)
    norms of each client's first gradient, "step1" and "step<rounds>":
    (leaves, n) norms of each client's change since round 0}."""
    n = mixing.shape[0]
    devs = jax.devices()
    dev = [devs[i % len(devs)] for i in range(n)]
    descend, advance = _fns(vocab, theta, fault != "no_qkv_bias", low)
    ps = [p0_fn(d) for d in dev]
    k_steps, b = batches[0]["tokens"].shape[1:3]
    mb = b // grad_accum
    used = grad_accum // 2 if fault == "half_batch" else grad_accum
    out = {"losses": []}
    for r in range(rounds):
        vs = [_zeros(p) for p in ps]
        vals = [[] for _ in range(n)]
        for k in range(k_steps):
            # v' = beta v - lr g, g the mean of the micro-batches' gradients,
            # each added to the momentum as it is computed
            vs = [_scale(v, F32(beta)) for v in vs]
            for m in range(used):
                for i in range(n):
                    micro = jax.device_put(
                        {name: a[i, k, m * mb:(m + 1) * mb]
                         for name, a in batches[r].items()}, dev[i])
                    vs[i], val = descend(ps[i], vs[i], micro,
                                         F32(lr / used))
                    vals[i].append(val)
            if r == 0 and k == 0:   # v = -lr g
                out["grads0"] = np.stack(
                    [np.asarray(_leaf_norms(v)) / lr for v in vs], 1)
            ps = [advance(p, v) for p, v in zip(ps, vs)]
            jax.block_until_ready(ps)
        del vs
        out["losses"].append(float(np.mean(
            [np.mean([float(v) for v in vi]) for vi in vals])))
        if fault != "no_mix":
            m = np.asarray(mixing, np.float32)
            new = []
            for i in range(n):
                js = [j for j in range(n) if m[i, j] != 0.0]
                new.append(_mix(jnp.asarray(m[i, js]),
                                *[jax.device_put(ps[j], dev[i]) for j in js]))
            ps = new
            jax.block_until_ready(ps)
        if r == 0 or r == rounds - 1:
            out["step1" if r == 0 else f"step{rounds}"] = np.stack(
                [np.asarray(_leaf_norms(p, p0_fn(d)))
                 for p, d in zip(ps, dev)], 1)
    return out
