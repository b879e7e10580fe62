"""Plain reference of the char-LSTM DFL round, independent of the program.

The paper's model (arXiv:2112.15486, section 5): characters are embedded,
projected to the hidden width, run through a stack of LSTM layers, and a
linear head gives next-character logits; the loss is the mean negative
log-likelihood over every position. The LSTM cell is the usual one with the
gates in the order input, forget, cell, output and 1 added to the forget
gate's pre-activation:

    z = x W_x + h W_h + b;   i, f, g, o = split(z, 4)
    c' = sigmoid(f + 1) c + sigmoid(i) tanh(g);   h' = sigmoid(o) tanh(c')

One round of DFedAvgM (the paper's eq. 2.1) for every client: K heavy-ball
steps from a zeroed velocity, v' = beta v - lr g and w' = w + v', then the
gossip step w_i' = sum_j M_ij w_j with Chow's weights on the overlay's
multigraph: M = I - c L, c = 2 / (lambda_2(L) + lambda_max(L)). The
overlay is drawn here from the traffic's seed as the paper's section 4
builds it, not taken from the program.

Everything is plain ``jax.numpy`` in the precision asked for. ``float32``
runs every matrix product at ``Precision.HIGHEST``; ``bfloat16`` is the
control: weights, activations, gradients and the update all held in
bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def overlay_adjacency(topology: str, n: int, degree: int, seed: int
                      ) -> np.ndarray:
    """Multigraph adjacency of the overlay, as the paper's section 4 draws
    it. ``ring``: one cycle in client order. ``expander`` of degree d: d // 2
    virtual rings, the first in client order and ring r in the order of
    column r of ``default_rng(seed).random((n, d // 2))``; each client is
    linked to the two clients beside it on every ring. An odd d adds the
    perfect matching that pairs ``default_rng(seed).permutation(n)`` (drawn
    after the coordinates) two by two."""
    a = np.zeros((n, n))

    def ring(order):
        for x, y in zip(order, np.roll(order, -1)):
            a[x, y] += 1.0
            a[y, x] += 1.0

    if topology == "ring":
        ring(np.arange(n))
        return a
    if topology != "expander" or degree < 2:
        raise ValueError(f"no reference overlay {topology!r} of degree "
                         f"{degree}")
    rng = np.random.default_rng(seed)
    coords = rng.random((n, degree // 2))
    coords[:, 0] = np.arange(n) / n
    for r in range(degree // 2):
        ring(np.argsort(coords[:, r], kind="stable"))
    if degree % 2:
        perm = rng.permutation(n)
        a[perm[0::2], perm[1::2]] += 1.0
        a[perm[1::2], perm[0::2]] += 1.0
    return a


def chow_mixing(a: np.ndarray) -> np.ndarray:
    """Float64 mixing matrix of Chow's weights on adjacency ``a``."""
    if not np.allclose(a, a.T):
        raise ValueError("overlay is not symmetric")
    lap = np.diag(a.sum(axis=1)) - a
    n = len(a)
    ev = np.linalg.eigvalsh(lap)
    if ev[1] <= 1e-12:
        raise ValueError("overlay is disconnected")
    return np.eye(n) - 2.0 / (ev[1] + ev[-1]) * lap


def _dot(a, b):
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.matmul(a, b, precision=prec)


def logits(p, tokens):
    """tokens (B, S) int -> logits (B, S, V) in the weights' dtype."""
    x = _dot(p["embed"][tokens], p["proj_in"])
    b = x.shape[0]
    hidden = p["layers"]["wh"].shape[1]
    for layer in range(p["layers"]["wx"].shape[0]):
        wx = p["layers"]["wx"][layer][:x.shape[-1]]
        wh = p["layers"]["wh"][layer]
        bias = p["layers"]["b"][layer]

        def cell(carry, xt, wx=wx, wh=wh, bias=bias):
            h, c = carry
            z = _dot(xt, wx) + _dot(h, wh) + bias
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        zero = jnp.zeros((b, hidden), x.dtype)
        _, hs = jax.lax.scan(cell, (zero, zero), jnp.swapaxes(x, 0, 1))
        x = jnp.swapaxes(hs, 0, 1)
    return _dot(x, p["head"])


def loss(p, tokens, labels):
    lp = jax.nn.log_softmax(logits(p, tokens), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)
    return jnp.mean(nll.astype(jnp.float32))


def client_round(p, tokens, labels, lr, beta):
    """K local heavy-ball steps of one client; tokens (K, B, S)."""
    v = jax.tree.map(jnp.zeros_like, p)
    total = jnp.zeros((), jnp.float32)
    for k in range(tokens.shape[0]):
        val, g = jax.value_and_grad(loss)(p, tokens[k], labels[k])
        v = jax.tree.map(lambda v, g: (beta * v - lr * g).astype(v.dtype), v, g)
        p = jax.tree.map(lambda w, v: (w + v).astype(w.dtype), p, v)
        total = total + val
    return p, total / tokens.shape[0]


def make_round(lr: float, beta: float):
    """jitted (params, tokens, labels, M) -> (mixed params, (n,) losses)
    for stacked clients; tokens (n, K, B, S)."""

    @jax.jit
    def one(p, tokens, labels, m):
        dt = jax.tree.leaves(p)[0].dtype
        p, losses = jax.vmap(client_round, in_axes=(0, 0, 0, None, None))(
            p, tokens, labels, jnp.asarray(lr, dt), jnp.asarray(beta, dt))
        mixed = jax.tree.map(
            lambda x: jnp.einsum("ij,j...->i...", m.astype(dt), x,
                                 precision=(jax.lax.Precision.HIGHEST
                                            if dt == jnp.float32 else None),
                                 preferred_element_type=jnp.float32
                                 ).astype(dt), p)
        return mixed, losses

    return one


def first_gradients(p, tokens, labels):
    """Every client's first local gradient (its norms leave out the leaves
    that move by round-off alone)."""
    return jax.jit(jax.vmap(lambda p, t, l: jax.grad(loss)(p, t[0], l[0])))(
        p, tokens, labels)
