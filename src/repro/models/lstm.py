"""The paper's language model: 2-layer LSTM, 256 hidden units (§5, Shakespeare).

Each layer is one recurrence with a hand-written backward pass: only the
``h @ wh`` product and the gates' elementwise work run step by step, in
both directions. The input product and every weight gradient are one
matrix product over all positions, so no loop carries a weight-sized
accumulator.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.params import Leaf

F32 = jnp.float32
PyTree = Any


def param_struct(vocab: int, d_embed: int = 128, d_hidden: int = 256,
                 n_layers: int = 2, dtype: str = "float32") -> PyTree:
    layers = {
        "wx": Leaf((n_layers, d_embed if n_layers == 1 else max(d_embed, d_hidden),
                    4 * d_hidden), ("layers", None, None), dtype),
        "wh": Leaf((n_layers, d_hidden, 4 * d_hidden), ("layers", None, None), dtype),
        "b": Leaf((n_layers, 4 * d_hidden), ("layers", None), dtype, "zeros"),
    }
    return {
        "embed": Leaf((vocab, d_embed), (None, None), dtype, scale=0.05),
        "proj_in": Leaf((d_embed, max(d_embed, d_hidden)), (None, None), dtype),
        "layers": layers,
        "head": Leaf((d_hidden, vocab), (None, None), dtype),
    }


def _recurrence(xt, wx, wh, b):
    """The sequential part of one layer, time-major: xt (S, B, D) -> the
    outputs h_t (S, B, H), the f32 cell states c_t and the f32 gate
    activations (i, f, g, o), each (S, B, H). The input product runs once
    over every position; the loop keeps only ``h @ wh`` and the gates."""
    xw = xt @ wx + b

    def step(carry, xw_t):
        h, c = carry
        i, f, g, o = jnp.split((xw_t + h @ wh).astype(F32), 4, axis=-1)
        i, f, g, o = (jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jnp.tanh(g),
                      jax.nn.sigmoid(o))
        c = f * c + i * g
        h = (o * jnp.tanh(c)).astype(xt.dtype)
        return (h, c), (h, c, (i, f, g, o))

    shape = (xt.shape[1], wh.shape[0])
    init = (jnp.zeros(shape, xt.dtype), jnp.zeros(shape, F32))
    return lax.scan(step, init, xw)[1]


def _shift(ys):
    """(S, ...) -> the values one step earlier, zeros at t = 0."""
    return jnp.concatenate([jnp.zeros_like(ys[:1]), ys[:-1]])


@jax.custom_vjp
def _lstm_layer(x, wx, wh, b):
    """One LSTM layer, x (B, S, D) -> hs (B, S, H)."""
    return _lstm_layer_fwd(x, wx, wh, b)[0]


def _lstm_layer_fwd(x, wx, wh, b):
    xt = jnp.moveaxis(x, 1, 0)
    hs, cs, acts = _recurrence(xt, wx, wh, b)
    return jnp.moveaxis(hs, 0, 1), (xt, _shift(hs), cs, acts, wx, wh, b)


def _lstm_layer_bwd(res, dhs):
    """The reverse loop carries only (dh, dc) and emits each step's gate
    gradient dG_t; the weight gradients are then one contraction each over
    all positions, accumulated in f32."""
    xt, h_prev, cs, acts, wx, wh, b = res

    def step(carry, ys):
        dh, dc = carry
        dh_t, (i, f, g, o), c, c_prev = ys
        dh = (dh + dh_t).astype(F32)
        tc = jnp.tanh(c)
        dc = dc + dh * o * (1.0 - tc * tc)
        dgates = jnp.concatenate(
            [dc * g * i * (1.0 - i), dc * c_prev * f * (1.0 - f),
             dc * i * (1.0 - g * g), dh * tc * o * (1.0 - o)],
            axis=-1).astype(wh.dtype)
        return (dgates @ wh.T, dc * f), dgates

    init = (jnp.zeros_like(h_prev[0]), jnp.zeros_like(cs[0]))
    _, dg = lax.scan(step, init, (jnp.moveaxis(dhs, 1, 0), acts, cs,
                                  _shift(cs)), reverse=True)

    def contract(a, w):  # the sum over every position of a_t^T dG_t
        return jnp.einsum("sbi,sbj->ij", a, dg,
                          preferred_element_type=F32).astype(w.dtype)

    dx = jnp.moveaxis(dg @ wx.T, 0, 1)
    db = dg.sum((0, 1), dtype=F32).astype(b.dtype)
    return dx, contract(xt, wx), contract(h_prev, wh), db


_lstm_layer.defvjp(_lstm_layer_fwd, _lstm_layer_bwd)


def forward(params: PyTree, tokens: jax.Array) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V)."""
    emb = jnp.take(params["embed"], tokens, axis=0)       # (B, S, E)
    x = emb @ params["proj_in"]                            # (B, S, H_in)
    layers = params["layers"]
    for l in range(layers["wx"].shape[0]):
        # the slice stays outside the layer, so wx's gradient fills its leaf
        x = _lstm_layer(x, layers["wx"][l][:x.shape[-1]], layers["wh"][l],
                        layers["b"][l])
    return x @ params["head"]


def loss_fn(params: PyTree, batch: dict) -> tuple[jax.Array, dict]:
    logits = forward(params, batch["tokens"])
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(F32))
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(F32))
    return nll, {"loss": nll, "acc": acc}
