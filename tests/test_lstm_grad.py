"""The char-LSTM's hand-written backward pass against plain autodiff of
the per-step cell, and the shape of the program it traces to."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.models import lstm
from repro.models.params import Leaf

VOCAB, BATCH, SEQ, CLIENTS = 11, 2, 5, 3


def _cell(x, h, c, wx, wh, b):
    gates = x @ wx + h @ wh + b
    i, f, g, o = jnp.split(gates.astype(jnp.float32), 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(x.dtype), c


def _autodiff_loss(params, batch):
    """The model as a per-step scan of the cell, differentiated by JAX."""
    x = jnp.take(params["embed"], batch["tokens"], axis=0) @ params["proj_in"]
    layers = params["layers"]
    d_hidden = layers["wh"].shape[1]
    for l in range(layers["wx"].shape[0]):
        wx = layers["wx"][l][:x.shape[-1]]
        wh, b = layers["wh"][l], layers["b"][l]

        def step(carry, xt):
            h, c = _cell(xt, *carry, wx, wh, b)
            return (h, c), h

        init = (jnp.zeros((x.shape[0], d_hidden), x.dtype),
                jnp.zeros((x.shape[0], d_hidden), jnp.float32))
        _, hs = lax.scan(step, init, jnp.moveaxis(x, 1, 0))
        x = jnp.moveaxis(hs, 0, 1)
    logp = jax.nn.log_softmax((x @ params["head"]).astype(jnp.float32))
    return -jnp.take_along_axis(logp, batch["labels"][..., None],
                                axis=-1).mean()


def _clients(d_embed, d_hidden, dtype, seed=0):
    """CLIENTS clients' random weights (biases too) and token batches."""
    struct = lstm.param_struct(VOCAB, d_embed=d_embed, d_hidden=d_hidden)
    leaves, tree = jax.tree.flatten(
        struct, is_leaf=lambda x: isinstance(x, Leaf))
    rng = np.random.default_rng(seed)
    params = jax.tree.unflatten(tree, [
        jnp.asarray(rng.normal(0, 0.5, (CLIENTS,) + leaf.shape), dtype)
        for leaf in leaves])
    toks = rng.integers(0, VOCAB, (CLIENTS, BATCH, SEQ + 1))
    batch = {"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
             "labels": jnp.asarray(toks[..., 1:], jnp.int32)}
    return params, batch


def _grads(loss, params, batch):
    return jax.jit(jax.vmap(jax.value_and_grad(loss)))(params, batch)


@pytest.mark.parametrize("d_embed,d_hidden", [(8, 16), (32, 16)])
def test_lstm_grad_matches_autodiff(d_embed, d_hidden):
    """Loss and every leaf's gradient equal plain autodiff of the cell at
    full f32 precision, with the input wider and narrower than the hidden
    width (both sides of the ``wx`` row slice)."""
    params, batch = _clients(d_embed, d_hidden, jnp.float32)
    with jax.default_matmul_precision("highest"):
        loss, grads = _grads(lambda p, b: lstm.loss_fn(p, b)[0], params, batch)
        want_loss, want = _grads(_autodiff_loss, params, batch)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5, atol=1e-5)
    paths = []
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        paths.append(jax.tree_util.keystr(path))
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5,
                                   err_msg=paths[-1])
        assert float(jnp.abs(w).max()) > 0, paths[-1]
    assert len(paths) == 6, paths


def test_lstm_grad_bf16_params():
    """bfloat16 weights: gradients keep the leaves' dtype and stay within
    bfloat16 rounding of autodiff run on the same bfloat16 weights."""
    params, batch = _clients(32, 16, jnp.bfloat16)
    loss, grads = _grads(lambda p, b: lstm.loss_fn(p, b)[0], params, batch)
    want_loss, want = _grads(_autodiff_loss, params, batch)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-2)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert g.dtype == jnp.bfloat16
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) <= 5e-2 * np.linalg.norm(w)


def _eqns(jaxpr, primitive):
    """Every ``primitive`` equation of ``jaxpr`` and of the jaxprs in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, primitive)


def test_lstm_grad_has_no_weight_sized_loop_state():
    """In the traced gradient, no scan carries an array of a weight's shape
    (the per-step accumulate of the weight gradients), and each layer's
    forward and backward loop holds exactly one product with a weight:
    ``h @ wh`` and ``dG_t @ wh^T``."""
    d_embed, d_hidden = 32, 16
    params, batch = _clients(d_embed, d_hidden, jnp.float32)
    params, batch = jax.tree.map(lambda x: x[0], (params, batch))
    layers = params["layers"]
    weight = set()
    for l in range(layers["wx"].shape[0]):
        rows = d_embed if l == 0 else d_hidden
        for shape in (layers["wx"][l][:rows].shape, layers["wh"][l].shape):
            weight |= {shape, shape[::-1]}
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: lstm.loss_fn(p, batch)[0]))(
        params).jaxpr
    scans = list(_eqns(jaxpr, "scan"))
    assert sorted(e.params["reverse"] for e in scans) == [False] * 2 + [True] * 2
    for eqn in scans:
        first = eqn.params["num_consts"]
        carry = eqn.invars[first:first + eqn.params["num_carry"]]
        assert not [v.aval.shape for v in carry if v.aval.shape in weight]
        against_weight = [
            d for d in _eqns(eqn.params["jaxpr"].jaxpr, "dot_general")
            if any(v.aval.shape in weight for v in d.invars + d.outvars)]
        assert len(against_weight) == 1, eqn.params["reverse"]
