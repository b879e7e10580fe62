"""FLOP counts from shapes, against hand counts at a tiny size, and the
peaks table."""
import json
import os

import pytest

from perf import harness
from perf.configs import char_lstm

TINY = {"d_embed": 4, "d_hidden": 8, "vocab": 5, "n_layers": 2}


def test_lstm_flops_per_token_by_hand():
    # forward, per position: embed -> hidden projection 2*4*8 = 64; each
    # layer's gates 2*(8 + 8)*32 = 1024, two layers 2048; head 2*8*5 = 80.
    # Backward is twice the forward.
    assert char_lstm.flops_per_token(TINY) == 3 * (64 + 2048 + 80)


def dot_flops(jaxpr, times=1) -> int:
    """2 * M * N * K of every matrix product in a jaxpr, loop bodies counted
    once per trip."""
    import math

    from jax.extend import core as jcore

    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            k = math.prod(eqn.invars[0].aval.shape[d] for d in contract)
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * k * times
        trips = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    total += dot_flops(sub.jaxpr, times * trips)
                elif isinstance(sub, jcore.Jaxpr):
                    total += dot_flops(sub, times * trips)
    return total


def test_lstm_flops_match_the_reference_matmuls():
    """The count equals the matrix products of the reference's forward,
    and of its forward and backward together, at a tiny size."""
    import jax
    import jax.numpy as jnp

    from perf.configs import char_lstm_ref as ref

    p = jax.tree.map(lambda x: x[0],
                     char_lstm.make_weights(TINY, 1, 0))
    toks = jnp.zeros((2, 16), jnp.int32)
    per_token = char_lstm.flops_per_token(TINY)
    fwd = jax.make_jaxpr(ref.logits)(p, toks)
    assert dot_flops(fwd.jaxpr) == per_token / 3 * toks.size
    both = jax.make_jaxpr(jax.grad(ref.loss))(p, toks, toks)
    assert dot_flops(both.jaxpr) == per_token * toks.size


def test_paper_model_size():
    cfg = harness.read_json("perf", "configs", "char_lstm.json")
    n = sum(int(__import__("math").prod(shape))
            for shape, _ in char_lstm.layout(cfg).values())
    assert n == cfg["params_per_client"] == 1103744
    # 6.57 MFLOP per training position at the paper's widths
    assert char_lstm.flops_per_token(cfg) == pytest.approx(6.569472e6)


def test_peaks_table():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12 and p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
    with open(os.path.join(harness.ROOT, "perf", "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
