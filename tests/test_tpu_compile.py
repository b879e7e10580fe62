"""Every Pallas kernel compiles for a described TPU v5e chip.

Interpret mode (the rest of the suite) checks what a kernel computes; it
cannot see what the TPU compiler refuses: a block whose last two dims are
neither (8, 128)-aligned nor the whole array, a scalar stored to VMEM. This
file compiles each kernel for one chip of a described ``v5e:2x2`` topology
(no chip needed) at the packed width of the paper's char-LSTM client —
8704 x 128 f32 rows, K = d + 1 = 5 — alone and, where the engine does so,
under ``vmap`` over clients, and checks that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_sgdm import kernel as sgdm_k
from repro.kernels.gossip_mix import kernel as mix_k
from repro.kernels.quant_gossip import kernel as quant_k

ROWS = 8704          # packed rows of the 2-layer, 256-hidden char-LSTM
K = 5                # self + d = 4 received buffers
N_BLOCKS = ROWS // mix_k.DEFAULT_BLOCK_ROWS
TOPK = 11141         # topk_ef's k = 1% of the packed LSTM buffer
F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32

# name -> (kernel call, argument (shape, dtype) list)
KERNELS = {
    "gossip_mix_2d": (
        lambda s, w: mix_k.gossip_mix_2d(s, w),
        [((K, ROWS, 128), F32), ((K,), F32)]),
    "gossip_mix_2d_alive": (
        lambda s, w, a: mix_k.gossip_mix_2d(s, w, a),
        [((K, ROWS, 128), F32), ((K,), F32), ((K,), F32)]),
    "gossip_mix_2d_trimmed": (
        lambda s, u, l: mix_k.gossip_mix_2d_trimmed(s, u, l, trim=1),
        [((K, ROWS, 128), F32), ((K,), F32), ((K,), F32)]),
    "gossip_mix_2d_trimmed_quant": (
        lambda f, q, s, u, l: mix_k.gossip_mix_2d_trimmed_quant(
            f, q, s, u, l, trim=1),
        [((ROWS, 128), F32), ((K - 1, ROWS, 128), I8), ((K - 1, 1), F32),
         ((K,), F32), ((K,), F32)]),
    "gossip_mix_2d_trimmed_quant_blockwise": (
        lambda f, q, s, u, l: mix_k.gossip_mix_2d_trimmed_quant(
            f, q, s, u, l, trim=1),
        [((ROWS, 128), F32), ((K - 1, ROWS, 128), I8),
         ((K - 1, N_BLOCKS), F32), ((K,), F32), ((K,), F32)]),
    "sqnorms_2d": (
        lambda b: mix_k.sqnorms_2d(b),
        [((ROWS, 128), F32)]),
    "quantize_2d": (
        lambda x, s: quant_k.quantize_2d(x, s),
        [((ROWS, 128), F32), ((), F32)]),
    "dequant_accumulate_2d": (
        lambda q, s, a: quant_k.dequant_accumulate_2d(q, s, a),
        [((ROWS, 128), I8), ((1, 3), F32), ((ROWS, 128), F32)]),
    "quantize_2d_blockwise": (
        lambda x, s: quant_k.quantize_2d_blockwise(x, s),
        [((ROWS, 128), F32), ((N_BLOCKS,), F32)]),
    "dequant_accumulate_2d_blockwise": (
        lambda q, s, a: quant_k.dequant_accumulate_2d_blockwise(q, s, a),
        [((ROWS, 128), I8), ((N_BLOCKS, 3), F32), ((ROWS, 128), F32)]),
    "scatter_accumulate_2d": (
        lambda v, i, s, a: quant_k.scatter_accumulate_2d(v, i, s, a),
        [((TOPK,), F32), ((TOPK,), I32), ((2,), F32), ((ROWS, 128), F32)]),
    # the stacked engine vmaps the encode over clients (int8_block) and the
    # telemetry sqnorm pass over clients (f32): batching adds a grid dim and
    # a leading block dim to every operand, scalar rows included
    "quantize_2d_blockwise_vmapped": (
        jax.vmap(lambda x, s: quant_k.quantize_2d_blockwise(x, s)),
        [((8, ROWS, 128), F32), ((8, N_BLOCKS), F32)]),
    "sqnorms_2d_vmapped": (
        jax.vmap(lambda b: mix_k.sqnorms_2d(b)),
        [((8, ROWS, 128), F32)]),
    "sgdm_2d": (
        lambda w, v, g, s: sgdm_k.sgdm_2d(w, v, g, s),
        [((ROWS, 128), F32), ((ROWS, 128), F32), ((ROWS, 128), F32),
         ((1, 2), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# the char-LSTM client of the benchmark's one-chip cell, per leaf
LSTM_LEAVES = {"embed": (53, 128), "proj_in": (128, 256),
               "wx": (2, 256, 1024), "wh": (2, 256, 1024), "b": (2, 1024),
               "head": (256, 53)}


def test_stacked_f32_round_reads_the_state_few_times(one_chip,
                                                     no_compile_cache):
    """The stacked f32 sync round of 128 char-LSTM clients (1,103,744 f32
    each) on a degree-4 expander, compiled for one described v5e chip,
    accesses at most 20x the state's bytes by XLA's own cost analysis: the
    leaves are gathered and mixed where they are, with no packed copy of
    the state and no (n, d+1, ...) stack (which took ~55x)."""
    import math

    from repro.core import engine, gossip, topology

    n = 128
    spec = gossip.make_gossip_spec(topology.expander_overlay(n, 4, seed=0))
    ex = engine.build_gossip_executor(
        engine.GossipEngineConfig(substrate="stacked"), spec)
    tree = {k: jax.ShapeDtypeStruct((n,) + s, F32, sharding=one_chip)
            for k, s in LSTM_LEAVES.items()}
    alive = jax.ShapeDtypeStruct((n,), F32, sharding=one_chip)
    cost = jax.jit(lambda t, a: ex(t, alive=a)).lower(
        tree, alive).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    state = n * sum(math.prod(s) for s in LSTM_LEAVES.values()) * 4
    assert cost["bytes accessed"] <= 20 * state, (
        cost["bytes accessed"] / state)
