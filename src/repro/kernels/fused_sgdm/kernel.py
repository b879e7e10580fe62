"""Pallas TPU kernel: fused DFedAvgM heavy-ball update (paper eq. 2.1).

    v' = beta * v - lr * g
    w' = w + v'

runs K times per communication round over the whole parameter state — a pure
memory-bound streaming op. Fused: 3 reads (w, v, g) + 2 writes (w', v') per
element; the unfused jnp graph without XLA fusion would be 5 reads + 3 writes
(and on TPU the fused kernel also guarantees a single pass regardless of how
XLA schedules the surrounding graph).

Accumulation is in f32 even for bf16 state, matching `dfedavg.momentum_update`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 256


def _sgdm_kernel(w_ref, v_ref, g_ref, s_ref, wo_ref, vo_ref):
    """s = (lr, beta) as a (1, 2) f32 VMEM operand."""
    lr = s_ref[0, 0]
    beta = s_ref[0, 1]
    v = beta * v_ref[...].astype(jnp.float32) - lr * g_ref[...].astype(jnp.float32)
    vo_ref[...] = v.astype(vo_ref.dtype)
    wo_ref[...] = (w_ref[...].astype(jnp.float32) + v).astype(wo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sgdm_2d(w: jax.Array, v: jax.Array, g: jax.Array, scalars: jax.Array, *,
            block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """w, v, g: (rows, LANE) with rows % block_rows == 0; scalars: (1, 2) f32."""
    rows, lane = w.shape
    assert lane == LANE and rows % block_rows == 0
    grid = (rows // block_rows,)
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _sgdm_kernel,
        grid=grid,
        in_specs=[blk, blk, blk, pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), w.dtype),
                   jax.ShapeDtypeStruct((rows, LANE), v.dtype)],
        interpret=interpret,
        name="fused_sgdm",
    )(w, v, g, scalars)
