"""Pallas TPU kernels: int8 quantize / dequantize for gossip payloads.

Beyond-paper optimization for the *collective* roofline term: gossip payloads
are symmetrically quantized to int8 before the ppermute, cutting ICI bytes 4x
(f32) or 2x (bf16). The amax reduction is a cheap jnp reduce in the wrapper;
the kernels do the per-tile scale/round/clip and the fused
dequantize-accumulate.

Two scale granularities share the same kernel bodies:

* per-buffer (`quantize_2d` / `dequant_accumulate_2d`): one f32 scale for the
  whole buffer — error is governed by the buffer-wide amax;
* per-row-block (`quantize_2d_blockwise` / `dequant_accumulate_2d_blockwise`):
  one f32 scale per (block_rows x LANE) kernel tile — a tile of
  small-magnitude parameters no longer inherits the quantization step of
  the buffer's global amax. The payload traffic is identical.

Every scalar operand (scales, mixing weights, alive weights, and the sparse
entries of `scatter_accumulate_2d`) is one (1, m) f32/int32 row resident in
SMEM for the whole grid; a tile reads its own group of scalars at
``program_id * n``. The TPU compiler refuses per-tile VMEM blocks of one
scalar (a block's last two dims must be multiples of (8, 128) or the whole
array), and it cannot store a scalar into VMEM. The leading unit dim keeps
that rule satisfied when the engine vmaps a kernel over clients: the batch
dim then lands in front of the whole (1, m) row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BLOCK_ROWS = 256

# the whole (1, m) operand in SMEM, read with scalar loads
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _smem_row(x, dtype=jnp.float32):
    return x.reshape(1, -1).astype(dtype)


def _tile_scalars(s_ref, n):
    """This tile's n scalars from a (1, m) SMEM operand that holds either
    one group of n for the whole buffer or one group per grid tile."""
    base = pl.program_id(0) * n if s_ref.shape[1] > n else 0
    return [s_ref[0, base + j] for j in range(n)]


def _quant_kernel(x_ref, s_ref, q_ref):
    (scale,) = _tile_scalars(s_ref, 1)
    x = x_ref[...].astype(jnp.float32) * (1.0 / scale)
    q_ref[...] = jnp.clip(jnp.round(x), -127.0, 127.0).astype(jnp.int8)


def _dequant_acc_kernel(q_ref, s_ref, acc_ref, o_ref, *, n_scalars):
    """o = acc + alive * c * (q * s).

    Per tile, s_ref holds (scale, c) or (scale, c, alive) — the
    failure-aware gossip path folds the sender's (renormalized) alive
    weight into the same fused pass instead of adding a masking pass.
    """
    scale, c, *alive = _tile_scalars(s_ref, n_scalars)
    if alive:
        c = c * alive[0]
    o_ref[...] = (acc_ref[...].astype(jnp.float32)
                  + c * scale * q_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def _scatter_acc_kernel(v_ref, i_ref, s_ref, acc_ref, o_ref, *, n_scalars):
    """o = acc + alive * c * scatter(vals at flat idx).

    ``v_ref`` / ``i_ref`` hold the sparse entries in SMEM — (k,) f32 values
    and int32 flat indices into THIS (dense) buffer, zero-padded past k
    (val 0 at idx 0 is a no-op). ``s_ref`` holds (c,) or (c, alive) — the
    failure-aware gossip path folds the sender's renormalized alive weight
    into the same fused pass, exactly like ``_dequant_acc_kernel``. Grid
    tiles cover the dense accumulator; every tile walks all k entries and
    lands the ones inside its flat range as a masked update of one lane
    row (top-k keeps k small — the walk is k scalar steps per tile, while
    the dense copy stays one vector pass).
    """
    c, *alive = _tile_scalars(s_ref, n_scalars)
    if alive:
        c = c * alive[0]
    block_rows, lane = o_ref.shape
    tile = block_rows * lane
    base = pl.program_id(0) * tile
    o_ref[...] = acc_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, lane), 1)

    def body(e, carry):
        j = i_ref[0, e] - base

        @pl.when((j >= 0) & (j < tile))
        def _():
            r = j // lane
            row = o_ref[pl.ds(r, 1), :]
            upd = (row.astype(jnp.float32) + c * v_ref[0, e]
                   ).astype(row.dtype)
            o_ref[pl.ds(r, 1), :] = jnp.where(lanes == j - r * lane, upd, row)

        return carry

    jax.lax.fori_loop(0, v_ref.shape[1], body, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def scatter_accumulate_2d(vals: jax.Array, idx: jax.Array,
                          c_alive: jax.Array, acc: jax.Array, *,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = False) -> jax.Array:
    """Fused sparse scatter-accumulate over a packed (rows, LANE) buffer.

    ``vals`` / ``idx`` are the (k,) sparse entries (f32 / int32, zero-
    padded); ``c_alive`` is (1,) = (c,) or (2,) = (c, alive weight). The
    whole sparse set sits in SMEM for every grid tile."""
    rows, lane = acc.shape
    assert lane == LANE and rows % block_rows == 0
    assert vals.ndim == 1 and idx.shape == vals.shape, (vals.shape, idx.shape)
    n_scalars = int(c_alive.size)
    assert n_scalars in (1, 2), c_alive.shape
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_scatter_acc_kernel, n_scalars=n_scalars),
        grid=(rows // block_rows,),
        in_specs=[_SMEM, _SMEM, _SMEM, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), acc.dtype),
        interpret=interpret,
    )(_smem_row(vals), _smem_row(idx, jnp.int32), _smem_row(c_alive), acc)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_2d(x: jax.Array, scale: jax.Array, *,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = False) -> jax.Array:
    rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _quant_kernel,
        grid=(rows // block_rows,),
        in_specs=[blk, _SMEM],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int8),
        interpret=interpret,
    )(x, _smem_row(scale))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dequant_accumulate_2d(q: jax.Array, scale_c: jax.Array, acc: jax.Array, *,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = False) -> jax.Array:
    """scale_c: (1, 2) = (scale, c) or (1, 3) = (scale, c, alive weight)."""
    rows, lane = q.shape
    assert lane == LANE and rows % block_rows == 0
    n_scalars = int(scale_c.size)
    assert n_scalars in (2, 3), scale_c.shape
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_dequant_acc_kernel, n_scalars=n_scalars),
        grid=(rows // block_rows,),
        in_specs=[blk, _SMEM, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), acc.dtype),
        interpret=interpret,
    )(q, _smem_row(scale_c), acc)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_2d_blockwise(x: jax.Array, scales: jax.Array, *,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = False) -> jax.Array:
    """Per-row-block quantize: ``scales`` is (n_blocks,), one f32 scale per
    (block_rows, LANE) tile; tile i reads scales[i]."""
    rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0
    n_blocks = rows // block_rows
    assert scales.shape == (n_blocks,), (scales.shape, n_blocks)
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _quant_kernel,
        grid=(n_blocks,),
        in_specs=[blk, _SMEM],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int8),
        interpret=interpret,
    )(x, _smem_row(scales))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dequant_accumulate_2d_blockwise(q: jax.Array, scale_c: jax.Array,
                                    acc: jax.Array, *,
                                    block_rows: int = DEFAULT_BLOCK_ROWS,
                                    interpret: bool = False) -> jax.Array:
    """Per-row-block fused dequant-accumulate: ``scale_c`` is (n_blocks, 2)
    rows of (scale_b, c) or (n_blocks, 3) rows of (scale_b, c, alive weight) —
    tile i reads its own row, same kernel body as the per-buffer variant."""
    rows, lane = q.shape
    assert lane == LANE and rows % block_rows == 0
    n_blocks = rows // block_rows
    n_scalars = scale_c.shape[-1]
    assert scale_c.shape == (n_blocks, n_scalars) and n_scalars in (2, 3), \
        scale_c.shape
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_dequant_acc_kernel, n_scalars=n_scalars),
        grid=(n_blocks,),
        in_specs=[blk, _SMEM, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), acc.dtype),
        interpret=interpret,
    )(q, _smem_row(scale_c), acc)
