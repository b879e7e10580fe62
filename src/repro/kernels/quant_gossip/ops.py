"""Jitted public wrappers for quant_gossip (any shape/dtype payloads)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.quant_gossip import kernel as _k
from repro.kernels.quant_gossip import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl",))
def quantize_int8(x: jax.Array, impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: returns (q:int8 same shape, scale:f32 scalar)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.quantize(x, scale), scale
    shape = x.shape
    t = x.size
    tile = _k.DEFAULT_BLOCK_ROWS * _k.LANE
    pad = (-t) % tile
    xf = x.reshape(-1)
    if pad:
        xf = jnp.pad(xf, (0, pad))
    q = _k.quantize_2d(xf.reshape(-1, _k.LANE), scale,
                       interpret=(impl == "pallas_interpret"))
    return q.reshape(-1)[:t].reshape(shape), scale


@functools.partial(jax.jit, static_argnames=("dtype", "impl"))
def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32,
                    impl: str = "auto") -> jax.Array:
    """Plain dequantize (no accumulate)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


@functools.partial(jax.jit, static_argnames=("impl",))
def dequant_accumulate(q: jax.Array, scale: jax.Array, c, acc: jax.Array,
                       impl: str = "auto") -> jax.Array:
    """acc + c * dequant(q): the fused per-neighbor gossip accumulation."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.dequant_accumulate(q, scale, jnp.asarray(c), acc)
    shape = acc.shape
    t = acc.size
    tile = _k.DEFAULT_BLOCK_ROWS * _k.LANE
    pad = (-t) % tile
    def prep(x):
        xf = x.reshape(-1)
        if pad:
            xf = jnp.pad(xf, (0, pad))
        return xf.reshape(-1, _k.LANE)
    sc = jnp.stack([scale.astype(jnp.float32),
                    jnp.asarray(c, jnp.float32)]).reshape(1, 2)
    out = _k.dequant_accumulate_2d(prep(q), sc, prep(acc),
                                   interpret=(impl == "pallas_interpret"))
    return out.reshape(-1)[:t].reshape(shape)


# ---------------------------------------------------- wire format (one
# collective per schedule): the 4-byte f32 scale rides inside the int8
# buffer as one trailing lane row, so the gossip round ships d single
# ppermutes instead of d (payload, scale) pairs. The extra row is 128
# bytes against a >= 32 KiB tile-aligned payload (<0.4% wire overhead),
# and split_wire's static slice restores the kernel-ready (rows, LANE)
# layout without copies the compiler can't elide.
def fold_scale_into_wire(q: jax.Array, scale: jax.Array) -> jax.Array:
    """(rows, LANE) int8 + f32 scalar -> (rows+1, LANE) int8 wire buffer."""
    sbytes = jax.lax.bitcast_convert_type(
        scale.astype(jnp.float32).reshape(1), jnp.int8).reshape(4)
    row = jnp.zeros((1, q.shape[1]), jnp.int8).at[0, :4].set(sbytes)
    return jnp.concatenate([q, row], axis=0)


def split_wire(wire: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Invert :func:`fold_scale_into_wire`: (payload, f32 scale scalar)."""
    scale = jax.lax.bitcast_convert_type(wire[-1, :4].reshape(1, 4),
                                         jnp.float32).reshape(())
    return wire[:-1], scale


# ------------------------------------- per-row-block wire format: one f32
# scale per (block_rows, LANE) kernel tile instead of per buffer, so a tile
# of small-magnitude parameters (a norm, a bias run) no longer inherits the
# quantization step of the buffer-wide amax (the PR-1 follow-up). All
# n_blocks scales ride inside the shipped int8 buffer as lane-folded
# trailing rows (4 bytes each, 32 scales per row — the PR-3 fold
# generalized), so the gossip round still ships exactly d collectives.
def fold_scales_into_wire(q: jax.Array, scales: jax.Array) -> jax.Array:
    """(rows, LANE) int8 + (n_blocks,) f32 -> (rows + scale_rows, LANE) int8
    wire buffer (see :func:`repro.core.packing.scale_rows`)."""
    from repro.core import packing
    n_blocks = scales.shape[0]
    tail_rows = packing.scale_rows(n_blocks)
    sbytes = jax.lax.bitcast_convert_type(
        scales.astype(jnp.float32), jnp.int8).reshape(-1)
    tail = jnp.zeros((tail_rows * q.shape[1],), jnp.int8)
    tail = tail.at[:sbytes.shape[0]].set(sbytes)
    return jnp.concatenate([q, tail.reshape(tail_rows, q.shape[1])], axis=0)


def split_wire_blockwise(wire: jax.Array,
                         n_blocks: int) -> tuple[jax.Array, jax.Array]:
    """Invert :func:`fold_scales_into_wire`: (payload, (n_blocks,) f32
    scales). All slices are static given ``n_blocks`` (baked from the
    PackSpec), so this is jit-friendly like PR-3's :func:`split_wire`."""
    from repro.core import packing
    tail_rows = packing.scale_rows(n_blocks)
    sbytes = wire[-tail_rows:].reshape(-1)[:packing.SCALE_BYTES * n_blocks]
    scales = jax.lax.bitcast_convert_type(
        sbytes.reshape(n_blocks, packing.SCALE_BYTES), jnp.float32)
    return wire[:-tail_rows], scales.reshape(n_blocks)


# ------------------------------------------- sparse top-k wire format (one
# collective per schedule): k f32 values and their k int32 flat indices both
# bitcast into int8 lane rows of ONE shipped buffer — the same fold that
# carries quant scales, taken to its limit: the whole payload is 8k bytes
# (vs 4 bytes/element dense), so k_fraction = 0.01 ships ~2% of the f32
# wire. Sections are padded to whole rows independently (see
# repro.core.packing.topk_wire_rows) so every slice below is static.
def fold_topk_into_wire(vals: jax.Array, idx: jax.Array) -> jax.Array:
    """(k,) f32 values + (k,) int32 flat indices -> (topk_wire_rows(k), LANE)
    int8 wire buffer (values section first, indices section after)."""
    from repro.core import packing
    half = packing.topk_wire_rows(vals.shape[0]) // 2

    def section(x):
        b = jax.lax.bitcast_convert_type(x, jnp.int8).reshape(-1)
        out = jnp.zeros((half * packing.LANE,), jnp.int8)
        return out.at[:b.shape[0]].set(b).reshape(half, packing.LANE)

    return jnp.concatenate([section(vals.astype(jnp.float32)),
                            section(idx.astype(jnp.int32))], axis=0)


def split_topk_wire(wire: jax.Array, k: int
                    ) -> tuple[jax.Array, jax.Array]:
    """Invert :func:`fold_topk_into_wire`: ((k,) f32 values, (k,) int32 flat
    indices). All slices are static given ``k`` (baked from the codec's
    k_fraction and the PackSpec rows)."""
    from repro.core import packing
    half = wire.shape[0] // 2

    def section(rows, dtype):
        b = rows.reshape(-1)[:packing.SCALE_BYTES * k]
        return jax.lax.bitcast_convert_type(
            b.reshape(k, packing.SCALE_BYTES), dtype).reshape(k)

    return section(wire[:half], jnp.float32), section(wire[half:], jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "impl"))
def scatter_accumulate_packed(vals: jax.Array, idx: jax.Array, c,
                              acc: jax.Array, alive=None, *,
                              block_rows: int = _k.DEFAULT_BLOCK_ROWS,
                              impl: str = "auto") -> jax.Array:
    """Fused acc + alive * c * scatter(vals at flat idx) for pre-packed
    (rows, LANE) buffers — the sparse top-k analogue of
    :func:`dequant_accumulate_packed`: the dense accumulator is read and
    written exactly once while the k sparse entries land in place.

    ``vals`` / ``idx`` are the flat (k,) arrays off the wire
    (:func:`split_topk_wire`); ``alive`` (traced scalar) is the
    failure-aware per-sender weight, folded into the same fused pass.
    """
    rows, lane = acc.shape
    assert lane == _k.LANE and rows % block_rows == 0, (acc.shape, block_rows)
    assert vals.shape == idx.shape and vals.ndim == 1, (vals.shape, idx.shape)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        eff_c = jnp.asarray(c, jnp.float32)
        if alive is not None:
            eff_c = eff_c * jnp.asarray(alive, jnp.float32)
        return _ref.scatter_accumulate(vals, idx, eff_c, acc)
    scalars = [jnp.asarray(c, jnp.float32)]
    if alive is not None:
        scalars.append(jnp.asarray(alive, jnp.float32))
    return _k.scatter_accumulate_2d(
        vals, idx, jnp.stack(scalars), acc, block_rows=block_rows,
        interpret=(impl == "pallas_interpret"))


def dequantize_packed(q: jax.Array, scale: jax.Array,
                      dtype=jnp.float32) -> jax.Array:
    """Plain dequantize of a per-buffer-scaled packed payload (the stacked
    engine substrate's gather source; the shard_map substrate uses the fused
    dequant-accumulate kernels instead)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def dequantize_packed_blockwise(q: jax.Array, scales: jax.Array,
                                dtype=jnp.float32, *,
                                block_rows: int = _k.DEFAULT_BLOCK_ROWS
                                ) -> jax.Array:
    """Plain dequantize with per-row-block scales (one f32 per
    ``(block_rows, LANE)`` tile)."""
    deq = q.astype(jnp.float32) * jnp.repeat(scales, block_rows)[:, None]
    return deq.astype(dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "impl"))
def quantize_packed_blockwise(buf: jax.Array, *,
                              block_rows: int = _k.DEFAULT_BLOCK_ROWS,
                              impl: str = "auto"
                              ) -> tuple[jax.Array, jax.Array]:
    """Per-row-block int8 quantize of a pre-packed (rows, LANE) buffer:
    returns (q, (n_blocks,) f32 scales), scale b = block-b amax / 127."""
    rows, lane = buf.shape
    assert lane == _k.LANE and rows % block_rows == 0, (buf.shape, block_rows)
    n_blocks = rows // block_rows
    amax = jnp.max(jnp.abs(buf.astype(jnp.float32)
                           .reshape(n_blocks, block_rows * lane)), axis=1)
    scales = jnp.maximum(amax, 1e-12) / 127.0
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.quantize_blockwise(buf, scales, block_rows), scales
    q = _k.quantize_2d_blockwise(buf, scales, block_rows=block_rows,
                                 interpret=(impl == "pallas_interpret"))
    return q, scales


@functools.partial(jax.jit, static_argnames=("block_rows", "impl"))
def dequant_accumulate_packed_blockwise(q: jax.Array, scales: jax.Array,
                                        c, acc: jax.Array, alive=None, *,
                                        block_rows: int = _k.DEFAULT_BLOCK_ROWS,
                                        impl: str = "auto") -> jax.Array:
    """Fused acc + alive * c * dequant(q) with per-row-block scales — same
    single HBM pass as :func:`dequant_accumulate_packed`; only the scalar
    operand grows to one (scale_b, c[, alive]) row per tile."""
    rows, lane = q.shape
    assert lane == _k.LANE and rows % block_rows == 0, (q.shape, block_rows)
    assert acc.shape == q.shape, (acc.shape, q.shape)
    n_blocks = rows // block_rows
    assert scales.shape == (n_blocks,), (scales.shape, n_blocks)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        eff_c = jnp.asarray(c, jnp.float32)
        if alive is not None:
            eff_c = eff_c * jnp.asarray(alive, jnp.float32)
        return _ref.dequant_accumulate_blockwise(q, scales, eff_c, acc,
                                                 block_rows)
    cols = [scales.astype(jnp.float32),
            jnp.broadcast_to(jnp.asarray(c, jnp.float32), (n_blocks,))]
    if alive is not None:
        cols.append(jnp.broadcast_to(jnp.asarray(alive, jnp.float32),
                                     (n_blocks,)))
    sc = jnp.stack(cols, axis=1)
    return _k.dequant_accumulate_2d_blockwise(
        q, sc, acc, block_rows=block_rows,
        interpret=(impl == "pallas_interpret"))


# ------------------------------------------------- packed (rows, LANE) fast path
@functools.partial(jax.jit, static_argnames=("block_rows", "impl"))
def quantize_packed(buf: jax.Array, *, block_rows: int = _k.DEFAULT_BLOCK_ROWS,
                    impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """quantize_int8 for a pre-packed (rows, LANE) buffer (PackSpec layout):
    rows is already a tile multiple, so the kernel runs with no reshape/pad."""
    rows, lane = buf.shape
    assert lane == _k.LANE and rows % block_rows == 0, (buf.shape, block_rows)
    amax = jnp.max(jnp.abs(buf.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.quantize(buf, scale), scale
    q = _k.quantize_2d(buf, scale, block_rows=block_rows,
                       interpret=(impl == "pallas_interpret"))
    return q, scale


@functools.partial(jax.jit, static_argnames=("block_rows", "impl"))
def dequant_accumulate_packed(q: jax.Array, scale: jax.Array, c,
                              acc: jax.Array, alive=None, *,
                              block_rows: int = _k.DEFAULT_BLOCK_ROWS,
                              impl: str = "auto") -> jax.Array:
    """dequant_accumulate for pre-packed (rows, LANE) buffers: acc + c*scale*q
    fused in one HBM pass, no reshape/pad in the jitted step.

    ``alive`` (traced scalar) is the failure-aware gossip path's per-sender
    weight (receiver-alive x sender-alive, pre-renormalized); it folds into
    the same fused pass, so masking dead senders costs zero extra HBM traffic.
    """
    rows, lane = q.shape
    assert lane == _k.LANE and rows % block_rows == 0, (q.shape, block_rows)
    assert acc.shape == q.shape, (acc.shape, q.shape)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        eff_c = jnp.asarray(c, jnp.float32)
        if alive is not None:
            eff_c = eff_c * jnp.asarray(alive, jnp.float32)
        return _ref.dequant_accumulate(q, scale, eff_c, acc)
    scalars = [scale.astype(jnp.float32), jnp.asarray(c, jnp.float32)]
    if alive is not None:
        scalars.append(jnp.asarray(alive, jnp.float32))
    sc = jnp.stack(scalars).reshape(1, len(scalars))
    return _k.dequant_accumulate_2d(q, sc, acc, block_rows=block_rows,
                                    interpret=(impl == "pallas_interpret"))
