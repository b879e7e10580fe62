"""Pallas TPU kernel: fused weighted reduction of gossip payloads.

Computes ``out = sum_k w[k] * stack[k]`` over a stacked axis of K = d+1
buffers (self + d received neighbor shards) in a single HBM pass.

Why a kernel: the unfused jnp form materializes d intermediate adds, each a
full HBM read+write of the parameter shard; the paper's gossip runs every K
local steps on the *entire* parameter state, so this reduction is pure memory
traffic. The fused kernel reads (d+1) x bytes and writes 1 x bytes — the HBM
lower bound.

Failure-aware variant (paper §5.2): passing an ``alive`` vector (K,) —
``alive[0]`` for self, ``alive[k]`` = liveness of the k-th received schedule's
sender — switches to the renormalized reduction

    out = sum_k (w[k] * alive[k] / sum_j w[j] * alive[j]) * stack[k]

with a dead self falling back to the identity (``out = stack[0]``). The
renormalization is a K-element scalar fixup computed once per tile on the VPU,
so the masked reduction is still one HBM pass — this is what lets the elastic
runtime treat stragglers as a *data* change (the alive vector is a step
argument) instead of a recompile.

Layout: the wrapper flattens/pads the payload to (rows, 128) so tiles are
(sublane=8·m, lane=128)-aligned; the stacked operand is (K, rows, 128) and the
weight/alive vectors live in VMEM as (K, 1).

Byzantine-robust variants (the engine's ``screen`` layer):

* ``gossip_mix_2d_trimmed`` replaces the weighted sum with a coordinate-wise
  trimmed mean: per element, the live contributors are ranked by a stable
  O(K^2) comparison network (K = d+1 is tiny, fully unrolled on the VPU),
  the top/bottom ``trim`` values are dropped, and the output renormalizes
  the nonnegative weights over the survivors. Dead/gated senders carry
  ``live = 0`` and are invisible to the order statistics. Same one-HBM-pass
  structure as `_mix_kernel` — the ranking is K^2 elementwise compares over
  data already resident in VMEM.
* ``gossip_mix_2d_trimmed_quant`` is the dequant-side variant for the int8
  codecs: received payloads stay int8 on the wire and dequantize in-register
  (per-buffer or per-row-block scales) before the same trim reduction.
* ``sqnorms_2d`` computes per-row-block partial squared norms (reduced to
  per-lane partials on-chip), the per-sender pass behind the norm-clip
  screen.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BLOCK_ROWS = 256  # 256 x 128 x f32 = 128 KiB per buffer tile


def _mix_kernel(x_ref, w_ref, o_ref):
    """o = sum_k w[k] * x[k]; x tile: (K, BR, LANE), w: (K, 1), o: (BR, LANE)."""
    x = x_ref[...]
    w = w_ref[...]
    acc = jnp.zeros(o_ref.shape, dtype=jnp.float32)
    for k in range(x.shape[0]):  # K is small (d+1), unrolled on the VPU
        acc = acc + w[k, 0].astype(jnp.float32) * x[k].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _mix_alive_kernel(x_ref, w_ref, a_ref, o_ref):
    """Renormalized masked reduction (see module docstring).

    x tile: (K, BR, LANE); w: (K, 1) raw weights (w0, c, ..., c);
    a: (K, 1) alive weights (a[0] = self). Per-tile scalar math only —
    the payload traffic is identical to `_mix_kernel`.
    """
    x = x_ref[...]
    wa = w_ref[...].astype(jnp.float32) * a_ref[...].astype(jnp.float32)
    tot = jnp.sum(wa)
    # no renormalizable mass (all contributors gated/masked away) => the
    # identity fallback REPLACES the renormalized term: inv is zeroed so
    # tiny fractional mass cannot add a second copy of the row
    ok = (tot > 1e-12).astype(jnp.float32)
    inv = ok / jnp.maximum(tot, 1e-12)
    a_self = a_ref[0, 0].astype(jnp.float32)
    # dead self => identity row (weight 1 on x[0], 0 elsewhere)
    eff0 = a_self * wa[0, 0] * inv + (1.0 - a_self) + a_self * (1.0 - ok)
    acc = eff0 * x[0].astype(jnp.float32)
    for k in range(1, x.shape[0]):  # K is small (d+1), unrolled on the VPU
        acc = acc + (a_self * wa[k, 0] * inv) * x[k].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _trimmed_reduce(vals, u, lv, trim, out_shape):
    """Shared trim body: vals = list of K f32 (BR, LANE) tiles, u/lv (K, 1)
    weight/live vectors, trim a *static* per-side drop count. Returns the
    f32 trimmed renormalized mean tile (identity fallback included)."""
    k = len(vals)
    n_live = jnp.sum(lv)
    t = jnp.minimum(jnp.float32(trim),
                    jnp.maximum(jnp.floor((n_live - 1.0) * 0.5), 0.0))
    num = jnp.zeros(out_shape, jnp.float32)
    den = jnp.zeros(out_shape, jnp.float32)
    for i in range(k):  # K = d+1 is small: the network fully unrolls
        rank = jnp.zeros(out_shape, jnp.float32)
        for j in range(k):
            if j == i:
                continue
            # stable ranks (ties broken by stack index) => exactly
            # n_live - 2t survivors per element
            cmp = (vals[j] <= vals[i]) if j < i else (vals[j] < vals[i])
            rank = rank + lv[j, 0] * cmp.astype(jnp.float32)
        surv = lv[i, 0] * ((rank >= t)
                           & (rank < n_live - t)).astype(jnp.float32)
        num = num + surv * u[i, 0] * vals[i]
        den = den + surv * u[i, 0]
    ok = den > 1e-12
    mean = jnp.where(ok, num / jnp.maximum(den, 1e-12), vals[0])
    l0 = lv[0, 0]
    return l0 * mean + (1.0 - l0) * vals[0]


def _mix_trimmed_kernel(x_ref, u_ref, l_ref, o_ref, *, trim):
    """Coordinate-wise trimmed renormalized mean (see module docstring).

    x tile: (K, BR, LANE); u: (K, 1) nonnegative weights; l: (K, 1) 0/1
    participation flags (l[0] = self; 0 => identity fallback).
    """
    x = x_ref[...]
    u = u_ref[...].astype(jnp.float32)
    lv = l_ref[...].astype(jnp.float32)
    vals = [x[i].astype(jnp.float32) for i in range(x.shape[0])]
    o_ref[...] = _trimmed_reduce(vals, u, lv, trim,
                                 o_ref.shape).astype(o_ref.dtype)


def _mix_trimmed_quant_kernel(f_ref, q_ref, s_ref, u_ref, l_ref, o_ref, *,
                              trim, n_s):
    """Dequant-side trimmed mix: the self tile is fresh f32, the K-1
    received tiles are int8 with their (per-buffer or per-row-block) f32
    scales in s_ref, the (K-1, n_s) array as one (1, m) SMEM row (see
    `repro.kernels.quant_gossip.kernel`) — dequantized
    in-register, then the same trim reduction as `_mix_trimmed_kernel`.
    """
    fresh = f_ref[...].astype(jnp.float32)
    q = q_ref[...]
    col = pl.program_id(0) if n_s > 1 else 0
    u = u_ref[...].astype(jnp.float32)
    lv = l_ref[...].astype(jnp.float32)
    vals = [fresh] + [q[i].astype(jnp.float32) * s_ref[0, i * n_s + col]
                      for i in range(q.shape[0])]
    o_ref[...] = _trimmed_reduce(vals, u, lv, trim,
                                 o_ref.shape).astype(o_ref.dtype)


def _sqnorm_kernel(x_ref, o_ref):
    """Per-lane partial squared norms of one (BR, LANE) tile: o = (1, LANE),
    the tile's own (1, LANE) slab of the (n_blocks, 1, LANE) output.
    The host-side wrapper finishes the reduction with one (n_blocks, LANE)
    sum — the payload is read exactly once."""
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(x * x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gossip_mix_2d(stack: jax.Array, weights: jax.Array,
                  alive: jax.Array | None = None, *,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = False) -> jax.Array:
    """stack: (K, rows, LANE) with rows % block_rows == 0; weights: (K,);
    alive: optional (K,) per-contributor alive weights (renormalized path)."""
    k, rows, lane = stack.shape
    assert lane == LANE and rows % block_rows == 0, (stack.shape, block_rows)
    w2 = weights.reshape(k, 1).astype(jnp.float32)
    grid = (rows // block_rows,)
    stack_spec = pl.BlockSpec((k, block_rows, LANE), lambda i: (0, i, 0))
    vec_spec = pl.BlockSpec((k, 1), lambda i: (0, 0))
    out_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((rows, LANE), stack.dtype)
    if alive is None:
        return pl.pallas_call(
            _mix_kernel, grid=grid, in_specs=[stack_spec, vec_spec],
            out_specs=out_spec, out_shape=out_shape, interpret=interpret,
            name="gossip_mix",
        )(stack, w2)
    a2 = alive.reshape(k, 1).astype(jnp.float32)
    return pl.pallas_call(
        _mix_alive_kernel, grid=grid,
        in_specs=[stack_spec, vec_spec, vec_spec],
        out_specs=out_spec, out_shape=out_shape, interpret=interpret,
        name="gossip_mix_alive",
    )(stack, w2, a2)


@functools.partial(jax.jit,
                   static_argnames=("trim", "block_rows", "interpret"))
def gossip_mix_2d_trimmed(stack: jax.Array, u: jax.Array, live: jax.Array, *,
                          trim: int, block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = False) -> jax.Array:
    """Trimmed-mean mix over a packed stack: stack (K, rows, LANE) with
    rows % block_rows == 0; u (K,) nonnegative weights; live (K,) 0/1
    participation flags; trim = static per-side drop count."""
    k, rows, lane = stack.shape
    assert lane == LANE and rows % block_rows == 0, (stack.shape, block_rows)
    u2 = u.reshape(k, 1).astype(jnp.float32)
    l2 = live.reshape(k, 1).astype(jnp.float32)
    grid = (rows // block_rows,)
    stack_spec = pl.BlockSpec((k, block_rows, LANE), lambda i: (0, i, 0))
    vec_spec = pl.BlockSpec((k, 1), lambda i: (0, 0))
    out_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((rows, LANE), stack.dtype)
    return pl.pallas_call(
        functools.partial(_mix_trimmed_kernel, trim=trim), grid=grid,
        in_specs=[stack_spec, vec_spec, vec_spec],
        out_specs=out_spec, out_shape=out_shape, interpret=interpret,
    )(stack, u2, l2)


@functools.partial(jax.jit,
                   static_argnames=("trim", "block_rows", "interpret"))
def gossip_mix_2d_trimmed_quant(fresh: jax.Array, qstack: jax.Array,
                                scales: jax.Array, u: jax.Array,
                                live: jax.Array, *, trim: int,
                                block_rows: int = DEFAULT_BLOCK_ROWS,
                                interpret: bool = False) -> jax.Array:
    """Dequant-side trimmed mix: fresh (rows, LANE) f32 self buffer,
    qstack (K-1, rows, LANE) int8 received payloads, scales (K-1, n_s) f32
    with n_s == 1 (per-buffer) or n_s == rows // block_rows (per-row-block;
    the scale column advances with the grid). u/live are (K,) over
    [self] + received."""
    km1, rows, lane = qstack.shape
    assert lane == LANE and rows % block_rows == 0, (qstack.shape, block_rows)
    assert fresh.shape == (rows, LANE), (fresh.shape, qstack.shape)
    n_blocks = rows // block_rows
    n_s = scales.shape[1]
    assert n_s in (1, n_blocks), (scales.shape, n_blocks)
    k = km1 + 1
    u2 = u.reshape(k, 1).astype(jnp.float32)
    l2 = live.reshape(k, 1).astype(jnp.float32)
    grid = (n_blocks,)
    fresh_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    q_spec = pl.BlockSpec((km1, block_rows, LANE), lambda i: (0, i, 0))
    # all scales resident in SMEM; a tile reads column i (per-row-block)
    s_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    vec_spec = pl.BlockSpec((k, 1), lambda i: (0, 0))
    out_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((rows, LANE), fresh.dtype)
    return pl.pallas_call(
        functools.partial(_mix_trimmed_quant_kernel, trim=trim, n_s=n_s),
        grid=grid,
        in_specs=[fresh_spec, q_spec, s_spec, vec_spec, vec_spec],
        out_specs=out_spec, out_shape=out_shape, interpret=interpret,
    )(fresh, qstack, scales.reshape(1, -1).astype(jnp.float32), u2, l2)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sqnorms_2d(buf: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False) -> jax.Array:
    """Per-row-block per-lane partial squared norms: (rows, LANE) ->
    (n_blocks, LANE) f32 (callers finish with a lane sum)."""
    rows, lane = buf.shape
    assert lane == LANE and rows % block_rows == 0, (buf.shape, block_rows)
    n_blocks = rows // block_rows
    grid = (n_blocks,)
    in_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    # a (1, LANE) block is only legal as the whole of the array's last two
    # dims, so each tile owns one (1, LANE) slab of a 3-D output
    out_spec = pl.BlockSpec((None, 1, LANE), lambda i: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((n_blocks, 1, LANE), jnp.float32)
    return pl.pallas_call(
        _sqnorm_kernel, grid=grid, in_specs=[in_spec],
        out_specs=out_spec, out_shape=out_shape, interpret=interpret,
    )(buf).reshape(n_blocks, LANE)
