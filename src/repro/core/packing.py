"""Flat-buffer packing of parameter pytrees for the gossip hot path.

The paper's gossip round ships the *entire* client parameter state every K
local steps. Executing it leaf-by-leaf costs d x n_leaves collective-permutes
per round and d+1 unfused HBM read-modify-write passes per leaf. Packing the
pytree into one lane-aligned flat buffer per dtype turns that into:

* **d collectives per round per dtype** — one ``lax.ppermute`` of the whole
  buffer per schedule, independent of how many parameter tensors the model
  has. Fewer, larger transfers saturate ICI and overlap with compute far
  better than hundreds of small per-leaf permutes.
* **one HBM pass for the mixing reduction** — the self buffer plus the d
  received buffers stack to ``(d+1, rows, 128)`` and feed straight into the
  fused ``gossip_mix_2d`` Pallas kernel (reads (d+1)x bytes, writes 1x bytes:
  the HBM lower bound), with no per-leaf flatten/pad work in the jitted step.

A :class:`PackSpec` is static and hashable, so it bakes into the jitted train
step as a closed-over constant: all offsets/shapes below are Python ints and
every slice in ``unpack_tree`` is static.

Layout: leaves are grouped by dtype (one buffer per distinct dtype — models
are usually single-dtype, so usually one buffer), raveled and concatenated in
tree-flatten order, then zero-padded so the buffer reshapes to
``(rows, LANE=128)`` with ``rows`` a multiple of ``PACK_BLOCK_ROWS`` — i.e.
already tiled for the Pallas gossip/quant kernels, no padding inside the step.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LANE", "PACK_BLOCK_ROWS", "SCALE_BYTES", "LeafSlot", "PackSpec",
           "make_pack_spec", "make_stacked_pack_spec", "pack_tree",
           "unpack_tree", "scale_rows", "topk_wire_rows"]

PyTree = Any

LANE = 128
# Matches the gossip_mix / quant_gossip kernels' DEFAULT_BLOCK_ROWS so packed
# buffers are directly consumable without repadding; 256 rows is a multiple of
# every dtype's sublane minimum (f32:8, bf16:16, int8:32).
PACK_BLOCK_ROWS = 256
# bytes per f32 quantization scale folded into an int8 wire buffer
SCALE_BYTES = 4


def scale_rows(n_blocks: int) -> int:
    """Trailing lane rows an int8 wire buffer needs to carry `n_blocks`
    per-row-block f32 quant scales (4 bytes each, lane-folded like the PR-3
    wire format). One row carries LANE // SCALE_BYTES = 32 scales, so the
    wire overhead stays <= 1 row per 32 tile blocks (each >= 32 KiB)."""
    return (SCALE_BYTES * n_blocks + LANE - 1) // LANE


def topk_wire_rows(k: int) -> int:
    """Lane rows of a sparse top-k wire buffer: ``k`` f32 values followed by
    ``k`` int32 flat indices, each 4 bytes, bitcast into int8 lane rows (the
    same fold that carries quant scales — one int8 buffer per schedule, ONE
    collective). The two sections are padded to whole rows independently so
    both bitcasts stay static slices."""
    half = (SCALE_BYTES * k + LANE - 1) // LANE
    return 2 * half


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf lives: ``buffers[buffer].reshape(-1)[offset:offset+size]``."""

    shape: tuple[int, ...]
    dtype: str
    buffer: int     # index into the spec's buffer list
    offset: int     # element offset within that flat buffer
    size: int       # number of elements


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static, hashable description of a packed parameter pytree.

    Attributes:
      slots: per-leaf placement, in ``jax.tree.flatten`` order.
      buffer_dtypes: dtype name of each flat buffer (one per distinct dtype).
      buffer_rows: row count of each ``(rows, LANE)`` buffer; always a
        multiple of ``block_rows``.
      block_rows: the kernel tile height the buffers are padded for.
      treedef: the source pytree structure (hashable), for ``unpack_tree``.
    """

    slots: tuple[LeafSlot, ...]
    buffer_dtypes: tuple[str, ...]
    buffer_rows: tuple[int, ...]
    block_rows: int
    treedef: Any

    @property
    def n_buffers(self) -> int:
        return len(self.buffer_dtypes)

    @property
    def n_leaves(self) -> int:
        return len(self.slots)

    def buffer_shape(self, b: int) -> tuple[int, int]:
        return (self.buffer_rows[b], LANE)

    def buffer_struct(self, b: int) -> jax.ShapeDtypeStruct:
        """Host-side ShapeDtypeStruct of buffer ``b`` — the base shape the
        engine's wire codecs derive their on-the-wire struct from (the f32
        codec ships it as-is; the int8 codecs append scale rows)."""
        return jax.ShapeDtypeStruct(self.buffer_shape(b),
                                    jnp.dtype(self.buffer_dtypes[b]))

    def buffer_blocks(self, b: int) -> int:
        """Row-block (kernel tile) count of buffer ``b`` — also the number of
        per-block quant scales its int8 wire buffer carries."""
        return self.buffer_rows[b] // self.block_rows

    @property
    def payload_elements(self) -> int:
        """Real (unpadded) elements across all buffers."""
        return sum(s.size for s in self.slots)

    @property
    def padded_elements(self) -> int:
        """Allocated elements including lane/tile padding."""
        return sum(r * LANE for r in self.buffer_rows)

    @property
    def payload_bytes(self) -> int:
        return sum(s.size * jnp.dtype(s.dtype).itemsize for s in self.slots)

    @property
    def padded_bytes(self) -> int:
        return sum(r * LANE * jnp.dtype(d).itemsize
                   for r, d in zip(self.buffer_rows, self.buffer_dtypes))


def make_stacked_pack_spec(tree: PyTree, *,
                           block_rows: int = PACK_BLOCK_ROWS) -> PackSpec:
    """PackSpec of a CLIENT-STACKED tree's per-client slice (leading axis =
    clients, stripped before packing). This is the layout shared by the
    stacked and blocked engine substrates: one ``(n, rows, 128)`` (or
    ``(B, rows, 128)`` device-local under ``blocked``) buffer per dtype, the
    per-client slice packed identically everywhere — which is why a splice
    repair remaps blocked state by the same old2new row take as stacked
    state, and why blocked-vs-stacked parity is bitwise for f32 cells.

    ``block_rows`` tunes the per-client padding floor: the default matches
    the Pallas kernels' tile, but f32 simulator cells at O(10^4) clients use
    no kernels and may pick a smaller multiple-of-8 block so 4096 tiny
    clients don't pad to 4096 x 256 rows (see benchmarks/bench_scale.py).
    """
    return make_pack_spec(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree),
        block_rows=block_rows)


def make_pack_spec(tree: PyTree, *, block_rows: int = PACK_BLOCK_ROWS
                   ) -> PackSpec:
    """Build a PackSpec from a pytree of arrays or ShapeDtypeStructs.

    Only ``.shape`` and ``.dtype`` of the leaves are consulted, so the spec
    can be built host-side from ``shape_structs`` without touching device
    memory, then reused against real arrays of the same structure.
    """
    leaves, treedef = jax.tree.flatten(tree)
    buffer_dtypes: list[str] = []
    fill: list[int] = []        # elements used so far per buffer
    slots: list[LeafSlot] = []
    for leaf in leaves:
        dt = str(jnp.dtype(leaf.dtype))
        if dt not in buffer_dtypes:
            buffer_dtypes.append(dt)
            fill.append(0)
        b = buffer_dtypes.index(dt)
        size = int(np.prod(leaf.shape)) if len(leaf.shape) else 1
        slots.append(LeafSlot(shape=tuple(int(x) for x in leaf.shape),
                              dtype=dt, buffer=b, offset=fill[b], size=size))
        fill[b] += size
    tile = block_rows * LANE
    rows = tuple((used + tile - 1) // tile * tile // LANE for used in fill)
    return PackSpec(slots=tuple(slots), buffer_dtypes=tuple(buffer_dtypes),
                    buffer_rows=rows, block_rows=block_rows, treedef=treedef)


def pack_tree(tree: PyTree, spec: PackSpec) -> tuple[jax.Array, ...]:
    """Pack a pytree into the spec's flat ``(rows, LANE)`` buffers."""
    leaves = jax.tree.leaves(tree)
    if len(leaves) != spec.n_leaves:
        raise ValueError(f"tree has {len(leaves)} leaves, spec packs "
                         f"{spec.n_leaves}")
    with jax.named_scope("pack"):
        parts: list[list[jax.Array]] = [[] for _ in range(spec.n_buffers)]
        for leaf, slot in zip(leaves, spec.slots):
            if (leaf.shape != slot.shape
                    or str(jnp.dtype(leaf.dtype)) != slot.dtype):
                raise ValueError(f"leaf {leaf.shape}/{leaf.dtype} does not "
                                 f"match slot {slot.shape}/{slot.dtype}")
            parts[slot.buffer].append(leaf.reshape(-1))
        bufs = []
        for b in range(spec.n_buffers):
            flat = (jnp.concatenate(parts[b]) if len(parts[b]) > 1
                    else parts[b][0])
            total = spec.buffer_rows[b] * LANE
            if flat.shape[0] < total:
                flat = jnp.pad(flat, (0, total - flat.shape[0]))
            bufs.append(flat.reshape(spec.buffer_rows[b], LANE))
        return tuple(bufs)


def unpack_tree(buffers: tuple[jax.Array, ...], spec: PackSpec) -> PyTree:
    """Invert :func:`pack_tree` (all slices static, jit-friendly)."""
    if len(buffers) != spec.n_buffers:
        raise ValueError(f"got {len(buffers)} buffers, spec has "
                         f"{spec.n_buffers}")
    with jax.named_scope("unpack"):
        flats = [b.reshape(-1) for b in buffers]
        leaves = [flats[s.buffer][s.offset:s.offset + s.size].reshape(s.shape)
                  for s in spec.slots]
        return jax.tree.unflatten(spec.treedef, leaves)
