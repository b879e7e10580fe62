"""Parameter-structure utilities: one declaration drives init, dry-run shapes,
and sharding specs.

A model declares its parameters as a pytree of :class:`Leaf` descriptors
(shape + *logical axes* + init). From that single structure we derive:

* `init_params`     — materialized arrays (smoke tests / real training),
* `shape_structs`   — `jax.ShapeDtypeStruct`s (dry-run: no allocation),
* `partition_specs` — `PartitionSpec`s under a logical->mesh-axis rule set,
  with automatic divisibility fallback (a logical axis maps to a mesh axis
  only if the dim is divisible by the mesh axis size — otherwise replicated).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

__all__ = ["Leaf", "init_params", "shape_structs", "partition_specs", "count_params"]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter tensor: shape, logical axes (len == ndim), init spec."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    dtype: str = "bfloat16"
    init: str = "normal"     # normal | zeros | ones
    scale: float | None = None  # stddev for normal; default fan-in

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def _fan_in_scale(leaf: Leaf) -> float:
    if leaf.scale is not None:
        return leaf.scale
    # a leading "layers" axis stacks per-layer weights (scan over layers);
    # it is not an input dim
    shape = leaf.shape[1:] if leaf.axes[:1] == ("layers",) else leaf.shape
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    # for 3D projections (embed, heads, hd) fan-in is the first dim
    return 1.0 / np.sqrt(max(fan_in, 1))


def init_params(struct: PyTree, rng: jax.Array) -> PyTree:
    """Materialize arrays; rng folded per leaf by a CRC-32 of its path, so
    the same ``rng`` gives the same weights in every process (``hash()`` of
    a string is salted per process)."""
    paths = jax.tree.leaves_with_path(struct, is_leaf=_is_leaf)

    leaves = []
    for path, leaf in paths:
        digest = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31)
        key = jax.random.fold_in(rng, digest)
        dt = jnp.dtype(leaf.dtype)
        if leaf.init == "zeros":
            arr = jnp.zeros(leaf.shape, dt)
        elif leaf.init == "ones":
            arr = jnp.ones(leaf.shape, dt)
        else:
            arr = (jax.random.normal(key, leaf.shape, jnp.float32)
                   * _fan_in_scale(leaf)).astype(dt)
        leaves.append(arr)
    treedef = jax.tree.structure(struct, is_leaf=_is_leaf)
    return jax.tree.unflatten(treedef, leaves)


def shape_structs(struct: PyTree) -> PyTree:
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.dtype(l.dtype)),
        struct, is_leaf=_is_leaf)


def _mesh_axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def partition_specs(struct: PyTree, rules: dict[str, Any], mesh) -> PyTree:
    """Logical axes -> PartitionSpec with divisibility fallback.

    rules: {"logical_name": candidate | [candidates...]} where a candidate is
    a mesh axis name, a tuple of names (sharded jointly), or None. For a list,
    the first candidate that (a) divides the dim and (b) doesn't reuse an
    axis already taken in this spec wins — e.g. "experts": ["model", "fsdp"]
    puts 384 kimi experts on the EP axis but falls back to fsdp for grok's 8.
    """

    def one(leaf: Leaf) -> PartitionSpec:
        used: set[str] = set()
        parts = []
        for size, logical in zip(leaf.shape, leaf.axes):
            rule = rules.get(logical) if logical is not None else None
            candidates = rule if isinstance(rule, list) else [rule]
            chosen = None
            for axis in candidates:
                if axis is None:
                    continue
                names = axis if isinstance(axis, tuple) else (axis,)
                if (not any(n in used for n in names)
                        and size % _mesh_axis_size(mesh, axis) == 0):
                    chosen = axis
                    used.update(names)
                    break
            parts.append(chosen)
        return PartitionSpec(*parts)

    return jax.tree.map(one, struct, is_leaf=_is_leaf)


def count_params(struct: PyTree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(struct, is_leaf=_is_leaf))
