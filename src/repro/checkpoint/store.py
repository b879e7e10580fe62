"""Sharded npz pytree checkpoint store: atomic, manifest-based, resumable.

Layout of one checkpoint:

    <dir>/step_000123/
        manifest.json       # treedef, leaf paths/shapes/dtypes, metadata
        shard_000.npz ...   # leaves, grouped into ~`shard_bytes` files

Writes go to `step_<n>.tmp/` and are renamed into place (atomic on POSIX), so
a crash mid-write can never corrupt the latest checkpoint — the core
requirement for fault-tolerant restarts.
"""
from __future__ import annotations

import json
import os
import shutil
from typing import Any

import jax
import ml_dtypes
import numpy as np

PyTree = Any

_MANIFEST = "manifest.json"

# dtypes numpy can't serialize natively: stored as same-width integer views
_EXOTIC = {
    "bfloat16": np.uint16,
    "float8_e4m3fn": np.uint8,
    "float8_e5m2": np.uint8,
}


def _to_storable(arr: np.ndarray) -> np.ndarray:
    name = str(arr.dtype)
    if name in _EXOTIC:
        return arr.view(_EXOTIC[name])
    return arr


def _from_storable(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name in _EXOTIC:
        return arr.view(getattr(ml_dtypes, dtype_name))
    return arr


def _leaf_paths(tree: PyTree) -> tuple[list[str], list[Any]]:
    flat = jax.tree.leaves_with_path(tree)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    leaves = [l for _, l in flat]
    return names, leaves


def save(directory: str, step: int, tree: PyTree, metadata: dict | None = None,
         shard_bytes: int = 1 << 28) -> str:
    """Write a checkpoint; returns the final path."""
    final = os.path.join(directory, f"step_{step:09d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)

    names, leaves = _leaf_paths(tree)
    arrays = [np.asarray(l) for l in leaves]

    shards: list[list[int]] = [[]]
    acc = 0
    for i, a in enumerate(arrays):
        if acc > 0 and acc + a.nbytes > shard_bytes:
            shards.append([])
            acc = 0
        shards[-1].append(i)
        acc += a.nbytes

    entries = []
    for s_idx, idxs in enumerate(shards):
        fname = f"shard_{s_idx:03d}.npz"
        np.savez(os.path.join(tmp, fname),
                 **{f"leaf_{i}": _to_storable(arrays[i]) for i in idxs})
        for i in idxs:
            entries.append({
                "name": names[i], "index": i, "shard": fname,
                "shape": list(arrays[i].shape), "dtype": str(arrays[i].dtype),
            })

    manifest = {
        "step": step,
        "n_leaves": len(arrays),
        "entries": entries,
        "metadata": metadata or {},
    }
    with open(os.path.join(tmp, _MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)

    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def available_steps(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        if name.startswith("step_") and not name.endswith(".tmp"):
            if os.path.exists(os.path.join(directory, name, _MANIFEST)):
                out.append(int(name.split("_")[1]))
    return sorted(out)


def load(directory: str, tree_like: PyTree, step: int | None = None
         ) -> tuple[PyTree, dict]:
    """Restore into the structure of `tree_like`; returns (tree, metadata)."""
    steps = available_steps(directory)
    if not steps:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    step = steps[-1] if step is None else step
    path = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(path, _MANIFEST)) as f:
        manifest = json.load(f)

    by_index: dict[int, np.ndarray] = {}
    by_shard: dict[str, list[dict]] = {}
    for e in manifest["entries"]:
        by_shard.setdefault(e["shard"], []).append(e)
    for fname, ents in by_shard.items():
        with np.load(os.path.join(path, fname)) as z:
            for e in ents:
                by_index[e["index"]] = _from_storable(z[f"leaf_{e['index']}"],
                                                      e["dtype"])

    names, leaves = _leaf_paths(tree_like)
    if len(leaves) != manifest["n_leaves"]:
        raise ValueError(
            f"structure mismatch: have {len(leaves)} leaves, checkpoint has "
            f"{manifest['n_leaves']}")
    restored = []
    for i, (name, like) in enumerate(zip(names, leaves)):
        arr = by_index[i]
        want = tuple(getattr(like, "shape", arr.shape))
        if tuple(arr.shape) != want:
            raise ValueError(f"leaf {name}: shape {arr.shape} != expected {want}")
        restored.append(arr)
    treedef = jax.tree.structure(tree_like)
    return jax.tree.unflatten(treedef, restored), manifest["metadata"]
