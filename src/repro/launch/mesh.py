"""Production meshes and the DFL device-grid factorization.

`make_production_mesh` builds the ("data", "model") mesh from the devices
the process can see: one chip is a (1, 1) mesh, a v5e 2x2 host a (4, 1)
mesh (every chip on "data", so each can hold its own DFL client). The pod
shapes (16, 16) and (2, 16, 16) remain as `POD_SHAPES`, the placeholder
worlds that `repro.launch.dryrun` compiles for.

`derive_dfl_mesh` refactors the same device grid for the DFL train step:
the "data" axis splits into (client, fsdp) — `clients_per_pod` DFL clients
per pod, each internally ZeRO/data-parallel over fsdp = data/clients_per_pod
rows — while "model" splits into (dp, tp). This is a pure reshape of the
device array (no re-placement); serving uses the production mesh directly.

Every mesh here has Auto axis types: the train and serve steps place parameters
with NamedShardings and let GSPMD propagate the rest, which is the
contract Auto names (jax's Explicit default would put shardings into the
types and reject plain indexing of sharded values).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

# the pod worlds the dry-run describes with placeholder CPU devices
POD_SHAPES = {"single": ((16, 16), ("data", "model")),
              "multi": ((2, 16, 16), ("pod", "data", "model"))}


def shard_map(f, mesh: Mesh, *, in_specs, out_specs):
    """*Full-manual* shard_map over every mesh axis.

    Replication checking is off (``check_vma=False``): the gossip islands
    return per-device values under specs that do not name every mesh axis
    (a leaf replicated over tp is mixed identically on each tp shard), which
    the checker cannot prove.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axis types (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, pod: str | None = None) -> Mesh:
    """("data", "model") mesh over the visible devices; ``pod`` = "single"
    | "multi" builds the placeholder pod world of `POD_SHAPES` instead
    (the dry-run's 256 / 512 fake devices)."""
    if pod is not None:
        return make_mesh(*POD_SHAPES[pod])
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def derive_dfl_mesh(mesh: Mesh, clients_per_pod: int, tp: int | None = None) -> Mesh:
    """(pod?, data, model) -> (pod?, client, fsdp, dp, tp).

    `tp` right-sizes tensor parallelism: the model axis splits into
    (dp = model//tp, tp); the freed `dp` factor becomes extra within-client
    data parallelism (small models drown in TP activation all-reduces at
    width 16 — per-device AR bytes scale with per-device batch).
    """
    data = mesh.shape["data"]
    model = mesh.shape["model"]
    tp = model if tp is None else tp
    if data % clients_per_pod != 0:
        raise ValueError(f"clients_per_pod={clients_per_pod} must divide {data}")
    if model % tp != 0:
        raise ValueError(f"tp={tp} must divide {model}")
    fsdp = data // clients_per_pod
    dp = model // tp
    devices = np.asarray(mesh.devices)
    if devices.ndim == 3:  # multi-pod
        pods = devices.shape[0]
        grid = devices.reshape(pods, clients_per_pod, fsdp, dp, tp)
        return Mesh(grid, ("pod", "client", "fsdp", "dp", "tp"))
    grid = devices.reshape(clients_per_pod, fsdp, dp, tp)
    return Mesh(grid, ("client", "fsdp", "dp", "tp"))


def client_axes(dfl_mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes that jointly form the DFL client (gossip) axis."""
    return ("pod", "client") if "pod" in dfl_mesh.axis_names else ("client",)


def n_clients(dfl_mesh: Mesh) -> int:
    return int(np.prod([dfl_mesh.shape[a] for a in client_axes(dfl_mesh)]))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Serving batch axes on the production mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
