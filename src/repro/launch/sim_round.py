"""The simulator's DFL round: one definition for both trainers.

`SimTrainer` (`launch/train.py`) and `ElasticTrainer` (`launch/elastic.py`)
run the same jitted round on a client-stacked pytree: every client's K
local DFedAvgM steps (vmapped), the scripted attack when there is one, then
one call of the gossip executor built from the trainer's
:class:`~repro.core.engine.GossipEngineConfig` — on the ``stacked``
substrate directly, on ``blocked`` inside a ``shard_map`` island over a 1-D
client-device mesh (the local phase runs on the GSPMD-sharded full stack;
only the mixing is manual). :func:`build` assembles it; the trainers keep
their host loops.

Every engine cell has the same call convention::

    round_fn(params, batches, lr, alive, gates, attack, akey, *extra)
        -> (params, losses, metrics, *carried)

``extra`` is empty for a plain, screened or blocked cell, ``(cheby,)`` for
``sub_rounds > 1``, and otherwise the carried channels: the delay snapshot,
then the codec state, each present only where the cell has it. ``carried``
returns those channels in the same order; :meth:`SimRound.extra` primes
them and :meth:`SimRound.carried` names them. ``alive``, ``gates``, the
attack operands and every ``extra`` are traced data, so straggler churn,
round plans, attacker scripts and Chebyshev coefficients never retrace.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dfedavg, engine as engine_lib, failures as failures_lib
from repro.core.gossip import GossipSpec
from repro.launch import mesh as mesh_lib

PyTree = Any


def check(owner: str, cfg: engine_lib.GossipEngineConfig, n: int,
          attack_plan: failures_lib.AttackPlan | None) -> None:
    """The checks a simulator trainer adds to those of ``cfg`` itself."""
    if cfg.substrate not in ("stacked", "blocked"):
        raise ValueError(
            f"{owner} runs the stacked | blocked substrates, got "
            f"engine.substrate={cfg.substrate!r} (production shard_map "
            "cells are built by launch.steps.build_train_step from "
            "ParallelConfig)")
    if attack_plan is not None and attack_plan.n_clients != n:
        raise ValueError(f"attack_plan is for {attack_plan.n_clients} "
                         f"clients, overlay has {n}")
    if cfg.substrate == "blocked":
        if n % cfg.block:
            raise ValueError(f"block={cfg.block} must be a positive divisor "
                             f"of the client count {n}")
        if n // cfg.block > len(jax.devices()):
            raise ValueError(f"blocked layout needs {n // cfg.block} devices "
                             f"(= n/block), only {len(jax.devices())} "
                             "visible")


@dataclasses.dataclass(frozen=True)
class SimRound:
    """One membership's round: the jitted ``round_fn``, the executor it
    calls, and on ``blocked`` the client-device mesh it runs over."""

    executor: engine_lib.GossipExecutor
    round_fn: Callable
    mesh: Mesh | None = None

    def extra(self, params: PyTree, inflight, codec_state) -> tuple:
        """This round's ``*extra`` operands. A carried channel that is still
        ``None`` is primed from ``params``: round 0 mixes the initial
        snapshot in the codec's wire format, and the EF residual starts at
        zeros."""
        ex = self.executor
        if ex.config.sub_rounds > 1:
            # from the live executor: a repair rebuilt it with the new
            # spec's lambda, and the fixed (k,) shape never retraces
            return (jnp.asarray(ex.cheby_coeffs()),)
        out = ()
        if ex.delayed:
            out += (ex.init_state(params) if inflight is None else inflight,)
        if ex.stateful:
            out += (ex.init_codec_state(params) if codec_state is None
                    else codec_state,)
        return out

    def carried(self, carried) -> tuple:
        """``(inflight, codec_state)`` from the round's carried results,
        ``None`` for a channel the cell does not have."""
        it = iter(carried)
        return (next(it) if self.executor.delayed else None,
                next(it) if self.executor.stateful else None)

    def place(self, tree: PyTree) -> PyTree:
        """Commit client-stacked ``tree`` to the blocked mesh. The round
        returns params committed there; an input committed there too keeps
        every round on one trace (the sharding is part of the traced
        type), and a splice that shrinks the mesh re-places the rows."""
        if self.mesh is None or tree is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, P("clients")))


def build(cfg: engine_lib.GossipEngineConfig, spec: GossipSpec, *,
          loss_fn: Callable, dcfg: dfedavg.DFedAvgMConfig, use_plan: bool,
          use_attack: bool, tracer) -> SimRound:
    """The round for engine cell ``cfg`` on membership ``spec``.

    ``use_plan`` turns the gate pathway on (without an active plan the
    exact Chow weights stay), ``use_attack`` the attack, and
    ``cfg.telemetry`` the metrics output: all three are build-time
    decisions. ``tracer.hit()`` runs once per trace.
    """
    blocked = cfg.substrate == "blocked"
    mesh = (Mesh(np.asarray(jax.devices()[:spec.n_clients // cfg.block]),
                 ("clients",)) if blocked else None)
    executor = engine_lib.build_gossip_executor(
        cfg, spec, axis_names="clients" if blocked else None)
    use_tel = cfg.telemetry is not None
    n_out = 1 + executor.delayed + executor.stateful + use_tel

    def client(p, b, lr):
        v = jax.tree.map(jnp.zeros_like, p)
        p, _, loss = dfedavg.local_round(p, v, b, loss_fn, dcfg, lr=lr)
        return p, loss

    def mix(p, alive, gates, *extra):
        kw = {}
        if cfg.sub_rounds > 1:
            (kw["cheby"],) = extra
        else:
            it = iter(extra)
            if executor.delayed:
                kw["state"] = next(it)
            if executor.stateful:
                kw["codec_state"] = next(it)
        return executor(p, alive=alive, gates=gates if use_plan else None,
                        **kw)

    if blocked:
        # alive and gates stay full-length and replicated; the metrics come
        # out device-local ((block,)-leading rows) and the P("clients")
        # out_spec concatenates them back to the (n,)-stacked layout
        gossip = mesh_lib.shard_map(
            mix, mesh, in_specs=(P("clients"), P(), P()),
            out_specs=(P("clients"),) * 2 if use_tel else P("clients"))
    else:
        gossip = mix

    def round_fn(params, batches, lr, alive, gates, attack, akey, *extra):
        tracer.hit()  # python side effect: runs only when tracing
        params, losses = jax.vmap(client, in_axes=(0, 0, None))(
            params, batches, lr)
        if use_attack:
            params = failures_lib.apply_attack(params, attack, akey)
        out = gossip(params, alive, gates, *extra)
        mixed, *carried = out if n_out > 1 else (out,)
        metrics = carried.pop() if use_tel else None
        return (mixed, losses, metrics, *carried)

    return SimRound(executor=executor, round_fn=jax.jit(round_fn), mesh=mesh)
