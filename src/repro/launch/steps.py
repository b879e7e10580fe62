"""Step builders: DFL train rounds and serving steps with full shardings.

This is where the paper's technique meets the device grid:

* **train round** = vmap over the client axis of (K local momentum steps)
  followed by the *gossip island*: a **fully-manual** `shard_map` over all
  mesh axes (in/out specs = the real parameter partition specs — mixing is
  elementwise, so mixing corresponding local shards is exact and each
  ppermute ships only shard-sized payloads). The executor is ONE engine
  cell (`repro.core.engine`: substrate x codec x timing), parsed from the
  legacy `gossip_impl` string + `gossip_delay` + `gossip_codec`:
  `"ppermute_packed"` (default) = shard_map x f32 x sync — d ppermutes per
  round total (one per schedule, independent of leaf count) + one fused
  Pallas reduction pass; `"ppermute_packed_quant"` = shard_map x
  int8_block x sync — int8 wire payloads with the fold-scales-into-wire
  format (still d collectives); `"ppermute"` / `"ppermute_quant"` are the
  per-leaf baseline substrate (d x n_leaves collectives); `"dense"` is the
  paper-naive dense mixing einsum (the §Perf baseline);
  `"ppermute_packed_async"` + `gossip_delay=1` is the **pipelined** engine:
  the step carries last round's snapshot *in the codec's wire format* as
  donated state, so the d ppermutes read a step input and overlap with the
  local-step scan (one-round-delayed mixing, `gossip.mix_dense_delayed`
  semantics); with `gossip_delay=0` it is bit-identical to
  `"ppermute_packed"`. Pipelined + quantized is the free composition:
  `gossip_impl="ppermute_packed_async"`, `gossip_delay=1`,
  `gossip_codec="int8_block"` ships d int8 wire collectives per round and
  carries a 4x smaller snapshot.

  The train step takes a per-client ``alive`` 0/1 vector as its **fourth,
  donated argument** and a per-schedule ``gates`` float vector (the
  time-varying round plan, `repro.overlay.plan`) as its **fifth, donated
  argument** — replicated f32 arrays threaded into the gossip island as
  plain data. On the packed paths (and the dense reference) dead senders
  and gated-off schedules are masked out of the reduction and survivors
  renormalize over their gated live in-degree (`mix_dense_gated`
  semantics), so transient stragglers AND round-plan changes (one-peer
  rotation, schedule subsets, throttling) cost **zero recompiles**: the
  round's liveness and topology-of-the-round are step arguments, never
  baked into the traced graph. Only membership *changes* (splice repair
  rebuilding the overlay) re-jit. The per-leaf ppermute baselines ignore
  both — the packed engine is the only failure/plan-handling path (see
  `core/failures.py`, `repro.overlay`).
* **serve steps** (prefill / decode) run on the raw production mesh with
  TP ("model") x batch-DP ("data"/"pod") and sequence-sharded KV caches.

Every builder returns (jitted_fn, input_specs_dict) so the dry-run can
`.lower(**specs).compile()` without touching device memory.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import DFLConfig, ModelConfig, ParallelConfig, ShapeConfig
from repro.core import dfedavg, engine as engine_lib, gossip as gossip_lib, \
    packing as packing_lib, topology
from repro.launch import mesh as mesh_lib
from repro.models import params as params_lib
from repro.models.api import ModelAPI
from repro.models.params import Leaf
from repro.models.sharding_ctx import activation_sharding
from repro.telemetry.metrics import TelemetryConfig

PyTree = Any


# ---------------------------------------------------------------- helpers
def local_shard_structs(struct: PyTree, pspecs: PyTree, mesh: Mesh) -> PyTree:
    """Per-device shard shapes inside the fully-manual gossip island, with the
    (fully-sharded, local size 1) leading client dim stripped. This is what a
    PackSpec for the packed gossip executors must be built from."""

    def one(leaf: Leaf, spec) -> jax.ShapeDtypeStruct:
        parts = tuple(spec) + (None,) * (len(leaf.shape) - len(tuple(spec)))
        dims = tuple(size // params_lib._mesh_axis_size(mesh, axis)
                     for size, axis in zip(leaf.shape[1:], parts[1:]))
        return jax.ShapeDtypeStruct(dims, jnp.dtype(leaf.dtype))

    return jax.tree.map(one, struct, pspecs,
                        is_leaf=lambda x: isinstance(x, Leaf))


def add_client_axis(struct: PyTree, n: int) -> PyTree:
    return jax.tree.map(
        lambda l: Leaf((n,) + l.shape, ("clients",) + l.axes, l.dtype, l.init,
                       l.scale),
        struct, is_leaf=lambda x: isinstance(x, Leaf))


def build_overlay(n: int, dfl: DFLConfig) -> topology.Overlay | None:
    """Overlay for `n` clients from the graph-family registry
    (:mod:`repro.overlay.registry`); degenerate sizes handled explicitly."""
    from repro.overlay import registry as overlay_registry

    if n < 2:
        return None
    if n == 2:
        return topology.Overlay(
            n=2, schedules=[np.array([1, 0])], name="pair")
    if dfl.topology == "ring" or n == 3:
        return topology.ring_overlay(n)
    d = min(dfl.degree, n - 1)
    if dfl.topology == "expander" and d % 2 == 1 and n % 2 == 1:
        d = max(2, d - 1)  # odd degree needs a perfect matching (even n)
    overlay, _meta = overlay_registry.build(dfl.topology, n, degree=d,
                                            seed=dfl.seed)
    return overlay


# ------------------------------------------------------------ train round
@dataclasses.dataclass(frozen=True)
class TrainSetup:
    # jitted (params, batch, lr, alive, gates, *extra) -> (params, metrics
    # [, inflight]); params, the (n_clients,) f32 alive vector, the
    # (n_schedules,) f32 gate vector, and every extra operand are DONATED —
    # ship fresh vectors per round. The extra operands appear in this fixed
    # order, each gated by its config knob (absent knob = absent argument;
    # a default config keeps the historical 5-argument signature and HLO):
    #   active      (n_clients,) f32   DFLConfig.active_set != "full" —
    #               round-level participation vector (repro.overlay.plan
    #               active-set plans); multiplies the alive mask
    #   attack      (2, n_clients) f32 DFLConfig.byzantine —
    #               failures.AttackPlan.round_vector operand
    #   attack_key  (2,) uint32        DFLConfig.byzantine — PRNG key
    #   inflight    wire-state tuple   gossip_delay=1 (pipelined) — last
    #               round's in-flight snapshot; the step also RETURNS the
    #               new snapshot as a third output. Prime it once with
    #               init_inflight(params) (round 0 then mixes the initial
    #               params as its delayed snapshot).
    #   codec_state state tuple        stateful codec (e.g. "topk_ef") —
    #               the per-client codec state (the EF residual), in the
    #               codec's state_struct layout; the step RETURNS the
    #               updated state as its LAST output. Prime it once with
    #               init_codec_state(params).
    #   cheby       (sub_rounds,) f32  gossip_sub_rounds > 1 (Chebyshev
    #               multi-round gossip) — the per-sub-round coefficient
    #               vector (``cheby_coeffs`` holds the host value for the
    #               current overlay; refresh it after a splice repair)
    # input_specs holds a ShapeDtypeStruct per present operand, in call
    # order, so callers can assemble the argument list generically.
    step_fn: Any
    param_specs: PyTree            # PartitionSpecs (client-stacked)
    param_struct: PyTree           # Leaf pytree (client-stacked)
    input_specs: dict              # ShapeDtypeStructs: batch, lr, alive,
    #                                gates (+ inflight in pipelined mode)
    in_shardings: Any
    overlay: topology.Overlay | None
    gossip_spec: gossip_lib.GossipSpec | None
    dfl_mesh: Mesh
    n_clients: int
    pack_spec: packing_lib.PackSpec | None = None  # packed-gossip layout
    gossip_delay: int = 0          # 1 = pipelined (one-round-delayed) gossip
    init_inflight: Any = None      # jitted params -> in-flight snapshot
    init_codec_state: Any = None   # jitted params -> codec state (stateful
    #                                codecs only; None otherwise)
    # the parsed engine cell (substrate x codec x timing) the step runs on
    engine_config: engine_lib.GossipEngineConfig | None = None
    # exact per-client wire bytes one round ships (0 when untelemetered /
    # no overlay) — the static fact behind metrics["telemetry"]["wire_bytes"]
    wire_bytes_per_round: int = 0
    # host-side (sub_rounds,) f32 Chebyshev coefficients for the baked
    # overlay (None unless gossip_sub_rounds > 1) — ship as the "cheby"
    # operand; same shape forever, so refreshed values never retrace
    cheby_coeffs: Any = None


def _train_rules(caxes: tuple[str, ...], zero3: bool = True) -> dict:
    return {
        "clients": caxes if len(caxes) > 1 else caxes[0],
        # zero3: shard the non-TP dim of every weight over the within-client
        # DP axes (ZeRO-3: weights gathered per use). zero3=False replicates
        # weights over fsdp/dp — more HBM, no per-layer weight all-gathers.
        "embed": ("fsdp", "dp") if zero3 else None,
        "vocab": "tp", "vocab_in": "tp", "ffn": "tp", "heads": "tp",
        "kv_heads": "tp",
        # experts shard on the EP ("tp") axis when divisible; few-expert
        # MoEs (grok: 8 experts, 16-way EP axis) leave E unsharded and rely on
        # the "ffn" tag to shard the per-expert hidden dim instead
        "experts": "tp",
        "layers": None,
    }


def build_train_step(cfg: ModelConfig, shape: ShapeConfig, base_mesh: Mesh,
                     par: ParallelConfig, dfl: DFLConfig,
                     gossip_spec_override: gossip_lib.GossipSpec | None = None
                     ) -> TrainSetup:
    api = ModelAPI(cfg)
    dmesh = mesh_lib.derive_dfl_mesh(base_mesh, par.clients_per_pod, par.tp)
    caxes = mesh_lib.client_axes(dmesh)
    n_cl = mesh_lib.n_clients(dmesh)
    if shape.global_batch % n_cl:
        raise ValueError(f"global_batch {shape.global_batch} % clients {n_cl}")
    local_b = shape.global_batch // n_cl

    overlay = build_overlay(n_cl, dfl)
    gspec = gossip_spec_override
    if gspec is None and overlay is not None:
        gspec = gossip_lib.make_gossip_spec(overlay)
    n_sched = gspec.degree if gspec is not None else 0

    # ---- parameter structure + sharding
    struct1 = api.param_struct()
    struct = add_client_axis(struct1, n_cl)
    rules = _train_rules(caxes, zero3=par.zero3)
    # expert placement: EP ("model") axis when divisible; otherwise E stays
    # unsharded and the per-expert hidden dim carries the TP split ("ffn"
    # tag). (Sharding E over fsdp was measured and REFUTED: mismatched
    # buffer/weight layouts made XLA reshard the big buffers — see
    # EXPERIMENTS.md §Perf.)
    expert_axis = None
    if cfg.moe is not None:
        if cfg.moe.n_experts % dmesh.shape["tp"] == 0:
            expert_axis = "tp"
        rules = dict(rules, experts=expert_axis)
    pspecs = params_lib.partition_specs(struct, rules, dmesh)
    client_spec = rules["clients"]

    # ---- batch specs
    bshape = (n_cl, par.local_steps)
    if par.grad_accum > 1:
        if local_b % par.grad_accum:
            raise ValueError(f"local batch {local_b} % grad_accum {par.grad_accum}")
    batch_specs = {
        "tokens": jax.ShapeDtypeStruct(bshape + (local_b, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct(bshape + (local_b, shape.seq_len), jnp.int32),
    }
    batch_pspec = {
        "tokens": P(client_spec, None, ("fsdp", "dp"), None),
        "labels": P(client_spec, None, ("fsdp", "dp"), None),
    }
    if cfg.stub_prefix:
        batch_specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            bshape + (local_b, cfg.stub_prefix, cfg.prefix_width),
            jnp.dtype(cfg.dtype))
        batch_pspec["prefix_embeds"] = P(client_spec, None, ("fsdp", "dp"), None, None)

    dcfg = dfedavg.DFedAvgMConfig(
        local_steps=par.local_steps, lr=dfl.lr, momentum=dfl.momentum,
        reset_momentum=True, grad_accum=par.grad_accum)

    remat = par.remat == "block"
    update_fn = None
    if par.use_fused_sgdm:
        from repro.kernels.fused_sgdm.ops import sgdm_update

        # GSPMD cannot partition a Mosaic kernel, so the fused update runs
        # per shard under a manual shard_map (it is elementwise: each
        # device updates its own shard). Inside the vmapped client round
        # a leaf's spec is its client-stacked spec minus the client dim.
        client_specs = jax.tree.map(lambda s: P(*tuple(s)[1:]), pspecs)

        def update_fn(p, v, g, lr, beta):
            return mesh_lib.shard_map(
                lambda p, v, g, lr: sgdm_update(p, v, g, lr, beta), dmesh,
                in_specs=(client_specs,) * 3 + (P(),),
                out_specs=(client_specs,) * 2)(p, v, g, lr)

    def loss_fn(p, b):
        return api.loss_fn(p, b, remat=remat)

    def client_round(p, b, lr):
        v = jax.tree.map(jnp.zeros_like, p)  # paper: momentum resets per round
        p, _v, loss = dfedavg.local_round(p, v, b, loss_fn, dcfg, lr=lr,
                                          update_fn=update_fn)
        return p, loss

    # ---- gossip island (fully-manual shard_map over the real param specs:
    # mixing is elementwise, so each device mixes its local shard in place —
    # no resharding, and every ppermute ships only shard-sized payloads).
    # The legacy gossip_impl string (+ gossip_delay / gossip_codec) parses
    # into ONE engine cell — substrate x codec x timing — and the executor
    # is assembled by repro.core.engine; there is no per-variant dispatch
    # below this point.
    if par.gossip_delay not in (0, 1):
        raise ValueError(f"gossip_delay must be 0 or 1, got {par.gossip_delay}")
    ecfg = engine_lib.parse_gossip_impl(par.gossip_impl, par.gossip_delay,
                                        par.gossip_codec, par.gossip_screen,
                                        par.gossip_clip_tau,
                                        par.gossip_trim_f,
                                        sub_rounds=par.gossip_sub_rounds,
                                        telemetry=(TelemetryConfig()
                                                   if par.gossip_telemetry
                                                   else None))
    pack_spec = None
    if ecfg.substrate == "shard_map":
        pack_spec = packing_lib.make_pack_spec(
            local_shard_structs(struct, pspecs, dmesh))

    # pipelined gossip: delay=1 is only meaningful (and only legal) on the
    # async packed impl; the async impl with delay=0 degrades to the exact
    # synchronous packed path (bit-identical — the regression anchor)
    use_delay = (ecfg.delay == 1 and gspec is not None and overlay is not None)
    run_cfg = ecfg if use_delay else dataclasses.replace(ecfg, delay=0)
    axis = caxes if len(caxes) > 1 else caxes[0]
    executor = None
    if gspec is not None and overlay is not None:
        executor = engine_lib.build_gossip_executor(
            run_cfg, gspec,
            axis_names=(axis if run_cfg.substrate in ("shard_map", "per_leaf")
                        else None),
            pack_spec=pack_spec)

    # build-time decision: the gate pathway only engages when the config
    # names a real round plan. A static run keeps the exact (possibly
    # negative-w0) Chow weights of the plain engine — gating with all-ones
    # would clamp those rows to the lazy variant and silently change
    # numerics (the gates argument is still accepted and simply unused).
    # The name is validated so a typo errors instead of silently flipping
    # the gate semantics; this rule must agree with plan_lib.is_active,
    # the predicate the simulator trainers pass to launch.sim_round.
    from repro.overlay import plan as plan_lib
    if dfl.round_plan not in plan_lib.PLAN_NAMES:
        raise ValueError(f"unknown round_plan {dfl.round_plan!r}; "
                         f"available: {', '.join(plan_lib.PLAN_NAMES)}")
    use_gates = dfl.round_plan != "static"
    # round-level client subsampling (active-set plans): same build-time
    # rule as gates — "full" keeps the historical 5-argument signature (and
    # its exact HLO), any real plan appends one donated (n,) vector. The
    # active set multiplies the alive mask OUTSIDE the gossip island, so
    # inactive clients get identity rows exactly like stragglers — but the
    # product never feeds the health tracker (see repro.overlay.plan).
    if dfl.active_set not in plan_lib.ACTIVE_SET_NAMES:
        raise ValueError(
            f"unknown active_set {dfl.active_set!r}; "
            f"available: {', '.join(plan_lib.ACTIVE_SET_NAMES)}")
    use_active = dfl.active_set != "full"

    # in-graph telemetry (build-time branch, same discipline as gates /
    # active / delay: config decides at trace time, off lowers to the exact
    # untelemetered HLO). The island additionally returns the executor's
    # RoundMetrics as per-DEVICE partials — each metric leaf gains one
    # leading dim per mesh axis with out_spec P(*axis_names), so NO
    # collective aggregates them in-graph; the host sums the device partials
    # (repro.telemetry.summarize_metrics — a per-shard proxy for leaves
    # replicated over fsdp/tp, which count once per copy).
    use_tel = run_cfg.telemetry is not None and executor is not None
    wire_bytes = executor.wire_bytes_per_round() if use_tel else 0
    axis_names = tuple(dmesh.axis_names)
    axis_sizes = tuple(int(dmesh.shape[a]) for a in axis_names)
    lead = (1,) * len(axis_sizes)
    tel_spec = P(*axis_names)
    # Chebyshev multi-round gossip (sub_rounds > 1): the (k,) coefficient
    # vector rides as one more donated replicated operand next to
    # alive/gates — plain data, zero retraces across coefficient refreshes
    # (a splice repair recomputes it from the rebuilt spec's lambda). The
    # engine-config validation guarantees the cheby cell is sync (delay=0),
    # screenless and stateless, so only the plain gossip_fn carries it; a
    # sub_rounds=1 build keeps the exact historical signature and HLO.
    use_cheby = executor is not None and run_cfg.sub_rounds > 1

    def gossip_fn(params, alive, gates, *maybe_cheby):
        if executor is None:
            return params
        if run_cfg.substrate == "dense":
            # paper-naive dense baseline, on the gated+masked effective
            # matrix (gates/alive are traced data here too)
            return executor(params, alive=alive,
                            gates=gates if use_gates else None)

        def body(p, alive_vec, gate_vec, *rest):
            local = jax.tree.map(lambda x: x[0], p)       # client-local shard
            # alive + round-plan gates ride into the island replicated; only
            # the packed engine is failure/plan-aware (the per-leaf
            # baseline substrate ignores both, and a static config drops
            # the gate pathway at trace time)
            kw = dict(alive=alive_vec,
                      gates=gate_vec if use_gates else None)
            if use_cheby:
                kw["cheby"] = rest[0]
            if use_tel:
                mixed, met = executor(local, **kw)
                return (jax.tree.map(lambda x: x[None], mixed),
                        jax.tree.map(lambda x: x.reshape(lead + x.shape),
                                     met))
            mixed = (executor(local)
                     if run_cfg.substrate == "per_leaf"
                     else executor(local, **kw))
            return jax.tree.map(lambda x: x[None], mixed)

        in_specs = (pspecs, P(), P()) + ((P(),) if use_cheby else ())
        args = (params, alive, gates) + tuple(maybe_cheby)
        if use_tel:
            return mesh_lib.shard_map(
                body, dmesh, in_specs=in_specs,
                out_specs=(pspecs, tel_spec))(*args)
        return mesh_lib.shard_map(body, dmesh, in_specs=in_specs,
                                  out_specs=pspecs)(*args)

    # ---- pipelined gossip state (delay=1): the in-flight snapshot is the
    # per-device *codec wire* of last round's post-local-step shards (the
    # packed f32 buffer for codec="f32", the folded int8 wire buffer for the
    # quantized codecs — so pipelined+quantized carries and ships int8
    # bytes). Its global representation carries one leading dim per mesh
    # axis (each sharded over that axis), so the fully-manual island sees
    # exactly one (rows, LANE) block per device — the state never reshards.
    inflight_structs = inflight_pspecs = None
    if use_delay:
        local_state_structs = executor.state_structs()
        inflight_pspecs = tuple(P(*axis_names, None, None)
                                for _ in local_state_structs)
        inflight_structs = tuple(
            jax.ShapeDtypeStruct(axis_sizes + s.shape, s.dtype)
            for s in local_state_structs)

        def gossip_fn_delayed(params, alive, gates, inflight):
            def body(p, alive_vec, gate_vec, state):
                local = jax.tree.map(lambda x: x[0], p)
                state_local = tuple(s.reshape(s.shape[-2:]) for s in state)
                if use_tel:
                    mixed, new_state, met = executor(
                        local, state=state_local, alive=alive_vec,
                        gates=gate_vec if use_gates else None)
                    return (jax.tree.map(lambda x: x[None], mixed),
                            tuple(s.reshape(lead + s.shape)
                                  for s in new_state),
                            jax.tree.map(lambda x: x.reshape(lead + x.shape),
                                         met))
                mixed, new_state = executor(
                    local, state=state_local, alive=alive_vec,
                    gates=gate_vec if use_gates else None)
                return (jax.tree.map(lambda x: x[None], mixed),
                        tuple(s.reshape(lead + s.shape) for s in new_state))

            out_specs = ((pspecs, inflight_pspecs, tel_spec) if use_tel
                         else (pspecs, inflight_pspecs))
            return mesh_lib.shard_map(
                body, dmesh, in_specs=(pspecs, P(), P(), inflight_pspecs),
                out_specs=out_specs)(params, alive, gates, inflight)

        def snapshot_fn(params):
            """Prime the pipeline: encode the current post-mix params into
            the in-flight wire layout (round 0 then mixes the initial params
            as its delayed snapshot — the mix_dense_delayed convention)."""
            def body(p):
                local = jax.tree.map(lambda x: x[0], p)
                bufs = executor.init_state(local)
                return tuple(b.reshape(lead + b.shape) for b in bufs)

            return mesh_lib.shard_map(body, dmesh, in_specs=(pspecs,),
                                      out_specs=inflight_pspecs)(params)

    # ---- stateful codec (e.g. "topk_ef"): the per-client codec state (the
    # error-feedback residual) is a SECOND threaded state channel, parallel
    # to the delay snapshot: one f32 (rows, LANE) buffer per packed buffer
    # per device, carried as a donated step operand and returned as the
    # step's LAST output. Same sharding discipline as the in-flight
    # snapshot — one leading dim per mesh axis, so the island sees exactly
    # its own (rows, LANE) block and the state never reshards.
    use_cstate = (executor is not None and executor.stateful
                  and run_cfg.substrate == "shard_map")
    cstate_structs = cstate_pspecs = None
    if use_cstate:
        local_cstate_structs = executor.codec_state_structs()
        cstate_pspecs = tuple(P(*axis_names, None, None)
                              for _ in local_cstate_structs)
        cstate_structs = tuple(
            jax.ShapeDtypeStruct(axis_sizes + s.shape, s.dtype)
            for s in local_cstate_structs)

        def gossip_fn_stateful(params, alive, gates, cstate, inflight=None):
            def body(p, alive_vec, gate_vec, cst, *maybe_state):
                local = jax.tree.map(lambda x: x[0], p)
                kw = dict(codec_state=tuple(s.reshape(s.shape[-2:])
                                            for s in cst),
                          alive=alive_vec,
                          gates=gate_vec if use_gates else None)
                if use_delay:
                    kw["state"] = tuple(s.reshape(s.shape[-2:])
                                        for s in maybe_state[0])
                out = executor(local, **kw)
                rest = list(out[1:])
                res = [jax.tree.map(lambda x: x[None], out[0])]
                if use_delay:
                    res.append(tuple(s.reshape(lead + s.shape)
                                     for s in rest.pop(0)))
                res.append(tuple(s.reshape(lead + s.shape)
                                 for s in rest.pop(0)))
                if use_tel:
                    res.append(jax.tree.map(
                        lambda x: x.reshape(lead + x.shape), rest.pop(0)))
                return tuple(res)

            in_specs = (pspecs, P(), P(), cstate_pspecs) \
                + ((inflight_pspecs,) if use_delay else ())
            out_specs = (pspecs,) \
                + ((inflight_pspecs,) if use_delay else ()) \
                + (cstate_pspecs,) + ((tel_spec,) if use_tel else ())
            args = (params, alive, gates, cstate) \
                + ((inflight,) if use_delay else ())
            return mesh_lib.shard_map(body, dmesh, in_specs=in_specs,
                                      out_specs=out_specs)(*args)

        def cstate_init_fn(params):
            """Prime the codec state (the topk_ef EF residual starts at
            zeros: nothing has been dropped yet)."""
            def body(p):
                local = jax.tree.map(lambda x: x[0], p)
                bufs = executor.init_codec_state(local)
                return tuple(b.reshape(lead + b.shape) for b in bufs)

            return mesh_lib.shard_map(body, dmesh, in_specs=(pspecs,),
                                      out_specs=cstate_pspecs)(params)

    # activation constraints visible inside the vmapped client round
    act_rules = {}
    if par.seq_parallel:
        # Megatron-SP: residual stream sequence-sharded over the TP axis —
        # GSPMD then lowers each TP boundary to reduce-scatter + all-gather
        # (half the wire bytes of the all-reduce it replaces). Measured: on
        # this XLA it *adds* seq all-gathers instead; kept off by default.
        act_rules["residual"] = NamedSharding(dmesh, P(None, "tp", None))
        act_rules["activation"] = NamedSharding(dmesh, P(None, "tp", None))
    if cfg.moe is not None:
        # buffers: E on the EP axis when sharded, capacity over fsdp so no
        # fsdp row computes a redundant expert matmul
        buf_spec = P(expert_axis, ("fsdp", "dp"), None)
        act_rules["moe_buffer"] = NamedSharding(dmesh, buf_spec)
        if expert_axis is None:
            # E-unsharded experts (grok): gather d from fsdp in bf16 here
            # (not a f32 copy), keep f on the TP axis. NOT applied to
            # EP-sharded experts (kimi) — measured: gathering d for 1T params
            # per microbatch regressed collective 456 -> 770 s.
            act_rules["expert_weights"] = NamedSharding(dmesh, P(None, None, "tp"))
            act_rules["expert_weights_t"] = NamedSharding(dmesh, P(None, "tp", None))

    # Byzantine attacker harness (dfl.byzantine): the step additionally
    # takes the (2, n) AttackPlan.round_vector operand and a (2,) uint32
    # PRNG key as traced DATA (donated, like alive/gates), perturbing the
    # post-local-step client-stacked params before they hit the wire —
    # attacker churn and attack-free rounds share the single trace
    use_attack = dfl.byzantine
    if use_attack:
        from repro.core import failures as failures_lib

    def _local_phase(params, batch, lr):
        # spmd_axis_name threads the client mesh axes through every
        # sharding constraint inside the vmapped round
        return jax.vmap(client_round, in_axes=(0, 0, None),
                        spmd_axis_name=caxes)(params, batch, lr)

    # ---- the ONE step function. Optional data operands (active-set vector,
    # attack operand + key, in-flight snapshot) ride as *extra positional
    # arguments in the fixed order below; a default config has an empty
    # extra list and lowers to the exact historical 5-argument HLO.
    extra_names = (["active"] if use_active else []) \
        + (["attack", "attack_key"] if use_attack else []) \
        + (["inflight"] if use_delay else []) \
        + (["codec_state"] if use_cstate else []) \
        + (["cheby"] if use_cheby else [])

    def train_step(params, batch, lr, alive, gates, *extra):
        kw = dict(zip(extra_names, extra))
        # active-set subsampling composes by masking: an inactive client is
        # mixed like a straggler (identity row, neighbors drop it and
        # renormalize) — the multiply happens outside the gossip island so
        # the island's trace is independent of whether a plan is on
        eff_alive = alive * kw["active"] if use_active else alive
        out_state = out_cstate = tel_met = None
        with activation_sharding(act_rules):
            params, loss = _local_phase(params, batch, lr)
            if use_attack:
                params = failures_lib.apply_attack(params, kw["attack"],
                                                   kw["attack_key"])
            if use_cstate:
                island = list(gossip_fn_stateful(
                    params, eff_alive, gates, kw["codec_state"],
                    kw.get("inflight")))
                params = island.pop(0)
                if use_delay:
                    out_state = island.pop(0)
                out_cstate = island.pop(0)
                if use_tel:
                    tel_met = island.pop(0)
            elif use_delay:
                # the d ppermutes inside gossip_fn_delayed read only the
                # snapshot (a step input), so the scheduler overlaps them
                # with the local-step scan
                island = gossip_fn_delayed(params, eff_alive, gates,
                                           kw["inflight"])
                if use_tel:
                    params, out_state, tel_met = island
                else:
                    params, out_state = island
            elif use_tel:
                params, tel_met = gossip_fn(
                    params, eff_alive, gates,
                    *((kw["cheby"],) if use_cheby else ()))
            else:
                params = gossip_fn(
                    params, eff_alive, gates,
                    *((kw["cheby"],) if use_cheby else ()))
        metrics = {"loss": jnp.mean(loss)}
        if use_tel:
            tel_met = dict(tel_met)
            # exact per-codec wire bytes (a static wire_struct fact) and the
            # attack-vector energy (zero on all-honest rounds) ride as
            # replicated scalars next to the island's per-device partials
            tel_met["wire_bytes"] = jnp.float32(wire_bytes)
            if use_attack:
                atk = kw["attack"]
                tel_met["attack_energy"] = (jnp.sum((atk[0] - 1.0) ** 2)
                                            + jnp.sum(atk[1] ** 2))
            metrics["telemetry"] = tel_met
        out = (params, metrics)
        if use_delay:
            out = out + (out_state,)
        if use_cstate:
            out = out + (out_cstate,)
        return out

    param_shardings = jax.tree.map(lambda s: NamedSharding(dmesh, s), pspecs)
    repl = NamedSharding(dmesh, P())
    in_shardings = [
        param_shardings,
        jax.tree.map(lambda s: NamedSharding(dmesh, s), batch_pspec),
        repl,
        repl,
        repl,
    ]
    metrics_shardings = NamedSharding(dmesh, P())
    if use_tel:
        # the telemetry subtree keeps the island's per-device layout (one
        # leading dim per mesh axis) — forcing it replicated here would
        # make jit insert the very all-gather telemetry promises not to add
        tel_shardings = {k: NamedSharding(dmesh, tel_spec)
                         for k in executor.metrics_structs()}
        tel_shardings["wire_bytes"] = NamedSharding(dmesh, P())
        if use_attack:
            tel_shardings["attack_energy"] = NamedSharding(dmesh, P())
        metrics_shardings = {"loss": NamedSharding(dmesh, P()),
                             "telemetry": tel_shardings}
    out_shardings = (
        param_shardings,
        metrics_shardings,
    )
    input_specs = {"batch": batch_specs,
                   "lr": jax.ShapeDtypeStruct((), jnp.float32),
                   "alive": jax.ShapeDtypeStruct((n_cl,), jnp.float32),
                   "gates": jax.ShapeDtypeStruct((n_sched,), jnp.float32)}
    # alive (argnum 3), the round-plan gates (argnum 4), and every extra
    # operand are donated with the params: each round ships fresh vectors
    # and the previous ones are dead weight. Consequence: callers must NOT
    # reuse a cached device array across rounds (it is consumed); build the
    # mask/gates/active per round (ElasticTrainer does)
    donate = [0, 3, 4]
    extra_specs = {
        "active": jax.ShapeDtypeStruct((n_cl,), jnp.float32),
        "attack": jax.ShapeDtypeStruct((2, n_cl), jnp.float32),
        "attack_key": jax.ShapeDtypeStruct((2,), jnp.uint32),
        "cheby": jax.ShapeDtypeStruct((run_cfg.sub_rounds,), jnp.float32),
    }
    inflight_shardings = cstate_shardings = None
    for name in extra_names:
        donate.append(len(in_shardings))
        if name == "inflight":
            # the snapshot is donated too: the step consumes last round's
            # in-flight buffers and emits this round's
            inflight_shardings = tuple(NamedSharding(dmesh, s)
                                       for s in inflight_pspecs)
            in_shardings.append(inflight_shardings)
            out_shardings = out_shardings + (inflight_shardings,)
            input_specs["inflight"] = inflight_structs
        elif name == "codec_state":
            # per-client codec state (the EF residual): donated in, updated
            # state is the step's LAST output
            cstate_shardings = tuple(NamedSharding(dmesh, s)
                                     for s in cstate_pspecs)
            in_shardings.append(cstate_shardings)
            out_shardings = out_shardings + (cstate_shardings,)
            input_specs["codec_state"] = cstate_structs
        else:
            in_shardings.append(repl)
            input_specs[name] = extra_specs[name]
    in_shardings = tuple(in_shardings)
    step = jax.jit(train_step, in_shardings=in_shardings,
                   out_shardings=out_shardings, donate_argnums=tuple(donate))
    init_inflight = None
    if use_delay:
        init_inflight = jax.jit(snapshot_fn, in_shardings=(param_shardings,),
                                out_shardings=inflight_shardings)
    init_codec_state = None
    if use_cstate:
        init_codec_state = jax.jit(cstate_init_fn,
                                   in_shardings=(param_shardings,),
                                   out_shardings=cstate_shardings)
    return TrainSetup(
        step_fn=step, param_specs=pspecs, param_struct=struct,
        input_specs=input_specs,
        in_shardings=in_shardings, overlay=overlay, gossip_spec=gspec,
        dfl_mesh=dmesh, n_clients=n_cl, pack_spec=pack_spec,
        gossip_delay=par.gossip_delay if use_delay else 0,
        init_inflight=init_inflight, init_codec_state=init_codec_state,
        engine_config=run_cfg, wire_bytes_per_round=wire_bytes,
        cheby_coeffs=executor.cheby_coeffs() if use_cheby else None)


# ------------------------------------------------------------- serve steps
@dataclasses.dataclass(frozen=True)
class ServeSetup:
    step_fn: Any
    param_specs: PyTree
    param_struct: PyTree
    input_specs: dict
    in_shardings: Any
    mesh: Mesh


def _serve_rules(cfg: ModelConfig, baxes: tuple[str, ...]) -> dict:
    # giant checkpoints also shard the non-TP dim over the batch axes
    # (weight-gathered / ZeRO-inference); threshold: >4 GiB per model shard
    per_model_shard = cfg.param_count() * 2 / 16
    big = per_model_shard > 4 * 1024**3
    b = baxes if len(baxes) > 1 else baxes[0]
    return {
        "embed": b if big else None,
        "vocab": "model", "vocab_in": "model", "ffn": "model", "heads": "model",
        "kv_heads": "model", "experts": "model", "layers": None,
        "act_batch": b, "act_seq": "model",
    }


def _serve_act_rules(mesh: Mesh, baxes: tuple[str, ...],
                     act_batch=None) -> dict:
    b = act_batch
    return {
        "activation": NamedSharding(mesh, P(b)),
        "residual": NamedSharding(mesh, P(b)),
        "logits": NamedSharding(mesh, P(b, None, "model")),
        "attn_q": NamedSharding(mesh, P(b, None, "model", None)),
        "attn_kv": NamedSharding(mesh, P(b, None, "model", None)),
        "cache": NamedSharding(mesh, P(b, "model", None, None)),
    }


def build_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh
                     ) -> ServeSetup:
    """Prefill or decode step on the production mesh (no client axis)."""
    api = ModelAPI(cfg)
    baxes = mesh_lib.batch_axes(mesh)
    struct = api.param_struct()
    rules = _serve_rules(cfg, baxes)
    # tiny batches (long_500k has global_batch=1) can't shard the batch axis;
    # the idle batch axes then join the cache's sequence sharding instead
    # (500k decode: the per-step cache read is the memory wall — spreading it
    # over data x model cuts per-device bytes by the data-axis width)
    n_batch_devices = int(np.prod([mesh.shape[a] for a in baxes]))
    if shape.global_batch % n_batch_devices != 0:
        act_batch = None
        rules = dict(rules, act_batch=None,
                     act_seq=tuple(baxes) + ("model",))
    else:
        act_batch = rules["act_batch"]
    pspecs = params_lib.partition_specs(struct, rules, mesh)
    act_rules = _serve_act_rules(mesh, baxes, act_batch)

    inputs = api.input_specs(shape)
    if shape.kind == "prefill":
        in_pspec = {"tokens": P(act_batch, None)}
        if "prefix_embeds" in inputs:
            in_pspec["prefix_embeds"] = P(act_batch, None, None)

        def step(params, **inp):
            with activation_sharding(act_rules):
                return api.prefill(params, inp["tokens"],
                                   prefix_embeds=inp.get("prefix_embeds"))
    else:  # decode
        cache_struct = api.cache_struct(shape.global_batch, shape.seq_len)
        cache_pspec = params_lib.partition_specs(cache_struct, rules, mesh)
        in_pspec = {"tokens": P(act_batch),
                    "pos": P(), "cache": cache_pspec}

        def step(params, **inp):
            with activation_sharding(act_rules):
                return api.decode_step(params, inp["cache"], inp["tokens"],
                                       inp["pos"])

    p_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    kw_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), in_pspec)

    def positional(params, inp):
        return step(params, **inp)

    jitted = jax.jit(positional, in_shardings=(p_shardings, kw_shardings))
    return ServeSetup(step_fn=jitted, param_specs=pspecs, param_struct=struct,
                      input_specs=inputs,
                      in_shardings=(p_shardings, kw_shardings), mesh=mesh)
