"""Elastic runtime: ties health tracking, overlay repair, and checkpointing
into a resilient training loop (the fault-tolerance story, end to end).

Built on the **packed gossip engine** — the only failure-handling path:

  * every round, each client group posts a heartbeat (simulated here by a
    FailurePlan / an explicit alive mask);
  * a client missing `straggler_rounds` heartbeats is *dropped for the
    round*: its 0/1 entry in the alive vector flips, and the packed mixing
    reduction renormalizes over the alive in-neighborhood *inside the fused
    kernel pass*. The alive vector is a **traced step argument**, so
    straggler churn — any pattern of drops and recoveries — causes **zero
    recompiles** of the jitted round (`n_traces` counts them; assert on it);
  * a client missing `failure_rounds` heartbeats is declared DEAD: the
    two-hop splice (`Overlay.remove_nodes`) repairs each virtual ring, the
    GossipSpec is re-derived, the client-stacked state (params + any caller
    state such as optimizer slots) is remapped to the compacted survivor
    indices with the *real* ``old2new`` permutation, surviving clients'
    in-flight heartbeat counters are carried through the remap, and the step
    re-jits **exactly once per membership change**;
  * if the process itself died, training resumes from the latest checkpoint.

Why alive-as-argument: baking the straggler set into the GossipSpec (the
removed PR-2-era design) made liveness part of the traced graph — a
fresh `jax.jit` trace per straggler-set change, i.e. potentially per round.
Passing the mask as data moves the renormalization into the (already fused)
mixing reduction, whose cost is a handful of scalar ops per tile.

Time-varying overlays ride the identical mechanism: an optional
:class:`repro.overlay.plan.RoundPlan` supplies a per-schedule gate vector
each round (one-peer rotation, random subsets, throttling), shipped as a
second data argument next to ``alive`` and folded into the same fused
renormalization — so the *topology of the round* changes every round with
zero recompiles, and gates compose transparently with straggler masking and
splice repair (plans are stateless in the round index, so a repair that
changes the schedule count needs no plan surgery).

Pipelined gossip (``gossip_delay=1``) is the third rider on the design: the
round mixes the **previous** round's packed snapshot
(`gossip.mix_packed_stacked_delayed`, `mix_dense_delayed` semantics) and the
snapshot is carried as trainer state — primed from the initial params at the
first step, threaded through every round, and **remapped through splice
repair together with the params** (its layout depends only on the parameter
structure, so `old2new` row compaction is exact; the spec/degree change from
the repair only alters who gathers from it). Delay composes with alive masks
and round-plan gates unchanged, and keeps the same retrace accounting: churn
and plans are data, membership changes re-jit once. With
``gossip_codec="int8"``/``"int8_block"`` the round is the pipelined +
quantized engine composition: the carried snapshot IS the int8 wire buffer
(4x smaller state, same remap), and the same accounting holds.

Round-level **active-set subsampling** (``active_plan``, an
:class:`repro.overlay.plan.ActiveSetPlan`) rides the same alive-as-data
mechanism from the other side: each round the plan's 0/1 participation
vector multiplies the health mask *before* it ships, so an inactive client
is mixed exactly like a straggler (identity row, neighbors renormalize) —
but the product never feeds the :class:`HealthTracker`. Resting is not
failing: a client outside the cohort must not accumulate missed heartbeats,
start counting toward eviction, or perturb quarantine telemetry. Cohort
rotation over any number of rounds reuses the one executable (the vector is
data), and composes with straggler churn, gates, attacks, and splice repair
unchanged.

The **blocked substrate** (``gossip_block=B > 0``) decouples the simulated
client count from the device count: each of the n/B devices holds a
(B, ...) stacked slice of the client axis, intra-device overlay edges are
plain stacked gathers, and the cross-device part of each schedule ships as
whole-block ``ppermute`` collectives (see `repro.core.gossip.BlockedSpec`).
Splice repair under a blocked layout only fires when the survivor count
stays a multiple of B (the layout invariant); otherwise the dead are
**permanently masked** instead — identity rows forever, zero re-jits —
and the splice retries at the next death that restores divisibility
(``repairs`` records which path ran via its ``spliced`` flag).

The default step builder runs the stacked simulator round
(`gossip.mix_packed_stacked`: vmapped local DFedAvgM + packed gather-mix on
one device); pass ``step_builder`` to drop in the production shard_map step
(`launch.steps.build_train_step` has the same ``(params, batches, lr,
alive, gates)`` calling convention — its pipelined variant additionally
threads the in-flight snapshot, see `launch.steps.TrainSetup`).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core import dfedavg, engine as engine_lib, failures as failures_lib, \
    gossip as gossip_lib
from repro.core.topology import Overlay
from repro.launch import mesh as mesh_lib
from repro.overlay import plan as plan_lib
from repro.overlay.plan import ActiveSetPlan, RoundPlan
from repro.telemetry import TelemetryLogger, TraceCounter, span
from repro.telemetry import metrics as telemetry_metrics

PyTree = Any

# (spec, trainer) -> round_fn(params, batches, lr, alive, gates)
#                       -> (params, losses)
# NOTE for production wrappers around launch.steps.build_train_step: that
# builder decides gate engagement from DFLConfig.round_plan at trace time,
# so the config's round_plan must name the same plan family as
# ``trainer.plan`` — a "static" config silently ignores the shipped gates.
StepBuilder = Callable[[gossip_lib.GossipSpec, "ElasticTrainer"], Callable]


@dataclasses.dataclass
class ElasticTrainer:
    overlay: Overlay
    loss_fn: Callable
    dcfg: dfedavg.DFedAvgMConfig
    ckpt: CheckpointManager | None = None
    straggler_rounds: int = 1
    failure_rounds: int = 3
    step_builder: StepBuilder | None = None
    # THE engine front door: pass the whole gossip cell as one
    # repro.core.engine.GossipEngineConfig (substrate "stacked" or
    # "blocked" + codec x delay x screen x telemetry). The per-knob
    # gossip_* arguments below are a deprecated shim over this — they
    # mirror into the same config (engine_lib.resolve_trainer_engine), so
    # either spelling builds the bitwise-identical round. Stateful codecs
    # (engine.CODECS entry "topk_ef": sparse top-k wire + per-client EF
    # residual) thread their codec state as trainer-carried rows, remapped
    # through splice repair like params and the in-flight snapshot.
    engine: engine_lib.GossipEngineConfig | None = None
    plan: RoundPlan | None = None  # time-varying round plan (gate source)
    # round-level client subsampling (repro.overlay.plan active-set plans):
    # the plan's 0/1 participation vector multiplies the health mask each
    # round — an inactive client is mixed like a straggler but NEVER feeds
    # the HealthTracker (resting is not failing). None/"full" = everyone.
    active_plan: ActiveSetPlan | None = None
    # B > 0 = blocked substrate: n/B devices each hold a (B, ...) stacked
    # client slice; intra-device edges are stacked gathers, cross-device
    # schedule parts ship as whole-block ppermutes (gossip.BlockedSpec).
    # 0 = single-device stacked round (unchanged path).
    gossip_block: int = 0
    # 1 = pipelined gossip: each round mixes the PREVIOUS round's packed
    # snapshot (mix_dense_delayed semantics) and the snapshot is carried as
    # trainer state — see _inflight. 0 = synchronous (unchanged path).
    gossip_delay: int = 0
    # k >= 2 = Chebyshev multi-round gossip: each round runs k gossip
    # sub-rounds with Chebyshev polynomial weights over the mixing matrix
    # (engine sub_rounds axis; coefficients from the overlay's lambda via
    # executor.cheby_coeffs(), shipped as traced data each round — zero
    # retraces, and a splice repair refreshes them with the rebuilt spec).
    # 1 = the sync engine round, bit-identical (unchanged path). Stacked
    # substrate only here; does not compose with delay / screens / stateful
    # codecs (the engine config rejects those cells).
    gossip_sub_rounds: int = 1
    # wire codec of the stacked engine round (repro.core.engine): "f32"
    # (default, the exact pre-engine numerics), "int8" / "int8_block"
    # simulate the quantized wire — with gossip_delay=1 this is the
    # pipelined+quantized composition, and the carried _inflight snapshot
    # is the int8 wire itself (remapped through splice repair like any
    # other per-client row state).
    gossip_codec: str = "f32"
    # Byzantine screen of the engine round (repro.core.engine SCREENS):
    # "none" | "norm_clip" (rescale received buffers whose norm exceeds
    # screen_tau x the receiver's own; per-sender clip telemetry feeds the
    # HealthTracker suspicion counters) | "trimmed_mean" (coordinate-wise
    # trimmed mean, screen_trim dropped per side).
    gossip_screen: str = "none"
    screen_tau: float = 3.0
    screen_trim: int = 1
    # scripted attackers (failures.AttackPlan): the round perturbs the
    # post-local-step params with the plan's (2, n) round_vector — traced
    # DATA, so attacker churn retraces nothing. Plan indices refer to the
    # INITIAL membership; splice repairs remap them with the survivors.
    attack_plan: failures_lib.AttackPlan | None = None
    attack_seed: int = 0
    # quarantine: a client clipped by >= 1 receiver on this many rounds
    # (norm_clip telemetry) is evicted through the SAME splice repair as a
    # heartbeat-dead client. 0 disables.
    quarantine_rounds: int = 0
    # opt-in in-graph round metrics (repro.telemetry.TelemetryConfig): the
    # stacked engine round additionally returns a RoundMetrics dict of
    # traced scalars (consensus residual, live in-degree, gate mass, clip
    # counts) with ZERO extra retraces — metrics of the latest round are
    # kept on ``last_metrics``. None = engine round lowers exactly as
    # before (norm_clip quarantine still works: the screen's clip counters
    # ride an internal clip-only config).
    telemetry: telemetry_metrics.TelemetryConfig | None = None
    # optional structured event stream (repro.telemetry.TelemetryLogger):
    # round records with metric summaries, compile/retrace events (via the
    # shared TraceCounter), splice/mask repair records, suspicion counts,
    # and scripted-attack activations all land in one JSONL log.
    logger: TelemetryLogger | None = None

    def __post_init__(self):
        # engine= front door first: mirrors the config onto the legacy
        # knobs (or warns on deprecated per-knob use), so every check and
        # builder below reads one source of truth
        engine_lib.resolve_trainer_engine(self)
        if self.gossip_delay not in (0, 1):
            raise ValueError(f"gossip_delay must be 0 or 1, "
                             f"got {self.gossip_delay}")
        if self.gossip_codec not in engine_lib.CODECS:
            raise ValueError(f"unknown gossip_codec {self.gossip_codec!r}; "
                             f"available: {', '.join(engine_lib.CODECS)}")
        if self.gossip_screen not in engine_lib.SCREENS:
            raise ValueError(f"unknown gossip_screen {self.gossip_screen!r}; "
                             f"available: {', '.join(engine_lib.SCREENS)}")
        if self.quarantine_rounds and self.gossip_screen != "norm_clip":
            raise ValueError("quarantine_rounds needs the norm_clip screen "
                             "(its clip telemetry is the suspicion signal)")
        if (self.attack_plan is not None
                and self.attack_plan.n_clients != self.overlay.n):
            raise ValueError(f"attack_plan is for "
                             f"{self.attack_plan.n_clients} clients, overlay "
                             f"has {self.overlay.n}")
        if (self.step_builder is not None
                and (self.gossip_screen != "none"
                     or self.attack_plan is not None)):
            raise ValueError("screens/attacks compose with the built-in "
                             "stacked round; a custom step_builder must "
                             "thread them itself (launch.steps supports "
                             "gossip_screen via ParallelConfig and attacks "
                             "via DFLConfig.byzantine)")
        if self.gossip_block:
            if self.gossip_block < 0 or self.overlay.n % self.gossip_block:
                raise ValueError(
                    f"gossip_block={self.gossip_block} must be a positive "
                    f"divisor of the client count {self.overlay.n}")
            n_dev = self.overlay.n // self.gossip_block
            if n_dev > len(jax.devices()):
                raise ValueError(
                    f"blocked layout needs {n_dev} devices (= n/block), "
                    f"only {len(jax.devices())} visible")
            if self.step_builder is not None:
                raise ValueError("gossip_block composes with the built-in "
                                 "round only; a custom step_builder owns "
                                 "its own substrate")
        if self.telemetry is not None:
            if not isinstance(self.telemetry,
                              telemetry_metrics.TelemetryConfig):
                raise TypeError("telemetry must be a telemetry.TelemetryConfig"
                                f" (got {type(self.telemetry).__name__})")
            if self.step_builder is not None:
                raise ValueError("telemetry composes with the built-in "
                                 "stacked round; a production step_builder "
                                 "carries its own metrics via "
                                 "ParallelConfig.gossip_telemetry")
        if self.gossip_delay and self.step_builder is not None:
            # the production pipelined step threads its own in-flight state
            # (mesh-leading-dims layout, primed via TrainSetup.init_inflight)
            # with a different argument order than this trainer's stacked
            # round — wrapping it here would silently mis-thread the state,
            # so the combination is rejected until a production wrapper
            # protocol exists. Use the stacked delayed round (step_builder
            # =None) or drive launch.steps.build_train_step directly.
            raise ValueError("gossip_delay=1 is not supported together with "
                             "a custom step_builder; the pipelined "
                             "production step manages its own in-flight "
                             "state (launch.steps.TrainSetup)")
        self.health = failures_lib.HealthTracker(
            self.overlay.n, self.straggler_rounds, self.failure_rounds,
            self.quarantine_rounds)
        self.spec = gossip_lib.make_gossip_spec(self.overlay)
        # jit traces of the round fn, via the shared telemetry counter: a
        # hit per trace, surviving repairs (n_traces == 1 + #splices), and
        # emitting "compile" events when a logger is attached
        self.tracer = TraceCounter("elastic_round", logger=self.logger)
        self.round_no = 0          # round index feeding the plan's gates
        self.last_metrics: dict | None = None  # latest round's RoundMetrics
        self.repairs: list[dict] = []
        # current-index -> original-attack-plan-column map, compacted on
        # every splice repair so attackers keep their script across repairs
        self._attack_cols = np.arange(self.overlay.n)
        # blocked layout: dead clients that could not be spliced out without
        # stranding a partial device block — gossip-masked forever instead
        self._masked: set[int] = set()
        # delayed mode's in-flight snapshot (pack_state_stacked of last
        # round's post-local-step params); primed lazily at the first step
        # so round 0 mixes the caller's initial params
        self._inflight = None
        # stateful codec's per-client codec state (the topk_ef EF
        # residual) — primed lazily like the snapshot, remapped through
        # splice repair by the same old2new row compaction
        self._codec_state = None
        self._round = self._build(self.spec)

    def _build(self, spec: gossip_lib.GossipSpec):
        """One jitted round: vmapped local DFedAvgM + packed masked gossip.

        Called exactly once per membership (the spec is baked in as a
        static closure); the alive mask and the round plan's gates are
        traced arguments, so every straggler pattern and every per-round
        topology (one-peer rotation, subsets, throttling) reuses the same
        executable.
        """
        if self.step_builder is not None:
            return self.step_builder(spec, self)
        # build-time decision: without an active plan (None or static) the
        # gate pathway is OFF so a plain run keeps the exact (possibly
        # negative-w0) Chow weights of the PR-1/PR-2 engine; with a real
        # plan, gates are traced data. plan_lib.is_active is the one shared
        # predicate — it matches steps.py's `round_plan != "static"` rule
        use_plan = plan_lib.is_active(self.plan)
        # attack + telemetry are build-time decisions like the plan: the
        # operands themselves (attack vector, PRNG key) are traced data.
        # norm_clip quarantine needs the per-sender clip counters, so the
        # screen forces at least a clip-only telemetry config even when the
        # caller asked for none — same lowering the old with_stats path had.
        use_attack = self.attack_plan is not None
        tel = self.telemetry
        if self.gossip_screen == "norm_clip":
            tel = (dataclasses.replace(tel, clip=True) if tel is not None
                   else telemetry_metrics.clip_only())
        use_tel = tel is not None

        def client(p, b, lr):
            v = jax.tree.map(jnp.zeros_like, p)
            p, _, loss = dfedavg.local_round(p, v, b, self.loss_fn,
                                             self.dcfg, lr=lr)
            return p, loss

        if self.gossip_block:
            # blocked substrate: the gossip island is a fully-manual
            # shard_map over a 1-D client-device mesh (n/B devices, each
            # holding a (B, ...) stacked slice). The local phase + attack
            # run on the GSPMD-sharded full stack; only the mixing round is
            # manual. delay=1 / screens on blocked are rejected by the
            # engine config itself (the satellite error messages).
            b_sz = self.gossip_block
            mesh = Mesh(np.asarray(jax.devices()[:spec.n_clients // b_sz]),
                        ("clients",))
            self._gossip_mesh = mesh  # repair re-places state onto this
            self._executor = engine_lib.build_gossip_executor(
                engine_lib.GossipEngineConfig(
                    substrate="blocked", codec=self.gossip_codec,
                    delay=self.gossip_delay,
                    sub_rounds=self.gossip_sub_rounds,
                    screen=self.gossip_screen,
                    clip_tau=self.screen_tau, trim_f=self.screen_trim,
                    block=b_sz, telemetry=tel), spec, axis_names="clients")
            executor = self._executor

            def round_fn(params, batches, lr, alive, gates, attack, akey):
                self.tracer.hit()  # python side effect: runs only on trace
                params, losses = jax.vmap(client, in_axes=(0, 0, None))(
                    params, batches, lr)
                if use_attack:
                    params = failures_lib.apply_attack(params, attack, akey)

                def island(p, alive_vec, gate_vec):
                    return executor(p, alive=alive_vec,
                                    gates=gate_vec if use_plan else None)

                # telemetry metrics come out of the island device-local
                # ((block,)-leading rows); the P("clients") out_spec
                # concatenates them back to the (n,)-stacked layout — no
                # collective, same permutes as the metrics-off build
                if use_tel:
                    params, metrics = mesh_lib.shard_map(
                        island, mesh, in_specs=(P("clients"), P(), P()),
                        out_specs=(P("clients"), P("clients")))(
                        params, alive, gates)
                else:
                    params = mesh_lib.shard_map(
                        island, mesh, in_specs=(P("clients"), P(), P()),
                        out_specs=P("clients"))(params, alive, gates)
                    metrics = None
                return params, losses, metrics
            return jax.jit(round_fn)

        self._executor = engine_lib.build_gossip_executor(
            engine_lib.GossipEngineConfig(substrate="stacked",
                                          codec=self.gossip_codec,
                                          delay=self.gossip_delay,
                                          sub_rounds=self.gossip_sub_rounds,
                                          screen=self.gossip_screen,
                                          clip_tau=self.screen_tau,
                                          trim_f=self.screen_trim,
                                          telemetry=tel), spec)
        executor = self._executor

        if self.gossip_sub_rounds > 1:
            # Chebyshev multi-round round: the (k,) coefficient vector is
            # one more traced data argument next to alive/gates (the engine
            # config has already rejected delay / screens / stateful codecs
            # for this cell, so this is the only cheby-carrying round_fn)
            def round_fn(params, batches, lr, alive, gates, attack, akey,
                         cheby):
                self.tracer.hit()  # python side effect: runs only on trace
                params, losses = jax.vmap(client, in_axes=(0, 0, None))(
                    params, batches, lr)
                if use_attack:
                    params = failures_lib.apply_attack(params, attack, akey)
                out = executor(params, alive=alive,
                               gates=gates if use_plan else None,
                               cheby=cheby)
                if use_tel:
                    mixed, metrics = out
                else:
                    mixed, metrics = out, None
                return mixed, losses, metrics
            return jax.jit(round_fn)

        if executor.stateful:
            # stateful codec (topk_ef): the per-client codec state rides
            # as a second threaded state channel next to the optional
            # delay snapshot — returned right after it, threaded back in
            # by step(). inflight stays None (an empty pytree) at delay=0.
            def round_fn(params, inflight, cstate, batches, lr, alive,
                         gates, attack, akey):
                self.tracer.hit()  # python side effect: only runs on trace
                params, losses = jax.vmap(client, in_axes=(0, 0, None))(
                    params, batches, lr)
                if use_attack:
                    params = failures_lib.apply_attack(params, attack, akey)
                kw = dict(codec_state=cstate, alive=alive,
                          gates=gates if use_plan else None)
                if self.gossip_delay:
                    kw["state"] = inflight
                out = list(executor(params, **kw))
                mixed = out.pop(0)
                inflight = out.pop(0) if self.gossip_delay else None
                cstate = out.pop(0)
                metrics = out.pop(0) if use_tel else None
                return mixed, losses, inflight, cstate, metrics
            return jax.jit(round_fn)

        if self.gossip_delay:
            def round_fn(params, inflight, batches, lr, alive, gates,
                         attack, akey):
                self.tracer.hit()  # python side effect: only runs on trace
                params, losses = jax.vmap(client, in_axes=(0, 0, None))(
                    params, batches, lr)
                if use_attack:
                    params = failures_lib.apply_attack(params, attack, akey)
                out = executor(params, state=inflight, alive=alive,
                               gates=gates if use_plan else None)
                if use_tel:
                    mixed, inflight, metrics = out
                else:
                    mixed, inflight = out
                    metrics = None
                return mixed, losses, inflight, metrics
            return jax.jit(round_fn)

        def round_fn(params, batches, lr, alive, gates, attack, akey):
            self.tracer.hit()  # python side effect: runs only when tracing
            params, losses = jax.vmap(client, in_axes=(0, 0, None))(
                params, batches, lr)
            if use_attack:
                params = failures_lib.apply_attack(params, attack, akey)
            out = executor(params, alive=alive,
                           gates=gates if use_plan else None)
            if use_tel:
                mixed, metrics = out
            else:
                mixed, metrics = out, None
            return mixed, losses, metrics
        return jax.jit(round_fn)

    def gates_for_round(self, rnd: int | None = None) -> jax.Array:
        """This round's per-schedule gate vector (all-ones without a plan)."""
        rnd = self.round_no if rnd is None else rnd
        return jnp.asarray(plan_lib.gates_for(self.plan, rnd,
                                              self.spec.degree))

    def active_for_round(self, rnd: int | None = None) -> np.ndarray:
        """This round's 0/1 participation vector (all-ones without a plan)."""
        rnd = self.round_no if rnd is None else rnd
        return plan_lib.active_for(self.active_plan, rnd, self.overlay.n)

    @property
    def n_clients(self) -> int:
        return self.overlay.n

    @property
    def n_traces(self) -> int:
        """Jit traces of the round fn so far (TraceCounter-backed)."""
        return self.tracer.count

    def observe_heartbeats(self, alive: np.ndarray, params: PyTree,
                           client_state: PyTree | None = None
                           ) -> tuple[PyTree, PyTree | None, np.ndarray | None]:
        """Process one round of heartbeats.

        Args:
          alive: this round's 0/1 heartbeat vector (length n_clients).
          params: client-stacked model state.
          client_state: optional extra per-client pytree (optimizer slots,
            shard assignments, ...) remapped together with ``params`` on
            permanent failures.

        Returns ``(params, client_state, old2new)``. ``old2new`` is ``None``
        for rounds without a membership change; after a splice repair it is
        the real survivor permutation from :func:`Overlay.remove_nodes`
        (``old2new[old] = new`` or ``-1`` for the dead) — apply it to any
        per-client state you keep outside ``client_state``.

        Straggler-only changes touch *no* compiled state: the next
        :meth:`step` simply ships a different alive vector.
        """
        self.health.observe(alive)
        dead = [int(d) for d in self.health.dead()
                if int(d) not in self._masked]
        if not dead:
            return params, client_state, None

        evict = sorted(self._masked | set(dead))
        if self.gossip_block and (self.overlay.n - len(evict)) \
                % self.gossip_block:
            # blocked layout invariant: the survivor count must stay a
            # multiple of block, or the splice would strand a partial
            # device slice. Mask the dead permanently instead (identity
            # rows forever, no re-jit) and retry the splice at the next
            # death that restores divisibility.
            self._masked.update(dead)
            self.repairs.append({"dead": dead, "spliced": False,
                                 "masked": sorted(self._masked),
                                 "n_after": self.overlay.n})
            if self.logger is not None:
                self.logger.repair(self.repairs[-1])
            return params, client_state, None

        # the in-flight snapshot and the codec state ride the same remap as
        # params: their layouts depend only on the parameter structure
        # (never on the topology), so dropping the dead rows keeps the
        # delayed semantics — and the survivors' EF residuals — exact
        bundle = (params, client_state, self._inflight, self._codec_state)
        self.overlay, self.spec, bundle, old2new = failures_lib.repair_and_remap(
            self.overlay, evict, bundle)
        params, client_state, self._inflight, self._codec_state = bundle
        suspects = set(int(s) for s in self.health.suspects())
        self.repairs.append({"dead": evict, "spliced": True,
                             "quarantined": sorted(suspects & set(evict)),
                             "n_after": self.overlay.n})
        if self.logger is not None:
            self.logger.repair(self.repairs[-1])
        self._masked.clear()
        # attackers keep their plan column across compaction: survivors'
        # current indices shift, their original-plan identity must not
        self._attack_cols = self._attack_cols[np.asarray(old2new) >= 0]
        # survivors carry their in-flight heartbeat counters to the
        # compacted indices (a straggling survivor stays a straggler)
        self.health = self.health.remap(old2new)
        self._round = self._build(self.spec)  # the one re-jit per repair
        if self.gossip_block:
            # a splice can shrink the blocked mesh (fewer client-devices);
            # the remapped rows are still committed to the OLD device set,
            # so re-place them onto the new mesh before the next round
            sh = NamedSharding(self._gossip_mesh, P("clients"))
            params = jax.device_put(params, sh)
            if client_state is not None:
                client_state = jax.device_put(client_state, sh)
        return params, client_state, old2new

    def step(self, params: PyTree, batches: PyTree, lr: float):
        """Run one round under the current health mask, the active-set
        plan's participation vector, and the round plan's gates (no rebuilds
        here — all three are data arguments). In delayed mode the in-flight
        snapshot is threaded through as trainer state. The round is a
        ``dfl.round`` span, its host work in ``dfl.*`` leaf spans."""
        with span("dfl.round", step=self.round_no):
            return self._step(params, batches, lr)

    def _step(self, params: PyTree, batches: PyTree, lr: float):
        with span("dfl.operands"):
            alive = self.health.alive_mask()
            if self._masked:
                # blocked-layout permanent masking: dead-but-unspliceable
                # clients stay gossip-masked (identity rows) forever
                alive = alive.copy()
                alive[sorted(self._masked)] = 0.0
            if plan_lib.is_subsampling(self.active_plan):
                # the active set multiplies the GOSSIP mask only — it is
                # computed here, after the heartbeats were observed,
                # precisely so it can never feed the HealthTracker
                # (resting != failing)
                alive = alive * plan_lib.active_for(self.active_plan,
                                                    self.round_no,
                                                    self.overlay.n)
            alive = jnp.asarray(alive)
            gates = self.gates_for_round()
            attack = akey = None
            if self.attack_plan is not None:
                # plan columns are in ORIGINAL indices; gather the
                # survivors' rows so a repaired run keeps each attacker's
                # script
                vec = self.attack_plan.round_vector(self.round_no)
                attack = jnp.asarray(vec[:, self._attack_cols])
                akey = jnp.asarray(
                    np.array([self.attack_seed, self.round_no], np.uint32))
                if self.logger is not None:
                    for r, ids, mode, mag in self.attack_plan.events:
                        if r == self.round_no:  # script activates this round
                            self.logger.event(
                                "attack", round=self.round_no, mode=mode,
                                clients=[int(c) for c in ids],
                                magnitude=float(mag))
            rnd = self.round_no
            self.round_no += 1
            lr = jnp.asarray(lr, jnp.float32)
            if self.gossip_block:
                # the blocked round returns params committed to the client
                # mesh; committing round 0's input there too keeps every
                # round on one trace (the sharding is part of the traced
                # type)
                params = jax.device_put(
                    params, NamedSharding(self._gossip_mesh, P("clients")))
        if self.step_builder is not None:
            # custom builders keep the documented 5-arg StepBuilder contract
            # (screens/attacks with a builder are rejected in __post_init__)
            with span("dfl.dispatch"):
                return self._round(params, batches, lr, alive, gates)
        phase = (self.logger.phase("round") if self.logger is not None
                 else contextlib.nullcontext())
        with span("dfl.dispatch"), phase:
            if self._executor.stateful:
                if self._codec_state is None:  # prime: EF residual zeros
                    self._codec_state = self._executor.init_codec_state(
                        params)
                if self.gossip_delay and self._inflight is None:
                    self._inflight = self._executor.init_state(params)
                (params, losses, self._inflight, self._codec_state,
                 metrics) = self._round(
                    params, self._inflight, self._codec_state, batches, lr,
                    alive, gates, attack, akey)
            elif self.gossip_delay:
                if self._inflight is None:  # prime: round 0 mixes the
                    # initial snapshot in the codec's wire format (packed
                    # f32 buffers, or the folded int8 wire when quantized)
                    self._inflight = self._executor.init_state(params)
                params, losses, self._inflight, metrics = self._round(
                    params, self._inflight, batches, lr, alive, gates,
                    attack, akey)
            elif not self.gossip_block and self.gossip_sub_rounds > 1:
                # coefficients recomputed from the live executor each round:
                # a splice repair rebuilt it with the new spec's lambda, and
                # the (k,) shape is fixed so the refresh never retraces
                cheby = jnp.asarray(self._executor.cheby_coeffs())
                params, losses, metrics = self._round(params, batches, lr,
                                                      alive, gates, attack,
                                                      akey, cheby)
            else:
                params, losses, metrics = self._round(params, batches, lr,
                                                      alive, gates, attack,
                                                      akey)
        self.last_metrics = metrics
        wants = self.logger is not None and self.logger.wants_round(rnd)
        counts = loss = None
        with span("dfl.sync"):
            if metrics is not None and "clipped" in metrics:
                # per-sender count of receivers that clipped them this round
                counts = np.asarray(metrics["clipped"])
            if wants:
                # peeked BEFORE building the record: the loss/metrics floats
                # are the round's only deliberate device->host sync, and the
                # sampled logger (round_every > 1) skips it on off-rounds
                loss = float(jnp.mean(losses))
        with span("dfl.record"):
            if counts is not None:
                self.health.observe_suspicion(counts)
                if self.logger is not None and counts.sum() > 0:
                    self.logger.event("suspicion", round=rnd,
                                      clipped=[int(c) for c in counts])
            if wants:
                self.logger.round(
                    rnd, loss=loss, alive=int(np.asarray(alive).sum()),
                    **telemetry_metrics.summarize_metrics(
                        metrics, n_clients=self.overlay.n))
        return params, losses

    def checkpoint(self, rnd: int, params: PyTree) -> None:
        if self.ckpt is not None:
            self.ckpt.maybe_save(rnd, params, {"round": rnd,
                                               "n_clients": self.overlay.n})
