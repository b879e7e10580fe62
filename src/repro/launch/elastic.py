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

Pipelined gossip (``engine.delay=1``) is the third rider on the design: the
round mixes the **previous** round's snapshot (`mix_dense_delayed`
semantics) and the snapshot is carried as trainer state — primed from the
initial params at the first step, threaded through every round, and
**remapped through splice repair together with the params** (its layout
depends only on the parameter structure, so `old2new` row compaction is
exact; the spec/degree change from the repair only alters who gathers from
it). Delay composes with alive masks and round-plan gates unchanged, and
keeps the same retrace accounting: churn and plans are data, membership
changes re-jit once. With ``engine.codec="int8"``/``"int8_block"`` the
round is the pipelined + quantized engine composition: the carried
snapshot IS the int8 wire buffer (4x smaller state, same remap), and the
same accounting holds.

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

The **blocked substrate** (``engine.substrate="blocked"``, ``block=B``)
decouples the simulated client count from the device count: each of the
n/B devices holds a (B, ...) stacked slice of the client axis, intra-device
overlay edges are plain stacked gathers, and the cross-device part of each
schedule ships as whole-block ``ppermute`` collectives (see
`repro.core.gossip.BlockedSpec`).
Splice repair under a blocked layout only fires when the survivor count
stays a multiple of B (the layout invariant); otherwise the dead are
**permanently masked** instead — identity rows forever, zero re-jits —
and the splice retries at the next death that restores divisibility
(``repairs`` records which path ran via its ``spliced`` flag).

Every round is the simulator round that `SimTrainer` runs too
(`launch.sim_round`: vmapped local DFedAvgM, the attack, then the gossip
executor of ``engine``); this module keeps the host loop around it.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import dfedavg, engine as engine_lib, failures as failures_lib, \
    gossip as gossip_lib
from repro.core.topology import Overlay
from repro.launch import sim_round
from repro.overlay import plan as plan_lib
from repro.overlay.plan import ActiveSetPlan, RoundPlan
from repro.telemetry import TelemetryLogger, TraceCounter, span
from repro.telemetry import metrics as telemetry_metrics

PyTree = Any


@dataclasses.dataclass
class ElasticTrainer:
    overlay: Overlay
    loss_fn: Callable
    dcfg: dfedavg.DFedAvgMConfig
    ckpt: CheckpointManager | None = None
    straggler_rounds: int = 1
    failure_rounds: int = 3
    # the gossip cell: substrate "stacked" or "blocked" (block=B clients
    # per device, n/B devices) x codec x delay x sub_rounds x screen x
    # telemetry. Delayed and stateful cells carry their snapshot and codec
    # state as trainer rows (_inflight, _codec_state), remapped through
    # splice repair like params. With engine.telemetry set, the latest
    # round's RoundMetrics are kept on ``last_metrics``; the norm_clip
    # screen adds the clip counts quarantine reads even when it is None.
    engine: engine_lib.GossipEngineConfig = engine_lib.GossipEngineConfig(
        substrate="stacked")
    plan: RoundPlan | None = None  # time-varying round plan (gate source)
    # round-level client subsampling (repro.overlay.plan active-set plans):
    # the plan's 0/1 participation vector multiplies the health mask each
    # round — an inactive client is mixed like a straggler but NEVER feeds
    # the HealthTracker (resting is not failing). None/"full" = everyone.
    active_plan: ActiveSetPlan | None = None
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
    # optional structured event stream (repro.telemetry.TelemetryLogger):
    # round records with metric summaries, compile/retrace events (via the
    # shared TraceCounter), splice/mask repair records, suspicion counts,
    # and scripted-attack activations all land in one JSONL log.
    logger: TelemetryLogger | None = None

    def __post_init__(self):
        sim_round.check("ElasticTrainer", self.engine, self.overlay.n,
                        self.attack_plan)
        if self.quarantine_rounds and self.engine.screen != "norm_clip":
            raise ValueError("quarantine_rounds needs the norm_clip screen "
                             "(its clip telemetry is the suspicion signal)")
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
        self._round_fn = self._build(self.spec)

    def _build(self, spec: gossip_lib.GossipSpec):
        """The shared simulator round (launch.sim_round) for ``spec``.

        Called exactly once per membership (the spec is baked in as a
        static closure); the alive mask and the round plan's gates are
        traced arguments, so every straggler pattern and every per-round
        topology (one-peer rotation, subsets, throttling) reuses the same
        executable.
        """
        cfg = self.engine
        if cfg.screen == "norm_clip":
            # quarantine reads the screen's per-sender clip counts, so the
            # round carries at least a clip-only telemetry config
            tel = cfg.telemetry
            cfg = dataclasses.replace(
                cfg, telemetry=(telemetry_metrics.clip_only() if tel is None
                                else dataclasses.replace(tel, clip=True)))
        self._sim = sim_round.build(
            cfg, spec, loss_fn=self.loss_fn, dcfg=self.dcfg,
            use_plan=plan_lib.is_active(self.plan),
            use_attack=self.attack_plan is not None, tracer=self.tracer)
        return self._sim.round_fn

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
        if self.engine.block and (self.overlay.n - len(evict)) \
                % self.engine.block:
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
        self._round_fn = self._build(self.spec)  # the one re-jit per repair
        return (self._sim.place(params), self._sim.place(client_state),
                old2new)

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
            params = self._sim.place(params)
        phase = (self.logger.phase("round") if self.logger is not None
                 else contextlib.nullcontext())
        with span("dfl.dispatch"), phase:
            extra = self._sim.extra(params, self._inflight, self._codec_state)
            params, losses, metrics, *carried = self._round_fn(
                params, batches, lr, alive, gates, attack, akey, *extra)
            self._inflight, self._codec_state = self._sim.carried(carried)
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
