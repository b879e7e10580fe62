"""End-to-end DFL training driver (CPU-runnable simulator path).

Runs the *same algorithm* as the production multi-pod step (DFedAvgM local
rounds + overlay gossip), with the client axis realized as a stacked/vmapped
array on the local device(s) instead of a 512-chip mesh. Includes the full
fault-tolerance loop: checkpoint/rotate/resume, straggler weight
renormalization, permanent-failure splice repair + re-jit.

Usage (example: char-LM over the bundled Shakespeare, 16 clients, d=4):
    PYTHONPATH=src python -m repro.launch.train --clients 16 --rounds 40 \
        --topology expander --degree 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import DFLConfig
from repro.core import dfedavg, engine as engine_lib, failures as failures_lib, \
    gossip as gossip_lib
from repro.core.topology import Overlay
from repro.launch import compile_cache, sim_round
from repro.launch.steps import build_overlay
from repro.models import lstm as lstm_model
from repro.models import params as params_lib
from repro.overlay import plan as overlay_plan
from repro.telemetry import TelemetryLogger, TraceCounter, span
from repro.telemetry import metrics as telemetry_metrics

PyTree = Any


@dataclasses.dataclass
class SimTrainer:
    """DFL simulator: stacked clients + schedule gossip (vmap path)."""

    overlay: Overlay
    loss_fn: Callable
    dcfg: dfedavg.DFedAvgMConfig
    ckpt: CheckpointManager | None = None
    # the gossip cell: substrate "stacked" or "blocked" (block=B clients
    # per device, n/B devices) x codec x delay x sub_rounds x screen x
    # telemetry. With engine.telemetry set, run()'s history records carry
    # the round metrics' host summary (consensus residual, in-degree, gate
    # mass, clip counts).
    engine: engine_lib.GossipEngineConfig = engine_lib.GossipEngineConfig(
        substrate="stacked")
    plan: overlay_plan.RoundPlan | None = None  # time-varying gates source
    # round-level client subsampling (active-set plans): the 0/1
    # participation vector multiplies the alive mask each round — inactive
    # clients keep their params (identity rows); cohort rotation is data,
    # never a retrace. None (or the "full" plan) = everyone participates.
    active_plan: overlay_plan.ActiveSetPlan | None = None
    # scripted attackers: the (2, n) round_vector + PRNG key are traced
    # data, so attacker churn never retraces the round
    attack_plan: failures_lib.AttackPlan | None = None
    attack_seed: int = 0
    # optional structured JSONL event stream (round records, compiles,
    # repairs) — see repro.telemetry.TelemetryLogger
    logger: TelemetryLogger | None = None

    def __post_init__(self):
        sim_round.check("SimTrainer", self.engine, self.overlay.n,
                        self.attack_plan)
        self.spec = gossip_lib.make_gossip_spec(self.overlay)
        # shared retrace accounting (emits "compile" events when logging)
        self.tracer = TraceCounter("sim_round", logger=self.logger)
        self.last_metrics: dict | None = None
        self._alive = np.ones(self.overlay.n, dtype=np.float32)
        self._inflight = None  # delayed mode's carried snapshot
        # stateful codec's per-client codec state (topk_ef EF residual);
        # primed lazily, remapped through repair like the snapshot
        self._codec_state = None
        # current-index -> original-plan-column map (compacted on repair)
        self._attack_cols = np.arange(self.overlay.n)
        self._round_fn = self._build(self.spec)

    def _build(self, spec):
        """The shared simulator round (launch.sim_round) for ``spec``."""
        self._sim = sim_round.build(
            self.engine, spec, loss_fn=self.loss_fn, dcfg=self.dcfg,
            use_plan=overlay_plan.is_active(self.plan),
            use_attack=self.attack_plan is not None, tracer=self.tracer)
        return self._sim.round_fn

    @property
    def round_fn(self):
        """The jitted round (local phase + gossip) that ``run`` calls."""
        return self._round_fn

    def _attack_operands(self, rnd: int):
        if self.attack_plan is None:
            return None, None
        vec = self.attack_plan.round_vector(rnd)
        return (jnp.asarray(vec[:, self._attack_cols]),
                jnp.asarray(np.array([self.attack_seed, rnd], np.uint32)))

    def _gates(self, rnd: int) -> jnp.ndarray:
        return jnp.asarray(overlay_plan.gates_for(self.plan, rnd,
                                                  self.spec.degree))

    # ---------------------------------------------------------- failures
    def set_stragglers(self, alive_mask: np.ndarray) -> None:
        """Transient failures: renormalized gossip for the coming rounds.

        The mask is a traced argument of the packed round (no rebuild here,
        zero recompiles — see launch/elastic.py for the full design note).
        """
        self._alive = np.asarray(alive_mask, dtype=np.float32)

    def repair(self, dead: list[int], params: PyTree) -> PyTree:
        """Permanent failures: splice repair, state remap, re-jit. The
        delayed-mode in-flight snapshot rides the same row compaction."""
        block = self.engine.block
        if block and (self.overlay.n - len(dead)) % block:
            # the blocked layout needs the survivor count to stay a
            # multiple of block; mask the dead via set_stragglers instead
            # (ElasticTrainer automates this masking-vs-splice decision)
            raise ValueError(
                f"splicing {len(dead)} of {self.overlay.n} clients leaves a "
                f"partial device block (block={block}); keep the "
                "dead masked or evict a block-multiple")
        bundle = (params, self._inflight, self._codec_state)
        self.overlay, self.spec, bundle, old2new = failures_lib.repair_and_remap(
            self.overlay, dead, bundle)
        params, self._inflight, self._codec_state = bundle
        # surviving stragglers keep their mask through the index compaction
        survivors = old2new >= 0
        new_alive = np.ones(self.overlay.n, dtype=np.float32)
        new_alive[old2new[survivors]] = self._alive[survivors]
        self._alive = new_alive
        # attackers keep their original plan column across compaction
        self._attack_cols = self._attack_cols[survivors]
        if self.logger is not None:
            self.logger.repair({"dead": [int(d) for d in dead],
                                "spliced": True,
                                "n_after": self.overlay.n})
        self._round_fn = self._build(self.spec)
        return self._sim.place(params)

    # ------------------------------------------------------------- train
    def _run_round(self, params, batches, batch_fn, rnd, rounds, lr_fn,
                   log_every, eval_fn, failure_plan):
        """One round of :meth:`run`, its host work in ``dfl.*`` spans.
        ``batches`` is round ``rnd``'s batch where the round before built it
        ahead, else None; returns the params, round ``rnd + 1``'s batch
        (None after the last round) and the round's record."""
        with span("dfl.batch"):
            if batches is None:  # a call's first round: none built ahead
                batches = batch_fn(rnd)
        with span("dfl.operands"):
            if failure_plan is not None:
                mask = failure_plan.alive_mask(rnd)
                if not np.array_equal(mask, self._alive):
                    self.set_stragglers(mask)
            lr_t = jnp.asarray(lr_fn(rnd), jnp.float32)
            attack, akey = self._attack_operands(rnd)
            alive_t = self._alive
            if overlay_plan.is_subsampling(self.active_plan):
                # inactive clients are mixed like stragglers (identity
                # rows) but are only resting — the plan never touches the
                # persistent straggler mask itself
                alive_t = alive_t * overlay_plan.active_for(
                    self.active_plan, rnd, self.overlay.n)
            alive_t = jnp.asarray(alive_t)
            gates = self._gates(rnd)
        with span("dfl.dispatch"):
            extra = self._sim.extra(params, self._inflight, self._codec_state)
            params, losses, metrics, *carried = self._round_fn(
                params, batches, lr_t, alive_t, gates, attack, akey, *extra)
            self._inflight, self._codec_state = self._sim.carried(carried)
        ahead = None
        if rnd + 1 < rounds:
            # the device runs round rnd while the host builds the next batch;
            # the span stays outside the dfl. leaf spans, so each round
            # keeps the five that perf/scopes.py splits its time by
            with span("sim.batch_ahead"):
                ahead = batch_fn(rnd + 1)
        with span("dfl.sync"):
            loss = float(jnp.mean(losses))
        with span("dfl.record"):
            self.last_metrics = metrics
            rec = {"round": rnd, "train_loss": loss}
            rec.update(telemetry_metrics.summarize_metrics(
                metrics, n_clients=self.overlay.n))
            if eval_fn is not None and rnd % log_every == 0:
                rec.update(eval_fn(params))
            if self.logger is not None and self.logger.wants_round(rnd):
                self.logger.round(rnd, **{k: v for k, v in rec.items()
                                          if k != "round"})
            if self.ckpt is not None:
                self.ckpt.maybe_save(rnd, params, {"round": rnd})
        return params, ahead, rec

    def run(self, params: PyTree, batch_fn: Callable[[int], PyTree],
            rounds: int, lr_fn: Callable[[int], float],
            start_round: int = 0, log_every: int = 1,
            eval_fn: Callable[[PyTree], dict] | None = None,
            failure_plan: failures_lib.FailurePlan | None = None
            ) -> tuple[PyTree, list[dict]]:
        """Runs rounds [``start_round``, ``rounds``); returns the params and
        one record per round.

        The host keeps one batch ahead of the device: ``batch_fn(r + 1)``
        is called once round r is dispatched, before the host waits for
        its loss, and so before round r's record, eval and checkpoint. A
        batch function must therefore depend on the round index alone.
        ``batch_fn`` is called once per round, in order, and never for a
        round past ``rounds``; an exception it raises leaves ``run`` at
        once, with the round before it dispatched and not synced."""
        history: list[dict] = []
        params = self._sim.place(params)
        batches = None
        for rnd in range(start_round, rounds):
            with span("dfl.round", step=rnd):
                params, batches, rec = self._run_round(
                    params, batches, batch_fn, rnd, rounds, lr_fn, log_every,
                    eval_fn, failure_plan)
            history.append(rec)
        return params, history


# --------------------------------------------------------------- char-LM app
@dataclasses.dataclass
class CharLM:
    """A char-LM DFL run over the bundled Shakespeare corpus, assembled and
    ready for ``trainer.run`` (see :func:`build_char_lm`)."""

    trainer: SimTrainer
    params: PyTree
    batch_fn: Callable[[int], PyTree]
    eval_fn: Callable[[PyTree], dict]
    lr: float
    failure_plan: failures_lib.FailurePlan | None = None
    logger: TelemetryLogger | None = None
    start_round: int = 0

    def run(self, rounds: int) -> tuple[PyTree, list[dict]]:
        params, history = self.trainer.run(
            self.params, self.batch_fn, rounds, lr_fn=lambda r: self.lr,
            eval_fn=self.eval_fn, failure_plan=self.failure_plan,
            start_round=self.start_round)
        if self.logger is not None:
            self.logger.close()
        return params, history


def build_char_lm(n_clients=16, topology="expander", degree=4,
                  local_steps=3, batch=8, seq=64, lr=0.5, momentum=0.9,
                  ckpt_dir=None, seed=0, drop_fraction=0.0, drop_round=10,
                  round_plan="static", gossip_delay=0, gossip_sub_rounds=1,
                  gossip_codec="f32", gossip_screen="none",
                  attackers=0, attack_mode="sign_flip",
                  attack_magnitude=1.0, active_set="full", active_k=1,
                  active_shards=2, gossip_block=0,
                  telemetry=False, telemetry_log=None) -> CharLM:
    from repro.data import federated, pipeline, shakespeare

    toks, vocab = shakespeare.corpus()
    spans = federated.span_split(len(toks), n_clients, seed=seed)
    batcher = pipeline.TokenBatcher(tokens=toks, spans=spans, batch_size=batch,
                                    seq_len=seq, local_steps=local_steps,
                                    seed=seed)
    struct = lstm_model.param_struct(vocab=len(vocab))
    rng = jax.random.key(seed)
    params = jax.vmap(lambda i: params_lib.init_params(struct, rng))(
        jnp.arange(n_clients))

    dfl = DFLConfig(topology=topology, degree=degree, seed=seed,
                    round_plan=round_plan)
    overlay = build_overlay(n_clients, dfl)
    dcfg = dfedavg.DFedAvgMConfig(local_steps=local_steps, lr=lr,
                                  momentum=momentum)
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    # a "static" plan is inert (is_active: gate pathway stays off)
    plan = overlay_plan.make_plan(dfl.round_plan, k=dfl.plan_k,
                                  fraction=dfl.plan_fraction, seed=seed)
    # a "full" active set is likewise inert (is_subsampling)
    active = overlay_plan.make_active_set(active_set, k=active_k,
                                          n_shards=active_shards, seed=seed)
    attack = None
    if attackers > 0:
        attack = failures_lib.sample_attackers(n_clients, attackers,
                                               mode=attack_mode,
                                               magnitude=attack_magnitude,
                                               seed=seed)
    logger = None
    if telemetry_log is not None:
        logger = TelemetryLogger(telemetry_log, run="char_lm",
                                 n_clients=n_clients, topology=topology,
                                 degree=degree, codec=gossip_codec)
    # the one engine-config front door: substrate x codec x delay x screen
    # (x telemetry) as a single cell instead of five loose knobs
    engine = engine_lib.GossipEngineConfig(
        substrate="blocked" if gossip_block else "stacked",
        codec=gossip_codec, delay=gossip_delay,
        sub_rounds=gossip_sub_rounds, screen=gossip_screen,
        block=gossip_block,
        telemetry=(telemetry_metrics.TelemetryConfig()
                   if telemetry or telemetry_log else None))
    trainer = SimTrainer(overlay=overlay, loss_fn=lstm_model.loss_fn,
                         dcfg=dcfg, ckpt=ckpt, plan=plan,
                         active_plan=active, engine=engine,
                         attack_plan=attack, attack_seed=seed,
                         logger=logger)

    # held-out evaluation: last 10% of the corpus
    ev = pipeline.TokenBatcher(tokens=toks, spans=[(int(len(toks) * .9),
                                                    len(toks))],
                               batch_size=32, seq_len=seq, local_steps=1,
                               seed=seed + 1)

    def eval_fn(params):
        b = ev.round_batches(0)
        p0 = jax.tree.map(lambda x: x[0], params)  # client-0 model
        loss, aux = lstm_model.loss_fn(p0, {"tokens": jnp.asarray(b["tokens"][0, 0]),
                                            "labels": jnp.asarray(b["labels"][0, 0])})
        return {"test_loss": float(loss), "test_acc": float(aux["acc"])}

    failure_plan = None
    if drop_fraction > 0:
        failure_plan = failures_lib.sample_failures(n_clients, drop_fraction,
                                                    drop_round, seed=seed)

    def batch_fn(rnd):
        b = batcher.round_batches(rnd)
        return {"tokens": jnp.asarray(b["tokens"]),
                "labels": jnp.asarray(b["labels"])}

    start = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        restored = ckpt.restore(params)
        if restored is not None:
            params, meta = restored
            start = int(meta.get("round", 0)) + 1
            print(f"[resume] from round {start}")

    return CharLM(trainer=trainer, params=params, batch_fn=batch_fn,
                  eval_fn=eval_fn, lr=lr, failure_plan=failure_plan,
                  logger=logger, start_round=start)


def run_char_lm(rounds=30, **kw) -> list[dict]:
    """Train the char-LM for ``rounds`` rounds (``kw``: see
    :func:`build_char_lm`); returns the per-round history records."""
    return build_char_lm(**kw).run(rounds)[1]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--topology", default="expander",
                    help="any family in repro.overlay.registry "
                         "(expander, ring, complete, torus, hypercube, "
                         "random_regular, onepeer_exp, erdos_renyi)")
    ap.add_argument("--degree", type=int, default=4)
    ap.add_argument("--plan", default="static",
                    choices=["static", "one_peer", "random_subset",
                             "throttle"],
                    help="time-varying round plan (gates-as-data)")
    ap.add_argument("--gossip-delay", type=int, default=0, choices=[0, 1],
                    help="1 = pipelined (one-round-delayed) gossip")
    ap.add_argument("--gossip-sub-rounds", type=int, default=1,
                    help="k >= 2: Chebyshev multi-round gossip — k gossip "
                         "sub-rounds per round with Chebyshev polynomial "
                         "weights over the mixing matrix (1 = sync engine)")
    ap.add_argument("--gossip-codec", default="f32",
                    choices=list(engine_lib.CODECS),
                    help="wire codec of the engine round (int8_block + "
                         "--gossip-delay 1 = pipelined+quantized; topk_ef "
                         "= sparse top-k wire with error feedback)")
    ap.add_argument("--gossip-screen", default="none",
                    choices=["none", "norm_clip", "trimmed_mean"],
                    help="Byzantine screen over received gossip payloads")
    ap.add_argument("--active-set", default="full",
                    choices=["full", "random_k", "shards", "stratified"],
                    help="round-level client subsampling plan "
                         "(participation-as-data, zero retraces)")
    ap.add_argument("--active-k", type=int, default=1,
                    help="active clients per round (random_k/stratified)")
    ap.add_argument("--active-shards", type=int, default=2,
                    help="cohort count (shards) / strata (stratified)")
    ap.add_argument("--gossip-block", type=int, default=0,
                    help="B > 0: blocked substrate, B simulated clients "
                         "per device (n/B devices; 0 = stacked)")
    ap.add_argument("--attackers", type=int, default=0,
                    help="number of scripted Byzantine clients")
    ap.add_argument("--attack-mode", default="sign_flip",
                    choices=["sign_flip", "scale", "noise"])
    ap.add_argument("--telemetry", action="store_true",
                    help="emit in-graph round metrics into the history "
                         "records (consensus residual, in-degree, gate "
                         "mass, clip counts)")
    ap.add_argument("--telemetry-log", default=None,
                    help="write the structured JSONL event stream here "
                         "(implies --telemetry)")
    ap.add_argument("--local-steps", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--drop-fraction", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    compile_cache.enable()

    hist = run_char_lm(n_clients=args.clients, rounds=args.rounds,
                       topology=args.topology, degree=args.degree,
                       local_steps=args.local_steps, lr=args.lr,
                       ckpt_dir=args.ckpt_dir,
                       drop_fraction=args.drop_fraction,
                       round_plan=args.plan, gossip_delay=args.gossip_delay,
                       gossip_sub_rounds=args.gossip_sub_rounds,
                       gossip_codec=args.gossip_codec,
                       gossip_screen=args.gossip_screen,
                       attackers=args.attackers,
                       attack_mode=args.attack_mode,
                       active_set=args.active_set, active_k=args.active_k,
                       active_shards=args.active_shards,
                       gossip_block=args.gossip_block,
                       telemetry=args.telemetry,
                       telemetry_log=args.telemetry_log)
    for rec in hist:
        print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()
