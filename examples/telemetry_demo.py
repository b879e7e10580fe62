"""Telemetry walkthrough: the same elastic run, fully observable.

Act 1 — in-graph round metrics: set ``telemetry=TelemetryConfig()`` on an
ElasticTrainer's engine config and every round also returns traced scalars
computed INSIDE the jitted round — the consensus residual (how far the
clients disagree), the realized in-degree under churn, the per-schedule gate
mass.  No extra collectives, no retraces: off, the round lowers to
bit-identical HLO; on, the metrics ride values the mix already holds.

Act 2 — the event stream: attach a ``TelemetryLogger`` and the trainer
narrates the run as ordered JSONL — run header, compile events (one per
re-jit), a scripted attacker switching on, norm-clip suspicion counts,
the quarantine splice repair, and one round record per round with the
metric summary and phase timings.  The stream then folds into the same
summary report the bench suite ships as its CI artifact.

    PYTHONPATH=src python examples/telemetry_demo.py
"""
import json
import os
import tempfile

import jax.numpy as jnp
import numpy as np

from repro.core import dfedavg, engine as engine_lib, failures
from repro.core.topology import expander_overlay
from repro.launch.elastic import ElasticTrainer
from repro.telemetry import TelemetryConfig, TelemetryLogger, read_jsonl
from repro.telemetry.report import summarize_run_log

N, DIM, DEGREE = 12, 16, 4
ATTACKER = 3


def loss_fn(params, batch):
    return jnp.mean(jnp.square(params["w"] - batch["target"])), {}


def batches(n, k=2):
    return {"target": jnp.zeros((n, k, DIM), jnp.float32)}


rng = np.random.default_rng(0)
init = {"w": jnp.asarray(rng.standard_normal((N, DIM)), jnp.float32)}

print("== act 1: in-graph round metrics (no logger, no host syncs) ==")
trainer = ElasticTrainer(
    overlay=expander_overlay(N, DEGREE, seed=0), loss_fn=loss_fn,
    dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2, momentum=0.5),
    failure_rounds=10**9,
    engine=engine_lib.GossipEngineConfig(substrate="stacked",
                                         telemetry=TelemetryConfig()))
params = init
print("round  resid_sqnorm  in_degree(mean)  live")
for rnd in range(6):
    alive = np.ones(N, np.float32)
    alive[rng.integers(N)] = 0.0  # a different straggler ~every round
    params, _, _ = trainer.observe_heartbeats(alive, params)
    params, _ = trainer.step(params, batches(N), 0.2)
    m = trainer.last_metrics  # traced values, fetched only when YOU look
    print(f"{rnd:5d}  {float(jnp.sum(m['resid_sqnorm'])):12.4f}  "
          f"{float(jnp.mean(m['in_degree'])):15.2f}  {int(alive.sum()):4d}")
assert trainer.n_traces == 1  # churn + metrics never retrace
print("consensus residual falls as gossip mixes; one executable "
      f"(n_traces={trainer.n_traces})\n")

print("== act 2: the event stream — attack, suspicion, quarantine ==")
log_path = os.path.join(tempfile.mkdtemp(prefix="telemetry_demo"),
                        "run.jsonl")
plan = failures.AttackPlan(N, events=((1, (ATTACKER,), "sign_flip", 20.0),))
with TelemetryLogger(log_path, run="telemetry_demo", n_clients=N,
                     topology="expander", degree=DEGREE) as logger:
    trainer = ElasticTrainer(
        overlay=expander_overlay(N, DEGREE, seed=0), loss_fn=loss_fn,
        dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2, momentum=0.5),
        failure_rounds=10**9, attack_plan=plan, quarantine_rounds=2,
        engine=engine_lib.GossipEngineConfig(
            substrate="stacked", screen="norm_clip", clip_tau=3.0),
        logger=logger)
    params = init
    for rnd in range(6):
        params, _, old2new = trainer.observe_heartbeats(
            np.ones(trainer.n_clients), params)
        params, _ = trainer.step(params, batches(trainer.n_clients), 0.2)

print(f"stream at {log_path}:")
for rec in read_jsonl(log_path):
    line = {k: v for k, v in rec.items() if k not in ("ts", "seq")}
    print(f"  [{rec['seq']:2d}] {json.dumps(line)[:112]}")

summary = summarize_run_log(log_path)
print("\nreport (the same summarizer CI folds into "
      "experiments/bench/summary.json):")
print(json.dumps(summary, indent=1)[:600])
assert summary["repairs"] == 1  # the quarantine splice made the stream
