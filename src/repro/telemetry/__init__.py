"""Telemetry: in-graph round metrics, the unified event stream, and reports.

The repo's central invariants — exactly d collectives/round, zero retraces
under churn/gates/active-sets, screened-wire suspicion — used to be
observable only through scattered per-bench asserts and ad-hoc ``n_traces``
counters. This package makes them one queryable layer:

* :mod:`repro.telemetry.metrics` — the **traced** side. An opt-in
  :class:`TelemetryConfig` on :class:`repro.core.engine.GossipEngineConfig`
  (surfaced as ``ParallelConfig.gossip_telemetry`` and the trainers'
  ``telemetry`` knob) makes the executor and the production train step
  additionally return a small :data:`RoundMetrics` pytree of traced values
  computed from what the round already materializes — neighborhood residual
  sqnorms (the consensus proxy), live/active in-degree, per-schedule
  contributor mass, norm-clip counts, attack-vector energy, exact per-codec
  wire bytes. Telemetry **off** is bit-identical HLO to the untelemetered
  step (anchored like delay-0); telemetry **on** adds zero collectives and
  zero retraces — metrics are outputs, never trace structure.
* :mod:`repro.telemetry.events` / :mod:`repro.telemetry.log` — the
  **host** side. One structured JSONL logger (:class:`TelemetryLogger`)
  both trainers thread through: round records, compile/retrace events via
  the one shared :class:`TraceCounter`, repair/quarantine/splice records,
  attack activations, per-phase wall-clock; and :func:`span`, the host
  spans both trainers mark each round with on the profiler's timeline.
* :mod:`repro.telemetry.report` — merge the per-bench
  ``experiments/bench/*.json`` records and run JSONL logs into one summary
  (wire bytes/round per codec, rounds/sec per cell, retrace counts,
  consensus trajectory) — the single CI artifact.
"""
from repro.telemetry.events import EVENT_KINDS, TraceCounter
from repro.telemetry.log import TelemetryLogger, read_jsonl, span
from repro.telemetry.metrics import (RoundMetrics, TelemetryConfig,
                                     summarize_metrics)
from repro.telemetry.report import build_summary

__all__ = [
    "EVENT_KINDS",
    "RoundMetrics",
    "TelemetryConfig",
    "TelemetryLogger",
    "TraceCounter",
    "build_summary",
    "read_jsonl",
    "span",
    "summarize_metrics",
]
