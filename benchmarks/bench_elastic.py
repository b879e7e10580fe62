"""Elastic-runtime benchmark: churn throughput + recompile accounting.

Drives `ElasticTrainer` (the packed gossip path) through a scripted
`FailurePlan` — healthy rounds, rotating transient stragglers, a permanent
death with splice repair — and reports:

  * rounds/sec per phase (healthy vs straggler-churn vs post-repair);
  * the jit trace count (`n_traces`): straggler churn must add ZERO traces
    (the alive mask is a step argument); each membership change adds exactly
    one. The same guard runs for the **pipelined** trainer (engine delay=1):
    the in-flight snapshot is step state, never trace structure;
  * a delayed-vs-sync convergence proxy: final mean-square distance to the
    shared quadratic target after the same scripted churn, engine delay 0
    vs 1 — one-round staleness costs a bounded constant, not divergence.

Output: the usual ``name,us_per_call,derived`` CSV rows, plus one JSON
record written to ``<out>/elastic.json`` (default ``experiments/bench/``;
re-runs overwrite it, dryrun-cache style) with the bench JSON schema::

    {"bench": "elastic", "n_clients", "degree", "dim", "rounds",
     "phases": {name: {"rounds", "seconds", "rounds_per_sec"}},
     "n_traces", "expected_traces", "repairs": [{"dead", "n_after"}],
     "plan": [[round, [dead ids]], ...],
     "delayed": {"n_traces", "expected_traces", "rounds_per_sec",
                 "proxy_sync", "proxy_delayed"},
     "chebyshev": {"eps", "cells": {label: {"rounds_to_threshold",
                   "bytes_to_threshold", ...}}, "headline"}}

The ``chebyshev`` panel is the sub_rounds=k timing-axis study: rounds- and
bytes-to-consensus-threshold for ring/expander at k=1 vs k=2 (hard gate:
k=2 Chebyshev on the ring crosses before the plain ring engine); the
summary.json rounds_to_threshold table is fed from these rows.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, rounds_to_threshold
from repro.core import dfedavg, engine as engine_lib, failures
from repro.core.topology import expander_overlay
from repro.launch.elastic import ElasticTrainer


def quad_loss(params, batch):
    return jnp.mean(jnp.square(params["w"] - batch["target"])), {}


def _batches(targets, k):
    return {"target": jnp.broadcast_to(
        targets[:, None], (targets.shape[0], k, targets.shape[1]))}


def run(n_clients: int = 16, degree: int = 4, dim: int = 4096,
        rounds_per_phase: int = 8, seed: int = 0) -> dict:
    r = np.random.default_rng(seed)
    trainer = ElasticTrainer(
        overlay=expander_overlay(n_clients, degree, seed=seed),
        loss_fn=quad_loss,
        dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.1, momentum=0.9),
        straggler_rounds=1, failure_rounds=3)
    params = {"w": jnp.asarray(r.standard_normal((n_clients, dim)),
                               jnp.float32)}
    # the scripted plan: one client starts missing heartbeats at the start
    # of phase 3 and is declared dead after `failure_rounds` misses
    death_round = 2 * rounds_per_phase
    plan = failures.FailurePlan(n_clients=n_clients,
                                events=((death_round, (n_clients // 2,)),))
    orig2cur = np.arange(n_clients)  # original id -> current index (-1 dead)

    def heartbeats(rnd: int, straggler: int | None) -> np.ndarray:
        mask = np.ones(trainer.n_clients, dtype=np.float32)
        for orig in plan.dead_at(rnd):
            if orig2cur[orig] >= 0:
                mask[orig2cur[orig]] = 0.0
        if straggler is not None:
            mask[straggler % trainer.n_clients] = 0.0
        return mask

    phases = {}
    rnd = 0

    def phase(name: str, n_rounds: int, straggler_fn):
        nonlocal rnd, params, orig2cur
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            mask = heartbeats(rnd, straggler_fn(rnd))
            params, _, old2new = trainer.observe_heartbeats(mask, params)
            if old2new is not None:
                alive = orig2cur >= 0
                orig2cur[alive] = old2new[orig2cur[alive]]
            targets = jnp.zeros((trainer.n_clients, dim), jnp.float32)
            params, _ = trainer.step(params, _batches(targets, 2), 0.1)
            rnd += 1
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        phases[name] = {"rounds": n_rounds, "seconds": round(dt, 4),
                        "rounds_per_sec": round(n_rounds / dt, 2)}

    phase("healthy", rounds_per_phase, lambda r_: None)
    phase("straggler_churn", rounds_per_phase, lambda r_: r_)  # rotating
    phase("death_and_repair", rounds_per_phase, lambda r_: None)

    # one initial trace + exactly one per membership change (with very short
    # phases the scripted death may not complete — repairs is the truth)
    expected = 1 + len(trainer.repairs)
    rec = {
        "bench": "elastic", "n_clients": n_clients, "degree": degree,
        "dim": dim, "rounds": rnd, "phases": phases,
        "n_traces": trainer.n_traces, "expected_traces": expected,
        "repairs": trainer.repairs,
        "plan": [[int(e[0]), [int(i) for i in e[1]]] for e in plan.events],
    }
    assert trainer.n_traces == expected, (trainer.n_traces, expected)
    rec["delayed"] = run_delayed(n_clients=n_clients, degree=degree, dim=dim,
                                 rounds=2 * rounds_per_phase, seed=seed)
    return rec


def run_delayed(n_clients: int = 16, degree: int = 4, dim: int = 4096,
                rounds: int = 16, seed: int = 0) -> dict:
    """Pipelined (engine delay=1) vs synchronous trainer under identical
    straggler churn: retrace guard + convergence proxy + rounds/sec.

    The third line is the **pipelined + quantized** engine composition
    (codec="int8_block", delay=1): same churn, int8 wire snapshot —
    its retrace count must also stay 1 and its convergence proxy must land
    in the same neighborhood as the f32 pipeline (the int8 error is bounded
    by the per-tile scales, not compounding)."""
    r = np.random.default_rng(seed)
    targets = jnp.zeros((n_clients, dim), jnp.float32)  # consensus: origin
    proxies = {}
    timing = {}
    traces = {}
    for name, delay, codec in (("sync", 0, "f32"), ("delayed", 1, "f32"),
                               ("delayed_quant", 1, "int8_block")):
        trainer = ElasticTrainer(
            overlay=expander_overlay(n_clients, degree, seed=seed),
            loss_fn=quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2, momentum=0.9),
            straggler_rounds=1, failure_rounds=10**9,
            engine=engine_lib.GossipEngineConfig(
                substrate="stacked", codec=codec, delay=delay))
        params = {"w": jnp.asarray(r.standard_normal((n_clients, dim)),
                                   jnp.float32)}
        rng = np.random.default_rng(seed + 1)
        t0 = time.perf_counter()
        for rnd in range(rounds):
            mask = (rng.random(n_clients) > 0.25).astype(np.float32)
            if mask.sum() < 2:
                mask[:] = 1.0
            params, _, _ = trainer.observe_heartbeats(mask, params)
            params, _ = trainer.step(params, _batches(targets, 2), 0.2)
        jax.block_until_ready(params)
        timing[name] = rounds / (time.perf_counter() - t0)
        proxies[name] = float(jnp.mean(jnp.square(params["w"])))
        traces[name] = trainer.n_traces
        # the pipelined retrace guard: churn is data in EVERY mode,
        # including the quantized pipeline (the CI bench-smoke gate)
        assert trainer.n_traces == 1, (name, trainer.n_traces)
    # the quantized pipeline must not diverge from the f32 pipeline
    assert proxies["delayed_quant"] <= 4 * proxies["delayed"] + 1e-4, proxies
    emit(f"elastic/delayed_vs_sync/n{n_clients}-d{degree}", 0.0,
         f"proxy_sync={proxies['sync']:.6f};"
         f"proxy_delayed={proxies['delayed']:.6f};"
         f"proxy_delayed_quant={proxies['delayed_quant']:.6f};"
         f"rps_sync={timing['sync']:.2f};"
         f"rps_delayed={timing['delayed']:.2f};"
         f"rps_delayed_quant={timing['delayed_quant']:.2f};"
         f"n_traces={traces['delayed']}")
    return {"n_traces": traces["delayed"], "expected_traces": 1,
            "n_traces_quant": traces["delayed_quant"],
            "rounds": rounds,
            "rounds_per_sec": round(timing["delayed"], 2),
            "rounds_per_sec_sync": round(timing["sync"], 2),
            "rounds_per_sec_quant": round(timing["delayed_quant"], 2),
            "proxy_sync": proxies["sync"],
            "proxy_delayed": proxies["delayed"],
            "proxy_delayed_quant": proxies["delayed_quant"]}


def run_chebyshev(n_clients: int = 16, dim: int = 256, rounds: int = 80,
                  eps: float = 1e-2, seed: int = 0) -> dict:
    """Chebyshev timing-axis panel: rounds- and bytes-to-consensus-threshold
    per (overlay family x sub_rounds) cell, pure gossip (no local SGD, so
    the crossing measures the mixing operator alone).

    The headline trade under test: does sub_rounds=2 Chebyshev on the CHEAP
    ring (2 wires/client/sub-round) beat the plain engine on the ring in
    rounds-to-threshold — the hard gate below — and how does it stand next
    to the costlier d=4 expander at k=1 in BYTES-to-threshold (recorded,
    per cell, in the JSON; the summary's rounds_to_threshold table picks
    these rows up)."""
    from repro.core import gossip, packing, spectral
    from repro.overlay import registry

    r = np.random.default_rng(seed)
    init = {"w": jnp.asarray(r.standard_normal((n_clients, dim)),
                             jnp.float32)}
    pack = packing.make_stacked_pack_spec(
        {"w": jax.ShapeDtypeStruct((dim,), jnp.float32)})

    def resid(t):
        w = t["w"]
        return float(jnp.sum(jnp.square(w - w.mean(axis=0, keepdims=True))))

    record = {"eps": eps, "n_clients": n_clients, "dim": dim,
              "max_rounds": rounds, "cells": {}}
    for family, k in (("ring", 1), ("ring", 2),
                      ("expander", 1), ("expander", 2)):
        overlay, meta = registry.build(family, n_clients, degree=4,
                                       seed=seed)
        spec = gossip.make_gossip_spec(overlay)
        ex = engine_lib.build_gossip_executor(
            engine_lib.GossipEngineConfig(substrate="stacked",
                                          sub_rounds=k), spec)
        # exact wire accounting from the shard_map twin's wire structs
        # (already k-fold for the sub-round loop)
        wire_pr = engine_lib.build_gossip_executor(
            engine_lib.GossipEngineConfig(substrate="shard_map",
                                          sub_rounds=k),
            spec, axis_names="client",
            pack_spec=pack).wire_bytes_per_round()
        if k > 1:
            cheby = jnp.asarray(ex.cheby_coeffs())
            step = jax.jit(lambda t, c, ex=ex: ex(t, cheby=c))
        else:
            step = jax.jit(lambda t, ex=ex: ex(t))
        x = init
        resids = [resid(x)]
        for _ in range(rounds):
            x = step(x, cheby) if k > 1 else step(x)
            resids.append(resid(x))
            if resids[-1] <= eps * resids[0]:
                break
        rt = rounds_to_threshold(resids, eps)
        label = f"{family}_k{k}"
        record["cells"][label] = {
            "label": label, "family": overlay.name, "sub_rounds": k,
            "lam": round(meta["lam"], 6),
            "cheby_lambda": round(spectral.chebyshev_lambda(meta["lam"], k),
                                  6),
            "rounds_to_threshold": rt,
            "wire_bytes_per_round": wire_pr,
            "bytes_to_threshold": rt * wire_pr if rt is not None else None,
            "resid_first": round(resids[0], 4),
            "resid_last": round(resids[-1], 6),
        }
        emit(f"elastic/chebyshev/{label}/n{n_clients}", 0.0,
             f"rounds_to_threshold={rt};"
             f"bytes_to_threshold={rt * wire_pr if rt is not None else None};"
             f"lam={meta['lam']:.4f};"
             f"wire_bytes_per_round={wire_pr}")
    cells = record["cells"]
    rk1 = cells["ring_k1"]["rounds_to_threshold"]
    rk2 = cells["ring_k2"]["rounds_to_threshold"]
    # the acceptance gate: k=2 Chebyshev on the ring crosses strictly
    # earlier than the plain ring engine
    assert rk2 is not None and (rk1 is None or rk2 < rk1), (rk1, rk2)
    ek1 = cells["expander_k1"]
    record["headline"] = {
        "ring_k2_beats_ring_k1_rounds": True,
        "ring_rounds_k1_vs_k2": [rk1, rk2],
        "ring_k2_vs_expander_k1_rounds":
            [rk2, ek1["rounds_to_threshold"]],
        "ring_k2_vs_expander_k1_bytes":
            [cells["ring_k2"]["bytes_to_threshold"],
             ek1["bytes_to_threshold"]],
    }
    return record


def main(rounds: int = 8, out_dir: str | None = "experiments/bench") -> None:
    rec = run(rounds_per_phase=rounds)
    rec["chebyshev"] = run_chebyshev()
    for name, ph in rec["phases"].items():
        emit(f"elastic/{name}/n{rec['n_clients']}-d{rec['degree']}",
             ph["seconds"] * 1e6 / ph["rounds"],
             f"rounds_per_sec={ph['rounds_per_sec']};"
             f"n_traces={rec['n_traces']}")
    emit(f"elastic/traces/n{rec['n_clients']}-d{rec['degree']}", 0.0,
         f"n_traces={rec['n_traces']};expected={rec['expected_traces']};"
         f"repairs={len(rec['repairs'])}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "elastic.json"), "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()
    main(rounds=args.rounds, out_dir=args.out)
