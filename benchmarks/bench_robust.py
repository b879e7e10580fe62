"""Byzantine-robustness benchmark: screens vs scripted attackers.

Drives `ElasticTrainer` (the stacked engine round) on the shared quadratic
consensus task through a scripted `AttackPlan` and sweeps the grid

    attackers f x screen ("none" | "norm_clip" | "trimmed_mean")
               x topology (ring vs expander)

reporting, per cell:

  * a convergence proxy — final mean-square distance to the consensus
    target over the *honest measurable* clients (honest AND attacker
    in-multiplicity <= trim: a receiver fed the same attacker on two
    schedules needs trim >= 2 by the order-statistics contract, so those
    receivers are excluded from the fairness comparison, not hidden);
  * rounds/sec and the per-round overhead of each screen against the
    unscreened round on the same cell (median us/round);
  * the retrace guard: the attack vector is traced DATA, so a plan whose
    attacker set *changes mid-run* must keep ``n_traces == 1`` (hard
    assert, the CI bench-smoke gate).

Acceptance (hard-asserted): under f >= 1 sign-flip attackers the
trimmed-mean proxy stays within a small factor of the attack-free
baseline, while screen="none" degrades by orders of magnitude.

Output: the usual ``name,us_per_call,derived`` CSV rows plus one JSON
record written to ``experiments/bench/robust.json``::

    {"bench": "robust", "n_clients", "degree", "dim", "rounds",
     "grid": [{"topology", "screen", "f", "proxy", "rounds_per_sec",
               "n_traces", "n_measured"}, ...],
     "overhead_us": {screen: us_per_round, ...},
     "acceptance": {"proxy_clean", "proxy_none_f1", "proxy_trimmed_f1"}}
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import dfedavg, engine as engine_lib, failures, gossip
from repro.core.topology import expander_overlay, ring_overlay
from repro.launch.elastic import ElasticTrainer


def quad_loss(params, batch):
    return jnp.mean(jnp.square(params["w"] - batch["target"])), {}


def _batches(n, dim, k=2):
    t = jnp.zeros((n, dim), jnp.float32)  # consensus target: the origin
    return {"target": jnp.broadcast_to(t[:, None], (n, k, dim))}


def _attack_multiplicity(overlay, attackers) -> np.ndarray:
    """Per-receiver count of schedules that deliver some attacker."""
    spec = gossip.make_gossip_spec(overlay)
    mult = np.zeros(overlay.n, dtype=int)
    for rf, m in zip(spec.recv_from, spec.live_masks):
        rf, m = np.asarray(rf), np.asarray(m).astype(bool)
        mult += np.isin(rf, list(attackers)) & m
    return mult


def _run_cell(overlay_fn, screen, f, *, dim, rounds, trim, seed=0):
    overlay = overlay_fn()
    n = overlay.n
    plan = None
    attackers: tuple[int, ...] = ()
    if f > 0:
        # the attacker set CHANGES mid-run (new ids join) — the retrace
        # guard below proves attacker churn is data, not trace structure
        plan = failures.sample_attackers(n, f, mode="sign_flip",
                                         magnitude=5.0, seed=seed)
        extra = failures.sample_attackers(n, f, mode="sign_flip",
                                          magnitude=5.0, seed=seed + 1)
        plan = failures.AttackPlan(
            n, events=plan.events + tuple(
                (rounds // 2, e[1], e[2], e[3]) for e in extra.events))
        attackers = tuple(sorted({i for e in plan.events for i in e[1]}))
    trainer = ElasticTrainer(
        overlay=overlay, loss_fn=quad_loss,
        dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2, momentum=0.9),
        failure_rounds=10**9, attack_plan=plan,
        engine=engine_lib.GossipEngineConfig(
            substrate="stacked", screen=screen, clip_tau=3.0, trim_f=trim))
    r = np.random.default_rng(seed)
    params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
    batches = _batches(n, dim)
    t0 = time.perf_counter()
    for _ in range(rounds):
        params, _ = trainer.step(params, batches, 0.2)
    jax.block_until_ready(params)
    rps = rounds / (time.perf_counter() - t0)
    # proxy over honest receivers whose attacker in-multiplicity the trim
    # budget can actually cover (see module docstring)
    mult = _attack_multiplicity(overlay, attackers)
    measured = np.array([i for i in range(n)
                         if i not in attackers and mult[i] <= trim])
    proxy = float(jnp.mean(jnp.square(params["w"][measured])))
    assert trainer.n_traces == 1, (screen, f, trainer.n_traces)
    return {"proxy": proxy, "rounds_per_sec": round(rps, 2),
            "n_traces": trainer.n_traces, "n_measured": int(len(measured))}


def _screen_overhead(n, degree, dim, *, trim, seed=0):
    """Median us/round of each screened round vs the unscreened one.

    CPU caveat: these are XLA-CPU schedules (the trimmed cell's single
    fused reduction can even beat the unscreened gather+einsum mix here);
    the TPU relationship is the kernel-analytic one in bench_kernels."""
    out = {}
    for screen in ("none", "norm_clip", "trimmed_mean"):
        trainer = ElasticTrainer(
            overlay=expander_overlay(n, degree, seed=seed),
            loss_fn=quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2, momentum=0.9),
            failure_rounds=10**9,
            engine=engine_lib.GossipEngineConfig(
                substrate="stacked", screen=screen, trim_f=trim))
        r = np.random.default_rng(seed)
        params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
        alive = jnp.ones(n, jnp.float32)
        gates = trainer.gates_for_round(0)
        lr = jnp.asarray(0.2, jnp.float32)
        out[screen] = time_call(trainer._round_fn, params, _batches(n, dim),
                                lr, alive, gates, None, None, iters=10)
    return out


def run(n_clients: int = 16, degree: int = 4, dim: int = 512,
        rounds: int = 10, trim: int = 1, seed: int = 0) -> dict:
    topos = {
        "ring": lambda: ring_overlay(n_clients),
        f"expander-d{degree}": lambda: expander_overlay(n_clients, degree,
                                                        seed=seed),
    }
    grid = []
    for tname, ofn in topos.items():
        for f in (0, 1, 2):
            for screen in ("none", "norm_clip", "trimmed_mean"):
                if f == 0 and screen != "none":
                    continue  # attack-free screened cells covered by tests
                cell = _run_cell(ofn, screen, f, dim=dim, rounds=rounds,
                                 trim=trim, seed=seed)
                cell.update(topology=tname, screen=screen, f=f)
                grid.append(cell)
                emit(f"robust/{tname}/f{f}/{screen}", 0.0,
                     f"proxy={cell['proxy']:.6f};"
                     f"rps={cell['rounds_per_sec']};"
                     f"n_traces={cell['n_traces']}")

    overhead = _screen_overhead(n_clients, degree, dim, trim=trim, seed=seed)
    for screen, us in overhead.items():
        emit(f"robust/overhead/{screen}", us,
             f"delta_vs_none={us - overhead['none']:.1f}us")

    def cell(tname, f, screen):
        return next(c for c in grid if c["topology"] == tname
                    and c["f"] == f and c["screen"] == screen)

    # acceptance: screens neutralize what the plain mean cannot. Proxies
    # are mean-square distances to the consensus target, so "neighborhood"
    # = a small constant factor of the attack-free run; "degrades" = an
    # order of magnitude or more. Asserted on the ring, where every edge
    # delivers once (in-multiplicity 1 == trim) and a single sign-flipper
    # visibly poisons the unscreened mean; the expander *dilutes* one
    # attacker across d+1 in-weights (its f=1 gap is real but smaller) —
    # that contrast is the paper's degree/robustness trade-off and is
    # recorded in the grid rather than asserted
    clean = cell("ring", 0, "none")["proxy"]
    none_f1 = cell("ring", 1, "none")["proxy"]
    trim_f1 = cell("ring", 1, "trimmed_mean")["proxy"]
    assert none_f1 > 10 * clean, (none_f1, clean)
    assert trim_f1 < 10 * clean + 1e-6, (trim_f1, clean)
    assert trim_f1 < none_f1 / 10, (trim_f1, none_f1)

    return {"bench": "robust", "n_clients": n_clients, "degree": degree,
            "dim": dim, "rounds": rounds, "trim": trim, "grid": grid,
            "overhead_us": {k: round(v, 1) for k, v in overhead.items()},
            "acceptance": {"proxy_clean": clean, "proxy_none_f1": none_f1,
                           "proxy_trimmed_f1": trim_f1}}


def main(rounds: int = 10, out_dir: str | None = "experiments/bench") -> None:
    rec = run(rounds=rounds)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "robust.json"), "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()
    main(rounds=args.rounds, out_dir=args.out)
