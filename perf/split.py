#!/usr/bin/env python3
"""Splits one cell's traced window over the program's scopes and spans.

    python3 perf/split.py --workload <name> --seed <n> --seconds <s> [--save <path>]

Sets the cell up as ``perf/run.py`` does, runs ``--seconds`` of rounds
under the profiler, and prints one JSON line: the window's device time by
scope (``local``, ``gossip``, ``gossip.pack``, ``gossip.unpack``,
``unscoped``), its idle time by the program's host span, and per round
``local_ms``, ``gossip_ms``, ``host_ms`` and ``idle_ms`` (see
``perf.scopes.split``), and how many of the window's operations are not
instructions of the round's compiled text (those of other programs, such
as the loss mean). The scope paths come from the compiled text of the
cell's round, compiled after the window (the persistent cache gives back
the program the window ran). ``--save`` writes the flat trace of the
window's second round, as the tests read it. It runs no comparison with the
reference, and needs the TPU chips the cell asks for. It reads cells whose
rounds go through ``SimTrainer.run`` without delay, codec state or
Chebyshev coefficients.
"""
import argparse
import glob
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def round_text(trainer, params, batch, lr) -> str:
    """Compiled text of ``trainer``'s round, lowered from the operands
    ``SimTrainer.run`` passes it."""
    import jax
    import jax.numpy as jnp

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    args = (jax.tree.map(shape, params), batch, jnp.asarray(lr, jnp.float32),
            jnp.ones(trainer.overlay.n, jnp.float32), trainer._gates(0),
            None, None)
    return trainer.round_fn.lower(*args).compile().as_text()


def one_round(flat: dict, span: str) -> dict:
    """The flat trace cut to the window's second ``dfl.round``, whose span
    becomes the window ``span``."""
    from perf import scopes, trace

    lo, hi = trace.window(flat, span)
    rounds = [s for s in flat["program"]
              if s[0] == scopes.ROUND_SPAN and lo <= s[1] < hi]
    lo, hi = rounds[1][1], rounds[2][1]

    def keep(events):
        return [e for e in events if e[1] < hi and e[1] + e[2] > lo]

    out = {"device": {}, "async": {}, "scopes": {},
           "host": [[span, lo, hi - lo]] + keep(
               [h for h in flat["host"] if h[0] != span]),
           "program": keep(flat["program"])}
    for plane, ops in flat["device"].items():
        kept = [i for i, o in enumerate(ops) if o[1] < hi and o[1] + o[2] > lo]
        out["device"][plane] = [ops[i] for i in kept]
        out["scopes"][plane] = [flat["scopes"][plane][i] for i in kept]
        out["async"][plane] = keep(flat["async"].get(plane, []))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save")
    args = ap.parse_args()

    import jax
    from jax.profiler import ProfileData

    from perf import harness, scopes, trace

    p = harness.parts(args.workload)
    chips = p.workload["chips"]
    devs = harness.check_devices(chips)
    harness.enable_cache()
    run = harness.Run(chips, devs[0].device_kind)
    cell = p.cell_module.build(p.config, p.traffic, harness.seed32(args.seed),
                               run.spans)
    cell.setup()
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          cell.params)
    with tempfile.TemporaryDirectory(prefix="perf-split-") as tmp:
        harness.trace_window(cell, args.seconds, run, tmp)
        text = round_text(cell.trainer, params, cell.fed[0], p.traffic["lr"])
        path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        flat = scopes.flatten(ProfileData.from_file(path),
                              scopes.op_names(text))
    cell.release()
    out = scopes.split(flat, run.rounds, chips)
    lo, hi = trace.window(flat, trace.SPAN_PREFIX + "window")
    known = set(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = ", text, re.M))
    ops = [o for plane in flat["device"].values() for o in plane
           if o[1] < hi and o[1] + o[2] > lo]
    out.update(workload=args.workload, seed=args.seed, rounds=run.rounds,
               busy_s=run.trace["busy_s"], window_s=run.trace["window_s"],
               ops=len(ops),
               ops_not_in_round=sum(o[0] not in known for o in ops),
               device={"kind": devs[0].device_kind, "count": chips})
    if args.save:
        trace.save(one_round(flat, trace.SPAN_PREFIX + "window"), args.save)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
