import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# (Tests may shrink the placeholder world via REPRO_DRYRUN_DEVICES.)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (no mismatched specs, no unsupported
    collectives) on the 16x16 single-pod AND 2x16x16 multi-pod meshes;
  * the per-device memory footprint (memory_analysis);
  * the roofline inputs (cost_analysis FLOPs/bytes + parsed collective bytes).

Results are cached as JSON per cell under --out (default
experiments/dryrun/), so re-runs after a perf change only recompile the
affected cells (--force to override).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import registry
from repro.configs.base import DFLConfig
from repro.launch import mesh as mesh_lib
from repro.launch import steps
from repro.models import params as params_lib
from repro.roofline import analysis, hw


def _mesh(kind: str):
    return mesh_lib.make_production_mesh(pod=kind)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             par=None, dfl=None, label: str = "") -> dict:
    """Lower+compile one cell; returns the record (raises on failure)."""
    cfg = registry.get(arch)
    shape = next(s for s in registry.shapes_for(arch) if s.name == shape_name)
    par = par or registry.parallel_for(arch)
    dfl = dfl or DFLConfig()
    mesh = _mesh(mesh_kind)
    world = int(len(jax.devices()))

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            setup = steps.build_train_step(cfg, shape, mesh, par, dfl)
            step_args = [
                params_lib.shape_structs(setup.param_struct),
                setup.input_specs["batch"], setup.input_specs["lr"],
                setup.input_specs["alive"], setup.input_specs["gates"]]
            # optional operands, in the step's fixed extra order
            for name in ("active", "attack", "attack_key", "inflight"):
                if name in setup.input_specs:
                    step_args.append(setup.input_specs[name])
            lowered = setup.step_fn.lower(*step_args)
            extra = {
                "n_clients": setup.n_clients,
                "overlay": setup.overlay.name if setup.overlay else None,
                "gossip_degree": (setup.gossip_spec.degree
                                  if setup.gossip_spec else 0),
                "gossip_lambda": (setup.gossip_spec.lam
                                  if setup.gossip_spec else None),
                "gossip_impl": par.gossip_impl,
                "gossip_delay": setup.gossip_delay,
                # the parsed engine cell (repro.core.engine) the step
                # actually lowered with — substrate x codec x timing
                "gossip_engine": (dataclasses.asdict(setup.engine_config)
                                  if setup.engine_config else None),
            }
            if setup.pack_spec is not None:
                # per-device gossip-buffer padding, measured per cell via
                # roofline/analysis.packing_report (and across every arch by
                # bench_comm.padding_by_arch: full-size trees pad <= 0.003%,
                # smoke 17-38% — a smoke-model artifact, not a wire cost)
                extra["packing"] = analysis.packing_report(setup.pack_spec)
        else:
            setup = steps.build_serve_step(cfg, shape, mesh)
            lowered = setup.step_fn.lower(
                params_lib.shape_structs(setup.param_struct),
                setup.input_specs)
            extra = {"gossip_impl": None}
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    roof = analysis.roofline(cost, hlo, world)

    n_active = cfg.active_param_count()
    if shape.kind == "train":
        # tokens processed per lowered round = K local steps x global batch x seq
        tokens = par.local_steps * shape.global_batch * shape.seq_len
        model_flops = analysis.model_flops_train(n_active, tokens)
    elif shape.kind == "prefill":
        model_flops = analysis.model_flops_prefill(
            n_active, shape.global_batch * shape.seq_len)
    else:
        model_flops = analysis.model_flops_decode(n_active, shape.global_batch)
    model_flops_per_chip = model_flops / world

    args_b = int(mem.argument_size_in_bytes)
    temp_b = int(mem.temp_size_in_bytes)
    out_b = int(mem.output_size_in_bytes)
    alias_b = int(mem.alias_size_in_bytes)
    peak = args_b + temp_b + out_b - alias_b

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind, "label": label,
        "world": world,
        "clients_per_pod": par.clients_per_pod,
        "grad_accum": par.grad_accum,
        "remat": par.remat,
        "seconds_lower": round(t_lower, 2),
        "seconds_compile": round(t_compile, 2),
        "memory": {
            "argument_bytes": args_b, "output_bytes": out_b,
            "temp_bytes": temp_b, "alias_bytes": alias_b,
            "peak_bytes": peak,
            "fits_16g": bool(peak <= hw.HBM_BYTES),
        },
        "roofline": roof.as_dict(),
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flop_ratio": (model_flops_per_chip / roof.flops
                              if roof.flops else None),
        **extra,
    }
    return record


def cell_path(out_dir: str, arch: str, shape: str, mesh_kind: str,
              label: str = "") -> str:
    suffix = f"_{label}" if label else ""
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--label", default="", help="config-variant tag (perf runs)")
    ap.add_argument("--gossip", default=None,
                    choices=["dense", "ppermute", "ppermute_quant",
                             "ppermute_packed", "ppermute_packed_quant",
                             "ppermute_packed_async"])
    ap.add_argument("--codec", default=None,
                    choices=["auto", "f32", "int8", "int8_block"],
                    help="wire-codec override (repro.core.engine); "
                         "--gossip ppermute_packed_async --codec int8_block "
                         "lowers the pipelined+quantized composition")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = registry.ARCH_IDS if (args.all or not args.arch) else (args.arch,)
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)

    failures = []
    for arch in archs:
        shapes = ([s for s in registry.shapes_for(arch) if s.name == args.shape]
                  if args.shape else registry.shapes_for(arch))
        for shape in shapes:
            for mk in meshes:
                path = cell_path(args.out, arch, shape.name, mk, args.label)
                if os.path.exists(path) and not args.force:
                    print(f"[skip cached] {arch} {shape.name} {mk}")
                    continue
                par = registry.parallel_for(arch)
                if args.gossip:
                    # the async impl is only interesting pipelined; delay=0
                    # would lower to HLO identical to ppermute_packed
                    delay = 1 if args.gossip == "ppermute_packed_async" else 0
                    par = dataclasses.replace(par, gossip_impl=args.gossip,
                                              gossip_delay=delay)
                if args.codec:
                    par = dataclasses.replace(par, gossip_codec=args.codec)
                try:
                    rec = run_cell(arch, shape.name, mk, par=par,
                                   label=args.label)
                except Exception as e:  # record failures; dry-run must be green
                    failures.append((arch, shape.name, mk, repr(e)))
                    print(f"[FAIL] {arch} {shape.name} {mk}: {e}")
                    traceback.print_exc()
                    continue
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"[ok] {arch:18s} {shape.name:12s} {mk:6s} "
                      f"compile={rec['seconds_compile']:6.1f}s "
                      f"peak={rec['memory']['peak_bytes']/2**30:7.2f}GiB "
                      f"comp={r['compute_s']*1e3:9.3f}ms "
                      f"mem={r['memory_s']*1e3:9.3f}ms "
                      f"coll={r['collective_s']*1e3:9.3f}ms "
                      f"dom={r['dominant']}", flush=True)

    # skipped long_500k rows (full-attention archs) recorded for the table
    for arch in archs:
        for sname in registry.skipped_shapes(arch):
            for mk in meshes:
                path = cell_path(args.out, arch, sname, mk, args.label)
                if not os.path.exists(path):
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": sname, "mesh": mk,
                                   "skipped": "full-attention arch: 500k decode "
                                              "needs sub-quadratic attention"},
                                  f, indent=1)

    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f4 in failures:
            print("  ", f4)
        raise SystemExit(1)
    print("\nDRY-RUN GREEN")


if __name__ == "__main__":
    main()
