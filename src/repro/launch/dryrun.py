import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes, prove memory fit, and extract roofline inputs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch qwen3-1.7b] [--shape train_4k] [--multi-pod] [--both] \
        [--no-costing] [--out results/dryrun]

Two compiles per cell:
1. PRODUCTION compile — scan-over-layers, exactly what would ship; gives
   memory_analysis (fits-HBM proof) and the collective schedule.
2. COSTING compiles — XLA's HloCostAnalysis counts while-loop bodies ONCE,
   so scanned programs under-report FLOPs/bytes by the trip count. We
   compile fully-unrolled 1-layer and 2-layer variants (layers identical
   => exact linear extrapolation): corrected = c1*(2-L) + c2*(L-1).
   DIEN extrapolates the GRU trip count. Recorded FLOPs/bytes/collective-bytes are corrected;
   memory numbers always come from the production compile.

This module MUST be the process entry point — the XLA_FLAGS line above
runs before jax initializes."""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import get_config, list_archs
from repro.distributed.meshrules import AxisRules, use_rules
from repro.launch import roofline as rl
from repro.launch.mesh import HBM_BYTES, make_production_mesh
from repro.launch.specs import all_cells, build_cell


def _compile(arch_id, shape_name, mesh, rules, model_override=None):
    with mesh:
        with use_rules(rules):
            cell = build_cell(arch_id, shape_name, rules=rules,
                              abstract=True, model_override=model_override)
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             donate_argnums=cell.donate_argnums)
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    mem = compiled.memory_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "fused_bytes": float(rl.fused_bytes(hlo)),
        "coll": coll,
        "mem_per_dev": int(mem.output_size_in_bytes + mem.temp_size_in_bytes
                           + mem.argument_size_in_bytes
                           - mem.alias_size_in_bytes),
    }


def costing_plan(arch, shape_name) -> list[tuple[object, float]] | None:
    """[(model_cfg, coefficient)] with corrected = sum coef_i * cost_i."""
    m = arch.model
    r = dataclasses.replace
    if arch.family == "lm":
        L = m.n_layers
        # long-seq cells: coarsen flash chunks for the unrolled costing
        # variants (pair count ~ (S/cq)*(S/ck)/2 would explode compile
        # time at 32k); the diagonal-tile overcount this introduces is
        # ~cq/S ~ 6-12% on the attention term (documented in EXPERIMENTS)
        shape = arch.shape(shape_name)
        big = shape.dims.get("seq_len", 0) >= 16384 and \
            shape.kind in ("train", "prefill")
        extra = (dict(q_chunk=2048, kv_chunk=4096) if big else {})
        mk = lambda n: r(m, n_layers=n, scan_layers=False,
                         unroll_pairs=True, **extra)
        return [(mk(1), 2.0 - L), (mk(2), L - 1.0)]
    if arch.family == "encoder":
        L = m.n_layers
        mk = lambda n: r(m, n_layers=n, scan_layers=False)
        return [(mk(1), 2.0 - L), (mk(2), L - 1.0)]
    if arch.family == "gnn":
        L = m.n_layers
        mk = lambda n: r(m, n_layers=n, scan_layers=False)
        return [(mk(1), 2.0 - L), (mk(2), L - 1.0)]
    if arch.family == "recsys" and m.kind == "dien":
        T = m.seq_len
        mk = lambda t: r(m, seq_len=t, unroll_gru=True)
        return [(mk(1), 2.0 - T), (mk(2), T - 1.0)]
    return None                                # exact as compiled


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             costing: bool = True, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = AxisRules(mesh)
    chips = int(len(mesh.devices.ravel()))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    arch = get_config(arch_id)
    t0 = time.time()
    prod = _compile(arch_id, shape_name, mesh, rules)
    t_prod = time.time() - t0

    flops, hbytes, coll = prod["flops"], prod["bytes"], dict(prod["coll"])
    fbytes = prod["fused_bytes"]
    corrected = False
    plan = costing_plan(arch, shape_name) if costing else None
    if plan is not None:
        flops = hbytes = fbytes = 0.0
        coll = {k: 0.0 for k in prod["coll"]}
        for model_cfg, coef in plan:
            c = _compile(arch_id, shape_name, mesh, rules,
                         model_override=model_cfg)
            flops += coef * c["flops"]
            hbytes += coef * c["bytes"]
            fbytes += coef * c["fused_bytes"]
            for k in coll:
                coll[k] += coef * c["coll"].get(k, 0)
        coll = {k: max(v, 0.0) for k, v in coll.items()}
        flops, hbytes = max(flops, 0.0), max(hbytes, 0.0)
        fbytes = max(fbytes, 0.0)
        corrected = True

    rec = rl.Roofline(
        arch=arch_id, shape=shape_name, mesh=mesh_name, chips=chips,
        flops=flops, hbm_bytes=hbytes, coll_bytes=coll,
        per_device_mem=prod["mem_per_dev"],
        model_flops=rl.model_flops_for(arch_id, shape_name),
        hbm_bytes_fused=fbytes,
    ).to_dict()
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["prod_compile_s"] = round(t_prod, 1)
    rec["fits_hbm"] = prod["mem_per_dev"] <= HBM_BYTES
    rec["mem_gb"] = round(prod["mem_per_dev"] / 2 ** 30, 2)
    rec["scan_corrected"] = corrected
    if verbose:
        print(f"[dryrun] {arch_id}/{shape_name} mesh={mesh_name} "
              f"mem/dev={rec['mem_gb']}GB fits={rec['fits_hbm']} "
              f"GFLOPs/dev={rec['flops']/1e9:.1f} "
              f"bottleneck={rec['bottleneck']} "
              f"frac={rec['roofline_fraction']*100:.1f}% "
              f"({rec['compile_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--no-costing", action="store_true",
                    help="skip the unrolled costing compiles")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = [False, True] if args.both else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi_pod in meshes:
        # roofline costing is single-pod only (the table's scope);
        # the multi-pod pass proves the pod axis shards
        costing = (not args.no_costing) and not multi_pod
        for arch_id, shape_name in cells:
            tag = f"{arch_id}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[dryrun] skip cached {tag}", flush=True)
                continue
            try:
                rec = run_cell(arch_id, shape_name, multi_pod,
                               costing=costing)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception as e:  # noqa: BLE001
                failures.append((tag, repr(e)))
                print(f"[dryrun] FAIL {tag}: {e}", flush=True)
                traceback.print_exc()
    print(f"\n[dryrun] done; {len(failures)} failures", flush=True)
    for t, e in failures:
        print("  FAIL", t, e[:200])
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
