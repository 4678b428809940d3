import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

# Hillclimb driver: lower one cell (with optimization variants), print the
# three roofline terms + the top memory/collective ops, and append the
# record to results/hillclimb/. Used for the hypothesis->change->measure
# loop in EXPERIMENTS.md §Perf.
#
#   PYTHONPATH=src python -m benchmarks.hillclimb --arch mixtral-8x7b \
#       --shape train_4k --variant baseline --top 12

import argparse
import json
import re

import jax


def diagnose(arch, shape, variant="baseline", top=14, out_dir="results/hillclimb",
             attention_impl=None, save=True, sp=False, moe_group=None):
    import dataclasses

    from repro.configs import get_config, shapes_for
    from repro.launch import hlo_analysis as H
    from repro.launch.dryrun import lower_cell
    from repro.launch.mesh import (cell_parallel, make_mesh,
                                   make_production_mesh)

    mesh = make_production_mesh()
    kwargs = {}
    if os.environ.get("HILLCLIMB_MESH"):
        d, m = (int(x) for x in os.environ["HILLCLIMB_MESH"].split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    if attention_impl:
        kwargs["attention_impl"] = attention_impl
    if moe_group:
        kwargs["moe_group"] = moe_group
    if sp:
        cfg = get_config(arch)
        shp = {s.name: s for s in shapes_for(cfg)}[shape]
        par = dataclasses.replace(cell_parallel(cfg, shp),
                                  sequence_sharding=True)
        kwargs["parallel"] = par
    rec, compiled = lower_cell(arch, shape, mesh, **kwargs)
    assert rec.get("status") == "ok", rec
    a = H.analyze_hlo(compiled.as_text(), total_devices=mesh.size)
    mem_rows = a.top_memory_ops
    coll_rows = a.top_collective_ops

    rl = rec["roofline"]
    print(f"=== {arch} {shape} [{variant}] ===")
    print(f"compute {rl['compute_s']:.4f}s  memory {rl['memory_s']:.4f}s  "
          f"collective {rl['collective_s']:.4f}s  dom={rl['dominant']}  "
          f"useful={rl['useful_fraction']}  "
          f"roofl={100*rl['achievable_mfu']:.2f}%")
    print("--- top memory ops (GB, accounted) ---")
    for r in mem_rows[:top]:
        print(f"  {r[0]/1e9:9.1f}  {r[1]:<22s} x{r[2]:<7g} {r[3]:<30s} "
              f"{r[4]} {r[5]}")
    print("--- top collectives (GB wire, accounted) ---")
    for r in coll_rows[:top]:
        print(f"  {r[0]/1e9:9.2f}  {r[1]:<18s} k={r[2]:<4d} x{r[3]:<7g} "
              f"{r[4]} {r[5]}")
    if save:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape}__{variant}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--attention-impl", default=None)
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--moe-group", type=int, default=None)
    args = ap.parse_args()
    diagnose(args.arch, args.shape, args.variant, args.top,
             attention_impl=args.attention_impl, sp=args.sp,
             moe_group=args.moe_group)
