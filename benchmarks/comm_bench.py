"""Gradient-sync microbenchmark + comm autotuner: per-leaf vs bucketed
compressed psum vs the ZeRO reduce-scatter + all-gather wire pattern vs
the hierarchical (intra-axis RS -> inter-axis AR -> intra-axis AG)
schedules, on an arbitrary 1- or 2-axis host-device mesh.

Measures the communication layer in isolation (DESIGN.md §6/§9/§14):
for each config's gradient pytree, time one explicit-DP sync step per
mode on the mesh and report the HLO-verified collective count, bytes
per collective, and wire dtype next to the wall-clock numbers.

    python benchmarks/comm_bench.py [--mesh 2x4] [--iters 20] \
        [--archs resnet50,llama3.2-1b] [--full] [--bucket-mib 64] \
        [--quick] [--out BENCH_comm.json]

``--sweep`` turns the benchmark into the comm autotuner: it sweeps
sync mode x wire dtype x bucket size (x hierarchy on a 2-axis mesh),
picks the fastest configuration, and persists it as a CommPlan
(``distributed/comm_plan.py``) that ``launch/train.py --comm-plan
auto`` picks up:

    python benchmarks/comm_bench.py --mesh 2x4 --sweep \
        [--plan-out results/comm_plan_resnet50_2x4.json]

``--quick`` is the CI smoke config (ResNet-50 only, few iterations,
small sweep grid) and ``--out`` writes the table as JSON so the run
leaves an artifact.

By default the LM configs are reduced (a 1.2B-param fp32 gradient tree
does not fit a CPU host); ResNet-50 runs at full size (25.5M params —
the paper's own workload). ``--full`` lifts the reduction everywhere;
``--reduced`` reduces every config (the round-trip tests use it).
"""
import argparse
import json
import math
import os
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config, reduced_config  # noqa: E402
from repro.core.compression import compressed_psum  # noqa: E402
from repro.distributed.bucketing import (  # noqa: E402
    bucketed_psum,
    make_hierarchy,
    plan_buckets,
)
from repro.distributed.comm_plan import (  # noqa: E402
    CommPlan,
    plan_path,
    save_plan,
)
from repro.launch.hlo_analysis import analyze_hlo, comm_report  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.training.specs import param_specs  # noqa: E402

#: sync modes the bench can time; hier* need a 2-axis mesh
ALL_MODES = ("per-leaf", "bucketed", "zero", "hier", "hier_zero")

#: bench mode -> the CommPlan sync_mode it corresponds to
PLAN_SYNC_MODE = {"bucketed": "bucketed", "zero": "zero",
                  "hier": "bucketed", "hier_zero": "zero"}


def parse_mesh(spec, n_dev):
    """``--mesh 2x4`` -> a named 2-axis mesh; default: all devices on
    one "data" axis (the old single-axis behavior)."""
    if not spec:
        return make_mesh((n_dev,), ("data",))
    dims = tuple(int(x) for x in spec.split("x"))
    if math.prod(dims) != n_dev:
        raise SystemExit(f"--mesh {spec}: product {math.prod(dims)} != "
                         f"device count {n_dev} (set XLA_FLAGS "
                         f"--xla_force_host_platform_device_count)")
    if len(dims) > 2:
        raise SystemExit(f"--mesh {spec}: at most 2 axes supported")
    axes = ("data",) if len(dims) == 1 else ("data", "model")
    return make_mesh(dims, axes)


def grad_tree(arch: str, full: bool, reduced: bool = False):
    cfg = get_config(arch)
    if reduced or (not full and cfg.family != "conv"):
        cfg = reduced_config(cfg)
    model = build_model(cfg, compute_dtype=jnp.float32)
    p_shapes, _ = param_specs(model, jnp.float32)
    key = iter(jax.random.split(jax.random.PRNGKey(0),
                                len(jax.tree.leaves(p_shapes))))
    return cfg, jax.tree.map(
        lambda s: jax.random.normal(next(key), s.shape, jnp.float32),
        p_shapes)


def build_sync(mode, mesh, grads, wire, bucket_bytes, hier_split=1):
    """jitted replicated-in/replicated-out sync step for one mode.

    DP spans every mesh axis (the paper's pure-DP ResNet regime), so a
    2-axis ``--mesh 2x4`` syncs over both axes — flat modes as one
    8-way group, hier modes as the two-stage schedule split at
    ``hier_split``."""
    dp_axes = tuple(mesh.axis_names)
    n_dev = 1
    for a in dp_axes:
        n_dev *= mesh.shape[a]
    hier = None
    if mode.startswith("hier"):
        hier = make_hierarchy(dp_axes, dict(mesh.shape), hier_split)

    def local(g):
        if mode in ("bucketed", "hier"):
            return bucketed_psum(g, dp_axes, wire=wire,
                                 bucket_bytes=bucket_bytes,
                                 use_kernel=False, hierarchy=hier)
        if mode in ("zero", "hier_zero"):
            # the ZeRO wire pattern in isolation (DESIGN.md §9/§14):
            # reduce-scatter each shard-aligned bucket, all-gather the
            # shards straight back (stand-in for the updated params),
            # unpack — numerically the same mean tree as bucketed
            from repro.distributed.bucketing import (
                hierarchical_all_gather,
                hierarchical_psum_scatter,
                pack,
                unpack,
            )
            plan = plan_buckets(g, bucket_bytes, wire, align=n_dev)
            bufs = pack(g, plan, use_kernel=False)
            if hier is not None:
                shards = [hierarchical_psum_scatter(b, hier)
                          for b in bufs]
                gathered = [hierarchical_all_gather(s, hier)
                            for s in shards]
            else:
                shards = [jax.lax.psum_scatter(b, dp_axes,
                                               scatter_dimension=0,
                                               tiled=True)
                          for b in bufs]
                gathered = [jax.lax.all_gather(s, dp_axes, tiled=True)
                            for s in shards]
            return unpack(gathered, plan, use_kernel=False,
                          denom=jax.lax.axis_size(dp_axes))
        return compressed_psum(g, dp_axes, wire, mean=True)

    specs = jax.tree.map(lambda _: P(), grads)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                       out_specs=specs, check_vma=False)
    return jax.jit(fn)


def bench(fn, grads, iters):
    out = fn(grads)  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(grads)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def time_cell(arch_name, mode, mesh, grads, wire, bucket_mib, iters,
              hier_split, n_dev):
    """Build + lower + time one (mode, wire, bucket) cell -> row dict."""
    bucket_bytes = bucket_mib * 1024 * 1024
    fn = build_sync(mode, mesh, grads, wire, bucket_bytes,
                    hier_split=hier_split)
    hlo = fn.lower(grads).compile().as_text()
    cr = comm_report(analyze_hlo(hlo, n_dev))
    ms = bench(fn, grads, iters)
    return {
        "arch": arch_name,
        "mode": mode,
        "wire": wire,
        "bucket_mib": bucket_mib,
        "hier_split": hier_split if mode.startswith("hier") else None,
        "leaves": len(jax.tree.leaves(grads)),
        "collectives_per_step": cr["total_executions_per_step"],
        "mib_per_collective": round(
            cr["mean_bytes_per_collective"] / 2 ** 20, 3),
        "wire_dtypes": sorted({d for op in cr["per_op"].values()
                               for d in op["dtype_bytes"]}),
        "ms_per_sync": round(ms, 3),
    }


def print_rows(rows):
    hdr = (f"{'arch':<16} {'mode':<10} {'wire':<5} {'MiB':>4} "
           f"{'hier':>4} {'leaves':>6} {'colls':>6} {'MiB/coll':>9} "
           f"{'wire dtypes':<16} {'ms/sync':>8}")
    print()
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        h = "-" if r["hier_split"] is None else str(r["hier_split"])
        print(f"{r['arch']:<16} {r['mode']:<10} {r['wire']:<5} "
              f"{r['bucket_mib']:>4} {h:>4} {r['leaves']:>6} "
              f"{r['collectives_per_step']:>6.0f} "
              f"{r['mib_per_collective']:>9.2f} "
              f"{','.join(r['wire_dtypes']):<16} "
              f"{r['ms_per_sync']:>8.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default="resnet50,llama3.2-1b")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--wire", default="bf16")
    ap.add_argument("--bucket-mib", type=int, default=64)
    ap.add_argument("--mesh", default=None,
                    help="AxB device mesh, e.g. 2x4 (hier modes need 2 "
                         "axes); default: all devices on one axis")
    ap.add_argument("--hier-split", type=int, default=1,
                    help="dp_axes split index for the hier modes "
                         "(DESIGN.md §14)")
    ap.add_argument("--modes", default=None,
                    help=f"comma list of {ALL_MODES} (default: all "
                         "that fit the mesh)")
    ap.add_argument("--full", action="store_true",
                    help="full-size LM configs (needs a lot of host RAM)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduce every config, conv included (fast "
                         "round-trip tests)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke config: ResNet-50 only, 5 iterations")
    ap.add_argument("--sweep", action="store_true",
                    help="autotune: sweep mode x wire x bucket size "
                         "(x hierarchy) and persist the winning "
                         "CommPlan (DESIGN.md §14)")
    ap.add_argument("--sweep-wires", default="bf16,f16")
    ap.add_argument("--sweep-bucket-mibs", default="4,16,64")
    ap.add_argument("--plan-out", default=None,
                    help="CommPlan path for --sweep (default: "
                         "results/comm_plan_{arch}_{AxB}.json)")
    ap.add_argument("--out", default=None,
                    help="also write the table as JSON (CI artifact)")
    args = ap.parse_args()
    if args.quick:
        args.archs = "resnet50"
        args.iters = min(args.iters, 5)
        args.sweep_bucket_mibs = "4,64"

    n_dev = jax.device_count()
    mesh = parse_mesh(args.mesh, n_dev)
    mesh_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    multi_axis = len(mesh_shape) > 1
    dp_axes = tuple(mesh.axis_names)

    if args.modes:
        modes = [m.strip() for m in args.modes.split(",") if m.strip()]
        for m in modes:
            if m not in ALL_MODES:
                ap.error(f"unknown mode {m!r}; pick from {ALL_MODES}")
    else:
        modes = [m for m in ALL_MODES
                 if multi_axis or not m.startswith("hier")]
    if not multi_axis and any(m.startswith("hier") for m in modes):
        ap.error("hier modes need a 2-axis mesh: pass --mesh AxB")

    rows = []
    plan = None
    plan_file = None
    for arch in args.archs.split(","):
        cfg, grads = grad_tree(arch, args.full, args.reduced)
        plan0 = plan_buckets(grads, args.bucket_mib * 1024 * 1024,
                             args.wire)
        print(f"[{cfg.name}] {plan0.describe()}")
        if args.sweep:
            # autotuner: the flat per-leaf baseline is timed once for
            # the table; the sweep grid covers the tunable schedules
            rows.append(time_cell(cfg.name, "per-leaf", mesh, grads,
                                  args.wire, args.bucket_mib,
                                  args.iters, args.hier_split, n_dev))
            grid = [m for m in modes if m != "per-leaf"]
            wires = [w.strip() for w in args.sweep_wires.split(",")]
            mibs = [int(x) for x in args.sweep_bucket_mibs.split(",")]
            best = None
            for mode in grid:
                for wire in wires:
                    for mib in mibs:
                        row = time_cell(cfg.name, mode, mesh, grads,
                                        wire, mib, args.iters,
                                        args.hier_split, n_dev)
                        rows.append(row)
                        if best is None or \
                                row["ms_per_sync"] < best["ms_per_sync"]:
                            best = row
            if best is not None and arch == args.archs.split(",")[0]:
                plan = CommPlan(
                    mesh_shape=mesh_shape, dp_axes=dp_axes,
                    sync_mode=PLAN_SYNC_MODE[best["mode"]],
                    wire=best["wire"],
                    bucket_bytes=best["bucket_mib"] * 1024 * 1024,
                    hier_split=best["hier_split"],
                    source="autotuner")
                plan_file = args.plan_out or plan_path(cfg.name,
                                                       mesh_shape)
                save_plan(plan, plan_file)
                print(f"[{cfg.name}] winner: {best['mode']} "
                      f"{best['wire']} {best['bucket_mib']}MiB "
                      f"({best['ms_per_sync']:.2f} ms) -> {plan_file}")
        else:
            for mode in modes:
                rows.append(time_cell(cfg.name, mode, mesh, grads,
                                      args.wire, args.bucket_mib,
                                      args.iters, args.hier_split,
                                      n_dev))

    print_rows(rows)
    by = {}
    for r in rows:
        by.setdefault(r["arch"], {})[r["mode"]] = r["ms_per_sync"]
    for name, d in by.items():
        if "per-leaf" in d and "bucketed" in d:
            print(f"{name}: bucketed is {d['per-leaf'] / d['bucketed']:.2f}x"
                  f" per-leaf wall-clock on {n_dev} host devices")
        if "bucketed" in d and "zero" in d:
            print(f"{name}: zero (scatter+gather) is "
                  f"{d['bucketed'] / d['zero']:.2f}x bucketed wall-clock")
        if "bucketed" in d and "hier" in d:
            print(f"{name}: hier (RS+AR+AG) is "
                  f"{d['bucketed'] / d['hier']:.2f}x bucketed wall-clock")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({
                "bench": "comm_bench",
                "devices": n_dev,
                "mesh": list(mesh_shape),
                "mesh_axes": list(dp_axes),
                "wire": args.wire,
                "bucket_bytes": args.bucket_mib * 1024 * 1024,
                "sweep": bool(args.sweep),
                "plan_path": plan_file,
                "plan": (None if plan is None
                         else json.loads(open(plan_file).read())),
                "rows": rows,
            }, f, indent=1)
        print(f"wrote {args.out}")
    print("\nNOTE: host-mesh 'devices' share one memory system, so this "
          "measures the collective-count/launch structure, not real "
          "interconnect time: the HLO columns (colls, MiB/coll, dtype) "
          "are the transferable result. On TPU, per-collective launch "
          "latency x leaf count is what bucketing removes (DESIGN.md "
          "§6), and the hierarchical schedules trade one big flat ring "
          "for two short intra/inter-axis stages (DESIGN.md §14).")


if __name__ == "__main__":
    main()
