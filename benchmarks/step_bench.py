"""End-to-end train-step benchmark across the six gradient-sync modes.

Times one full optimizer step (fwd + bwd + sync + update) of reduced
ResNet-50 on an 8-virtual-device host mesh for:

  gspmd                 jit + NamedShardings, XLA-placed collectives
  shardmap_perleaf      explicit DP, one bf16 psum per gradient leaf
  shardmap_bucketed     explicit DP, one psum per fixed-size bucket (§6)
  shardmap_overlap      bucketed + backward-overlapped launch (§8)
  shardmap_zero         bucketed + ZeRO reduce-scatter / sharded
                        update / param all-gather (§9)
  shardmap_zero_overlap zero + backward-overlapped scatter launch

and writes a top-level ``BENCH_step.json`` so every PR leaves a
steps/sec trajectory point behind (CI uploads it as an artifact; its
schema is pinned by tests/test_bench_schema.py).

    PYTHONPATH=src python benchmarks/step_bench.py [--quick] \
        [--out BENCH_step.json]

Host-mesh caveat (same as comm_bench): the 8 "devices" share one memory
system, so wall-clock differences measure collective count / launch
structure and scheduling, not real interconnect time. The transferable
claims — collective counts, interleaving — are HLO-verified in the test
suite; these numbers bound the *overhead* of each mechanism.
"""
import argparse
import json
import os
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import telemetry  # noqa: E402
from repro.configs import (  # noqa: E402
    OptimizerConfig,
    get_config,
    reduced_config,
)
from repro.data.pipeline import DataPipeline  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import build_train_setup  # noqa: E402

MODES = {
    "gspmd": dict(dp_mode="gspmd", compression="bf16"),
    "shardmap_perleaf": dict(dp_mode="shardmap", compression="bf16"),
    "shardmap_bucketed": dict(dp_mode="shardmap",
                              compression="bf16+bucketed"),
    "shardmap_overlap": dict(dp_mode="shardmap",
                             compression="bf16+bucketed",
                             overlap_comm=True),
    "shardmap_zero": dict(dp_mode="shardmap",
                          compression="bf16+bucketed", zero_dp=True),
    "shardmap_zero_overlap": dict(dp_mode="shardmap",
                                  compression="bf16+bucketed",
                                  zero_dp=True, overlap_comm=True),
}


def bench_mode(name: str, kw: dict, *, arch: str, global_batch: int,
               bucket_bytes: int, iters: int, warmup: int,
               data_workers: int) -> dict:
    cfg = reduced_config(get_config(arch))
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    model, state, step, data, put, _ = build_train_setup(
        cfg, global_batch=global_batch, seq_len=16,
        opt_cfg=OptimizerConfig(), steps_per_epoch=10, mesh=mesh,
        seed=0, bucket_bytes=bucket_bytes, **kw)
    batch = put({k: jnp.asarray(v) for k, v in data.batch_at(0).items()})
    t0 = time.perf_counter()
    for _ in range(warmup):  # includes compile on the first call
        state, metrics = step(state, dict(batch))
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, dict(batch))
    jax.block_until_ready(metrics["loss"])
    dt = (time.perf_counter() - t0) / iters
    # ---- input-boundedness attribution (DESIGN.md §15): re-run with the
    # live multi-worker feed, splitting each step into time blocked on
    # the prefetch buffer (data-starved) vs everything else
    # (compute-bound). Per-step block_until_ready keeps the attribution
    # honest — async dispatch would hide compute under the next wait.
    with telemetry.recording() as rec:
        pipe = DataPipeline(data, start_step=0, depth=4,
                            num_workers=data_workers, put=put)
        try:
            t0 = time.perf_counter()
            for _ in range(iters):
                _, fed = next(pipe)
                state, metrics = step(state, fed)
                jax.block_until_ready(metrics["loss"])
            fed_dt = (time.perf_counter() - t0) / iters
        finally:
            pipe.close()
    wait_s = rec.total_s("feed.wait") / iters
    row = {"ms_per_step": round(dt * 1e3, 3),
           "steps_per_sec": round(1.0 / dt, 3),
           "warmup_s": round(compile_s, 2),
           "data_wait_ms": round(wait_s * 1e3, 3),
           "compute_ms": round((fed_dt - wait_s) * 1e3, 3),
           "data_starved_frac": round(wait_s / fed_dt, 4)}
    print(f"{name:<20} {row['ms_per_step']:>9.1f} ms/step "
          f"{row['steps_per_sec']:>8.2f} steps/s  "
          f"starved {row['data_starved_frac']:.1%}", flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--bucket-kib", type=int, default=16,
                    help="bucket size (KiB) — small so the reduced "
                         "gradient tree still spans several buckets")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--data-workers", type=int, default=2,
                    help="producer threads for the attribution pass")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke settings (fewer iterations)")
    ap.add_argument("--out", default="BENCH_step.json")
    args = ap.parse_args()
    if args.quick:
        args.iters = min(args.iters, 8)
        args.warmup = min(args.warmup, 2)

    print(f"devices={jax.device_count()} arch={args.arch}(reduced) "
          f"batch={args.global_batch} bucket={args.bucket_kib}KiB")
    modes = {}
    for name, kw in MODES.items():
        modes[name] = bench_mode(
            name, kw, arch=args.arch, global_batch=args.global_batch,
            bucket_bytes=args.bucket_kib * 1024, iters=args.iters,
            warmup=args.warmup, data_workers=args.data_workers)

    overlap_speedup = (modes["shardmap_bucketed"]["ms_per_step"]
                       / modes["shardmap_overlap"]["ms_per_step"])
    zero_speedup = (modes["shardmap_bucketed"]["ms_per_step"]
                    / modes["shardmap_zero"]["ms_per_step"])
    result = {
        "bench": "step_bench",
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
        "arch": f"{args.arch}-reduced",
        "global_batch": args.global_batch,
        "bucket_bytes": args.bucket_kib * 1024,
        "iters": args.iters,
        "data_workers": args.data_workers,
        "modes": modes,
        "overlap_vs_bucketed_speedup": round(overlap_speedup, 3),
        "zero_vs_bucketed_speedup": round(zero_speedup, 3),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"overlap vs bucketed: {overlap_speedup:.2f}x, "
          f"zero vs bucketed: {zero_speedup:.2f}x -> wrote {args.out}")


if __name__ == "__main__":
    main()
