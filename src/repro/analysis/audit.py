"""Audit driver: lower the real train step in every sync mode and gate
the per-(model, mode) contracts (DESIGN.md §12).

    PYTHONPATH=src python -m repro.analysis.audit \
        --model resnet50 --modes all            # reduced config, ~2 min

For each cell of {gspmd, perleaf, bucketed, overlap, zero,
zero_overlap, hier, hier_overlap, hier_zero, hier_zero_overlap} x
{sgd, lars} the driver AOT-lowers the real
``training/step.py`` train step on the local 8-virtual-device mesh
(flat cells on (8,1); hierarchical cells on the 2-axis DP mesh (2,4)
with hier_split=1, DESIGN.md §14)
(ShapeDtypeStructs only — nothing is allocated, no data pipeline),
runs every audit pass on the compiled HLO, and evaluates the mode's
contract (``analysis/contracts.py``). Facts the HLO cannot know —
how many state leaves are donated, how many buckets the plan cuts,
the wire itemsize — are computed here from the same planning code the
training step uses (``distributed/bucketing.py:stream_layout``) and
handed to the contracts as ``$``-expectations.

The result is ``AUDIT.json``: per-cell pass records + violations,
cross-cell relations (ZeRO must shrink resident optimizer state by
~(N-1)/N vs the replicated-stream cell), and a top-level ``ok`` that CI
gates on (exit code 1 on any violation).

Cells use f32 compute (the CPU backend's bf16->f32 promotions would
drown the precision lint in backend artifacts — see the gotcha in
launch/hlo_analysis.py) and an f16 wire (f16 collectives survive CPU
lowering at their true dtype). Bucket bytes default small enough that
the reduced config still cuts >= 2 buckets per step.
"""
import os

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import argparse
import json
import math
import sys
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.contracts import Contract, contract_for, evaluate, resolve
from repro.analysis.passes import AuditContext, run_pass
from repro.configs import (
    OptimizerConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
    get_config,
    reduced_config,
)
from repro.distributed.bucketing import stream_layout
from repro.distributed.sharding import make_rules, tree_shardings
from repro.models import build_model, init_model_state
from repro.optim import make_optimizer
from repro.training.specs import input_specs, param_specs

MODES: Dict[str, Dict[str, Any]] = {
    # wire: f16 survives CPU lowering at its true dtype (bf16 would be
    # promoted to f32 and confuse byte accounting)
    "gspmd": dict(dp_mode="gspmd", compression="f16",
                  overlap=False, zero=False),
    "perleaf": dict(dp_mode="shardmap", compression="f16",
                    overlap=False, zero=False),
    "bucketed": dict(dp_mode="shardmap", compression="f16+bucketed",
                     overlap=False, zero=False),
    "overlap": dict(dp_mode="shardmap", compression="f16+bucketed",
                    overlap=True, zero=False),
    "zero": dict(dp_mode="shardmap", compression="f16+bucketed",
                 overlap=False, zero=True),
    "zero_overlap": dict(dp_mode="shardmap", compression="f16+bucketed",
                         overlap=True, zero=True),
    # hierarchical schedules (DESIGN.md §14) lower on a 2-axis DP mesh
    # (2, 4) with hier_split=1: outer=("data",) size 2, inner=("model",)
    # size 4 — inner > outer so the shard-level inter-axis all-reduce is
    # strictly smaller than a flat full-bucket all-reduce would be,
    # which lets the byte ceilings prove the flat sync is gone
    "hier": dict(dp_mode="shardmap", compression="f16+bucketed",
                 overlap=False, zero=False, hier=1),
    "hier_overlap": dict(dp_mode="shardmap", compression="f16+bucketed",
                         overlap=True, zero=False, hier=1),
    "hier_zero": dict(dp_mode="shardmap", compression="f16+bucketed",
                      overlap=False, zero=True, hier=1),
    "hier_zero_overlap": dict(dp_mode="shardmap",
                              compression="f16+bucketed",
                              overlap=True, zero=True, hier=1),
}

#: mesh shape for the hierarchical cells; flat cells use (8, 1)
HIER_MESH_SHAPE = (2, 4)

OPTIMIZERS = {"sgd": "momentum_sgd", "lars": "lars"}

AUDIT_PASSES = ("comm", "interleave", "precision", "donation", "memory",
                "collectives", "determinism")


def _lower_cell(cfg, mode: str, opt_kind: str, mesh: Mesh, *,
                global_batch: int, bucket_bytes: int,
                steps_per_epoch: int = 40
                ) -> Tuple[str, Dict[str, Any]]:
    """AOT-lower one (mode, optimizer) train cell; returns
    ``(compiled_hlo_text, info)`` where ``info`` carries the
    spec-derived facts the contracts need. Mirrors
    launch/dryrun.py:lower_cell, minus the data pipeline and with f32
    compute."""
    spec = MODES[mode]
    hier = spec.get("hier")
    # hierarchical cells run pure DP over both mesh axes (the paper's
    # ResNet regime); flat cells keep the single "data" DP axis
    dp_axes = ("data", "model") if hier is not None else ("data",)
    shp = ShapeConfig("audit", cfg.image_size, global_batch, "train")
    parallel = ParallelConfig(
        dp_axes=dp_axes,
        tp_axis=None if hier is not None else "model", zero_1=False,
        compression=spec["compression"], bucket_bytes=bucket_bytes,
        overlap_comm=spec["overlap"], zero_dp=spec["zero"],
        hier_split=hier)
    opt_cfg = OptimizerConfig(kind=OPTIMIZERS[opt_kind])
    train_cfg = TrainConfig(optimizer=opt_cfg, parallel=parallel)
    compute_dtype = jnp.float32

    model = build_model(cfg, compute_dtype=compute_dtype)
    p_shapes, p_axes = param_specs(model, jnp.float32)
    leaves = jax.tree.leaves(p_shapes)
    total_elems = sum(math.prod(l.shape) for l in leaves)
    repl = NamedSharding(mesh, P())
    n_workers = 1
    for a in dp_axes:
        n_workers *= mesh.shape[a]
    batch = input_specs(cfg, shp, compute_dtype)

    info: Dict[str, Any] = {
        "total_param_elems": total_elems,
        "n_param_leaves": len(leaves),
        "n_workers": n_workers,
    }
    if hier is not None:
        from repro.distributed.bucketing import make_hierarchy
        h = make_hierarchy(dp_axes, mesh.shape, hier)
        info["hier_outer"] = h.outer_size
        info["hier_inner"] = h.inner_size

    if spec["dp_mode"] == "gspmd":
        from repro.training.step import make_train_step
        rules = make_rules(cfg, mesh, parallel)
        p_shard = tree_shardings(p_axes, mesh, rules)
        optimizer = make_optimizer(opt_cfg, steps_per_epoch=steps_per_epoch,
                                   global_batch=global_batch)
        opt_shapes = jax.eval_shape(optimizer.init, p_shapes)
        opt_shard = {"step": repl,
                     **{f: p_shard for f in optimizer.state_fields}}
        mstate_shapes = jax.eval_shape(lambda: init_model_state(model))
        state_shapes = {"params": p_shapes, "opt": opt_shapes,
                        "model_state": mstate_shapes}
        state_shard = {
            "params": p_shard, "opt": opt_shard,
            "model_state": jax.tree.map(lambda _: repl, mstate_shapes)}
        b_shard = jax.tree.map(
            lambda v: NamedSharding(mesh, P("data")) if v.ndim else repl,
            batch)
        step = make_train_step(model, optimizer, train_cfg, mesh, rules,
                               None, param_shardings=p_shard)
        opt_bytes_per_device = sum(
            l.size * l.dtype.itemsize
            for l in jax.tree.leaves(opt_shapes))
    else:
        from repro.training.step import (
            make_dp_overlap_train_step,
            make_dp_shardmap_train_step,
            replicate_model_state,
        )
        dp_shard = NamedSharding(mesh, P(dp_axes))
        # stream layout: always under zero; also LARS on the bucketed
        # explicit-DP paths (stream-LARS, DESIGN.md §11) — same rule as
        # launch/train.py:build_train_setup
        use_stream = spec["zero"] or (
            opt_cfg.kind == "lars" and
            "bucketed" in (spec["compression"] or ""))
        if use_stream:
            from repro.optim.stream import (
                make_stream_optimizer,
                zero_padded_total,
            )
            optimizer = make_stream_optimizer(
                opt_cfg, steps_per_epoch=steps_per_epoch,
                global_batch=global_batch)
            padded_total = zero_padded_total(
                p_shapes, parallel.compression, bucket_bytes, n_workers)
            opt_shapes = jax.eval_shape(
                lambda: optimizer.init(padded_total))
            field_shard = dp_shard if spec["zero"] else repl
            opt_shard = {"step": repl,
                         **{f: field_shard
                            for f in optimizer.state_fields}}
            shard_div = n_workers if spec["zero"] else 1
            opt_bytes_per_device = 4 + sum(
                padded_total * 4 // shard_div
                for _ in optimizer.state_fields)
            info["padded_total"] = padded_total
        else:
            optimizer = make_optimizer(
                opt_cfg, steps_per_epoch=steps_per_epoch,
                global_batch=global_batch)
            opt_shapes = jax.eval_shape(optimizer.init, p_shapes)
            opt_shard = jax.tree.map(lambda _: repl, opt_shapes)
            opt_bytes_per_device = sum(
                l.size * l.dtype.itemsize
                for l in jax.tree.leaves(opt_shapes))
        mstate_shapes = jax.eval_shape(
            lambda: replicate_model_state(init_model_state(model),
                                          n_workers))
        state_shapes = {"params": p_shapes, "opt": opt_shapes,
                        "model_state": mstate_shapes}
        state_shard = {
            "params": jax.tree.map(lambda _: repl, p_shapes),
            "opt": opt_shard,
            "model_state": jax.tree.map(lambda _: dp_shard,
                                        mstate_shapes)}
        b_shard = jax.tree.map(
            lambda v: dp_shard if v.ndim else repl, batch)
        step_builder = (make_dp_overlap_train_step if spec["overlap"]
                        else make_dp_shardmap_train_step)
        step = step_builder(model, optimizer, train_cfg, mesh, dp_axes)

    jitted = jax.jit(step, in_shardings=(state_shard, b_shard),
                     out_shardings=(state_shard, None),
                     donate_argnums=(0,))
    compiled = jitted.lower(state_shapes, batch).compile()
    info["n_state_leaves"] = len(jax.tree.leaves(state_shapes))
    info["n_batch_params"] = len(jax.tree.leaves(batch))
    info["opt_bytes_per_device"] = opt_bytes_per_device
    return compiled.as_text(), info


def _cell_expectations(info: Dict[str, Any], mode: str, opt_kind: str,
                       bucket_bytes: int) -> Dict[str, Any]:
    """The ``$``-facts the contracts resolve against, computed from the
    same bucket arithmetic the training step uses."""
    spec = MODES[mode]
    hier = spec.get("hier")
    wire_itemsize = 2  # f16 wire in every audit cell
    n = info["n_workers"]
    # align mirrors training/step.py: shard-aligned under zero; the
    # stream-LARS non-zero paths align too (identical layout to zero,
    # DESIGN.md §11); hierarchical schedules always align to the full
    # DP size (the double scatter needs n_workers-divisible buckets);
    # plain bucketed/overlap sgd uses the tree update with align=1
    if hier is not None or spec["zero"] or (
            opt_kind == "lars" and
            "bucketed" in (spec["compression"] or "")):
        align = n
    else:
        align = 1
    bucket_elems, n_buckets, pad = stream_layout(
        info["total_param_elems"], bucket_bytes, wire_itemsize, align)
    # the tail bucket can be tiny (the stream is cut at fixed offsets);
    # contracts count *qualifying* collectives, so drop it from the
    # expected count when it falls under the schedule byte floor
    tail_elems = (info["total_param_elems"] + pad -
                  (n_buckets - 1) * bucket_elems)
    schedule_min_bytes = 2048
    n_qualifying = (n_buckets - 1) + int(
        tail_elems * wire_itemsize >= schedule_min_bytes)
    exp: Dict[str, Any] = {
        "n_state_params": info["n_state_leaves"],
        "n_batch_params": info["n_batch_params"],
        "n_buckets_planned": n_buckets,
        "n_buckets": n_qualifying,
        # slack: the stacked-metrics pmean and (LARS) trust psum also
        # execute, but they sit under schedule_min_bytes; +2 headroom
        # for a backend-materialized -start/-done splitting artifact.
        # zero runs TWO collectives per bucket (reduce-scatter in,
        # all-gather out)
        "collective_budget":
            (2 * n_qualifying if spec["zero"] else n_qualifying) + 2,
        "metric_bytes_floor": 2048,
        "schedule_min_bytes": schedule_min_bytes,
        # per-leaf wire floor: every big leaf crosses the ring once
        # (2 * bytes * (n-1)/n per all-reduce, cost.py:_wire_bytes)
        "min_gradient_wire_bytes":
            2 * (info["total_param_elems"] * wire_itemsize) *
            (n - 1) / n * 0.9,
    }
    if hier is not None:
        # per-op qualifying counts + byte ceilings for the hierarchical
        # pipeline (DESIGN.md §14). Buckets travel as f32 between the
        # inner reduce-scatter and the final cast (round-once
        # semantics), so intermediates are 4 B/elem; only the non-zero
        # modes' final all-gather is wire-dtype (2 B/elem). Sized like
        # the collectives pass: max(input, output) bytes per execution.
        inner = info["hier_inner"]
        sizes = [bucket_elems] * (n_buckets - 1) + [tail_elems]
        fl = schedule_min_bytes
        if spec["zero"]:
            # inner RS (4E) + outer RS (4E/inner) in; outer AG
            # (4E/inner) + inner AG (4E, f32 param stream) out
            rs_b = [b for e in sizes for b in (4 * e, 4 * e // inner)]
            ag_b = [b for e in sizes for b in (4 * e // inner, 4 * e)]
            n_rs = sum(b >= fl for b in rs_b)
            n_ar = 0
            n_ag = sum(b >= fl for b in ag_b)
            rs_ceil, ag_ceil = max(rs_b), max(ag_b)
            ar_ceil = exp["metric_bytes_floor"]
        else:
            n_rs = sum(4 * e >= fl for e in sizes)
            n_ar = sum(4 * e // inner >= fl for e in sizes)
            n_ag = sum(2 * e >= fl for e in sizes)
            rs_ceil = 4 * max(sizes)
            ar_ceil = 4 * max(sizes) // inner
            ag_ceil = 2 * max(sizes)
        exp.update({
            "n_rs": n_rs, "n_ar": n_ar, "n_ag": n_ag,
            "rs_bytes_ceiling": rs_ceil,
            "ar_bytes_ceiling": ar_ceil,
            "ag_bytes_ceiling": ag_ceil,
            "collective_budget": n_rs + n_ar + n_ag + 2,
        })
    return exp


def audit_cell(cfg, model: str, mode: str, opt_kind: str, mesh: Mesh, *,
               global_batch: int, bucket_bytes: int) -> Dict[str, Any]:
    """Lower + analyze + contract-check one cell; returns its record."""
    hlo, info = _lower_cell(cfg, mode, opt_kind, mesh,
                            global_batch=global_batch,
                            bucket_bytes=bucket_bytes)
    expectations = _cell_expectations(info, mode, opt_kind, bucket_bytes)
    contract = contract_for(model, mode, opt_kind)
    gates = {k: resolve(v, expectations)
             for k, v in contract.expectations.items()}
    ctx = AuditContext(hlo_text=hlo,
                       total_devices=math.prod(mesh.devices.shape),
                       expectations={**expectations, **gates})
    record = {name: run_pass(name, ctx).as_dict()
              for name in contract.passes}
    violations = evaluate(contract, record, expectations)
    return {
        "mode": mode,
        "optimizer": opt_kind,
        "contract": contract.name,
        "ok": not violations,
        "violations": violations,
        "expectations": expectations,
        "info": info,
        "passes": record,
    }


def _zero_relations(cells: List[Dict[str, Any]],
                    n_workers: int) -> List[Dict[str, Any]]:
    """Cross-cell memory relation: for each optimizer with both a
    ``bucketed`` and a ``zero`` cell, the resident entry-parameter bytes
    must drop by ~the sharded slice of the optimizer state —
    ``opt_bytes(bucketed) - opt_bytes(zero)``, i.e. ~(N-1)/N of the
    stream state (DESIGN.md §9). Params/model-state/batch are identical
    between the cells, so the entry-param delta isolates optimizer
    residency."""
    by_key = {(c["mode"], c["optimizer"]): c for c in cells}
    relations = []
    for opt in sorted({c["optimizer"] for c in cells}):
        a = by_key.get(("bucketed", opt))
        b = by_key.get(("zero", opt))
        if a is None or b is None:
            continue
        try:
            mem_a = a["passes"]["memory"]["summary"]["entry_param_bytes"]
            mem_b = b["passes"]["memory"]["summary"]["entry_param_bytes"]
        except KeyError:
            continue
        expected = (a["info"]["opt_bytes_per_device"] -
                    b["info"]["opt_bytes_per_device"])
        actual = mem_a - mem_b
        ok = expected > 0 and 0.5 * expected <= actual <= 1.5 * expected
        relations.append({
            "relation": "zero_shrinks_optimizer_residency",
            "optimizer": opt,
            "n_workers": n_workers,
            "entry_param_bytes": {"bucketed": mem_a, "zero": mem_b},
            "actual_shrink_bytes": actual,
            "expected_shrink_bytes": expected,
            "ok": ok,
        })
    return relations


def run_audit(model: str = "resnet50", modes: Optional[List[str]] = None,
              optimizers: Optional[List[str]] = None, full: bool = False,
              global_batch: int = 16,
              bucket_bytes: Optional[int] = None,
              verbose: bool = True) -> Dict[str, Any]:
    modes = list(modes or MODES)
    optimizers = list(optimizers or OPTIMIZERS)
    cfg = get_config(model)
    if not full:
        cfg = reduced_config(cfg)
    if bucket_bytes is None:
        # small enough that even the reduced param stream cuts >1 bucket
        bucket_bytes = 4 * 2 ** 20 if full else 8 * 2 ** 10
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8, 1), ("data", "model"))
    # hierarchical cells need a genuinely 2-axis DP mesh (outer x inner)
    hier_mesh = make_mesh(HIER_MESH_SHAPE, ("data", "model"))

    cells = []
    for mode in modes:
        for opt in optimizers:
            cell_mesh = (hier_mesh if MODES[mode].get("hier") is not None
                         else mesh)
            if verbose:
                print(f"[audit] {model}/{mode}/{opt} ...",
                      flush=True)
            try:
                cell = audit_cell(cfg, model, mode, opt, cell_mesh,
                                  global_batch=global_batch,
                                  bucket_bytes=bucket_bytes)
            except Exception as e:  # lowering itself failed the cell
                cell = {"mode": mode, "optimizer": opt, "ok": False,
                        "violations": [{
                            "kind": "lowering_failed",
                            "message": f"{type(e).__name__}: {e}"}],
                        "passes": {}}
            if verbose:
                status = "ok" if cell["ok"] else "FAIL"
                print(f"[audit] {model}/{mode}/{opt}: {status}",
                      flush=True)
                for v in cell["violations"]:
                    print(f"  violation: {v}", flush=True)
            cells.append(cell)

    relations = _zero_relations(cells, mesh.shape["data"])
    report = {
        "model": model,
        "config": "full" if full else "reduced",
        "mesh": list(mesh.devices.shape),
        "hier_mesh": list(HIER_MESH_SHAPE),
        "global_batch": global_batch,
        "bucket_bytes": bucket_bytes,
        "modes": modes,
        "optimizers": optimizers,
        "cells": cells,
        "relations": relations,
        "ok": (all(c["ok"] for c in cells) and
               all(r["ok"] for r in relations)),
    }
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Static-analysis audit of the compiled train step "
                    "across sync modes (DESIGN.md §12)")
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--modes", default="all",
                    help=f"comma list of {sorted(MODES)} or 'all'")
    ap.add_argument("--optimizers", default="all",
                    help=f"comma list of {sorted(OPTIMIZERS)} or 'all'")
    ap.add_argument("--quick", action="store_true",
                    help="reduced config (the default; alias for CI)")
    ap.add_argument("--full", action="store_true",
                    help="full model config (slow: ~2 min compile/cell "
                         "on CPU)")
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--bucket-bytes", type=int, default=None)
    ap.add_argument("--out", default="AUDIT.json")
    args = ap.parse_args(argv)

    modes = list(MODES) if args.modes == "all" else [
        m.strip() for m in args.modes.split(",") if m.strip()]
    for m in modes:
        if m not in MODES:
            ap.error(f"unknown mode {m!r}; pick from {sorted(MODES)}")
    opts = list(OPTIMIZERS) if args.optimizers == "all" else [
        o.strip() for o in args.optimizers.split(",") if o.strip()]
    for o in opts:
        if o not in OPTIMIZERS:
            ap.error(f"unknown optimizer {o!r}; pick from "
                     f"{sorted(OPTIMIZERS)}")

    report = run_audit(args.model, modes, opts, full=args.full,
                       global_batch=args.global_batch,
                       bucket_bytes=args.bucket_bytes)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    n_bad = sum(not c["ok"] for c in report["cells"]) + \
        sum(not r["ok"] for r in report["relations"])
    print(f"[audit] wrote {args.out}: "
          f"{len(report['cells'])} cells, "
          f"{len(report['relations'])} relations, "
          f"{n_bad} failing")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
