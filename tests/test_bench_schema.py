"""Schema guard for the committed benchmark trajectory file.

``BENCH_step.json`` is the per-PR steps/sec trajectory point
(benchmarks/step_bench.py, uploaded by CI). Refactors that touch the
bench emitter must not silently drop a sync-mode column or rename a
field — downstream trajectory tooling keys on this exact schema, so the
shape is pinned here, including the ``zero`` modes (DESIGN.md §9).
"""
import json
import os

REPO = os.path.join(os.path.dirname(__file__), "..")

EXPECTED_MODES = (
    "gspmd",
    "shardmap_perleaf",
    "shardmap_bucketed",
    "shardmap_overlap",
    "shardmap_zero",
    "shardmap_zero_overlap",
)

MODE_FIELDS = ("ms_per_step", "steps_per_sec", "warmup_s", "compute_ms")

# input-boundedness attribution (DESIGN.md §15): legitimately 0.0 when
# the feed never starves the step, so guarded as >= 0 rather than > 0
MODE_WAIT_FIELDS = ("data_wait_ms", "data_starved_frac")

TOP_FIELDS = ("bench", "devices", "backend", "arch", "global_batch",
              "bucket_bytes", "iters", "data_workers", "modes",
              "overlap_vs_bucketed_speedup", "zero_vs_bucketed_speedup")


def _load():
    with open(os.path.join(REPO, "BENCH_step.json")) as f:
        return json.load(f)


def test_bench_step_json_has_all_sync_mode_columns():
    data = _load()
    assert data["bench"] == "step_bench"
    missing = [m for m in EXPECTED_MODES if m not in data["modes"]]
    assert not missing, f"BENCH_step.json lost sync-mode columns: {missing}"


def test_bench_step_json_mode_fields_and_types():
    data = _load()
    for top in TOP_FIELDS:
        assert top in data, f"BENCH_step.json lost top-level field {top!r}"
    for mode, row in data["modes"].items():
        for field in MODE_FIELDS:
            assert field in row, (mode, field)
            assert isinstance(row[field], (int, float)), (mode, field)
            assert row[field] > 0, (mode, field, row[field])
        for field in MODE_WAIT_FIELDS:
            assert field in row, (mode, field)
            assert isinstance(row[field], (int, float)), (mode, field)
            assert row[field] >= 0, (mode, field, row[field])
        assert row["data_starved_frac"] <= 1.0, mode
    assert isinstance(data["devices"], int) and data["devices"] >= 1
    assert isinstance(data["data_workers"], int) and data["data_workers"] >= 1


def test_bench_step_json_speedups_consistent_with_modes():
    data = _load()
    modes = data["modes"]
    want = round(modes["shardmap_bucketed"]["ms_per_step"]
                 / modes["shardmap_zero"]["ms_per_step"], 3)
    assert abs(data["zero_vs_bucketed_speedup"] - want) < 1e-6
    want = round(modes["shardmap_bucketed"]["ms_per_step"]
                 / modes["shardmap_overlap"]["ms_per_step"], 3)
    assert abs(data["overlap_vs_bucketed_speedup"] - want) < 1e-6


# ---------------------------------------------------------------------------
# BENCH_input.json (benchmarks/input_bench.py, DESIGN.md §15)
# ---------------------------------------------------------------------------

INPUT_TOP_FIELDS = ("bench", "backend", "devices", "batch", "image_size",
                    "iters", "workers", "multi_worker_speedup",
                    "host_shard", "transform")

INPUT_WORKER_FIELDS = ("ms_per_batch", "batches_per_s")

INPUT_SHARD_FIELDS = ("num_hosts", "global_ms_per_batch",
                      "shard_ms_per_batch", "shard_speedup")


def _load_input():
    with open(os.path.join(REPO, "BENCH_input.json")) as f:
        return json.load(f)


def test_bench_input_json_schema():
    data = _load_input()
    assert data["bench"] == "input_bench"
    for top in INPUT_TOP_FIELDS:
        assert top in data, f"BENCH_input.json lost top-level field {top!r}"
    counts = [k for k in data["workers"] if k != "note"]
    assert "1" in counts, "single-thread baseline row missing"
    assert len(counts) >= 2, "need at least one multi-worker row"
    for k in counts:
        row = data["workers"][k]
        for field in INPUT_WORKER_FIELDS:
            assert field in row, (k, field)
            assert row[field] > 0, (k, field, row[field])
    assert data["workers"]["note"], \
        "GIL-bound-source caveat must stay documented"
    assert data["multi_worker_speedup"] > 0


def test_bench_input_json_host_shard_does_fractional_work():
    """The per-host sharded source must actually generate ~1/N the
    batch — the property that keeps host feed time flat at scale."""
    shard = _load_input()["host_shard"]
    for field in INPUT_SHARD_FIELDS:
        assert field in shard, field
    assert shard["num_hosts"] >= 2
    assert shard["shard_ms_per_batch"] < shard["global_ms_per_batch"]
    assert shard["shard_speedup"] > 1.5


def test_bench_input_json_transform_rows():
    tr = _load_input()["transform"]
    for field in ("host_aug_ms", "fused_ms", "note"):
        assert field in tr, field
    assert tr["host_aug_ms"] >= 0
    assert tr["fused_ms"] > 0
    assert tr["note"], "interpret-mode caveat must stay documented"


# ---------------------------------------------------------------------------
# BENCH_bn.json (benchmarks/bn_bench.py, DESIGN.md §10)
# ---------------------------------------------------------------------------

BN_TOP_FIELDS = ("bench", "backend", "devices", "iters", "epilogue",
                 "shapes", "fusion_report", "caveat")

BN_SHAPE_FIELDS = ("fused_fwd_ms", "unfused_fwd_ms", "fused_fwdbwd_ms",
                   "unfused_fwdbwd_ms", "fwd_speedup", "fwdbwd_speedup")


def _load_bn():
    with open(os.path.join(REPO, "BENCH_bn.json")) as f:
        return json.load(f)


def test_bench_bn_json_schema():
    data = _load_bn()
    assert data["bench"] == "bn_bench"
    for top in BN_TOP_FIELDS:
        assert top in data, f"BENCH_bn.json lost top-level field {top!r}"
    assert data["caveat"], "CPU-interpret caveat must stay documented"
    assert data["shapes"], "per-stage shape rows missing"
    for name, row in data["shapes"].items():
        assert isinstance(row.get("shape"), list) and len(row["shape"]) == 4
        for field in BN_SHAPE_FIELDS:
            assert field in row, (name, field)
            assert isinstance(row[field], (int, float)), (name, field)
            assert row[field] > 0, (name, field, row[field])


def test_bench_bn_json_fusion_report_proves_collapse():
    """The committed trajectory point must carry the HLO op-count
    collapse proof, not just wall-clocks (the clock is a CPU-interpret
    proxy; the per-site collapse is the transferable claim)."""
    rep = _load_bn()["fusion_report"]
    for section in ("fused", "unfused"):
        assert rep[section]["reduction_ops"] > 0
    assert rep["fused"]["reduction_ops"] < rep["unfused"]["reduction_ops"]
    assert rep["collapsed"] is True


# ---------------------------------------------------------------------------
# BENCH_scaling.json (examples/large_batch_sweep.py, DESIGN.md §11)
# ---------------------------------------------------------------------------

SCALING_TOP_FIELDS = ("bench", "arch", "backend", "devices", "quick",
                      "steps", "steps_per_epoch", "batches", "recipes")

SCALING_POINT_FIELDS = ("global_batch", "lr_scale", "final_loss",
                        "final_accuracy", "diverged")


def _load_scaling():
    with open(os.path.join(REPO, "BENCH_scaling.json")) as f:
        return json.load(f)


def test_bench_scaling_json_schema():
    data = _load_scaling()
    assert data["bench"] == "scaling_sweep"
    for top in SCALING_TOP_FIELDS:
        assert top in data, \
            f"BENCH_scaling.json lost top-level field {top!r}"
    assert isinstance(data["steps"], int) and data["steps"] > 0
    # acceptance: >= 2 recipes x >= 3 batch sizes
    assert len(data["recipes"]) >= 2
    assert len(data["batches"]) >= 3
    names = [r["recipe"] for r in data["recipes"]]
    assert len(set(names)) == len(names), f"duplicate recipes: {names}"


def test_bench_scaling_json_points_and_divergence_contract():
    data = _load_scaling()
    for rec in data["recipes"]:
        for field in ("recipe", "optimizer", "schedule",
                      "label_smoothing", "points"):
            assert field in rec, (rec.get("recipe"), field)
        # every recipe sweeps exactly the advertised batch grid, in order
        assert [p["global_batch"] for p in rec["points"]] == \
            data["batches"], rec["recipe"]
        assert len(rec["points"]) >= 3
        for p in rec["points"]:
            for field in SCALING_POINT_FIELDS:
                assert field in p, (rec["recipe"], field)
            assert p["lr_scale"] > 0
            # final metrics are None exactly when the cell diverged
            for metric in ("final_loss", "final_accuracy"):
                if p["diverged"]:
                    assert p[metric] is None, (rec["recipe"], p)
                else:
                    assert isinstance(p[metric], (int, float)), \
                        (rec["recipe"], metric, p)


def test_bench_scaling_covers_lars_and_baseline():
    """The sweep's point: the paper baseline vs the trust-ratio recipes
    on the same grid. Both optimizer kinds must be present."""
    kinds = {r["optimizer"] for r in _load_scaling()["recipes"]}
    assert "rmsprop_warmup" in kinds
    assert "lars" in kinds


# ---------------------------------------------------------------------------
# AUDIT.json (the compiled-program audit report, DESIGN.md §12)
# ---------------------------------------------------------------------------

AUDIT_PASSES = ("comm", "interleave", "precision", "donation", "memory",
                "collectives", "determinism")

AUDIT_CELL_FIELDS = ("mode", "optimizer", "contract", "ok", "violations",
                     "expectations", "info", "passes")

AUDIT_EXPECTATION_KEYS = ("n_buckets", "n_buckets_planned",
                          "collective_budget", "n_batch_params",
                          "metric_bytes_floor", "schedule_min_bytes",
                          "min_gradient_wire_bytes")


def _load_audit():
    with open(os.path.join(REPO, "AUDIT.json")) as f:
        return json.load(f)


def test_audit_json_covers_full_mode_matrix():
    data = _load_audit()
    assert data["ok"] is True, "committed AUDIT.json must be green"
    cells = {(c["mode"], c["optimizer"]) for c in data["cells"]}
    want = {(m, o)
            for m in ("gspmd", "perleaf", "bucketed", "overlap", "zero",
                      "zero_overlap", "hier", "hier_overlap",
                      "hier_zero", "hier_zero_overlap")
            for o in ("sgd", "lars")}
    assert cells == want, f"AUDIT.json lost cells: {want - cells}"
    # the hierarchical cells lower on their own 2-axis mesh
    assert len(data["hier_mesh"]) == 2
    assert all(s >= 2 for s in data["hier_mesh"])


def test_audit_json_cell_schema():
    data = _load_audit()
    for cell in data["cells"]:
        for field in AUDIT_CELL_FIELDS:
            assert field in cell, (cell["mode"], field)
        assert cell["ok"] is True and cell["violations"] == []
        missing = [p for p in AUDIT_PASSES if p not in cell["passes"]]
        assert not missing, (cell["mode"], missing)
        for pname, rec in cell["passes"].items():
            assert {"pass", "ok", "findings", "summary"} <= set(rec), \
                (cell["mode"], pname)
        for k in AUDIT_EXPECTATION_KEYS:
            assert k in cell["expectations"], (cell["mode"], k)


def test_audit_json_relations():
    data = _load_audit()
    rels = {(r["relation"], r["optimizer"]) for r in data["relations"]}
    assert rels == {("zero_shrinks_optimizer_residency", "sgd"),
                    ("zero_shrinks_optimizer_residency", "lars")}
    for r in data["relations"]:
        assert r["ok"] is True
        assert r["actual_shrink_bytes"] > 0


# ---------------------------------------------------------------------------
# BENCH_resilience.json (benchmarks/resilience_bench.py, DESIGN.md §13)
# ---------------------------------------------------------------------------

RESILIENCE_SCENARIOS = ("baseline", "nan_bucket", "rollback",
                        "ckpt_corrupt", "data_crash", "straggler")

RESILIENCE_FIELDS = ("chaos", "completed", "final_top1", "skipped_steps",
                     "rollbacks", "wasted_steps", "steps_to_recover",
                     "events", "ok", "wall_s")


def _load_resilience():
    with open(os.path.join(REPO, "BENCH_resilience.json")) as f:
        return json.load(f)


def test_bench_resilience_json_covers_all_fault_classes():
    data = _load_resilience()
    assert data["all_ok"] is True, "committed soak must be green"
    missing = [s for s in RESILIENCE_SCENARIOS
               if s not in data["scenarios"]]
    assert not missing, f"BENCH_resilience.json lost scenarios: {missing}"
    assert isinstance(data["baseline_top1"], (int, float))


def test_bench_resilience_json_scenario_schema():
    data = _load_resilience()
    for name, rec in data["scenarios"].items():
        for field in RESILIENCE_FIELDS:
            assert field in rec, (name, field)
        assert rec["completed"] is True and rec["ok"] is True, name
        if name != "baseline":
            assert rec["within_tolerance"] is True, name


def test_bench_resilience_json_recovery_contracts():
    """Each fault class must have driven its intended recovery path."""
    sc = _load_resilience()["scenarios"]
    assert sc["baseline"]["events"] == {}
    assert sc["nan_bucket"]["skipped_steps"] >= 1
    assert sc["nan_bucket"]["rollbacks"] == 0
    assert sc["rollback"]["rollbacks"] >= 1
    assert sc["rollback"]["wasted_steps"] >= 1
    assert sc["ckpt_corrupt"]["events"].get(
        "corrupt_checkpoint_skipped", 0) >= 1
    assert sc["ckpt_corrupt"]["rollbacks"] >= 1
    assert sc["data_crash"]["events"].get("data_restart", 0) >= 1
    assert sc["straggler"]["events"].get("chaos_injected", 0) >= 1


# ---------------------------------------------------------------------------
# BENCH_comm.json (benchmarks/comm_bench.py sweep artifact, DESIGN.md §14)
# ---------------------------------------------------------------------------

COMM_TOP_FIELDS = ("bench", "devices", "mesh", "mesh_axes", "wire",
                   "bucket_bytes", "sweep", "plan_path", "plan", "rows")

COMM_ROW_FIELDS = ("arch", "mode", "wire", "bucket_mib", "hier_split",
                   "leaves", "collectives_per_step", "mib_per_collective",
                   "wire_dtypes", "ms_per_sync")

COMM_PLAN_FIELDS = ("mesh_shape", "dp_axes", "sync_mode", "wire",
                    "bucket_bytes", "hier_split", "source", "version")


def _load_comm():
    path = os.path.join(REPO, "BENCH_comm.json")
    if not os.path.exists(path):
        import pytest
        pytest.skip("BENCH_comm.json not present (CI writes it right "
                    "before running this guard)")
    with open(path) as f:
        return json.load(f)


def test_bench_comm_json_schema():
    data = _load_comm()
    assert data["bench"] == "comm_bench"
    for top in COMM_TOP_FIELDS:
        assert top in data, f"BENCH_comm.json lost top-level field {top!r}"
    import math
    assert math.prod(data["mesh"]) == data["devices"]
    assert len(data["mesh"]) == len(data["mesh_axes"])
    assert data["rows"], "sweep produced no rows"
    for row in data["rows"]:
        for field in COMM_ROW_FIELDS:
            assert field in row, (row.get("mode"), field)
        assert row["ms_per_sync"] > 0, row
        assert row["collectives_per_step"] >= 1, row
        # hierarchical rows carry their split; flat rows carry None
        if row["mode"].startswith("hier"):
            assert row["hier_split"] is not None, row
        else:
            assert row["hier_split"] is None, row


def test_bench_comm_json_sweep_persists_winning_plan(tmp_path):
    """A --sweep run must leave a loadable CommPlan whose schedule is
    one of the swept rows — the artifact `--comm-plan auto` consumes.
    The plan file itself lives under the untracked ``results/``, so the
    test writes the embedded plan with ``save_plan`` at the artifact's
    file name and reads it back with ``load_plan``."""
    data = _load_comm()
    if not data["sweep"]:
        import pytest
        pytest.skip("not a sweep artifact: no plan to check")
    plan = data["plan"]
    assert plan is not None, "sweep artifact lost the embedded plan"
    for field in COMM_PLAN_FIELDS:
        assert field in plan, field
    assert plan["source"] == "autotuner"
    assert list(plan["mesh_shape"]) == list(data["mesh"])
    assert plan["bucket_bytes"] > 0
    from repro.distributed.comm_plan import (
        PLAN_VERSION,
        CommPlan,
        load_plan,
        save_plan,
    )
    assert plan["version"] == PLAN_VERSION
    path = save_plan(
        CommPlan(**{**plan, "mesh_shape": tuple(plan["mesh_shape"]),
                    "dp_axes": tuple(plan["dp_axes"])}),
        str(tmp_path / os.path.basename(data["plan_path"])))
    loaded = load_plan(path)
    assert loaded.mesh_shape == tuple(data["mesh"])
    assert loaded.sync_mode == plan["sync_mode"]
    assert loaded.hier_split == plan["hier_split"]
