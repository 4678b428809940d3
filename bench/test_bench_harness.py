"""The harness on the CPU: cells, configurations and metrics are found by
name from their own files, a whole run at a tiny size agrees with the
plain reference, and without an accelerator the command prints no
result."""
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NEW_METRIC = '''"""Steps in the window: a metric added as a file of its own."""


def read(ctx):
    return float(ctx.steps)
'''


@pytest.fixture(scope="module")
def grown_root(tiny_root):
    """The tiny checkout with one more metric, added by a new file and a
    new entry only."""
    with open(os.path.join(tiny_root, "bench", "metrics",
                           "window_steps.py"), "w") as f:
        f.write(NEW_METRIC)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "window_steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step",
                              "moves": "images_per_s",
                              "workloads": ["tiny.t4"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    return tiny_root


@pytest.fixture(scope="module")
def traced_run(grown_root):
    import jax
    cell = harness.load_cell("tiny.t4", grown_root)
    return cell, harness.run(cell, 2 ** 33 + 7, 0.5, True, jax.devices(),
                             time.perf_counter(), log=lambda s: None)


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert cell.global_batch % cell.chips == 0
        assert cell.limits and set(cell.limits) <= set(harness.NUMBERS)
        assert all(v is not None and v > 0 for v in cell.limits.values())
        names = {m["name"] for m in cell.end_to_end}
        assert {"images_per_s", "step_ms_p95", "setup_s"} <= names
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"))
        ref = cell.reference()
        spec_ = ref.param_spec(cell.model)
        assert sum(math.prod(s) for _, s in spec_) \
            == cell.model["param_count"]


def test_new_config_traffic_cell_and_metric_are_found_by_name(grown_root):
    cell = harness.load_cell("tiny.t4", grown_root)
    assert cell.config["name"] == "tiny"
    assert cell.traffic["per_chip_batch"] == 4
    assert "window_steps" in [m["name"] for m in cell.per_layer]
    # a metric listed for other cells only is not read in this one
    assert "bucket_cast_ms" not in [m["name"] for m in cell.per_layer]
    # the cells already there are untouched by the additions
    old = harness.load_cell("resnet50.b32", grown_root)
    assert "window_steps" not in [m["name"] for m in old.per_layer]


def test_tiny_run_agrees_with_the_reference(traced_run):
    cell, res = traced_run
    assert res["correct"], harness.checks_text(res["checks"])
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell.limits)


def test_traced_run_reports_per_layer_metrics(traced_run):
    cell, res = traced_run
    metrics = res["metrics"]
    assert metrics["window_steps"]["value"] == res["attempted"]
    assert 0 <= metrics["input_wait_pct"]["value"] <= 100
    # the CPU has no device trace and no published peak: readers that
    # find nothing return nothing, and the metric is left out
    for name in ("device_idle_pct", "step_mfu_pct",
                 "bucket_cast_ms"):
        assert name not in metrics
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_step_p95_is_over_every_step():
    steps = [0.1] * 90 + [0.15] * 10  # ten stalls in a 100 ms stream
    starts = list(np.cumsum([0.0] + steps[:-1]))
    end = float(np.sum(steps))
    assert harness.step_p95_ms(starts, end) == pytest.approx(150.0)
    # three stalls in 93 steps lie beyond the 95th percentile
    assert harness.step_p95_ms(starts[:93], starts[93]) == pytest.approx(100.0)
    assert harness.step_p95_ms([0.0], 2.0) == pytest.approx(2000.0)


def test_command_without_accelerator_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "run.py"),
                        "--workload", "resnet50.b32", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no accelerator" in p.stderr
