"""A tiny copy of the benchmark for the CPU tests: the real harness,
reference and metric readers, with a ResNet of one block per stage,
8 channels and 64x64 images, so a whole run compiles and finishes in
seconds on the CPU."""
import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

TINY_MODEL = {"conv_stages": [1, 1, 1, 1], "conv_width": 8,
              "image_size": 64, "num_classes": 10}


def make_tiny_root(path):
    """A checkout-like directory under ``path`` holding BENCHMARK.json and
    bench/ with one more configuration ``tiny``, traffic ``t4`` and cell
    ``tiny.t4`` on one chip, whose correctness limits are those of
    ``resnet50.b32``."""
    root = os.path.join(str(path), "root")
    shutil.copytree(HERE, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny"
    cfg["model"].update(TINY_MODEL)
    cfg["model"]["param_count"] = None
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    traffic = {"per_chip_batch": 4, "input": "host", "pool_batches": 4,
               "augment": {"max_shift": 4, "mean": [0.0, 0.0, 0.0],
                           "std": [1.0, 1.0, 1.0]}}
    with open(os.path.join(bench, "traffic", "t4.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "workloads", "resnet50.b32.json")) as f:
        limits = json.load(f)["limits"]
    with open(os.path.join(bench, "workloads", "tiny.t4.json"), "w") as f:
        json.dump({"limits": limits}, f)
    spec["configs"].append({"name": "tiny", "source": "tests",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["conv_stages"], "why": "tests"})
    spec["workloads"].append({"name": "tiny.t4", "config": "tiny",
                              "traffic": "t4", "chips": 1,
                              "why": "tests"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))
