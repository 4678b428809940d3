"""The reduction from trace to metrics, on synthetic intervals and on a
few steps of a trace recorded on a TPU v5e."""
import os

import pytest

from bench import traces
from bench.traces import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def _trace(devices, spans, window=(0, 100 * MS)):
    return Trace(devices=devices,
                 spans=[("bench.window", *window)] + spans)


def test_union_gaps_and_subtract():
    merged = traces.union([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25)
    assert merged == [(1, 3), (5, 12), (20, 25)]
    assert traces.length(merged) == 14
    assert traces.gaps(merged, 0, 30) == [(0, 1), (3, 5), (12, 20), (25, 30)]
    assert traces.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == 2 + 4 + 8


def test_busy_idle_averaged_over_devices():
    tr = _trace({"/device:TPU:0": [("conv", 0, 40 * MS), ("add", 30 * MS, 60 * MS)],
                 "/device:TPU:1": [("conv", 10 * MS, 30 * MS)]}, [])
    assert traces.window_s(tr) == pytest.approx(0.1)
    assert traces.busy_s(tr) == pytest.approx((0.060 + 0.020) / 2)
    # events outside the window do not count
    tr.devices["/device:TPU:1"].append(("late", 150 * MS, 160 * MS))
    assert traces.busy_s(tr) == pytest.approx(0.040)


def test_exposed_collective_is_the_part_nothing_else_covers():
    tr = _trace({"/device:TPU:0": [("fusion.1", 0, 50 * MS),
                                   ("all-reduce.3", 40 * MS, 70 * MS)],
                 "/device:TPU:1": [("fusion.1", 0, 50 * MS),
                                   ("all-reduce.3", 60 * MS, 70 * MS)]}, [])
    assert traces.exposed_collective_s(tr) == pytest.approx((0.020 + 0.010) / 2)
    tr.devices = {k: [e for e in v if "all-reduce" not in e[0]]
                  for k, v in tr.devices.items()}
    assert traces.exposed_collective_s(tr) is None


def test_op_seconds_and_top_ops():
    tr = _trace({"/device:TPU:0": [("k_pack", 0, 5 * MS), ("conv", 5 * MS, 50 * MS),
                                   ("k_pack", 60 * MS, 65 * MS)]}, [])
    assert traces.op_seconds(tr, lambda n: "pack" in n) == pytest.approx(0.010)
    assert traces.op_seconds(tr, lambda n: "nothing" in n) is None
    top = traces.top_ops(tr, 1)
    assert top[0][0] == "conv" and top[0][1] == pytest.approx(0.045)


def test_idle_gaps_are_labelled_by_the_open_host_span():
    tr = _trace({"/device:TPU:0": [("a", 10 * MS, 20 * MS), ("b", 50 * MS, 90 * MS)]},
                [("bench.train_step", 20 * MS, 40 * MS),
                 ("bench.batch_at", 0, 30 * MS)])
    got = dict((n, v) for n, v in traces.idle_gaps(tr)[:3])
    # gaps: [0,10) mid 5 -> batch_at; [20,50) mid 35 -> train_step;
    # [90,100) mid 95 -> none
    assert got["total:bench.train_step"] == pytest.approx(0.030)
    assert got["total:bench.batch_at"] == pytest.approx(0.010)
    assert got["total:none"] == pytest.approx(0.010)
    assert traces.idle_gaps(tr)[3] == ["bench.train_step", pytest.approx(0.030)]


def test_recorded_chip_trace():
    """Four steps of resnet50.b32 traced on a TPU v5e."""
    tr = traces.load_json(os.path.join(HERE, "data",
                                       "trace_resnet50.b32.json.gz"))
    assert list(tr.devices) == ["/device:TPU:0"]
    w, busy = traces.window_s(tr), traces.busy_s(tr)
    assert 0 < busy < w
    top = traces.top_ops(tr)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert sum(v for n, v in traces.idle_gaps(tr) if n.startswith("total:")) \
        == pytest.approx(w - busy, rel=1e-6)
    assert traces.exposed_collective_s(tr) is None  # one chip
    assert sum(1 for n, _, _ in tr.spans if n == "bench.train_step") == 4


def test_recorded_trace_shows_the_bucket_cast_kernels():
    """The f16 pack and unpack kernels, once each per step, over the
    ResNet-50 gradient stream padded to whole tiles."""
    from bench import harness
    tr = traces.load_json(os.path.join(HERE, "data",
                                       "trace_resnet50.b32.json.gz"))
    reader = harness.load_module(os.path.join(HERE, "metrics",
                                              "bucket_cast_ms.py"), "bc")
    lo, hi = tr.window()
    casts = [n for ops in tr.devices.values() for n, s, e in ops
             if reader.is_cast_kernel(n) and lo <= s < hi]
    assert len(casts) == 2 * 4
    assert all("[199680,128]" in n for n in casts)
    ms = traces.op_seconds(tr, reader.is_cast_kernel) * 1e3 / 4
    assert 0.1 < ms < 5.0
