"""Production input pipeline (repro.data.pipeline, DESIGN.md §15).

Covers the DataPipeline delivery/error/close/backpressure contracts,
the device-staging double buffer, the legacy Prefetcher raise-once
port, and the SyntheticImageData allocation regression.
"""
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro import telemetry
from repro.data.pipeline import DataPipeline, StepStampSource
from repro.data.synthetic import Prefetcher, SyntheticImageData


class CountingSource:
    """batch_at returns a recognizable payload and records every step
    (thread-safely), with an optional per-step delay/failure."""

    def __init__(self, batch=4, delay=0.0, fail_at=None,
                 delays=None):
        self.batch = batch
        self.delay = delay
        self.fail_at = fail_at
        self.delays = delays or {}
        self.calls = []
        self._lock = threading.Lock()

    def batch_at(self, step):
        with self._lock:
            self.calls.append(step)
        time.sleep(self.delays.get(step, self.delay))
        if self.fail_at is not None and step == self.fail_at:
            raise RuntimeError(f"boom at {step}")
        return {"x": np.full((self.batch,), step, np.int64)}


# ---------------------------------------------------------------------------
# ordered delivery and determinism
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [1, 4])
def test_ordered_delivery(workers):
    src = CountingSource()
    pipe = DataPipeline(src, num_workers=workers, depth=4)
    try:
        for want in range(10):
            step, batch = next(pipe)
            assert step == want
            np.testing.assert_array_equal(batch["x"], want)
    finally:
        pipe.close()


def test_multi_worker_bitwise_equals_single_worker():
    """Worker count is a throughput knob, not a semantic one: the
    delivered stream is bitwise identical for any num_workers."""
    src = SyntheticImageData(4, 8, 4, seed=3)
    ref = [src.batch_at(s) for s in range(6)]
    pipe = DataPipeline(src, num_workers=3, depth=4)
    try:
        for s in range(6):
            step, batch = next(pipe)
            assert step == s
            for k in ref[s]:
                np.testing.assert_array_equal(batch[k], ref[s][k])
    finally:
        pipe.close()


def test_start_step_and_restart_stability():
    """A pipeline rebuilt at step k (elastic restart / rollback seek)
    delivers exactly what the original stream had at step k."""
    src = SyntheticImageData(4, 8, 4, seed=0)
    p1 = DataPipeline(src, num_workers=2)
    try:
        seen = {s: b for s, b in (next(p1) for _ in range(5))}
    finally:
        p1.close()
    p2 = DataPipeline(src, start_step=3, num_workers=2)
    try:
        step, batch = next(p2)
        assert step == 3
        np.testing.assert_array_equal(batch["images"], seen[3]["images"])
        np.testing.assert_array_equal(batch["labels"], seen[3]["labels"])
    finally:
        p2.close()


def test_transform_applied_by_workers():
    src = CountingSource()
    pipe = DataPipeline(src, num_workers=2,
                        transform=lambda b: {"x": b["x"] * 10})
    try:
        for want in range(4):
            _, batch = next(pipe)
            np.testing.assert_array_equal(batch["x"], want * 10)
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------


def test_backpressure_bounds_claim_horizon():
    """Producers may claim at most ``depth`` steps past the last
    delivered one — a stalled consumer stalls the pool instead of
    buffering unboundedly."""
    src = CountingSource()
    depth = 3
    pipe = DataPipeline(src, num_workers=4, depth=depth)
    try:
        next(pipe)  # consumer at step 1 now
        time.sleep(0.3)  # give the pool every chance to overrun
        assert max(src.calls) <= depth  # claims < next_out(1) + depth
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# error contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [1, 3])
def test_error_raised_once_at_its_step_then_stopiteration(workers):
    src = CountingSource(fail_at=2)
    pipe = DataPipeline(src, num_workers=workers, depth=4)
    try:
        for want in range(2):  # earlier steps still arrive
            step, _ = next(pipe)
            assert step == want
        with pytest.raises(RuntimeError, match="boom at 2"):
            next(pipe)
        # exactly once; afterwards the stream is closed, not a loop of
        # re-raises of the same exception object
        with pytest.raises(StopIteration):
            next(pipe)
    finally:
        pipe.close()


def test_error_attributed_to_smallest_failed_step():
    """With concurrent workers, a fast-failing later step must not
    mask (or get masked by) the error the consumer hits first."""
    src = CountingSource(fail_at=1, delays={0: 0.2})
    pipe = DataPipeline(src, num_workers=4, depth=4)
    try:
        step, _ = next(pipe)  # step 0, despite being the slowest
        assert step == 0
        with pytest.raises(RuntimeError, match="boom at 1"):
            next(pipe)
    finally:
        pipe.close()


def test_close_unblocks_waiting_consumer():
    src = CountingSource(delay=60.0)  # nothing will ever be ready
    pipe = DataPipeline(src, num_workers=2, depth=2)
    got = {}

    def consume():
        try:
            next(pipe)
        except StopIteration:
            got["stopped"] = True

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.2)
    pipe.close()
    t.join(timeout=5)
    assert not t.is_alive(), "consumer stayed parked across close()"
    assert got.get("stopped")


def test_close_idempotent_and_joins_workers():
    src = CountingSource()
    pipe = DataPipeline(src, num_workers=3)
    next(pipe)
    pipe.close()
    pipe.close()
    assert all(not t.is_alive() for t in pipe._threads)


# ---------------------------------------------------------------------------
# device staging
# ---------------------------------------------------------------------------


def test_device_staging_orders_and_stages_each_step_once():
    staged = []

    def put(batch):
        staged.append(int(batch["x"][0]))
        return {"x": batch["x"] + 1000}

    src = CountingSource()
    pipe = DataPipeline(src, num_workers=2, depth=4, put=put,
                        device_ahead=2)
    try:
        for want in range(8):
            step, batch = next(pipe)
            assert step == want
            np.testing.assert_array_equal(batch["x"], want + 1000)
        # each step staged exactly once, in order
        assert staged[:8] == list(range(8))
        assert len(staged) == len(set(staged))
    finally:
        pipe.close()


def test_device_staging_never_swallows_error_attribution():
    """Opportunistic staging for step k+1 must not raise step k+1's
    error while the caller is still consuming step k."""
    src = CountingSource(fail_at=1)
    pipe = DataPipeline(src, num_workers=2, depth=4,
                        put=lambda b: b, device_ahead=2)
    try:
        step, _ = next(pipe)  # stages ahead; error at 1 already pending
        assert step == 0
        with pytest.raises(RuntimeError, match="boom at 1"):
            next(pipe)
    finally:
        pipe.close()


def test_wait_attribution_counters():
    src = CountingSource(delays={3: 0.25})
    with telemetry.recording() as rec:
        pipe = DataPipeline(src, num_workers=1, depth=2)
        try:
            for _ in range(5):
                next(pipe)
        finally:
            pipe.close()
    spans = rec.named("feed.wait")
    waits = [s.seconds for s in spans]
    assert [s.step for s in spans] == list(range(5))  # 5 deliveries
    assert rec.total_s("feed.wait") == pytest.approx(sum(waits))
    assert waits[3] >= 0.1  # the slow step shows up as wait


def test_step_stamp_source():
    src = StepStampSource(CountingSource())
    b = src.batch_at(7)
    assert b["input_step"] == np.int32(7)
    assert b["input_step"].dtype == np.int32
    np.testing.assert_array_equal(b["x"], 7)


# ---------------------------------------------------------------------------
# per-host shard partition (deterministic twin of the hypothesis
# properties in test_properties.py, which skip when hypothesis is absent)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hosts", [2, 4])
@pytest.mark.parametrize("split", ["train", "val"])
def test_host_shards_union_is_bitwise_global_batch(hosts, split):
    batch = 8
    full = SyntheticImageData(4, 8, batch, seed=11, split=split)
    per = batch // hosts
    for step in (0, 3, 17):
        want = full.batch_at(step)
        shards = [SyntheticImageData(4, 8, per, seed=11, split=split,
                                     sample_offset=h * per).batch_at(step)
                  for h in range(hosts)]
        np.testing.assert_array_equal(
            np.concatenate([s["images"] for s in shards]), want["images"])
        np.testing.assert_array_equal(
            np.concatenate([s["labels"] for s in shards]), want["labels"])


# ---------------------------------------------------------------------------
# legacy Prefetcher: raise-once port
# ---------------------------------------------------------------------------


def test_prefetcher_raises_once_then_stopiteration():
    src = CountingSource(fail_at=0)
    pf = Prefetcher(src)
    try:
        with pytest.raises(RuntimeError, match="boom at 0"):
            next(pf)
        with pytest.raises(StopIteration):
            next(pf)
        with pytest.raises(StopIteration):
            next(pf)
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# SyntheticImageData allocation regression
# ---------------------------------------------------------------------------


def test_batch_at_peak_allocation_near_one_batch():
    """batch_at must fill one preallocated float32 buffer in place.

    The seed-era path generated float64 noise per sample and then
    ``astype``-copied the whole summed batch a second time — peak well
    above 2x the batch. The rewrite's peak is the output buffer plus
    one per-sample float32 noise tile (~1/batch extra)."""
    src = SyntheticImageData(4, 32, 16, seed=0)
    batch_bytes = 16 * 32 * 32 * 3 * 4
    src.batch_at(0)  # warm any lazy machinery outside the trace
    tracemalloc.start()
    src.batch_at(1)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 1.5 * batch_bytes, (
        f"batch_at peak {peak} vs batch {batch_bytes}: an extra "
        "batch-sized temporary is back")
