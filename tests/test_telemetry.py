"""Spans and counters (repro.telemetry): the recorder itself, and the
spans ``Trainer.run`` and its ``DataPipeline`` record."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.training import Trainer, TrainerConfig


def _sleep_span(name, step=None, s=0.002):
    with telemetry.span(name, step) as sp:
        time.sleep(s)
    return sp


def test_nesting_parent_and_self_time():
    with telemetry.recording() as rec:
        with telemetry.span("outer", 3) as outer:
            _sleep_span("inner", 3)
            _sleep_span("inner", 3)
            with telemetry.span("other", 3):
                _sleep_span("deep", 3)
    assert telemetry.last() is rec
    by = {s.name: s for s in rec.spans}
    assert by["outer"].parent is None
    assert by["inner"].parent == "outer" and by["deep"].parent == "other"
    assert {s.step for s in rec.spans} == {3}
    # children tile the parent in time and in the open span's tally
    for s in rec.named("inner"):
        assert outer.t0_ns <= s.t0_ns < s.t1_ns <= outer.t1_ns
    inner_ns = sum(s.t1_ns - s.t0_ns for s in rec.named("inner"))
    assert outer.children["inner"] == inner_ns
    assert set(outer.children) == {"inner", "other"}
    # self time: the duration less what the direct children cover
    count, total, mx, self_ns = rec.stats["outer"]
    assert count == 1 and total == mx == outer.t1_ns - outer.t0_ns
    assert self_ns == total - inner_ns - (by["other"].t1_ns
                                          - by["other"].t0_ns)
    assert rec.stats["inner"][0] == 2
    summ = rec.summary()["spans"]
    assert summ["inner"]["count"] == 2
    assert summ["other"]["self_s"] < summ["other"]["total_s"]


def test_counters_under_the_open_step():
    with telemetry.recording() as rec:
        telemetry.count("bytes", 5)
        with telemetry.span("train.step", 7):
            with telemetry.span("inner"):  # no step: the step's
                telemetry.count("bytes", 2)
                telemetry.count("hits")
    assert rec.counters == {"bytes": 7, "hits": 1}
    assert rec.step_counters == {7: {"bytes": 2, "hits": 1}}
    telemetry.count("bytes")  # no recorder active: dropped
    assert rec.counters["bytes"] == 7


def test_bound_keeps_the_newest_spans_and_every_total():
    rec = telemetry.Recorder(limit=4)
    with telemetry.recording(rec):
        for i in range(10):
            with telemetry.span("s", i):
                pass
    assert [s.step for s in rec.spans] == [6, 7, 8, 9]
    assert rec.stats["s"][0] == 10


def test_spans_from_worker_threads():
    n_threads, per = 8, 50
    with telemetry.recording() as rec:
        def work(t):
            for i in range(per):
                with telemetry.span("feed.batch", t * per + i):
                    with telemetry.span("leaf"):
                        pass
        threads = [threading.Thread(target=work, args=(t,),
                                    name=f"w{t}") for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    batches = rec.named("feed.batch")
    assert sorted(s.step for s in batches) == list(range(n_threads * per))
    assert {s.thread for s in batches} == {f"w{t}" for t in range(n_threads)}
    assert all(s.parent is None for s in batches)  # parents per thread
    assert all(s.parent == "feed.batch" for s in rec.named("leaf"))
    assert rec.stats["leaf"][0] == n_threads * per


def test_a_span_records_where_it_began():
    """A worker's span that ends after its run records into that run."""
    first = telemetry.Recorder()
    with telemetry.recording(first):
        sp = telemetry.span("feed.batch", 0).__enter__()
    with telemetry.recording() as second:
        sp.__exit__(None, None, None)
    assert [s.name for s in first.spans] == ["feed.batch"]
    assert not second.spans


# ------------------------------------------------------------- trainer

class _Source:
    """Batches of ``width`` floats; at ``wide_at`` a wider one, which
    makes the jitted step trace and compile again."""
    batch = 4

    def __init__(self, width=8, wide_at=None):
        self.width, self.wide_at = width, wide_at

    def batch_at(self, step):
        w = self.width * (2 if step == self.wide_at else 1)
        return {"x": np.full((self.batch, w), step, np.float32)}


@jax.jit
def _step(state, batch):
    loss = jnp.mean(batch["x"]) + state
    return state + 1.0, {"loss": loss}


def _run(source, steps, workers=2):
    cfg = TrainerConfig(epochs=1, steps_per_epoch=steps,
                        eval_every_epochs=0, val_batches=0,
                        checkpoint_every=0, log_every=1,
                        data_workers=workers)
    res = Trainer(_step, jnp.float32(0.0), source, cfg,
                  put_batch=jax.device_put).run()
    return res, telemetry.last()


def test_trainer_spans_every_step():
    steps = 12
    res, rec = _run(_Source(), steps)
    by_step = {}
    for s in rec.spans:
        by_step.setdefault(s.step, []).append(s)
    for k in range(steps):
        main = {s.name: s for s in by_step[k] if s.name != "feed.batch"}
        outer = main["train.step"]
        for name in ("feed.wait", "train.dispatch", "train.sync"):
            inner = main[name]
            assert inner.parent == "train.step"
            assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
        assert sum(s.name == "feed.batch" for s in by_step[k]) == 1
        assert sum(s.name == "train.step" for s in by_step[k]) == 1
    # the feed's workers made the batches, off the main thread
    assert {s.thread for s in rec.named("feed.batch")} <= {
        "data-worker-0", "data-worker-1"}
    assert res.input_stats["data_wait_s"] == pytest.approx(
        rec.total_s("feed.wait"))
    assert res.input_stats["wall_s"] == pytest.approx(
        rec.total_s("train.step"))
    assert sum(h["time"] for h in res.history) == pytest.approx(
        rec.total_s("train.step"))
    assert [h["data_wait"] for h in res.history] == pytest.approx(
        [s.seconds for s in rec.named("feed.wait")])
    assert res.telemetry["spans"]["train.step"]["count"] == steps


def test_recompile_is_counted_in_its_step_and_the_straggler_record():
    wide = 9
    res, rec = _run(_Source(width=24, wide_at=wide), 14)
    assert rec.step_counters[wide]["traces"] >= 1
    assert rec.step_counters[wide]["compiles"] >= 1
    # steady steps (after the first, which compiled) compile nothing
    assert all(k in (0, wide) for k in rec.step_counters)
    slow = {e["step"]: e for e in res.straggler_events}
    assert wide in slow, res.straggler_events
    got = slow[wide]
    assert got["compiles"] >= 1 and got["compile_s"] > 0
    assert set(got["spans_ms"]) >= {"feed.wait", "train.dispatch",
                                    "train.sync", "self"}
    assert sum(got["spans_ms"].values()) == pytest.approx(got["time"] * 1e3)
    # traced and compiled inside the call
    assert got["spans_ms"]["train.dispatch"] >= 0.9e3 * got["compile_s"]
