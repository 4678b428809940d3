"""The readers of the program's spans: on the steps of a trace recorded on
a TPU v5e, with program spans made from its ``bench.train_step`` spans
on another clock."""
import os
import types

import pytest

from bench import harness, spans, traces

HERE = os.path.dirname(os.path.abspath(__file__))
OFFSET = 1_790_000_000_123_456_789  # program clock minus trace clock, ns
TAIL = 15_000  # ns the dispatch span holds after the call


def _reader(name):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "bench_metric_" + name)


@pytest.fixture
def recorded():
    """The recorded trace, and the program's spans of its four steps: a
    ``train.dispatch`` span around each ``bench.train_step`` call,
    ``train.step`` around that, and a worker's ``feed.batch`` per step
    (one more for a step beyond the window)."""
    from repro import telemetry
    tr = traces.load_json(os.path.join(HERE, "data",
                                       "trace_resnet50.b32.json.gz"))
    calls = sorted((s, e) for n, s, e in tr.spans if n == traces.STEP_SPAN)
    rec = telemetry.Recorder()
    for k, (s, e) in enumerate(calls):
        t0, t1 = s + OFFSET, e + OFFSET + TAIL
        rec.add(telemetry.Span("feed.batch", k, "data-worker-0", None,
                               t0 - 60_000_000, t0 - 10_000_000))
        rec.add(telemetry.Span("train.dispatch", k, "MainThread",
                               "train.step", t0, t1))
        rec.add(telemetry.Span("train.step", k, "MainThread", None,
                               t0 - 1_000_000, t1 + 2_000_000))
    rec.add(telemetry.Span("feed.batch", len(calls), "data-worker-0", None,
                           0, 1_000_000_000))
    with telemetry.recording(rec):
        pass
    ctx = types.SimpleNamespace(trace=tr, traced_steps=len(calls))
    return ctx, rec, calls


def test_alignment_recovers_the_offset(recorded):
    ctx, rec, calls = recorded
    got = spans.dispatch_on_trace(rec, ctx.trace, ctx.traced_steps)
    assert got == [(s, e + TAIL) for s, e in calls]
    prog = [(s.t0_ns, s.t1_ns) for s in rec.named("train.dispatch")]
    assert spans.offset_ns(prog, calls) == -OFFSET


def test_counts_that_do_not_pair_read_nothing(recorded):
    ctx, rec, _ = recorded
    for n in (0, ctx.traced_steps - 1, ctx.traced_steps + 1):
        assert spans.dispatch_on_trace(rec, ctx.trace, n) is None
    ctx.traced_steps += 1
    assert _reader("idle_dispatch_pct").read(ctx) is None


def test_idle_dispatch_is_part_of_device_idle(recorded):
    ctx, _, calls = recorded
    idle = _reader("idle_dispatch_pct").read(ctx)
    device_idle = _reader("device_idle_pct").read(ctx)
    assert 0 < idle <= device_idle
    # each idle gap of the device, as much of it as a dispatch covers
    lo, hi = ctx.trace.window()
    ops = ctx.trace.devices["/device:TPU:0"]
    gaps = traces.gaps(traces.union(((s, e) for _, s, e in ops), lo, hi),
                       lo, hi)
    covered = sum(max(0, min(e, c1 + TAIL) - max(s, c0))
                  for s, e in gaps for c0, c1 in calls)
    assert idle == pytest.approx(100 * covered / (hi - lo))


def test_program_span_readers(recorded):
    ctx, _, calls = recorded
    assert _reader("dispatch_ms").read(ctx) == pytest.approx(
        sum(e - s + TAIL for s, e in calls) / len(calls) / 1e6)
    # the window's four batches, not the one made beyond it
    assert _reader("feed_batch_ms").read(ctx) == pytest.approx(50.0)


def test_a_program_without_spans_reads_nothing(recorded, monkeypatch):
    ctx, _, _ = recorded
    monkeypatch.setattr(spans, "recorder", lambda: None)
    for name in ("feed_batch_ms", "dispatch_ms", "idle_dispatch_pct"):
        assert _reader(name).read(ctx) is None
