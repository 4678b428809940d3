"""The program's own spans (``repro.telemetry``) as the benchmark reads
them: the recorder of the window's ``Trainer.run`` (the last run of the
trainer; the reference runs none), and its clock put onto a device
trace's.

The program's spans keep ``time.perf_counter_ns``; the profiler's
timestamps count from elsewhere. The program's ``train.dispatch`` span
wraps each call of the train step, and the benchmark's
``bench.train_step`` span wraps the same call a few microseconds inside
it, so the median gap between the starts of paired calls maps one clock
onto the other. A program without ``repro.telemetry`` reads nothing."""
from __future__ import annotations

import statistics
from typing import List, Optional, Tuple

from bench import traces

DISPATCH = "train.dispatch"


def recorder():
    """The recorder of the program's last trainer run, or None."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    return telemetry.last()


def mean_ms(spans) -> Optional[float]:
    return (1e3 * sum(s.seconds for s in spans) / len(spans)
            if spans else None)


def window_spans(rec, name: str) -> List:
    """Spans ``name`` that serve a step of the run (a feed's workers run
    ahead of the last step)."""
    steps = {s.step for s in rec.named("train.step")}
    return [s for s in rec.named(name) if s.step in steps]


def offset_ns(program: List[Tuple[int, int]],
              calls: List[Tuple[int, int]]) -> float:
    """Trace time minus program time, from paired call intervals."""
    return statistics.median_low(c[0] - p[0] for p, c in zip(program, calls))


def dispatch_on_trace(rec, trace, n: int) -> Optional[List[Tuple[int,
                                                                  int]]]:
    """The last ``n`` ``train.dispatch`` spans on the trace's clock; None
    unless the trace holds exactly ``n`` step calls to pair them with."""
    calls = sorted((s, e) for name, s, e in trace.spans
                   if name == traces.STEP_SPAN)
    prog = [(s.t0_ns, s.t1_ns) for s in rec.named(DISPATCH)]
    if n <= 0 or len(calls) != n or len(prog) < n:
        return None
    prog = prog[-n:]
    off = offset_ns(prog, calls)
    return [(s + off, e + off) for s, e in prog]
