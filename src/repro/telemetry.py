"""Spans and counters of the trainer and its input feed.

``span(name, step)`` times a piece of host work twice over:

- a ``jax.profiler.TraceAnnotation`` puts it in any profile taken
  meanwhile, on the device trace's clock (``train.step`` is a
  ``StepTraceAnnotation``, so xprof sees the steps). When no profile is
  being taken this costs about a microsecond.
- the active ``Recorder`` keeps it in memory as a ``Span``
  ``(name, step, thread, parent, t0_ns, t1_ns)`` on the
  ``time.perf_counter_ns`` clock, with a per-name count, total, max and
  self time (the duration less what its child spans cover).

``step`` is the identifier the spans of one training step share: the
``feed.batch`` span a feed worker records for step *s* carries *s*, like
the main thread's spans of step *s*. The parent is the span open on the
same thread when this one began.

``count(name, n)`` adds to a counter of the active recorder, under the
step of the innermost open span that has one. A ``jax.monitoring``
listener counts every retrace (``traces``) and backend compile
(``compiles``) and their seconds (``compile_s``) the same way: with the
persistent compilation cache on, a retrace can hit the cache and never
reach the backend, so both are counted.

The active recorder is one per process, not per context: the feed's
worker threads, which ``DataPipeline`` starts itself, record into it. A
span records into the recorder that was active when it began.
``recording()`` makes a recorder active for a block; ``last()`` is the
recorder of the block that ended last.

The spans and who reads them (PERF.md section 3):

=================  ==========================================  =============
span / counter     where                                       reader
=================  ==========================================  =============
``train.step``     ``Trainer.run``: next batch .. the previous step time,
                   step's loss sync                            stragglers
``train.dispatch`` ``Trainer.run``: the jitted step call       ``dispatch_ms``,
                                                               ``idle_dispatch_pct``
``train.sync``     ``Trainer.run``: a step's loss to the host, stragglers
                   inside the next step's ``train.step``
``feed.wait``      ``DataPipeline.__next__`` until the batch   ``input_wait_pct``
                   is in hand
``feed.put``       ``DataPipeline``: each device ``put``       stragglers
``feed.batch``     ``DataPipeline`` worker: ``batch_at`` and   ``feed_batch_ms``
                   the transform
``eval``           ``Trainer.run``: ``evaluate``               ``--log-json``
``ckpt.save``      ``Trainer.run``: each checkpoint save       ``--log-json``
``traces``,        the ``jax.monitoring`` listener             stragglers
``compiles``,
``compile_s``
``train.overlap``  ``Trainer.run``: a step dispatched while    stragglers,
                   the previous step's loss was unread         ``--log-json``
=================  ==========================================  =============
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Deque, Dict, Iterator, List, NamedTuple, Optional

import jax

# Spans a recorder keeps; a 40 s benchmark window holds under 5,000.
SPAN_LIMIT = 1 << 16
STEP_SPAN = "train.step"
_COUNTED_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
}


class Span(NamedTuple):
    name: str
    step: Optional[int]
    thread: str
    parent: Optional[str]
    t0_ns: int
    t1_ns: int

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


class Recorder:
    """Spans (the newest ``limit``) and counters of one run; safe to feed
    from several threads."""

    def __init__(self, limit: int = SPAN_LIMIT):
        self.spans: Deque[Span] = collections.deque(maxlen=limit)
        # name -> [count, total_ns, max_ns, self_ns], over every span,
        # also those the bounded deque has let go
        self.stats: Dict[str, List[int]] = {}
        self.counters: Dict[str, float] = {}
        self.step_counters: Dict[int, Dict[str, float]] = {}
        self._lock = threading.Lock()

    def add(self, span: Span, child_ns: int = 0) -> None:
        d = span.t1_ns - span.t0_ns
        with self._lock:
            self.spans.append(span)
            st = self.stats.setdefault(span.name, [0, 0, 0, 0])
            st[0] += 1
            st[1] += d
            st[2] = max(st[2], d)
            st[3] += d - child_ns

    def count(self, name: str, n: float = 1,
              step: Optional[int] = None) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n
            if step is not None:
                per = self.step_counters.setdefault(step, {})
                per[name] = per.get(name, 0) + n

    def named(self, name: str) -> List[Span]:
        """The kept spans called ``name``, in the order they ended."""
        with self._lock:
            return [s for s in self.spans if s.name == name]

    def total_s(self, name: str) -> float:
        with self._lock:
            st = self.stats.get(name)
        return st[1] / 1e9 if st else 0.0

    def summary(self) -> Dict:
        """Per span name: count, total and self seconds, mean and max
        milliseconds; and the counters."""
        with self._lock:
            stats = {k: list(v) for k, v in self.stats.items()}
            counters = dict(self.counters)
        return {"spans": {k: {"count": c, "total_s": t / 1e9,
                              "self_s": s / 1e9, "mean_ms": t / c / 1e6,
                              "max_ms": m / 1e6}
                          for k, (c, t, m, s) in sorted(stats.items())},
                "counters": counters}


_active: Optional[Recorder] = None
_last: Optional[Recorder] = None
_open = threading.local()  # .stack: this thread's open spans
_listening = False
_listen_lock = threading.Lock()


def _stack() -> List["_Open"]:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _current_step() -> Optional[int]:
    for s in reversed(_stack()):
        if s.step is not None:
            return s.step
    return None


class _Open:
    """An open span; after the block, ``seconds`` is its duration and
    ``children`` the nanoseconds of its direct child spans by name."""

    __slots__ = ("name", "step", "children", "t0_ns", "t1_ns", "_rec",
                 "_ann", "_parent")

    def __init__(self, name: str, step: Optional[int]):
        self.name, self.step = name, step
        self.children: Dict[str, int] = {}
        self.t0_ns = self.t1_ns = 0

    def __enter__(self) -> "_Open":
        self._rec = _active
        if self.name == STEP_SPAN and self.step is not None:
            self._ann = jax.profiler.StepTraceAnnotation(
                self.name, step_num=self.step)
        else:
            self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack = _stack()
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.perf_counter_ns()
        _stack().pop()
        self._ann.__exit__(*exc)
        d = self.t1_ns - self.t0_ns
        parent = self._parent
        if parent is not None:
            parent.children[self.name] = parent.children.get(self.name,
                                                             0) + d
        if self._rec is not None:
            self._rec.add(Span(self.name, self.step,
                               threading.current_thread().name,
                               parent.name if parent else None,
                               self.t0_ns, self.t1_ns),
                          sum(self.children.values()))
        return False

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def span(name: str, step: Optional[int] = None) -> _Open:
    """A context manager that records the block as span ``name``."""
    return _Open(name, step)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the active recorder."""
    rec = _active
    if rec is not None:
        rec.count(name, n, _current_step())


def _on_duration(event: str, duration: float, **_) -> None:
    name = _COUNTED_EVENTS.get(event)
    rec = _active
    if name is None or rec is None:
        return
    step = _current_step()
    rec.count(name, 1, step)
    rec.count("compile_s", duration, step)


def _listen() -> None:
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


@contextlib.contextmanager
def recording(rec: Optional[Recorder] = None) -> Iterator[Recorder]:
    """Make ``rec`` (a new recorder by default) the active one for the
    block; afterwards ``last()`` returns it."""
    global _active, _last
    _listen()
    rec = Recorder() if rec is None else rec
    prev, _active = _active, rec
    try:
        yield rec
    finally:
        _active, _last = prev, rec


def last() -> Optional[Recorder]:
    """The recorder of the ``recording`` block that ended last."""
    return _last
