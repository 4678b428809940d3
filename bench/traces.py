"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is reduced to plain intervals first (``Trace``): for each device
the operations that ran on it, and the benchmark's own host spans (names
starting ``bench.``). Every number below is computed from those
intervals, so the same code serves a trace read from the profiler's
``.xplane.pb`` and the small recorded trace the tests keep.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]  # (name, start_ns, end_ns)

OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.train_step"
SOURCE_SPAN = "bench.batch_at"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]  # device plane -> its op events
    spans: List[Interval]               # the benchmark's host spans

    def window(self) -> Tuple[int, int]:
        """(start, end) of the measured window, from its host span."""
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[-1]

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls(devices={k: [tuple(e) for e in v]
                            for k, v in d["devices"].items()},
                   spans=[tuple(e) for e in d["spans"]])


def load_xplane(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [(e.name, int(e.start_ns), int(e.end_ns))
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [(e.name, int(e.start_ns), int(e.end_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices=devices, spans=spans)


def load_json(path: str) -> Trace:
    """A trace kept as gzipped JSON ``{"devices": ..., "spans": ...}``."""
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# ------------------------------------------------------------ intervals

def union(intervals: Iterable[Tuple[int, int]], lo: int,
          hi: int) -> List[Tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def gaps(merged: Sequence[Tuple[int, int]], lo: int,
         hi: int) -> List[Tuple[int, int]]:
    """The parts of [lo, hi] that ``merged`` does not cover."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(a: Sequence[Tuple[int, int]],
             b: Sequence[Tuple[int, int]]) -> int:
    """Length of merged ``a`` not covered by merged ``b``."""
    total, j = 0, 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                total += b[k][0] - t
            t = max(t, b[k][1])
            k += 1
        if e > t:
            total += e - t
    return total


# ------------------------------------------------------------- numbers

def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window()
    per = [length(union(((s, e) for _, s, e in ops), lo, hi))
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def is_collective(name: str) -> bool:
    return "all-reduce" in name or "all_reduce" in name


def exposed_collective_s(trace: Trace) -> Optional[float]:
    """Seconds of all-reduce during which no other operation ran on the
    same device, averaged over the devices; None without all-reduce."""
    lo, hi = trace.window()
    per, seen = [], False
    for ops in trace.devices.values():
        coll = union(((s, e) for n, s, e in ops if is_collective(n)), lo, hi)
        seen = seen or bool(coll)
        other = union(((s, e) for n, s, e in ops if not is_collective(n)),
                      lo, hi)
        per.append(subtract(coll, other))
    if not seen:
        return None
    return sum(per) / len(per) / 1e9


def op_seconds(trace: Trace, match) -> Optional[float]:
    """Device seconds of the operations whose name ``match`` accepts,
    summed over the window and averaged over the devices; None where no
    operation matches."""
    lo, hi = trace.window()
    per, seen = [], False
    for ops in trace.devices.values():
        sel = union(((s, e) for n, s, e in ops if match(n)), lo, hi)
        seen = seen or bool(sel)
        per.append(length(sel))
    if not seen:
        return None
    return sum(per) / len(per) / 1e9


_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """``fusion.12 fusion`` for the HLO text the profiler gives an
    operation (``%fusion.12 = f32[...] fusion(...), ...``)."""
    m = _INSTR.match(name)
    if not m:
        return name[:80]
    op = _OPCODE.search(name, m.end() - 1)
    return f"{m.group(1)} {op.group(1)}" if op else m.group(1)


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operations with the most device seconds in the window,
    by short name, averaged over the devices."""
    lo, hi = trace.window()
    tot: Dict[str, int] = {}
    for ops in trace.devices.values():
        for n, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                n = short_name(n)
                tot[n] = tot.get(n, 0) + d
    nd = max(1, len(trace.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / nd / 1e9] for n, v in best]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """Device idle time in the window by what the host was doing: first
    the total per host span open at each gap's midpoint (the train step
    call, the input source, or none), then the longest single gaps."""
    lo, hi = trace.window()
    spans = sorted((s, e, n) for n, s, e in trace.spans
                   if n in (STEP_SPAN, SOURCE_SPAN))
    labelled = []
    for ops in trace.devices.values():
        busy = union(((s, e) for _, s, e in ops), lo, hi)
        for s, e in gaps(busy, lo, hi):
            mid = (s + e) // 2
            open_ = {n for a, b, n in spans if a <= mid < b}
            label = (STEP_SPAN if STEP_SPAN in open_ else
                     SOURCE_SPAN if SOURCE_SPAN in open_ else "none")
            labelled.append((label, e - s))
    nd = max(1, len(trace.devices))
    totals: Dict[str, int] = {}
    for label, d in labelled:
        totals[label] = totals.get(label, 0) + d
    out = [[f"total:{n}", v / nd / 1e9]
           for n, v in sorted(totals.items(), key=lambda kv: -kv[1])]
    longest = sorted(labelled, key=lambda x: -x[1])[:max(0, k - len(out))]
    return out + [[n, d / 1e9] for n, d in longest]
