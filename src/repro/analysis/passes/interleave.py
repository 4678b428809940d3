"""Collective/compute interleaving (backward-overlapped sync,
DESIGN.md §8) — migrated from ``launch/hlo_analysis.py`` and wrapped as
the ``interleave`` audit pass."""
from __future__ import annotations

import re
from typing import Dict, List

from repro.analysis.hlo_ir import (
    COLLECTIVES,
    Op,
    _BRANCHES_RE,
    _CALLED_RE,
    _op_defs,
    parse_computations,
    type_bytes,
)
from repro.analysis.passes import AuditContext, PassResult, register_pass

_COMPUTE_OPS = ("convolution", "dot")
_CALLING_OPS = ("call", "fusion", "while", "conditional")


def _transitive_compute_counts(comps: Dict[str, List[Op]]) -> Dict[str, int]:
    """conv+dot ops per computation, following call/fusion/while bodies
    (counted once, not trip-weighted — presence is what the interleave
    check needs)."""
    memo: Dict[str, int] = {}

    def count(cname: str, seen) -> int:
        if cname in memo:
            return memo[cname]
        if cname in seen:
            return 0
        seen = seen | {cname}
        total = 0
        for op in comps.get(cname, []):
            if op.opcode in _COMPUTE_OPS:
                total += 1
            elif op.opcode in _CALLING_OPS:
                for target in _CALLED_RE.findall(op.attrs):
                    if target in comps:
                        total += count(target, seen)
                bs = _BRANCHES_RE.search(op.attrs)
                if bs:
                    for nm in re.findall(r"%?([\w.\-]+)", bs.group(1)):
                        if nm in comps:
                            total += count(nm, seen)
        memo[cname] = total
        return total

    for cname in comps:
        count(cname, frozenset())
    return memo


def _op_compute_weight(op: Op, memo: Dict[str, int]) -> int:
    if op.opcode in _COMPUTE_OPS:
        return 1
    if op.opcode in _CALLING_OPS:
        total = 0
        for target in _CALLED_RE.findall(op.attrs):
            total += memo.get(target, 0)
        bs = _BRANCHES_RE.search(op.attrs)
        if bs:
            for nm in re.findall(r"%?([\w.\-]+)", bs.group(1)):
                total += memo.get(nm, 0)
        return total
    return 0


def _collective_bytes_of(op: Op, defs: Dict[str, Op]) -> float:
    in_b = sum(type_bytes(defs[o].result) for o in op.operands if o in defs)
    return max(type_bytes(op.result), in_b)


def interleave_report(text: str,
                      min_collective_bytes: int = 512) -> Dict[str, object]:
    """Verify from the *scheduled* HLO whether the gradient collectives
    are interleaved with backward compute or clustered at the tail.

    The XLA text is emitted in scheduled program order, so position is
    evidence: in the non-overlapped step every gradient all-reduce
    depends on the full backward and must sit after the last backward
    convolution/dot; in the overlapped step (DESIGN.md §8) the
    data edges of ``core.compression.after`` pin each bucket's collective
    between backward segments, so substantial conv/dot compute appears
    between the first and last collective and after the first one.

    A program counts as ``interleaved`` when it has >= 2 qualifying
    (>= ``min_collective_bytes``) collectives, at least one conv/dot
    between the first and the last of them, and at least one conv/dot
    after the first one. Tiny metric pmeans fall under the byte floor.
    """
    comps = parse_computations(text)
    comps.pop("__entry__", None)
    memo = _transitive_compute_counts(comps)

    # the computation carrying the gradient sync = the one with the most
    # qualifying collective bytes
    best_name = None
    best_bytes = -1.0
    for cname, ops in comps.items():
        defs = _op_defs(ops)
        tot = 0.0
        for op in ops:
            base = op.opcode[:-6] if op.opcode.endswith("-start") \
                else op.opcode
            if base in COLLECTIVES:
                b = _collective_bytes_of(op, defs)
                if b >= min_collective_bytes:
                    tot += b
        if tot > best_bytes:
            best_bytes, best_name = tot, cname

    if best_name is None or best_bytes <= 0:
        return {"n_collectives": 0, "interleaved": False,
                "reason": "no qualifying collectives"}

    ops = comps[best_name]
    defs = _op_defs(ops)
    coll_pos: List[int] = []
    weights: List[int] = []
    for idx, op in enumerate(ops):
        weights.append(_op_compute_weight(op, memo))
        base = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
        if base in COLLECTIVES and \
                _collective_bytes_of(op, defs) >= min_collective_bytes:
            coll_pos.append(idx)

    total = sum(weights)
    first, last = coll_pos[0], coll_pos[-1]
    after_first = sum(weights[first + 1:])
    between = sum(weights[first + 1:last])
    gaps_with_compute = sum(
        1 for lo, hi in zip(coll_pos, coll_pos[1:])
        if sum(weights[lo + 1:hi]) > 0)
    n = len(coll_pos)
    interleaved = n >= 2 and between >= 1 and after_first >= 1
    return {
        "computation": best_name,
        "n_collectives": n,
        "compute_ops_total": total,
        "compute_ops_before_first": sum(weights[:first]),
        "compute_ops_after_first": after_first,
        "compute_ops_between_first_last": between,
        "gaps_with_compute": gaps_with_compute,
        "interleaved": interleaved,
    }


@register_pass("interleave")
def interleave_pass(ctx: AuditContext) -> PassResult:
    """Pass wrapper: summary = ``interleave_report``; when the driver
    sets ``expectations["require_interleaved"]`` a non-interleaved
    schedule is an error (the overlap modes' contract)."""
    res = PassResult(name="interleave")
    floor = int(ctx.expectations.get("min_collective_bytes", 512))
    rep = interleave_report(ctx.hlo_text, min_collective_bytes=floor)
    res.summary.update(rep)
    if ctx.expectations.get("require_interleaved") and \
            not rep.get("interleaved"):
        res.add("error",
                "gradient collectives are clustered at the tail, not "
                "interleaved with backward compute",
                op=str(rep.get("computation", "")),
                n_collectives=rep.get("n_collectives", 0),
                compute_ops_between_first_last=rep.get(
                    "compute_ops_between_first_last", 0))
    return res
