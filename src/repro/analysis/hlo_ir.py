"""Typed HLO IR: parse XLA's compiled-program text into computations and
ops, and back.

XLA emits the optimized module in *scheduled program order*: column-0
lines open computations (headers may wrap), indented lines are ops, a
column-0 ``}`` closes. This module owns the grammar — every analysis
pass (DESIGN.md §12) reads the IR built here rather than regexing raw
text itself:

- ``parse_computations``  name -> [Op] (plus an ``__entry__`` alias)
- ``parse_module``        adds the header facts: entry name, the
                          ``input_output_alias`` map (buffer donation),
                          lazy per-computation defs and trip-count
                          multipliers
- ``render_op``           one op back to canonical text; parse -> render
                          -> parse is identity on the structured fields
                          (property-tested in tests/test_properties.py)
- ``compute_multipliers`` trip-count weighting through (possibly nested)
                          while loops — XLA's own cost_analysis counts
                          loop bodies ONCE (verified in this container)

The type table is deliberately strict-able: ``type_bytes(..., strict=
True)`` raises on a dtype token it does not know instead of silently
sizing it as 0 bytes (the seed-era bug for ``f8e4m3[...]``).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# Bytes per element. Sub-byte types (s4/u4/f4) are fractional — XLA
# packs two per byte — so ``type_bytes`` returns a float. ``token`` and
# ``opaque`` occupy no HBM.
DTYPE_BYTES = {
    "pred": 1, "s2": 0.25, "u2": 0.25, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    # the full f8/f4 family (StableHLO names); the seed table knew only
    # f8e4m3fn/f8e5m2 and silently sized the rest as 0 bytes
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 0.5,
    "token": 0, "opaque": 0,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result: str  # raw type string
    operands: List[str]
    attrs: str  # everything after "opcode(" (operands + attributes)
    root: bool = False
    # structured split of ``attrs`` (renderer inputs): the operand list
    # up to the matching close paren, and the raw attribute tail after it
    args_raw: str = ""
    suffix: str = ""


_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def type_bytes(type_str: str, strict: bool = False) -> float:
    """Bytes of a (possibly tuple) HLO type string.

    ``strict=True`` raises ValueError on a dtype token missing from
    ``DTYPE_BYTES`` instead of skipping it — silently sizing an unknown
    dtype as 0 bytes is exactly how mixed-precision regressions hide.
    """
    total = 0.0
    for dtype, dims in _TYPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            if strict:
                raise ValueError(
                    f"unknown HLO dtype {dtype!r} in {type_str!r}; add it "
                    "to repro.analysis.hlo_ir.DTYPE_BYTES")
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def type_shape(type_str: str) -> Tuple[str, Tuple[int, ...]]:
    m = _TYPE_RE.search(type_str)
    if not m:
        return ("", ())
    dims = tuple(int(d) for d in m.group(2).split(",")) if m.group(2) else ()
    return m.group(1), dims


# result types may carry TPU tiled layouts, e.g. f32[8,128]{1,0:T(8,128)}
_OP_RE = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"((?:\((?:[^()]|\([^()]*\))*\))|(?:[\w\[\],{}.:()]+))\s+"
    r"([\w\-]+)\((.*)$"
)


_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")


def _split_args(rest: str) -> Tuple[str, str]:
    """Split the text after ``opcode(`` into (args, suffix): args is the
    operand list up to the matching close paren, suffix the raw tail
    after it (leading ``, `` kept). Falls back to ``(rest, "")`` when the
    parens never balance (string literals inside constants)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def parse_op_line(line: str) -> Optional[Op]:
    """One indented op line -> Op, or None if the line is not an op."""
    m = _OP_RE.match(line)
    if not m:
        return None
    root, name, rtype, opcode, rest = m.groups()
    args_raw, suffix = _split_args(rest)
    # operands: the %names inside the argument list
    operands = re.findall(r"%([\w.\-]+)", args_raw)
    return Op(name=name, opcode=opcode, result=rtype, operands=operands,
              attrs=rest, root=bool(root), args_raw=args_raw,
              suffix=suffix)


def render_op(op: Op) -> str:
    """Canonical text of one op; ``parse_op_line(render_op(op))``
    reproduces every structured field (the roundtrip property test)."""
    head = "ROOT " if op.root else ""
    return (f"  {head}%{op.name} = {op.result} "
            f"{op.opcode}({op.args_raw}){op.suffix}")


def parse_computations(text: str) -> Dict[str, List[Op]]:
    """Column-0 lines open computations (headers may wrap over several
    lines); indented lines are ops; a column-0 '}' closes. The ENTRY
    computation is additionally aliased as ``"__entry__"``."""
    comps: Dict[str, List[Op]] = {}
    current: Optional[str] = None
    entry_marked: Optional[str] = None
    for line in text.splitlines():
        if line.startswith("}"):
            current = None
            continue
        if line and not line[0].isspace():
            m = _HEADER_RE.match(line)
            if m:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry_marked = current
            continue
        if current is None:
            continue
        op = parse_op_line(line)
        if op is not None:
            comps[current].append(op)
    if entry_marked:
        comps["__entry__"] = comps[entry_marked]
    return comps


def _op_defs(ops: List[Op]) -> Dict[str, Op]:
    return {o.name: o for o in ops}


def op_consumers(ops: List[Op]) -> Dict[str, List[Op]]:
    """name -> the ops (same computation) that consume it as an operand."""
    users: Dict[str, List[Op]] = defaultdict(list)
    for op in ops:
        for o in op.operands:
            users[o].append(op)
    return dict(users)


def _trip_count(cond_ops: List[Op]) -> int:
    """Trip count heuristic: the max scalar s32/u32/s64 constant in the
    loop-condition computation (jax scans compare a counter against the
    length constant)."""
    best = 1
    for o in cond_ops:
        if o.opcode != "constant":
            continue
        dtype, dims = type_shape(o.result)
        if dims != () or dtype not in ("s32", "u32", "s64", "u64"):
            continue
        m = re.search(r"constant\((-?\d+)\)", "constant(" + o.attrs)
        if m:
            best = max(best, int(m.group(1)))
    return best


_CALLED_RE = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def compute_multipliers(comps: Dict[str, List[Op]]
                        ) -> Tuple[Dict[str, float], Dict[str, int]]:
    entry = comps.get("__entry__")
    if entry is None:  # fall back: last computation is usually ENTRY
        entry_name = list(comps)[-1]
    else:
        entry_name = [k for k, v in comps.items()
                      if v is entry and k != "__entry__"][0]
    mult: Dict[str, float] = defaultdict(float)
    mult[entry_name] = 1.0
    trips: Dict[str, int] = {}

    # iterate to fixpoint (call graph is a DAG; few passes suffice)
    for _ in range(20):
        changed = False
        new_mult = defaultdict(float)
        new_mult[entry_name] = 1.0
        for cname, ops in comps.items():
            if cname == "__entry__" or mult.get(cname, 0) == 0:
                continue
            m_c = mult[cname]
            for op in ops:
                if op.opcode == "while":
                    body = cond = None
                    bm = re.search(r"body=%?([\w.\-]+)", op.attrs)
                    cm = re.search(r"condition=%?([\w.\-]+)", op.attrs)
                    if bm:
                        body = bm.group(1)
                    if cm:
                        cond = cm.group(1)
                    trip = _trip_count(comps.get(cond, [])) if cond else 1
                    if body:
                        trips[body] = trip
                        new_mult[body] += m_c * trip
                    if cond:
                        new_mult[cond] += m_c * (trip + 1)
                elif op.opcode == "conditional":
                    bs = _BRANCHES_RE.search(op.attrs)
                    names = []
                    if bs:
                        names = re.findall(r"%?([\w.\-]+)", bs.group(1))
                    for nm in names:
                        new_mult[nm] += m_c  # upper bound: every branch
                else:
                    for target in _CALLED_RE.findall(op.attrs):
                        if target in comps and target != cname:
                            new_mult[target] += m_c
        if dict(new_mult) != {k: v for k, v in mult.items() if v}:
            changed = True
        mult = new_mult
        if not changed:
            break
    return dict(mult), trips


# ---------------------------------------------------------------------------
# Module-level facts (header + entry computation)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AliasEntry:
    """One ``input_output_alias`` record: output tuple index <- (param
    number, param tuple index), may- or must-alias."""

    output_index: Tuple[int, ...]
    param_number: int
    param_index: Tuple[int, ...]
    kind: str


_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{([\d,\s]*)\}\s*,"
    r"\s*(may-alias|must-alias)\s*\)")


def _index_tuple(s: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in s.replace(" ", "").split(",") if x)


def parse_input_output_alias(text: str) -> List[AliasEntry]:
    """The module header's donation map. Post-SPMD compiled text carries
    it as ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` on the
    ``HloModule`` line; absent entirely when nothing was donated."""
    start = text.find("input_output_alias={")
    if start < 0:
        return []
    body = text[start + len("input_output_alias={"):]
    depth = 1
    for i, ch in enumerate(body):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                body = body[:i]
                break
    return [AliasEntry(output_index=_index_tuple(out),
                       param_number=int(pnum),
                       param_index=_index_tuple(pidx), kind=kind)
            for out, pnum, pidx, kind in _ALIAS_ENTRY_RE.findall(body)]


@dataclasses.dataclass
class HloModule:
    """Parsed module: computations + the header facts the passes need.

    ``multipliers``/``trip_counts`` are computed lazily once (they walk
    the call graph to fixpoint)."""

    text: str
    computations: Dict[str, List[Op]]  # no "__entry__" alias key
    entry_name: str
    input_output_alias: List[AliasEntry]
    _mult: Optional[Dict[str, float]] = None
    _trips: Optional[Dict[str, int]] = None

    @property
    def entry_ops(self) -> List[Op]:
        return self.computations[self.entry_name]

    @property
    def multipliers(self) -> Dict[str, float]:
        if self._mult is None:
            comps = dict(self.computations)
            comps["__entry__"] = comps[self.entry_name]
            self._mult, self._trips = compute_multipliers(comps)
        return self._mult

    @property
    def trip_counts(self) -> Dict[str, int]:
        self.multipliers
        return self._trips

    def defs(self, cname: str) -> Dict[str, Op]:
        return _op_defs(self.computations[cname])

    def entry_params(self) -> List[Tuple[int, Op]]:
        """(parameter number, op) for the entry computation, sorted by
        number — jax numbers them in flattened (state, batch) argument
        order, which is what the donation audit keys on."""
        out = []
        for op in self.entry_ops:
            if op.opcode != "parameter":
                continue
            m = re.match(r"\s*(\d+)", op.args_raw)
            if m:
                out.append((int(m.group(1)), op))
        out.sort(key=lambda t: t[0])
        return out


def parse_module(text: str) -> HloModule:
    comps = parse_computations(text)
    entry = comps.pop("__entry__", None)
    if entry is not None:
        entry_name = next(k for k, v in comps.items() if v is entry)
    elif comps:
        entry_name = list(comps)[-1]
    else:
        raise ValueError("no computations found in HLO text")
    return HloModule(text=text, computations=comps, entry_name=entry_name,
                     input_output_alias=parse_input_output_alias(text))
