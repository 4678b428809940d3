"""Half-precision gradient communication (paper §3) + error feedback.

The paper casts gradients to fp16 for the NCCL all-reduce and observed a
negligible accuracy effect. TPU adaptation (DESIGN.md §2): bf16 is the
default wire format (fp32 exponent range => no loss scaling), fp16 is
available for paper-faithfulness.

Three sync modes (selected by ``ParallelConfig.compression``):
  * ``compressed_psum`` — explicit shard_map DP mode (``"bf16"``/``"f16"``):
    cast -> psum -> cast, one collective per gradient leaf — exactly the
    paper's mechanism.
  * bucketed (``"bf16+bucketed"`` etc., DESIGN.md §6) — the per-leaf cast
    feeds ``distributed/bucketing.py``, which packs the gradient stream
    into fixed-size contiguous buckets and issues one collective per
    bucket instead of one per leaf.
  * ``simulate_wire_cast`` — GSPMD mode: gradients are cast to the wire
    dtype and back *at the sync boundary*, so the numerics match the
    compressed collective even when XLA chooses where the all-reduce
    lives. The dry-run HLO parse reports actual collective dtypes.

Beyond paper: error feedback (residual accumulation) removes the bias of
repeated rounding at very large scale; ``compressed_psum_ef`` threads the
residuals through either explicit sync path (the bucketed variant lives
in ``distributed/bucketing.py``).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

WIRE_DTYPES = {"bf16": jnp.bfloat16, "f16": jnp.float16, None: None,
               "none": None}


def _wire(dtype_name: Optional[str]):
    if dtype_name not in WIRE_DTYPES:
        raise ValueError(f"unknown wire dtype {dtype_name}")
    return WIRE_DTYPES[dtype_name]


def parse_compression(spec: Optional[str]) -> Tuple[Optional[str], bool]:
    """Split a ``ParallelConfig.compression`` string into
    ``(wire_dtype_name, bucketed)``.

    ``None``/"none" -> (None, False); "bf16" -> ("bf16", False);
    "bf16+bucketed" -> ("bf16", True); "bucketed" -> (None, True) —
    bucketing without a wire cast still fuses the per-leaf collectives.
    """
    if spec is None:
        return None, False
    wire: Optional[str] = None
    bucketed = False
    seen_wire = False
    for part in spec.split("+"):
        if part == "bucketed":
            if bucketed:
                raise ValueError(f"duplicate 'bucketed' in {spec!r}")
            bucketed = True
        elif part in WIRE_DTYPES:
            if seen_wire:
                raise ValueError(
                    f"conflicting wire dtypes in {spec!r}")
            seen_wire = True
            wire = None if part == "none" else part
        else:
            raise ValueError(f"unknown compression spec part {part!r} "
                             f"in {spec!r}")
    return wire, bucketed


def after(x: jax.Array, dep: jax.Array) -> jax.Array:
    """``x`` with every value unchanged, made data-dependent on ``dep``.

    Subtracting +0.0 is the identity for every ``x`` (-0.0 included),
    and the +0.0 is computed from one element of ``dep``, so whatever
    consumes the result cannot run before ``dep`` exists. This is how
    the explicit sync modes order their per-leaf and per-bucket
    collectives: an ``optimization_barrier`` alone no longer does it,
    because XLA's CPU pipeline expands barriers before its all-reduce
    combiner, which then merges every independent collective into
    one."""
    d = dep.reshape(-1)[0].astype(jnp.float32)
    zero = jnp.where(jnp.isfinite(d), jnp.abs(d) * 0.0, 0.0)
    return x - zero.astype(x.dtype)


def chained(parts: Sequence[jax.Array], collective) -> List[jax.Array]:
    """``collective`` applied part by part (leaves or buckets), each
    input ``after`` the previous result: exactly one collective per
    part, in order, which no combiner can merge."""
    out: List[jax.Array] = []
    for x in parts:
        out.append(collective(after(x, out[-1]) if out else x))
    return out


def compressed_psum(grads: PyTree, axis_names: Sequence[str],
                    wire: Optional[str] = "bf16",
                    mean: bool = True) -> PyTree:
    """Paper-faithful compressed all-reduce (shard_map mode).

    Cast each gradient leaf to the wire dtype, psum over the data axes
    (one collective per leaf, ``chained`` in leaf order), cast back to
    the accumulation dtype. ``mean=True`` divides by the number of
    workers (the paper averages per-worker gradients).
    """
    wdt = _wire(wire)
    n = jax.lax.axis_size(tuple(axis_names))  # static worker count

    leaves, treedef = jax.tree.flatten(grads)
    wired = [g.astype(wdt) if wdt is not None else g for g in leaves]
    summed = chained(wired, lambda g: jax.lax.psum(g, tuple(axis_names)))
    out = []
    for g, s in zip(leaves, summed):
        s = s.astype(g.dtype)
        out.append(s / n if mean else s)
    return jax.tree.unflatten(treedef, out)


def simulate_wire_cast(grads: PyTree, wire: Optional[str] = "bf16") -> PyTree:
    """GSPMD mode: round-trip gradients through the wire dtype so the
    numerics of compressed communication are applied.

    The round trip sits behind an ``optimization_barrier``: XLA's TPU
    compiler otherwise folds ``convert(convert(g, f16), f32)`` to ``g``
    (excess precision is allowed by default), and the GSPMD step then
    trained on f32 gradients while the shard_map step rounded them to
    f16 — measured on a TPU v5e, PERF.md."""
    wdt = _wire(wire)
    if wdt is None:
        return grads
    return jax.tree.map(
        lambda g: jax.lax.optimization_barrier(g.astype(wdt)).astype(
            g.dtype), grads)


# ---------------------------------------------------------------------------
# Error feedback (beyond paper)
# ---------------------------------------------------------------------------


def init_error_feedback(grads: PyTree) -> PyTree:
    return jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)


def apply_error_feedback(grads: PyTree, residual: PyTree,
                         wire: str = "bf16") -> Tuple[PyTree, PyTree]:
    """q = Q(g + r);  r' = (g + r) - q.  Returns (quantized, new_residual)."""
    wdt = _wire(wire)

    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        q = corrected.astype(wdt).astype(jnp.float32)
        return q.astype(g.dtype), corrected - q

    pairs = jax.tree.map(one, grads, residual)
    quant = jax.tree.map(lambda t: t[0], pairs,
                         is_leaf=lambda x: isinstance(x, tuple))
    resid = jax.tree.map(lambda t: t[1], pairs,
                         is_leaf=lambda x: isinstance(x, tuple))
    return quant, resid


def compressed_psum_ef(grads: PyTree, residual: PyTree,
                       axis_names: Sequence[str], wire: str = "bf16",
                       mean: bool = True) -> Tuple[PyTree, PyTree]:
    """Per-leaf compressed psum with error feedback threaded through.

    The residual update is worker-local (it sees the *local* gradient, so
    every worker's rounding error is corrected on its next step); only
    the wire-rounded value crosses the interconnect. The subsequent wire
    cast inside ``compressed_psum`` is exact because ``q`` is already
    wire-representable.
    """
    quant, new_residual = apply_error_feedback(grads, residual, wire)
    synced = compressed_psum(quant, axis_names, wire, mean=mean)
    return synced, new_residual


def compression_error(grads: PyTree, wire: str = "bf16") -> jax.Array:
    """Relative L2 rounding error of the wire cast — logged as a training
    metric so the paper's 'effect ... was relatively small' claim is
    checkable per run."""
    def err(g):
        g32 = g.astype(jnp.float32)
        q = g32.astype(_wire(wire)).astype(jnp.float32)
        return jnp.sum(jnp.square(q - g32)), jnp.sum(jnp.square(g32))

    num = sum(jax.tree.leaves(jax.tree.map(lambda g: err(g)[0], grads)))
    den = sum(jax.tree.leaves(jax.tree.map(lambda g: err(g)[1], grads)))
    return jnp.sqrt(num / jnp.maximum(den, 1e-30))
