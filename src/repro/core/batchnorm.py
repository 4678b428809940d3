"""Batch normalization *without moving averages* (paper §2).

The model keeps only the **last minibatch's** statistics as state. Before
validation, those statistics are all-reduced across workers (the paper's
"all-reduce communication on these statistics ... before validation").

Two execution modes:
  * GSPMD jit (default): the batch dim is sharded over the data axes, so
    ``jnp.mean`` over it is already a global (cross-replica) statistic —
    sync-BN comes out of the partitioner for free.
  * Explicit shard_map DP (paper-faithful mode): stats are per-worker;
    ``finalize_bn_stats`` performs the paper's pre-validation all-reduce
    (and is also usable per-step for sync-BN).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def bn_batch_stats(x: jax.Array,
                   cross_replica: Optional[Sequence[str]] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Mean/var over all but the channel (last) axis, fp32 accumulation.

    The variance uses the **centered** form E[(x - mu)^2], not
    E[x^2] - E[x]^2: for a large-mean bf16/fp16 activation the
    uncentered difference cancels almost all significant bits (both
    terms ~mean^2, their gap ~var), while the centered second moment is
    computed on values of magnitude ~sigma and stays accurate — the
    f64-oracle regression in tests/test_core_batchnorm.py pins this.
    The fp32 upcast of ``x - mu`` feeds only the square-reduce, so XLA
    fuses it into the reduction (no fp32 activation copy in HBM).

    ``cross_replica``: axis names when running under shard_map — the
    mean is psum-averaged first, then the per-worker second moments
    about the *global* mean are psum-averaged (sync-BN; equal to the
    statistics of the concatenated global batch). Under GSPMD jit leave
    it None; the partitioner already makes the reduction global.
    """
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
    if cross_replica:
        mean = jax.lax.pmean(mean, cross_replica)
    var = jnp.mean(jnp.square(x.astype(jnp.float32) - mean), axis=axes)
    if cross_replica:
        var = jax.lax.pmean(var, cross_replica)
    return mean, var


def bn_apply_stats(x: jax.Array, mean, var, scale, bias,
                   eps: float = 1e-5) -> jax.Array:
    """Normalize with the per-channel scale/offset folded in fp32: one
    compute-dtype read and one write per element (the upcast fuses into
    the elementwise op — EXPERIMENTS.md §Perf resnet iteration).

    The arithmetic is fp32 even for a bf16 ``x``, so autodiff's
    per-channel reductions for ``scale``/``bias`` (and ``mean``/``var``)
    accumulate in fp32. Multiplying in bf16 instead made those
    reductions run in bf16 under XLA 0.9, several percent off for a few
    hundred elements (tests/test_fused_bn.py bf16 parity matrix, checked
    against an f64 reference)."""
    inv = (jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32))
    off = bias.astype(jnp.float32) - mean * inv
    return (x.astype(jnp.float32) * inv + off).astype(x.dtype)


def _is_stat(node) -> bool:
    """A BN statistics record: dict carrying mean + var leaves."""
    return isinstance(node, dict) and "mean" in node and "var" in node


def _combine_moments(mean_w, var_w, reduce_mean):
    """Average per-worker (mean, var) pairs moment-correctly.

    Averaging variances directly drops the spread of the per-worker
    means; reconstructing E[x^2] = var + mean^2 first makes the combined
    statistics *exactly* the global-minibatch statistics when every
    worker saw an equal shard — which is what makes shard_map-DP eval
    logits match GSPMD eval logits (DESIGN.md §7). ``reduce_mean``
    abstracts over host-side mean (leading worker axis) vs in-program
    pmean.
    """
    mean = reduce_mean(mean_w)
    ex2 = reduce_mean(var_w + jnp.square(mean_w))
    var = jnp.maximum(ex2 - jnp.square(mean), 0.0)
    return mean, var


def _reduce_stats(state: PyTree, reduce) -> PyTree:
    """Apply ``reduce`` to every leaf, combining (mean, var) stat
    records moment-correctly along the way."""

    def combine(d):
        mean, var = _combine_moments(d["mean"], d["var"], reduce)
        out = dict(d)
        out.update(mean=mean, var=var)
        for k in out:
            if k not in ("mean", "var"):
                out[k] = reduce(out[k])
        return out

    def visit(node):
        if _is_stat(node):
            return combine(node)
        return jax.tree.map(reduce, node)

    return jax.tree.map(visit, state, is_leaf=_is_stat)


def combine_worker_bn_stats(state: PyTree) -> PyTree:
    """Paper §2's pre-validation all-reduce, host/jit form: statistics
    carry a leading per-worker axis (the shard_map DP layout); returns
    the global statistics with that axis reduced. ``mean`` leaves are
    plain-averaged; ``var`` leaves are combined via E[x^2] so the result
    equals the statistics of the concatenated (global) minibatch."""
    return _reduce_stats(state, lambda x: jnp.mean(x, axis=0))


def finalize_bn_stats(state: PyTree,
                      axis_names: Optional[Sequence[str]] = None) -> PyTree:
    """The paper's pre-validation all-reduce of last-minibatch statistics.

    Inside shard_map: pmean over ``axis_names`` (moment-correct for
    mean/var stat records, see ``combine_worker_bn_stats``). Under GSPMD
    (or single process) the stats are already global and this is the
    identity — kept as an explicit step so the serving/validation path
    is the same program in both modes.
    """
    if not axis_names:
        return state

    return _reduce_stats(state,
                         lambda leaf: jax.lax.pmean(leaf, axis_names))


def merge_bn_stats(states: Sequence[PyTree]) -> PyTree:
    """Host-side helper: average stats across a list of per-worker states
    (used by elastic restore when re-sharding a checkpoint)."""
    def avg(*leaves):
        return sum(leaves) / len(leaves)

    return jax.tree.map(avg, *states)
