"""ZeRO packed-stream optimizer: the sharded update of the reduce-scatter
sync mode (DESIGN.md §9).

In the ``--zero`` shard_map DP paths every gradient bucket is
``psum_scatter``'d instead of ``psum``'d, so each worker only ever holds
its contiguous 1/N shard of the packed gradient stream. This module owns
what happens to that shard: the optimizer state (``delta``/``m``) lives
as flat arrays in the *shard layout* of the packed stream
(``distributed/bucketing.py:shard_perm``), the hybrid RMSprop-warm-up
update runs elementwise on the shard only (optionally through the fused
Pallas kernel, ``kernels/fused_update.py``), and per-element weight
decay comes from a static ``wd_stream`` built from the same
``_decay_mask`` the tree optimizer uses — which is what makes the
updated parameters bitwise-equal to the replicated tree update
(tests/test_zero.py).

It also provides the checkpoint resharding path: converters between the
tree-layout optimizer state a non-zero run saves and the shard-layout
flat arrays a ``--zero`` run saves, so either can restore the other's
checkpoints (``checkpoint/checkpointer.py:restore(transform=...)``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OptimizerConfig
from repro.core.optimizer import (
    HybridHyper,
    hybrid_update,
    momentum_sgd_update,
)
from repro.core.schedules import alpha_sgd_schedule, make_lr_schedule
from repro.distributed.bucketing import (
    BucketPlan,
    segment_sq_partials,
    shard_layout_to_stream,
    stream_to_shard_layout,
)
from repro.optim.rmsprop_warmup import _decay_mask

PyTree = Any

ZERO_STATE_FIELDS = ("delta", "m")


@dataclasses.dataclass(frozen=True)
class StreamOptimizer:
    """The packed-shard twin of ``optim.interface.Optimizer``.

    ``init(padded_total)`` builds the flat global state (zeros, so the
    shard-layout permutation is irrelevant at init); ``update_shard``
    advances one worker's contiguous shard; ``wd_stream`` bakes the
    per-element weight-decay vector for a plan-structured tree.
    """

    init: Callable[[int], PyTree]
    # rmsprop_warmup: (p, g, delta, m, step, wd) -> (p', d', m', metrics)
    # lars:           (p, g, delta, step, wd, seg, trust) -> (p', d', metrics)
    update_shard: Callable
    wd_stream: Callable  # (tree matching plan.treedef, plan) -> np.f32[padded]
    kind: str
    state_fields: Tuple[str, ...] = ZERO_STATE_FIELDS
    # stream-LARS only (None for rmsprop_warmup): per-segment [p^2,
    # (g+wd*p)^2] partial sums over a locally-held slice, and the trust
    # vector from the psum'd totals. The psum between them belongs to
    # the caller (training/step.py) — the optimizer stays collective-free
    # so the same code runs on a ZeRO shard or the full stream.
    segment_partials: Optional[Callable] = None
    trust_ratios: Optional[Callable] = None


def make_stream_optimizer(cfg: OptimizerConfig, steps_per_epoch: int,
                          global_batch: int,
                          use_fused: bool = False) -> StreamOptimizer:
    """Packed-stream optimizers: ``rmsprop_warmup`` (the same
    ``core.optimizer.hybrid_update`` formula applied to the flat shard —
    elementwise, so position in the stream cannot change any value; the
    only per-leaf input, the decay mask, rides along as ``wd_stream``)
    and ``lars`` (elementwise update plus per-segment trust norms,
    DESIGN.md §11)."""
    if cfg.kind == "lars":
        return _make_stream_lars(cfg, steps_per_epoch, global_batch,
                                 use_fused)
    if cfg.kind == "momentum_sgd":
        return _make_stream_momentum_sgd(cfg, steps_per_epoch,
                                         global_batch)
    if cfg.kind != "rmsprop_warmup":
        raise ValueError(
            f"the packed stream shards the rmsprop_warmup, momentum_sgd "
            f"and lars updates; got optimizer kind {cfg.kind!r}")
    lr_fn = make_lr_schedule(cfg.schedule, global_batch,
                             base_lr_per_256=cfg.base_lr_per_256,
                             warmup_epochs=cfg.warmup_epochs)
    state_dtype = jnp.dtype(cfg.state_dtype)

    def init(padded_total: int) -> PyTree:
        return {
            "step": jnp.zeros((), jnp.int32),
            "delta": jnp.zeros((padded_total,), state_dtype),
            "m": jnp.zeros((padded_total,), state_dtype),
        }

    def update_shard(p_shard, g_shard, delta_shard, m_shard, step,
                     wd_shard):
        """One hybrid update on the worker-owned shard. ``wd_shard`` is
        the per-element weight decay (0.0 on no-decay leaves and on the
        alignment pad, whose g=0/m=0 elements stay exactly zero)."""
        epoch = step.astype(jnp.float32) / steps_per_epoch
        eta = lr_fn(epoch)
        a_sgd = alpha_sgd_schedule(epoch, cfg.beta_center, cfg.beta_period,
                                   kind=cfg.transition)
        h = HybridHyper(eta=eta, alpha_sgd=a_sgd, mu1=cfg.mu1, mu2=cfg.mu2,
                        eps=cfg.eps, eta_rmsprop=cfg.eta_rmsprop)
        d32 = delta_shard.astype(jnp.float32)
        m32 = m_shard.astype(jnp.float32)
        if use_fused:
            from repro.kernels import ops as kops

            p_new, d_new, m_new = kops.fused_hybrid_update(
                g_shard, p_shard, d32, m32, h, wd_shard)
        else:
            p_new, d_new, m_new = hybrid_update(g_shard, p_shard, d32, m32,
                                                h, wd_shard)
        metrics = {"lr": eta, "alpha_sgd": a_sgd, "epoch": epoch}
        return (p_new, d_new.astype(state_dtype), m_new.astype(state_dtype),
                metrics)

    def wd_stream(tree: PyTree, plan: BucketPlan) -> np.ndarray:
        return decay_wd_stream(tree, plan, cfg.weight_decay)

    return StreamOptimizer(init=init, update_shard=update_shard,
                           wd_stream=wd_stream, kind=cfg.kind)


def _make_stream_momentum_sgd(cfg: OptimizerConfig, steps_per_epoch: int,
                              global_batch: int) -> StreamOptimizer:
    """Stream-layout momentum SGD — the Goyal baseline sharded over the
    packed stream so ``--zero`` runs it too (the audit matrix lowers
    every mode x optimizer cell). Same ``update_shard`` signature as the
    rmsprop_warmup stream — ``m`` rides along untouched (zeros) so the
    ZeRO caller's state plumbing is identical — and the math is
    ``core.optimizer.momentum_sgd_update`` with the decay applied
    elementwise: ``wd_shard`` is 0.0 off the decay set, and adding
    ``0.0 * p`` is value-neutral, so the parameters match the
    replicated tree update exactly (tests/test_audit.py)."""
    lr_fn = make_lr_schedule("goyal" if cfg.schedule == "goyal" else
                             cfg.schedule, global_batch,
                             base_lr_per_256=cfg.base_lr_per_256,
                             warmup_epochs=cfg.warmup_epochs,
                             total_epochs=cfg.total_epochs,
                             poly_power=cfg.poly_power)
    state_dtype = jnp.dtype(cfg.state_dtype)

    def init(padded_total: int) -> PyTree:
        return {
            "step": jnp.zeros((), jnp.int32),
            "delta": jnp.zeros((padded_total,), state_dtype),
            "m": jnp.zeros((padded_total,), state_dtype),
        }

    def update_shard(p_shard, g_shard, delta_shard, m_shard, step,
                     wd_shard):
        epoch = step.astype(jnp.float32) / steps_per_epoch
        eta = lr_fn(epoch)
        h = HybridHyper(eta=eta, alpha_sgd=jnp.float32(1.0), mu1=cfg.mu1)
        p_new, d_new = momentum_sgd_update(
            g_shard, p_shard, delta_shard.astype(jnp.float32), h, wd_shard)
        metrics = {"lr": eta, "epoch": epoch}
        return (p_new, d_new.astype(state_dtype),
                m_shard.astype(state_dtype), metrics)

    def wd_stream(tree: PyTree, plan: BucketPlan) -> np.ndarray:
        return decay_wd_stream(tree, plan, cfg.weight_decay)

    return StreamOptimizer(init=init, update_shard=update_shard,
                           wd_stream=wd_stream, kind=cfg.kind)


def _make_stream_lars(cfg: OptimizerConfig, steps_per_epoch: int,
                      global_batch: int,
                      use_fused: bool) -> StreamOptimizer:
    """Stream-layout LARS (DESIGN.md §11). Trust ratios need per-leaf
    norms over the *whole* stream, so the update splits in three:
    ``segment_partials`` reduces whatever slice this worker holds (the
    full stream, or a ZeRO shard — a leaf may span shard boundaries) to
    per-segment squared-norm partial sums; the caller psums the (2, L+1)
    partials over the DP axes; ``trust_ratios`` turns the totals into
    the per-segment trust vector; and ``update_shard`` applies the
    trust-scaled momentum step elementwise. Identical programs on a
    shard and on the full stream — which is what keeps all four sync
    modes in lockstep (tests/test_lars_stream.py)."""
    from repro.optim.lars import trust_from_sq

    lr_fn = make_lr_schedule(cfg.schedule, global_batch,
                             base_lr_per_256=cfg.base_lr_per_256,
                             warmup_epochs=cfg.warmup_epochs,
                             total_epochs=cfg.total_epochs,
                             poly_power=cfg.poly_power)
    state_dtype = jnp.dtype(cfg.state_dtype)

    def init(padded_total: int) -> PyTree:
        return {"step": jnp.zeros((), jnp.int32),
                "delta": jnp.zeros((padded_total,), state_dtype)}

    def segment_partials(p_loc, g_loc, wd_loc, seg_loc, num_segments):
        if use_fused:
            from repro.kernels import ops as kops
            return kops.fused_segment_sq_partials(p_loc, g_loc, wd_loc,
                                                  seg_loc, num_segments)
        p32 = p_loc.astype(jnp.float32)
        g_eff = g_loc.astype(jnp.float32) + wd_loc * p32
        return jnp.stack([segment_sq_partials(p32, seg_loc, num_segments),
                          segment_sq_partials(g_eff, seg_loc,
                                              num_segments)])

    def trust_ratios(totals, trust_mask):
        """(L+1,) trust from the psum'd (2, L+1) totals; 1.0 on masked
        segments (bias/BN leaves, the alignment pad)."""
        return trust_from_sq(totals[0], totals[1], cfg.trust_coef,
                             trust_mask)

    def update_shard(p_loc, g_loc, delta_loc, step, wd_loc, seg_loc,
                     trust):
        """One trust-scaled momentum step on the locally-held slice.
        Pad elements sit in segment L with wd=0/g=0/delta=0 and stay
        exactly zero forever."""
        epoch = step.astype(jnp.float32) / steps_per_epoch
        eta = lr_fn(epoch)
        d32 = delta_loc.astype(jnp.float32)
        if use_fused:
            from repro.kernels import ops as kops
            p_new, d_new = kops.fused_lars_update(
                g_loc, p_loc, d32, wd_loc, seg_loc, trust, eta, cfg.mu1)
        else:
            p32 = p_loc.astype(jnp.float32)
            g_eff = g_loc.astype(jnp.float32) + wd_loc * p32
            d_new = cfg.mu1 * d32 - trust[seg_loc] * g_eff
            p_new = (p32 + eta * d_new).astype(p_loc.dtype)
        metrics = {"lr": eta, "epoch": epoch}
        return p_new, d_new.astype(state_dtype), metrics

    def wd_stream(tree: PyTree, plan: BucketPlan) -> np.ndarray:
        return decay_wd_stream(tree, plan, cfg.weight_decay)

    return StreamOptimizer(init=init, update_shard=update_shard,
                           wd_stream=wd_stream, kind="lars",
                           state_fields=("delta",),
                           segment_partials=segment_partials,
                           trust_ratios=trust_ratios)


def trust_mask_segments(tree: PyTree, plan: BucketPlan) -> np.ndarray:
    """bool[len(slots) + 1]: True where a stream segment participates in
    the LARS trust ratio. The exemption set is exactly the no-decay set
    (``_decay_mask``: bias/BN leaves), per You et al.; the trailing
    alignment-pad segment is always exempt."""
    mask_leaves = plan.treedef.flatten_up_to(_decay_mask(tree))
    assert len(mask_leaves) == len(plan.slots)
    return np.asarray(list(mask_leaves) + [False], bool)


def zero_padded_total(params: PyTree, compression: str,
                      bucket_bytes: int, n_workers: int) -> int:
    """Length of the flat shard-layout optimizer state for a --zero run:
    total param elements + the shard-alignment tail. One definition of
    the layout rule, shared by launch/train.py and launch/dryrun.py —
    the padded length depends only on these scalars, never on leaf
    order, so the plain and ready-order (overlap) layouts agree.
    ``params`` may be arrays or ShapeDtypeStructs."""
    from repro.core.compression import _wire, parse_compression
    from repro.distributed.bucketing import stream_layout

    wire_name, bucketed = parse_compression(compression)
    if not bucketed:
        raise ValueError(
            "--zero reduce-scatters packed buckets: use a bucketed "
            f"compression spec (got {compression!r}, e.g. "
            "'bf16+bucketed'; DESIGN.md §9)")
    wdt = _wire(wire_name)
    itemsize = (jnp.dtype(wdt).itemsize if wdt is not None
                else jnp.dtype(jnp.float32).itemsize)
    total = sum(v.size for v in jax.tree.leaves(params))
    _, _, pad = stream_layout(total, bucket_bytes, itemsize,
                              align=n_workers)
    return total + pad


def decay_wd_stream(tree: PyTree, plan: BucketPlan,
                    weight_decay: float) -> np.ndarray:
    """Static per-element weight-decay vector for the packed stream:
    ``weight_decay`` on decayed leaves, 0.0 on ``NO_DECAY`` leaves and on
    the shard-alignment pad. ``tree`` must match ``plan.treedef`` (the
    full param tree for plain plans, the ready-ordered tuple of stage
    trees for overlap plans — leaf key names, hence the mask, are
    identical either way)."""
    mask_leaves = plan.treedef.flatten_up_to(_decay_mask(tree))
    assert len(mask_leaves) == len(plan.slots)
    wd = np.zeros((plan.padded_total,), np.float32)
    for slot, decayed in zip(plan.slots, mask_leaves):
        if decayed:
            wd[slot.offset:slot.offset + slot.size] = weight_decay
    return wd


# ---------------------------------------------------------------------------
# Checkpoint resharding (zero <-> tree optimizer-state layout)
# ---------------------------------------------------------------------------
#
# A non-zero run checkpoints opt state as one array per param leaf
# ("['opt']['delta']['stem']['conv']", ...); a --zero run checkpoints one
# flat shard-layout array per field ("['opt']['delta']"). The converters
# below rewrite a loaded checkpoint's array dict from either layout into
# the other, keyed by the *original param keystrs* in plan-slot order —
# plug them into ``checkpoint.restore(transform=...)``.


def param_key_tree(params: PyTree) -> PyTree:
    """Tree of the same structure whose leaves are each param's keystr
    (e.g. "['stem']['conv']") — the suffix every opt-state checkpoint
    key carries after "['opt']['<field>']"."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [jax.tree_util.keystr(p) for p, _ in flat])


def _slot_keys(plan: BucketPlan, key_tree: PyTree):
    keys = plan.treedef.flatten_up_to(key_tree)
    assert len(keys) == len(plan.slots)
    return keys


def zero_state_to_tree_arrays(arrays: Dict[str, np.ndarray],
                              plan: BucketPlan, key_tree: PyTree,
                              n_shards: int,
                              fields: Tuple[str, ...] = ZERO_STATE_FIELDS
                              ) -> Dict[str, np.ndarray]:
    """Rewrite a --zero checkpoint's flat shard-layout opt fields into
    per-leaf tree-layout arrays (the non-zero checkpoint schema)."""
    out = dict(arrays)
    keys = _slot_keys(plan, key_tree)
    for f in fields:
        flat_key = f"['opt']['{f}']"
        if flat_key not in out:
            raise KeyError(f"checkpoint has no shard-layout field "
                           f"{flat_key!r}; is it a --zero checkpoint?")
        stream = shard_layout_to_stream(out.pop(flat_key), plan, n_shards)
        for slot, key in zip(plan.slots, keys):
            out[flat_key + key] = stream[
                slot.offset:slot.offset + slot.size].reshape(slot.shape)
    return out


def tree_arrays_to_zero_state(arrays: Dict[str, np.ndarray],
                              plan: BucketPlan, key_tree: PyTree,
                              n_shards: int,
                              fields: Tuple[str, ...] = ZERO_STATE_FIELDS
                              ) -> Dict[str, np.ndarray]:
    """Rewrite a non-zero checkpoint's per-leaf opt fields into the flat
    shard-layout arrays a --zero run restores (pad tail = zeros, exactly
    the state the padding elements hold forever)."""
    out = dict(arrays)
    keys = _slot_keys(plan, key_tree)
    for f in fields:
        flat_key = f"['opt']['{f}']"
        parts = []
        for slot, key in zip(plan.slots, keys):
            leaf_key = flat_key + key
            if leaf_key not in out:
                raise KeyError(f"checkpoint missing {leaf_key!r}; is it "
                               "a tree-layout (non-zero) checkpoint?")
            parts.append(np.asarray(out.pop(leaf_key)).reshape(-1))
        stream = np.concatenate(parts)
        if plan.pad_elems:
            stream = np.concatenate(
                [stream, np.zeros((plan.pad_elems,), stream.dtype)])
        out[flat_key] = stream_to_shard_layout(stream, plan, n_shards)
    return out


def make_zero_restore_transform(plan: BucketPlan, key_tree: PyTree,
                                n_shards: int, to_zero: bool,
                                fields: Tuple[str, ...] = ZERO_STATE_FIELDS):
    """A ``checkpoint.restore(transform=...)`` hook crossing the
    zero/non-zero boundary: ``to_zero=True`` reshapes a tree-layout
    checkpoint for a --zero target, ``False`` the reverse. ``fields``
    names the flat opt-state arrays to convert — ``("delta", "m")`` for
    rmsprop_warmup, ``("delta",)`` for LARS (``optimizer.state_fields``)."""
    def transform(arrays, manifest):
        del manifest
        fn = (tree_arrays_to_zero_state if to_zero
              else zero_state_to_tree_arrays)
        return fn(arrays, plan, key_tree, n_shards, fields=fields)

    return transform
