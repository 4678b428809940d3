"""Step builders.

Gradient-sync modes (``ParallelConfig.compression`` selects the wire
format; ``dp_mode`` selects the mechanism):
  * GSPMD (default): jit + NamedShardings; XLA inserts TP/FSDP/DP
    collectives from the logical-axis rules. Gradient "wire" compression
    is applied at the sync boundary (core/compression.py, DESIGN.md §2)
    and the dry-run verifies the resulting collective dtypes from the
    HLO.
  * shard_map DP per-leaf (paper-faithful): explicit per-worker fwd/bwd,
    explicit half-precision psum per gradient leaf (the paper's
    mechanism, DESIGN.md §2), replicated optimizer — the structure of
    ChainerMN's all-reduce data parallelism.
  * shard_map DP bucketed (``compression="bf16+bucketed"``): same step,
    but the gradient stream is packed into fixed-size contiguous buckets
    and all-reduced one bucket at a time
    (distributed/bucketing.py, DESIGN.md §6) — numerically identical to
    per-leaf, with ~leaf-count fewer collectives. Error-feedback
    residuals (``ParallelConfig.error_feedback``) thread through either
    explicit path.
  * shard_map DP overlapped (``ParallelConfig.overlap_comm``): the
    backward pass is split into per-segment VJPs (models expose
    ``loss_segments``) and each ready-order bucket's psum is launched
    the moment the bucket's last gradient leaf materializes, pipelined
    one segment deep so communication hides behind the remaining
    backward compute (DESIGN.md §8). Bitwise-identical gradients to the
    non-overlapped bucketed path.
  * shard_map DP ZeRO (``ParallelConfig.zero_dp``, ``--zero``): each
    packed bucket is **reduce-scattered** (``psum_scatter``) instead of
    all-reduced, the optimizer update runs only on the worker-owned
    contiguous shard of the stream (delta/m sharded over the DP axis,
    optim/stream.py), and the updated parameter slices are all-gathered
    back — roughly half the wire volume and 1/N the update FLOPs/state
    memory, bitwise-identical end state (DESIGN.md §9). Composes with
    both the plain bucketed path and the overlapped path (the scatter
    launches between segment VJPs in the same pipeline).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.core.compression import (
    compressed_psum,
    compressed_psum_ef,
    parse_compression,
    simulate_wire_cast,
)
from repro.distributed.sharding import activation_sharding
from repro.optim.interface import Optimizer

PyTree = Any


def global_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


def jit_train_step(step, *, donate: bool = True, **jit_kwargs):
    """jit a ``(state, batch) -> (state', metrics)`` train step with the
    state argument **donated**, so the updated params / optimizer state /
    BN model-state trees reuse the input buffers instead of allocating a
    second copy (halves the step's peak state residency — at 400B-scale
    fp32 masters that is the difference between fitting and not).

    All step builders in this module share the same state-in /
    state-out aliasing contract, so donation is always safe for them;
    ``donate=False`` keeps the inputs alive (the A/B half of the parity
    check in tests/test_donation.py, which pins that donation changes
    buffers only, never results)."""
    return jax.jit(step, donate_argnums=(0,) if donate else (),
                   **jit_kwargs)


def lower_train_hlo(step, state, batch, *, donate: bool = True,
                    **jit_kwargs):
    """Compiled-HLO text of one train step — the hook the audit
    subsystem (repro.analysis, DESIGN.md §12) uses to statically verify
    a jit site: donation/aliasing coverage, collective schedule,
    accumulation precision. ``state``/``batch`` may be real arrays or
    ``ShapeDtypeStruct`` trees (AOT — nothing is allocated).

    Returns ``(hlo_text, n_batch_params)`` where ``n_batch_params`` is
    the flattened batch leaf count — jax flattens ``(state, batch)``
    state-first, so the audit's donation pass treats every entry
    parameter except the trailing ``n_batch_params`` as donated state
    (``repro.analysis.quick_audit``)."""
    jitted = jit_train_step(step, donate=donate, **jit_kwargs)
    hlo = jitted.lower(state, batch).compile().as_text()
    return hlo, len(jax.tree.leaves(batch))


def make_train_step(model, optimizer: Optimizer, train_cfg: TrainConfig,
                    mesh: Optional[Mesh] = None,
                    rules: Optional[Dict] = None,
                    grad_constraint: Optional[Callable] = None,
                    param_shardings: Optional[PyTree] = None,
                    microbatches: int = 1):
    """GSPMD train step: state=(params, opt, model_state), batch -> state'.

    ``grad_constraint`` (optional): pins gradients to ZeRO shardings so
    the partitioner reduce-scatters instead of all-reducing.
    ``param_shardings`` (optional): pins the bf16 working copy of the
    params to the master shardings so FSDP all-gathers move bf16.
    ``microbatches`` > 1: gradient accumulation — the batch's leading dim
    is split and scanned, so peak activation memory drops by the factor
    while the gradient math is unchanged (mean of microbatch grads ==
    full-batch grad for mean losses).
    """
    # GSPMD leaves collective placement to XLA, so only the wire dtype of
    # the compression spec applies here; "+bucketed" is a shard_map-DP
    # concern (DESIGN.md §6) and is ignored by this builder.
    wire, _ = parse_compression(train_cfg.parallel.compression)

    compute_dtype = getattr(model, "compute_dtype", jnp.bfloat16)

    def train_step(state: PyTree, batch: PyTree):
        ctx = (activation_sharding(mesh, rules) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            def compute(params, mstate, mbatch):
                # cast params to the compute dtype HERE, before any FSDP
                # all-gather, so weight gathers move bf16 not fp32
                # (§Perf llama4 iteration 5). Gradients flow back to the
                # fp32 master copies through the cast.
                params = jax.tree.map(
                    lambda x: x.astype(compute_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    params)
                if param_shardings is not None:
                    params = jax.lax.with_sharding_constraint(
                        params, param_shardings)
                return model.loss_fn(params, mstate, mbatch,
                                     train_cfg.label_smoothing)

            grad_fn = jax.value_and_grad(compute, has_aux=True)
            if microbatches <= 1:
                (loss, (new_mstate, metrics)), grads = grad_fn(
                    state["params"], state["model_state"], batch)
            else:
                def split(x):
                    b = x.shape[0]
                    assert b % microbatches == 0, (b, microbatches)
                    return x.reshape(microbatches, b // microbatches,
                                     *x.shape[1:])

                mb = jax.tree.map(
                    lambda x: split(x) if jnp.ndim(x) else x, batch)

                def acc_step(carry, mbatch):
                    g_acc, mstate = carry
                    (loss, (mstate, metrics)), g = grad_fn(
                        state["params"], mstate, mbatch)
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32)
                        / microbatches, g_acc, g)
                    return (g_acc, mstate), metrics

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])
                (grads, new_mstate), metrics_seq = jax.lax.scan(
                    acc_step, (g0, state["model_state"]), mb)
                # average across microbatches (equal sizes, mean losses)
                # so the logged loss is the full-batch loss — reporting
                # only the last microbatch would make the logged curve
                # depend on the accumulation factor.
                metrics = jax.tree.map(
                    lambda m: jnp.mean(m.astype(jnp.float32), axis=0),
                    metrics_seq)

            grads = simulate_wire_cast(grads, wire)
            if grad_constraint is not None:
                grads = grad_constraint(grads)
            new_params, new_opt, opt_metrics = optimizer.update(
                state["params"], grads, state["opt"])
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        if train_cfg.log_grad_norm:
            # opt-in: a full extra tree reduction per step (DESIGN.md §8)
            metrics["grad_norm"] = global_norm(grads)
        new_state = {"params": new_params, "opt": new_opt,
                     "model_state": new_mstate}
        return new_state, metrics

    return train_step


def make_eval_step(model, train_cfg: Optional[TrainConfig] = None,
                   mesh: Optional[Mesh] = None,
                   rules: Optional[Dict] = None):
    """Validation step: (params, model_state, batch) -> metrics dict.

    ``model_state`` must already be finalized (paper §2: BN statistics
    all-reduced across workers before validation — identity under GSPMD,
    ``finalize_worker_bn_stats`` under shard_map DP; DESIGN.md §7). The
    step itself is mode-agnostic: a plain jit over (possibly sharded)
    inputs, so the same compiled program serves both execution modes.
    """
    del train_cfg  # schedules don't enter the eval path

    def eval_step(params, model_state, batch) -> Dict:
        ctx = (activation_sharding(mesh, rules) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            if hasattr(model, "eval_fn"):
                return model.eval_fn(params, model_state, batch)
            loss, (_, metrics) = model.loss_fn(params, model_state, batch)
            out = {k: v for k, v in metrics.items() if jnp.ndim(v) == 0}
            out["loss"] = loss
            return out

    return eval_step


def make_prefill_step(model, mesh=None, rules=None):
    def prefill_step(params, cache, batch):
        ctx = (activation_sharding(mesh, rules) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            kw = {k: batch[k] for k in ("frames", "patches") if k in batch}
            logits, new_cache = model.prefill(params, batch["tokens"],
                                              cache, **kw)
        return logits, new_cache

    return prefill_step


def make_decode_step(model, mesh=None, rules=None):
    def decode_step(params, cache, batch):
        ctx = (activation_sharding(mesh, rules) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            logits, new_cache = model.decode_step(
                params, cache, batch["tokens"], batch["cache_index"])
        return logits, new_cache

    return decode_step


# ---------------------------------------------------------------------------
# Paper-faithful explicit-DP mode (shard_map + compressed psum)
# ---------------------------------------------------------------------------


def _pmean_metrics(metrics: Dict, dp_axes: Sequence[str]) -> Dict:
    """One collective for all scalar metrics (stack -> pmean -> split)
    instead of one tiny all-reduce per metric — keeps the step's
    collective count at n_buckets + 1 in the bucketed modes."""
    scalar_keys = sorted(k for k, v in metrics.items() if jnp.ndim(v) == 0)
    if not scalar_keys:
        return {k: jax.lax.pmean(v, dp_axes) for k, v in metrics.items()}
    stacked = jax.lax.pmean(
        jnp.stack([metrics[k].astype(jnp.float32) for k in scalar_keys]),
        dp_axes)
    return {**{k: jax.lax.pmean(v, dp_axes) for k, v in metrics.items()
               if k not in scalar_keys},
            **{k: stacked[i] for i, k in enumerate(scalar_keys)}}


def _wrap_dp_step(local_step, mesh: Mesh, dp_axes: Sequence[str],
                  use_ef: bool, opt_specs=None, aux_builder=None):
    """shard_map plumbing shared by the explicit-DP step builders:
    params/opt replicated, model_state (and EF residual) per-worker.
    ``opt_specs`` overrides the replicated default for the opt state —
    the ZeRO mode shards the stream state over the DP axis
    (DESIGN.md §9). ``aux_builder(state, batch) -> (aux, aux_specs)``,
    if given,
    appends extra input-only arguments after the EF residual — the
    packed-stream side inputs (wd/segment streams) ride in as sharded
    shard_map *inputs* instead of being baked into every rank's program
    as full-stream trace constants (DESIGN.md §11)."""
    batch_spec = P(tuple(dp_axes))
    state_spec = P(tuple(dp_axes))  # per-worker last-minibatch BN / EF

    def train_step(state, batch):
        opt_spec_tree = (jax.tree.map(lambda _: P(), state["opt"])
                         if opt_specs is None else opt_specs)
        in_specs = (
            jax.tree.map(lambda _: P(), state["params"]),
            jax.tree.map(lambda _: state_spec, state["model_state"]),
            opt_spec_tree,
            # scalar batch leaves (the fused-input ``input_step`` stamp,
            # DESIGN.md §15) have no batch dim to shard: replicate them
            jax.tree.map(lambda x: batch_spec if jnp.ndim(x) else P(),
                         batch),
        )
        out_specs = (
            jax.tree.map(lambda _: P(), state["params"]),
            jax.tree.map(lambda _: state_spec, state["model_state"]),
            opt_spec_tree,
            P(),
        )
        args = (state["params"], state["model_state"], state["opt"], batch)
        if use_ef:
            ef_spec = jax.tree.map(lambda _: state_spec,
                                   state["ef_residual"])
            in_specs += (ef_spec,)
            out_specs += (ef_spec,)
            args += (state["ef_residual"],)
        if aux_builder is not None:
            aux, aux_specs = aux_builder(state, batch)
            in_specs += (aux_specs,)
            args += (aux,)
        fn = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        outs = fn(*args)
        new_params, new_mstate, new_opt, metrics = outs[:4]
        new_state = {"params": new_params, "opt": new_opt,
                     "model_state": new_mstate}
        if use_ef:
            new_state["ef_residual"] = outs[4]
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# ZeRO reduce-scatter plumbing shared by the bucketed + overlap builders
# (DESIGN.md §9)
# ---------------------------------------------------------------------------


def _static_dp_size(dp_axes, mesh: Mesh) -> int:
    """Total DP degree as a python int (a trace constant)."""
    n = 1
    for a in dp_axes:
        n *= int(mesh.shape[a])
    return n


def _zero_checks(parallel, dp_axes, optimizer, bucketed: bool,
                 mesh: Mesh) -> int:
    """Validate a --zero step request; returns the static DP size."""
    if not bucketed:
        raise ValueError(
            "zero_dp reduce-scatters packed buckets, which requires "
            "bucketed compression (e.g. compression='bf16+bucketed', "
            f"got {parallel.compression!r}; DESIGN.md §9)")
    if not hasattr(optimizer, "update_shard"):
        raise ValueError(
            "zero_dp needs a packed-stream optimizer "
            "(optim/stream.py:make_stream_optimizer), got "
            f"{type(optimizer).__name__}")
    n = _static_dp_size(dp_axes, mesh)
    if n < 2:
        raise ValueError(f"zero_dp needs DP degree >= 2, got {n}")
    return n


def _hier_or_none(parallel, dp_axes, mesh: Mesh, bucketed: bool):
    """Build the ``Hierarchy`` for ``parallel.hier_split``, or None for
    the flat schedule. Hierarchical schedules reschedule packed buckets
    (DESIGN.md §14), so they require bucketed compression; the axis
    split itself is validated by ``make_hierarchy`` (multi-axis DP mesh,
    both stages >= 2 ranks)."""
    if parallel.hier_split is None:
        return None
    if not bucketed:
        raise ValueError(
            "hier_split reschedules packed buckets, which requires "
            "bucketed compression (e.g. compression='bf16+bucketed', "
            f"got {parallel.compression!r}; DESIGN.md §14)")
    from repro.distributed.bucketing import make_hierarchy
    return make_hierarchy(dp_axes, mesh.shape, parallel.hier_split)


def _stream_checks(parallel, optimizer, bucketed: bool) -> None:
    """Validate a non-zero packed-stream step request (stream-LARS)."""
    if not bucketed:
        raise ValueError(
            "the packed-stream optimizer updates a contiguous stream, "
            "which requires bucketed compression (e.g. "
            "compression='bf16+bucketed', got "
            f"{parallel.compression!r}; DESIGN.md §11)")
    if optimizer.kind != "lars":
        raise ValueError(
            "non-zero packed-stream updates exist for kind='lars' only "
            "(rmsprop_warmup uses the replicated tree update unless "
            f"--zero shards it); got kind={optimizer.kind!r}")


def _stream_aux(optimizer, plan, param_tree, n: int, dp_axes,
                sharded: bool):
    """Static per-element side inputs of a packed-stream update, built at
    trace level to ride in as shard_map *inputs* (the carried ROADMAP
    fix): the wd stream — and for LARS the segment-id stream and trust
    mask — are plan constants, but feeding them through ``in_specs``
    makes them one outer (shardable) array instead of a full
    padded-stream constant baked into every rank's program.

    ``sharded=True`` (ZeRO): wd/seg are converted to shard layout and
    partitioned with ``P(dp_axes)``, so worker w's block is exactly its
    shard in bucket-chunk order — matching the scattered gradient.
    ``sharded=False`` (non-zero stream-LARS): full streams, replicated.
    """
    from repro.distributed.bucketing import (
        segment_ids_stream,
        stream_to_shard_layout,
    )

    spec = P(tuple(dp_axes)) if sharded else P()

    def as_input(arr):
        return jnp.asarray(stream_to_shard_layout(arr, plan, n)
                           if sharded else arr)

    aux = {"wd": as_input(optimizer.wd_stream(param_tree, plan))}
    specs = {"wd": spec}
    if optimizer.kind == "lars":
        from repro.optim.stream import trust_mask_segments
        aux["seg"] = as_input(segment_ids_stream(plan))
        specs["seg"] = spec
        aux["trust_mask"] = jnp.asarray(
            trust_mask_segments(param_tree, plan))
        specs["trust_mask"] = P()
    return aux, specs


def _cast_divide_stream(stream, plan, n):
    """Cast a synced wire stream back to fp32 and divide by the worker
    count with exactly ``unpack()``'s ops — elementwise, so a scattered
    shard and the full stream get bitwise-equal values."""
    from repro.distributed.bucketing import _kernel_on

    acc_dtypes = {jnp.dtype(s.dtype) for s in plan.slots}
    if acc_dtypes != {jnp.dtype(jnp.float32)}:
        raise ValueError(
            "packed-stream updates need a uniform fp32 param tree; got "
            f"leaf dtypes {sorted(d.name for d in acc_dtypes)}")
    if stream.dtype != jnp.float32:
        if _kernel_on(None):
            from repro.kernels.ops import unpack_cast
            stream = unpack_cast(stream, jnp.float32)
        else:
            stream = stream.astype(jnp.float32)
    return stream / n


def _dp_linear_index(dp_axes: Sequence[str], mesh: Mesh):
    """This worker's rank in the row-major order psum_scatter/all_gather
    use over a tuple of mesh axes (pinned by bitwise parity on a (4, 2)
    dual-axis DP mesh: tests/test_zero.py::
    test_zero_bitwise_parity_two_dp_axes_8dev)."""
    w = jax.lax.axis_index(dp_axes[0])
    for a in dp_axes[1:]:
        w = w * mesh.shape[a] + jax.lax.axis_index(a)
    return w


def _zero_sharded_update(optimizer, plan, param_tree, g_shard, opt,
                         n: int, dp_axes: Sequence[str], mesh: Mesh,
                         aux, hier=None):
    """The rank-local half of the ZeRO step: cast+divide the scattered
    gradient shard exactly as ``unpack`` would (bitwise-equal elements),
    update the worker-owned param shard against the dp-sharded stream
    state, all-gather the updated slices per bucket, and unpack back to
    the plan-structured param tree.

    ``aux`` carries the per-element side inputs (``_stream_aux``,
    sharded=True): this worker's shard of the wd stream — and for LARS
    the segment-id shard plus the replicated trust mask. The LARS trust
    norms are the shard's per-segment partial sums psum'd over the DP
    axes (a leaf may span shard boundaries, DESIGN.md §11); the update
    itself stays on the worker-owned shard.

    ``hier`` swaps the per-bucket param all-gather for the two-level
    ``hierarchical_all_gather`` (bitwise-identical data movement, the
    expensive link carries 1/inner_size; DESIGN.md §14) — shard
    ownership itself is hierarchy-invariant, so nothing else changes.

    Returns ``(new_param_tree, new_opt, opt_metrics, local_sq)`` where
    ``local_sq`` is this worker's partial squared grad norm (the caller
    folds it into the stacked metrics pmean, DESIGN.md §8)."""
    import dataclasses as _dc

    from repro.core.compression import chained
    from repro.distributed.bucketing import (
        hierarchical_all_gather,
        pack,
        shard_chunks,
        unpack,
    )

    g_shard = _cast_divide_stream(g_shard, plan, n)
    local_sq = jnp.sum(jnp.square(g_shard))

    chunks = shard_chunks(plan, n)
    w = _dp_linear_index(dp_axes, mesh)
    p_plan = _dc.replace(plan, wire=None,
                         stream_dtype=jnp.dtype(jnp.float32))
    p_buckets = pack(param_tree, p_plan)
    p_shard = jnp.concatenate(
        [jax.lax.dynamic_slice(b, (w * c,), (c,))
         for b, c in zip(p_buckets, chunks)])
    wd_shard = aux["wd"]

    if optimizer.kind == "lars":
        num_segments = len(plan.slots) + 1
        partials = optimizer.segment_partials(
            p_shard, g_shard, wd_shard, aux["seg"], num_segments)
        totals = jax.lax.psum(partials, tuple(dp_axes))
        trust = optimizer.trust_ratios(totals, aux["trust_mask"])
        p_new, d_new, opt_metrics = optimizer.update_shard(
            p_shard, g_shard, opt["delta"], opt["step"], wd_shard,
            aux["seg"], trust)
        new_opt = {"step": opt["step"] + 1, "delta": d_new}
    else:
        p_new, d_new, m_new, opt_metrics = optimizer.update_shard(
            p_shard, g_shard, opt["delta"], opt["m"], opt["step"],
            wd_shard)
        new_opt = {"step": opt["step"] + 1, "delta": d_new, "m": m_new}

    offs = [sum(chunks[:i]) for i in range(len(chunks))]
    pieces = [jax.lax.slice(p_new, (o,), (o + c,))
              for o, c in zip(offs, chunks)]
    if hier is not None:
        gathered = chained(pieces,
                           lambda x: hierarchical_all_gather(x, hier))
    else:
        gathered = chained(pieces, lambda x: jax.lax.all_gather(
            x, tuple(dp_axes), tiled=True))
    new_param_tree = unpack(gathered, p_plan)
    return new_param_tree, new_opt, opt_metrics, local_sq


def _stream_full_update(optimizer, plan, param_tree, g_stream, opt,
                        n: int, dp_axes: Sequence[str], mesh: Mesh, aux):
    """Replicated-stream LARS update for the non-zero packed paths
    (DESIGN.md §11): the update itself runs on the full synced stream on
    every worker — like the replicated tree update — but the trust norms
    come from the *identical* shard-decomposed program as the ZeRO path:
    each worker reduces only its own 1/N slice (the same chunks
    ``psum_scatter`` would hand it) and the (2, L+1) partials are
    psum'd. Same reduction tree, same fold order — which is what makes
    bucketed<->zero and overlap<->zero-overlap parameters bitwise-equal
    (tests/test_lars_stream.py).

    ``g_stream`` must already be cast+divided (``_cast_divide_stream``).
    Returns ``(new_param_tree, new_opt, opt_metrics, local_sq)``."""
    import dataclasses as _dc

    from repro.distributed.bucketing import local_shard, pack, unpack

    w = _dp_linear_index(dp_axes, mesh)
    p_plan = _dc.replace(plan, wire=None,
                         stream_dtype=jnp.dtype(jnp.float32))
    p_stream = jnp.concatenate(pack(param_tree, p_plan))

    g_loc = local_shard(g_stream, plan, n, w)
    local_sq = jnp.sum(jnp.square(g_loc))
    num_segments = len(plan.slots) + 1
    partials = optimizer.segment_partials(
        local_shard(p_stream, plan, n, w), g_loc,
        local_shard(aux["wd"], plan, n, w),
        local_shard(aux["seg"], plan, n, w), num_segments)
    totals = jax.lax.psum(partials, tuple(dp_axes))
    trust = optimizer.trust_ratios(totals, aux["trust_mask"])

    p_new, d_new, opt_metrics = optimizer.update_shard(
        p_stream, g_stream, opt["delta"], opt["step"], aux["wd"],
        aux["seg"], trust)
    new_opt = {"step": opt["step"] + 1, "delta": d_new}
    new_param_tree = unpack([p_new], p_plan)
    return new_param_tree, new_opt, opt_metrics, local_sq


def _zero_grad_norm(metrics: Dict, n: int) -> Dict:
    """Recover the global grad norm from the pmean'd per-worker partial
    sums (exact when n is a power of two — psum/n*n == psum — and a
    last-ulp metric either way; never parity-asserted)."""
    sq = metrics.pop("grad_sq_local") * n
    metrics["grad_norm"] = jnp.sqrt(sq)
    return metrics


def make_batch_input_transform(input_cfg, seed: int, model, mesh: Mesh,
                               dp_axes: Sequence[str]):
    """Per-worker fused input transform for the shard_map local steps
    (DESIGN.md §15), or None when the fused path is off.

    The returned callable runs *inside* shard_map on each worker's local
    batch slice: it pops the ``input_step`` stamp (StepStampSource),
    derives the global (B, 4) augmentation-parameter table from
    ``(seed, step)`` — bitwise-identical to the host AugmentedSource
    draw — takes this worker's row block by its DP linear rank (the same
    rank order ``P(dp_axes)`` used to place the batch rows), and applies
    the one-pass Pallas augment+normalize+cast kernel. It must hook the
    local steps rather than the model because parameter slicing needs
    ``lax.axis_index``, which only exists under shard_map (the overlap
    mode's aux_builder calls ``loss_segments`` outside it)."""
    if input_cfg is None or not input_cfg.fused:
        return None
    from repro.kernels import ops

    compute_dtype = getattr(model, "compute_dtype", jnp.bfloat16)
    n = _static_dp_size(dp_axes, mesh)
    mean = jnp.asarray(input_cfg.mean, jnp.float32)
    inv_std = 1.0 / jnp.asarray(input_cfg.std, jnp.float32)
    augment = input_cfg.augment
    max_shift = input_cfg.max_shift

    def transform(batch):
        batch = dict(batch)
        step_no = batch.pop("input_step")
        x = batch["images"]
        b_local = x.shape[0]
        if augment:
            # total must be the *global* batch: threefry draws are not
            # prefix-stable across sizes (ops.input_augment_params)
            params = ops.input_augment_params(
                seed, step_no, b_local * n, max_shift=max_shift)
            w = _dp_linear_index(dp_axes, mesh)
            mine = jax.lax.dynamic_slice(
                params, (w * b_local, 0), (b_local, 4))
            batch["images"] = ops.fused_input_train(
                x, mine, mean, inv_std, out_dtype=compute_dtype)
        else:
            batch["images"] = ops.fused_input_eval(
                x, mean, inv_std, out_dtype=compute_dtype)
        return batch

    return transform


def make_dp_shardmap_train_step(model, optimizer: Optimizer,
                                train_cfg: TrainConfig, mesh: Mesh,
                                dp_axes: Sequence[str],
                                input_transform=None):
    """Synchronous data-parallel step exactly as the paper's system:
    per-worker forward/backward, **half-precision all-reduce of
    gradients**, replicated optimizer update. Model must be pure-DP
    (params replicated), e.g. ResNet-50 or small LMs.

    ``compression="<wire>+bucketed"`` swaps the per-leaf psum for the
    bucketed subsystem (one collective per ``bucket_bytes`` of wire
    traffic, DESIGN.md §6); ``error_feedback=True`` threads rounding
    residuals through either sync path (state gains an ``ef_residual``
    entry, per-worker like the BN stats); ``zero_dp=True`` (--zero)
    swaps each bucket's all-reduce for a reduce-scatter and shards the
    optimizer update over the DP ranks (DESIGN.md §9), bitwise-equal
    end state.
    """
    from repro.distributed.bucketing import bucketed_psum, bucketed_psum_ef

    parallel = train_cfg.parallel
    wire, bucketed = parse_compression(parallel.compression)
    use_ef = parallel.error_feedback
    if use_ef and wire is None:
        raise ValueError("error_feedback requires a wire dtype "
                         f"(compression={parallel.compression!r})")
    dp_axes = tuple(dp_axes)

    if parallel.zero_dp:
        return _make_dp_zero_train_step(model, optimizer, train_cfg, mesh,
                                        dp_axes, wire, bucketed,
                                        input_transform=input_transform)
    if hasattr(optimizer, "update_shard"):
        # non-zero packed-stream optimizer (stream-LARS): replicated
        # update over the full synced stream, shard-decomposed trust
        # norms (DESIGN.md §11)
        return _make_dp_stream_train_step(model, optimizer, train_cfg,
                                          mesh, dp_axes, wire, bucketed,
                                          input_transform=input_transform)
    hier = _hier_or_none(parallel, dp_axes, mesh, bucketed)

    def sync_grads(grads, residual):
        """One of the four (per-leaf|bucketed) x (plain|EF) sync paths.

        Returns (synced, new_residual, sq_norm). The bucketed paths get
        the squared grad norm from one pass over the packed stream
        instead of a second full-tree reduction (DESIGN.md §8)."""
        if use_ef:
            if bucketed:
                return bucketed_psum_ef(
                    grads, residual, dp_axes, wire=wire,
                    bucket_bytes=parallel.bucket_bytes, with_sq_norm=True,
                    hierarchy=hier)
            synced, new_residual = compressed_psum_ef(
                grads, residual, dp_axes, wire)
            return synced, new_residual, None
        if bucketed:
            synced, sq = bucketed_psum(grads, dp_axes, wire=wire,
                                       bucket_bytes=parallel.bucket_bytes,
                                       mean=True, with_sq_norm=True,
                                       hierarchy=hier)
            return synced, None, sq
        return compressed_psum(grads, dp_axes, wire, mean=True), None, None

    def local_step(params, mstate, opt, batch, residual=None):
        if input_transform is not None:
            batch = input_transform(batch)
        # mstate leaves carry a leading per-worker dim (1, ...) locally
        local_mstate = jax.tree.map(lambda x: x[0], mstate)
        (loss, (new_mstate, metrics)), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, local_mstate, batch,
                                         train_cfg.label_smoothing)
        # ---- the paper's technique: fp16/bf16 compressed all-reduce ----
        local_residual = (jax.tree.map(lambda x: x[0], residual)
                          if use_ef else None)
        grads, new_residual, sq_norm = sync_grads(grads, local_residual)
        metrics = _pmean_metrics(metrics, dp_axes)
        new_params, new_opt, opt_metrics = optimizer.update(
            params, grads, opt)
        metrics.update(opt_metrics)
        metrics["grad_norm"] = (jnp.sqrt(sq_norm) if sq_norm is not None
                                else global_norm(grads))
        new_mstate = jax.tree.map(lambda x: x[None], new_mstate)
        out = (new_params, new_mstate, new_opt, metrics)
        if use_ef:
            out += (jax.tree.map(lambda x: x[None], new_residual),)
        return out

    return _wrap_dp_step(local_step, mesh, dp_axes, use_ef)


def _make_dp_zero_train_step(model, optimizer, train_cfg: TrainConfig,
                             mesh: Mesh, dp_axes: Sequence[str],
                             wire, bucketed: bool, input_transform=None):
    """ZeRO variant of the plain bucketed DP step (DESIGN.md §9):
    pack -> psum_scatter per bucket -> sharded optimizer update on the
    owned stream shard -> all-gather the updated param slices -> unpack.
    Error feedback stays rank-local and full-tree, applied before
    packing exactly as in ``bucketed_psum_ef`` — which is what keeps the
    residuals (and everything downstream) bitwise-equal to the
    all-reduce path."""
    from repro.core.compression import apply_error_feedback, chained
    from repro.distributed.bucketing import (
        hierarchical_psum_scatter,
        pack,
        plan_buckets,
    )

    parallel = train_cfg.parallel
    use_ef = parallel.error_feedback
    n = _zero_checks(parallel, dp_axes, optimizer, bucketed, mesh)
    hier = _hier_or_none(parallel, dp_axes, mesh, bucketed)

    def local_step(params, mstate, opt, batch, *extra):
        residual = extra[0] if use_ef else None
        aux = extra[-1]
        if input_transform is not None:
            batch = input_transform(batch)
        local_mstate = jax.tree.map(lambda x: x[0], mstate)
        (loss, (new_mstate, metrics)), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, local_mstate, batch,
                                         train_cfg.label_smoothing)
        if use_ef:
            local_residual = jax.tree.map(lambda x: x[0], residual)
            quant, new_residual = apply_error_feedback(
                grads, local_residual, wire)
        else:
            quant, new_residual = grads, None
        # shard-aligned plan: every bucket splits evenly across the ranks
        plan = plan_buckets(quant, parallel.bucket_bytes, wire, align=n)
        if hier is not None:
            g_shard = jnp.concatenate(chained(
                pack(quant, plan),
                lambda b: hierarchical_psum_scatter(b, hier)))
        else:
            g_shard = jnp.concatenate(chained(
                pack(quant, plan),
                lambda b: jax.lax.psum_scatter(
                    b, tuple(dp_axes), scatter_dimension=0, tiled=True)))
        new_params, new_opt, opt_metrics, local_sq = _zero_sharded_update(
            optimizer, plan, params, g_shard, opt, n, dp_axes, mesh, aux,
            hier=hier)
        metrics["grad_sq_local"] = local_sq
        metrics = _zero_grad_norm(_pmean_metrics(metrics, dp_axes), n)
        metrics.update(opt_metrics)
        new_mstate = jax.tree.map(lambda x: x[None], new_mstate)
        out = (new_params, new_mstate, new_opt, metrics)
        if use_ef:
            out += (jax.tree.map(lambda x: x[None], new_residual),)
        return out

    def aux_builder(state, batch):
        plan = plan_buckets(state["params"], parallel.bucket_bytes, wire,
                            align=n)
        return _stream_aux(optimizer, plan, state["params"], n, dp_axes,
                           sharded=True)

    opt_specs = {"step": P(), **{f: P(tuple(dp_axes))
                                 for f in optimizer.state_fields}}
    return _wrap_dp_step(local_step, mesh, dp_axes, use_ef,
                         opt_specs=opt_specs, aux_builder=aux_builder)


def _make_dp_stream_train_step(model, optimizer, train_cfg: TrainConfig,
                               mesh: Mesh, dp_axes: Sequence[str],
                               wire, bucketed: bool, input_transform=None):
    """Non-zero packed-stream variant of the plain bucketed DP step
    (stream-LARS, DESIGN.md §11): pack -> psum per bucket -> replicated
    update over the full fp32 stream, with the LARS trust norms reduced
    shard-by-shard exactly as the ZeRO path reduces them — which is what
    makes this path's parameters bitwise-equal to ``--zero``'s
    (tests/test_lars_stream.py). Error feedback stays rank-local and
    full-tree, applied before packing, as in ``bucketed_psum_ef``."""
    from repro.core.compression import apply_error_feedback, chained
    from repro.distributed.bucketing import (
        hierarchical_psum,
        pack,
        plan_buckets,
    )

    parallel = train_cfg.parallel
    use_ef = parallel.error_feedback
    _stream_checks(parallel, optimizer, bucketed)
    n = _static_dp_size(dp_axes, mesh)
    hier = _hier_or_none(parallel, dp_axes, mesh, bucketed)

    def local_step(params, mstate, opt, batch, *extra):
        residual = extra[0] if use_ef else None
        aux = extra[-1]
        if input_transform is not None:
            batch = input_transform(batch)
        local_mstate = jax.tree.map(lambda x: x[0], mstate)
        (loss, (new_mstate, metrics)), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, local_mstate, batch,
                                         train_cfg.label_smoothing)
        if use_ef:
            local_residual = jax.tree.map(lambda x: x[0], residual)
            quant, new_residual = apply_error_feedback(
                grads, local_residual, wire)
        else:
            quant, new_residual = grads, None
        # shard-aligned plan (align=n): not required for the psum itself,
        # but it gives every rank the same 1/N norm slices as the ZeRO
        # reduce-scatter would — the bitwise-parity contract above
        plan = plan_buckets(quant, parallel.bucket_bytes, wire, align=n)
        if hier is not None:
            synced = chained(pack(quant, plan),
                             lambda b: hierarchical_psum(b, hier))
        else:
            synced = chained(pack(quant, plan),
                             lambda b: jax.lax.psum(b, tuple(dp_axes)))
        g_stream = _cast_divide_stream(jnp.concatenate(synced), plan, n)
        new_params, new_opt, opt_metrics, local_sq = _stream_full_update(
            optimizer, plan, params, g_stream, opt, n, dp_axes, mesh, aux)
        metrics["grad_sq_local"] = local_sq
        metrics = _zero_grad_norm(_pmean_metrics(metrics, dp_axes), n)
        metrics.update(opt_metrics)
        new_mstate = jax.tree.map(lambda x: x[None], new_mstate)
        out = (new_params, new_mstate, new_opt, metrics)
        if use_ef:
            out += (jax.tree.map(lambda x: x[None], new_residual),)
        return out

    def aux_builder(state, batch):
        plan = plan_buckets(state["params"], parallel.bucket_bytes, wire,
                            align=n)
        return _stream_aux(optimizer, plan, state["params"], n, dp_axes,
                           sharded=False)

    return _wrap_dp_step(local_step, mesh, dp_axes, use_ef,
                         aux_builder=aux_builder)


def make_dp_overlap_train_step(model, optimizer: Optimizer,
                               train_cfg: TrainConfig, mesh: Mesh,
                               dp_axes: Sequence[str],
                               input_transform=None):
    """Backward-overlapped bucketed DP step (DESIGN.md §8).

    Same contract and bitwise-identical numerics as
    ``make_dp_shardmap_train_step`` with ``"<wire>+bucketed"``
    compression, but the gradient all-reduces launch *during* the
    backward pass: the model's loss is split into K segments
    (``model.loss_segments``), each segment's VJP is taken independently,
    and every ready-order bucket's psum is issued the moment the
    bucket's last leaf exists. A data edge (``compression.after``) from
    each collective's result into the cotangent of the segment two
    downstream pins its completion there, so the interconnect works on
    bucket i while the VJP of segment i-1 computes — the paper's
    "aggregate finished layers in parallel with backprop" (Goyal et al.
    §Gradient aggregation; verified from the compiled HLO by
    ``launch/hlo_analysis.py:interleave_report``).
    """
    from repro.core.compression import after, apply_error_feedback
    from repro.distributed.bucketing import (
        hierarchical_psum,
        hierarchical_psum_scatter,
        pack_bucket,
        plan_ready_buckets,
        unpack,
    )
    from repro.models.common import staged_forward

    parallel = train_cfg.parallel
    wire, _bucketed = parse_compression(parallel.compression)
    use_ef = parallel.error_feedback
    if use_ef and wire is None:
        raise ValueError("error_feedback requires a wire dtype "
                         f"(compression={parallel.compression!r})")
    if not hasattr(model, "loss_segments"):
        raise ValueError(
            f"{type(model).__name__} has no loss_segments(); "
            "overlap_comm needs a staged model (ResNet50 / TransformerLM,"
            " DESIGN.md §8)")
    dp_axes = tuple(dp_axes)
    use_zero = parallel.zero_dp
    use_stream = hasattr(optimizer, "update_shard")
    if use_zero:
        n_static = _zero_checks(parallel, dp_axes, optimizer, _bucketed,
                                mesh)
    elif use_stream:
        # non-zero stream-LARS rides the same shard-aligned ready plan
        _stream_checks(parallel, optimizer, _bucketed)
        n_static = _static_dp_size(dp_axes, mesh)
    else:
        n_static = 1
    hier = _hier_or_none(parallel, dp_axes, mesh, _bucketed)
    # ZeRO/stream plans shard-align for scatter/trust slicing; a
    # hierarchical plain plan aligns too, so every bucket splits over
    # the inner axis (hier.n_workers == the static DP size)
    plan_align = n_static if n_static > 1 else (
        hier.n_workers if hier is not None else 1)

    def local_step(params, mstate, opt, batch, *extra):
        residual = extra[0] if use_ef else None
        aux = extra[-1] if use_stream else None
        if input_transform is not None:
            batch = input_transform(batch)
        local_mstate = jax.tree.map(lambda x: x[0], mstate)
        staged = model.loss_segments(params, local_mstate, batch,
                                     train_cfg.label_smoothing)
        n_seg = len(staged)
        # ---- forward: per-segment VJP chain ----
        loss, vjps, auxes = staged_forward(staged)
        # ready order = reverse segment order (last segment's grads
        # materialize first); the plan is shape-only, so it is a trace
        # constant like the treedef. ZeRO shard-aligns every bucket so
        # psum_scatter splits it evenly across ranks (DESIGN.md §9).
        plan = plan_ready_buckets(list(reversed(staged.seg_params)),
                                  parallel.bucket_bytes, wire,
                                  align=plan_align)
        res_rev = None
        if use_ef:
            local_residual = jax.tree.map(lambda x: x[0], residual)
            res_rev = list(reversed(staged.split_tree(local_residual)))
        n = jax.lax.axis_size(dp_axes)
        # ---- backward: VJP segment i, launch ready buckets, require
        # completion only before segment i-2 (one-segment-deep pipeline:
        # bucket i's wire time hides behind segment i-1's compute). With
        # zero_dp the launched collective is the bucket's reduce-scatter
        # — same launch points, same pipeline. Completion is pinned by a
        # data edge into segment i-2's cotangent (``after``). ----
        ct: Any = jnp.ones_like(loss)
        synced: Dict[int, jax.Array] = {}
        pending: List[List[int]] = []  # launched ids, newest last
        last = None  # newest collective result: the launch chain
        pack_carry = None
        new_res_rev: List[PyTree] = []
        for ridx, i in enumerate(reversed(range(n_seg))):
            if len(pending) >= 2:
                # segment i's VJP input waits on these collectives
                for b in pending.pop(0):
                    ct = jax.tree.map(lambda x, d=synced[b]: after(x, d),
                                      ct)
            g_seg, ct = vjps[i](ct)
            if use_ef:
                g_seg, r_new = apply_error_feedback(g_seg, res_rev[ridx],
                                                    wire)
                new_res_rev.append(r_new)
            ready, pack_carry = pack_bucket(plan, ridx, g_seg, pack_carry)
            launched = []
            for b, arr in ready:
                # with a hierarchy the whole two-level schedule launches
                # here; the pipeline pins only its completion, exactly
                # as for the flat collective (DESIGN.md §14). Each
                # launch is chained after the previous one, so no
                # combiner merges two buckets into one collective.
                if last is not None:
                    arr = after(arr, last)
                if use_zero:
                    synced[b] = (
                        hierarchical_psum_scatter(arr, hier)
                        if hier is not None else
                        jax.lax.psum_scatter(arr, tuple(dp_axes),
                                             scatter_dimension=0,
                                             tiled=True))
                else:
                    synced[b] = (hierarchical_psum(arr, hier)
                                 if hier is not None else
                                 jax.lax.psum(arr, dp_axes))
                last = synced[b]
                launched.append(b)
            pending.append(launched)
        assert len(synced) == plan.n_buckets, (len(synced), plan.n_buckets)
        new_mstate, metrics = staged.finalize_aux(auxes)
        if use_zero:
            # scattered shards (bucket order) -> sharded update ->
            # all-gather updated param slices -> ready-ordered stage
            # trees -> merge back to the full param structure
            g_shard = jnp.concatenate(
                [synced[b] for b in range(plan.n_buckets)])
            param_rev = tuple(reversed(staged.seg_params))
            new_param_rev, new_opt, opt_metrics, local_sq = \
                _zero_sharded_update(optimizer, plan.base, param_rev,
                                     g_shard, opt, n_static, dp_axes,
                                     mesh, aux, hier=hier)
            new_params = staged.merge_grads(
                list(reversed(list(new_param_rev))))
            metrics["grad_sq_local"] = local_sq
            metrics = _zero_grad_norm(_pmean_metrics(metrics, dp_axes),
                                      n_static)
        elif use_stream:
            # non-zero stream-LARS: full all-reduced stream, replicated
            # update; trust norms shard-decomposed as in the ZeRO branch
            g_stream = _cast_divide_stream(
                jnp.concatenate([synced[b]
                                 for b in range(plan.n_buckets)]),
                plan.base, n_static)
            param_rev = tuple(reversed(staged.seg_params))
            new_param_rev, new_opt, opt_metrics, local_sq = \
                _stream_full_update(optimizer, plan.base, param_rev,
                                    g_stream, opt, n_static, dp_axes,
                                    mesh, aux)
            new_params = staged.merge_grads(
                list(reversed(list(new_param_rev))))
            metrics["grad_sq_local"] = local_sq
            metrics = _zero_grad_norm(_pmean_metrics(metrics, dp_axes),
                                      n_static)
        else:
            stage_grads_rev, sq_norm = unpack(
                [synced[b] for b in range(plan.n_buckets)], plan.base,
                denom=n, with_sq_norm=True)
            grads = staged.merge_grads(
                list(reversed(list(stage_grads_rev))))
            metrics = _pmean_metrics(metrics, dp_axes)
            new_params, new_opt, opt_metrics = optimizer.update(
                params, grads, opt)
            metrics["grad_norm"] = jnp.sqrt(sq_norm)
        metrics.update(opt_metrics)
        new_mstate = jax.tree.map(lambda x: x[None], new_mstate)
        out = (new_params, new_mstate, new_opt, metrics)
        if use_ef:
            new_residual = staged.merge_grads(
                list(reversed(new_res_rev)))
            out += (jax.tree.map(lambda x: x[None], new_residual),)
        return out

    opt_specs = ({"step": P(), **{f: P(tuple(dp_axes))
                                  for f in optimizer.state_fields}}
                 if use_zero else None)

    def aux_builder(state, batch):
        # loss_segments at trace level is compute-free (the segment
        # closures go unexecuted) — we only need seg_params for the
        # ready-order plan. Outer model_state leaves carry the leading
        # per-worker dim, hence the x[0].
        staged = model.loss_segments(
            state["params"],
            jax.tree.map(lambda x: x[0], state["model_state"]), batch,
            train_cfg.label_smoothing)
        param_rev = tuple(reversed(staged.seg_params))
        plan = plan_ready_buckets(list(param_rev), parallel.bucket_bytes,
                                  wire, align=plan_align).base
        return _stream_aux(optimizer, plan, param_rev, n_static, dp_axes,
                           sharded=use_zero)

    return _wrap_dp_step(local_step, mesh, dp_axes, use_ef,
                         opt_specs=opt_specs,
                         aux_builder=aux_builder if use_stream else None)


def replicate_model_state(state: PyTree, n_workers: int) -> PyTree:
    """Give BN stats a leading per-worker dim for the shard_map DP mode."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_workers,) + x.shape).copy(), state)


def finalize_worker_bn_stats(state: PyTree) -> PyTree:
    """Paper §2: all-reduce the per-worker last-minibatch BN statistics
    before validation (the all-reduce happens when XLA gathers the
    worker-sharded stats for the mean). Variances are combined
    moment-correctly (via E[x^2]) so the result equals the global-batch
    statistics — see ``core.batchnorm.combine_worker_bn_stats`` and
    DESIGN.md §7."""
    from repro.core.batchnorm import combine_worker_bn_stats

    return combine_worker_bn_stats(state)
