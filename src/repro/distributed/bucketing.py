"""Bucketed gradient all-reduce (DESIGN.md §6).

The paper's 15-minute result depends on the interconnect seeing a few
large transfers, not hundreds of small ones: gradients are chunked and
all-reduced in half precision so latency/launch overhead is amortized
(§3; the same fused all-reduce is the core of Yamazaki et al.'s 74.7 s
follow-up). ``compressed_psum`` already casts to the wire dtype but still
issues one collective per parameter leaf — 161 all-reduces per step for
ResNet-50. This module flattens the gradient pytree into one contiguous
wire-dtype stream, splits it into fixed-size buckets (default 64 MiB),
runs **one psum per bucket**, and scatters the result back to leaves.

Leaves may span bucket boundaries (the stream is split at fixed byte
offsets, not at leaf edges), so the collective count is exactly
``ceil(total_wire_bytes / bucket_bytes)`` with no fragmentation waste.

Numerics are bitwise-identical to the per-leaf path: cast-to-wire,
elementwise sum over workers, cast-back, divide — packing only changes
*where* element i sits during the reduction, never its value. The
bucketing tests assert this on a multi-device host mesh.

The cast+copy into/out of the bucket is the Pallas kernel pair in
``kernels/bucket_ops.py`` (fused, padding-aware) when ``use_kernel`` is
on (default on TPU); the pure-JAX path is the reference and the CPU
default (interpret-mode Pallas is Python-speed).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import _wire, apply_error_feedback, chained

PyTree = Any

DEFAULT_BUCKET_BYTES = 64 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one gradient leaf lives in the packed stream."""

    offset: int  # element offset into the global flat stream
    size: int
    shape: Tuple[int, ...]
    dtype: Any  # original (accumulation) dtype, restored on unpack


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static layout of a gradient pytree packed into fixed buckets.

    Derived from shapes only, so one plan serves every step (it is
    closed over by the jitted train step, like the tree structure
    itself).

    ``pad_elems`` is the zero tail appended after the last leaf so the
    final bucket's length is a multiple of ``align`` — the shard-aligned
    layout the ZeRO sync mode needs (every bucket must split evenly
    across the DP ranks for ``psum_scatter``, DESIGN.md §9). The default
    ``align=1`` keeps the historical truncated-last-bucket layout
    (``pad_elems == 0``): a pad reduced over the wire for nothing.
    """

    treedef: Any
    slots: Tuple[LeafSlot, ...]
    total_elems: int
    bucket_elems: int  # elements per bucket (fixed; last one truncated)
    n_buckets: int
    wire: Optional[str]  # wire dtype name, None = no cast
    stream_dtype: Any  # wire dtype, or the (uniform) leaf dtype if None
    align: int = 1  # every bucket length is a multiple of this
    pad_elems: int = 0  # zero tail making the last bucket align-even

    @property
    def padded_total(self) -> int:
        return self.total_elems + self.pad_elems

    def bucket_bounds(self, i: int) -> Tuple[int, int]:
        """Element range of bucket ``i`` within the (padded) stream. All
        buckets are ``bucket_elems`` long except the last, which ends at
        the padded stream end (== ``total_elems`` when ``align == 1``)."""
        lo = i * self.bucket_elems
        return lo, min(lo + self.bucket_elems, self.padded_total)

    @property
    def bucket_bytes(self) -> int:
        return self.bucket_elems * jnp.dtype(self.stream_dtype).itemsize

    def describe(self) -> str:
        itemsize = jnp.dtype(self.stream_dtype).itemsize
        total_mib = self.total_elems * itemsize / 2 ** 20
        pad = f" +{self.pad_elems}pad" if self.pad_elems else ""
        return (f"{len(self.slots)} leaves / {total_mib:.1f} MiB wire "
                f"-> {self.n_buckets} bucket(s) of "
                f"<= {self.bucket_bytes / 2**20:.0f} MiB "
                f"({self.wire or 'f32'} wire{pad})")


def stream_layout(total_elems: int, bucket_bytes: int, itemsize: int,
                  align: int = 1) -> Tuple[int, int, int]:
    """The pure bucket arithmetic shared by every plan flavor: returns
    ``(bucket_elems, n_buckets, pad_elems)`` for a stream of
    ``total_elems``. Layout depends only on these scalars — never on
    leaf order — which is why the plain (pytree-order) and ready-order
    plans of the same tree have identical padded lengths and the ZeRO
    optimizer-state size can be computed without a plan."""
    if align < 1:
        raise ValueError(f"align must be >= 1, got {align}")
    bucket_elems = max(1, int(bucket_bytes) // itemsize)
    bucket_elems = -(-bucket_elems // align) * align  # round UP to align
    n_buckets = max(1, -(-total_elems // bucket_elems))
    last = total_elems - (n_buckets - 1) * bucket_elems
    pad_elems = (-last) % align
    return bucket_elems, n_buckets, pad_elems


def plan_buckets(grads: PyTree,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 wire: Optional[str] = "bf16",
                 align: int = 1) -> BucketPlan:
    """Lay out the gradient pytree as a contiguous wire-dtype stream cut
    into fixed-size buckets. Works on arrays or ShapeDtypeStructs.
    ``align > 1`` pads every bucket to an ``align`` multiple (ZeRO)."""
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        raise ValueError("cannot plan buckets for an empty gradient tree")
    wdt = _wire(wire)
    if wdt is None:
        # no wire cast: the stream keeps the leaves' own dtype, so the
        # psum runs in the same precision as per-leaf wire=None sync
        leaf_dtypes = {jnp.dtype(l.dtype) for l in leaves}
        if len(leaf_dtypes) > 1:
            raise ValueError(
                "bucketing without a wire dtype needs uniform leaf "
                f"dtypes, got {sorted(d.name for d in leaf_dtypes)}; "
                "set a wire dtype (e.g. 'bf16+bucketed')")
        sdt = next(iter(leaf_dtypes))
    else:
        sdt = jnp.dtype(wdt)
    slots: List[LeafSlot] = []
    offset = 0
    for leaf in leaves:
        size = math.prod(leaf.shape)
        slots.append(LeafSlot(offset=offset, size=size,
                              shape=tuple(leaf.shape), dtype=leaf.dtype))
        offset += size
    bucket_elems, n_buckets, pad_elems = stream_layout(
        offset, bucket_bytes, sdt.itemsize, align)
    return BucketPlan(treedef=treedef, slots=tuple(slots),
                      total_elems=offset, bucket_elems=bucket_elems,
                      n_buckets=n_buckets, wire=wire, stream_dtype=sdt,
                      align=align, pad_elems=pad_elems)


def _kernel_on(use_kernel: Optional[bool]) -> bool:
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return use_kernel


def _cast_stream(leaves: List[jax.Array], sdt,
                 use_kernel: Optional[bool]) -> jax.Array:
    """Flatten leaves into one wire-dtype stream. The cast happens on
    the whole stream (fused Pallas cast+copy when ``use_kernel``),
    which is elementwise-identical to casting each leaf before
    concatenation — the bitwise guarantee the tests pin down. Shared by
    ``pack`` (full tree) and ``pack_bucket`` (one stage), so the two
    paths can never drift apart."""
    if not leaves:
        return jnp.zeros((0,), sdt)
    same_dtype = all(l.dtype == leaves[0].dtype for l in leaves)
    if same_dtype:
        stream = jnp.concatenate([l.reshape(-1) for l in leaves])
        if stream.dtype != sdt:
            if _kernel_on(use_kernel):
                from repro.kernels.ops import pack_cast
                stream = pack_cast(stream, sdt)
            else:
                stream = stream.astype(sdt)
        return stream
    return jnp.concatenate([l.reshape(-1).astype(sdt) for l in leaves])


def pack(grads: PyTree, plan: BucketPlan,
         use_kernel: Optional[bool] = None) -> List[jax.Array]:
    """Gradient pytree -> list of ``n_buckets`` wire-dtype bucket arrays
    (``_cast_stream`` + fixed-offset slicing; shard-aligned plans get
    their zero tail here)."""
    leaves = plan.treedef.flatten_up_to(grads)
    stream = _cast_stream(leaves, plan.stream_dtype, use_kernel)
    if plan.pad_elems:
        stream = jnp.concatenate(
            [stream, jnp.zeros((plan.pad_elems,), plan.stream_dtype)])
    bounds = [plan.bucket_bounds(i) for i in range(plan.n_buckets)]
    return [jax.lax.slice(stream, (lo,), (hi,)) for lo, hi in bounds]


def unpack(buckets: Sequence[jax.Array], plan: BucketPlan,
           use_kernel: Optional[bool] = None,
           denom: Optional[int] = None,
           with_sq_norm: bool = False):
    """Bucket arrays -> gradient pytree (original shapes/dtypes).

    ``denom`` (the worker count for the mean) divides after the cast back
    to the accumulation dtype — the same cast-then-divide order (and the
    same division, not a reciprocal multiply) as ``compressed_psum``, so
    the two paths agree bitwise.

    ``with_sq_norm=True`` additionally returns the squared L2 norm of
    the whole (cast-back, divided) gradient stream, computed in one
    fused pass over the contiguous stream — this is how the sync paths
    report ``grad_norm`` without a second full-tree reduction
    (DESIGN.md §8).
    """
    stream = jnp.concatenate(list(buckets))
    sq_norm = None
    acc_dtypes = {s.dtype for s in plan.slots}
    if len(acc_dtypes) == 1:
        acc = next(iter(acc_dtypes))
        if stream.dtype != acc:
            if _kernel_on(use_kernel):
                from repro.kernels.ops import unpack_cast
                stream = unpack_cast(stream, acc)
            else:
                stream = stream.astype(acc)
        if denom is not None:
            stream = stream / denom
        if with_sq_norm:
            sq_norm = jnp.sum(jnp.square(stream.astype(jnp.float32)))
        leaves = [jax.lax.slice(stream, (s.offset,),
                                (s.offset + s.size,)).reshape(s.shape)
                  for s in plan.slots]
    else:
        leaves = []
        for s in plan.slots:
            leaf = jax.lax.slice(stream, (s.offset,),
                                 (s.offset + s.size,))
            leaf = leaf.astype(s.dtype)
            if denom is not None:
                leaf = leaf / denom
            leaves.append(leaf.reshape(s.shape))
        if with_sq_norm:
            sq_norm = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                          for l in leaves)
    tree = jax.tree.unflatten(plan.treedef, leaves)
    return (tree, sq_norm) if with_sq_norm else tree


def bucketed_psum(grads: PyTree, axis_names: Sequence[str],
                  wire: Optional[str] = "bf16",
                  bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                  mean: bool = True,
                  plan: Optional[BucketPlan] = None,
                  use_kernel: Optional[bool] = None,
                  with_sq_norm: bool = False,
                  hierarchy: Optional["Hierarchy"] = None):
    """Drop-in for ``compressed_psum`` issuing one psum per bucket.

    Same contract: cast each gradient element to the wire dtype, sum over
    the data axes, cast back, optionally divide by the worker count —
    but the interconnect sees ``plan.n_buckets`` large collectives
    instead of one per leaf. ``with_sq_norm=True`` returns
    ``(grads, sq_norm)`` with the synced gradients' squared L2 norm from
    one pass over the stream (see ``unpack``).

    ``hierarchy`` replaces each bucket's flat psum with the two-level
    reduce-scatter → all-reduce → all-gather schedule of DESIGN.md §14
    (``hierarchical_psum``); the plan is then laid out shard-aligned
    (``align = hierarchy.n_workers``) so every bucket splits evenly
    across the inner axis.
    """
    if plan is None:
        align = hierarchy.n_workers if hierarchy is not None else 1
        plan = plan_buckets(grads, bucket_bytes, wire, align=align)
    n = jax.lax.axis_size(tuple(axis_names))  # static worker count
    buckets = pack(grads, plan, use_kernel=use_kernel)
    if hierarchy is not None:
        synced = chained(buckets,
                         lambda b: hierarchical_psum(b, hierarchy))
    else:
        synced = chained(buckets,
                         lambda b: jax.lax.psum(b, tuple(axis_names)))
    return unpack(synced, plan, use_kernel=use_kernel,
                  denom=n if mean else None, with_sq_norm=with_sq_norm)


def bucketed_psum_ef(grads: PyTree, residual: PyTree,
                     axis_names: Sequence[str],
                     wire: str = "bf16",
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     mean: bool = True,
                     plan: Optional[BucketPlan] = None,
                     use_kernel: Optional[bool] = None,
                     with_sq_norm: bool = False,
                     hierarchy: Optional["Hierarchy"] = None):
    """Bucketed psum with error feedback (core/compression.py) threaded
    through: q = Q(g + r) is what gets packed and reduced; r' stays
    worker-local. The residual update is identical to the per-leaf
    ``compressed_psum_ef`` path — EF happens before packing, so bucketing
    cannot change it (asserted by the bucketing tests). With
    ``with_sq_norm`` returns ``(synced, new_residual, sq_norm)``."""
    quant, new_residual = apply_error_feedback(grads, residual, wire)
    out = bucketed_psum(quant, axis_names, wire=wire,
                        bucket_bytes=bucket_bytes, mean=mean,
                        plan=plan, use_kernel=use_kernel,
                        with_sq_norm=with_sq_norm, hierarchy=hierarchy)
    if with_sq_norm:
        synced, sq_norm = out
        return synced, new_residual, sq_norm
    return out, new_residual


# ---------------------------------------------------------------------------
# Ready-order bucketing (backward-overlapped sync, DESIGN.md §8)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReadyBucketPlan:
    """A ``BucketPlan`` whose stream is laid out in backward-completion
    order: the stage trees are given in the order the backward pass
    *produces* them (last forward segment first), so every bucket's
    element range is a contiguous run of already-materialized gradients
    and the bucket closes the moment its completing stage's VJP finishes
    — not when the full backward ends.

    ``ready_stage[b]`` is the index (into the ready-ordered stage list)
    of the stage whose gradients complete bucket ``b``; it is
    non-decreasing in ``b`` by construction.
    """

    base: BucketPlan  # treedef = tuple(stage trees, ready order)
    stage_ends: Tuple[int, ...]  # cumulative element end offset per stage
    ready_stage: Tuple[int, ...]  # per bucket

    @property
    def n_buckets(self) -> int:
        return self.base.n_buckets

    @property
    def n_stages(self) -> int:
        return len(self.stage_ends)

    def buckets_ready_at(self, stage_idx: int) -> Tuple[int, ...]:
        return tuple(b for b, s in enumerate(self.ready_stage)
                     if s == stage_idx)

    def describe(self) -> str:
        return (f"{self.base.describe()} over {self.n_stages} stages, "
                f"ready stages {list(self.ready_stage)}")


def plan_ready_buckets(stage_trees: Sequence[PyTree],
                       bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                       wire: Optional[str] = "bf16",
                       align: int = 1) -> ReadyBucketPlan:
    """Lay out per-stage gradient trees (given in backward-completion
    order) as one contiguous stream cut into fixed-size buckets.

    The element values and the per-bucket psum contract are identical to
    ``plan_buckets`` — only *where* each leaf sits in the stream changes
    (completion order instead of pytree order), which is exactly what
    makes overlap possible and exactly what cannot change numerics
    (elementwise cast/sum/cast/divide is position-independent). The
    shard-aligned tail (``align > 1``, ZeRO) belongs to the last bucket,
    so it closes at the same stage as the last real gradient element."""
    stage_trees = tuple(stage_trees)
    if not stage_trees:
        raise ValueError("need at least one stage tree")
    base = plan_buckets(stage_trees, bucket_bytes, wire, align=align)
    ends: List[int] = []
    off = 0
    for t in stage_trees:
        off += sum(math.prod(l.shape) for l in jax.tree.leaves(t))
        ends.append(off)
    assert off == base.total_elems
    ready = []
    for b in range(base.n_buckets):
        _, hi = base.bucket_bounds(b)
        # first stage whose cumulative end covers the bucket's last REAL
        # element (the zero tail of a shard-aligned plan needs no stage)
        hi_real = min(hi, base.total_elems)
        stage = next(i for i, e in enumerate(ends) if e >= hi_real)
        ready.append(stage)
    return ReadyBucketPlan(base=base, stage_ends=tuple(ends),
                           ready_stage=tuple(ready))


def pack_bucket(plan: ReadyBucketPlan, stage_idx: int,
                stage_tree: PyTree, carry: Optional[jax.Array] = None,
                use_kernel: Optional[bool] = None
                ) -> Tuple[List[Tuple[int, jax.Array]], jax.Array]:
    """Feed stage ``stage_idx``'s just-materialized gradients; returns
    ``(ready, carry')`` where ``ready`` is the list of
    ``(bucket_id, wire_array)`` buckets that *closed* at this stage (its
    gradients were their last missing elements) and ``carry'`` is the
    unemitted tail awaiting later stages.

    Stages must be fed in ready order (0, 1, ...). All shapes are static
    — the carry length after each stage is a plan constant — so the
    emission loop unrolls cleanly under jit inside the backward chain
    (training/step.py:make_dp_overlap_train_step, DESIGN.md §8)."""
    flat = _cast_stream(jax.tree.leaves(stage_tree),
                        plan.base.stream_dtype, use_kernel)
    carry_len = 0 if carry is None else carry.shape[0]
    fed_end = plan.stage_ends[stage_idx]
    flat_start = fed_end - flat.shape[0]
    stream_start = flat_start - carry_len

    # lazily materialize carry++flat only for carry-spanning buckets;
    # buckets interior to this stage slice straight out of ``flat``
    joined = None

    def view(lo, hi):
        nonlocal joined
        if lo >= flat_start:
            return jax.lax.slice(flat, (lo - flat_start,),
                                 (hi - flat_start,))
        if joined is None:
            joined = jnp.concatenate([carry, flat])
        return jax.lax.slice(joined, (lo - stream_start,),
                             (hi - stream_start,))

    ready = []
    emitted_end = stream_start
    for b in plan.buckets_ready_at(stage_idx):
        lo, hi = plan.base.bucket_bounds(b)
        # a shard-aligned plan's final bucket extends past the last real
        # element; ONLY that alignment tail may be zero-filled here — a
        # bucket marked ready before its last real element is fed must
        # still trip the assert, never sync zeros in its place
        hi_real = min(hi, plan.base.total_elems)
        assert lo >= stream_start and hi_real <= fed_end, (b, lo, hi)
        arr = view(lo, hi_real)
        if hi > hi_real:
            arr = jnp.concatenate(
                [arr, jnp.zeros((hi - hi_real,), plan.base.stream_dtype)])
        ready.append((b, arr))
        emitted_end = hi_real
    new_carry = view(emitted_end, fed_end)
    return ready, new_carry


# ---------------------------------------------------------------------------
# ZeRO shard layout (reduce-scatter sync mode, DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# With a shard-aligned plan (``align = n_shards``) every bucket splits
# evenly across the DP ranks, so ``psum_scatter`` hands worker ``w`` the
# contiguous chunk ``[lo_b + w*c_b, lo_b + (w+1)*c_b)`` of each reduced
# bucket. A worker's *shard* is the concatenation of its per-bucket
# chunks (bucket order), and the *shard layout* of the whole stream is
# the worker-major concatenation of all shards — the layout the sharded
# optimizer state (delta/m) lives in, and the layout the checkpoint
# resharding path (optim/stream.py) converts from/to.


def shard_chunks(plan: BucketPlan, n_shards: int) -> Tuple[int, ...]:
    """Per-bucket chunk length owned by each of ``n_shards`` workers."""
    sizes = []
    for b in range(plan.n_buckets):
        lo, hi = plan.bucket_bounds(b)
        if (hi - lo) % n_shards:
            raise ValueError(
                f"bucket {b} has {hi - lo} elements, not divisible by "
                f"{n_shards} shards; plan with align={n_shards}")
        sizes.append((hi - lo) // n_shards)
    return tuple(sizes)


def shard_size(plan: BucketPlan, n_shards: int) -> int:
    """Elements per worker shard (== padded_total / n_shards)."""
    return sum(shard_chunks(plan, n_shards))


def local_shard(stream: jax.Array, plan: BucketPlan, n_shards: int,
                shard_idx) -> jax.Array:
    """Worker ``shard_idx``'s shard of a full packed (padded) stream —
    the concatenation of its per-bucket chunks. ``shard_idx`` may be a
    traced scalar (``jax.lax.axis_index`` inside shard_map)."""
    parts = []
    for b, c in enumerate(shard_chunks(plan, n_shards)):
        lo, _ = plan.bucket_bounds(b)
        parts.append(jax.lax.dynamic_slice(stream, (lo + shard_idx * c,),
                                           (c,)))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def split_shard(shard: jax.Array, plan: BucketPlan,
                n_shards: int) -> List[jax.Array]:
    """Inverse bookkeeping of ``local_shard``: cut a worker shard back
    into its per-bucket chunks (static offsets)."""
    chunks = shard_chunks(plan, n_shards)
    out, off = [], 0
    for c in chunks:
        out.append(jax.lax.slice(shard, (off,), (off + c,)))
        off += c
    return out


def shard_perm(plan: BucketPlan, n_shards: int):
    """Gather indices ``perm`` with ``shard_layout = stream[perm]``:
    worker-major, bucket order within each worker. A plain numpy array —
    the permutation is a plan constant used host-side by the checkpoint
    resharding path."""
    import numpy as np

    idx = []
    chunks = shard_chunks(plan, n_shards)
    for w in range(n_shards):
        for b, c in enumerate(chunks):
            lo, _ = plan.bucket_bounds(b)
            idx.append(np.arange(lo + w * c, lo + (w + 1) * c))
    return np.concatenate(idx)


def stream_to_shard_layout(arr, plan: BucketPlan, n_shards: int):
    """Reorder a padded-stream-order array into shard layout."""
    return arr[shard_perm(plan, n_shards)]


def shard_layout_to_stream(arr, plan: BucketPlan, n_shards: int):
    """Inverse of ``stream_to_shard_layout``."""
    import numpy as np

    return arr[np.argsort(shard_perm(plan, n_shards), kind="stable")]


# ---------------------------------------------------------------------------
# Hierarchical collective schedules (topology-aware sync, DESIGN.md §14)
# ---------------------------------------------------------------------------
#
# Over a multi-axis DP mesh (e.g. ("node", "device")) a flat psum makes
# every transfer cross the slowest link. The 2D-torus schedule of
# Yamazaki et al. (arXiv:1903.12650) — and the host-level reduction of
# Goyal et al. (arXiv:1706.02677) — instead runs, per bucket:
#
#   intra-axis reduce-scatter  (cheap links, full bucket)
#   inter-axis all-reduce      (expensive links, 1/inner_size shard)
#   intra-axis all-gather      (cheap links, full bucket)
#
# so the expensive inter-node link carries ``1/inner_size`` of the bucket
# instead of all of it. Ranks are linearized row-major over the DP axis
# tuple — ``w = outer_lin * inner_size + inner_lin`` — exactly the
# ``_dp_linear_index`` order (training/step.py), which is what lets the
# ZeRO double-scatter below hand every worker the *same* chunk the flat
# ``psum_scatter`` would (after the ``inner_major_perm`` pre-permutation)
# and keeps param slicing, optimizer-state layout and checkpoint
# resharding untouched.
#
# Numerics: the bucket is accumulated in f32 throughout both stages and
# rounded to the wire dtype exactly once ("round-once"), so the result is
# association-stable at wire precision — equal to the flat collective
# bitwise whenever the additions are order-exact (the property tests and
# the slow collective battery pin this with exponent-bounded data), to
# last-ulp otherwise. A reassociated reduction can never be
# *unconditionally* bitwise-identical to the flat fold (DESIGN.md §14);
# the f32 accumulator is what pins the difference to rounding-boundary
# ulps instead of wire-precision drift.


@dataclasses.dataclass(frozen=True)
class Hierarchy:
    """Static spec of a two-level collective schedule over the DP axes.

    ``outer`` are the inter-node (expensive) mesh axes, ``inner`` the
    intra-node (cheap) ones; the flat DP rank is the row-major
    linearization ``w = outer_lin * inner_size + inner_lin`` — the same
    order ``_dp_linear_index`` and a flat ``psum_scatter`` over the full
    axis tuple use.
    """

    outer: Tuple[str, ...]
    inner: Tuple[str, ...]
    outer_size: int
    inner_size: int

    @property
    def n_workers(self) -> int:
        return self.outer_size * self.inner_size

    def describe(self) -> str:
        return (f"hier[{'x'.join(self.outer)}({self.outer_size}) | "
                f"{'x'.join(self.inner)}({self.inner_size})]")


def make_hierarchy(dp_axes: Sequence[str], mesh_shape,
                   split: int) -> Hierarchy:
    """Split ``dp_axes`` into outer ``dp_axes[:split]`` / inner
    ``dp_axes[split:]``. ``mesh_shape`` maps axis name -> size (a
    ``Mesh.shape`` mapping works as-is). Both factors must be real
    (size >= 2): a size-1 stage is a flat collective wearing a costume —
    callers should fall back to flat instead (comm_plan.py does)."""
    dp_axes = tuple(dp_axes)
    if not 1 <= split < len(dp_axes):
        raise ValueError(
            f"hier_split must be in [1, {len(dp_axes) - 1}] for dp_axes "
            f"{dp_axes}, got {split}")
    outer, inner = dp_axes[:split], dp_axes[split:]
    outer_size = math.prod(int(mesh_shape[a]) for a in outer)
    inner_size = math.prod(int(mesh_shape[a]) for a in inner)
    if outer_size < 2 or inner_size < 2:
        raise ValueError(
            f"hierarchical schedule needs both stages >= 2 ranks, got "
            f"outer={outer}:{outer_size} inner={inner}:{inner_size}; "
            "use the flat schedule on this mesh")
    return Hierarchy(outer=outer, inner=inner,
                     outer_size=outer_size, inner_size=inner_size)


def inner_major_perm(x, outer_size: int, inner_size: int):
    """Reorder a flat stream so the hierarchical double reduce-scatter
    (inner stage first) hands rank ``w = n*inner_size + d`` exactly the
    chunk the flat ``psum_scatter`` would: viewing the stream as
    ``n_workers`` chunks, chunk ``w = n*b + d`` must land in inner
    position ``d``, outer position ``n`` — i.e. the stream is re-laid
    inner-major. Works on numpy and jax arrays (pure reshape/transpose),
    so the Hypothesis property tests reuse it verbatim."""
    a, b = outer_size, inner_size
    c = x.shape[0] // (a * b)
    return x.reshape(a, b, c).transpose(1, 0, 2).reshape(-1)


def inner_major_unperm(x, outer_size: int, inner_size: int):
    """Inverse of ``inner_major_perm`` (used after the two-level
    all-gather to restore stream order)."""
    a, b = outer_size, inner_size
    c = x.shape[0] // (a * b)
    return x.reshape(b, a, c).transpose(1, 0, 2).reshape(-1)


def hierarchical_psum(bucket: jax.Array, hier: Hierarchy) -> jax.Array:
    """Two-level all-reduce of one packed bucket: f32 reduce-scatter over
    the inner axes, f32 all-reduce over the outer axes on the
    ``1/inner_size`` shard, one rounding to the bucket dtype, all-gather
    back over the inner axes. The bucket length must be a multiple of
    ``inner_size`` (a plan with ``align = hier.n_workers`` guarantees
    it)."""
    if bucket.shape[0] % hier.inner_size:
        raise ValueError(
            f"bucket of {bucket.shape[0]} elements does not split over "
            f"{hier.inner_size} inner ranks; plan with "
            f"align={hier.n_workers}")
    wire_dt = bucket.dtype
    shard = jax.lax.psum_scatter(bucket.astype(jnp.float32), hier.inner,
                                 scatter_dimension=0, tiled=True)
    shard = jax.lax.psum(shard, hier.outer)
    return jax.lax.all_gather(shard.astype(wire_dt), hier.inner,
                              axis=0, tiled=True)


def hierarchical_psum_scatter(bucket: jax.Array,
                              hier: Hierarchy) -> jax.Array:
    """Two-level reduce-scatter of one packed bucket (ZeRO sync): after
    the ``inner_major_perm`` pre-permutation, the inner then outer f32
    reduce-scatters leave rank ``w = n*inner_size + d`` holding exactly
    the flat ``psum_scatter`` chunk ``w`` — shard ownership, and with it
    ``_dp_linear_index`` param slicing and the sharded optimizer-state
    layout, are unchanged by the hierarchy. Rounds to the bucket dtype
    once, after both reduction stages."""
    if bucket.shape[0] % hier.n_workers:
        raise ValueError(
            f"bucket of {bucket.shape[0]} elements does not split over "
            f"{hier.n_workers} ranks; plan with align={hier.n_workers}")
    f = inner_major_perm(bucket.astype(jnp.float32),
                         hier.outer_size, hier.inner_size)
    s = jax.lax.psum_scatter(f, hier.inner, scatter_dimension=0,
                             tiled=True)
    s = jax.lax.psum_scatter(s, hier.outer, scatter_dimension=0,
                             tiled=True)
    return s.astype(bucket.dtype)


def hierarchical_all_gather(shard: jax.Array,
                            hier: Hierarchy) -> jax.Array:
    """Two-level inverse of the flat ``all_gather`` over all DP axes:
    gather over the outer axes, then the inner axes, then undo the
    inner-major layout. Pure data movement (dtype-preserving), so it is
    bitwise-identical to the flat gather for any input."""
    g = jax.lax.all_gather(shard, hier.outer, axis=0, tiled=True)
    g = jax.lax.all_gather(g, hier.inner, axis=0, tiled=True)
    return inner_major_unperm(g, hier.outer_size, hier.inner_size)


# ---------------------------------------------------------------------------
# Leaf-segment map (stream-layout LARS trust ratios, DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# LARS needs per-leaf ||p||/||g|| over the *packed* stream: segment id i
# marks every element of plan.slots[i]; the shard-alignment pad gets its
# own trailing id len(slots), so it can never contaminate a real leaf's
# norm. Per-segment squared norms are ``jax.ops.segment_sum`` reductions
# — the one reduction primitive shared by the per-leaf reference
# optimizer (optim/lars.py) and every stream path, which is what keeps
# the two bitwise in lockstep on identical operands (CPU/TPU sums are
# fold-order-sensitive; tests/test_lars_stream.py pins the equality).


def segment_ids_stream(plan: BucketPlan):
    """int32[padded_total] mapping each stream position to its leaf index
    in ``plan.slots`` order; the alignment pad maps to the extra trailing
    segment ``len(plan.slots)`` (never trusted, never decayed)."""
    import numpy as np

    ids = np.full((plan.padded_total,), len(plan.slots), np.int32)
    for i, s in enumerate(plan.slots):
        ids[s.offset:s.offset + s.size] = i
    return ids


def segment_sq_partials(x: jax.Array, seg_ids, num_segments: int
                        ) -> jax.Array:
    """f32[num_segments] per-segment sums of squares of flat ``x``.

    ``x``/``seg_ids`` may be the full padded stream or any sub-slice of
    it (a ZeRO worker shard): segment_sum accumulates each segment
    independently of where its elements sit, so psum'ing per-shard
    partials over the DP axes recovers the full-stream per-leaf norms —
    exactly when the additions are order-exact (the Hypothesis property
    test pins this with power-of-two data), to last-ulp otherwise
    (which is why cross-decomposition parity is allclose, not bitwise;
    DESIGN.md §11)."""
    return jax.ops.segment_sum(
        jnp.square(x.astype(jnp.float32)),
        jnp.asarray(seg_ids), num_segments=num_segments)
