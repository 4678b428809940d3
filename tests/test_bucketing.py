"""Bucketed gradient all-reduce (distributed/bucketing.py, DESIGN.md §6).

Single-device tests cover the pack/unpack layout and the Pallas
cast+copy kernel pair (interpret mode); the multi-device equivalence
tests (bucketed == per-leaf bitwise, EF residual parity) run in
subprocesses on a virtual host mesh, like tests/test_distributed.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import parse_compression
from repro.distributed.bucketing import (
    pack,
    plan_buckets,
    unpack,
)

ENV = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}


def run_py(body: str, timeout=420) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=ENV, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# plan / parse
# ---------------------------------------------------------------------------


def test_parse_compression():
    assert parse_compression(None) == (None, False)
    assert parse_compression("none") == (None, False)
    assert parse_compression("bf16") == ("bf16", False)
    assert parse_compression("f16") == ("f16", False)
    assert parse_compression("bf16+bucketed") == ("bf16", True)
    assert parse_compression("f16+bucketed") == ("f16", True)
    assert parse_compression("bucketed") == (None, True)
    with pytest.raises(ValueError):
        parse_compression("int8")
    with pytest.raises(ValueError, match="conflicting wire"):
        parse_compression("bf16+f16")
    with pytest.raises(ValueError, match="duplicate"):
        parse_compression("bucketed+bucketed")


def test_plan_collective_count_bound():
    """n_buckets == ceil(total_wire_bytes / bucket_bytes), no
    fragmentation waste even with many odd-size leaves."""
    leaves = {f"l{i}": jnp.zeros((97 + i,)) for i in range(50)}
    total = sum(x.size for x in jax.tree.leaves(leaves))
    for bucket_bytes in (256, 1024, 1 << 20):
        plan = plan_buckets(leaves, bucket_bytes=bucket_bytes, wire="bf16")
        expect = max(1, -(-total * 2 // bucket_bytes))
        assert plan.n_buckets == expect, (bucket_bytes, plan.n_buckets)
        lo, hi = plan.bucket_bounds(plan.n_buckets - 1)
        assert hi == total  # last bucket truncated, not zero-padded


def test_plan_no_wire_keeps_leaf_dtype():
    """wire=None must not upcast: the stream (and the psum) stay in the
    leaves' own dtype, and bucket sizing uses that itemsize."""
    tree = {"a": jnp.zeros((100,), jnp.bfloat16),
            "b": jnp.zeros((28,), jnp.bfloat16)}
    plan = plan_buckets(tree, bucket_bytes=64, wire=None)
    assert plan.stream_dtype == jnp.dtype(jnp.bfloat16)
    assert plan.bucket_elems == 32  # 64 B / 2 B, not / 4 B
    buckets = pack(tree, plan, use_kernel=False)
    assert all(b.dtype == jnp.bfloat16 for b in buckets)
    mixed = {"a": jnp.zeros((4,), jnp.float32),
             "b": jnp.zeros((4,), jnp.bfloat16)}
    with pytest.raises(ValueError, match="uniform leaf dtypes"):
        plan_buckets(mixed, bucket_bytes=64, wire=None)


def test_error_feedback_rejected_outside_shardmap():
    from repro.configs import OptimizerConfig, get_config, reduced_config
    from repro.launch.train import build_train_setup
    cfg = reduced_config(get_config("resnet50"))
    with pytest.raises(ValueError, match="shard_map"):
        build_train_setup(cfg, global_batch=8, seq_len=16,
                          opt_cfg=OptimizerConfig(), steps_per_epoch=5,
                          error_feedback=True)


# ---------------------------------------------------------------------------
# pack/unpack round-trip
# ---------------------------------------------------------------------------


ODD_TREE_SHAPES = [(3, 7), (129,), (1,), (), (50, 3, 2), (1000,)]


def _odd_tree(dtype):
    key = jax.random.PRNGKey(0)
    tree = {}
    for i, shp in enumerate(ODD_TREE_SHAPES):
        key, sub = jax.random.split(key)
        tree[f"leaf{i}"] = jax.random.normal(sub, shp).astype(dtype)
    return tree


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_roundtrip_identity_per_leaf(dtype, use_kernel):
    """pack -> unpack restores every leaf exactly once the values are
    wire-representable (odd sizes, scalars, padding across buckets)."""
    tree = _odd_tree(dtype)
    # make values exactly representable in the wire dtype so the
    # round-trip is identity, not just close
    tree = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(dtype), tree)
    plan = plan_buckets(tree, bucket_bytes=512, wire="bf16")
    assert plan.n_buckets > 1  # leaves genuinely span buckets
    buckets = pack(tree, plan, use_kernel=use_kernel)
    assert all(b.dtype == jnp.bfloat16 for b in buckets)
    out = unpack(buckets, plan, use_kernel=use_kernel)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_roundtrip_no_wire_cast_is_exact():
    """wire=None: bucketing alone (collective fusion without
    compression) is bit-exact for arbitrary f32 values."""
    tree = _odd_tree(jnp.float32)
    plan = plan_buckets(tree, bucket_bytes=512, wire=None)
    out = unpack(pack(tree, plan, use_kernel=False), plan,
                 use_kernel=False)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("wire", [jnp.bfloat16, jnp.float16])
def test_kernel_matches_ref_oracle(wire):
    """Pallas cast+copy kernel (interpret mode) == ref.cast_copy on odd
    lengths that exercise the lane padding. f16 goes through the bit-
    level kernels (round to nearest even, subnormals, overflow), so its
    values span every f16 range."""
    from repro.kernels import ref
    from repro.kernels.bucket_ops import pack_cast, unpack_cast
    key = jax.random.PRNGKey(1)
    for n in (1, 127, 128, 129, 1000, 4096):
        x = jax.random.normal(key, (n,), jnp.float32)
        if wire == jnp.float16:
            x = x * jnp.exp2(jnp.arange(n) % 48 - 30.0)
        got = pack_cast(x, wire, interpret=True)
        want = ref.cast_copy(x, wire)
        assert got.shape == (n,) and got.dtype == wire
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        back = unpack_cast(got, jnp.float32, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(back), np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# multi-device equivalence (2-device host mesh, subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                   jnp.float16])
def test_after_keeps_every_value(dtype):
    """``after`` ties x to a dependency without changing one bit of x
    (-0.0, subnormals, inf and NaN included), whatever the dependency
    holds (a non-finite dependency contributes +0.0 too)."""
    from repro.core.compression import after
    x = jnp.asarray([0.0, -0.0, 1.5, -2.25, 1e-7, jnp.inf, -jnp.inf,
                     jnp.nan], dtype)
    for dep in (jnp.asarray([-3.0]), jnp.asarray([jnp.nan]),
                jnp.asarray([-jnp.inf, 1.0]), jnp.zeros((2, 2))):
        got = jax.jit(after)(x, dep)
        assert got.dtype == x.dtype
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint16 if x.dtype.itemsize == 2
                                 else np.uint32),
            np.asarray(x).view(np.uint16 if x.dtype.itemsize == 2
                               else np.uint32))


def test_bucketed_psum_matches_per_leaf_bitwise():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.compression import compressed_psum
        from repro.distributed.bucketing import bucketed_psum
        mesh = make_mesh((2,), ('data',))
        key = jax.random.PRNGKey(0)
        grads = {'a': jax.random.normal(key, (2, 300, 7)),
                 'b': jax.random.normal(key, (2, 129)),
                 'c': jax.random.normal(key, (2,))}
        specs = jax.tree.map(lambda _: P('data'), grads)
        outs = {'a': P(), 'b': P(), 'c': P()}
        def leaf(g):
            local = jax.tree.map(lambda x: x[0] if x.ndim > 1 else x[0:1][0],
                                 g)
            return compressed_psum(local, ('data',), 'bf16')
        def bucket(g):
            local = jax.tree.map(lambda x: x[0] if x.ndim > 1 else x[0:1][0],
                                 g)
            return bucketed_psum(local, ('data',), wire='bf16',
                                 bucket_bytes=1024, use_kernel=False)
        kw = dict(mesh=mesh, in_specs=(specs,), out_specs=outs,
                  check_vma=False)
        r1 = shard_map(leaf, **kw)(grads)
        r2 = shard_map(bucket, **kw)(grads)
        for x, y in zip(jax.tree.leaves(r1), jax.tree.leaves(r2)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print('BITWISE_OK')
    """)
    assert "BITWISE_OK" in out


def test_error_feedback_residuals_identical_both_paths():
    """EF happens before packing, so residuals (and synced grads) must
    accumulate identically over multiple steps in both paths."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.compression import (compressed_psum_ef,
                                            init_error_feedback)
        from repro.distributed.bucketing import bucketed_psum_ef
        mesh = make_mesh((2,), ('data',))
        key = jax.random.PRNGKey(0)
        grads = {'a': jax.random.normal(key, (2, 300, 7)),
                 'b': jax.random.normal(key, (2, 129))}
        specs = jax.tree.map(lambda _: P('data'), grads)
        gspec = {'a': P(), 'b': P()}
        def leaf(g, r):
            local = jax.tree.map(lambda x: x[0], g)
            return compressed_psum_ef(local, r, ('data',), 'bf16')
        def bucket(g, r):
            local = jax.tree.map(lambda x: x[0], g)
            return bucketed_psum_ef(local, r, ('data',), wire='bf16',
                                    bucket_bytes=1024, use_kernel=False)
        kw = dict(mesh=mesh,
                  in_specs=(specs, jax.tree.map(lambda _: P(), gspec)),
                  out_specs=(gspec, jax.tree.map(lambda _: P(), gspec)),
                  check_vma=False)
        r_leaf = init_error_feedback({'a': grads['a'][0],
                                      'b': grads['b'][0]})
        r_buck = jax.tree.map(lambda x: x, r_leaf)
        for step in range(4):
            g = jax.tree.map(lambda x: x * (1.0 + 0.37 * step), grads)
            s1, r_leaf = shard_map(leaf, **kw)(g, r_leaf)
            s2, r_buck = shard_map(bucket, **kw)(g, r_buck)
            for x, y in zip(jax.tree.leaves((s1, r_leaf)),
                            jax.tree.leaves((s2, r_buck))):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            # residuals are genuinely nonzero (EF is doing something)
            assert max(float(jnp.abs(x).max())
                       for x in jax.tree.leaves(r_leaf)) > 0
        print('EF_OK')
    """)
    assert "EF_OK" in out


def test_hlo_collective_count_and_dtype():
    """The fusion claim, verified from compiled HLO: bucketed mode
    issues <= ceil(total_wire_bytes/bucket_bytes) all-reduces for the
    gradients, vs one per leaf in per-leaf mode, at the wire dtype."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.compression import compressed_psum
        from repro.distributed.bucketing import bucketed_psum, plan_buckets
        from repro.launch.hlo_analysis import analyze_hlo, comm_report
        mesh = make_mesh((2,), ('data',))
        key = jax.random.PRNGKey(0)
        grads = {f'l{i}': jax.random.normal(key, (97 + i,))
                 for i in range(20)}
        specs = jax.tree.map(lambda _: P(), grads)
        BUCKET = 1024
        def leaf(g):
            return compressed_psum(g, ('data',), 'f16')
        def bucket(g):
            return bucketed_psum(g, ('data',), wire='f16',
                                 bucket_bytes=BUCKET, use_kernel=False)
        kw = dict(mesh=mesh, in_specs=(specs,), out_specs=specs,
                  check_vma=False)
        counts = {}
        for name, fn in (('leaf', leaf), ('bucket', bucket)):
            txt = jax.jit(shard_map(fn, **kw)).lower(grads)\
                .compile().as_text()
            cr = comm_report(analyze_hlo(txt, 2))
            ar = cr['per_op'].get('all-reduce', {})
            counts[name] = ar.get('executions_per_step', 0)
            assert any('f16' in d for d in ar.get('dtype_bytes', {})), ar
        plan = plan_buckets(grads, BUCKET, 'f16')
        total_wire = plan.total_elems * 2
        bound = -(-total_wire // BUCKET)
        assert counts['bucket'] <= bound, (counts, bound)
        assert counts['leaf'] == len(grads), counts
        assert counts['bucket'] < counts['leaf']
        print('HLO_OK', counts)
    """)
    assert "HLO_OK" in out


@pytest.mark.slow
def test_shardmap_bucketed_mode_trains_identically():
    """End-to-end: dp_mode=shardmap with compression='bf16+bucketed'
    produces the same loss trajectory as per-leaf 'bf16' (ResNet-50,
    2 workers). The sync primitive itself is bitwise-identical (tested
    above); at whole-program level XLA may still fuse/reorder *other*
    reductions (BN batch stats) differently between the two compiles,
    so the trajectory check uses a tight tolerance instead of ==."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import OptimizerConfig, get_config, reduced_config
        from repro.launch.train import build_train_setup
        cfg = reduced_config(get_config('resnet50'))
        mesh = make_mesh((2, 1), ('data', 'model'))
        losses = {}
        for comp in ('bf16', 'bf16+bucketed'):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=8, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=5, mesh=mesh,
                dp_mode='shardmap', seed=0, sync_bn=True,
                compression=comp, bucket_bytes=4096)
            ls = []
            for s in range(3):
                batch = put({k: jnp.asarray(v)
                             for k, v in data.batch_at(s).items()})
                state, metrics = step(state, batch)
                ls.append(float(metrics['loss']))
            losses[comp] = ls
        np.testing.assert_allclose(losses['bf16'],
                                   losses['bf16+bucketed'],
                                   rtol=1e-5, atol=0)
        print('TRAIN_OK', losses['bf16'])
    """)
    assert "TRAIN_OK" in out
