"""ZeRO reduce-scatter sync mode (DESIGN.md §9).

Fast single-process tests cover the decay-mask regression, the
wd-stream codec, the per-element-decay fused kernel, the shard-layout
permutation, and the mode's validation errors. The step-level parity
matrix — zero vs bucketed and zero-overlap vs overlap, bitwise, across
{plain, error-feedback} x {bf16, f16} — plus the checkpoint boundary
round-trip and the HLO reduce-scatter proof run in subprocesses on
virtual host meshes (marked ``slow``; the fast CI job skips them, the
``-m slow`` job runs them).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, ParallelConfig, TrainConfig
from repro.distributed.bucketing import (
    local_shard,
    plan_buckets,
    shard_chunks,
    shard_layout_to_stream,
    shard_size,
    stream_to_shard_layout,
)
from repro.launch.mesh import make_mesh
from repro.optim.rmsprop_warmup import _decay_mask
from repro.optim.stream import decay_wd_stream, make_stream_optimizer

ENV8 = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}
ENV2 = {**ENV8, "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}


def run_py(body: str, env=ENV8, timeout=600) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# decay mask: substring-safe exact-key matching (regression)
# ---------------------------------------------------------------------------


def test_decay_mask_exact_key_not_substring():
    """NO_DECAY entries match path fragments by exact equality only: a
    param literally named 'Dense_bias_proj' (contains 'bias') or
    'Dscale' (contains both 'D' and 'scale') must stay decayed, while
    exact 'bias'/'scale'/'D' keys are exempt wherever they sit."""
    params = {
        "fc": {"w": jnp.zeros(3), "bias": jnp.zeros(3),
               "Dense_bias_proj": jnp.zeros(3)},
        "norm": {"scale": jnp.zeros(3), "Dscale": jnp.zeros(3),
                 "scales": jnp.zeros(3)},
        "ssm": {"D": jnp.zeros(3), "blockD": jnp.zeros(3)},
    }
    mask = _decay_mask(params)
    assert mask["fc"]["w"] is True
    assert mask["fc"]["bias"] is False
    assert mask["fc"]["Dense_bias_proj"] is True  # the regression
    assert mask["norm"]["scale"] is False
    assert mask["norm"]["Dscale"] is True
    assert mask["norm"]["scales"] is True
    assert mask["ssm"]["D"] is False
    assert mask["ssm"]["blockD"] is True


def test_decay_mask_outer_module_named_bias_exempts_subtree():
    # any exact NO_DECAY fragment on the path exempts the leaf — the
    # longstanding per-component semantics, now pinned
    params = {"bias": {"w": jnp.zeros(2)}, "layer": {"w": jnp.zeros(2)}}
    mask = _decay_mask(params)
    assert mask["bias"]["w"] is False
    assert mask["layer"]["w"] is True


def test_wd_stream_places_decay_and_zero_pad():
    tree = {"a": {"w": jnp.zeros((5,)), "bias": jnp.zeros((3,))},
            "z": jnp.zeros((6,))}
    plan = plan_buckets(tree, bucket_bytes=4 * 4, wire=None, align=4)
    wd = decay_wd_stream(tree, plan, 0.5)
    assert wd.shape == (plan.padded_total,)
    # tree order: a/bias (3), a/w (5), z (6) = 14 elems, pad to align
    assert plan.total_elems == 14
    np.testing.assert_array_equal(wd[:3], 0.0)  # bias exempt
    np.testing.assert_array_equal(wd[3:14], 0.5)
    np.testing.assert_array_equal(wd[14:], 0.0)  # alignment pad


# ---------------------------------------------------------------------------
# shard layout: permutation round-trip + local_shard agreement
# ---------------------------------------------------------------------------


def test_shard_layout_roundtrip_and_local_shard():
    tree = {f"l{i}": jnp.arange(i * 7 + 1, dtype=jnp.float32)
            for i in range(6)}
    n = 4
    plan = plan_buckets(tree, bucket_bytes=13 * 4, wire=None, align=n)
    total = plan.padded_total
    assert total % n == 0
    stream = np.arange(total, dtype=np.float32)
    lay = stream_to_shard_layout(stream, plan, n)
    np.testing.assert_array_equal(
        shard_layout_to_stream(lay, plan, n), stream)
    s = shard_size(plan, n)
    assert s * n == total
    for w in range(n):
        got = np.asarray(local_shard(jnp.asarray(stream), plan, n, w))
        np.testing.assert_array_equal(got, lay[w * s:(w + 1) * s])
    # chunks tile each bucket exactly
    for b, c in enumerate(shard_chunks(plan, n)):
        lo, hi = plan.bucket_bounds(b)
        assert c * n == hi - lo


# ---------------------------------------------------------------------------
# fused kernel: per-element wd array == scalar wd, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wd", [0.0, 1e-4])
def test_fused_update_wd_array_matches_scalar(wd):
    from repro.core.optimizer import HybridHyper
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    shape = (3, 130)  # non-multiple of 128 lanes: exercises padding
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    p = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    d = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.abs(jnp.asarray(rng.standard_normal(shape), jnp.float32))
    h = HybridHyper(eta=jnp.float32(0.1), alpha_sgd=jnp.float32(0.4))
    ref = ops.fused_hybrid_update(g, p, d, m, h, wd)
    got = ops.fused_hybrid_update(g, p, d, m, h,
                                  jnp.full(shape, wd, jnp.float32))
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_optimizer_matches_tree_optimizer_elementwise():
    """One update on a packed stream == the per-leaf tree update packed
    afterwards, bitwise — the single-process core of the mode's parity
    claim (8-device step-level parity runs in the slow sweep)."""
    from repro.optim import make_optimizer

    cfg = OptimizerConfig()
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.standard_normal((7, 3)), jnp.float32),
              "bias": jnp.asarray(rng.standard_normal((5,)), jnp.float32)}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    tree_opt = make_optimizer(cfg, steps_per_epoch=5, global_batch=32)
    st = tree_opt.init(params)
    new_p, new_st, _ = tree_opt.update(params, grads, st)

    plan = plan_buckets(params, bucket_bytes=16, wire=None, align=2)
    sopt = make_stream_optimizer(cfg, steps_per_epoch=5, global_batch=32)
    zst = sopt.init(plan.padded_total)

    def to_stream(tree):
        flat = np.concatenate([np.asarray(l).reshape(-1)
                               for l in plan.treedef.flatten_up_to(tree)])
        return jnp.asarray(np.concatenate(
            [flat, np.zeros(plan.pad_elems, np.float32)]))

    wd = jnp.asarray(sopt.wd_stream(params, plan))
    p2, d2, m2, _ = sopt.update_shard(
        to_stream(params), to_stream(grads), zst["delta"], zst["m"],
        zst["step"], wd)
    np.testing.assert_array_equal(np.asarray(p2),
                                  np.asarray(to_stream(new_p)))
    np.testing.assert_array_equal(np.asarray(d2[:plan.total_elems]),
                                  np.asarray(to_stream(new_st["delta"])
                                             )[:plan.total_elems])
    np.testing.assert_array_equal(np.asarray(m2[:plan.total_elems]),
                                  np.asarray(to_stream(new_st["m"])
                                             )[:plan.total_elems])


# ---------------------------------------------------------------------------
# validation errors
# ---------------------------------------------------------------------------


def test_zero_requires_bucketed_compression():
    from repro.training.step import make_dp_shardmap_train_step

    cfg = TrainConfig(optimizer=OptimizerConfig(),
                      parallel=ParallelConfig(compression="bf16",
                                              zero_dp=True))
    with pytest.raises(ValueError, match="bucketed"):
        make_dp_shardmap_train_step(object(), object(), cfg, None,
                                    ("data",))


def test_zero_requires_stream_optimizer():
    from repro.optim import make_optimizer
    from repro.training.step import make_dp_shardmap_train_step

    opt = make_optimizer(OptimizerConfig(), 5, 32)
    cfg = TrainConfig(optimizer=OptimizerConfig(),
                      parallel=ParallelConfig(
                          compression="bf16+bucketed", zero_dp=True))
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    with pytest.raises(ValueError, match="stream optimizer"):
        make_dp_shardmap_train_step(object(), opt, cfg, mesh, ("data",))


def test_stream_optimizer_rejects_unsupported_kind():
    # momentum_sgd is stream-supported now (the zero x sgd audit cells,
    # DESIGN.md §12); kinds outside the stream family still raise
    with pytest.raises(ValueError, match="rmsprop_warmup"):
        make_stream_optimizer(OptimizerConfig(kind="adamw"), 5, 32)


def test_zero_rejected_outside_shardmap():
    from repro.configs import get_config, reduced_config
    from repro.launch.train import build_train_setup

    cfg = reduced_config(get_config("resnet50"))
    with pytest.raises(ValueError, match="shard_map"):
        build_train_setup(cfg, global_batch=8, seq_len=16,
                          opt_cfg=OptimizerConfig(), steps_per_epoch=5,
                          dp_mode="gspmd", zero_dp=True,
                          compression="bf16+bucketed")


def test_zero_without_mesh_raises_cleanly():
    from repro.configs import get_config, reduced_config
    from repro.launch.train import build_train_setup

    cfg = reduced_config(get_config("resnet50"))
    with pytest.raises(ValueError, match="mesh"):
        build_train_setup(cfg, global_batch=8, seq_len=16,
                          opt_cfg=OptimizerConfig(), steps_per_epoch=5,
                          dp_mode="shardmap", mesh=None, zero_dp=True,
                          compression="bf16+bucketed")


def test_zero_padded_total_rejects_unbucketed():
    from repro.optim.stream import zero_padded_total

    with pytest.raises(ValueError, match="bucketed"):
        zero_padded_total({"w": jnp.zeros((4,))}, "bf16", 8192, 8)


# ---------------------------------------------------------------------------
# step-level parity matrix (subprocess, 8-device virtual mesh, slow)
# ---------------------------------------------------------------------------

_PARITY_HEADER = """
    WIRE = '{wire}'
    EF = {ef}
"""

_PARITY_BODY = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import OptimizerConfig, get_config, reduced_config
    from repro.distributed.bucketing import (plan_buckets,
                                             plan_ready_buckets,
                                             stream_to_shard_layout)
    from repro.launch.train import build_train_setup
    cfg = reduced_config(get_config('resnet50'))
    mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
    N = jax.device_count()
    BB = 8192

    def run(overlap, zero):
        model, state, step, data, put, _ = build_train_setup(
            cfg, global_batch=8, seq_len=16, opt_cfg=OptimizerConfig(),
            steps_per_epoch=5, mesh=mesh, dp_mode='shardmap', seed=0,
            compression=WIRE + '+bucketed', bucket_bytes=BB,
            error_feedback=EF, overlap_comm=overlap, zero_dp=zero)
        for s in range(3):
            batch = put({k: jnp.asarray(v)
                         for k, v in data.batch_at(s).items()})
            state, metrics = step(state, batch)
        return model, state, metrics

    def to_shard_layout(tree, plan):
        flat = np.concatenate([np.asarray(l).reshape(-1)
                               for l in plan.treedef.flatten_up_to(tree)])
        flat = np.concatenate([flat,
                               np.zeros(plan.pad_elems, flat.dtype)])
        return stream_to_shard_layout(flat, plan, N)

    def check(name, ref, zro, plan, to_plan_tree):
        s0, m0 = ref
        s1, m1 = zro
        assert float(m0['loss']) == float(m1['loss']), name
        keys = ['params', 'model_state'] + (['ef_residual'] if EF else [])
        for key in keys:
            for a, b in zip(jax.tree.leaves(s0[key]),
                            jax.tree.leaves(s1[key])):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=name + ':' + key)
        if EF:
            nz = max(float(jnp.abs(x).max())
                     for x in jax.tree.leaves(s1['ef_residual']))
            assert nz > 0, name  # EF genuinely active
        assert int(s1['opt']['step']) == int(s0['opt']['step']) == 3
        # opt state: tree layout -> the zero run's shard layout, bitwise
        for f in ('delta', 'm'):
            want = to_shard_layout(to_plan_tree(s0['opt'][f]), plan)
            np.testing.assert_array_equal(
                want, np.asarray(s1['opt'][f]),
                err_msg=name + ':opt.' + f)

    # ---- plain bucketed vs zero ----
    model, sb, mb = run(overlap=False, zero=False)
    _, sz, mz = run(overlap=False, zero=True)
    plan_p = plan_buckets(sb['params'], BB, WIRE, align=N)
    check('plain', (sb, mb), (sz, mz), plan_p, lambda t: t)

    # ---- overlap vs zero-overlap ----
    model, so, mo = run(overlap=True, zero=False)
    _, szo, mzo = run(overlap=True, zero=True)
    mstate0 = jax.tree.map(lambda x: x[0], so['model_state'])
    dummy = {'images': jnp.zeros((8, 32, 32, 3)),
             'labels': jnp.zeros((8,), jnp.int32)}
    staged = model.loss_segments(so['params'], mstate0, dummy, 0.0)

    def split_rev(tree):
        return tuple(reversed(staged.split_tree(tree)))

    plan_o = plan_ready_buckets(list(split_rev(so['params'])), BB, WIRE,
                                align=N).base
    check('overlap', (so, mo), (szo, mzo), plan_o, split_rev)
    print('ZERO_PARITY_OK')
"""


@pytest.mark.slow
@pytest.mark.parametrize("ef", [False, True])
@pytest.mark.parametrize("wire", ["bf16", "f16"])
def test_zero_bitwise_parity_matrix_8dev(ef, wire):
    """Acceptance: --zero end state (params, opt incl. the shard-layout
    delta/m, BN stats, EF residuals) bitwise-equals the all-reduce
    bucketed path after 3 steps on the 8-virtual-device mesh — for both
    the plain bucketed and the backward-overlapped variant."""
    body = (textwrap.dedent(_PARITY_HEADER).format(ef=ef, wire=wire)
            + textwrap.dedent(_PARITY_BODY))
    out = run_py(body)
    assert "ZERO_PARITY_OK" in out


@pytest.mark.slow
def test_zero_bitwise_parity_two_dp_axes_8dev():
    """The dryrun conv cell runs pure DP over BOTH mesh axes: the zero
    step's row-major rank linearization (`_dp_linear_index`) must match
    psum_scatter/all_gather's group order over an axis tuple, or every
    worker updates the wrong shard. Verified by bitwise parity vs the
    all-reduce path on a (4, 2) mesh with dp_axes=('data', 'model')."""
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import (OptimizerConfig, ParallelConfig,
                                   TrainConfig, get_config,
                                   reduced_config)
        from repro.distributed.bucketing import (plan_buckets,
                                                 stream_to_shard_layout)
        from repro.models import build_model, init_model_state
        from repro.optim import make_optimizer
        from repro.optim.stream import (make_stream_optimizer,
                                        zero_padded_total)
        from repro.training.step import (make_dp_shardmap_train_step,
                                         replicate_model_state)
        cfg = reduced_config(get_config('resnet50'))
        mesh = make_mesh((4, 2), ('data', 'model'))
        DP = ('data', 'model')
        N, BB = 8, 8192
        opt_cfg = OptimizerConfig()
        model = build_model(cfg, compute_dtype=jnp.float32)
        rng = np.random.default_rng(0)
        batches = [
            {'images': jnp.asarray(rng.standard_normal((16, 32, 32, 3)),
                                   jnp.float32),
             'labels': jnp.asarray(rng.integers(0, cfg.num_classes, 16))}
            for _ in range(2)]
        bshard = NamedSharding(mesh, P(DP))

        def run(zero):
            parallel = ParallelConfig(
                dp_axes=DP, tp_axis=None, zero_1=False,
                compression='bf16+bucketed', bucket_bytes=BB,
                zero_dp=zero)
            tcfg = TrainConfig(optimizer=opt_cfg, parallel=parallel)
            params, _ = model.init_params(jax.random.PRNGKey(0))
            mstate = replicate_model_state(init_model_state(model), N)
            if zero:
                opt = make_stream_optimizer(opt_cfg, 5, 16)
                ostate = opt.init(zero_padded_total(
                    params, 'bf16+bucketed', BB, N))
            else:
                opt = make_optimizer(opt_cfg, 5, 16)
                ostate = opt.init(params)
            state = {'params': params, 'opt': ostate,
                     'model_state': mstate}
            step = jax.jit(make_dp_shardmap_train_step(
                model, opt, tcfg, mesh, DP))
            for b in batches:
                state, metrics = step(
                    state, {k: jax.device_put(v, bshard)
                            for k, v in b.items()})
            return state, metrics

        s0, m0 = run(False)
        s1, m1 = run(True)
        assert float(m0['loss']) == float(m1['loss'])
        for key in ('params', 'model_state'):
            for a, b in zip(jax.tree.leaves(s0[key]),
                            jax.tree.leaves(s1[key])):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b),
                                              err_msg=key)
        plan = plan_buckets(s0['params'], BB, 'bf16', align=N)
        for f in ('delta', 'm'):
            flat = np.concatenate(
                [np.asarray(l).reshape(-1)
                 for l in plan.treedef.flatten_up_to(s0['opt'][f])])
            flat = np.concatenate(
                [flat, np.zeros(plan.pad_elems, flat.dtype)])
            np.testing.assert_array_equal(
                stream_to_shard_layout(flat, plan, N),
                np.asarray(s1['opt'][f]), err_msg=f)
        print('TWO_AXIS_OK')
    """))
    assert "TWO_AXIS_OK" in out


# ---------------------------------------------------------------------------
# checkpoint round-trip across the zero/non-zero boundary (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_zero_checkpoint_crosses_layout_boundary_8dev(tmp_path):
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from repro.launch.mesh import make_mesh
        from repro.checkpoint.checkpointer import restore, save
        from repro.configs import (OptimizerConfig, get_config,
                                   reduced_config)
        from repro.distributed.bucketing import plan_buckets
        from repro.launch.train import build_train_setup
        from repro.optim.stream import (make_zero_restore_transform,
                                        param_key_tree)
        cfg = reduced_config(get_config('resnet50'))
        mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
        N = jax.device_count()
        BB = 8192

        def run(zero):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=8, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=5, mesh=mesh,
                dp_mode='shardmap', seed=0,
                compression='bf16+bucketed', bucket_bytes=BB,
                zero_dp=zero)
            for s in range(2):
                batch = put({k: jnp.asarray(v)
                             for k, v in data.batch_at(s).items()})
                state, _ = step(state, batch)
            return state, step, data, put

        state_b, step_b, data, put = run(zero=False)
        state_z, step_z, _, _ = run(zero=True)
        plan = plan_buckets(state_b['params'], BB, 'bf16', align=N)
        key_tree = param_key_tree(state_b['params'])
        root = tempfile.mkdtemp()
        dir_b, dir_z = os.path.join(root, 'b'), os.path.join(root, 'z')
        save(dir_b, 2, state_b, metadata={'opt_layout': 'tree'})
        save(dir_z, 2, state_z, metadata={'opt_layout': 'zero_stream'})

        def assert_equal(t1, t2, what):
            for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b),
                                              err_msg=what)

        # zero checkpoint -> tree-layout run
        to_tree = make_zero_restore_transform(plan, key_tree, N,
                                              to_zero=False)
        restored_b, _ = restore(dir_z, target=state_b,
                                transform=to_tree)
        assert_equal(restored_b, state_b, 'zero->tree')
        # tree checkpoint -> zero run, then keep training: one more step
        # from either restore path stays bitwise-identical
        to_zero = make_zero_restore_transform(plan, key_tree, N,
                                              to_zero=True)
        restored_z, _ = restore(dir_b, target=state_z,
                                transform=to_zero)
        assert_equal(restored_z, state_z, 'tree->zero')
        batch = put({k: jnp.asarray(v)
                     for k, v in data.batch_at(2).items()})
        cont_b, _ = step_b(state_b, dict(batch))
        cont_z, _ = step_z(restored_z, dict(batch))
        assert_equal(cont_b['params'], cont_z['params'],
                     'continued params')
        print('ZERO_CKPT_OK')
    """))
    assert "ZERO_CKPT_OK" in out


# ---------------------------------------------------------------------------
# HLO: the full-gradient all-reduce is gone; scatter+gather interleave
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_zero_hlo_reduce_scatter_no_allreduce():
    """comm_report must classify the zero step as
    reduce_scatter+all_gather (every surviving all-reduce is
    metric-sized) and the bucketed step as all_reduce; the zero-overlap
    step's scatters must interleave with backward conv/dot compute."""
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import (OptimizerConfig, get_config,
                                   reduced_config)
        from repro.launch.hlo_analysis import analyze_hlo, comm_report
        from repro.launch.train import build_train_setup
        cfg = reduced_config(get_config('resnet50'))
        mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
        reports = {}
        for name, kw in (('bucketed', {}),
                         ('zero', dict(zero_dp=True)),
                         ('zero_overlap', dict(zero_dp=True,
                                               overlap_comm=True))):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=8, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=5, mesh=mesh,
                dp_mode='shardmap', seed=0,
                compression='bf16+bucketed', bucket_bytes=8192, **kw)
            batch = put({k: jnp.asarray(v)
                         for k, v in data.batch_at(0).items()})
            txt = step.lower(state, batch).compile().as_text()
            reports[name] = comm_report(
                analyze_hlo(txt, jax.device_count()), hlo_text=txt)
        b = reports['bucketed']
        assert b['gradient_sync'] == 'all_reduce', b['gradient_sync']
        assert 'reduce-scatter' not in b['per_op']
        for name in ('zero', 'zero_overlap'):
            r = reports[name]
            assert r['gradient_sync'] == 'reduce_scatter+all_gather', (
                name, r['gradient_sync'])
            assert r['per_op']['reduce-scatter'][
                'executions_per_step'] >= 2, name
            assert r['per_op']['all-gather'][
                'executions_per_step'] >= 2, name
            ar = r['per_op'].get('all-reduce')
            assert ar is None or \\
                ar['max_bytes_per_collective'] < 1024, (name, ar)
        assert not reports['zero']['interleave']['interleaved']
        assert reports['zero_overlap']['interleave']['interleaved'], \\
            reports['zero_overlap']['interleave']
        print('ZERO_HLO_OK')
    """), env=ENV2)
    assert "ZERO_HLO_OK" in out
