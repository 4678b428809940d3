"""Packed-stream LARS (DESIGN.md §11).

Fast single-process tests cover the reference LARS bias/BN trust
exemption (regression), the leaf-segment map, the trust mask, the
single-process stream == reference bitwise equivalence (the shared
``segment_sum`` primitive contract), the stream-optimizer wiring, the
fused Pallas segment-norm/update kernels (allclose — MXU dot fold order
differs), and the polynomial-decay schedule. The step-level parity
matrix — {bucketed, overlap} x {zero, non-zero} x {bf16, f16} wire,
plain + error-feedback — runs in subprocesses on an 8-virtual-device
mesh (marked ``slow``), mirroring tests/test_zero.py: within a family
the decomposition is identical, so bucketed == zero and overlap ==
zero-overlap are asserted *bitwise*; across families (and vs the
per-leaf reference) the norm fold order legitimately differs, so those
are tight allclose only.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig
from repro.distributed.bucketing import (
    pack,
    plan_buckets,
    segment_ids_stream,
    segment_sq_partials,
    unpack,
)
from repro.launch.mesh import make_mesh
from repro.optim import make_optimizer
from repro.optim.lars import leaf_sq_norm, trust_from_sq
from repro.optim.stream import make_stream_optimizer, trust_mask_segments

ENV8 = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}


def run_py(body: str, env=ENV8, timeout=900) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


def _tree(rng):
    """Small mixed tree: decayed weights + NO_DECAY bias/scale leaves."""
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"blk": {"w": mk(7, 3), "bias": mk(3)},
            "norm": {"scale": mk(4)},
            "head": {"w": mk(3, 5)}}


# ---------------------------------------------------------------------------
# reference LARS: bias/BN leaves exempt from the trust ratio (regression)
# ---------------------------------------------------------------------------


def test_reference_lars_exempts_bias_bn_from_trust():
    """You et al. exempt bias/BN params from the layer-wise trust ratio:
    on a NO_DECAY leaf the update must be plain momentum (trust = 1),
    bitwise — not a norm-scaled step."""
    cfg = OptimizerConfig(kind="lars", schedule="constant",
                          base_lr_per_256=0.4)
    rng = np.random.default_rng(3)
    params = _tree(rng)
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    opt = make_optimizer(cfg, steps_per_epoch=5, global_batch=32)
    state = opt.init(params)
    new_p, new_st, metrics = opt.update(params, grads, state)
    eta = float(metrics["lr"])

    # bias/scale: d = -g, p' = p - eta*g exactly (trust 1, no decay)
    for path in (("blk", "bias"), ("norm", "scale")):
        p0, g = params[path[0]][path[1]], grads[path[0]][path[1]]
        got = new_p[path[0]][path[1]]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(p0 - eta * g),
                                      err_msg=str(path))
    # weight leaf: trust-scaled, and the ratio matches trust_from_sq on
    # the decayed gradient
    p0, g = params["blk"]["w"], grads["blk"]["w"]
    g_eff = g + cfg.weight_decay * p0
    trust = trust_from_sq(leaf_sq_norm(p0), leaf_sq_norm(g_eff),
                          cfg.trust_coef, True)
    assert 0 < float(trust) < 1
    np.testing.assert_array_equal(
        np.asarray(new_p["blk"]["w"]),
        np.asarray(p0 - eta * trust * g_eff))


# ---------------------------------------------------------------------------
# leaf-segment map + trust mask
# ---------------------------------------------------------------------------


def test_segment_ids_stream_tiles_plan():
    rng = np.random.default_rng(4)
    tree = _tree(rng)
    plan = plan_buckets(tree, bucket_bytes=64, wire=None, align=4)
    seg = segment_ids_stream(plan)
    assert seg.shape == (plan.padded_total,)
    assert seg.dtype == np.int32
    for i, slot in enumerate(plan.slots):
        np.testing.assert_array_equal(
            seg[slot.offset:slot.offset + slot.size], i)
    # pad elements map to the synthetic trailing segment
    n_pad = int(np.sum(seg == len(plan.slots)))
    assert n_pad == plan.padded_total - plan.total_elems


def test_trust_mask_matches_decay_mask_and_exempts_pad():
    rng = np.random.default_rng(5)
    tree = _tree(rng)
    plan = plan_buckets(tree, bucket_bytes=64, wire=None, align=4)
    mask = trust_mask_segments(tree, plan)
    assert mask.shape == (len(plan.slots) + 1,)
    assert mask[-1] == False  # noqa: E712 — the pad segment
    # slots are in treedef leaf order; bias/scale exempt, weights not
    names = ["blk/bias", "blk/w", "head/w", "norm/scale"]
    want = {"blk/bias": False, "blk/w": True, "head/w": True,
            "norm/scale": False}
    assert list(mask[:-1]) == [want[n] for n in names]


# ---------------------------------------------------------------------------
# single-process stream == reference, bitwise (3 steps)
# ---------------------------------------------------------------------------


def test_stream_lars_matches_reference_bitwise_single_process():
    """The core of the parity claim: with one worker (no psum, no shard
    decomposition) the packed-stream LARS step reproduces the per-leaf
    reference bitwise over 3 steps — both compute norms through the same
    ``segment_sum`` primitive and the same ``trust_from_sq`` ratio."""
    cfg = OptimizerConfig(kind="lars", schedule="poly", warmup_epochs=1.0,
                          total_epochs=4.0, base_lr_per_256=0.4)
    rng = np.random.default_rng(6)
    params = _tree(rng)
    ref = make_optimizer(cfg, steps_per_epoch=5, global_batch=32)
    sopt = make_stream_optimizer(cfg, steps_per_epoch=5, global_batch=32)
    assert sopt.kind == "lars"

    plan = plan_buckets(params, bucket_bytes=48, wire=None, align=1)
    seg = jnp.asarray(segment_ids_stream(plan))
    wd = jnp.asarray(sopt.wd_stream(params, plan))
    tmask = jnp.asarray(trust_mask_segments(params, plan))
    n_seg = len(plan.slots) + 1

    ref_state = ref.init(params)
    sstate = sopt.init(plan.padded_total)
    ref_params = stream_params = params
    for step in range(3):
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.standard_normal(p.shape),
                                  jnp.float32), params)
        ref_params, ref_state, _ = ref.update(ref_params, grads,
                                              ref_state)
        p_stream = jnp.concatenate(pack(stream_params, plan))
        g_stream = jnp.concatenate(pack(grads, plan))
        partials = sopt.segment_partials(p_stream, g_stream, wd, seg,
                                         n_seg)
        assert partials.shape == (2, n_seg)
        trust = sopt.trust_ratios(partials, tmask)  # n=1: psum == id
        p_new, d_new, _ = sopt.update_shard(
            p_stream, g_stream, sstate["delta"], sstate["step"], wd,
            seg, trust)
        sstate = {"step": sstate["step"] + 1, "delta": d_new}
        stream_params = unpack([p_new], plan)
        # exempt segments (bias/scale/pad) got trust exactly 1
        t = np.asarray(trust)
        np.testing.assert_array_equal(t[~np.asarray(tmask)], 1.0)
        assert np.all(t[np.asarray(tmask)] < 1.0)

    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_params),
            jax.tree_util.tree_leaves_with_path(stream_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(ka))
    d_ref = jnp.concatenate(pack(ref_state["delta"], plan))
    np.testing.assert_array_equal(
        np.asarray(sstate["delta"])[:plan.total_elems],
        np.asarray(d_ref)[:plan.total_elems])


def test_stream_optimizer_lars_wiring():
    sopt = make_stream_optimizer(OptimizerConfig(kind="lars"), 5, 32)
    assert sopt.kind == "lars"
    assert sopt.state_fields == ("delta",)
    assert sopt.segment_partials is not None
    assert sopt.trust_ratios is not None
    st = sopt.init(16)
    assert set(st) == {"step", "delta"}
    assert st["delta"].shape == (16,)


def test_stream_optimizer_still_rejects_unknown_kind():
    # momentum_sgd joined the stream family (the audit matrix lowers
    # every mode x optimizer cell, tests/test_audit.py pins parity);
    # anything outside {rmsprop_warmup, momentum_sgd, lars} still raises
    with pytest.raises(ValueError, match="rmsprop_warmup"):
        make_stream_optimizer(OptimizerConfig(kind="adamw"), 5, 32)
    sopt = make_stream_optimizer(OptimizerConfig(kind="momentum_sgd"), 5, 32)
    assert set(sopt.init(16)) == {"step", "delta", "m"}


def test_stream_checks_require_bucketed_and_lars():
    from repro.configs import ParallelConfig, TrainConfig
    from repro.training.step import make_dp_shardmap_train_step

    sopt = make_stream_optimizer(OptimizerConfig(kind="lars"), 5, 32)
    cfg = TrainConfig(optimizer=OptimizerConfig(kind="lars"),
                      parallel=ParallelConfig(compression="bf16"))
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    with pytest.raises(ValueError, match="bucketed"):
        make_dp_shardmap_train_step(object(), sopt, cfg, mesh, ("data",))


# ---------------------------------------------------------------------------
# fused Pallas kernels (allclose: the MXU one-hot dot folds differently)
# ---------------------------------------------------------------------------


def test_fused_segment_sq_partials_matches_segment_sum():
    from repro.kernels import ops as kops

    rng = np.random.default_rng(7)
    n, n_seg = 300, 4
    seg_np = np.repeat(np.arange(n_seg), [100, 80, 70, 50]).astype(
        np.int32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    wd = jnp.asarray(rng.uniform(0, 1e-3, n), jnp.float32)
    seg = jnp.asarray(seg_np)
    got = kops.fused_segment_sq_partials(p, g, wd, seg, n_seg)
    want = jnp.stack([
        segment_sq_partials(p, seg, n_seg),
        segment_sq_partials(g + wd * p, seg, n_seg)])
    assert got.shape == (2, n_seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_fused_lars_update_matches_reference():
    from repro.kernels import ops as kops

    rng = np.random.default_rng(8)
    n, n_seg = 300, 3
    seg_np = np.repeat(np.arange(n_seg), [150, 100, 50]).astype(np.int32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    d = jnp.asarray(rng.standard_normal(n), jnp.float32)
    wd = jnp.asarray(rng.uniform(0, 1e-3, n), jnp.float32)
    trust = jnp.asarray([1.0, 0.5, 2.0], jnp.float32)
    seg = jnp.asarray(seg_np)
    eta, mu1 = jnp.float32(0.3), 0.9
    p2, d2 = kops.fused_lars_update(g, p, d, wd, seg, trust, eta, mu1)
    g_eff = g + wd * p
    d_ref = mu1 * d - trust[seg] * g_eff
    p_ref = p + eta * d_ref
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d_ref),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p_ref),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# polynomial-decay schedule
# ---------------------------------------------------------------------------


def test_poly_schedule_warmup_and_decay():
    from repro.core.schedules import make_lr_schedule

    lr = make_lr_schedule("poly", global_batch=256, base_lr_per_256=0.1,
                          warmup_epochs=1.0, total_epochs=4.0,
                          poly_power=2.0)
    # batch 256: eta_base == base, so warmup is flat at 0.1
    for e, want in ((0.0, 0.1), (0.5, 0.1), (1.0, 0.1),
                    (2.5, 0.1 * 0.25), (4.0, 0.0), (5.0, 0.0)):
        np.testing.assert_allclose(float(lr(jnp.float32(e))), want,
                                   rtol=1e-6, atol=1e-9,
                                   err_msg=f"epoch {e}")
    # linear scaling: batch 512 doubles the post-warmup LR
    lr2 = make_lr_schedule("poly", global_batch=512, base_lr_per_256=0.1,
                           warmup_epochs=1.0, total_epochs=4.0)
    np.testing.assert_allclose(float(lr2(jnp.float32(1.0))), 0.2,
                               rtol=1e-6)
    # warmup ramps from base_lr_per_256 toward eta_base
    np.testing.assert_allclose(float(lr2(jnp.float32(0.0))), 0.1,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# step-level parity matrix (subprocess, 8-device virtual mesh, slow)
# ---------------------------------------------------------------------------

_PARITY_BODY = """
    WIRE = @WIRE@
    EF = @EF@
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
    opt_cfg = OptimizerConfig(kind='lars', schedule='poly',
                              warmup_epochs=1.0, total_epochs=4.0,
                              base_lr_per_256=0.3)

    def run(compression, overlap, zero):
        model, state, step, data, put, _ = build_train_setup(
            cfg, global_batch=8, seq_len=16, opt_cfg=opt_cfg,
            steps_per_epoch=5, mesh=mesh, dp_mode='shardmap', seed=0,
            compression=compression, bucket_bytes=BB,
            error_feedback=EF, overlap_comm=overlap, zero_dp=zero,
            label_smoothing=0.1)
        losses = []
        for s in range(3):
            batch = put({k: jnp.asarray(v)
                         for k, v in data.batch_at(s).items()})
            state, metrics = step(state, batch)
            losses.append(float(metrics['loss']))
        return model, state, losses

    def leaves(tree):
        return sorted(((jax.tree_util.keystr(k), np.asarray(v))
                       for k, v in
                       jax.tree_util.tree_leaves_with_path(tree)),
                      key=lambda kv: kv[0])

    def assert_state(name, s0, s1, exact):
        # ef_residual is compared bitwise within a family only: it IS
        # the wire-rounding LSB of the gradient, so across families
        # (slightly different gradients -> different rounding) it has
        # no meaningful tolerance.
        keys = ['params', 'model_state'] + (
            ['ef_residual'] if (EF and exact) else [])
        for key in keys:
            for (ka, a), (kb, b) in zip(leaves(s0[key]), leaves(s1[key])):
                if exact:
                    np.testing.assert_array_equal(
                        a, b, err_msg=name + ':' + key + ka)
                else:
                    # fold-order noise across stream layouts: relative
                    # for normal-sized params, absolute floor for
                    # near-zero elements (BN biases ~1e-4 after 3 steps)
                    np.testing.assert_allclose(
                        a, b, rtol=1e-2, atol=1e-4,
                        err_msg=name + ':' + key + ka)

    def shard_layout(stream, plan):
        return stream_to_shard_layout(np.asarray(stream), plan, N)

    # ---- the four packed-stream sync modes ----
    model, sb, lb = run(WIRE + '+bucketed', False, False)
    _, sz, lz = run(WIRE + '+bucketed', False, True)
    _, so, lo = run(WIRE + '+bucketed', True, False)
    _, szo, lzo = run(WIRE + '+bucketed', True, True)
    # within a family the norm decomposition is identical: bitwise
    assert lb == lz, (lb, lz)
    assert lo == lzo, (lo, lzo)
    assert_state('bucketed_vs_zero', sb, sz, exact=True)
    assert_state('overlap_vs_zero_overlap', so, szo, exact=True)
    if EF:
        nz = max(float(jnp.abs(x).max())
                 for x in jax.tree.leaves(sz['ef_residual']))
        assert nz > 0  # EF genuinely active

    # delta layout: non-zero keeps the full stream, zero the shard
    # layout of the same plan — bitwise-equal values either way
    assert all(int(s['opt']['step']) == 3 for s in (sb, sz, so, szo))
    plan_p = plan_buckets(sb['params'], BB, WIRE, align=N)
    np.testing.assert_array_equal(
        shard_layout(sb['opt']['delta'], plan_p),
        np.asarray(sz['opt']['delta']), err_msg='delta:bucketed/zero')
    mstate0 = jax.tree.map(lambda x: x[0], so['model_state'])
    dummy = {'images': jnp.zeros((8, 32, 32, 3)),
             'labels': jnp.zeros((8,), jnp.int32)}
    staged = model.loss_segments(so['params'], mstate0, dummy, 0.0)
    plan_o = plan_ready_buckets(
        [jax.tree.map(lambda x: x, t)
         for t in reversed(staged.seg_params)], BB, WIRE, align=N).base
    np.testing.assert_array_equal(
        shard_layout(so['opt']['delta'], plan_o),
        np.asarray(szo['opt']['delta']), err_msg='delta:overlap/zero')

    # across families the norm fold order differs: tight allclose
    assert_state('bucketed_vs_overlap', sb, so, exact=False)

    # ---- vs the per-leaf reference (tree LARS, unbucketed wire) ----
    _, sr, lr_ = run(WIRE, False, False)
    assert np.allclose(lb, lr_, rtol=1e-3), (lb, lr_)
    assert_state('bucketed_vs_reference', sb, sr, exact=False)
    print('LARS_PARITY_OK')
"""


@pytest.mark.slow
@pytest.mark.parametrize("ef", [False, True])
@pytest.mark.parametrize("wire", ["bf16", "f16"])
def test_lars_stream_parity_matrix_8dev(ef, wire):
    """Acceptance: kind='lars' runs through the packed-stream path in
    all four sync modes on the 8-virtual-device mesh. Bucketed == zero
    and overlap == zero-overlap bitwise (identical shard-decomposed norm
    program); cross-family and vs the per-leaf tree reference are tight
    allclose (the fold order across different stream layouts legitimately
    differs)."""
    body = _PARITY_BODY.replace("@WIRE@", repr(wire)).replace(
        "@EF@", str(ef))
    out = run_py(textwrap.dedent(body))
    assert "LARS_PARITY_OK" in out
