"""Backward-overlapped bucketed all-reduce (DESIGN.md §8).

Single-process tests cover the ready-order BucketPlan (hypothesis
round-trip). The staged-apply oracle (chained per-segment VJPs ==
monolithic AD, bitwise) runs in a subprocess compiled without FMA; the
step-level equivalence — overlapped == non-overlapped bucketed, bitwise,
plain + error-feedback — and the HLO interleaving proof run in
subprocesses on virtual host meshes, like tests/test_bucketing.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.bucketing import (
    pack,
    pack_bucket,
    plan_ready_buckets,
    unpack,
)

ENV8 = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}
ENV2 = {**ENV8, "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
# One device, CPU code generated for AVX, which has no FMA. The staged
# and monolithic backward passes are different XLA programs (the staged
# one fuses per segment); on an FMA-capable host XLA's CPU backend
# contracts multiply-add pairs into FMAs within each fusion, so the two
# round differently although they run the same primitives. Without FMA
# the bitwise comparison checks what it means to: same primitives, same
# order.
ENV_NO_FMA = {**ENV8, "XLA_FLAGS": "--xla_cpu_max_isa=AVX"}


def run_py(body: str, env=ENV8, timeout=420) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# staged apply == monolithic AD (one device, no FMA, bitwise)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["resnet50", "llama3.2-1b"])
def test_staged_grads_bitwise_equal_monolithic(arch):
    """Chained per-segment VJPs must emit the same primitives as
    reverse-mode AD of the composite loss — loss, grads, and (for BN
    models) the new model_state all bitwise-equal. llama3.2-1b ties its
    embeddings, so this also pins the carry-passthrough gradient path
    for the shared table. Compiled without FMA (``ENV_NO_FMA``)."""
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.models import build_model, init_model_state
        from repro.models.common import staged_value_and_grad

        def leaves_by_path(tree):
            return {{jax.tree_util.keystr(k): np.asarray(v)
                     for k, v in jax.tree_util.tree_leaves_with_path(tree)}}

        def assert_trees_bitwise(t1, t2, what=""):
            d1, d2 = leaves_by_path(t1), leaves_by_path(t2)
            assert set(d1) == set(d2), (what, set(d1) ^ set(d2))
            for k in d1:
                np.testing.assert_array_equal(d1[k], d2[k],
                                              err_msg=f"{{what}}{{k}}")

        cfg = reduced_config(get_config({arch!r}))
        model = build_model(cfg, compute_dtype=jnp.float32)
        params, _ = model.init_params(jax.random.PRNGKey(0))
        mstate = init_model_state(model)
        if cfg.family == "conv":
            batch = {{"images": jax.random.normal(
                jax.random.PRNGKey(1), (8, 32, 32, 3)),
                "labels": jax.random.randint(
                    jax.random.PRNGKey(2), (8,), 0, cfg.num_classes)}}
        else:
            assert cfg.tie_embeddings  # the interesting case
            batch = {{"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
                "targets": jax.random.randint(
                    jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)}}

        (l1, (ns1, _)), g1 = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, mstate, batch, 0.1),
            has_aux=True))(params)
        l2, (ns2, _), g2 = jax.jit(lambda p: staged_value_and_grad(
            model.loss_segments(p, mstate, batch, 0.1)))(params)

        assert float(l1) == float(l2)
        assert_trees_bitwise(g1, g2, "grad ")
        assert_trees_bitwise(ns1, ns2, "state ")
        print("STAGED_BITWISE_OK")
    """, env=ENV_NO_FMA)
    assert "STAGED_BITWISE_OK" in out


def test_overlap_step_rejects_unstaged_model():
    from repro.configs import OptimizerConfig, ParallelConfig, TrainConfig
    from repro.training.step import make_dp_overlap_train_step

    class NoSegments:
        pass

    cfg = TrainConfig(optimizer=OptimizerConfig(),
                      parallel=ParallelConfig(compression="bf16+bucketed"))
    with pytest.raises(ValueError, match="loss_segments"):
        make_dp_overlap_train_step(NoSegments(), None, cfg, None, ("data",))


# ---------------------------------------------------------------------------
# ready-order BucketPlan: property round-trip
# ---------------------------------------------------------------------------

try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings

    hypothesis.settings.register_profile(
        "ci", deadline=None, max_examples=20,
        suppress_health_check=list(hypothesis.HealthCheck))
    hypothesis.settings.load_profile("ci")
    HAVE_HYPOTHESIS = True

    @st.composite
    def stage_trees_and_bucket(draw):
        n_stages = draw(st.integers(1, 5))
        stages = []
        rng = np.random.default_rng(draw(st.integers(0, 2 ** 16)))
        for s in range(n_stages):
            n_leaves = draw(st.integers(0, 4))
            tree = {f"l{i}": jnp.asarray(
                rng.standard_normal(draw(st.integers(1, 40))),
                jnp.float32) for i in range(n_leaves)}
            stages.append(tree)
        if not any(jax.tree.leaves(t) for t in stages):
            stages[0] = {"l0": jnp.ones((3,), jnp.float32)}
        bucket_bytes = draw(st.integers(8, 256))
        return stages, bucket_bytes
except ImportError:  # hypothesis optional, like tests/test_properties.py
    HAVE_HYPOTHESIS = False

    def given(*a, **k):  # pragma: no cover - skip path
        return lambda fn: fn

    def settings(*a, **k):  # pragma: no cover
        return lambda fn: fn

    def stage_trees_and_bucket():  # pragma: no cover
        return None


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@given(stage_trees_and_bucket())
@settings(max_examples=30)
def test_ready_order_plan_roundtrip_property(case):
    """Incremental pack_bucket over ready-ordered stages == whole-tree
    pack; every bucket closes exactly once, at its plan ready_stage;
    unpack restores the stage trees exactly (wire=None, f32)."""
    stages, bucket_bytes = case
    plan = plan_ready_buckets(stages, bucket_bytes=bucket_bytes, wire=None)
    total = sum(l.size for t in stages for l in jax.tree.leaves(t))
    assert plan.base.total_elems == total
    bucket_elems = max(1, bucket_bytes // 4)  # f32 stream (wire=None)
    assert plan.n_buckets == max(1, -(-total // bucket_elems))
    # ready stages non-decreasing, and within stage-feed bounds
    assert list(plan.ready_stage) == sorted(plan.ready_stage)

    whole = pack(tuple(stages), plan.base, use_kernel=False)
    seen = {}
    carry = None
    for s, tree in enumerate(stages):
        ready, carry = pack_bucket(plan, s, tree, carry, use_kernel=False)
        for b, arr in ready:
            assert b not in seen
            assert plan.ready_stage[b] == s
            seen[b] = arr
    assert carry.size == 0
    assert sorted(seen) == list(range(plan.n_buckets))
    for b in range(plan.n_buckets):
        np.testing.assert_array_equal(np.asarray(seen[b]),
                                      np.asarray(whole[b]))
    out = unpack([seen[b] for b in range(plan.n_buckets)], plan.base,
                 use_kernel=False)
    for a, b in zip(jax.tree.leaves(tuple(stages)), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ready_order_buckets_close_before_full_backward():
    """The point of ready order: with the backward-completion layout,
    early stages close buckets long before the last stage is fed —
    pytree order cannot do that when late-materializing leaves sit at
    the stream front."""
    stages = [{"a": jnp.ones((100,))}, {"b": jnp.ones((100,))},
              {"c": jnp.ones((100,))}]
    plan = plan_ready_buckets(stages, bucket_bytes=400, wire=None)
    assert plan.n_buckets == 3
    assert plan.ready_stage == (0, 1, 2)
    ready0, carry = pack_bucket(plan, 0, stages[0], None, use_kernel=False)
    assert [b for b, _ in ready0] == [0]  # closed after the FIRST stage


# ---------------------------------------------------------------------------
# step-level equivalence + HLO interleaving (subprocess, virtual mesh)
# ---------------------------------------------------------------------------

_STEP_PAIR = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import OptimizerConfig, get_config, reduced_config
    from repro.launch.train import build_train_setup
    cfg = reduced_config(get_config('resnet50'))
    mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
    def build(overlap):
        return build_train_setup(
            cfg, global_batch=8, seq_len=16, opt_cfg=OptimizerConfig(),
            steps_per_epoch=5, mesh=mesh, dp_mode='shardmap', seed=0,
            compression='bf16+bucketed', bucket_bytes=8192,
            error_feedback={EF}, overlap_comm=overlap)
"""


def _parity_body(ef: bool) -> str:
    return textwrap.dedent(_STEP_PAIR).format(EF=ef) + textwrap.dedent("""
        results = {}
        for overlap in (False, True):
            model, state, step, data, put, _ = build(overlap)
            for s in range(2):
                batch = put({k: jnp.asarray(v)
                             for k, v in data.batch_at(s).items()})
                state, metrics = step(state, batch)
            results[overlap] = (state, metrics)
        s0, m0 = results[False]
        s1, m1 = results[True]
        assert float(m0['loss']) == float(m1['loss'])
        keys = ['params', 'opt', 'model_state']
        if %s:
            keys.append('ef_residual')
            nz = max(float(jnp.abs(x).max())
                     for x in jax.tree.leaves(s1['ef_residual']))
            assert nz > 0  # EF genuinely active
        for key in keys:
            for a, b in zip(jax.tree.leaves(s0[key]),
                            jax.tree.leaves(s1[key])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('PARITY_OK')
    """ % ef)


@pytest.mark.slow
def test_overlap_step_bitwise_equals_bucketed_8dev():
    """Acceptance: the overlapped step's gradients (hence params, opt
    state, BN stats after 2 steps) are bitwise-equal to the
    non-overlapped bucketed path on the 8-virtual-device mesh."""
    out = run_py(_parity_body(ef=False))
    assert "PARITY_OK" in out


@pytest.mark.slow
def test_overlap_step_bitwise_equals_bucketed_error_feedback_8dev():
    out = run_py(_parity_body(ef=True))
    assert "PARITY_OK" in out


def test_overlap_interleaves_collectives_in_hlo():
    """The comm_report interleave check must reject the non-overlapped
    program (collectives clustered after the whole backward) and accept
    the overlapped one (collectives separated by backward conv/dot
    compute). 2 virtual devices keep the compiles cheap — interleaving
    is a program-structure property, not a worker-count one."""
    out = run_py(textwrap.dedent(_STEP_PAIR).format(EF=False) +
                 textwrap.dedent("""
        from repro.launch.hlo_analysis import (analyze_hlo, comm_report,
                                               interleave_report)
        reports = {}
        for overlap in (False, True):
            model, state, step, data, put, _ = build(overlap)
            batch = put({k: jnp.asarray(v)
                         for k, v in data.batch_at(0).items()})
            txt = step.lower(state, batch).compile().as_text()
            reports[overlap] = interleave_report(txt)
            # comm_report embeds the same section when given the text
            cr = comm_report(analyze_hlo(txt, jax.device_count()),
                             hlo_text=txt)
            assert cr['interleave'] == reports[overlap]
        assert reports[False]['n_collectives'] >= 2, reports[False]
        assert not reports[False]['interleaved'], reports[False]
        assert reports[False]['compute_ops_after_first'] == 0
        assert reports[True]['interleaved'], reports[True]
        assert reports[True]['compute_ops_between_first_last'] > 0
        print('INTERLEAVE_OK', reports[True])
    """), env=ENV2)
    assert "INTERLEAVE_OK" in out


@pytest.mark.slow
def test_overlap_trains_same_as_perleaf_trajectory():
    """End-to-end: overlapped bucketed sync produces the same loss
    trajectory as the original per-leaf compressed psum (the seed
    path), tight tolerance — whole-program compile differences only."""
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import OptimizerConfig, get_config, \\
            reduced_config
        from repro.launch.train import build_train_setup
        cfg = reduced_config(get_config('resnet50'))
        mesh = make_mesh((2, 1), ('data', 'model'))
        losses = {}
        for comp, overlap in (('bf16', False), ('bf16+bucketed', True)):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=8, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=5, mesh=mesh,
                dp_mode='shardmap', seed=0, compression=comp,
                bucket_bytes=8192, overlap_comm=overlap)
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
        print('TRAJ_OK', losses['bf16'])
    """), env=ENV2)
    assert "TRAJ_OK" in out
