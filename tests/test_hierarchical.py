"""Hierarchical (two-level) collective schedules + persisted comm
plans (DESIGN.md §14).

Fast tests pin the Hierarchy factorization rules and the ``--comm-plan``
grammar / persistence / fallback behavior (a wrong plan silently
applied would reshape every collective in the compiled step, so the
fallback paths are regression-tested explicitly). The slow battery
proves the acceptance claims on real 8-virtual-device host meshes:

- the collective primitives (hierarchical psum / psum_scatter /
  all_gather) are BITWISE equal to their flat counterparts on exact
  data, for both (2, 4) and (4, 2) factorizations and both wire dtypes;
- the end-to-end parity matrix — {bucketed, overlap, zero,
  zero_overlap} x {momentum_sgd, lars} — is bitwise vs the flat
  schedule on bf16 wire (the round-once f32 pipeline reassociates
  nothing the flat f32-promoted psum didn't), and on f16 wire is
  bitwise split-invariant (hier on 2x4 == hier on 4x2) and close to
  flat (flat f16 folds sequentially; hier re-rounds once);
- an autotuner-persisted plan round-trips through ``--comm-plan auto``
  into a compiled step whose HLO schedule matches the plan.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.bucketing import make_hierarchy
from repro.distributed.comm_plan import (
    PLAN_VERSION,
    CommPlan,
    CommPlanWarning,
    StaleCommPlan,
    load_plan,
    plan_path,
    resolve_comm_plan,
    save_plan,
)

ENV8 = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}

REPO = os.path.join(os.path.dirname(__file__), "..")


def run_py(body: str, env=ENV8, timeout=900) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# Hierarchy factorization rules
# ---------------------------------------------------------------------------


def test_make_hierarchy_splits_axes_row_major():
    h = make_hierarchy(("data", "model"), {"data": 2, "model": 4}, 1)
    assert h.outer == ("data",) and h.inner == ("model",)
    assert (h.outer_size, h.inner_size) == (2, 4)
    assert h.n_workers == 8


def test_make_hierarchy_multi_axis_split():
    sizes = {"a": 2, "b": 2, "c": 2}
    h = make_hierarchy(("a", "b", "c"), sizes, 2)
    assert h.outer == ("a", "b") and h.inner == ("c",)
    assert (h.outer_size, h.inner_size) == (4, 2)


@pytest.mark.parametrize("split", [0, 2, -1])
def test_make_hierarchy_split_out_of_range(split):
    with pytest.raises(ValueError, match="hier_split"):
        make_hierarchy(("data", "model"), {"data": 2, "model": 4}, split)


def test_make_hierarchy_rejects_size_one_stage():
    # a size-1 stage is a flat collective wearing a costume: callers
    # must fall back to the flat schedule instead
    with pytest.raises(ValueError, match="stages >= 2"):
        make_hierarchy(("data", "model"), {"data": 1, "model": 8}, 1)
    with pytest.raises(ValueError, match="stages >= 2"):
        make_hierarchy(("data", "model"), {"data": 8, "model": 1}, 1)


def test_hier_split_rejected_outside_shardmap():
    from repro.configs import OptimizerConfig, get_config, reduced_config
    from repro.launch.train import build_train_setup

    cfg = reduced_config(get_config("resnet50"))
    with pytest.raises(ValueError, match="shard"):
        build_train_setup(cfg, global_batch=8, seq_len=16,
                          opt_cfg=OptimizerConfig(), steps_per_epoch=5,
                          dp_mode="gspmd", hier_split=1,
                          compression="bf16+bucketed")


# ---------------------------------------------------------------------------
# --comm-plan grammar + persistence + fallback
# ---------------------------------------------------------------------------

_RUN = dict(arch="resnet50", mesh_shape=(2, 4),
            dp_axes=("data", "model"))


def _plan(**kw) -> CommPlan:
    base = dict(mesh_shape=(2, 4), dp_axes=("data", "model"),
                sync_mode="zero_overlap", wire="f16",
                bucket_bytes=4 << 20, hier_split=1, source="autotuner")
    base.update(kw)
    return CommPlan(**base)


def test_comm_plan_flat_resolves_to_none():
    assert resolve_comm_plan("flat", **_RUN) is None


def test_comm_plan_hier_grammar():
    plan = resolve_comm_plan("hier", **_RUN)
    assert plan.hier_split == 1
    # grammar form only reschedules: no wire-config override
    assert plan.bucket_bytes == 0
    assert resolve_comm_plan("hier:1", **_RUN).hier_split == 1


def test_comm_plan_hier_invalid_split_raises():
    # the user named an exact schedule: no silent fallback
    with pytest.raises(ValueError, match="hier_split"):
        resolve_comm_plan("hier:2", **_RUN)


def test_comm_plan_save_load_roundtrip(tmp_path):
    plan = _plan()
    path = save_plan(plan, str(tmp_path / "p.json"))
    assert load_plan(path) == plan
    assert resolve_comm_plan(path, **_RUN) == plan


def test_comm_plan_auto_finds_canonical_path(tmp_path):
    plan = _plan()
    save_plan(plan, plan_path("resnet50", (2, 4), str(tmp_path)))
    got = resolve_comm_plan("auto", out_dir=str(tmp_path), **_RUN)
    assert got == plan
    assert got.compression == "f16+bucketed"


def test_comm_plan_auto_missing_warns_and_falls_back(tmp_path):
    with pytest.warns(CommPlanWarning, match="no plan"):
        got = resolve_comm_plan("auto", out_dir=str(tmp_path), **_RUN)
    assert got is None


def test_comm_plan_stale_version_warns_and_falls_back(tmp_path):
    import dataclasses
    raw = dataclasses.asdict(_plan())
    raw["version"] = PLAN_VERSION + 999
    path = tmp_path / "stale.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(StaleCommPlan, match="version"):
        load_plan(str(path))
    with pytest.warns(CommPlanWarning, match="version"):
        assert resolve_comm_plan(str(path), **_RUN) is None


def test_comm_plan_malformed_warns_and_falls_back(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"version": PLAN_VERSION,
                                "sync_mode": "nope"}))
    with pytest.warns(CommPlanWarning, match="malformed"):
        assert resolve_comm_plan(str(path), **_RUN) is None


def test_comm_plan_mesh_mismatch_warns_and_falls_back(tmp_path):
    # tuned on 4x2, this run is 2x4: same device count, different
    # topology — the plan's split/bucket choices do not transfer
    path = save_plan(_plan(mesh_shape=(4, 2)), str(tmp_path / "p.json"))
    with pytest.warns(CommPlanWarning, match="tuned for mesh"):
        assert resolve_comm_plan(path, **_RUN) is None


def test_comm_plan_axes_mismatch_warns_and_falls_back(tmp_path):
    path = save_plan(_plan(dp_axes=("x", "y")), str(tmp_path / "p.json"))
    with pytest.warns(CommPlanWarning, match="DP axes"):
        assert resolve_comm_plan(path, **_RUN) is None


# ---------------------------------------------------------------------------
# collective primitives: bitwise vs flat on exact data (slow, 8 dev)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hier_primitives_bitwise_vs_flat_8dev():
    """hierarchical_psum == flat psum and hierarchical double-scatter ==
    flat psum_scatter, BITWISE, on exact integer data — for both mesh
    factorizations and both wire dtypes; the double all-gather is pure
    data movement so it is bitwise on any data."""
    out = run_py("""
        import os
        os.environ['XLA_FLAGS'] = \\
            '--xla_force_host_platform_device_count=8'
        import functools
        import numpy as np
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.distributed.bucketing import (
            make_hierarchy, hierarchical_psum, hierarchical_psum_scatter,
            hierarchical_all_gather)
        L = 512
        rng = np.random.default_rng(0)
        for shape in [(2, 4), (4, 2)]:
            mesh = make_mesh(shape, ('data', 'model'))
            dp = ('data', 'model')
            hier = make_hierarchy(dp, dict(zip(dp, shape)), 1)
            N = hier.n_workers
            for wire in ('bfloat16', 'float16'):
                exact = rng.integers(-256, 257, size=(N, L)).astype(wire)
                fuzzy = rng.standard_normal((N, L)).astype(wire)

                @functools.partial(
                    shard_map, mesh=mesh, in_specs=P(dp),
                    out_specs=P(dp), check_vma=False)
                def both(x):
                    b = x.reshape(-1)
                    flat = jax.lax.psum(b, dp)
                    h = hierarchical_psum(b, hier)
                    sc_flat = jax.lax.psum_scatter(
                        b, dp, scatter_dimension=0, tiled=True)
                    sc_h = hierarchical_psum_scatter(b, hier)
                    ag_flat = jax.lax.all_gather(
                        sc_h, dp, axis=0, tiled=True)
                    ag_h = hierarchical_all_gather(sc_h, hier)
                    return (flat[None], h[None], sc_flat[None],
                            sc_h[None], ag_flat[None], ag_h[None])

                for name, data in (('exact', exact), ('fuzzy', fuzzy)):
                    r = [np.asarray(v) for v in jax.jit(both)(data)]
                    flat, h, sc_flat, sc_h, ag_flat, ag_h = r
                    tag = f'{shape} {wire} {name}'
                    if name == 'exact':
                        np.testing.assert_array_equal(
                            flat.view(np.uint16), h.view(np.uint16),
                            err_msg=tag + ' psum')
                        np.testing.assert_array_equal(
                            sc_flat.view(np.uint16),
                            sc_h.view(np.uint16),
                            err_msg=tag + ' scatter')
                    # gather is pure data movement: bitwise always
                    np.testing.assert_array_equal(
                        ag_flat.view(np.uint16), ag_h.view(np.uint16),
                        err_msg=tag + ' gather')
                    np.testing.assert_allclose(
                        flat.astype(np.float32), h.astype(np.float32),
                        rtol=2e-2, atol=1e-2, err_msg=tag)
        print('PRIMS_OK')
    """)
    assert "PRIMS_OK" in out


# ---------------------------------------------------------------------------
# end-to-end parity matrix (slow, 8 dev)
# ---------------------------------------------------------------------------

_PARITY_HEADER = """
    OPT = '{opt}'
    WIRE = '{wire}'
"""

_PARITY_BODY = """
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import (OptimizerConfig, ParallelConfig,
                               TrainConfig, get_config, reduced_config)
    from repro.models import build_model, init_model_state
    from repro.optim import make_optimizer
    from repro.optim.stream import make_stream_optimizer, zero_padded_total
    from repro.training.step import (make_dp_shardmap_train_step,
                                     make_dp_overlap_train_step,
                                     replicate_model_state)

    cfg = reduced_config(get_config('resnet50'))
    N, BB = 8, 8192
    opt_cfg = OptimizerConfig(kind=OPT)
    model = build_model(cfg, compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    batches = [
        {'images': jnp.asarray(rng.standard_normal((16, 32, 32, 3)),
                               jnp.float32),
         'labels': jnp.asarray(rng.integers(0, cfg.num_classes, 16))}
        for _ in range(2)]

    def run(shape, overlap, zero, hier_split):
        mesh = make_mesh(shape, ('data', 'model'))
        DP = ('data', 'model')
        bshard = NamedSharding(mesh, P(DP))
        parallel = ParallelConfig(
            dp_axes=DP, tp_axis=None, zero_1=False,
            compression=WIRE + '+bucketed', bucket_bytes=BB,
            zero_dp=zero, overlap_comm=overlap, hier_split=hier_split)
        tcfg = TrainConfig(optimizer=opt_cfg, parallel=parallel)
        params, _ = model.init_params(jax.random.PRNGKey(0))
        if zero or OPT == 'lars':
            opt = make_stream_optimizer(opt_cfg, 5, 16)
            ostate = opt.init(zero_padded_total(
                params, WIRE + '+bucketed', BB, N))
        else:
            opt = make_optimizer(opt_cfg, 5, 16)
            ostate = opt.init(params)
        mstate = replicate_model_state(init_model_state(model), N)
        state = {'params': params, 'opt': ostate, 'model_state': mstate}
        builder = (make_dp_overlap_train_step if overlap
                   else make_dp_shardmap_train_step)
        step = jax.jit(builder(model, opt, tcfg, mesh, DP))
        for b in batches:
            state, metrics = step(state, {k: jax.device_put(v, bshard)
                                          for k, v in b.items()})
        return state, metrics

    def leaves(s):
        return [np.asarray(x) for x in jax.tree.leaves(s['params'])]

    for overlap, zero, name in ((False, False, 'bucketed'),
                                (True, False, 'overlap'),
                                (False, True, 'zero'),
                                (True, True, 'zero_overlap')):
        s_flat, m_flat = run((2, 4), overlap, zero, None)
        s_h24, m_h24 = run((2, 4), overlap, zero, 1)
        s_h42, m_h42 = run((4, 2), overlap, zero, 1)
        # split-invariance: 2x4 and 4x2 round identically (the shard
        # boundaries differ, the round-once arithmetic does not)
        for a, b in zip(leaves(s_h24), leaves(s_h42)):
            np.testing.assert_array_equal(a, b,
                                          err_msg=name + ':split-inv')
        if WIRE == 'bf16':
            # bf16 psum promotes to f32 on this backend: the
            # hierarchical round-once pipeline reassociates nothing, so
            # parity vs flat is BITWISE — the acceptance criterion
            assert float(m_flat['loss']) == float(m_h24['loss']), name
            for a, b in zip(leaves(s_flat), leaves(s_h24)):
                np.testing.assert_array_equal(a, b,
                                              err_msg=name + ':flat')
        else:
            # f16 flat folds sequentially in f16; hier rounds once from
            # f32 — numerically close, not bitwise (measured worst
            # rel diff ~4.5e-2 after 2 steps on this config)
            for a, b in zip(leaves(s_flat), leaves(s_h24)):
                np.testing.assert_allclose(
                    a, b, rtol=1.5e-1, atol=1e-4,
                    err_msg=name + ':flat')
    print('PARITY_OK')
"""


@pytest.mark.slow
@pytest.mark.parametrize("opt", ["momentum_sgd", "lars"])
@pytest.mark.parametrize("wire", ["bf16", "f16"])
def test_hier_parity_matrix_8dev(opt, wire):
    """Acceptance: the hierarchical schedule bitwise-matches the flat
    schedule in all four bucketed sync modes (bf16 wire), and is
    bitwise split-invariant ((2,4) vs (4,2)) on both wires, after
    multi-step training on the 8-virtual-device mesh."""
    body = (textwrap.dedent(_PARITY_HEADER).format(opt=opt, wire=wire)
            + textwrap.dedent(_PARITY_BODY))
    out = run_py(body)
    assert "PARITY_OK" in out


# ---------------------------------------------------------------------------
# autotuner plan -> --comm-plan auto -> compiled HLO (slow, 8 dev)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_comm_plan_autotune_roundtrip_hlo_8dev(tmp_path):
    """The full persistence loop: the comm autotuner sweep writes a
    plan; ``--comm-plan auto`` resolution loads it; a train step built
    from the plan's configuration lowers to HLO whose gradient-sync
    schedule matches what the plan promises."""
    plan_file = str(tmp_path / "comm_plan_resnet50_2x4.json")
    out_file = str(tmp_path / "BENCH_comm.json")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "comm_bench.py"),
         "--mesh", "2x4", "--reduced", "--quick", "--sweep",
         "--plan-out", plan_file, "--out", out_file],
        env=ENV8, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    plan = load_plan(plan_file)
    assert plan.source == "autotuner"
    assert tuple(plan.mesh_shape) == (2, 4)
    assert plan.bucket_bytes > 0
    # the sweep artifact embeds the winning plan it persisted
    bench = json.loads(open(out_file).read())
    assert bench["plan"]["sync_mode"] == plan.sync_mode
    assert bench["plan"]["hier_split"] == plan.hier_split

    out = run_py(f"""
        import os
        os.environ['XLA_FLAGS'] = \\
            '--xla_force_host_platform_device_count=8'
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import (OptimizerConfig, get_config,
                                   reduced_config)
        from repro.distributed.comm_plan import resolve_comm_plan
        from repro.launch.hlo_analysis import analyze_hlo, comm_report
        from repro.launch.train import build_train_setup

        plan = resolve_comm_plan(
            'auto', arch='resnet50', mesh_shape=(2, 4),
            dp_axes=('data', 'model'), out_dir={str(tmp_path)!r})
        assert plan is not None, 'auto must find the tuned plan'
        # apply the plan the way launch/train.py main() does
        mesh = make_mesh((2, 4), ('data', 'model'))
        dp_axes = (plan.dp_axes if plan.hier_split is not None
                   else ('data',))
        model, state, step, data, put, _ = build_train_setup(
            reduced_config(get_config('resnet50')), global_batch=8,
            seq_len=16, opt_cfg=OptimizerConfig(), steps_per_epoch=5,
            mesh=mesh, dp_mode='shardmap', seed=0,
            compression=plan.compression,
            bucket_bytes=plan.bucket_bytes,
            overlap_comm=plan.sync_mode in ('overlap', 'zero_overlap'),
            zero_dp=plan.sync_mode in ('zero', 'zero_overlap'),
            dp_axes=dp_axes, hier_split=plan.hier_split)
        batch = put({{k: jnp.asarray(v)
                     for k, v in data.batch_at(0).items()}})
        txt = step.lower(state, batch).compile().as_text()
        rep = comm_report(analyze_hlo(txt, 8), hlo_text=txt)
        if plan.sync_mode in ('zero', 'zero_overlap'):
            want = 'reduce_scatter+all_gather'
        elif plan.hier_split is not None:
            want = 'hierarchical'
        else:
            want = 'all_reduce'
        assert rep['gradient_sync'] == want, (
            rep['gradient_sync'], want, plan.describe())
        print('ROUNDTRIP_OK', plan.describe(), rep['gradient_sync'])
    """)
    assert "ROUNDTRIP_OK" in out
