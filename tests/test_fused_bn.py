"""Fused Pallas batch norm (kernels/fused_bn.py, DESIGN.md §10).

Fast lane: single-config fwd/bwd parity vs the jnp oracle, gradcheck,
multi-block accumulation, the given-stats (eval) variant with full
mean/var cotangents, and the real-lowering fusion_report collapse
proof. The full {train, eval} x {ReLU, identity, residual} x
{f32, bf16} parity matrix, the cross-replica (sync-BN) 8-virtual-device
check, and the 3-step fused-vs-unfused train-step parity run under the
``slow`` marker (subprocess compiles dominate), like the §9 sweeps.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batchnorm import bn_apply_stats
from repro.kernels import fused_bn as fb
from repro.kernels import ops, ref

ENV8 = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}


def run_py(body: str, env=ENV8, timeout=600) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


def _data(key, shape, dtype, has_res):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], shape, dtype) * 2.0 + 0.5
    res = (jax.random.normal(ks[1], shape, dtype) if has_res else None)
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (shape[-1],))
    bias = 0.1 * jax.random.normal(ks[3], (shape[-1],))
    dy = jax.random.normal(ks[4], shape, dtype)
    return x, res, scale, bias, dy


def _assert_train_parity(shape, dtype, relu, has_res, key):
    """Fused fwd (y, mean, var) + VJP vs the jnp oracle. bf16 tolerances
    are loose for the reduced param grads: the oracle accumulates its
    reductions through bf16 intermediates while the kernel accumulates
    in fp32 (the kernel is the *more* accurate side); ReLU-boundary
    elements may also flip mask under bf16 rounding of the
    pre-activation."""
    x, res, scale, bias, dy = _data(key, shape, dtype, has_res)

    def fused(x, s, b, r):
        return ops.fused_bn_train(x, s, b, residual=r, relu=relu)

    def oracle(x, s, b, r):
        return ref.bn_forward(x, s, b, residual=r, relu=relu)

    (y1, m1, v1), vjp1 = jax.vjp(fused, x, scale, bias, res)
    (y2, m2, v2), vjp2 = jax.vjp(oracle, x, scale, bias, res)
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=1e-4 if f32 else 5e-2,
                               rtol=1e-6 if f32 else 2e-2)
    np.testing.assert_allclose(m1, m2, atol=1e-4 if f32 else 5e-3)
    np.testing.assert_allclose(v1, v2, atol=1e-4 if f32 else 5e-3)
    cts = (dy, jnp.zeros_like(m1), jnp.zeros_like(v1))
    g1, g2 = vjp1(cts), vjp2(cts)
    for a, b, name in zip(g1, g2, ("dx", "dscale", "dbias", "dres")):
        if a is None and b is None:
            continue
        aa = np.asarray(a, np.float32)
        bb = np.asarray(b, np.float32)
        if f32:
            np.testing.assert_allclose(aa, bb, atol=5e-4, err_msg=name)
        elif name in ("dx", "dres"):
            np.testing.assert_allclose(aa, bb, atol=0.1, err_msg=name)
        else:
            np.testing.assert_allclose(aa, bb, rtol=0.2, atol=0.2,
                                       err_msg=name)


def _assert_eval_parity(shape, dtype, relu, has_res, key):
    """Given-stats variant vs oracle, with cotangents for every input
    including mean/var (the fused op stays differentiable everywhere)."""
    x, res, scale, bias, dy = _data(key, shape, dtype, has_res)
    ks = jax.random.split(jax.random.fold_in(key, 7), 2)
    mean = jax.random.normal(ks[0], (shape[-1],))
    var = jnp.abs(jax.random.normal(ks[1], (shape[-1],))) + 0.5

    def fused(x, m, v, s, b, r):
        return ops.fused_bn_apply(x, m, v, s, b, residual=r, relu=relu)

    def oracle(x, m, v, s, b, r):
        y = bn_apply_stats(x, m, v, s, b)
        if r is not None:
            y = y + r
        return jax.nn.relu(y) if relu else y

    y1, vjp1 = jax.vjp(fused, x, mean, var, scale, bias, res)
    y2, vjp2 = jax.vjp(oracle, x, mean, var, scale, bias, res)
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=1e-4 if f32 else 5e-2,
                               rtol=1e-6 if f32 else 2e-2)
    names = ("dx", "dmean", "dvar", "dscale", "dbias", "dres")
    for a, b, name in zip(vjp1(dy), vjp2(dy), names):
        if a is None and b is None:
            continue
        aa = np.asarray(a, np.float32)
        bb = np.asarray(b, np.float32)
        if f32:
            np.testing.assert_allclose(aa, bb, atol=2e-3, err_msg=name)
        elif name in ("dx", "dres"):
            np.testing.assert_allclose(aa, bb, atol=0.1, err_msg=name)
        else:
            np.testing.assert_allclose(aa, bb, rtol=0.2, atol=0.2,
                                       err_msg=name)


# ---------------------------------------------------------------------------
# fast lane: smoke parity + kernel mechanics
# ---------------------------------------------------------------------------


def test_train_parity_smoke(key):
    """One representative cell of the matrix stays in the fast lane:
    f32, ReLU + residual epilogue (the ResNet block-output site)."""
    _assert_train_parity((4, 6, 5, 19), jnp.float32, True, True, key)


def test_eval_parity_smoke(key):
    _assert_eval_parity((8, 3, 3, 7), jnp.float32, True, True, key)


def test_gradcheck_identity_epilogue(key):
    """Numerical gradcheck on the custom VJP (identity epilogue: ReLU
    kinks would poison finite differences)."""
    from jax import test_util as jtu
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (2, 4, 4, 5))
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (5,))
    bias = 0.1 * jax.random.normal(ks[2], (5,))
    jtu.check_grads(lambda x, s, b: ops.fused_bn_train(x, s, b)[0],
                    (x, scale, bias), order=1, modes=["rev"],
                    atol=2e-2, rtol=2e-2)


def test_multiblock_accumulation(key):
    """Forcing a small row_block exercises the grid-accumulation path
    (the compiled-TPU tiling) against the same oracle; 105 rows over
    16-row blocks also hits the zero-pad tail."""
    x = jax.random.normal(key, (3, 5, 7, 11)) * 1.5 + 1.0
    dy = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
    scale, bias = jnp.ones(11), jnp.zeros(11)

    def fused(x):
        return fb.fused_bn_train(x, scale, bias, relu=True,
                                 interpret=True, row_block=16)

    (y1, m1, v1), vjp1 = jax.vjp(fused, x)
    (y2, m2, v2), vjp2 = jax.vjp(lambda x: ref.bn_forward(
        x, scale, bias, relu=True), x)
    np.testing.assert_allclose(y1, y2, atol=1e-5)
    np.testing.assert_allclose(m1, m2, atol=1e-5)
    np.testing.assert_allclose(v1, v2, atol=1e-5)
    cts = (dy, jnp.zeros_like(m1), jnp.zeros_like(v1))
    np.testing.assert_allclose(np.asarray(vjp1(cts)[0]),
                               np.asarray(vjp2(cts)[0]), atol=1e-4)


def test_stats_output_cotangents(key):
    """The mean/var outputs carry real cotangents (zero in the training
    step, where new BN state is value_and_grad aux — but the op must
    stay correct when they are used)."""
    x = jax.random.normal(key, (3, 5, 7, 11))
    s, b = jnp.ones(11), jnp.zeros(11)

    def through_stats(f):
        def g(x):
            y, m, v = f(x)
            return jnp.sum(y) + 2.0 * jnp.sum(m) + 3.0 * jnp.sum(v)
        return g

    g1 = jax.grad(through_stats(
        lambda x: ops.fused_bn_train(x, s, b)))(x)
    g2 = jax.grad(through_stats(
        lambda x: ref.bn_forward(x, s, b)))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


def test_large_mean_variance(key):
    """The stats kernel's block-centered + Chan-combined variance must
    match the centered oracle on the same large-mean bf16 data that
    breaks the uncentered E[x^2]-mu^2 form (see
    test_core_batchnorm.py::test_variance_large_mean_bf16_vs_f64_oracle)
    — in both the single-block and multi-block grid regimes."""
    k = jax.random.randint(key, (64, 4, 4, 8), -2, 3).astype(jnp.float32)
    x = (1024.0 + 4.0 * k).astype(jnp.bfloat16)
    x64 = np.asarray(x, np.float64)
    var64 = ((x64 - x64.mean((0, 1, 2))) ** 2).mean((0, 1, 2))
    for rb in (None, 16):  # whole-array block / 64-step grid
        _, mean, var = fb.fused_bn_train(
            x, jnp.ones(8), jnp.zeros(8), interpret=True, row_block=rb)
        np.testing.assert_allclose(np.asarray(var), var64, rtol=1e-3,
                                   err_msg=f"row_block={rb}")
        np.testing.assert_allclose(np.asarray(mean),
                                   x64.mean((0, 1, 2)), rtol=1e-6)


def test_resnet_apply_fused_matches_unfused(key):
    """Model level: the fused ResNet50 forward (train + eval paths)
    matches the unfused model on the same params/state."""
    from repro.configs import get_config, reduced_config
    from repro.models.resnet import ResNet50
    import dataclasses

    cfg = reduced_config(get_config("resnet50"))
    m0 = ResNet50(cfg, compute_dtype=jnp.float32)
    m1 = ResNet50(dataclasses.replace(cfg, fused_bn=True),
                  compute_dtype=jnp.float32)
    assert not m0.fused_bn and m1.fused_bn
    params = m0.init_params(key)[0]
    state = m0.init_state()
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 32, 32, 3))
    logits0, ns0 = m0.apply(params, state, x, train=True)
    logits1, ns1 = m1.apply(params, state, x, train=True)
    np.testing.assert_allclose(np.asarray(logits0), np.asarray(logits1),
                               atol=1e-3)
    for (k0, a), (k1, b) in zip(
            sorted(ns0.items()), sorted(ns1.items())):
        assert k0 == k1
        np.testing.assert_allclose(np.asarray(a["mean"]),
                                   np.asarray(b["mean"]), atol=1e-4,
                                   err_msg=k0)
        np.testing.assert_allclose(np.asarray(a["var"]),
                                   np.asarray(b["var"]), atol=1e-4,
                                   err_msg=k0)
    # eval path (given stats) through the fused apply kernel
    e0, _ = m0.apply(params, ns0, x, train=False)
    e1, _ = m1.apply(params, ns1, x, train=False)
    np.testing.assert_allclose(np.asarray(e0), np.asarray(e1), atol=1e-3)


def test_fusion_report_real_lowering():
    """The §10 claim from compiled HLO: per site, the fused fwd+VJP
    performs strictly fewer reduction passes than the unfused chain
    (2 stats + 2 backward sums vs XLA's mean/var/dscale/dbias/... set)
    and no more activation-sized writes."""
    from repro.launch.hlo_analysis import fusion_report

    shape = (4, 8, 8, 32)
    act = int(np.prod(shape))
    xs = jax.ShapeDtypeStruct(shape, jnp.float32)
    ss = jax.ShapeDtypeStruct((shape[-1],), jnp.float32)

    def prog(site):
        def p(x, scale, bias, res, dy):
            y, vjp = jax.vjp(site, x, scale, bias, res)
            return (y,) + vjp(dy)
        return jax.jit(p).lower(xs, ss, ss, xs, xs).compile().as_text()

    fused = prog(lambda x, s, b, r: ops.fused_bn_train(
        x, s, b, residual=r, relu=True)[0])
    unfused = prog(lambda x, s, b, r: ref.bn_forward(
        x, s, b, residual=r, relu=True)[0])
    rep = fusion_report(fused, unfused, act)
    assert rep["collapsed"], rep
    assert rep["fused"]["reduction_ops"] == 4.0, rep  # 2 fwd + 2 bwd
    assert rep["fused"]["reduction_ops"] < rep["unfused"]["reduction_ops"]


# ---------------------------------------------------------------------------
# slow lane: the full parity matrix + mesh tests
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("has_res", [False, True])
def test_train_parity_matrix(dtype, relu, has_res, key):
    _assert_train_parity((4, 6, 5, 19), dtype, relu, has_res, key)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("has_res", [False, True])
def test_eval_parity_matrix(dtype, relu, has_res, key):
    _assert_eval_parity((8, 3, 3, 7), dtype, relu, has_res, key)


@pytest.mark.slow
def test_cross_replica_parity_8dev():
    """Sync-BN on the 8-virtual-device mesh: the fused kernel's local
    moments + pmean combine and its psum'd backward must match the
    oracle (bn_batch_stats cross_replica + apply + epilogue) — outputs,
    global statistics, and grads for x (per-worker) and scale/bias
    (replicated, cotangents psum'd by shard_map AD)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.batchnorm import bn_apply_stats, bn_batch_stats
        from repro.kernels.fused_bn import fused_bn_train

        mesh = make_mesh((8,), ("data",))
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (16, 4, 4, 12)) * 2.0 + 1.0
        cot = jax.random.normal(ks[1], x.shape)
        scale = 1.0 + 0.1 * jax.random.normal(ks[2], (12,))
        bias = 0.1 * jax.random.normal(ks[3], (12,))

        def make_loss(fused):
            def local(x, scale, bias, cot):
                if fused:
                    y, m, v = fused_bn_train(
                        x, scale, bias, relu=True,
                        cross_replica=("data",), interpret=True)
                else:
                    m, v = bn_batch_stats(x, cross_replica=("data",))
                    y = jax.nn.relu(
                        bn_apply_stats(x, m, v, scale, bias))
                loss = jax.lax.psum(jnp.sum(y * cot), ("data",))
                return loss, m, v
            sm = shard_map(local, mesh=mesh,
                           in_specs=(P("data"), P(), P(), P("data")),
                           out_specs=(P(), P(), P()),
                           check_vma=False)
            def loss(x, scale, bias):
                l, m, v = sm(x, scale, bias, cot)
                return l, (m, v)
            return loss

        outs = {}
        for fused in (False, True):
            (l, (m, v)), g = jax.jit(jax.value_and_grad(
                make_loss(fused), argnums=(0, 1, 2),
                has_aux=True))(x, scale, bias)
            outs[fused] = (l, m, v) + g
        names = ("loss", "mean", "var", "dx", "dscale", "dbias")
        for a, b, n in zip(outs[False], outs[True], names):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, err_msg=n)
        print("CROSS_REPLICA_OK")
    """)
    assert "CROSS_REPLICA_OK" in out


@pytest.mark.slow
def test_fused_composes_with_overlap_and_zero_8dev():
    """The fused sites live inside the staged stem/stage0..3 segment
    forwards/VJPs and change no gradient leaf structure, so --fused-bn
    must compose with the backward-overlapped ZeRO step (§8/§9):
    2 steps of the fused overlap+zero step match the fused plain
    bucketed step within tolerance."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import OptimizerConfig, get_config, \\
            reduced_config
        from repro.launch.train import build_train_setup

        cfg = reduced_config(get_config("resnet50"))
        mesh = make_mesh((8, 1), ("data", "model"))

        def run(**kw):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=16, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=10,
                mesh=mesh, dp_mode="shardmap",
                compression="bf16+bucketed", bucket_bytes=16 * 1024,
                seed=0, fused_bn=True, **kw)
            batch = put({k: jnp.asarray(v)
                         for k, v in data.batch_at(0).items()})
            for _ in range(2):
                state, metrics = step(state, dict(batch))
            return state

        s0 = run()
        s1 = run(overlap_comm=True, zero_dp=True)
        for part in ("params", "model_state"):
            l0 = sorted(jax.tree_util.tree_leaves_with_path(s0[part]),
                        key=lambda t: str(t[0]))
            l1 = sorted(jax.tree_util.tree_leaves_with_path(s1[part]),
                        key=lambda t: str(t[0]))
            assert len(l0) == len(l1) and l0
            for (k0, a), (k1, b) in zip(l0, l1):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32),
                    np.asarray(b, np.float32), atol=1e-5,
                    err_msg=f"{part}{k0}")
        print("COMPOSE_OK")
    """)
    assert "COMPOSE_OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("sync_bn", [False, True],
                         ids=["plain", "cross_replica"])
def test_fused_step_matches_unfused_3steps_8dev(sync_bn):
    """Acceptance: the fused-BN training step (shardmap bucketed, 8
    virtual devices, --fused-bn) matches the unfused step's params and
    BN state within tolerance after 3 steps, plain and sync-BN."""
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import OptimizerConfig, get_config, \\
            reduced_config
        from repro.launch.train import build_train_setup

        cfg = reduced_config(get_config("resnet50"))
        mesh = make_mesh((8, 1), ("data", "model"))

        def run(fused):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=16, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=10,
                mesh=mesh, dp_mode="shardmap",
                compression="bf16+bucketed",
                bucket_bytes=16 * 1024, sync_bn={sync_bn},
                seed=0, fused_bn=fused)
            batch = put({{k: jnp.asarray(v)
                          for k, v in data.batch_at(0).items()}})
            for _ in range(3):
                state, metrics = step(state, dict(batch))
            return state

        s0, s1 = run(False), run(True)
        for part in ("params", "model_state"):
            l0 = sorted(jax.tree_util.tree_leaves_with_path(s0[part]),
                        key=lambda t: str(t[0]))
            l1 = sorted(jax.tree_util.tree_leaves_with_path(s1[part]),
                        key=lambda t: str(t[0]))
            assert len(l0) == len(l1) and l0
            for (k0, a), (k1, b) in zip(l0, l1):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32),
                    np.asarray(b, np.float32), atol=5e-4,
                    err_msg=f"{{part}}{{k0}}")
        print("STEP_PARITY_OK")
    """)
    assert "STEP_PARITY_OK" in out
