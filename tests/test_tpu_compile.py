"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e, at the
widths ResNet-50 trains at, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology. Interpret-mode tests cannot see what it refuses
(unaligned tiles, too much VMEM, bf16 vector ops the v5e lacks), so each
kernel of the main path is compiled here for one described chip and its
program must hold a ``tpu_custom_call`` (a compiled kernel, not an
interpreted loop). Nothing runs: these say nothing about results or
times.

The topology is described only inside the fixture below, so importing
this file loads no TPU library; where it cannot be described, every test
here skips.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.optimizer import HybridHyper
from repro.kernels import ops

N_PARAMS = 25_557_032  # ResNet-50
N_SEGMENTS = 161  # ResNet-50 parameter leaves
BUCKET_ELEMS = 64 * 2 ** 20 // 2  # one 64 MiB bucket of a 16-bit wire


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler or library lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's compile cannot be read back from the
        # persistent cache, so keep it out of the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer ``ops`` to the compiled kernels (this process runs on CPU,
    where ``ops._interpret`` picks interpret mode)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [(32, 56, 56, 256), (32, 7, 7, 2048)])
@pytest.mark.parametrize("residual", [False, True])
def test_fused_bn_train_fwd_bwd(one_chip, compiled_kernels, shape,
                                residual):
    c = shape[-1]

    def loss(x, r, scale, bias):
        y, _, _ = ops.fused_bn_train(x, scale, bias, relu=True,
                                     residual=r if residual else None)
        return jnp.sum(y.astype(jnp.float32))

    x = _spec(one_chip, shape, jnp.bfloat16)
    ch = _spec(one_chip, (c,), jnp.float32)
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, ch, ch)


def test_fused_bn_apply(one_chip, compiled_kernels):
    shape = (32, 56, 56, 256)
    ch = _spec(one_chip, (256,), jnp.float32)
    _compile(lambda x, m, v, s, b: ops.fused_bn_apply(x, m, v, s, b,
                                                      relu=True),
             _spec(one_chip, shape, jnp.bfloat16), ch, ch, ch, ch)


@pytest.mark.parametrize("array_wd", [False, True])
def test_fused_update(one_chip, compiled_kernels, array_wd):
    def update(g, p, d, m, wd):
        h = HybridHyper(eta=jnp.float32(0.1), alpha_sgd=jnp.float32(0.3))
        return ops.fused_hybrid_update(g, p, d, m, h,
                                       wd if array_wd else 1e-4)

    s = _spec(one_chip, (N_PARAMS,), jnp.float32)
    _compile(update, s, s, s, s, s)


@pytest.mark.parametrize("wire", [jnp.bfloat16, jnp.float16])
def test_bucket_pack_unpack_cast(one_chip, compiled_kernels, wire):
    pack = _compile(lambda x: ops.pack_cast(x, wire),
                    _spec(one_chip, (BUCKET_ELEMS,), jnp.float32))
    unpack = _compile(lambda x: ops.unpack_cast(x, jnp.float32),
                      _spec(one_chip, (BUCKET_ELEMS,), wire))
    # the kernels' operations carry their names into the device trace
    assert re.search(r"%bucket_pack[.\d]* = \S+ custom-call\(", pack)
    assert re.search(r"%bucket_unpack[.\d]* = \S+ custom-call\(", unpack)


@pytest.mark.parametrize("train", [True, False])
def test_fused_input(one_chip, compiled_kernels, train):
    x = _spec(one_chip, (32, 224, 224, 3), jnp.float32)
    ch = _spec(one_chip, (3,), jnp.float32)
    if train:
        _compile(lambda x, p, m, i: ops.fused_input_train(
            x, p, m, i, out_dtype=jnp.bfloat16),
            x, _spec(one_chip, (32, 4), jnp.int32), ch, ch)
    else:
        _compile(lambda x, m, i: ops.fused_input_eval(
            x, m, i, out_dtype=jnp.bfloat16), x, ch, ch)


def test_lars_segment_partials_and_update(one_chip, compiled_kernels):
    f = _spec(one_chip, (N_PARAMS,), jnp.float32)
    seg = _spec(one_chip, (N_PARAMS,), jnp.int32)
    _compile(lambda p, g, wd, s: ops.fused_segment_sq_partials(
        p, g, wd, s, N_SEGMENTS), f, f, f, seg)
    _compile(lambda g, p, d, wd, s, t: ops.fused_lars_update(
        g, p, d, wd, s, t, jnp.float32(0.1), 0.9),
        f, f, f, f, seg, _spec(one_chip, (N_SEGMENTS,), jnp.float32))
