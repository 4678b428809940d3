"""Buffer donation on the jitted train steps (training/step.py:
jit_train_step): donating the state argument lets the updated
params/opt-state/BN-state reuse the input buffers — it must change
buffer lifetimes only, never results. Parity is checked in both DP
modes (GSPMD single-device jit; explicit shard_map DP on the 8-virtual-
device mesh in a subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

ENV8 = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
}

_BODY = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import OptimizerConfig, get_config, reduced_config
    from repro.launch.train import build_train_setup
    from repro.training.step import jit_train_step

    cfg = reduced_config(get_config("resnet50"))
    mesh = {mesh}
    def run(donate):
        model, state, _step, data, put, _ = build_train_setup(
            cfg, global_batch=8, seq_len=16, opt_cfg=OptimizerConfig(),
            steps_per_epoch=10, mesh=mesh, dp_mode={dp_mode!r}, seed=0,
            compression={compression!r})
        # re-jit the underlying step with/without donation: the
        # build path donates by default, so rebuild the un-jitted fn
        from repro.training.step import (
            make_dp_shardmap_train_step, make_train_step)
        from repro.configs import ParallelConfig, TrainConfig
        from repro.optim import make_optimizer
        opt = make_optimizer(OptimizerConfig(), 10, 8)
        tc = TrainConfig(optimizer=OptimizerConfig(),
                         parallel=ParallelConfig(
                             dp_axes=("data",),
                             compression={compression!r}, zero_1=False))
        if {dp_mode!r} == "shardmap":
            raw = make_dp_shardmap_train_step(model, opt, tc, mesh,
                                              ("data",))
        else:
            raw = make_train_step(model, opt, tc)
        step = jit_train_step(raw, donate=donate)
        batch = data.batch_at(0)
        batch = {{k: jnp.asarray(v) for k, v in batch.items()}}
        if put is not None:
            batch = put(batch)
        for _ in range(2):
            state, metrics = step(state, dict(batch))
        return state, metrics

    s0, m0 = run(False)
    s1, m1 = run(True)
    for (k0, a), (k1, b) in zip(
            jax.tree_util.tree_leaves_with_path(s0),
            jax.tree_util.tree_leaves_with_path(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(k0))
    np.testing.assert_array_equal(np.asarray(m0["loss"]),
                                  np.asarray(m1["loss"]))
    print("DONATION_PARITY_OK")
"""


def test_donation_parity_gspmd_single_device():
    """GSPMD mode: donated vs non-donated step, bitwise-equal state
    after 2 steps (no mesh: plain jit path)."""
    body = _BODY.format(mesh="None", dp_mode="gspmd",
                        compression="bf16")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=ENV8, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    assert "DONATION_PARITY_OK" in res.stdout


def test_donation_parity_shardmap_8dev():
    """Explicit shard_map DP mode (bucketed sync) on 8 virtual devices:
    donation changes buffers only, never results."""
    body = _BODY.format(
        mesh='make_mesh((8, 1), ("data", "model"))',
        dp_mode="shardmap", compression="bf16+bucketed")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=ENV8, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    assert "DONATION_PARITY_OK" in res.stdout
