"""Epoch-driven Trainer: validation actually runs (DESIGN.md §7).

Covers the paper's eval protocol — held-out split, pre-validation BN
all-reduce, best-checkpoint retention, eval-state resume — plus the
GSPMD/shard_map eval-logits parity the protocol guarantees.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, get_config, reduced_config
from repro.launch.train import build_eval_setup, build_train_setup
from repro.training import Trainer, TrainerConfig

from conftest import SUBPROCESS_ENV_8DEV


def _setup(steps_per_epoch=5, seed=0, global_batch=16):
    cfg = reduced_config(get_config("resnet50"))
    opt_cfg = OptimizerConfig(kind="rmsprop_warmup")
    model, state, step_fn, data, put, sh = build_train_setup(
        cfg, global_batch=global_batch, seq_len=16, opt_cfg=opt_cfg,
        steps_per_epoch=steps_per_epoch, seed=seed)
    eval_step, val_data, finalize = build_eval_setup(
        model, cfg, global_batch=global_batch, seq_len=16, seed=seed)
    return model, state, step_fn, data, eval_step, val_data, finalize


def _trainer_cfg(**kw):
    base = dict(epochs=3, steps_per_epoch=5, eval_every_epochs=1,
                val_batches=2, checkpoint_every=0, checkpoint_dir=None,
                log_every=100)
    base.update(kw)
    return TrainerConfig(**base)


class TestEpochEval:
    def test_per_epoch_top1_history(self):
        model, state, step_fn, data, ev, vd, fin = _setup()
        res = Trainer(step_fn, state, data, _trainer_cfg(),
                      eval_step=ev, val_data=vd, finalize_state=fin).run()
        assert [r["epoch"] for r in res.epoch_history] == [1, 2, 3]
        for r in res.epoch_history:
            assert 0.0 <= r["top1"] <= 1.0
            assert np.isfinite(r["loss"])
            assert r["step"] == r["epoch"] * 5
        # the synthetic task is learnable: accuracy must improve
        assert res.epoch_history[-1]["top1"] > res.epoch_history[0]["top1"] \
            or res.epoch_history[0]["top1"] == 1.0
        assert res.best is not None and 0.0 <= res.best["top1"] <= 1.0

    def test_eval_every_epochs_cadence_includes_final(self):
        model, state, step_fn, data, ev, vd, fin = _setup()
        res = Trainer(step_fn, state, data,
                      _trainer_cfg(epochs=3, eval_every_epochs=2),
                      eval_step=ev, val_data=vd, finalize_state=fin).run()
        # epoch 2 (cadence) and epoch 3 (final epoch always evaluated)
        assert [r["epoch"] for r in res.epoch_history] == [2, 3]

    def test_val_split_disjoint_and_deterministic(self):
        from repro.data import SyntheticImageData
        tr = SyntheticImageData(10, 16, 4, seed=3, split="train")
        va = SyntheticImageData(10, 16, 4, seed=3, split="val")
        va2 = SyntheticImageData(10, 16, 4, seed=3, split="val")
        # deterministic: same (seed, split, step) -> same batch
        np.testing.assert_array_equal(va.batch_at(5)["images"],
                                      va2.batch_at(5)["images"])
        # disjoint: no val batch equals any train batch over a horizon
        val0 = va.batch_at(0)["images"]
        for step in range(50):
            assert not np.array_equal(tr.batch_at(step)["images"], val0)

    def test_legacy_run_training_unchanged(self):
        from repro.training import LoopConfig, run_training
        model, state, step_fn, data, *_ = _setup()
        res = run_training(step_fn, state, data,
                           LoopConfig(total_steps=6, log_every=2))
        assert [h["step"] for h in res.history] == [0, 2, 4, 5]
        assert res.resumed_from is None


class TestBestCheckpointRetention:
    def _fake_pieces(self, top1s):
        """Scripted eval so best-tracking logic is exercised without
        depending on a real accuracy trajectory."""
        state = {"params": {"w": jnp.zeros(2)},
                 "model_state": {"s": jnp.zeros(2)},
                 "opt": {"step": jnp.zeros((), jnp.int32)}}

        def train_step(s, batch):
            return s, {"loss": jnp.float32(0.0)}

        calls = iter(top1s)

        def eval_step(params, mstate, batch):
            return {"top1": jnp.float32(next(calls)),
                    "loss": jnp.float32(1.0)}

        class Data:
            def batch_at(self, step):
                return {"x": np.zeros(2, np.float32)}

        return state, train_step, eval_step, Data()

    def test_best_is_retained_not_last(self, tmp_path):
        from repro.checkpoint import restore_best
        ck = str(tmp_path / "ck")
        state, tstep, estep, data = self._fake_pieces([0.2, 0.8, 0.5])
        res = Trainer(tstep, state, data,
                      _trainer_cfg(epochs=3, steps_per_epoch=2,
                                   val_batches=1, checkpoint_dir=ck,
                                   checkpoint_every=2),
                      eval_step=estep, val_data=data).run()
        assert res.best == {"top1": pytest.approx(0.8), "epoch": 2,
                            "step": 4}
        _, manifest = restore_best(ck)
        assert manifest["step"] == 4
        assert manifest["metadata"]["best"]["top1"] == pytest.approx(0.8)
        # exactly one best checkpoint on disk
        from repro.checkpoint import list_checkpoints
        import os
        assert list_checkpoints(os.path.join(ck, "best")) == [4]

    def test_eval_history_in_checkpoint_metadata(self, tmp_path):
        from repro.checkpoint import restore
        ck = str(tmp_path / "ck")
        state, tstep, estep, data = self._fake_pieces([0.2, 0.8, 0.5])
        Trainer(tstep, state, data,
                _trainer_cfg(epochs=3, steps_per_epoch=2, val_batches=1,
                             checkpoint_dir=ck, checkpoint_every=2),
                eval_step=estep, val_data=data).run()
        _, manifest = restore(ck)
        hist = manifest["metadata"]["eval_history"]
        assert [r["epoch"] for r in hist] == [1, 2, 3]
        assert hist[1]["top1"] == pytest.approx(0.8)


class TestResumeEval:
    def test_resume_then_eval_matches_uninterrupted(self, tmp_path):
        """Determinism contract (DESIGN.md §5+§7): crash after epoch 2,
        resume, and the epoch-3/4 evals equal the uninterrupted run's."""
        spe = 5
        # uninterrupted 4-epoch reference
        model, state, step_fn, data, ev, vd, fin = _setup(spe)
        ref = Trainer(step_fn, state, data, _trainer_cfg(epochs=4),
                      eval_step=ev, val_data=vd, finalize_state=fin).run()

        ck = str(tmp_path / "ck")
        model, state, step_fn, data, ev, vd, fin = _setup(spe)
        Trainer(step_fn, state, data,
                _trainer_cfg(epochs=2, checkpoint_dir=ck,
                             checkpoint_every=spe),
                eval_step=ev, val_data=vd, finalize_state=fin).run()
        model, state2, step_fn2, data2, ev2, vd2, fin2 = _setup(spe)
        res = Trainer(step_fn2, state2, data2,
                      _trainer_cfg(epochs=4, checkpoint_dir=ck,
                                   checkpoint_every=spe),
                      eval_step=ev2, val_data=vd2,
                      finalize_state=fin2).run()
        assert res.resumed_from == 2 * spe
        # restored epochs 1-2 + fresh 3-4 == reference trajectory
        assert [r["epoch"] for r in res.epoch_history] == [1, 2, 3, 4]
        for a, b in zip(ref.epoch_history, res.epoch_history):
            np.testing.assert_allclose(a["top1"], b["top1"], rtol=1e-6)
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)


def run_py(body: str, timeout=420) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=SUBPROCESS_ENV_8DEV, capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
    return res.stdout


def test_eval_logits_parity_gspmd_vs_shardmap():
    """Acceptance: after the paper's pre-validation BN all-reduce, the
    shard_map DP mode produces the same eval logits as GSPMD (same data,
    same init, uncompressed sync to isolate the BN path)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import OptimizerConfig, get_config, \\
            reduced_config
        from repro.data import make_data
        from repro.configs import ShapeConfig
        from repro.launch.train import build_train_setup
        from repro.training.step import finalize_worker_bn_stats
        cfg = reduced_config(get_config('resnet50'))
        mesh = make_mesh((8, 1), ('data', 'model'))
        logits = {}
        vb = make_data(cfg, ShapeConfig('val', 16, 16, 'train'), seed=0,
                       split='val').batch_at(0)
        for mode in ('gspmd', 'shardmap'):
            model, state, step, data, put, _ = build_train_setup(
                cfg, global_batch=16, seq_len=16,
                opt_cfg=OptimizerConfig(), steps_per_epoch=5,
                mesh=mesh, dp_mode=mode, seed=0, sync_bn=True,
                compression='none')
            for s in range(3):
                batch = put({k: jnp.asarray(v)
                             for k, v in data.batch_at(s).items()})
                state, _ = step(state, batch)
            mstate = state['model_state']
            if mode == 'shardmap':
                assert jax.tree.leaves(
                    mstate)[0].shape[0] == 8  # per-worker stats
                mstate = finalize_worker_bn_stats(mstate)
            out_logits, _ = model.apply(
                state['params'], mstate, jnp.asarray(vb['images']),
                train=False)
            logits[mode] = np.asarray(jax.device_get(out_logits),
                                      np.float32)
        diff = np.abs(logits['gspmd'] - logits['shardmap']).max()
        print('LOGIT_DIFF', diff)
        assert diff < 1e-4, diff
    """)
    assert "LOGIT_DIFF" in out


def test_cli_epoch_driven_both_modes():
    """Acceptance: the train CLI prints per-epoch held-out top-1 in both
    --dp-mode gspmd and --dp-mode shardmap."""
    for mode in ("gspmd", "shardmap"):
        res = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--arch",
             "resnet50", "--reduced", "--epochs", "2",
             "--eval-every-epochs", "1", "--steps-per-epoch", "3",
             "--global-batch", "16", "--val-batches", "1",
             "--dp-mode", mode],
            env=SUBPROCESS_ENV_8DEV, capture_output=True, text=True,
            timeout=420)
        assert res.returncode == 0, f"STDERR:\n{res.stderr[-4000:]}"
        lines = [l for l in res.stdout.splitlines() if "val top1" in l]
        assert len(lines) == 2, res.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    """``enable_compile_cache``: with JAX_COMPILATION_CACHE_DIR set the
    cache is written there and nothing is set in code; without it the
    cache goes to the one fixed directory inside the checkout."""
    env = dict(SUBPROCESS_ENV_8DEV)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import os, jax, jax.numpy as jnp
        from repro.launch.train import COMPILE_CACHE_DIR, enable_compile_cache
        d = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == d, d
        print('DIR', d)
        if 'JAX_COMPILATION_CACHE_DIR' in os.environ:
            jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
            print('ENTRIES', len(os.listdir(d)))
    """)], env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-4000:]
    lines = dict(l.split(" ", 1) for l in res.stdout.splitlines())
    if from_env:
        assert lines["DIR"] == str(tmp_path / "cache")
        assert int(lines["ENTRIES"]) > 0
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert lines["DIR"] == os.path.join(repo, ".jax_cache")
