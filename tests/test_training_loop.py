"""Fault tolerance at the loop level: resume, determinism, stragglers,
prefetcher failure propagation, checkpoint-save dedup."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, get_config, reduced_config
from repro.launch.train import build_train_setup
from repro.training import LoopConfig, run_training


def _setup(steps_per_epoch=5, seed=0):
    cfg = reduced_config(get_config("resnet50"))
    opt_cfg = OptimizerConfig(kind="rmsprop_warmup")
    return build_train_setup(cfg, global_batch=8, seq_len=16,
                             opt_cfg=opt_cfg,
                             steps_per_epoch=steps_per_epoch, seed=seed)


def test_checkpoint_restart_bitwise_continuation(tmp_path):
    """Crash after step 10, restart => identical final state as an
    uninterrupted 20-step run (determinism contract of DESIGN.md §5)."""
    ckpt = str(tmp_path / "ck")

    # uninterrupted reference run
    model, state, step_fn, data, _, _ = _setup()
    ref = run_training(step_fn, state, data,
                       LoopConfig(total_steps=20, checkpoint_dir=None))

    # interrupted run: 10 steps (checkpointing), then a fresh process-like
    # resume for the remaining 10
    model, state, step_fn, data, _, _ = _setup()
    run_training(step_fn, state, data,
                 LoopConfig(total_steps=10, checkpoint_every=5,
                            checkpoint_dir=ckpt))
    model, state2, step_fn2, data2, _, _ = _setup()  # fresh init
    res = run_training(step_fn2, state2, data2,
                       LoopConfig(total_steps=20, checkpoint_every=100,
                                  checkpoint_dir=ckpt))
    assert res.resumed_from == 10
    for a, b in zip(jax.tree.leaves(ref.state["params"]),
                    jax.tree.leaves(res.state["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_straggler_event_detection(tmp_path):
    model, state, step_fn, data, _, _ = _setup()

    class SlowData:
        def __init__(self, inner):
            self.inner = inner

        def batch_at(self, step):
            if step == 15:
                time.sleep(1.0)  # simulated straggling host
            return self.inner.batch_at(step)

    res = run_training(step_fn, state, SlowData(data),
                       LoopConfig(total_steps=20, deadline_factor=3.0))
    assert any(e["step"] == 15 for e in res.straggler_events)


def test_prefetcher_propagates_worker_error():
    """Regression: a raising batch_at used to kill the daemon silently,
    leaving the consumer blocked forever on Queue.get()."""
    from repro.data import Prefetcher

    class Bad:
        def batch_at(self, step):
            if step >= 3:
                raise ValueError("boom at step 3")
            return {"x": np.zeros(2, np.float32)}

    p = Prefetcher(Bad())
    try:
        with pytest.raises(ValueError, match="boom at step 3"):
            for _ in range(10):
                next(p)
    finally:
        p.close()


def test_prefetcher_transform_error_propagates():
    from repro.data import Prefetcher

    class Ok:
        def batch_at(self, step):
            return {"x": np.zeros(2, np.float32)}

    def bad_transform(batch):
        raise RuntimeError("device_put failed")

    p = Prefetcher(Ok(), transform=bad_transform)
    try:
        with pytest.raises(RuntimeError, match="device_put failed"):
            next(p)
    finally:
        p.close()


def test_prefetcher_close_unblocks_pending_next():
    """Regression: close() must not race a consumer parked in next()."""
    from repro.data import Prefetcher

    class Slow:
        def batch_at(self, step):
            time.sleep(30.0)  # never yields a batch in test time
            return {}

    p = Prefetcher(Slow())
    got = {}

    def consume():
        try:
            next(p)
            got["out"] = "batch"
        except StopIteration:
            got["out"] = "stopped"

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.2)  # consumer is now blocked waiting for a batch
    p.close()
    t.join(timeout=5)
    assert not t.is_alive()
    assert got["out"] == "stopped"


def test_no_duplicate_final_checkpoint_save(tmp_path, monkeypatch):
    """Regression: when total_steps %% checkpoint_every == 0 the final
    step was saved async then immediately re-saved blocking (rmtree-ing
    the fresh directory). Each step must be serialized exactly once —
    counted at _write_checkpoint, the choke point both the sync save()
    and the AsyncCheckpointer worker funnel through."""
    import repro.checkpoint.checkpointer as cp
    saved = []
    real_write = cp._write_checkpoint

    def counting_write(directory, step, arrays, metadata=None):
        saved.append(step)
        return real_write(directory, step, arrays, metadata)

    monkeypatch.setattr(cp, "_write_checkpoint", counting_write)
    model, state, step_fn, data, _, _ = _setup()
    run_training(step_fn, state, data,
                 LoopConfig(total_steps=10, checkpoint_every=5,
                            checkpoint_dir=str(tmp_path / "ck")))
    assert sorted(saved) == [5, 10], saved
    from repro.checkpoint import list_checkpoints
    assert list_checkpoints(str(tmp_path / "ck")) == [5, 10]


def test_data_determinism():
    from repro.data import SyntheticImageData, SyntheticLMData
    a = SyntheticImageData(10, 16, 4, seed=3).batch_at(7)
    b = SyntheticImageData(10, 16, 4, seed=3).batch_at(7)
    np.testing.assert_array_equal(a["images"], b["images"])
    cfg = reduced_config(get_config("llama3.2-1b"))
    x = SyntheticLMData(cfg, 4, 32, seed=3).batch_at(9)
    y = SyntheticLMData(cfg, 4, 32, seed=3).batch_at(9)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    # targets are next-token shifted tokens
    z = SyntheticLMData(cfg, 4, 32, seed=3)
    b0 = z.batch_at(0)
    assert (b0["tokens"][:, 1:] == b0["targets"][:, :-1]).mean() > 0.99


# ---------------------------------------------------------------------------
# One step in flight: step n+1 is dispatched before step n's loss is read
# ---------------------------------------------------------------------------


class _StepLog:
    """A fake jitted step and a recording ``jax.device_get``: ``events``
    is the order in which the trainer dispatched steps (``("dispatch",
    n)``), read their losses (``("read", n)``) and evaluated
    (``("eval",)``)."""

    def __init__(self, monkeypatch):
        self.events = []
        self._step_of = {}  # id of a step's loss array -> the step
        self._losses = []  # held, so that no id is reused
        real = jax.device_get

        def device_get(x):
            n = self._step_of.get(id(x))
            if n is not None:
                self.events.append(("read", n))
            return real(x)

        monkeypatch.setattr(jax, "device_get", device_get)

    def step(self, state, batch, controls=None):
        n = int(batch["step"])
        self.events.append(("dispatch", n))
        loss = jnp.float32(n + 0.5)
        self._step_of[id(loss)] = n
        self._losses.append(loss)
        metrics = {"loss": loss}
        if controls is not None:  # the sentinel's outputs
            metrics.update(bad_step=jnp.float32(0.0),
                           nonfinite_step=jnp.float32(0.0),
                           grad_spike=jnp.float32(0.0))
        return {**state, "params": state["params"] + 1.0}, metrics

    def eval_step(self, params, model_state, batch):
        self.events.append(("eval",))
        return {"top1": jnp.float32(0.5), "loss": jnp.float32(1.0)}


class _Steps:
    def batch_at(self, step):
        return {"step": np.int32(step)}


def _fake_trainer(log, epochs, steps_per_epoch, eval_every=0, **kw):
    from repro.training import Trainer, TrainerConfig
    cfg = TrainerConfig(epochs=epochs, steps_per_epoch=steps_per_epoch,
                        eval_every_epochs=eval_every,
                        val_batches=1 if eval_every else 0,
                        checkpoint_every=0, log_every=1)
    state = {"params": jnp.float32(0.0), "model_state": {}}
    return Trainer(log.step, state, _Steps(), cfg,
                   eval_step=log.eval_step if eval_every else None,
                   val_data=_Steps() if eval_every else None, **kw).run()


def _d(n):
    return ("dispatch", n)


def _r(n):
    return ("read", n)


@pytest.mark.parametrize("with_eval,want,overlap", [
    (False, [_d(0), _d(1), _r(0), _d(2), _r(1), _d(3), _r(2), _d(4),
             _r(3), _d(5), _r(4), _r(5)], 5),
    # each epoch's last loss is read before the eval sees the state, so
    # the first step of the next epoch has nothing in flight before it
    (True, [_d(0), _d(1), _r(0), _d(2), _r(1), _r(2), ("eval",),
            _d(3), _d(4), _r(3), _d(5), _r(4), _r(5), ("eval",)], 4),
])
def test_next_step_dispatched_before_loss_is_read(monkeypatch, with_eval,
                                                  want, overlap):
    epochs, per_epoch = 2, 3
    steps = epochs * per_epoch
    log = _StepLog(monkeypatch)
    res = _fake_trainer(log, epochs, per_epoch,
                        eval_every=1 if with_eval else 0)
    assert log.events == want
    assert [h["step"] for h in res.history] == list(range(steps))
    assert [h["loss"] for h in res.history] == [n + 0.5
                                                 for n in range(steps)]
    assert res.telemetry["counters"]["train.overlap"] == overlap
    assert float(res.state["params"]) == steps


def test_recovery_manager_reads_each_loss_before_the_next_step(
        monkeypatch):
    from repro.resilience import ResilienceConfig
    steps = 5
    log = _StepLog(monkeypatch)
    res = _fake_trainer(log, 1, steps, resilience=ResilienceConfig())
    assert log.events == [e for n in range(steps)
                          for e in (("dispatch", n), ("read", n))]
    assert res.telemetry["counters"].get("train.overlap", 0) == 0
    assert [h["loss"] for h in res.history] == [n + 0.5
                                                 for n in range(steps)]


def test_trainer_matches_plain_loop_bitwise():
    """The trainer, one step in flight, against a plain Python loop over
    the same jitted step: the same losses and final state, bit for bit
    (no step missed, repeated or reordered; no donated buffer read)."""
    steps = 4
    _, state, step_fn, data, _, _ = _setup()
    res = run_training(step_fn, state, data,
                       LoopConfig(total_steps=steps, log_every=1))

    _, state, step_fn, data, _, _ = _setup()
    losses = []
    for n in range(steps):
        state, metrics = step_fn(state, data.batch_at(n))
        losses.append(float(jax.device_get(metrics["loss"])))

    assert [h["step"] for h in res.history] == list(range(steps))
    assert [h["loss"] for h in res.history] == losses
    assert all(np.isfinite(losses))
    got, want = jax.tree.leaves(res.state), jax.tree.leaves(state)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
