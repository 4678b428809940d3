"""Fault-tolerant training (DESIGN.md §13).

Fast single-process tests cover the chaos spec grammar, the event log,
the recovery state machine, checkpoint integrity (atomic replace,
crc32, corrupted-newest fallback), the sentinel's no-fault bitwise
parity and NaN/spike skip gates on the GSPMD path, and the Trainer's
skip / rollback / data-retry / abort flows driven by injected chaos.
The six-sync-mode parity matrix runs in subprocesses on a virtual
8-device host mesh (marked ``slow``).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ck
from repro.checkpoint.checkpointer import (
    ARRAYS,
    AsyncCheckpointer,
    CheckpointCorruptError,
    MANIFEST,
    gc_stale_tmpdirs,
    list_checkpoints,
    restore,
    save,
)
from repro.configs import OptimizerConfig, get_config, reduced_config
from repro.launch.train import build_train_setup
from repro.resilience import (
    Action,
    ChaosError,
    EventLog,
    RecoveryManager,
    ResilienceConfig,
    parse_chaos,
    sentinel_controls,
)
from repro.training import Trainer, TrainerConfig

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


# ---------------------------------------------------------------------------
# chaos spec grammar
# ---------------------------------------------------------------------------


def test_chaos_grammar_parses_kinds_ranges_and_seed():
    eng = parse_chaos("nan_grad@3,data_stall@5-7:0.25,seed=9,straggler@2")
    assert eng.seed == 9
    kinds = [(t.kind, t.step, t.arg) for t in eng.triggers]
    assert ("nan_grad", 3, None) in kinds
    assert ("data_stall", 5, 0.25) in kinds and ("data_stall", 7, 0.25) \
        in kinds
    assert ("straggler", 2, 0.5) in kinds  # default arg


@pytest.mark.parametrize("spec", [
    "bogus@3",            # unknown kind
    "nan_grad",           # missing @step
    "nan_grad@7-3",       # inverted range
    "nan_grad@x",         # non-integer step
])
def test_chaos_grammar_rejects_malformed(spec):
    with pytest.raises(ValueError):
        parse_chaos(spec)


def test_chaos_triggers_fire_once_and_deterministically():
    batch = {"images": np.zeros((2, 4, 4, 3), np.float32),
             "labels": np.zeros((2,), np.int32)}
    poisoned = []
    for _ in range(2):
        eng = parse_chaos("nan_grad@1", seed=5)
        out = eng.inject_batch(1, dict(batch))
        poisoned.append(int(np.flatnonzero(np.isnan(out["images"]))[0]))
        # one-shot: a post-rollback replay of the same step is clean
        again = eng.inject_batch(1, dict(batch))
        assert not np.isnan(again["images"]).any()
    assert poisoned[0] == poisoned[1]  # seed-keyed position
    assert not np.isnan(batch["images"]).any()  # source never mutated


def test_chaos_data_crash_raises_chaos_error():
    eng = parse_chaos("data_crash@2")
    src = eng.wrap_source(_ArraySource())
    _ = src.batch_at(1)
    with pytest.raises(ChaosError):
        src.batch_at(2)
    _ = src.batch_at(2)  # one-shot: retry succeeds


class _ArraySource:
    def batch_at(self, step):
        return {"images": np.full((2, 2), float(step), np.float32),
                "labels": np.zeros((2,), np.int32)}


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------


def test_event_log_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path) as log:
        log.emit("rollback", to_step=4, wasted=np.int64(3),
                 loss=jnp.float32(1.5))
        log.emit("abort", step=9)
    lines = [json.loads(x) for x in open(path)]
    assert [r["kind"] for r in lines] == ["rollback", "abort"]
    assert lines[0]["wasted"] == 3  # numpy/jax scalars serialized plain
    assert lines[0]["loss"] == 1.5
    assert log.of_kind("abort")[0]["step"] == 9
    assert [r["seq"] for r in lines] == [0, 1]


# ---------------------------------------------------------------------------
# recovery state machine (host-side, no training)
# ---------------------------------------------------------------------------


def _mgr(**kw):
    return RecoveryManager(ResilienceConfig(**kw), EventLog())


def test_recovery_skip_then_rollback_then_abort():
    mgr = _mgr(max_consecutive_bad=2, max_rollbacks=1)
    bad = {"bad_step": 1.0, "nonfinite_step": 1.0}
    assert mgr.observe(5, bad) is Action.SKIPPED
    assert mgr.observe(6, bad) is Action.ROLLBACK
    mgr.on_rollback(from_step=6, to_step=4)
    assert mgr.observe(4, {"bad_step": 0.0}) is Action.CONTINUE
    assert mgr.consecutive_bad == 0
    assert mgr.observe(5, bad) is Action.SKIPPED
    assert mgr.observe(6, bad) is Action.ABORT  # budget of 1 spent
    assert mgr.events.kinds().count("step_skipped") == 4
    assert "abort" in mgr.events.kinds()


def test_recovery_spike_threshold_arms_after_warmup():
    mgr = _mgr(spike_factor=3.0, warmup_steps=3, ema_decay=0.5)
    assert mgr.spike_threshold() == float("inf")
    for s in range(3):
        mgr.observe(s, {"bad_step": 0.0, "grad_norm": 2.0})
    assert mgr.spike_threshold() == pytest.approx(6.0)  # 3.0 * EMA(2.0)
    # a skipped step must NOT poison the EMA
    mgr.observe(3, {"bad_step": 1.0, "grad_norm": float("nan")})
    assert mgr.spike_threshold() == pytest.approx(6.0)


def test_recovery_lr_backoff_window():
    mgr = _mgr(lr_backoff=0.5, backoff_steps=4)
    assert mgr.lr_scale(10) == 1.0
    mgr.on_rollback(from_step=12, to_step=10)
    assert mgr.lr_scale(10) == 0.5
    assert mgr.lr_scale(13) == 0.5
    assert mgr.lr_scale(14) == 1.0  # window expired
    ctl = mgr.controls(10)
    assert float(ctl["lr_scale"]) == 0.5
    assert float(ctl["spike_threshold"]) == float("inf")


# ---------------------------------------------------------------------------
# checkpoint integrity + atomic replace
# ---------------------------------------------------------------------------


def _tree(v=0.0):
    return {"params": {"w": np.arange(6, dtype=np.float32) + v,
                       "b": np.ones((2,), np.float32) * v},
            "opt": {"step": np.int32(int(v))}}


def test_list_checkpoints_requires_payload(tmp_path):
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    os.makedirs(os.path.join(d, "step_0000000002"))
    with open(os.path.join(d, "step_0000000002", MANIFEST), "w") as f:
        json.dump({"step": 2, "keys": []}, f)  # manifest, no arrays.npz
    assert list_checkpoints(d) == [1]


def test_restore_falls_back_on_truncated_newest(tmp_path):
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    save(d, 2, _tree(2.0))
    payload = os.path.join(d, "step_0000000002", ARRAYS)
    with open(payload, "r+b") as f:
        f.truncate(os.path.getsize(payload) // 2)
    seen = []
    arrays, manifest = restore(d, on_corrupt=lambda s, e: seen.append(s))
    assert manifest["step"] == 1
    assert seen == [2]
    np.testing.assert_array_equal(arrays["['params']['w']"],
                                  _tree(1.0)["params"]["w"])


def test_restore_falls_back_on_bitflipped_newest(tmp_path):
    # regression: a single flipped byte mid-file (silent media
    # corruption) must be caught, not loaded as garbage weights
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    save(d, 2, _tree(2.0))
    payload = os.path.join(d, "step_0000000002", ARRAYS)
    # flip a byte inside the stored array payload itself (a flip in zip
    # header slack would be harmless); npz members are ZIP_STORED, so
    # the raw array bytes appear verbatim in the file
    needle = _tree(2.0)["params"]["w"].tobytes()
    blob = open(payload, "rb").read()
    pos = blob.find(needle)
    assert pos > 0, "stored array bytes not found in npz"
    with open(payload, "r+b") as f:
        f.seek(pos + 2)
        byte = f.read(1)
        f.seek(pos + 2)
        f.write(bytes([byte[0] ^ 0xFF]))
    _, manifest = restore(d)
    assert manifest["step"] == 1


def test_restore_explicit_step_still_raises_on_corrupt(tmp_path):
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    save(d, 2, _tree(2.0))
    payload = os.path.join(d, "step_0000000002", ARRAYS)
    with open(payload, "r+b") as f:
        f.truncate(10)
    with pytest.raises(CheckpointCorruptError):
        restore(d, step=2)
    _, manifest = restore(d, step=1)  # older one untouched
    assert manifest["step"] == 1


def test_restore_crc_mismatch_detected(tmp_path):
    # a VALID zip whose array bytes changed after the manifest was
    # written: only the crc32 check can catch this
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    save(d, 2, _tree(2.0))
    payload = os.path.join(d, "step_0000000002", ARRAYS)
    with np.load(payload) as z:
        arrays = {k: z[k] for k in z.files}
    key = "['params']['w']"
    arrays[key] = arrays[key] + 1.0
    np.savez(payload, **arrays)
    _, manifest = restore(d)
    assert manifest["step"] == 1
    with pytest.raises(CheckpointCorruptError, match="crc32"):
        restore(d, step=2)


def test_restore_raises_when_every_candidate_corrupt(tmp_path):
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    with open(os.path.join(d, "step_0000000001", ARRAYS), "r+b") as f:
        f.truncate(4)
    with pytest.raises(CheckpointCorruptError, match="every candidate"):
        restore(d)


def test_atomic_resave_preserves_old_when_rename_fails(tmp_path,
                                                       monkeypatch):
    # crash in the replace window: the old data must come back, not be
    # rmtree'd first (the pre-fix save deleted old THEN renamed)
    d = str(tmp_path)
    save(d, 1, _tree(1.0))
    real_rename = os.rename

    def failing_rename(src, dst):
        if os.path.basename(src).startswith(".tmp_ckpt_"):
            raise OSError("simulated crash at rename")
        return real_rename(src, dst)

    monkeypatch.setattr(ck.os, "rename", failing_rename)
    with pytest.raises(OSError, match="simulated"):
        save(d, 1, _tree(99.0))
    monkeypatch.undo()
    arrays, manifest = restore(d)
    assert manifest["step"] == 1
    np.testing.assert_array_equal(arrays["['params']['w']"],
                                  _tree(1.0)["params"]["w"])
    assert gc_stale_tmpdirs(d) == 0  # failed save left no litter


def test_save_failure_before_replace_keeps_old(tmp_path, monkeypatch):
    d = str(tmp_path)
    save(d, 1, _tree(1.0))

    def failing_savez(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(ck.np, "savez", failing_savez)
    with pytest.raises(OSError, match="disk full"):
        save(d, 1, _tree(99.0))
    monkeypatch.undo()
    arrays, _ = restore(d)
    np.testing.assert_array_equal(arrays["['params']['w']"],
                                  _tree(1.0)["params"]["w"])


def test_async_checkpointer_gcs_stale_tmpdirs(tmp_path):
    d = str(tmp_path)
    os.makedirs(os.path.join(d, ".tmp_ckpt_dead"))
    os.makedirs(os.path.join(d, ".old_ckpt_dead"))
    save(d, 1, _tree(1.0))
    AsyncCheckpointer(d)
    names = set(os.listdir(d))
    assert ".tmp_ckpt_dead" not in names
    assert ".old_ckpt_dead" not in names
    assert "step_0000000001" in names


def test_async_save_snapshots_host_arrays_exactly_once(tmp_path,
                                                       monkeypatch):
    calls = []
    real_flatten = ck._flatten

    def counting_flatten(tree):
        calls.append(1)
        return real_flatten(tree)

    monkeypatch.setattr(ck, "_flatten", counting_flatten)
    ac = AsyncCheckpointer(str(tmp_path))
    ac.save(3, _tree(3.0), block=True)
    assert len(calls) == 1, "async save must not re-copy on the worker"
    _, manifest = restore(str(tmp_path))
    assert manifest["step"] == 3


def test_manifest_carries_crc32_per_array(tmp_path):
    d = str(tmp_path)
    path = save(d, 1, _tree(1.0))
    manifest = json.load(open(os.path.join(path, MANIFEST)))
    assert set(manifest["crc32"]) == set(manifest["keys"])
    for v in manifest["crc32"].values():
        assert isinstance(v, int)


# ---------------------------------------------------------------------------
# sentinel + Trainer integration (GSPMD fast path)
# ---------------------------------------------------------------------------


def _build(sentinel: bool):
    cfg = reduced_config(get_config("resnet50"))
    opt_cfg = OptimizerConfig(kind="momentum_sgd", schedule="constant")
    return build_train_setup(cfg, global_batch=8, seq_len=16,
                             opt_cfg=opt_cfg, steps_per_epoch=4, seed=0,
                             sentinel=sentinel)


@pytest.fixture(scope="module")
def sent():
    """Sentinel-enabled GSPMD setup; host snapshot of the init so every
    test re-materializes fresh state (the jitted step donates)."""
    model, state, train_step, data, put_batch, _ = _build(sentinel=True)
    host0 = jax.tree.map(np.array, state)
    return {"train_step": train_step, "host0": host0, "data": data}


def _fresh(host0):
    return jax.tree.map(jnp.asarray, host0)


def _assert_trees_bitwise_equal(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        assert np.asarray(la).tobytes() == np.asarray(lb).tobytes(), \
            jax.tree_util.keystr(path)


def test_sentinel_disabled_vs_enabled_bitwise_parity(sent):
    """The no-fault contract: with default controls the wrapped step's
    select gates pass every leaf through bitwise-unchanged."""
    _, state, plain_step, data, _, _ = _build(sentinel=False)
    controls = sentinel_controls()
    wrapped = _fresh(sent["host0"])
    for s in range(3):
        batch = data.batch_at(s)
        state, _ = plain_step(state, batch)
        wrapped, metrics = sent["train_step"](wrapped, batch, controls)
        assert float(metrics["bad_step"]) == 0.0
    _assert_trees_bitwise_equal(state, wrapped)


def test_nan_batch_skipped_state_bitwise_unchanged(sent):
    batch = sent["data"].batch_at(0)
    batch = dict(batch)
    poisoned = np.array(batch["images"])
    poisoned.reshape(-1)[7] = np.nan
    batch["images"] = poisoned
    state, metrics = sent["train_step"](_fresh(sent["host0"]), batch,
                                        sentinel_controls())
    assert float(metrics["bad_step"]) == 1.0
    assert float(metrics["nonfinite_step"]) == 1.0
    # params, optimizer state (incl. step counter) and BN statistics all
    # carried over untouched — as if the step never ran
    _assert_trees_bitwise_equal(state, _fresh(sent["host0"]))


def test_spike_gate_skips_but_flags_finite(sent):
    batch = sent["data"].batch_at(0)
    state, metrics = sent["train_step"](
        _fresh(sent["host0"]), batch,
        sentinel_controls(spike_threshold=1e-12))
    assert float(metrics["grad_spike"]) == 1.0
    assert float(metrics["nonfinite_step"]) == 0.0
    assert float(metrics["bad_step"]) == 1.0
    _assert_trees_bitwise_equal(state, _fresh(sent["host0"]))


def _run_trainer(sent, tmp_path, chaos_spec=None, resilience=None,
                 epochs=2, ckpt_every=2, **res_kw):
    tcfg = TrainerConfig(epochs=epochs, steps_per_epoch=4,
                         eval_every_epochs=0, val_batches=0,
                         checkpoint_every=ckpt_every,
                         checkpoint_dir=str(tmp_path) if ckpt_every
                         else None, log_every=1)
    if resilience is None:
        resilience = ResilienceConfig(**res_kw)
    chaos = parse_chaos(chaos_spec) if chaos_spec else None
    return Trainer(sent["train_step"], _fresh(sent["host0"]),
                   sent["data"], tcfg, resilience=resilience,
                   chaos=chaos).run()


def test_trainer_skips_nan_step_and_completes(sent, tmp_path):
    res = _run_trainer(sent, tmp_path, chaos_spec="nan_grad@3")
    kinds = [r["kind"] for r in res.events]
    assert kinds.count("step_skipped") == 1
    assert "rollback" not in kinds
    skipped = [r for r in res.events if r["kind"] == "step_skipped"][0]
    assert skipped["step"] == 3 and skipped["nonfinite"]
    assert res.history[-1]["step"] == 7  # ran to completion


def test_trainer_rollback_restores_last_good(sent, tmp_path):
    res = _run_trainer(sent, tmp_path, chaos_spec="nan_grad@4-6",
                       max_consecutive_bad=3)
    rb = [r for r in res.events if r["kind"] == "rollback"]
    assert len(rb) == 1
    # checkpoints at 2 and 4; bad streak 4-6 -> restore the step-4 save
    # (mid-streak saves are suppressed, so the target did not advance)
    assert rb[0] == {**rb[0], "from_step": 6, "to_step": 4,
                     "wasted_steps": 2}
    assert res.history[-1]["step"] == 7
    losses = [r["loss"] for r in res.history if r["step"] == 7]
    assert np.isfinite(losses[-1])


def test_trainer_rollback_falls_back_past_corrupt_newest(sent, tmp_path):
    res = _run_trainer(sent, tmp_path, epochs=3,
                       chaos_spec="ckpt_truncate@7,nan_grad@8-9",
                       max_consecutive_bad=2)
    kinds = [r["kind"] for r in res.events]
    assert "corrupt_checkpoint_skipped" in kinds
    rb = [r for r in res.events if r["kind"] == "rollback"][0]
    assert rb["to_step"] == 6  # newest (8) was truncated -> next-newest
    assert res.history[-1]["step"] == 11


def test_trainer_abort_after_rollback_budget(sent, tmp_path):
    with pytest.raises(RuntimeError, match="aborted"):
        _run_trainer(sent, tmp_path, chaos_spec="nan_grad@3-5",
                     max_consecutive_bad=3, max_rollbacks=0)


def test_trainer_rollback_without_ckpt_dir_raises(sent, tmp_path):
    with pytest.raises(RuntimeError, match="checkpoint_dir"):
        _run_trainer(sent, tmp_path, chaos_spec="nan_grad@2-4",
                     ckpt_every=0, max_consecutive_bad=3)


def test_trainer_data_crash_recovers_with_resilience(sent, tmp_path):
    res = _run_trainer(sent, tmp_path, chaos_spec="data_crash@5")
    restarts = [r for r in res.events if r["kind"] == "data_restart"]
    assert len(restarts) == 1 and restarts[0]["step"] == 5
    assert res.history[-1]["step"] == 7


def test_prefetcher_crash_propagates_without_resilience(sent, tmp_path):
    """The pre-existing error contract is unchanged when resilience is
    off: a dead input worker kills the run."""
    tcfg = TrainerConfig(epochs=1, steps_per_epoch=8,
                         eval_every_epochs=0, val_batches=0,
                         checkpoint_every=0, log_every=1)
    chaos = parse_chaos("data_crash@3")
    # no resilience: 2-arg step required, so wrap data only
    _, state, plain_step, data, _, _ = _build(sentinel=False)
    with pytest.raises(ChaosError):
        Trainer(plain_step, state, chaos.wrap_source(data), tcfg).run()


def test_step_misalignment_raises_runtime_error(sent, monkeypatch):
    import repro.training.loop as loop_mod

    class _Skewed:
        def __init__(self, source, start_step=0, depth=2, transform=None,
                     num_workers=1, put=None, device_ahead=1):
            self._step = start_step
            self.last_wait_s = 0.0

        def __next__(self):
            return self._step + 1, None  # off by one

        def close(self):
            pass

    monkeypatch.setattr(loop_mod, "DataPipeline", _Skewed)
    tcfg = TrainerConfig(epochs=1, steps_per_epoch=4,
                         eval_every_epochs=0, val_batches=0,
                         checkpoint_every=0, log_every=1)
    with pytest.raises(RuntimeError, match="misalignment"):
        Trainer(sent["train_step"], _fresh(sent["host0"]), sent["data"],
                tcfg, resilience=ResilienceConfig()).run()


def test_event_log_written_to_disk(sent, tmp_path):
    path = str(tmp_path / "events.jsonl")
    res = _run_trainer(
        sent, tmp_path / "ckpt", chaos_spec="nan_grad@3",
        resilience=ResilienceConfig(event_log=path))
    lines = [json.loads(x) for x in open(path)]
    assert [r["kind"] for r in lines] == [r["kind"] for r in res.events]
    assert any(r["kind"] == "step_skipped" for r in lines)


# ---------------------------------------------------------------------------
# six-sync-mode no-fault parity matrix (subprocess, virtual 8-dev host)
# ---------------------------------------------------------------------------

MODE_KW = {
    "gspmd": "dict(dp_mode='gspmd')",
    "perleaf": "dict(dp_mode='shardmap', compression='none')",
    "bucketed": "dict(dp_mode='shardmap', compression='bf16+bucketed')",
    "overlap": ("dict(dp_mode='shardmap', compression='bf16+bucketed', "
                "overlap_comm=True)"),
    "zero": ("dict(dp_mode='shardmap', compression='bf16+bucketed', "
             "zero_dp=True)"),
    "zero_overlap": ("dict(dp_mode='shardmap', "
                     "compression='bf16+bucketed', zero_dp=True, "
                     "overlap_comm=True)"),
}

_PARITY_BODY = """
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import OptimizerConfig, get_config, reduced_config
from repro.launch.train import build_train_setup
from repro.resilience.sentinel import sentinel_controls

cfg = reduced_config(get_config("resnet50"))
opt = OptimizerConfig(kind="momentum_sgd", schedule="constant")
mesh = make_mesh((8, 1), ("data", "model"))
finals = []
for sentinel in (False, True):
    _, state, step, data, put_batch, _ = build_train_setup(
        cfg, global_batch=16, seq_len=16, opt_cfg=opt,
        steps_per_epoch=4, mesh=mesh, seed=0, sentinel=sentinel,
        **{kw})
    controls = sentinel_controls()
    for s in range(2):
        batch = put_batch(data.batch_at(s))
        if sentinel:
            state, m = step(state, batch, controls)
            assert float(m["bad_step"]) == 0.0
        else:
            state, m = step(state, batch)
    finals.append(jax.tree.map(np.array, state))
plain, sent = finals
fp = jax.tree_util.tree_flatten_with_path(plain)[0]
fs = jax.tree.leaves(sent)
assert len(fp) == len(fs)
for (path, lp), ls in zip(fp, fs):
    assert np.asarray(lp).tobytes() == np.asarray(ls).tobytes(), \\
        ("{mode}", jax.tree_util.keystr(path))
print("PARITY_OK {mode}")
"""


@pytest.mark.slow
@pytest.mark.parametrize("mode", list(MODE_KW))
def test_sentinel_parity_all_sync_modes(mode):
    """Acceptance: with no fault injected, the sentinel-enabled step is
    bitwise-equal to the current step in every sync mode."""
    out = run_py(_PARITY_BODY.format(kw=MODE_KW[mode], mode=mode))
    assert f"PARITY_OK {mode}" in out
