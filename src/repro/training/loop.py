"""Training loop: epoch-aware trainer interleaving jitted train steps
with jitted validation, plus prefetching data, async checkpointing and
fault-tolerance hooks (resume, straggler deadline accounting).

The paper's headline claim is a *validation* number, and its §2 BN
technique only exists at validation time: the last-minibatch BN
statistics are all-reduced across workers right before each eval
(DESIGN.md §7). ``Trainer`` owns that interleaving for both execution
modes — GSPMD (stats already global; ``finalize_state`` is identity)
and shard_map DP (``finalize_worker_bn_stats`` merges the per-worker
statistics). It also owns per-epoch top-1/loss history, best-checkpoint
retention, and eval-state resume.

The hot loop stays deliberately thin — all heavy lifting is in the
jitted steps — so at 1000+ nodes the host-side critical path is just
`device_put` of the next batch (prefetched) and dispatch. Validation
runs only at epoch boundaries, off the steady-state path.

``run_training`` remains as the legacy step-driven API (one epoch, no
eval) layered on the same loop.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro import telemetry
from repro.checkpoint import AsyncCheckpointer, list_checkpoints, restore
from repro.checkpoint.checkpointer import BEST_DIR
from repro.data.pipeline import DataPipeline
from repro.resilience.events import EventLog
from repro.resilience.recovery import (Action, RecoveryManager,
                                       ResilienceConfig)
from repro.resilience.sentinel import SENTINEL_METRICS

PyTree = Any


@dataclasses.dataclass
class TrainerConfig:
    epochs: int = 1
    steps_per_epoch: int = 100
    # validation cadence: every N epochs (0 disables eval entirely);
    # the final epoch is always evaluated when eval is enabled.
    eval_every_epochs: int = 1
    val_batches: int = 4
    checkpoint_every: int = 50  # steps; 0 => final checkpoint only
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 3
    keep_best: bool = True  # retain best-top-1 state outside the GC window
    log_every: int = 10
    # straggler mitigation: if a step exceeds deadline_factor x the median
    # step time, it is logged as a straggler event; at cluster scale the
    # launcher uses this to trigger backup-step execution (DESIGN.md §5).
    deadline_factor: float = 3.0
    # input pipeline (DESIGN.md §15): host producer threads, reorder-
    # buffer bound, and how many steps to stage on device ahead of
    # consumption (device staging needs put_batch)
    data_workers: int = 1
    prefetch_depth: int = 4
    device_ahead: int = 1


@dataclasses.dataclass
class TrainResult:
    state: PyTree
    history: list  # per-step train log ({"step", "loss", "time"})
    epoch_history: list  # per-eval {"epoch", "step", "top1", "loss", ...}
    straggler_events: list
    resumed_from: Optional[int]
    best: Optional[Dict]  # {"top1", "epoch", "step"} (eval enabled only)
    # resilience event records (DESIGN.md §13): skipped steps, rollbacks,
    # chaos injections, corrupt checkpoints skipped on restore
    events: list = dataclasses.field(default_factory=list)
    # input-boundedness attribution (DESIGN.md §15): total wall time,
    # total time blocked on the input pipeline, and their ratio —
    # ~0 means compute-bound, ~1 means data-starved
    input_stats: Dict = dataclasses.field(default_factory=dict)
    # the run's spans and counters (``telemetry.Recorder.summary``)
    telemetry: Dict = dataclasses.field(default_factory=dict)


class Trainer:
    """Epoch-driven train/eval loop (DESIGN.md §7).

    ``train_step``: jitted (state, batch) -> (state, metrics).
    ``eval_step``: jitted (params, model_state, batch) -> metrics dict
        (must contain ``top1`` for best-checkpoint tracking; see
        ``training.step.make_eval_step``).
    ``finalize_state``: model_state -> eval model_state, the paper's
        pre-validation BN all-reduce. None = identity (GSPMD, where the
        partitioner already made the statistics global); shard_map DP
        passes ``finalize_worker_bn_stats``.
    ``val_data``: held-out pipeline with ``batch_at(i)`` disjoint from
        the training split (``data.synthetic`` split contract); eval
        always replays batches ``0..val_batches-1`` so every epoch is
        scored on the same held-out set.
    """

    def __init__(self, train_step: Callable, state: PyTree, train_data,
                 cfg: TrainerConfig, *, eval_step: Optional[Callable] = None,
                 val_data=None, finalize_state: Optional[Callable] = None,
                 put_batch: Optional[Callable] = None,
                 metadata: Optional[Dict] = None,
                 state_shardings: Optional[PyTree] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 chaos=None):
        if cfg.eval_every_epochs and eval_step is not None \
                and val_data is None:
            raise ValueError("eval enabled but no val_data given")
        self.train_step = train_step
        self.state = state
        self.train_data = train_data
        self.cfg = cfg
        self.eval_step = eval_step
        self.val_data = val_data
        self.finalize_state = finalize_state
        self.put_batch = put_batch
        self.metadata = dict(metadata or {})
        self.state_shardings = state_shardings
        # fault tolerance (DESIGN.md §13): with `resilience` set,
        # ``train_step`` must be the 3-arg sentinel-wrapped form
        # (resilience.sentinel.wrap_step_with_sentinel); `chaos` is a
        # resilience.chaos.ChaosEngine for deterministic fault injection
        self.resilience = resilience
        self.chaos = chaos
        self._val_batches = None  # built once: the held-out set is fixed

    # ------------------------------------------------------------- eval
    def _eval_enabled(self) -> bool:
        return (self.eval_step is not None
                and self.cfg.eval_every_epochs > 0
                and self.cfg.val_batches > 0)

    def evaluate(self, state: PyTree, epoch: int, step: int) -> Dict:
        """One validation pass over the held-out set. Applies the
        pre-validation BN finalize, then averages the jitted eval
        metrics over ``val_batches`` fixed batches."""
        mstate = state["model_state"]
        if self.finalize_state is not None:
            mstate = self.finalize_state(mstate)
        if self._val_batches is None:
            batches = [self.val_data.batch_at(i)
                       for i in range(self.cfg.val_batches)]
            if self.put_batch is not None:
                batches = [self.put_batch(b) for b in batches]
            self._val_batches = batches
        sums: Dict[str, float] = {}
        for batch in self._val_batches:
            metrics = self.eval_step(state["params"], mstate, batch)
            for k, v in metrics.items():
                sums[k] = sums.get(k, 0.0) + float(jax.device_get(v))
        rec = {k: v / self.cfg.val_batches for k, v in sums.items()}
        rec.update(epoch=epoch, step=step)
        return rec

    # -------------------------------------------------------------- run
    def _ckpt_metadata(self, eval_history: List[Dict],
                       best: Optional[Dict]) -> Dict:
        # snapshot, not reference: AsyncCheckpointer json.dumps metadata
        # on a background thread while the loop keeps appending records
        meta = dict(self.metadata)
        meta["eval_history"] = [dict(r) for r in eval_history]
        if best is not None:
            meta["best"] = dict(best)
        return meta

    def run(self) -> TrainResult:
        with telemetry.recording() as rec:
            return self._run(rec)

    def _run(self, rec: telemetry.Recorder) -> TrainResult:
        cfg = self.cfg
        total_steps = cfg.epochs * cfg.steps_per_epoch
        ckpt = (AsyncCheckpointer(cfg.checkpoint_dir, cfg.keep_checkpoints)
                if cfg.checkpoint_dir else None)
        # best-top-1 retention, off the hot path: snapshot on this
        # thread, serialize off-thread; keep=1 GC leaves exactly one
        # best checkpoint, outside the main rotating window
        best_ckpt = (AsyncCheckpointer(
            os.path.join(cfg.checkpoint_dir, BEST_DIR), keep=1)
            if ckpt and self._eval_enabled() and cfg.keep_best else None)

        # ---- resilience plumbing (DESIGN.md §13) ----
        events = (EventLog(self.resilience.event_log
                           if self.resilience else None)
                  if (self.resilience or self.chaos) is not None else None)
        manager = (RecoveryManager(self.resilience, events)
                   if self.resilience is not None else None)
        chaos = self.chaos
        if chaos is not None and chaos.events is None:
            chaos.events = events
        train_source = (chaos.wrap_source(self.train_data)
                        if chaos is not None else self.train_data)

        def on_corrupt(s, exc):  # corrupt checkpoint skipped on restore
            if events is not None:
                events.emit("corrupt_checkpoint_skipped", step=s,
                            error=str(exc))

        # ---- resume (fault tolerance: newest valid manifest), restoring
        # the eval trajectory and best-so-far alongside the arrays ----
        state = self.state
        start_step = 0
        resumed_from = None
        eval_history: List[Dict] = []
        best: Optional[Dict] = None
        if ckpt and list_checkpoints(cfg.checkpoint_dir):
            state, manifest = restore(cfg.checkpoint_dir, target=state,
                                      shardings=self.state_shardings,
                                      on_corrupt=on_corrupt)
            start_step = manifest["step"]
            resumed_from = start_step
            eval_history = list(manifest["metadata"].get(
                "eval_history", []))
            best = manifest["metadata"].get("best")

        def make_pipeline(at_step):
            # device staging rides the `put` stage (H2D one step ahead);
            # host transforms (augmentation, chaos) live in the source
            return DataPipeline(
                train_source, start_step=at_step,
                depth=cfg.prefetch_depth,
                num_workers=cfg.data_workers,
                put=self.put_batch,
                device_ahead=cfg.device_ahead)

        prefetch = make_pipeline(start_step)
        history = []
        straggler_events = []
        step_times = []
        last_saved = start_step if resumed_from is not None else -1
        try:
            # anchor checkpoint: rollback must always have a target, even
            # when the divergence hits before the first periodic save
            if manager is not None and ckpt and not list_checkpoints(
                    cfg.checkpoint_dir):
                with telemetry.span("ckpt.save", start_step):
                    ckpt.save(start_step, state,
                              metadata=self._ckpt_metadata(eval_history,
                                                           best))
                last_saved = start_step

            step = start_step
            data_retries_left = (self.resilience.data_retries
                                 if self.resilience else 0)
            while step < total_steps:
                if chaos is not None:
                    chaos.on_step_start(step)
                # the step's span includes the wait on the feed: that
                # is what a straggling host looks like
                with telemetry.span("train.step", step) as st:
                    try:
                        got_step, batch = next(prefetch)
                    except Exception as exc:
                        # a dead input worker (the pipeline re-raises
                        # from the consumer). With resilience: bounded
                        # pipeline restarts at the current step;
                        # without: propagate (the pre-existing error
                        # contract).
                        if manager is None or data_retries_left <= 0:
                            raise
                        data_retries_left -= 1
                        events.emit("data_restart", step=step,
                                    error=str(exc),
                                    retries_left=data_retries_left)
                        prefetch.close()
                        prefetch = make_pipeline(step)
                        continue
                    if got_step != step:
                        # a real error, not an assert: data/step
                        # misalignment silently trains on wrong batches
                        # under `python -O`
                        raise RuntimeError(
                            f"prefetcher misalignment: got batch for "
                            f"step {got_step}, expected {step}")
                    if self.resilience is not None:
                        data_retries_left = self.resilience.data_retries
                    with telemetry.span("train.dispatch", step):
                        if manager is not None:
                            state, metrics = self.train_step(
                                state, batch, manager.controls(step))
                        else:
                            state, metrics = self.train_step(state, batch)
                    loss = metrics.get("loss")
                    if loss is not None:
                        with telemetry.span("train.sync", step):
                            loss = float(jax.device_get(loss))
                dt = st.seconds
                data_wait = st.children.get("feed.wait", 0) / 1e9
                step_times.append(dt)
                med = float(np.median(step_times[-50:]))
                if len(step_times) > 5 and dt > cfg.deadline_factor * med:
                    slow = {"step": step, "time": dt, "median": med,
                            **_breakdown(rec, st)}
                    straggler_events.append(slow)
                    if events is not None:
                        events.emit("straggler", **slow)

                # ---- recovery decision (before eval/save: a bad step
                # must never be checkpointed or scored) ----
                if manager is not None:
                    host = {"loss": loss}
                    for k in SENTINEL_METRICS + ("grad_norm",):
                        if k in metrics:
                            host[k] = float(jax.device_get(metrics[k]))
                    action = manager.observe(step, host)
                    if action is Action.ABORT:
                        raise RuntimeError(
                            f"training aborted at step {step}: "
                            f"{manager.cfg.max_rollbacks} rollbacks "
                            "exhausted and the step is still diverging "
                            "(see the resilience event log)")
                    if action is Action.ROLLBACK:
                        if ckpt is None:
                            raise RuntimeError(
                                "resilience rollback requires "
                                "TrainerConfig.checkpoint_dir (no "
                                "checkpoint to restore from)")
                        ckpt.wait()  # flush in-flight save + its errors
                        state, manifest = restore(
                            cfg.checkpoint_dir, target=state,
                            shardings=self.state_shardings,
                            on_corrupt=on_corrupt)
                        restored = manifest["step"]
                        eval_history = list(manifest["metadata"].get(
                            "eval_history", []))
                        best = manifest["metadata"].get("best")
                        history = [r for r in history
                                   if r["step"] < restored]
                        prefetch.close()
                        prefetch = make_pipeline(restored)
                        manager.on_rollback(from_step=step,
                                            to_step=restored)
                        last_saved = restored
                        step = restored
                        continue
                    # CONTINUE / SKIPPED fall through: on a skipped step
                    # the state was carried over in-jit, the batch is
                    # simply abandoned

                # mid-streak, hold back eval and checkpoints: the state
                # is identical to the pre-streak state, and saving here
                # would advance the rollback target past the steps that
                # need replaying
                in_bad_streak = (manager is not None
                                 and manager.consecutive_bad > 0)

                if step % cfg.log_every == 0 or step == total_steps - 1:
                    history.append({"step": step, "loss": loss,
                                    "time": dt, "data_wait": data_wait})

                done = step + 1
                # ---- epoch boundary: the paper's eval path ----
                if self._eval_enabled() and not in_bad_streak \
                        and done % cfg.steps_per_epoch == 0:
                    epoch = done // cfg.steps_per_epoch
                    if (epoch % cfg.eval_every_epochs == 0
                            or epoch == cfg.epochs):
                        with telemetry.span("eval", done):
                            ev = self.evaluate(state, epoch, done)
                        eval_history.append(ev)
                        top1 = ev.get("top1")
                        if top1 is not None and (
                                best is None or top1 > best["top1"]):
                            best = {"top1": top1, "epoch": epoch,
                                    "step": done}
                            if best_ckpt:
                                with telemetry.span("ckpt.save", done):
                                    best_ckpt.save(
                                        done, state,
                                        metadata=self._ckpt_metadata(
                                            eval_history, best))
                # eval before checkpoint so a resume replays from a
                # manifest that already contains this epoch's record
                if ckpt and cfg.checkpoint_every and not in_bad_streak \
                        and done % cfg.checkpoint_every == 0:
                    with telemetry.span("ckpt.save", done):
                        ckpt.save(done, state,
                                  metadata=self._ckpt_metadata(
                                      eval_history, best))
                    last_saved = done
                    if chaos is not None \
                            and chaos.has_pending_ckpt_fault(done):
                        ckpt.wait()  # land the save, then corrupt it
                        chaos.after_save(cfg.checkpoint_dir, done)
                step = done
            # final checkpoint — skipped when the periodic save above
            # already wrote this exact step (previously the same step was
            # saved async then immediately re-saved blocking, rmtree-ing
            # the fresh directory)
            if ckpt and last_saved != total_steps:
                with telemetry.span("ckpt.save", total_steps):
                    ckpt.save(total_steps, state,
                              metadata=self._ckpt_metadata(eval_history,
                                                           best),
                              block=True)
        finally:
            prefetch.close()
            if best_ckpt:
                best_ckpt.wait()
            if ckpt:
                ckpt.wait()
            if events is not None:
                events.close()
        wall_s = rec.total_s("train.step")
        wait_s = rec.total_s("feed.wait")
        input_stats = {
            "wall_s": wall_s,
            "data_wait_s": wait_s,
            "data_starved_frac": wait_s / wall_s if wall_s > 0 else 0.0,
        }
        return TrainResult(state=state, history=history,
                           epoch_history=eval_history,
                           straggler_events=straggler_events,
                           resumed_from=resumed_from, best=best,
                           events=list(events.records) if events else [],
                           input_stats=input_stats,
                           telemetry=rec.summary())


def _breakdown(rec: telemetry.Recorder, st) -> Dict:
    """What a step's time went to: the milliseconds of each span inside
    its ``train.step`` span (``self`` for the rest), and the retraces and
    compiles counted in it."""
    spans_ms = {k: v / 1e6 for k, v in st.children.items()}
    spans_ms["self"] = st.seconds * 1e3 - sum(spans_ms.values())
    counters = rec.step_counters.get(st.step, {})
    return {"spans_ms": spans_ms,
            **{k: counters.get(k, 0) for k in ("traces", "compiles",
                                               "compile_s")}}


# ---------------------------------------------------------------------------
# Legacy step-driven API (pre-epoch callers: examples, elastic tests)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 3
    log_every: int = 10
    deadline_factor: float = 3.0
    data_workers: int = 1


@dataclasses.dataclass
class LoopResult:
    state: PyTree
    history: list
    straggler_events: list
    resumed_from: Optional[int]
    telemetry: Dict = dataclasses.field(default_factory=dict)


def run_training(
    train_step: Callable,  # jitted (state, batch) -> (state, metrics)
    state: PyTree,
    data,  # has batch_at(step)
    loop_cfg: LoopConfig,
    put_batch: Optional[Callable] = None,  # host batch -> device arrays
    metadata: Optional[Dict] = None,
    state_shardings: Optional[PyTree] = None,
) -> LoopResult:
    """Step-counter training without validation: one ``Trainer`` epoch."""
    cfg = TrainerConfig(
        epochs=1, steps_per_epoch=loop_cfg.total_steps,
        eval_every_epochs=0, val_batches=0,
        checkpoint_every=loop_cfg.checkpoint_every,
        checkpoint_dir=loop_cfg.checkpoint_dir,
        keep_checkpoints=loop_cfg.keep_checkpoints,
        log_every=loop_cfg.log_every,
        deadline_factor=loop_cfg.deadline_factor,
        data_workers=loop_cfg.data_workers)
    result = Trainer(train_step, state, data, cfg, put_batch=put_batch,
                     metadata=metadata,
                     state_shardings=state_shardings).run()
    return LoopResult(state=result.state, history=result.history,
                      straggler_events=result.straggler_events,
                      resumed_from=result.resumed_from,
                      telemetry=result.telemetry)
