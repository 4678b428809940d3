"""Smoke run of the paper's training path on a TPU, through the trainer's
normal build (``repro.launch.train``), at ResNet-50's published widths.

    python3 chip_smoke.py               # one chip: phases (a) and (b)
    python3 chip_smoke.py --four-chips  # 2x2 host: the DP mesh phase only

Phases on one chip:
  (a) ResNet-50 (224x224x3, 1000 classes, 25.6M parameters), per-chip
      batch 32 (the paper's 32k over 1024 workers), shard_map DP with the
      f16 bucketed all-reduce, RMSprop warm-up, host input pipeline: three
      training steps through ``Trainer``, then one validation pass after
      ``finalize_worker_bn_stats`` (the paper's BN without moving
      averages). Checks finite losses and moved parameters, and that the
      eval step, given the statistics one training step recorded and the
      parameters that step saw, reproduces that step's training loss.
  (b) The same three steps with the Pallas kernels on the path (fused BN,
      fused optimizer update, fused on-device input transform). Checks
      that the compiled step holds ``tpu_custom_call`` (kernels compiled,
      not interpreted), that every loss agrees with (a), and that the
      parameter update of the first step agrees with (a)'s leaf by leaf.

``--four-chips``: a 4x1 mesh at global batch 128 (32 per chip), shard_map
DP with the f16 bucketed all-reduce and cross-replica BN against GSPMD on
the same chips, both with f32 matmuls at full precision; three steps, loss
difference under 0.05, and the batch and the per-worker BN state must span
all four devices. It also prints how far the two modes' first-step updates
differ.

The one-step comparisons, the eval replay and the four-chip modes run with
f32 matmuls at full precision (``CHECK_PRECISION``); the Trainer runs of
(a) and (b) use the TPU's default, as ``launch/train.py`` does.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no result. Everything
runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GLOBAL_BATCH_PER_CHIP = 32
STEPS = 3
# (a) and (b) differ in how BN, the optimizer update and the input
# transform round in f32 (~1e-7 relative per op). The trainer computes in
# f32, whose convolutions the MXU runs on bf16-rounded operands (2^-9
# relative), so an f32 difference that flips one bf16 rounding moves an
# activation by up to 2^-9; over ResNet-50's ~50 conv layers that adds up
# (in quadrature) to about sqrt(50) * 2^-9 = 1.4% of a logit at worst and
# far less on average. Losses must agree to 1% of their value.
FUSED_LOSS_RTOL = 1e-2
# Every comparison below between two programs -- (a) against (b) for one
# step, the eval replay, shard_map against GSPMD -- traces its programs
# with f32 matmuls and convolutions at full precision. At the TPU's
# default (one bf16 pass) ResNet-50's gradients at initialisation are
# mostly rounding noise: the same XLA program at default and at full
# precision gave gradients 1.25 apart (relative L2, all leaves) with a
# third of their signs opposite, so two programs that differ in fusion
# agree on nothing (TPU v5e readings in PERF.md). At full precision the
# fused-BN and XLA-BN gradients were 0.0197 apart, 0.39% of signs opposite.
CHECK_PRECISION = "highest"
# The first RMSprop warm-up step moves nearly every weight by the same
# amount, 10 * eta_rmsprop * sign(g) (m starts at 0), so one step's update
# differs between (a) and (b) where a gradient element takes the other
# sign: 0.39% of elements give about 2 * sqrt(0.0039) = 0.13 over all
# leaves, and a few flips in a 64-element BN leaf give up to ~0.5. A
# kernel that is wrong at one site makes that site's update uncorrelated
# (sqrt(2)) or reversed (2); the default-precision noise above gave 0.94.
FUSED_UPDATE_RTOL = 0.5  # all leaves
FUSED_UPDATE_LEAF_RTOL = 1.0  # worst leaf
# eval-mode BN with the statistics a training step recorded, on that
# step's batch and parameters, is the training forward again: the same
# f32 ops in another fusion
EVAL_REPLAY_RTOL = 1e-4
# the shard_map-vs-GSPMD bound of tests/test_distributed.py: the two modes
# reduce the gradient in different orders and cast it to f16 on the wire
FOUR_CHIP_LOSS_ATOL = 0.05

class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def device_record():
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def build(cfg, *, global_batch, fused, dp_mode="shardmap",
          sync_bn=False, mesh=None):
    """Train setup exactly as ``launch/train.py:main`` builds it for
    ``--dp-mode shardmap --compression f16+bucketed --optimizer
    rmsprop_warmup`` plus, with ``fused``, ``--fused-bn
    --use-fused-kernel --fused-input`` (without it, the host input
    pipeline applies the same augment+normalize transform)."""
    from repro.configs import InputConfig, OptimizerConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_train_setup

    if mesh is None:
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    opt_cfg = OptimizerConfig(kind="rmsprop_warmup", schedule="slow_start")
    input_cfg = InputConfig(fused=fused, num_workers=1)
    model, state, step, data, put, shardings = build_train_setup(
        cfg, global_batch=global_batch, seq_len=128, opt_cfg=opt_cfg,
        steps_per_epoch=STEPS, mesh=mesh, dp_mode=dp_mode, seed=0,
        use_fused_kernel=fused, compression="f16+bucketed",
        fused_bn=fused, sync_bn=sync_bn, input_cfg=input_cfg)
    return model, state, step, data, put, shardings, mesh, input_cfg


def compile_step(step, state, data, put):
    """AOT-compile the train step for the first batch; returns the
    compiled text and the seconds it took."""
    t0 = time.perf_counter()
    batch = put(data.batch_at(0))
    text = step.lower(state, batch).compile().as_text()
    return text, time.perf_counter() - t0


def host_leaves(state):
    """Host copies of a few parameters (the jitted step donates state)."""
    p = state["params"]
    return {k: np.asarray(jax.device_get(v)) for k, v in
            (("stem/conv", p["stem"]["conv"]), ("fc/w", p["fc"]["w"]))}


def host_params(params):
    """Every parameter leaf on the host, keyed by its tree path."""
    return {jax.tree_util.keystr(k): np.asarray(jax.device_get(v))
            for k, v in jax.tree_util.tree_leaves_with_path(params)}


def probe_step(step, state, batch):
    """One step from a copy of ``state`` (the Trainer's run starts from
    ``state`` itself). Returns (params before, params after, the state
    the step left, the step's loss) — parameters on the host."""
    copy = jax.tree.map(lambda x: x.copy(), state)
    before = host_params(copy["params"])
    new, metrics = step(copy, batch)
    return before, host_params(new["params"]), new, float(metrics["loss"])


def update_gap(before, after_a, after_b):
    """Relative L2 gap between two one-step parameter updates: the worst
    leaf (with its name) and over all leaves."""
    worst, name, num, den = 0.0, "", 0.0, 0.0
    for k in before:
        da = after_a[k].astype(np.float64) - before[k]
        db = after_b[k].astype(np.float64) - before[k]
        n, d = np.sum((da - db) ** 2), np.sum(da ** 2)
        num, den = num + n, den + d
        r = float(np.sqrt(n / max(d, 1e-300)))
        if r > worst:
            worst, name = r, k
    return worst, name, float(np.sqrt(num / max(den, 1e-300)))


def run_trainer(model, cfg, state, step, data, put, shardings, mesh,
                input_cfg, *, global_batch, evaluate):
    from repro.launch.train import build_eval_setup
    from repro.training import Trainer, TrainerConfig

    eval_step = val_data = finalize = None
    if evaluate:
        eval_step, val_data, finalize = build_eval_setup(
            model, cfg, global_batch=global_batch,
            seq_len=128, dp_mode="shardmap", mesh=mesh, seed=0,
            input_cfg=input_cfg)
    tcfg = TrainerConfig(epochs=1, steps_per_epoch=STEPS,
                         eval_every_epochs=1 if evaluate else 0,
                         val_batches=1, checkpoint_every=0, log_every=1,
                         data_workers=1)
    return Trainer(step, state, data, tcfg, eval_step=eval_step,
                   val_data=val_data, finalize_state=finalize,
                   put_batch=put, state_shardings=shardings).run()


def eval_replay(model, cfg, mesh, icfg, global_batch, params, probed,
                batch, train_loss):
    """The paper's validation BN (statistics recorded by the last training
    step, all-reduced over workers, no moving averages): the eval step
    given the statistics the probe step recorded, its batch and the
    parameters it started from must reproduce its training loss."""
    from repro.launch.train import build_eval_setup

    eval_step, _, finalize = build_eval_setup(
        model, cfg, global_batch=global_batch, seq_len=128,
        dp_mode="shardmap", mesh=mesh, seed=0, input_cfg=icfg)
    stats = finalize(probed["model_state"])
    loss = float(eval_step(params, stats, batch)["loss"])
    print(f"(a) eval replay loss={loss:.6f} train loss={train_loss:.6f}",
          flush=True)
    check(abs(loss - train_loss) <= EVAL_REPLAY_RTOL * abs(train_loss),
          f"(a) eval step with the recorded BN statistics gives {loss}, "
          f"the training step gave {train_loss} (rtol {EVAL_REPLAY_RTOL})")


def phase_main(cfg, global_batch):
    """(a): the paper's path; returns its per-step losses and the
    parameters before and after its first step."""
    model, state, step, data, put, sh, mesh, icfg = build(
        cfg, global_batch=global_batch, fused=False)
    before = host_leaves(state)
    _, compile_s = compile_step(step, state, data, put)
    print(f"(a) compile_s={compile_s:.2f}", flush=True)
    batch = put(data.batch_at(0))
    params0 = jax.tree.map(lambda x: x.copy(), state["params"])
    with jax.default_matmul_precision(CHECK_PRECISION):
        p0, p1, probed, loss0 = probe_step(step, state, batch)
        eval_replay(model, cfg, mesh, icfg, global_batch, params0, probed,
                    batch, loss0)
    del probed, params0
    res = run_trainer(model, cfg, state, step, data, put, sh, mesh, icfg,
                      global_batch=global_batch, evaluate=True)
    losses = [h["loss"] for h in res.history]
    print("(a) step_s=" + ",".join(f"{h['time']:.4f}" for h in res.history)
          + " losses=" + ",".join(f"{x:.6f}" for x in losses), flush=True)
    check(len(losses) == STEPS, f"(a) expected {STEPS} losses: {losses}")
    check(all(np.isfinite(losses)), f"(a) non-finite loss: {losses}")
    after = host_leaves(res.state)
    for k in before:
        check(np.isfinite(after[k]).all(), f"(a) non-finite param {k}")
        check(not np.array_equal(before[k], after[k]),
              f"(a) param {k} did not move")
    check(len(res.epoch_history) == 1, "(a) no validation pass ran")
    val = res.epoch_history[0]
    print(f"(a) val loss={val['loss']:.6f} top1={val['top1']:.4f}",
          flush=True)
    check(np.isfinite(val["loss"]) and 0.0 <= val["top1"] <= 1.0,
          f"(a) bad validation record {val}")
    return losses, p0, p1


def phase_fused(cfg, global_batch, ref_losses, ref_p0, ref_p1):
    """(b): the same steps with the Pallas kernels on the path."""
    model, state, step, data, put, sh, mesh, icfg = build(
        cfg, global_batch=global_batch, fused=True)
    text, compile_s = compile_step(step, state, data, put)
    print(f"(b) compile_s={compile_s:.2f} tpu_custom_calls="
          f"{text.count('tpu_custom_call')}", flush=True)
    check("tpu_custom_call" in text,
          "(b) compiled step holds no tpu_custom_call: kernels interpreted")
    with jax.default_matmul_precision(CHECK_PRECISION):
        p0, p1, probed, _ = probe_step(step, state, put(data.batch_at(0)))
    del probed
    check(all(np.array_equal(p0[k], ref_p0[k]) for k in ref_p0),
          "(b) initial parameters differ from (a)'s")
    worst, name, total = update_gap(p0, ref_p1, p1)
    print(f"(b) first-step update vs (a): worst leaf rel {worst:.6f} "
          f"({name}) all leaves rel {total:.6f}", flush=True)
    check(total <= FUSED_UPDATE_RTOL and worst <= FUSED_UPDATE_LEAF_RTOL,
          f"(b) first-step update differs from (a)'s by {total} over all "
          f"leaves (limit {FUSED_UPDATE_RTOL}) and {worst} at {name} "
          f"(limit {FUSED_UPDATE_LEAF_RTOL}), relative L2")
    res = run_trainer(model, cfg, state, step, data, put, sh, mesh, icfg,
                      global_batch=global_batch, evaluate=False)
    losses = [h["loss"] for h in res.history]
    print("(b) step_s=" + ",".join(f"{h['time']:.4f}" for h in res.history)
          + " losses=" + ",".join(f"{x:.6f}" for x in losses), flush=True)
    check(len(losses) == STEPS and all(np.isfinite(losses)),
          f"(b) bad losses {losses}")
    for i, (a, b) in enumerate(zip(ref_losses, losses)):
        check(abs(a - b) <= FUSED_LOSS_RTOL * abs(a),
              f"(b) step {i}: fused loss {b} vs {a} beyond rtol "
              f"{FUSED_LOSS_RTOL}")


def spans(x, n):
    return len(x.sharding.device_set) == n


def run_mode(cfg, mesh, mode, n):
    """One four-chip mode: the first step's update (from a copy of the
    initial state), then three steps; returns (update, losses, state)."""
    _, state, step, data, put, _, _, _ = build(
        cfg, global_batch=GLOBAL_BATCH_PER_CHIP * n, fused=False,
        dp_mode=mode, sync_bn=True, mesh=mesh)
    p0, p1, probed, _ = probe_step(step, state, put(data.batch_at(0)))
    del probed
    ls = []
    for s in range(STEPS):
        batch = put(data.batch_at(s))
        check(spans(batch["images"], n) and
              batch["images"].addressable_shards[0].data.shape[0]
              == GLOBAL_BATCH_PER_CHIP,
              f"{mode}: batch not split over {n} devices")
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        ls.append(float(metrics["loss"]))
        print(f"{mode} step {s} s={time.perf_counter() - t0:.4f} "
              f"loss={ls[-1]:.6f}", flush=True)
    return (p0, p1), ls, state


def phase_four_chips(cfg, precision=CHECK_PRECISION):
    """shard_map f16-bucketed DP with sync BN vs GSPMD on a 4x1 mesh."""
    from repro.launch.mesh import make_mesh

    n = jax.device_count()
    check(n == 4, f"--four-chips needs 4 devices, found {n}")
    mesh = make_mesh((n, 1), ("data", "model"))
    print(f"four-chip matmul precision: {precision}", flush=True)
    losses, first = {}, {}
    for mode in ("gspmd", "shardmap"):
        with jax.default_matmul_precision(precision):
            first[mode], losses[mode], state = run_mode(cfg, mesh, mode, n)
        if mode == "shardmap":
            for leaf in jax.tree.leaves(state["model_state"]):
                check(leaf.shape[0] == n and spans(leaf, n),
                      "per-worker BN state does not span the devices")
        del state
    worst, name, total = update_gap(first["gspmd"][0], first["gspmd"][1],
                                    first["shardmap"][1])
    print(f"first-step update shardmap vs gspmd: worst leaf rel "
          f"{worst:.6f} ({name}) all leaves rel {total:.6f}", flush=True)
    diff = max(abs(a - b) for a, b in zip(losses["gspmd"],
                                          losses["shardmap"]))
    print(f"four-chip max |loss diff| = {diff:.6f}", flush=True)
    check(all(np.isfinite(losses["shardmap"] + losses["gspmd"])),
          f"non-finite loss {losses}")
    check(diff < FOUR_CHIP_LOSS_ATOL, f"loss diff {diff} >= "
          f"{FOUR_CHIP_LOSS_ATOL}: {losses}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1 data-parallel mesh phase")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.train import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {device_record()}", flush=True)
    cfg = get_config("resnet50")
    try:
        if args.four_chips:
            phase_four_chips(cfg)
        else:
            batch = GLOBAL_BATCH_PER_CHIP * jax.device_count()
            phase_fused(cfg, batch, *phase_main(cfg, batch))
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_record()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
