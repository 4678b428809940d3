"""End-to-end training driver.

Runs real training (synthetic data) on whatever devices exist — reduced
configs on CPU for the examples/tests, full configs on a TPU pod with the
same code path. Demonstrates the paper's full recipe: hybrid RMSprop
warm-up, slow-start LR, compressed gradient sync, BN handling, async
checkpointing and resume.

    PYTHONPATH=src python -m repro.launch.train --arch resnet50 --reduced \
        --steps 100 --global-batch 64 --optimizer rmsprop_warmup
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    InputConfig,
    OptimizerConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
    get_config,
    reduced_config,
)
from repro.data import AugmentedSource, StepStampSource, make_data
from repro.distributed.sharding import make_rules, tree_shardings
from repro.launch.mesh import make_mesh
from repro.models import build_model, init_model_state
from repro.models.common import unbox
from repro.optim import make_optimizer
from repro.training import (
    LoopConfig,
    Trainer,
    TrainerConfig,
    run_training,
)
from repro.training.step import (
    finalize_worker_bn_stats,
    jit_train_step,
    make_dp_shardmap_train_step,
    make_eval_step,
    make_train_step,
)


# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed directory inside the checkout (listed in .gitignore).
# The path is part of what a later run must match to hit the cache, so it
# never depends on a temporary name, a process id or the time.
COMPILE_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def build_train_setup(cfg, *, global_batch: int, seq_len: int,
                      opt_cfg: OptimizerConfig, steps_per_epoch: int,
                      mesh=None, dp_mode: str = "gspmd",
                      compute_dtype=jnp.float32, attention_impl="naive",
                      seed: int = 0, use_fused_kernel: bool = False,
                      sync_bn: bool = False, compression: str = "bf16",
                      bucket_bytes: int = 64 * 1024 * 1024,
                      error_feedback: bool = False,
                      overlap_comm: bool = False,
                      zero_dp: bool = False,
                      fused_bn: bool = False,
                      label_smoothing: float = 0.0,
                      data_noise: Optional[float] = None,
                      sentinel: bool = False,
                      dp_axes=("data",),
                      hier_split: Optional[int] = None,
                      input_cfg: Optional[InputConfig] = None):
    """Returns (model, state, train_step, data, put_batch,
    state_shardings).

    ``data_noise``: difficulty of the synthetic image task (None = the
    pipeline default); the recipe/ablation proxies raise it so training
    is still in progress at the schedule-transition epochs.

    ``input_cfg``: production input pipeline (DESIGN.md §15). Selects
    this host's shard of the global batch (``num_hosts``/``host_id``),
    turns on per-sample augmentation, and with ``fused=True`` moves
    augment+normalize+cast onto the device as one Pallas pass inside
    the shard_map local step (shard_map DP + conv only; the host
    AugmentedSource path covers every other mode).

    ``sentinel``: wrap the train step with the divergence sentinel
    (resilience/sentinel.py, DESIGN.md §13) — the jitted step becomes
    the 3-arg ``(state, batch, controls)`` form that the Trainer's
    recovery state machine drives. On the GSPMD path this forces
    ``log_grad_norm`` on (the one extra tree reduction documented
    there); the shard_map modes already get the norm free from the
    packed gradient stream.
    """
    if fused_bn:
        if cfg.family != "conv":
            raise ValueError(
                "--fused-bn fuses the ResNet BN sites (Pallas kernels, "
                f"DESIGN.md §10); arch family {cfg.family!r} has no BN")
        cfg = dataclasses.replace(cfg, fused_bn=True)
    shape = ShapeConfig("train", seq_len, global_batch, "train")
    dp_axes = tuple(dp_axes)
    if hier_split is not None and dp_mode != "shardmap":
        raise ValueError(
            "hier_split reschedules explicit per-bucket collectives, "
            "which only exist in the shard_map DP mode "
            "(dp_mode='shardmap', DESIGN.md §14)")
    # pure DP spans every mesh axis under a hierarchical schedule (the
    # paper's ResNet regime); otherwise "model" stays the TP axis
    tp_axis = ("model" if mesh is not None and "model" not in dp_axes
               else None)
    parallel = ParallelConfig(
        dp_axes=dp_axes, tp_axis=tp_axis,
        compression=compression, bucket_bytes=bucket_bytes,
        error_feedback=error_feedback, overlap_comm=overlap_comm,
        zero_dp=zero_dp, zero_1=False, hier_split=hier_split)
    if overlap_comm and dp_mode != "shardmap":
        raise ValueError(
            "overlap_comm launches explicit per-bucket collectives inside "
            "the backward pass, which only exists in the shard_map DP "
            "mode (dp_mode='shardmap', DESIGN.md §8)")
    if zero_dp and dp_mode != "shardmap":
        raise ValueError(
            "--zero reduce-scatters explicit per-bucket collectives, "
            "which only exist in the shard_map DP mode "
            "(dp_mode='shardmap'; GSPMD has zero_1 sharding constraints "
            "instead, DESIGN.md §9)")
    if cfg.family == "conv" and dp_mode == "shardmap" and sync_bn:
        from repro.models.resnet import ResNet50
        model = ResNet50(cfg, compute_dtype=compute_dtype,
                         cross_replica_bn=parallel.dp_axes)
    else:
        model = build_model(cfg, compute_dtype=compute_dtype,
                            attention_impl=attention_impl,
                            remat=cfg.n_layers > 8)
    if input_cfg is not None and input_cfg.fused:
        if cfg.family != "conv":
            raise ValueError(
                "fused input (Pallas augment+normalize+cast) transforms "
                f"image batches; arch family {cfg.family!r} has none "
                "(DESIGN.md §15)")
        if dp_mode != "shardmap" or mesh is None:
            raise ValueError(
                "fused input slices per-worker augmentation parameters "
                "with lax.axis_index, which only exists inside the "
                "shard_map DP step (dp_mode='shardmap', DESIGN.md §15); "
                "use the host AugmentedSource path (fused=False) "
                "elsewhere")
    train_cfg = TrainConfig(optimizer=opt_cfg, parallel=parallel,
                            label_smoothing=label_smoothing,
                            input=input_cfg,
                            # sentinel needs grad_norm as its whole-
                            # gradient health flag; GSPMD is the only
                            # mode where it is not already free
                            log_grad_norm=sentinel and dp_mode != "shardmap")
    from repro.core.compression import parse_compression
    _, bucketed = parse_compression(compression)
    # packed-stream optimizer layout: always under --zero; also for LARS
    # on the explicit bucketed DP paths (stream-LARS, DESIGN.md §11)
    use_stream = zero_dp or (opt_cfg.kind == "lars"
                             and dp_mode == "shardmap"
                             and mesh is not None and bucketed)
    if use_stream:
        from repro.optim.stream import make_stream_optimizer
        optimizer = make_stream_optimizer(opt_cfg, steps_per_epoch,
                                          global_batch,
                                          use_fused=use_fused_kernel)
    else:
        optimizer = make_optimizer(opt_cfg, steps_per_epoch, global_batch,
                                   use_fused=use_fused_kernel)

    key = jax.random.PRNGKey(seed)
    params, axes = model.init_params(key)
    mstate = init_model_state(model)
    ef_residual = None
    if dp_mode == "shardmap" and mesh is not None:
        from repro.training.step import replicate_model_state
        n_workers = 1
        for a in parallel.dp_axes:
            n_workers *= mesh.shape[a]
        mstate = replicate_model_state(mstate, n_workers)
        if error_feedback:
            from repro.core.compression import init_error_feedback
            # per-worker residuals, leading worker dim like the BN stats
            ef_residual = replicate_model_state(
                init_error_feedback(params), n_workers)
    elif error_feedback:
        raise ValueError(
            "error_feedback is only implemented for the explicit "
            "shard_map DP mode on a mesh (dp_mode='shardmap'); the "
            "GSPMD path has no worker-local gradients to correct")
    if zero_dp and mesh is None:
        raise ValueError(
            "--zero shards the optimizer update over a DP mesh; "
            "pass a mesh (dp_mode='shardmap' builds a pure-DP one "
            "by default in the CLI)")
    if hasattr(optimizer, "update_shard"):
        # flat stream state (optim/stream.py): shard layout under --zero
        # (DESIGN.md §9), full replicated stream for stream-LARS — the
        # padded length is the same either way
        from repro.optim.stream import zero_padded_total
        opt_state = optimizer.init(zero_padded_total(
            params, compression, bucket_bytes, n_workers))
    else:
        opt_state = optimizer.init(params)
    state = {"params": params, "opt": opt_state, "model_state": mstate}
    if ef_residual is not None:
        state["ef_residual"] = ef_residual

    def _finalize_step(step, **jit_kwargs):
        # sentinel wraps OUTSIDE the sync-mode builder and INSIDE jit:
        # the skip gate must live in the compiled program because the
        # jitted step donates its input state (DESIGN.md §13)
        if sentinel:
            from repro.resilience.sentinel import wrap_step_with_sentinel
            step = wrap_step_with_sentinel(step)
        return jit_train_step(step, **jit_kwargs)

    rules = None
    state_shardings = None
    put_batch = None
    if mesh is not None:
        rules = make_rules(cfg, mesh, parallel)
        batch_sharding = NamedSharding(mesh, P(parallel.dp_axes))

        def put_batch(batch):
            return {k: jax.device_put(v, batch_sharding if
                                      np.ndim(v) else None)
                    for k, v in batch.items()}

        if dp_mode == "shardmap":
            from repro.training.step import make_batch_input_transform
            input_transform = make_batch_input_transform(
                input_cfg, seed, model, mesh, parallel.dp_axes)
            if overlap_comm:
                from repro.training.step import make_dp_overlap_train_step
                step = make_dp_overlap_train_step(
                    model, optimizer, train_cfg, mesh, parallel.dp_axes,
                    input_transform=input_transform)
            else:
                step = make_dp_shardmap_train_step(
                    model, optimizer, train_cfg, mesh, parallel.dp_axes,
                    input_transform=input_transform)
            # the state goes in and comes out with one placement
            # (replicated params/opt, per-worker BN/EF rows, ZeRO-sharded
            # stream state), so every step reuses the first step's
            # executable instead of compiling again for new shardings
            rep = NamedSharding(mesh, P())
            per_worker = NamedSharding(mesh, P(parallel.dp_axes))
            placed = {
                "params": jax.tree.map(lambda _: rep, state["params"]),
                "opt": {k: jax.tree.map(
                    lambda _, sh=(per_worker if zero_dp and k != "step"
                                  else rep): sh, v)
                    for k, v in state["opt"].items()},
                "model_state": jax.tree.map(lambda _: per_worker,
                                            state["model_state"]),
            }
            if "ef_residual" in state:
                placed["ef_residual"] = jax.tree.map(
                    lambda _: per_worker, state["ef_residual"])
            state = jax.device_put(state, placed)
            train_step = _finalize_step(step, out_shardings=(placed, rep))
        else:
            p_shard = tree_shardings(axes, mesh, rules)
            state_shardings = {
                "params": p_shard,
                "opt": {"step": NamedSharding(mesh, P()),
                        **{f: p_shard for f in optimizer.state_fields}},
                "model_state": jax.tree.map(
                    lambda _: NamedSharding(mesh, P()), mstate),
            }
            state = jax.device_put(state, state_shardings)
            step = make_train_step(model, optimizer, train_cfg, mesh, rules)
            train_step = _finalize_step(step)
    else:
        step = make_train_step(model, optimizer, train_cfg)
        train_step = _finalize_step(step)

    data = _wrap_train_source(
        make_data(cfg, shape, seed=seed, noise=data_noise,
                  num_hosts=input_cfg.num_hosts if input_cfg else 1,
                  host_id=input_cfg.host_id if input_cfg else 0),
        input_cfg, seed=seed, global_batch=global_batch,
        is_conv=cfg.family == "conv")
    return model, state, train_step, data, put_batch, state_shardings


def _wrap_train_source(data, input_cfg, *, seed, global_batch, is_conv):
    """Apply the input pipeline's host-side wrappers (DESIGN.md §15):
    fused -> stamp each batch with its step (the kernel's seed material);
    host augmentation -> numpy mirror of the fused transform."""
    if input_cfg is None or not is_conv:
        return data
    if input_cfg.fused:
        return StepStampSource(data)
    if input_cfg.augment:
        return AugmentedSource(data, seed=seed, mean=input_cfg.mean,
                               std=input_cfg.std,
                               max_shift=input_cfg.max_shift, train=True,
                               global_batch=global_batch)
    return AugmentedSource(data, seed=seed, mean=input_cfg.mean,
                           std=input_cfg.std, train=False,
                           global_batch=global_batch)


def build_eval_setup(model, cfg, *, global_batch: int, seq_len: int,
                     dp_mode: str = "gspmd", mesh=None, seed: int = 0,
                     data_noise: Optional[float] = None,
                     input_cfg: Optional[InputConfig] = None):
    """Validation pieces for ``Trainer``: (eval_step, val_data, finalize).

    The eval step is one plain-jit program for both execution modes
    (DESIGN.md §7): under GSPMD the model_state statistics are already
    global, under shard_map DP ``finalize_worker_bn_stats`` performs the
    paper's pre-validation all-reduce first, and either way the step
    sees worker-free statistics. ``val_data`` is the deterministic
    held-out split (seed-space disjoint from train by construction).

    With ``input_cfg``, validation applies the eval input variant
    (normalize+cast, no augmentation — DESIGN.md §15): on device via the
    fused Pallas kernel when ``fused=True``, else on the host feed.
    """
    shape = ShapeConfig("val", seq_len, global_batch, "train")
    val_data = make_data(cfg, shape, seed=seed, split="val",
                         noise=data_noise)
    fused_input = (input_cfg is not None and input_cfg.fused
                   and cfg.family == "conv")
    if input_cfg is not None and cfg.family == "conv" and not fused_input:
        val_data = AugmentedSource(val_data, seed=seed,
                                   mean=input_cfg.mean, std=input_cfg.std,
                                   train=False, global_batch=global_batch)
    rules = None
    eval_mesh = None
    finalize = None
    if mesh is not None:
        if dp_mode == "shardmap":
            # params/stats replicated after finalize: plain jit evals
            finalize = jax.jit(finalize_worker_bn_stats)
        else:
            # GSPMD: keep the activation-sharding hints so validation
            # stays partitioned like training (TP models especially)
            parallel = ParallelConfig(dp_axes=("data",), tp_axis="model",
                                      zero_1=False)
            rules = make_rules(cfg, mesh, parallel)
            eval_mesh = mesh
    base_eval = make_eval_step(model, mesh=eval_mesh, rules=rules)
    if fused_input:
        from repro.kernels import ops
        mean = jnp.asarray(input_cfg.mean, jnp.float32)
        inv_std = 1.0 / jnp.asarray(input_cfg.std, jnp.float32)
        out_dtype = getattr(model, "compute_dtype", jnp.bfloat16)

        def eval_with_input(params, model_state, batch):
            batch = dict(batch)
            batch["images"] = ops.fused_input_eval(
                batch["images"], mean, inv_std, out_dtype=out_dtype)
            return base_eval(params, model_state, batch)

        eval_step = jax.jit(eval_with_input)
    else:
        eval_step = jax.jit(base_eval)
    return eval_step, val_data, finalize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50,
                    help="legacy step-driven run (no validation); "
                         "ignored when --epochs is given")
    ap.add_argument("--epochs", type=int, default=None,
                    help="epoch-driven run: train "
                         "epochs*steps-per-epoch steps with held-out "
                         "validation at epoch boundaries (DESIGN.md §7)")
    ap.add_argument("--eval-every-epochs", type=int, default=1)
    ap.add_argument("--val-batches", type=int, default=4)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--optimizer", default="rmsprop_warmup",
                    choices=["rmsprop_warmup", "momentum_sgd", "lars"])
    ap.add_argument("--schedule", default="slow_start",
                    choices=["slow_start", "goyal", "poly", "constant"])
    ap.add_argument("--label-smoothing", type=float, default=0.0,
                    help="label smoothing epsilon (large-batch recipes "
                         "pair it with --schedule poly)")
    ap.add_argument("--steps-per-epoch", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="DxM virtual mesh, e.g. 4x2 (needs XLA_FLAGS)")
    ap.add_argument("--dp-mode", default="gspmd",
                    choices=["gspmd", "shardmap"])
    ap.add_argument("--compression", default="bf16",
                    help="gradient sync wire format: none|bf16|f16|"
                         "bf16+bucketed|f16+bucketed (DESIGN.md §2/§6)")
    ap.add_argument("--bucket-mib", type=int, default=64,
                    help="bucket size in MiB for the +bucketed modes")
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--overlap-comm", action="store_true",
                    help="launch each gradient bucket's all-reduce as "
                         "soon as the backward pass produces its leaves "
                         "(shard_map DP only, DESIGN.md §8)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO sync: reduce-scatter each packed bucket, "
                         "shard the optimizer update over the DP ranks, "
                         "all-gather the updated params (shard_map DP + "
                         "bucketed compression, DESIGN.md §9; composes "
                         "with --overlap-comm)")
    ap.add_argument("--comm-plan", default="flat",
                    help="collective schedule: flat | hier[:k] | auto | "
                         "<path>. 'hier:k' splits dp_axes at k into an "
                         "intra-axis reduce-scatter -> inter-axis "
                         "all-reduce -> intra-axis all-gather pipeline; "
                         "'auto' loads the autotuner's persisted plan "
                         "for this mesh (results/comm_plan_*.json, "
                         "benchmarks/comm_bench.py) and applies its "
                         "full wire config (DESIGN.md §14)")
    ap.add_argument("--use-fused-kernel", action="store_true")
    ap.add_argument("--fused-bn", action="store_true",
                    help="fused Pallas BN at every ResNet BN site: "
                         "one-pass stats + normalize/ReLU/residual "
                         "epilogue + fused custom-VJP backward "
                         "(kernels/fused_bn.py, DESIGN.md §10)")
    ap.add_argument("--data-workers", type=int, default=1,
                    help="host input-producer threads feeding the "
                         "step-ordered prefetch buffer (data/pipeline.py,"
                         " DESIGN.md §15)")
    ap.add_argument("--fused-input", action="store_true",
                    help="one-pass Pallas augment+normalize+cast on "
                         "device instead of the host feed "
                         "(kernels/fused_input.py; shard_map DP + conv "
                         "archs, DESIGN.md §15)")
    ap.add_argument("--host-shard", default=None, metavar="H/N",
                    help="per-host input sharding: this host generates "
                         "only shard H of N of every global batch, e.g. "
                         "0/4 (deterministic slice of the (seed, split, "
                         "step) contract, DESIGN.md §15)")
    ap.add_argument("--sentinel", action="store_true",
                    help="divergence sentinel + recovery state machine: "
                         "skip non-finite/spiking steps in-jit, roll "
                         "back to the last good checkpoint after "
                         "repeated bad steps (DESIGN.md §13; needs "
                         "--epochs and, for rollback, --ckpt-dir)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'nan_grad@6,ckpt_truncate@10,seed=3' "
                         "(resilience/chaos.py grammar; implies "
                         "--sentinel)")
    ap.add_argument("--event-log", default=None,
                    help="JSONL path for resilience events")
    ap.add_argument("--log-json", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.chaos:
        args.sentinel = True
    if args.sentinel and args.epochs is None:
        ap.error("--sentinel/--chaos need the epoch-driven loop: "
                 "pass --epochs")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    elif args.dp_mode == "shardmap":
        # explicit DP needs a mesh; default to pure-DP over all devices
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))

    opt_cfg = OptimizerConfig(kind=args.optimizer, schedule=args.schedule)
    input_cfg = None
    if args.fused_input or args.host_shard:
        num_hosts, host_id = 1, 0
        if args.host_shard:
            try:
                host_id, num_hosts = (int(x)
                                      for x in args.host_shard.split("/"))
            except ValueError:
                ap.error("--host-shard expects H/N, e.g. 0/4")
        input_cfg = InputConfig(fused=args.fused_input,
                                num_workers=args.data_workers,
                                num_hosts=num_hosts, host_id=host_id)
    # --comm-plan: resolve the collective schedule (DESIGN.md §14).
    # Grammar forms (flat / hier[:k]) only reschedule; a plan loaded
    # from disk (auto / path) carries the autotuner's full wire config.
    dp_axes = ("data",)
    hier_split = None
    compression = args.compression
    bucket_bytes = args.bucket_mib * 1024 * 1024
    overlap_comm, zero_dp = args.overlap_comm, args.zero
    if args.comm_plan != "flat":
        if mesh is None:
            ap.error("--comm-plan needs a mesh (--mesh DxM, or "
                     "--dp-mode shardmap's default pure-DP mesh)")
        if args.dp_mode != "shardmap":
            ap.error("--comm-plan reschedules explicit per-bucket "
                     "collectives: pass --dp-mode shardmap")
        from repro.distributed.comm_plan import resolve_comm_plan
        mesh_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
        plan = resolve_comm_plan(args.comm_plan, arch=args.arch,
                                 mesh_shape=mesh_shape,
                                 dp_axes=tuple(mesh.axis_names))
        if plan is not None:
            hier_split = plan.hier_split
            if hier_split is not None:
                dp_axes = plan.dp_axes  # pure DP over the whole mesh
            if plan.bucket_bytes:  # loaded plan: apply its wire config
                compression = plan.compression
                bucket_bytes = plan.bucket_bytes
                overlap_comm = plan.sync_mode in ("overlap",
                                                  "zero_overlap")
                zero_dp = plan.sync_mode in ("zero", "zero_overlap")
            print(f"comm plan: {plan.describe()}")

    model, state, train_step, data, put_batch, shardings = \
        build_train_setup(
            cfg, global_batch=args.global_batch, seq_len=args.seq_len,
            opt_cfg=opt_cfg, steps_per_epoch=args.steps_per_epoch,
            mesh=mesh, dp_mode=args.dp_mode, seed=args.seed,
            use_fused_kernel=args.use_fused_kernel,
            compression=compression,
            bucket_bytes=bucket_bytes,
            error_feedback=args.error_feedback,
            overlap_comm=overlap_comm, zero_dp=zero_dp,
            fused_bn=args.fused_bn,
            label_smoothing=args.label_smoothing,
            sentinel=args.sentinel,
            dp_axes=dp_axes, hier_split=hier_split,
            input_cfg=input_cfg)

    metadata = {"arch": args.arch, "optimizer": args.optimizer,
                "opt_layout": "zero_stream" if zero_dp else "tree"}
    t0 = time.time()
    if args.epochs is not None:
        # ---- epoch-driven train/eval (the paper's actual protocol) ----
        eval_step, val_data, finalize = build_eval_setup(
            model, cfg, global_batch=args.global_batch,
            seq_len=args.seq_len, dp_mode=args.dp_mode, mesh=mesh,
            seed=args.seed, input_cfg=input_cfg)
        total_steps = args.epochs * args.steps_per_epoch
        tcfg = TrainerConfig(
            epochs=args.epochs, steps_per_epoch=args.steps_per_epoch,
            eval_every_epochs=args.eval_every_epochs,
            val_batches=args.val_batches,
            checkpoint_every=args.ckpt_every if args.ckpt_dir else 0,
            checkpoint_dir=args.ckpt_dir,
            data_workers=args.data_workers,
            log_every=max(1, total_steps // 20))
        resilience = chaos = None
        if args.sentinel:
            from repro.resilience import ResilienceConfig, parse_chaos
            resilience = ResilienceConfig(event_log=args.event_log)
            if args.chaos:
                chaos = parse_chaos(args.chaos, seed=args.seed)
        result = Trainer(train_step, state, data, tcfg,
                         eval_step=eval_step, val_data=val_data,
                         finalize_state=finalize, put_batch=put_batch,
                         metadata=metadata,
                         state_shardings=shardings,
                         resilience=resilience, chaos=chaos).run()
        wall = time.time() - t0
        print(f"trained {args.epochs} epochs x {args.steps_per_epoch} "
              f"steps in {wall:.1f}s (dp_mode={args.dp_mode}, "
              f"resumed_from={result.resumed_from})")
        if result.events:
            kinds: Dict[str, int] = {}
            for r in result.events:
                kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
            print("resilience events: " + ", ".join(
                f"{k}={v}" for k, v in sorted(kinds.items())))
        for r in result.epoch_history:
            top1 = r.get("top1")  # LM archs eval loss only
            t = f"val top1 {top1:.4f} " if top1 is not None else ""
            print(f"  epoch {r['epoch']:3d} {t}"
                  f"val loss {r['loss']:.4f}")
        if result.best:
            print(f"best: top1 {result.best['top1']:.4f} at epoch "
                  f"{result.best['epoch']}")
        _print_stragglers(result.straggler_events)
        if args.log_json:
            with open(args.log_json, "w") as f:
                json.dump({"history": result.history,
                           "epoch_history": result.epoch_history,
                           "best": result.best, "wall": wall,
                           "resumed_from": result.resumed_from,
                           "events": result.events,
                           "straggler_events": result.straggler_events,
                           "telemetry": result.telemetry}, f)
        return

    # ---- legacy step-driven run (no validation) ----
    loop_cfg = LoopConfig(total_steps=args.steps,
                          checkpoint_every=args.ckpt_every,
                          checkpoint_dir=args.ckpt_dir,
                          data_workers=args.data_workers,
                          log_every=max(1, args.steps // 20))
    result = run_training(train_step, state, data, loop_cfg,
                          put_batch=put_batch, metadata=metadata,
                          state_shardings=shardings)
    wall = time.time() - t0
    print(f"trained {args.steps} steps in {wall:.1f}s "
          f"(resumed_from={result.resumed_from})")
    for h in result.history:
        print(f"  step {h['step']:5d} loss {h['loss']:.4f} "
              f"({h['time']*1e3:.0f} ms)")
    _print_stragglers(result.straggler_events)
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump({"history": result.history, "wall": wall,
                       "resumed_from": result.resumed_from,
                       "straggler_events": result.straggler_events,
                       "telemetry": result.telemetry}, f)


def _print_stragglers(events) -> None:
    """Each slow step with what its time went to (``Trainer.run``'s
    straggler records)."""
    if events:
        print(f"straggler events: {len(events)}")
    for e in events:
        parts = ", ".join(f"{k} {v:.1f}" for k, v in e["spans_ms"].items())
        print(f"  step {e['step']}: {e['time'] * 1e3:.1f} ms against a "
              f"median {e['median'] * 1e3:.1f} ms ({parts} ms; "
              f"{e['traces']} traces, {e['compiles']} compiles)")


if __name__ == "__main__":
    main()
