"""Production meshes. Functions, never module-level constants, so
importing this module never touches jax device state."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig

# v5e hardware constants for the dry run's roofline model (per chip);
# nothing on the training path reads them
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None):
    """The one mesh constructor: ``jax.make_mesh`` with every axis
    ``AxisType.Auto``. ``jax.make_mesh`` defaults to Explicit axes,
    under which ``with_sharding_constraint`` refuses the mesh and the
    GSPMD partitioner raises ``ShardingTypeError`` on gathers and
    dynamic slices; every mesh in this repo leaves placement to the
    partitioner (GSPMD) or to shard_map, so all axes are Auto."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_small_mesh(data: int = 4, model: int = 2):
    """Virtual-device mesh for tests (XLA_FLAGS host device count)."""
    return make_mesh((data, model), ("data", "model"))


def preferred_mesh(cfg: ModelConfig, *, multi_pod: bool = False):
    """Per-arch mesh-shape selection over the same chips.

    §Perf llama4 iteration 4: 40 heads % 16 != 0 makes attention
    replicate on a (16,16) mesh (11x slower); (data=32, model=8) shards
    heads/experts/ffn/vocab evenly. Archs that divide 16 keep the
    standard production mesh.
    """
    if cfg.n_heads and cfg.n_heads % 16 != 0 and cfg.n_heads % 8 == 0 \
            and cfg.param_count() > 3e9:
        shape = (2, 32, 8) if multi_pod else (32, 8)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return make_mesh(shape, axes)
    return make_production_mesh(multi_pod=multi_pod)


def cell_parallel(cfg: ModelConfig, shape: ShapeConfig) -> ParallelConfig:
    """Default parallelism policy for one (arch, shape) cell.

    conv (ResNet-50)   : pure DP over every mesh axis — the paper's regime,
                         fp16 wire compression (paper-faithful), replicated
                         optimizer (the paper's workers update redundantly).
    LM train           : DP over data(+pod), Megatron TP over model,
                         ZeRO-1 (+FSDP for >=6B params), bf16 wire.
    LM prefill/decode  : TP over model, batch over data, bf16 params, and
                         sequence sharding when the batch can't shard
                         (long-context B=1 cells).
    """
    if cfg.family == "conv":
        return ParallelConfig(
            dp_axes=("data", "model"), tp_axis=None, zero_1=False,
            fsdp_params=False, compression="f16", remat="none")
    n = cfg.param_count()
    tiny = n < 3e9  # pure-DP below Megatron-worthwhile size (paper regime)
    big = n > 6e9
    if shape.kind == "train":
        if tiny:
            return ParallelConfig(
                dp_axes=("data", "model"), tp_axis=None, zero_1=True,
                fsdp_params=False, compression="bf16", remat="block")
        return ParallelConfig(
            dp_axes=("data",), tp_axis="model", zero_1=True,
            fsdp_params=big, compression="bf16", remat="block")
    if tiny:
        return ParallelConfig(
            dp_axes=("data", "model"), tp_axis=None, zero_1=False,
            fsdp_params=False, compression=None, remat="none",
            kv_seq_sharding=True)
    # serve of very large models: bf16 params exceed TP-sharded HBM
    # (llama4 400B: 795 GB/16 = 50 GB/chip) => weight-gather FSDP serving
    serve_fsdp = n * 2 / 16 > 12e9
    return ParallelConfig(
        dp_axes=("data",), tp_axis="model", zero_1=False,
        fsdp_params=serve_fsdp, compression=None, remat="none",
        sequence_sharding=shape.global_batch == 1,
        kv_seq_sharding=True)
