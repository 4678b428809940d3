"""Pallas TPU kernel: fused RMSNorm (fp32 statistics, compute-dtype IO).

§Perf iteration 1 measured the unfused norm's fp32 upcast as ~11% of
ResNet's memory term and a similar share per transformer layer; the
fused kernel reads x once, keeps the fp32 square-sum in VMEM, and writes
one output stream. Tiling: rows x d_model blocks, d padded to the lane
width by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 256


def _kernel(x_ref, scale_ref, o_ref, *, eps):
    x = x_ref[...]
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x32 * inv).astype(x.dtype) * scale_ref[...]


def rmsnorm(x, scale, *, eps: float = 1e-5, interpret: bool,
            row_block: int = ROW_BLOCK):
    """x: (..., d); scale: (d,). Returns RMS-normalized x * scale."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    xr = x.reshape(rows, d)
    rb = min(row_block, rows)
    pad = (-rows) % rb
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    grid = (xr.shape[0] // rb,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rb, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        interpret=interpret,
    )(xr, scale.reshape(1, d).astype(x.dtype))
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
