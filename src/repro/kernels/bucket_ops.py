"""Pallas TPU kernel pair for bucketed gradient communication
(DESIGN.md §6): fused cast+copy between the fp32 accumulation stream and
the wire-dtype bucket.

Packing a gradient bucket is two logical ops — a dtype cast (fp32 ->
bf16/f16) and a copy into the contiguous bucket buffer. Left to XLA these
can materialize as separate HBM round-trips per leaf; the kernel fuses
them into one pass per VMEM tile, so each bucket element is read once and
written once at the wire width. Unpack is the mirror image (wire -> fp32).

Tiling follows fused_update.py: the flat stream is reshaped to
(rows, 128) — the last dim matches the VPU lane width — and processed in
BLOCK_ROWS x 128 tiles. Padding-awareness lives in the wrappers: an
arbitrary-length stream is zero-padded to a whole number of lanes (and
trimmed after), so odd leaf sizes never reach the kernel.

f16 moves through the kernels as its uint16 bit pattern: Mosaic on TPU
v5e has no f16 vectors (it refuses both the f16 load and the f32->f16
pack), so the f16 kernels convert with integer bit operations, IEEE
round-to-nearest-even, and the wrappers reinterpret the bits (free).

Off-TPU the kernels run in interpret mode. Pure-jnp oracles:
ref.cast_copy; tests/test_tpu_compile.py compiles them for v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 1024  # 1024*128 elems: 512 KiB fp32 + 256 KiB bf16 per tile


def _cast_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def f32_to_f16_bits(x):
    """f32 -> the f16 bit pattern (in uint32), round-to-nearest-even;
    overflow -> inf, NaN -> quiet NaN (F. Giesen's float_to_half)."""
    u32 = jnp.uint32
    u = jax.lax.bitcast_convert_type(x, u32)
    sign = u & u32(0x80000000)
    a = u ^ sign
    # |x| >= 2^16 (or inf/NaN): inf, or the canonical quiet NaN
    special = jnp.where(a > u32(0x7F800000), u32(0x7E00), u32(0x7C00))
    # |x| < 2^-14: f16 subnormal or zero. Adding 0.5 aligns the f16
    # subnormal grid (2^-24) with the f32 ulp of 0.5, so the f32 add
    # itself rounds to nearest even.
    sub = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.float32) + 0.5,
        u32) - u32(0x3F000000)
    # normal: rebias the exponent (-112 << 23) and round the 13 dropped
    # mantissa bits to nearest even; a carry lands in the exponent
    normal = (a + u32(0xC8000FFF) + ((a >> 13) & u32(1))) >> 13
    h = jnp.where(a >= u32(0x47800000), special,
                  jnp.where(a < u32(0x38800000), sub, normal))
    return h | (sign >> 16)


def f16_bits_to_f32(h):
    """The f16 bit pattern (in uint32) -> f32, exact."""
    u32 = jnp.uint32
    sign = (h & u32(0x8000)) << 16
    e = (h >> 10) & u32(0x1F)
    m = h & u32(0x3FF)
    normal = ((e + u32(112)) << 23) | (m << 13)
    special = u32(0x7F800000) | (m << 13)  # inf / NaN
    sub = jax.lax.bitcast_convert_type(
        m.astype(jnp.int32).astype(jnp.float32) * (2.0 ** -24), u32)
    u = jnp.where(e == u32(0x1F), special,
                  jnp.where(e == u32(0), sub, normal))
    return jax.lax.bitcast_convert_type(u | sign, jnp.float32)


def _pack_f16_kernel(x_ref, o_ref):
    o_ref[...] = f32_to_f16_bits(
        x_ref[...].astype(jnp.float32)).astype(jnp.uint16)


def _unpack_f16_kernel(x_ref, o_ref):
    o_ref[...] = f16_bits_to_f32(
        x_ref[...].astype(jnp.uint32)).astype(o_ref.dtype)


def cast_copy_2d(x, out_dtype, *, interpret, name, block_rows=BLOCK_ROWS):
    """x: (rows, 128) with rows a multiple of block_rows; returns x cast
    to out_dtype, one fused pass. ``name`` names the kernel's operation
    in the compiled program and the device trace."""
    rows = x.shape[0]
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    f16 = jnp.dtype(jnp.float16)
    out_dtype = jnp.dtype(out_dtype)
    kernel, src, dst = _cast_kernel, x, out_dtype
    if out_dtype == f16:
        kernel, dst = _pack_f16_kernel, jnp.dtype(jnp.uint16)
    elif x.dtype == f16:
        kernel = _unpack_f16_kernel
        src = jax.lax.bitcast_convert_type(x, jnp.uint16)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, dst),
        interpret=interpret,
        name=name,
    )(src)
    return (jax.lax.bitcast_convert_type(out, f16) if dst != out_dtype
            else out)


def _to_lanes(flat, block_rows=BLOCK_ROWS):
    """Pad a 1-D stream to a whole (rows, LANES) tile grid whose row
    count divides into block_rows tiles — padding a few extra zero rows
    is far cheaper than the degenerate (1, LANES) grid a prime row
    count would otherwise force."""
    n = flat.shape[0]
    rows = max(1, -(-n // LANES))
    block = min(block_rows, rows)
    rows = -(-rows // block) * block
    pad = rows * LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, LANES), n


def pack_cast(flat, wire_dtype, *, interpret):
    """Fused cast+copy of a 1-D fp32 stream into the wire dtype.

    Padding-aware: any length is accepted; the tail is zero-padded to a
    whole tile grid for the kernel and trimmed from the result.
    """
    x2d, n = _to_lanes(flat)
    out = cast_copy_2d(x2d, wire_dtype, interpret=interpret,
                       name="bucket_pack")
    return out.reshape(-1)[:n]


def unpack_cast(flat, acc_dtype, *, interpret):
    """Inverse of pack_cast: wire-dtype stream -> accumulation dtype."""
    x2d, n = _to_lanes(flat)
    out = cast_copy_2d(x2d, acc_dtype, interpret=interpret,
                       name="bucket_unpack")
    return out.reshape(-1)[:n]
