"""Pallas TPU kernel: fused RMSprop-warm-up hybrid update (paper A.1).

The update reads 4 streams (g, theta, Delta, m) and writes 3 — pure
elementwise, so it is HBM-bandwidth-bound. Unfused, XLA may materialize
m_new and the coefficient as separate HBM round-trips; the kernel does the
whole update in one pass per VMEM tile.

Tiling: params are flattened and reshaped to (rows, 128) — the last dim
matches the VPU lane width; BLOCK_ROWS x 128 fp32 tiles keep the 7
resident streams under ~2 MB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 512  # 512*128*4B = 256 KiB per stream; 7 streams ~ 1.8 MiB


def _kernel(scalars_ref, g_ref, p_ref, d_ref, m_ref,
            p_out, d_out, m_out, *, mu1, mu2, eps, eta_rmsprop,
            weight_decay):
    eta = scalars_ref[0, 0]
    a_sgd = scalars_ref[0, 1]
    g = g_ref[...]
    p = p_ref[...]
    d = d_ref[...]
    m = m_ref[...]
    if weight_decay:
        g = g + weight_decay * p
    m_new = mu2 * m + (1.0 - mu2) * g * g
    a_rms = (1.0 - a_sgd) * eta_rmsprop / eta
    coef = a_sgd + a_rms / (jnp.sqrt(m_new) + eps)
    d_new = mu1 * d - coef * g
    p_out[...] = p + eta * d_new
    d_out[...] = d_new
    m_out[...] = m_new


def _kernel_wd(scalars_ref, g_ref, p_ref, d_ref, m_ref, wd_ref,
               p_out, d_out, m_out, *, mu1, mu2, eps, eta_rmsprop):
    """Per-element weight-decay variant: the ZeRO packed shard spans
    decayed and no-decay leaves, so wd rides in as a 5th stream (0.0
    where the leaf is exempt) instead of a compile-time scalar."""
    eta = scalars_ref[0, 0]
    a_sgd = scalars_ref[0, 1]
    g = g_ref[...]
    p = p_ref[...]
    d = d_ref[...]
    m = m_ref[...]
    g = g + wd_ref[...] * p
    m_new = mu2 * m + (1.0 - mu2) * g * g
    a_rms = (1.0 - a_sgd) * eta_rmsprop / eta
    coef = a_sgd + a_rms / (jnp.sqrt(m_new) + eps)
    d_new = mu1 * d - coef * g
    p_out[...] = p + eta * d_new
    d_out[...] = d_new
    m_out[...] = m_new


def fused_update_2d(g, p, d, m, scalars, *, mu1, mu2, eps, eta_rmsprop,
                    weight_decay, interpret, block_rows=BLOCK_ROWS):
    """g/p/d/m: (rows, 128) fp32; scalars: (1, 2) [eta, alpha_sgd].

    ``weight_decay`` is either a python float (baked into the kernel, the
    per-leaf tree-update path) or a (rows, 128) fp32 array of per-element
    decay factors (the ZeRO packed-shard path, DESIGN.md §9).

    Arbitrary row counts are supported: the streams are zero-padded (m
    with ones, so sqrt/eps stays benign) up to a ``block_rows`` multiple
    and the outputs sliced back — full-width tiles for any parameter
    count instead of degrading to tiny blocks or asserting.
    """
    wd_arr = None if isinstance(weight_decay, (int, float)) \
        else weight_decay
    rows = g.shape[0]
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        zrow = ((0, pad), (0, 0))
        g = jnp.pad(g, zrow)
        p = jnp.pad(p, zrow)
        d = jnp.pad(d, zrow)
        m = jnp.pad(m, zrow, constant_values=1.0)
        if wd_arr is not None:
            wd_arr = jnp.pad(wd_arr, zrow)
    padded_rows = rows + pad
    grid = (padded_rows // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((padded_rows, LANES),
                                      jnp.float32)] * 3
    if wd_arr is None:
        kernel = functools.partial(
            _kernel, mu1=mu1, mu2=mu2, eps=eps, eta_rmsprop=eta_rmsprop,
            weight_decay=weight_decay)
        in_specs = [scalar_spec, tile, tile, tile, tile]
        args = (scalars, g, p, d, m)
    else:
        kernel = functools.partial(
            _kernel_wd, mu1=mu1, mu2=mu2, eps=eps,
            eta_rmsprop=eta_rmsprop)
        in_specs = [scalar_spec, tile, tile, tile, tile, tile]
        args = (scalars, g, p, d, m, wd_arr)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tile, tile, tile],
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    if pad:
        outs = [o[:rows] for o in outs]
    return tuple(outs)


# ---------------------------------------------------------------------------
# Stream-LARS kernels (DESIGN.md §11): per-segment squared norms over the
# packed stream, and the trust-scaled momentum update.
# ---------------------------------------------------------------------------

SEG_BLOCK_ROWS = 256  # rows of 128 lanes per grid step


def _seg_sq_kernel(g_ref, p_ref, wd_ref, seg_ref, pp_ref, gg_ref):
    """Accumulate per-segment sums of p^2 and (g + wd*p)^2 into two
    (n_seg_padded, 128) per-lane accumulators revisited by every grid
    step; the wrapper sums their lanes. Row by row: a (n_seg, 128)
    segment mask selects each lane's value into its segment's row —
    exact selects, f32 adds, no lane-changing reshape (which the TPU
    compiler refuses)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        gg_ref[...] = jnp.zeros_like(gg_ref)

    sid = jax.lax.broadcasted_iota(jnp.int32, pp_ref.shape, 0)

    def row(r, acc):
        acc_p, acc_g = acc
        p = p_ref[pl.ds(r, 1), :]
        ge = g_ref[pl.ds(r, 1), :] + wd_ref[pl.ds(r, 1), :] * p
        hit = sid == seg_ref[pl.ds(r, 1), :]
        return (acc_p + jnp.where(hit, p * p, 0.0),
                acc_g + jnp.where(hit, ge * ge, 0.0))

    acc_p, acc_g = jax.lax.fori_loop(
        0, g_ref.shape[0], row,
        (jnp.zeros(pp_ref.shape, jnp.float32),
         jnp.zeros(gg_ref.shape, jnp.float32)))
    pp_ref[...] += acc_p
    gg_ref[...] += acc_g


def _pad_rows(block_rows, rows, streams, seg, seg_fill):
    """Zero-pad (rows, 128) streams and the segment ids (with
    ``seg_fill``) up to a ``block_rows`` multiple."""
    pad = (-rows) % block_rows
    if not pad:
        return streams, seg
    zrow = ((0, pad), (0, 0))
    return ([jnp.pad(x, zrow) for x in streams],
            jnp.pad(seg, zrow, constant_values=seg_fill))


def seg_sq_partials_2d(g, p, wd, seg, n_seg_padded, *, interpret,
                       block_rows=SEG_BLOCK_ROWS):
    """g/p/wd: (rows, 128) fp32; seg: (rows, 128) int32 segment ids.
    Returns (2, n_seg_padded) f32: per-segment sums of [p^2, (g+wd*p)^2].

    ``n_seg_padded`` must be a multiple of 8 (the wrapper in
    kernels/ops.py pads and slices). Row padding points the pad elements
    at segment ``n_seg_padded - 1`` with zero values — an exact +0.0."""
    rows = g.shape[0]
    block_rows = min(block_rows, rows)
    (g, p, wd), seg = _pad_rows(block_rows, rows, [g, p, wd], seg,
                                n_seg_padded - 1)
    grid = (g.shape[0] // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    acc = pl.BlockSpec((n_seg_padded, LANES), lambda i: (0, 0))
    pp, gg = pl.pallas_call(
        _seg_sq_kernel,
        grid=grid,
        in_specs=[tile, tile, tile, tile],
        out_specs=[acc, acc],
        out_shape=[jax.ShapeDtypeStruct((n_seg_padded, LANES),
                                        jnp.float32)] * 2,
        interpret=interpret,
    )(g, p, wd, seg)
    return jnp.stack([pp.sum(axis=1), gg.sum(axis=1)])


def _lars_update_kernel(scalars_ref, trust_ref, g_ref, p_ref, d_ref,
                        wd_ref, seg_ref, p_out, d_out, *, mu1):
    """Trust-scaled momentum step. Per-element trust is looked up row by
    row: a (n_seg, 128) segment mask selects the trust column
    (``trust_ref`` holds trust[s] in every lane of row s) and a sublane
    sum keeps the single hit — one trust value plus zeros, so the
    lookup adds no rounding."""
    eta = scalars_ref[0, 0]
    sid = jax.lax.broadcasted_iota(jnp.int32, trust_ref.shape, 0)
    trust = trust_ref[...]

    def row(r, carry):
        sl = pl.ds(r, 1)
        p = p_ref[sl, :]
        ge = g_ref[sl, :] + wd_ref[sl, :] * p
        t = jnp.sum(jnp.where(sid == seg_ref[sl, :], trust, 0.0), axis=0,
                    keepdims=True)
        d_new = mu1 * d_ref[sl, :] - t * ge
        p_out[sl, :] = p + eta * d_new
        d_out[sl, :] = d_new
        return carry

    jax.lax.fori_loop(0, g_ref.shape[0], row, 0)


def lars_update_2d(g, p, d, wd, seg, trust_col, scalars, *, mu1,
                   interpret, block_rows=SEG_BLOCK_ROWS):
    """g/p/d/wd: (rows, 128) fp32; seg: (rows, 128) int32; trust_col:
    (n_seg_padded, 128) fp32 with trust[s] across row s (1.0 in the
    padding rows); scalars: (1, 2) [eta, unused]. Returns (p', d')."""
    rows = g.shape[0]
    n_seg = trust_col.shape[0]
    block_rows = min(block_rows, rows)
    (g, p, d, wd), seg = _pad_rows(block_rows, rows, [g, p, d, wd], seg,
                                   n_seg - 1)
    padded_rows = g.shape[0]
    grid = (padded_rows // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_lars_update_kernel, mu1=mu1),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0)),
                  pl.BlockSpec((n_seg, LANES), lambda i: (0, 0)),
                  tile, tile, tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((padded_rows, LANES),
                                        jnp.float32)] * 2,
        interpret=interpret,
    )(scalars, trust_col, g, p, d, wd, seg)
    return tuple(o[:rows] for o in outs)
