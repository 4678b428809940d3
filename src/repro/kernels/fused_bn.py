"""Pallas TPU kernel family: fused batch norm for the paper's §2 BN
variant (no moving averages) — the ResNet-50 per-step hot path.

Unfused, every BN site is three+ passes over an activation-sized tensor:
the ``bn_batch_stats`` reduction, the ``bn_apply_stats`` normalize, the
``jax.nn.relu`` (and for the block-output sites a residual add) — the
classic memory-bound term of conv nets, and a first-order cost at the
paper's 8k-32k batches (Goyal et al., You et al.; PAPERS.md). Fused:

  forward   one reduction pass emits per-channel sum and block-centered
            second moment (fp32 accumulation, Chan combine across
            blocks, C on the lane dim — the same cancellation-free
            variance as bn_batch_stats), then one normalize pass folds
            scale/bias and the optional ReLU and residual-add epilogue
            into the single output write.
  backward  a ``jax.custom_vjp`` replaces XLA's multi-kernel AD chain:
            one dy+x-hat reduction pass produces S1 = sum(dy_masked)
            and S2 = sum(dy_masked * x_hat) — which ARE dbias/dscale —
            and one elementwise pass emits
            dx = gamma*rstd * (dy_m - S1/m - x_hat * S2/m)
            with the ReLU mask (recovered from the saved output) and
            the residual gradient (dres = dy_m) folded in.

Cross-replica (sync-BN) composes exactly as ``core.batchnorm``: the
kernel emits *local* moments, the wrapper ``pmean``s them over the DP
axes (the moment-correct E[x^2] combine), and the backward ``psum``s
S1/S2 and scales by the global count — the textbook sync-BN VJP, equal
to autodiff of the pmean'd jnp path (DESIGN.md §10).

The pure-jnp path in ``core/batchnorm.py`` stays the oracle; the
analytic reference fwd/bwd lives in ``kernels/ref.py``. On TPU the
kernels run compiled with ``ROW_BLOCK`` tiles of a (rows, C) view; on
CPU they run in interpret mode with a single whole-array block in the
activation's own shape (grid tracing cost, not VMEM, is the binding
constraint there; see ``_view``) — how the tests validate them
(tests/test_fused_bn.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 256  # rows x C fp32 tiles; C <= 2048 keeps ~2 MB in VMEM


# ---------------------------------------------------------------------------
# kernels (row-blocked over a (rows, C) view; C on the lane dim)
# ---------------------------------------------------------------------------


def _stats_kernel(x_ref, s_ref, q_ref, *, n_rows, rb, masked):
    """One-pass per-channel sum and **centered** second moment
    M2 = sum((x - mu)^2), fp32 accumulation: each block computes its sum
    and its moment about the block mean, and grid steps merge via
    Chan's parallel-variance combine into the (1, C) accumulators
    (init on step 0). Centered-per-block keeps the E[x^2] - mu^2
    cancellation out of the kernel — the same fix bn_batch_stats got —
    at zero extra HBM traffic (the block is already VMEM-resident).
    With ``masked`` (the view has a zero-padded tail), rows at index
    >= ``n_rows`` are masked out of both moments. Every axis but the
    last (channel) one is reduced, so the kernel serves both the
    (rows, C) tiles and the whole-array N-D block (``_view``)."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    if masked:
        ridx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) + i * rb
        valid = ridx < n_rows
        x = jnp.where(valid, x, 0.0)
    bn = jnp.clip(n_rows - i * rb, 1, rb).astype(jnp.float32)
    bsum = jnp.sum(x, axis=axes, keepdims=True)
    bmean = bsum / bn
    d = x - bmean
    if masked:
        d = jnp.where(valid, d, 0.0)
    bm2 = jnp.sum(d * d, axis=axes, keepdims=True)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = bsum
        q_ref[...] = bm2

    @pl.when(i > 0)
    def _acc():
        n_prev = (i * rb) * 1.0  # every earlier block is full
        s_prev = s_ref[...]
        delta = s_prev / n_prev - bmean
        q_ref[...] += bm2 + (delta * delta) * (n_prev * bn
                                               / (n_prev + bn))
        s_ref[...] += bsum


def _apply_kernel(x_ref, a_ref, o_ref, y_ref, *, relu):
    """Normalize + epilogue: y = epi(x * a + o), a = rstd*scale (fp32),
    o = bias - mean*a. One activation read, one write."""
    y = x_ref[...].astype(jnp.float32) * a_ref[...] + o_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _apply_res_kernel(x_ref, r_ref, a_ref, o_ref, y_ref, *, relu):
    """Residual-add epilogue variant (the ResNet block-output sites)."""
    y = x_ref[...].astype(jnp.float32) * a_ref[...] + o_ref[...] \
        + r_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_sums_kernel(dy_ref, x_ref, y_ref, mu_ref, rstd_ref,
                     s1_ref, s2_ref, *, relu):
    """The single backward reduction pass: S1 = sum(dy_m),
    S2 = sum(dy_m * x_hat), with the ReLU mask recovered from the saved
    forward output (y > 0). These are dbias and dscale directly."""
    i = pl.program_id(0)
    dy = dy_ref[...].astype(jnp.float32)
    if relu:
        dy = jnp.where(y_ref[...].astype(jnp.float32) > 0, dy, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * rstd_ref[...]
    axes = tuple(range(dy.ndim - 1))
    s1 = jnp.sum(dy, axis=axes, keepdims=True)
    s2 = jnp.sum(dy * xhat, axis=axes, keepdims=True)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = s1
        s2_ref[...] = s2

    @pl.when(i > 0)
    def _acc():
        s1_ref[...] += s1
        s2_ref[...] += s2


def _bwd_dx_kernel(dy_ref, x_ref, y_ref, mu_ref, rstd_ref,
                   a_ref, b_ref, c_ref, dx_ref, *, relu):
    """The single backward elementwise pass:
    dx = A*dy_m - B - x_hat*C with per-channel A = gamma*rstd,
    B = A*S1/m (- stats-cotangent terms), C = A*S2/m (- dvar term).
    The eval (given-stats) variant is the same kernel with B = C = 0."""
    dy = dy_ref[...].astype(jnp.float32)
    if relu:
        dy = jnp.where(y_ref[...].astype(jnp.float32) > 0, dy, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * rstd_ref[...]
    dx = a_ref[...] * dy - b_ref[...] - xhat * c_ref[...]
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _bwd_dx_res_kernel(dy_ref, x_ref, y_ref, mu_ref, rstd_ref,
                       a_ref, b_ref, c_ref, dx_ref, dr_ref, *, relu):
    """dx pass with the residual gradient folded in (dres = dy_m) —
    no extra pass for the shortcut branch."""
    dy = dy_ref[...].astype(jnp.float32)
    if relu:
        dy = jnp.where(y_ref[...].astype(jnp.float32) > 0, dy, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * rstd_ref[...]
    dx = a_ref[...] * dy - b_ref[...] - xhat * c_ref[...]
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dr_ref[...] = dy.astype(dr_ref.dtype)


# ---------------------------------------------------------------------------
# operand views and pallas_call plumbing
# ---------------------------------------------------------------------------


def _view(x, row_block: Optional[int]) -> Tuple[jax.Array, int, int]:
    """The kernels' view of an (..., C) activation; returns
    (view, rows, rb) with ``rows`` the true (..., ) element count.

    Compiled on TPU (``row_block`` set): the zero-padded (rows_p, C)
    view, cut into ``row_block``-row tiles to bound VMEM.
    ``row_block=None`` (interpret mode, off-TPU): the activation itself
    as one whole-array block, in its own N-D shape. The grid is then a
    single step (interpret mode traces it in Python) and the block
    reductions run over the activation's own axes — the same reduce the
    jnp path emits, which XLA's CPU compiler fuses into the producing
    elementwise op, where a (rows, C) column reduce is split into a
    reduce-window that materialises its input first
    (tests/test_fused_bn.py::test_fusion_report_real_lowering)."""
    c = x.shape[-1]
    rows = x.size // c
    if row_block is None:
        return x, rows, rows
    x2 = x.reshape(rows, c)
    rb = min(row_block, rows)
    pad = (-rows) % rb
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, rows, rb


def _unview(v, shape, rows: int):
    """Inverse of ``_view`` for an activation-shaped output."""
    return v.reshape(-1, shape[-1])[:rows].reshape(shape)


def _blocked(kernel, n_in: int, n_out: int, view, rb: int, out_dtypes,
             interpret: bool, per_channel_in: int = 0):
    """pallas_call factory over ``view`` (from ``_view``): ``n_in``
    activation-shaped streams + ``per_channel_in`` per-channel
    broadcast inputs (``_ch``) -> ``n_out`` outputs (per-channel
    accumulators for reduction kernels, activation-shaped streams
    otherwise). Blocks are ``rb`` rows of a (rows, C) view, or the whole
    array when ``view`` is the N-D activation itself."""
    shape = view.shape
    nd = len(shape)
    lead = rb if nd == 2 else shape[0]
    grid = (shape[0] // lead,)
    rest = (0,) * (nd - 1)
    row_spec = pl.BlockSpec((lead,) + tuple(shape[1:]),
                            lambda i: (i,) + rest)
    ch_shape = (1,) * (nd - 1) + (shape[-1],)
    ch_spec = pl.BlockSpec(ch_shape, lambda i: (0,) * nd)
    in_specs = [row_spec] * n_in + [ch_spec] * per_channel_in
    out_specs = []
    out_shape = []
    for dt, kind in out_dtypes:
        if kind == "channel":
            out_specs.append(ch_spec)
            out_shape.append(jax.ShapeDtypeStruct(ch_shape, dt))
        else:
            out_specs.append(row_spec)
            out_shape.append(jax.ShapeDtypeStruct(shape, dt))
    if n_out == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret)


def _moments(xv, n_rows, rb, interpret):
    """Returns per-channel (sum, centered M2) over the ``n_rows`` true
    rows of the view ``xv``."""
    rows_p = xv.size // xv.shape[-1]
    kernel = functools.partial(_stats_kernel, n_rows=n_rows, rb=rb,
                               masked=rows_p != n_rows)
    s, q = _blocked(kernel, 1, 2, xv, rb,
                    [(jnp.float32, "channel")] * 2, interpret)(xv)
    return s.reshape(-1), q.reshape(-1)


def _ch(v, view):
    """A per-channel vector shaped to broadcast against ``view``."""
    return jnp.asarray(v, jnp.float32).reshape(
        (1,) * (view.ndim - 1) + (view.shape[-1],))


# ---------------------------------------------------------------------------
# custom-VJP entry points
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _train_fn(relu: bool, has_res: bool, res_dtype: Optional[str],
              axes: Optional[Tuple[str, ...]], eps: float,
              interpret: bool, row_block: Optional[int]):
    """Cached per static-config custom_vjp function for the train-mode
    (batch-stats) fused BN. Returns f(x, scale, bias[, residual]) ->
    (y, mean, var)."""

    def fwd_impl(x, scale, bias, residual):
        x2, rows, rb = _view(x, row_block)
        s, m2 = _moments(x2, rows, rb, interpret)
        m = float(rows)
        mean = s / m
        var = m2 / m  # centered: >= 0 by construction
        if axes:
            # moment-correct sync-BN combine: global mean, then each
            # worker's second moment re-centered about it (Chan again)
            local_mean = mean
            mean = jax.lax.pmean(mean, axes)
            var = jax.lax.pmean(
                var + jnp.square(local_mean - mean), axes)
        rstd = jax.lax.rsqrt(var + eps)
        a = rstd * scale.astype(jnp.float32)
        off = bias.astype(jnp.float32) - mean * a
        if has_res:
            r2, _, _ = _view(residual, row_block)
            y2 = _blocked(functools.partial(_apply_res_kernel, relu=relu),
                          2, 1, x2, rb, [(x.dtype, "rows")],
                          interpret, per_channel_in=2)(
                x2, r2, _ch(a, x2), _ch(off, x2))
        else:
            y2 = _blocked(functools.partial(_apply_kernel, relu=relu),
                          1, 1, x2, rb, [(x.dtype, "rows")],
                          interpret, per_channel_in=2)(
                x2, _ch(a, x2), _ch(off, x2))
        y = _unview(y2, x.shape, rows)
        return y, mean, var

    def bwd_impl(res, cts):
        x, y, mean, var, scale = res
        dy, dmean_ct, dvar_ct = cts
        x2, rows, rb = _view(x, row_block)
        y2, _, _ = _view(y, row_block)
        dy2, _, _ = _view(dy, row_block)
        rstd = jax.lax.rsqrt(var + eps)
        s1, s2 = _blocked(
            functools.partial(_bwd_sums_kernel, relu=relu), 3, 2, x2, rb,
            [(jnp.float32, "channel")] * 2, interpret,
            per_channel_in=2)(dy2, x2, y2, _ch(mean, x2), _ch(rstd, x2))
        s1, s2 = s1.reshape(-1), s2.reshape(-1)
        m = float(rows)
        if axes:
            # global sums / count: the textbook sync-BN backward, equal
            # to autodiff through the pmean'd statistics
            n = jax.lax.psum(jnp.ones((), jnp.float32), axes)
            big_m = m * n
            s1g = jax.lax.psum(s1, axes)
            s2g = jax.lax.psum(s2, axes)
            dm = jax.lax.psum(dmean_ct, axes)
            dv = jax.lax.psum(dvar_ct, axes)
        else:
            big_m = m
            s1g, s2g, dm, dv = s1, s2, dmean_ct, dvar_ct
        g32 = scale.astype(jnp.float32)
        a_coef = g32 * rstd
        # stats-output cotangents (zero in the training step, where the
        # new BN state is value_and_grad aux) fold into the same two
        # per-channel offsets: dmean adds dm/M, dvar adds
        # 2*dv*(x-mu)/M = (2*dv/(M*rstd)) * x_hat
        b_coef = a_coef * s1g / big_m - dm / big_m
        c_coef = a_coef * s2g / big_m - 2.0 * dv / (big_m * rstd)
        ch = [_ch(v, x2) for v in (mean, rstd, a_coef, b_coef, c_coef)]
        if has_res:
            dx2, dr2 = _blocked(
                functools.partial(_bwd_dx_res_kernel, relu=relu), 3, 2,
                x2, rb, [(x.dtype, "rows"), (jnp.dtype(res_dtype), "rows")],
                interpret, per_channel_in=5)(dy2, x2, y2, *ch)
            dres = _unview(dr2, x.shape, rows)
        else:
            dx2 = _blocked(
                functools.partial(_bwd_dx_kernel, relu=relu), 3, 1, x2,
                rb, [(x.dtype, "rows")], interpret,
                per_channel_in=5)(dy2, x2, y2, *ch)
            dres = None
        dx = _unview(dx2, x.shape, rows)
        dscale = s2.astype(scale.dtype)  # local sums: DP sync happens
        dbias = s1.astype(scale.dtype)   # downstream, like any leaf grad
        return dx, dscale, dbias, dres

    if has_res:
        @jax.custom_vjp
        def fused(x, scale, bias, residual):
            return fwd_impl(x, scale, bias, residual)

        def fused_fwd(x, scale, bias, residual):
            out = fwd_impl(x, scale, bias, residual)
            y, mean, var = out
            return out, (x, y, mean, var, scale)

        def fused_bwd(res, cts):
            return bwd_impl(res, cts)
    else:
        @jax.custom_vjp
        def fused(x, scale, bias):
            return fwd_impl(x, scale, bias, None)

        def fused_fwd(x, scale, bias):
            out = fwd_impl(x, scale, bias, None)
            y, mean, var = out
            return out, (x, y, mean, var, scale)

        def fused_bwd(res, cts):
            return bwd_impl(res, cts)[:3]

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


@functools.lru_cache(maxsize=None)
def _apply_fn(relu: bool, has_res: bool, res_dtype: Optional[str],
              eps: float, interpret: bool, row_block: Optional[int]):
    """Given-stats (eval / finalized-statistics) fused BN:
    f(x, mean, var, scale, bias[, residual]) -> y, with full cotangents
    for mean/var so the op stays differentiable everywhere."""

    def fwd_impl(x, mean, var, scale, bias, residual):
        x2, rows, rb = _view(x, row_block)
        rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        a = rstd * scale.astype(jnp.float32)
        off = bias.astype(jnp.float32) - mean.astype(jnp.float32) * a
        if has_res:
            r2, _, _ = _view(residual, row_block)
            y2 = _blocked(functools.partial(_apply_res_kernel, relu=relu),
                          2, 1, x2, rb, [(x.dtype, "rows")],
                          interpret, per_channel_in=2)(
                x2, r2, _ch(a, x2), _ch(off, x2))
        else:
            y2 = _blocked(functools.partial(_apply_kernel, relu=relu),
                          1, 1, x2, rb, [(x.dtype, "rows")],
                          interpret, per_channel_in=2)(
                x2, _ch(a, x2), _ch(off, x2))
        return _unview(y2, x.shape, rows)

    def bwd_impl(res, dy):
        x, y, mean, var, scale = res
        x2, rows, rb = _view(x, row_block)
        y2, _, _ = _view(y, row_block)
        dy2, _, _ = _view(dy, row_block)
        mean32 = mean.astype(jnp.float32)
        rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        s1, s2 = _blocked(
            functools.partial(_bwd_sums_kernel, relu=relu), 3, 2, x2, rb,
            [(jnp.float32, "channel")] * 2, interpret,
            per_channel_in=2)(dy2, x2, y2, _ch(mean32, x2), _ch(rstd, x2))
        s1, s2 = s1.reshape(-1), s2.reshape(-1)
        g32 = scale.astype(jnp.float32)
        a_coef = g32 * rstd
        zero = jnp.zeros_like(a_coef)
        ch = [_ch(v, x2) for v in (mean32, rstd, a_coef, zero, zero)]
        if has_res:
            dx2, dr2 = _blocked(
                functools.partial(_bwd_dx_res_kernel, relu=relu), 3, 2,
                x2, rb, [(x.dtype, "rows"), (jnp.dtype(res_dtype), "rows")],
                interpret, per_channel_in=5)(dy2, x2, y2, *ch)
            dres = _unview(dr2, x.shape, rows)
        else:
            dx2 = _blocked(
                functools.partial(_bwd_dx_kernel, relu=relu), 3, 1, x2,
                rb, [(x.dtype, "rows")], interpret,
                per_channel_in=5)(dy2, x2, y2, *ch)
            dres = None
        dx = _unview(dx2, x.shape, rows)
        dmean = (-a_coef * s1).astype(mean.dtype)
        dvar = (-0.5 * g32 * jnp.square(rstd) * s2).astype(var.dtype)
        dscale = s2.astype(scale.dtype)
        dbias = s1.astype(scale.dtype)
        return dx, dmean, dvar, dscale, dbias, dres

    if has_res:
        @jax.custom_vjp
        def fused(x, mean, var, scale, bias, residual):
            return fwd_impl(x, mean, var, scale, bias, residual)

        def fused_fwd(x, mean, var, scale, bias, residual):
            y = fwd_impl(x, mean, var, scale, bias, residual)
            return y, (x, y, mean, var, scale)

        def fused_bwd(res, dy):
            return bwd_impl(res, dy)
    else:
        @jax.custom_vjp
        def fused(x, mean, var, scale, bias):
            return fwd_impl(x, mean, var, scale, bias, None)

        def fused_fwd(x, mean, var, scale, bias):
            y = fwd_impl(x, mean, var, scale, bias, None)
            return y, (x, y, mean, var, scale)

        def fused_bwd(res, dy):
            return bwd_impl(res, dy)[:5]

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


def fused_bn_train(x, scale, bias, *, residual=None, relu: bool = False,
                   eps: float = 1e-5,
                   cross_replica: Optional[Sequence[str]] = None,
                   interpret: bool,
                   row_block: Optional[int] = None):
    """Train-mode fused BN: (y, mean, var) from one stats pass + one
    normalize/epilogue pass; fused custom-VJP backward (module
    docstring). ``cross_replica``: DP axis names for sync-BN under
    shard_map (local moments are pmean'd, the backward psums S1/S2).
    ``row_block=None``: single block off-TPU, ``ROW_BLOCK`` tiles when
    compiled."""
    axes = tuple(cross_replica) if cross_replica else None
    if row_block is None and not interpret:
        row_block = ROW_BLOCK
    has_res = residual is not None
    res_dtype = jnp.dtype(residual.dtype).name if has_res else None
    f = _train_fn(bool(relu), has_res, res_dtype, axes, float(eps),
                  bool(interpret), row_block)
    if has_res:
        return f(x, scale, bias, residual)
    return f(x, scale, bias)


def fused_bn_apply(x, mean, var, scale, bias, *, residual=None,
                   relu: bool = False, eps: float = 1e-5,
                   interpret: bool,
                   row_block: Optional[int] = None):
    """Given-stats fused BN (eval / finalized statistics): normalize +
    epilogue in one pass, differentiable (full mean/var cotangents)."""
    if row_block is None and not interpret:
        row_block = ROW_BLOCK
    has_res = residual is not None
    res_dtype = jnp.dtype(residual.dtype).name if has_res else None
    f = _apply_fn(bool(relu), has_res, res_dtype, float(eps),
                  bool(interpret), row_block)
    if has_res:
        return f(x, mean, var, scale, bias, residual)
    return f(x, mean, var, scale, bias)
