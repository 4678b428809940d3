"""jit'd public wrappers around the Pallas kernels.

On TPU the kernels run compiled; on CPU they run in interpret mode
(Python-executed kernel body) — which is how this container validates
them. The pure-jnp oracles live in ref.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_update as _fu

LANES = _fu.LANES


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# fused hybrid optimizer update
# ---------------------------------------------------------------------------


def fused_hybrid_update(g, p, d, m, h, weight_decay=0.0) -> Tuple:
    """Drop-in for core.optimizer.hybrid_update: (theta', delta', m').

    Flattens the leaf to (rows, 128) fp32 tiles, pads the tail, runs the
    one-pass Pallas update, unpads. ``weight_decay`` may be a scalar
    (per-leaf tree update) or an array shaped like the leaf (ZeRO
    packed-shard update with per-element decay, DESIGN.md §9).
    """
    orig_shape = p.shape
    orig_dtype = p.dtype
    n = p.size
    rows = max(1, -(-n // LANES))
    pad = rows * LANES - n

    def flat(x):
        x = x.astype(jnp.float32).reshape(-1)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
        return x.reshape(rows, LANES)

    scalars = jnp.stack([jnp.asarray(h.eta, jnp.float32),
                         jnp.asarray(h.alpha_sgd, jnp.float32)]).reshape(1, 2)
    if not isinstance(weight_decay, (int, float)):
        weight_decay = flat(weight_decay)
    # fused_update_2d pads the row stream to a block multiple internally,
    # so any row count gets full-width tiles (no divisor search needed)
    p_new, d_new, m_new = _fu.fused_update_2d(
        flat(g), flat(p), flat(d), flat(m), scalars,
        mu1=h.mu1, mu2=h.mu2, eps=h.eps, eta_rmsprop=h.eta_rmsprop,
        weight_decay=weight_decay, interpret=_interpret())

    def unflat(x, dtype):
        return x.reshape(-1)[:n].reshape(orig_shape).astype(dtype)

    return (unflat(p_new, orig_dtype), unflat(d_new, jnp.float32),
            unflat(m_new, jnp.float32))


def _lars_flat(n, rows, pad):
    """(flatten-to-(rows, 128)) helper shared by the stream-LARS wrappers
    below; mirrors fused_hybrid_update's tiling."""
    def flat(x, fill=0.0, dtype=jnp.float32):
        x = x.astype(dtype).reshape(-1)
        if pad:
            x = jnp.concatenate(
                [x, jnp.full((pad,), fill, dtype)])
        return x.reshape(rows, LANES)
    return flat


def fused_segment_sq_partials(p, g, wd, seg, num_segments):
    """(2, num_segments) f32 per-segment sums of [p^2, (g+wd*p)^2] over a
    flat stream — the Pallas twin of stacking two
    ``bucketing.segment_sq_partials`` calls (stream-LARS trust norms,
    DESIGN.md §11). The per-lane accumulation's fold order differs
    from segment_sum's, so this path is allclose- (not bitwise-) parity
    tested and excluded from the bitwise parity matrix."""
    n = p.size
    rows = max(1, -(-n // LANES))
    pad = rows * LANES - n
    flat = _lars_flat(n, rows, pad)
    n_seg_padded = -(-num_segments // 8) * 8
    out = _fu.seg_sq_partials_2d(
        flat(g), flat(p), flat(wd),
        flat(seg, fill=num_segments - 1, dtype=jnp.int32),
        n_seg_padded, interpret=_interpret())
    return out[:, :num_segments]


def fused_lars_update(g, p, d, wd, seg, trust, eta, mu1):
    """(p', d') trust-scaled momentum update on a flat stream: one fused
    pass over 5 streams with the per-segment trust column resident in
    VMEM (stream-LARS fused path, DESIGN.md §11)."""
    orig_dtype = p.dtype
    n = p.size
    rows = max(1, -(-n // LANES))
    pad = rows * LANES - n
    flat = _lars_flat(n, rows, pad)
    num_segments = trust.shape[0]
    n_seg_padded = -(-num_segments // 8) * 8
    trust_col = jnp.broadcast_to(jnp.concatenate(
        [trust.astype(jnp.float32),
         jnp.ones((n_seg_padded - num_segments,), jnp.float32)]
    )[:, None], (n_seg_padded, LANES))
    scalars = jnp.stack([jnp.asarray(eta, jnp.float32),
                         jnp.zeros((), jnp.float32)]).reshape(1, 2)
    p_new, d_new = _fu.lars_update_2d(
        flat(g), flat(p), flat(d), flat(wd),
        flat(seg, fill=n_seg_padded - 1, dtype=jnp.int32),
        trust_col, scalars, mu1=mu1, interpret=_interpret())

    def unflat(x, dtype):
        return x.reshape(-1)[:n].astype(dtype)

    return unflat(p_new, orig_dtype), unflat(d_new, jnp.float32)


# ---------------------------------------------------------------------------
# bucket pack/unpack (bucketed gradient all-reduce, DESIGN.md §6)
# ---------------------------------------------------------------------------


def pack_cast(flat, wire_dtype):
    """Fused cast+copy of a flat fp32 stream to the wire dtype
    (padding-aware). See ref.cast_copy."""
    from repro.kernels import bucket_ops as _bo
    return _bo.pack_cast(flat, wire_dtype, interpret=_interpret())


def unpack_cast(flat, acc_dtype):
    """Inverse of pack_cast: wire stream back to the accumulation dtype."""
    from repro.kernels import bucket_ops as _bo
    return _bo.unpack_cast(flat, acc_dtype, interpret=_interpret())


# ---------------------------------------------------------------------------
# fused batch norm (forward stats+normalize+epilogue, fused VJP)
# ---------------------------------------------------------------------------


def fused_bn_train(x, scale, bias, *, residual=None, relu=False,
                   eps=1e-5, cross_replica=None):
    """Train-mode fused BN: (y, mean, var) in one stats pass + one
    normalize/epilogue pass, with the fused custom-VJP backward
    (DESIGN.md §10). Oracle: core.batchnorm + epilogue (ref.bn_forward /
    ref.bn_backward)."""
    from repro.kernels import fused_bn as _fb
    return _fb.fused_bn_train(x, scale, bias, residual=residual,
                              relu=relu, eps=eps,
                              cross_replica=cross_replica,
                              interpret=_interpret())


def fused_bn_apply(x, mean, var, scale, bias, *, residual=None,
                   relu=False, eps=1e-5):
    """Given-stats fused BN (eval / finalized statistics)."""
    from repro.kernels import fused_bn as _fb
    return _fb.fused_bn_apply(x, mean, var, scale, bias,
                              residual=residual, relu=relu, eps=eps,
                              interpret=_interpret())


# ---------------------------------------------------------------------------
# fused input (augment + normalize + cast, DESIGN.md §15)
# ---------------------------------------------------------------------------


def input_augment_params(seed, step, total, *, max_shift: int = 4):
    """(total, 4) int32 per-sample augmentation parameters
    ``[flip, dy, dx, reserved]`` for ``step``, derived from the
    counter-based threefry stream keyed ``fold_in(PRNGKey(seed), step)``.

    threefry is backend- and trace-invariant, so the host feed workers
    (eager, pipeline.AugmentedSource) and the on-device fused path
    (traced ``step`` inside the train step) draw bitwise-identical
    parameters — but NOT prefix-stable across draw sizes, so ``total``
    must always be the *global* batch; shards slice their rows.
    ``step`` may be a traced scalar."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    kf, ks = jax.random.split(key)
    flip = jax.random.bernoulli(kf, 0.5, (total,)).astype(jnp.int32)
    shifts = jax.random.randint(ks, (total, 2), -max_shift, max_shift + 1,
                                dtype=jnp.int32)
    zeros = jnp.zeros((total, 1), jnp.int32)
    return jnp.concatenate([flip[:, None], shifts, zeros], axis=1)


def fused_input_train(x, params, mean, inv_std, *, out_dtype):
    """One-pass augment+normalize+cast (train). See ref.input_forward."""
    from repro.kernels import fused_input as _fi
    return _fi.fused_input_train(x, params, mean, inv_std,
                                 out_dtype=out_dtype,
                                 interpret=_interpret())


def fused_input_eval(x, mean, inv_std, *, out_dtype):
    """Normalize+cast only (eval variant)."""
    from repro.kernels import fused_input as _fi
    return _fi.fused_input_eval(x, mean, inv_std, out_dtype=out_dtype,
                                interpret=_interpret())


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def attention(q, k, v, *, causal: bool = True, window=None,
              block_q: int = 128, block_k: int = 128):
    """Tiled online-softmax attention (GQA-aware). See ref.attention."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


# ---------------------------------------------------------------------------
# fused RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, *, eps: float = 1e-5):
    """One-pass RMSNorm (fp32 stats in VMEM). See ref.rmsnorm."""
    from repro.kernels import rmsnorm as _rn
    return _rn.rmsnorm(x, scale, eps=eps, interpret=_interpret())
