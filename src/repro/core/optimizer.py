"""The paper's hybrid RMSprop-warm-up update rule (Appendix A.1), as pure
per-leaf math. ``optim/`` wires it into the GradientTransformation
interface; ``kernels/fused_update.py`` is the fused Pallas twin.

    m_t     = mu2 * m_{t-1} + (1 - mu2) * g_t^2
    Delta_t = mu1 * Delta_{t-1} - (a_sgd + a_rms / (sqrt(m_t) + eps)) * g_t
    theta_t = theta_{t-1} + eta * Delta_t

with  a_rms = (1 - a_sgd) * eta_rmsprop / eta_sgd  so that Delta stays
learning-rate independent (Goyal momentum correction, paper A.1).

At a_sgd = 1 this is exactly momentum SGD (Delta = mu1*Delta - g);
at a_sgd = 0 it is RMSprop-with-momentum.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp


class HybridHyper(NamedTuple):
    """Per-step scalars (traced inside the train step)."""

    eta: jnp.ndarray  # eta_SGD(t) from the LR schedule
    alpha_sgd: jnp.ndarray  # transition schedule value in [0, 1]
    mu1: float = 0.9
    mu2: float = 0.99
    eps: float = 1e-8
    eta_rmsprop: float = 3e-4


def alpha_rmsprop(h: HybridHyper):
    """Paper A.1 coupling: a_rms = (1 - a_sgd) * eta_rms / eta_sgd."""
    return (1.0 - h.alpha_sgd) * h.eta_rmsprop / h.eta


def _decayed(g, theta32, weight_decay):
    """L2-in-gradient (Goyal baseline): ``g + wd * theta``.
    ``weight_decay`` is a python float (per-leaf tree update; 0.0 skips
    the term) or an array shaped like ``g`` (packed stream, 0.0 on the
    no-decay leaves).

    The decay product is rounded on its own. Left as a bare multiply,
    the compiler may contract it with the add into one FMA, or contract
    the gradient's 1/n mean multiply instead, depending on how each
    layout happened to fuse — and the per-leaf tree, full-stream and
    ZeRO-shard updates then differ in the last bit. The select passes
    every value through (NaN stays NaN) but is not a multiply, so this
    side offers nothing to contract; the 1/n side is exact for
    power-of-two worker counts (tests/test_zero.py bitwise parity)."""
    if isinstance(weight_decay, (int, float)) and not weight_decay:
        return g
    decay = weight_decay * theta32
    return g + jnp.where(jnp.isnan(decay), jnp.nan, decay)


def hybrid_update(g, theta, delta, m, h: HybridHyper,
                  weight_decay=0.0) -> Tuple:
    """One update over a leaf or a flat stream. Returns
    (theta', delta', m'). fp32 math."""
    g = g.astype(jnp.float32)
    theta32 = theta.astype(jnp.float32)
    g = _decayed(g, theta32, weight_decay)
    m_new = h.mu2 * m + (1.0 - h.mu2) * jnp.square(g)
    coef = h.alpha_sgd + alpha_rmsprop(h) / (jnp.sqrt(m_new) + h.eps)
    delta_new = h.mu1 * delta - coef * g
    theta_new = theta32 + h.eta * delta_new
    return theta_new.astype(theta.dtype), delta_new, m_new


def momentum_sgd_update(g, theta, delta, h: HybridHyper,
                        weight_decay=0.0) -> Tuple:
    """Goyal et al. baseline: the a_sgd = 1 special case, no m state."""
    g = g.astype(jnp.float32)
    theta32 = theta.astype(jnp.float32)
    g = _decayed(g, theta32, weight_decay)
    delta_new = h.mu1 * delta - g
    theta_new = theta32 + h.eta * delta_new
    return theta_new.astype(theta.dtype), delta_new
