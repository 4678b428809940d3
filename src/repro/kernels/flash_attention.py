"""Pallas TPU kernel: tiled online-softmax (flash) attention with GQA,
causal and sliding-window masking.

Grid: (batch, q_heads, Sq/BQ, Sk/BK) — the kv dim is innermost, so the
(m, l, acc) running statistics live in VMEM scratch across kv steps and
the output block is written once on the last kv step (standard TPU
revisiting-grid pattern; MXU-aligned 128x128 tiles).

GQA is handled in the BlockSpec index maps: kv blocks for query head h
come from kv head h // (Hq // Hkv) — no materialized head repetition
(the jnp reference path pays that copy; the kernel does not).

Block-level masking: fully-masked (future / out-of-window) kv blocks are
skipped with pl.when, so causal attention does ~half the work and sliding
windows touch only O(window) tiles per query block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, bq, bk, n_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * bq
    k_lo = ik * bk
    # block-level skip: entire kv block in the future / outside the window
    live = True
    if causal:
        live = k_lo <= q_lo + bq - 1
    if window is not None:
        live = jnp.logical_and(live, q_lo - (k_lo + bk - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=128, block_k=128, interpret):
    """q: (B, Sq, Hq, Dh); k/v: (B, Sk, Hkv, Dh) -> (B, Sq, Hq, Dh).

    Layout inside the kernel is (B, H, S, Dh) for MXU-friendly tiles.
    """
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    n_q, n_k = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(dh)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, hq, n_q, n_k)
    q_spec = pl.BlockSpec((1, 1, bq, dh), lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, dh), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0))
    o_spec = pl.BlockSpec((1, 1, bq, dh), lambda ib, ih, iq, ik: (ib, ih, iq, 0))

    from jax.experimental.pallas import tpu as pltpu
    scratch = [
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq, dh), jnp.float32),
    ]
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
