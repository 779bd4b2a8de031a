"""Pallas TPU flash attention: online-softmax over KV blocks in VMEM.

TPU-native adaptation (not a CUDA port): HBM->VMEM staging via BlockSpec
tiling replaces shared-memory blocking; the score matmul and the PV matmul
are MXU-shaped (block_q x D and block_q x block_k, multiples of 128 at
production sizes); the softmax running max/denominator live in fp32 VMEM
scratch that persists across the sequential KV grid dimension.

Grid: (B, Hq, Sq/block_q, Sk/block_k) — last dim sequential ("arbitrary"),
carrying (m, l, acc) scratch.  Supports GQA (kv head = q head // group),
causal and sliding-window masking (with whole-block skip via pl.when),
logit soft-capping, and packed-sequence segment masking.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    # refs (blocked by BlockSpec):
    q_ref,        # (1, 1, bq, D)
    k_ref,        # (1, 1, bk, D)
    v_ref,        # (1, 1, bk, D)
    qseg_ref,     # (1, bq, 1)
    kseg_ref,     # (1, 1, bk)
    o_ref,        # (1, 1, bq, D)
    m_scr,        # (bq,) f32 scratch
    l_scr,        # (bq,) f32
    acc_scr,      # (bq, D) f32
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    use_segments: bool,
    scale: float,
    block_q: int,
    block_k: int,
    n_k: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + q_offset        # absolute first q position
    k_start = ki * block_k

    # Whole-block skip: causal => skip blocks entirely above the diagonal;
    # window => skip blocks entirely older than the window.
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= (q_start - (k_start + block_k - 1)) < window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        if use_segments:
            mask &= qseg_ref[0] == kseg_ref[0]               # (bq,1)==(1,bk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows: keep m finite so exp() is well-defined
        m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF * 0.5, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,              # (B, Sq, Hq, D)
    k: jnp.ndarray,              # (B, Sk, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_segments: Optional[jnp.ndarray] = None,
    kv_segments: Optional[jnp.ndarray] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k

    use_segments = q_segments is not None
    if not use_segments:
        q_segments = jnp.zeros((B, Sq), dtype=jnp.int32)
        kv_segments = jnp.zeros((B, Sk), dtype=jnp.int32)

    # (B, H, S, D) layout for clean 4D blocking.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_kernel, causal=causal, window=window, softcap=softcap,
        use_segments=use_segments, scale=scale, block_q=block_q,
        block_k=block_k, n_k=n_k, q_offset=q_offset,
    )
    # Segment ids as a column for q and a row for kv: each block's last two
    # dims are then (aligned, whole) or (whole, aligned).
    qseg = q_segments.astype(jnp.int32).reshape(B, Sq, 1)
    kseg = kv_segments.astype(jnp.int32).reshape(B, 1, Sk)
    out = _call(kernel, qt, kt, vt, qseg, kseg,
                B, Hq, n_q, n_k, block_q, block_k, D, group,
                q.dtype, interpret)
    return out.transpose(0, 2, 1, 3)


def _call(kernel, qt, kt, vt, qseg, kseg, B, Hq, n_q, n_k, block_q, block_k,
          D, group, dtype, interpret):
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, qi, ki: (b, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, n_q * block_q, D), dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, qseg, kseg)
