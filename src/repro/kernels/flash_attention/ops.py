"""Public attention op with implementation dispatch.

- ``impl="pallas"``: the TPU kernel; raises off the TPU.
- ``impl="pallas_interpret"``: the same kernel in interpret mode (CPU tests).
- ``impl="xla"``: memory-efficient chunked flash in pure jnp (nested scans,
  online softmax) — used for dry-run lowering on CPU and as a safe fallback;
  never materializes (Sq, Sk).
- ``impl="naive"``: the oracle (small shapes / decode single-token).
- ``impl="auto"``: pallas on TPU, xla for long sequences elsewhere, naive
  when the score matrix is small.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import attention_reference

_NO_TPU = ("impl='pallas' needs a TPU backend; "
           "impl='pallas_interpret' runs the kernel in interpret mode")

__all__ = ["flash_attention"]

NEG_INF = -1e30
# Below this Sq*Sk, the naive path is both faster to compile and accurately
# costed by XLA; above it, chunking bounds the transient memory.
_NAIVE_SCORE_LIMIT = 4096 * 4096


def flash_attention(
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
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    B, Sq, Hq, D = q.shape
    _, Sk, _, _ = k.shape
    if impl == "auto":
        if jax.default_backend() == "tpu":
            impl = "pallas"
        elif Sq * Sk <= _NAIVE_SCORE_LIMIT:
            impl = "naive"
        else:
            impl = "xla"
    common = dict(causal=causal, window=window, softcap=softcap,
                  q_segments=q_segments, kv_segments=kv_segments,
                  q_offset=q_offset, scale=scale)
    if impl == "naive":
        return attention_reference(q, k, v, **common)
    if impl in ("pallas", "pallas_interpret"):
        if impl == "pallas" and jax.default_backend() != "tpu":
            raise RuntimeError(_NO_TPU)
        return flash_attention_pallas(
            q, k, v, block_q=block_q, block_k=block_k,
            interpret=impl == "pallas_interpret", **common)
    if impl == "xla":
        return _flash_xla(q, k, v, block_q=block_q, block_k=block_k, **common)
    raise ValueError(f"unknown impl {impl!r}")


def _flash_xla(
    q, k, v, *, causal, window, softcap, q_segments, kv_segments, q_offset,
    scale, block_q, block_k,
):
    """Chunked online-softmax attention in pure jnp (scan over q and kv
    blocks).  Transient memory is O(bq * bk) per (B, H) — never (Sq, Sk)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    n_q, n_k = Sq // bq, Sk // bk

    use_segments = q_segments is not None
    if not use_segments:
        q_segments = jnp.zeros((B, Sq), jnp.int32)
        kv_segments = jnp.zeros((B, Sk), jnp.int32)

    if n_q == 1 and n_k == 1:
        # Single block: no loops — the whole computation is explicit HLO
        # (used by the roofline dry-run so cost_analysis sees the attention
        # FLOPs; XLA never counts lax.scan/map bodies).
        return attention_reference(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_segments=q_segments if use_segments else None,
            kv_segments=kv_segments if use_segments else None,
            q_offset=q_offset, scale=scale)

    # (n_q, B, bq, Hq, D) / (n_k, B, bk, Hkv, D)
    qb = q.reshape(B, n_q, bq, Hq, D).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, n_k, bk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_k, bk, Hkv, D).transpose(1, 0, 2, 3, 4)
    qsb = q_segments.reshape(B, n_q, bq).transpose(1, 0, 2)
    ksb = kv_segments.reshape(B, n_k, bk).transpose(1, 0, 2)

    kf = kb.astype(jnp.float32)
    vf = vb.astype(jnp.float32)

    def q_block(qi, q_blk, qs_blk):
        qf = q_blk.astype(jnp.float32) * scale         # (B, bq, Hq, D)

        def kv_step(carry, inputs):
            m_prev, l_prev, acc = carry
            ki, k_blk, v_blk, ks_blk = inputs
            k_rep = jnp.repeat(k_blk, group, axis=2)    # (B, bk, Hq, D)
            v_rep = jnp.repeat(v_blk, group, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_rep)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            q_pos = q_offset + qi * bq + jnp.arange(bq)
            k_pos = ki * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            mask = mask[None, None]
            if use_segments:
                mask = mask & (qs_blk[:, None, :, None]
                               == ks_blk[:, None, None, :])
            s = jnp.where(mask, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
            p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
            alpha = jnp.where(m_prev <= NEG_INF * 0.5, 0.0,
                              jnp.exp(m_prev - m_safe))
            l_new = alpha * l_prev + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_rep)
            return (m_new, l_new, acc), None

        init = (
            jnp.full((B, Hq, bq), NEG_INF, jnp.float32),
            jnp.zeros((B, Hq, bq), jnp.float32),
            jnp.zeros((B, Hq, bq, D), jnp.float32),
        )
        # The model's scope again: under remat JAX drops the caller's name
        # stack from this scan's own slicing of the kv blocks, and the
        # scope is what attributes those ops to the attention.
        with jax.named_scope("attn_core"):
            (m, l, acc), _ = jax.lax.scan(
                kv_step, init, (jnp.arange(n_k), kf, vf, ksb))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3)   # (B,bq,Hq,D)
        return out.astype(q.dtype)

    outs = jax.lax.map(
        lambda xs: q_block(*xs), (jnp.arange(n_q), qb, qsb))     # (n_q,B,bq,H,D)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, Hq, D)
