"""Public attention op with implementation dispatch.

- ``impl="pallas"``: the TPU kernel; raises off the TPU.
- ``impl="pallas_interpret"``: the same kernel in interpret mode (CPU tests).
- ``impl="xla"``: memory-efficient chunked flash in pure jnp (one scan over
  the block pairs the causal and window rules leave live, online softmax)
  with its own backward (recomputed score blocks) — the differentiated path
  of the train step; never materializes (Sq, Sk).
- ``impl="naive"``: the oracle (small shapes / decode single-token).
- ``impl="auto"``: pallas on TPU, xla for long sequences elsewhere, naive
  when the score matrix is small.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
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
    """Chunked online-softmax attention in pure jnp (one scan over the live
    pairs of q and kv blocks).  Transient memory is O(bq * bk) per (B, H) —
    never (Sq, Sk), in the backward too (``_chunked_bwd``).  With ``obs``
    on, each trace counts the pairs run (``attn.pairs_live``) of the grid
    (``attn.pairs_all``)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
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

    spec = _Blocks(causal, window, softcap, q_offset, scale, bq, bk,
                   use_segments)
    obs.count("attn.pairs_live", len(spec.live_pairs(n_q, n_k)))
    obs.count("attn.pairs_all", n_q * n_k)
    return _chunked(spec, q, k, v, q_segments, kv_segments)


class _Blocks(NamedTuple):
    """The static side of a chunked attention call."""
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    q_offset: int
    scale: float
    bq: int
    bk: int
    use_segments: bool

    def visible(self, d):
        """The causal and window rule on ``d``, a query's position minus a
        key's (a NumPy or jnp integer array): whether the query may see the
        key.  Segments aside, this is the whole mask."""
        ok = True
        if self.causal:
            ok = ok & (d >= 0)
        if self.window is not None:
            ok = ok & (d < self.window)
        return ok

    def mask(self, qi, ki, qs_blk, ks_blk):
        """Which keys of kv block ``ki`` the queries of q block ``qi`` see:
        bool (B or 1, bq, bk)."""
        q_pos = self.q_offset + qi * self.bq + jnp.arange(self.bq)
        k_pos = ki * self.bk + jnp.arange(self.bk)
        mask = jnp.ones((self.bq, self.bk), bool) & self.visible(
            q_pos[:, None] - k_pos[None, :])
        mask = mask[None]
        if self.use_segments:
            mask = mask & (qs_blk[:, :, None] == ks_blk[:, None, :])
        return mask

    def live_pairs(self, n_q, n_k):
        """The block pairs ``(qi, ki)`` in which some query may see some key,
        q-major with kv ascending; every other pair is masked whole, whatever
        the segments.  Within a pair the query minus key positions take every
        integer from ``1 - bk`` to ``bq - 1`` past the pair's own offset."""
        d = np.arange(1 - self.bk, self.bq)
        return [(qi, ki) for qi in range(n_q) for ki in range(n_k)
                if np.any(self.visible(
                    self.q_offset + qi * self.bq - ki * self.bk + d))]


def _pair_indices(pairs):
    """[(qi, ki), ...] -> the int32 arrays of q and kv block indices."""
    qi, ki = np.asarray(pairs, np.int32).reshape(-1, 2).T
    return jnp.asarray(qi), jnp.asarray(ki)


def _chunked_forward(spec, q, k, v, q_segments, kv_segments):
    """The online softmax in one scan over the live block pairs, q-major
    with kv ascending; each q block's running max, sum and output sit in
    its slot of a stack, which starts as a block that has seen no key.
    Returns the output in float32 by q block (n_q, B, Hq, bq, D) and each
    query row's logsumexp (n_q, B, Hq, bq); a row that sees no key has
    output 0 and logsumexp +inf."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    bq, bk, scale, softcap = spec.bq, spec.bk, spec.scale, spec.softcap
    n_q, n_k = Sq // bq, Sk // bk

    # (n_q, B, bq, Hq, D) / (n_k, B, bk, Hkv, D)
    qb = q.reshape(B, n_q, bq, Hq, D).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, n_k, bk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_k, bk, Hkv, D).transpose(1, 0, 2, 3, 4)
    qsb = q_segments.reshape(B, n_q, bq).transpose(1, 0, 2)
    ksb = kv_segments.reshape(B, n_k, bk).transpose(1, 0, 2)

    kf = kb.astype(jnp.float32)
    vf = vb.astype(jnp.float32)

    def pair_step(carry, pair):
        m_all, l_all, acc_all = carry
        qi, ki = pair
        qf = qb[qi].astype(jnp.float32) * scale          # (B, bq, Hq, D)
        k_rep = jnp.repeat(kf[ki], group, axis=2)       # (B, bk, Hq, D)
        v_rep = jnp.repeat(vf[ki], group, axis=2)
        m_prev, l_prev, acc = m_all[qi], l_all[qi], acc_all[qi]
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_rep)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = spec.mask(qi, ki, qsb[qi], ksb[ki])[:, None]
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
        put = jax.lax.dynamic_update_index_in_dim
        return (put(m_all, m_new, qi, 0), put(l_all, l_new, qi, 0),
                put(acc_all, acc, qi, 0)), None

    init = (
        jnp.full((n_q, B, Hq, bq), NEG_INF, jnp.float32),
        jnp.zeros((n_q, B, Hq, bq), jnp.float32),
        jnp.zeros((n_q, B, Hq, bq, D), jnp.float32),
    )
    # The model's scope again: under remat JAX drops the caller's name
    # stack from this scan's own slicing of the blocks, and the scope is
    # what attributes those ops to the attention.
    with jax.named_scope("attn_core"):
        (m, l, acc), _ = jax.lax.scan(
            pair_step, init, _pair_indices(spec.live_pairs(n_q, n_k)))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        lse = jnp.where(l == 0.0, jnp.inf, m + jnp.log(l_safe))
        return acc / l_safe[..., None], lse


def _unblock(out, dtype):
    """(n_q, B, Hq, bq, D) float32 -> (B, Sq, Hq, D) in ``dtype``."""
    n_q, B, Hq, bq, D = out.shape
    return out.transpose(1, 0, 3, 2, 4).reshape(B, n_q * bq, Hq, D).astype(
        dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunked(spec, q, k, v, q_segments, kv_segments):
    out, _ = _chunked_forward(spec, q, k, v, q_segments, kv_segments)
    return _unblock(out, q.dtype)


def _chunked_fwd(spec, q, k, v, q_segments, kv_segments):
    out, lse = _chunked_forward(spec, q, k, v, q_segments, kv_segments)
    return _unblock(out, q.dtype), (q, k, v, q_segments, kv_segments, out,
                                    lse)


def _chunked_bwd(spec, res, d_out):
    """FlashAttention-2's backward (Dao 2023, Algorithm 2): each score block
    is recomputed from q and k and the kept logsumexp, so nothing of shape
    (..., bq, bk) outlives its loop iteration.  One scan over the live block
    pairs, kv-major with q ascending, adds each pair's dq into its q block's
    slot and its dk, dv into its kv block's.  The q heads that share a kv
    head are stacked on the query axis, so every product is a matmul
    batched over (B, Hkv) and dk, dv sum the group."""
    q, k, v, q_segments, kv_segments, out, lse = res
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    bq, bk, scale, softcap = spec.bq, spec.bk, spec.scale, spec.softcap
    n_q, n_k = Sq // bq, Sk // bk

    def q_blocks(x):        # (B, Sq, Hq, ...) -> (n_q, B, Hkv, g * bq, ...)
        x = x.reshape(B, n_q, bq, Hkv, g, *x.shape[3:])
        x = jnp.moveaxis(x, (1, 2), (0, 4))
        return x.reshape(n_q, B, Hkv, g * bq, *x.shape[5:])

    def kv_blocks(x):       # (B, Sk, Hkv, D) -> (n_k, B, bk, Hkv, D)
        return jnp.moveaxis(x.reshape(B, n_k, bk, Hkv, D), 1, 0)

    with jax.named_scope("attn_core"):
        d_out = d_out.astype(jnp.float32)
        qf = q_blocks(q.astype(jnp.float32) * scale)
        dof = q_blocks(d_out)
        # rowsum(dO * O), with O kept by q block as (n_q, B, Hq, bq, D).
        delta = (dof * out.reshape(dof.shape)).sum(-1)
        lse = lse.reshape(n_q, B, Hkv, g * bq)
        qsb = jnp.moveaxis(q_segments.reshape(B, n_q, bq), 1, 0)
        ksb = jnp.moveaxis(kv_segments.reshape(B, n_k, bk), 1, 0)
        kf = kv_blocks(k.astype(jnp.float32))
        vf = kv_blocks(v.astype(jnp.float32))

        def pair_step(carry, pair):
            dq, dk, dv = carry
            qi, ki = pair
            q_blk, do_blk = qf[qi], dof[qi]
            lse_blk, delta_blk = lse[qi], delta[qi]
            k_blk, v_blk = kf[ki], vf[ki]
            s = jnp.einsum("bhmd,bkhd->bhmk", q_blk, k_blk)
            if softcap is not None:
                t = jnp.tanh(s / softcap)
                s = softcap * t
            mask = spec.mask(qi, ki, qsb[qi], ksb[ki])[:, None, None]
            p = jnp.where(
                mask, jnp.exp(s - lse_blk[..., None]).reshape(
                    B, Hkv, g, bq, bk), 0.0).reshape(s.shape)
            dp = jnp.einsum("bhmd,bkhd->bhmk", do_blk, v_blk)
            ds = p * (dp - delta_blk[..., None])
            if softcap is not None:
                ds = ds * (1.0 - t * t)
            dq_blk = jnp.einsum("bhmk,bkhd->bhmd", ds, k_blk) * scale
            dk_blk = jnp.einsum("bhmk,bhmd->bkhd", ds, q_blk)
            dv_blk = jnp.einsum("bhmk,bhmd->bkhd", p, do_blk)
            put = jax.lax.dynamic_update_index_in_dim
            return (put(dq, dq[qi] + dq_blk, qi, 0),
                    put(dk, dk[ki] + dk_blk, ki, 0),
                    put(dv, dv[ki] + dv_blk, ki, 0)), None

        kv_major = sorted(spec.live_pairs(n_q, n_k), key=lambda p: p[::-1])
        (dq, dk, dv), _ = jax.lax.scan(
            pair_step, (jnp.zeros_like(qf), jnp.zeros_like(kf),
                        jnp.zeros_like(vf)), _pair_indices(kv_major))
        dq = jnp.moveaxis(dq.reshape(n_q, B, Hkv, g, bq, D), (0, 4), (1, 2))
        dq = dq.reshape(B, Sq, Hq, D)
        dk = jnp.moveaxis(dk, 0, 1).reshape(B, Sk, Hkv, D)
        dv = jnp.moveaxis(dv, 0, 1).reshape(B, Sk, Hkv, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)
