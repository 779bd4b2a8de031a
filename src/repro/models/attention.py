"""GQA attention module: train/prefill via the flash kernel, decode via a
single-token cache read.  Supports QKV bias, RoPE, sliding windows, logit
softcap, MQA..MHA, cross-attention (no RoPE on encoder keys), and packed
segments.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.flash_attention import flash_attention
from .common import Initializer, RuntimeConfig, apply_rope, dense_init

__all__ = ["attn_init", "attn_apply", "attn_decode", "init_kv_cache"]

NEG_INF = -1e30


def attn_init(ini: Initializer, cfg: ModelConfig, dtype) -> Dict:
    D = cfg.d_model
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": dense_init(ini, D, Hq * dh, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(ini, D, Hkv * dh, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(ini, D, Hkv * dh, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ini, Hq * dh, D, dtype, bias=False),
    }


def _project(p, x, n_heads, dh):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    B, S, _ = y.shape
    return y.reshape(B, S, n_heads, dh)


def attn_apply(
    params: Dict,
    x: jnp.ndarray,                      # (B, S, D)
    cfg: ModelConfig,
    rt: RuntimeConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: Optional[int] = None,
    segments: Optional[jnp.ndarray] = None,
    kv_x: Optional[jnp.ndarray] = None,  # cross-attention source
    use_rope: bool = True,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill / encoder)."""
    B, S, _ = x.shape
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if kv_x is None else kv_x
    with jax.named_scope("attn_proj"):
        q = _project(params["wq"], x, Hq, dh)
        k = _project(params["wk"], src, Hkv, dh)
        v = _project(params["wv"], src, Hkv, dh)
        if use_rope and kv_x is None:
            if positions is None:
                positions = jnp.arange(S)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attn_core"):
        out = flash_attention(
            q, k, v,
            causal=causal and kv_x is None,
            window=window,
            softcap=cfg.attn_softcap,
            q_segments=segments,
            kv_segments=segments if kv_x is None else None,
            impl=rt.attn_impl,
            block_q=rt.attn_block_q,
            block_k=rt.attn_block_k,
        )
    with jax.named_scope("attn_proj"):
        y = out.reshape(B, S, Hq * dh) @ params["wo"]["w"].astype(x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype
                  ) -> Dict[str, jnp.ndarray]:
    Hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, Hkv, dh), dtype),
        "v": jnp.zeros((batch, max_len, Hkv, dh), dtype),
    }


def attn_decode(
    params: Dict,
    x_t: jnp.ndarray,                    # (B, 1, D)
    cache: Dict[str, jnp.ndarray],       # k/v: (B, S_max, Hkv, dh)
    pos: jnp.ndarray,                    # scalar int32: current position
    cfg: ModelConfig,
    rt: RuntimeConfig,
    *,
    window: Optional[int] = None,
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cross_len: Optional[jnp.ndarray] = None,
    context_start: Optional[jnp.ndarray] = None,   # (B,) first valid slot
):
    """One-token decode.  Returns (y: (B,1,D), updated cache).

    Self-attention: writes k/v at slot ``pos`` (or ``pos % L`` when the
    cache is a window-sized ring buffer) then attends over the valid
    entries.  ``pos`` is always the *absolute* position (RoPE uses it).
    Cross-attention: attends over precomputed encoder K/V (no cache
    update).
    """
    B = x_t.shape[0]
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = Hq // Hkv
    q = _project(params["wq"], x_t, Hq, dh)             # (B, 1, Hq, dh)

    if cross_kv is None:
        k_t = _project(params["wk"], x_t, Hkv, dh)
        v_t = _project(params["wv"], x_t, Hkv, dh)
        pos_arr = jnp.full((B, 1), pos, jnp.int32)
        q = apply_rope(q, pos_arr, cfg.rope_theta)
        k_t = apply_rope(k_t, pos_arr, cfg.rope_theta)
        L = cache["k"].shape[1]
        ring = window is not None
        slot = (pos % L) if ring else pos
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_t.astype(cache["k"].dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_t.astype(cache["v"].dtype), slot, axis=1)
        cache = {"k": k_cache, "v": v_cache}
        k, v = k_cache, v_cache
        slots = jnp.arange(L)
        if ring:
            # absolute position stored in slot s: pos - ((pos - s) mod L)
            abs_pos = pos - jnp.mod(pos - slots, L)
            valid = (abs_pos >= 0) & (pos - abs_pos < window)
        else:
            abs_pos = slots
            valid = slots <= pos
        valid = jnp.broadcast_to(valid[None, :], (B, L))
        if context_start is not None:
            valid = valid & (abs_pos[None, :] >= context_start[:, None])
    else:
        k, v = cross_kv
        S_kv = k.shape[1]
        valid = (jnp.arange(S_kv) < cross_len if cross_len is not None
                 else jnp.ones((S_kv,), bool))
        valid = jnp.broadcast_to(valid[None, :], (B, S_kv))

    qf = q.astype(jnp.float32) * (dh ** -0.5)
    kf = k.astype(jnp.float32)
    s = _decode_scores(qf, kf, B, group, Hkv, dh)   # (B, Hkv, group, S_kv)
    if cfg.attn_softcap is not None:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngk,bknd->bngd", p, v.astype(jnp.float32))
    # (B, Hkv, group, dh) is already q-head order (h = n * group + g).
    out = out.reshape(B, 1, Hq * dh).astype(x_t.dtype)
    y = out @ params["wo"]["w"].astype(x_t.dtype)
    return y, cache


def _decode_scores(qf, kf, B, group, Hkv, dh):
    # qf: (B, 1, Hq, dh) with Hq = group * Hkv (head-major grouping:
    # q head h attends kv head h // group).
    q5 = qf.reshape(B, Hkv, group, dh)                  # squeeze S=1
    return jnp.einsum("bngd,bknd->bngk", q5, kf)
