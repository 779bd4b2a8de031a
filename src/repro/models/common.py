"""Shared model building blocks: norms, MLPs, RoPE, init helpers, runtime.

Models are pure-functional: params are nested dicts of jnp arrays, built by
``init_*`` functions and consumed by ``apply_*`` functions.  No framework
dependency — pjit/shard_map see plain pytrees.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["RuntimeConfig", "Initializer", "rmsnorm", "layernorm",
           "norm_init", "norm_apply", "dense_init", "mlp_init", "mlp_apply",
           "apply_rope", "softcap"]

PyTree = Any


@dataclass(frozen=True)
class RuntimeConfig:
    """Execution knobs orthogonal to the architecture."""

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"              # auto | pallas | xla | naive
    ssd_impl: str = "auto"
    rglru_impl: str = "auto"
    remat: str = "none"                  # none | full | dots
    attn_block_q: int = 512
    attn_block_k: int = 1024
    moe_group_size: int = 512
    max_cache_len: int = 0               # serve: KV cache allocation length
    # ActivationSharding (train/sharding.py) or None; models call
    # .hidden()/.logits() at the constraint points when set.
    act_sharding: Any = None
    # MoE execution path: "gspmd" (capacity einsums under pjit) or
    # "shard_map" (explicit all_to_all expert parallelism).
    moe_impl: str = "gspmd"

    def with_(self, **kw) -> "RuntimeConfig":
        return dataclasses.replace(self, **kw)

    def hidden(self, x):
        return self.act_sharding.hidden(x) if self.act_sharding else x

    def logits_constraint(self, x):
        return self.act_sharding.logits(x) if self.act_sharding else x


class Initializer:
    """Deterministic per-path param init (truncated-normal fan-in)."""

    def __init__(self, key: jax.Array):
        self._key = key
        self._n = 0

    def next_key(self) -> jax.Array:
        self._n += 1
        return jax.random.fold_in(self._key, self._n)

    def normal(self, shape, scale: float, dtype) -> jnp.ndarray:
        return (jax.random.truncated_normal(
            self.next_key(), -2.0, 2.0, shape, jnp.float32) * scale
        ).astype(dtype)

    def zeros(self, shape, dtype) -> jnp.ndarray:
        return jnp.zeros(shape, dtype)

    def ones(self, shape, dtype) -> jnp.ndarray:
        return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(ini: Initializer, d: int, kind: str, dtype) -> Dict:
    if kind == "rmsnorm":
        return {"scale": ini.zeros((d,), dtype)}        # gemma-style (1+scale)
    return {"scale": ini.ones((d,), dtype), "bias": ini.zeros((d,), dtype)}


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + 1e-6)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray
              ) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + 1e-6)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def norm_apply(params: Dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params["bias"])


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------


def dense_init(ini: Initializer, d_in: int, d_out: int, dtype,
               bias: bool = False) -> Dict:
    p = {"w": ini.normal((d_in, d_out), d_in ** -0.5, dtype)}
    if bias:
        p["b"] = ini.zeros((d_out,), dtype)
    return p


def dense_apply(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def mlp_init(ini: Initializer, d: int, f: int, dtype) -> Dict:
    return {
        "wi": ini.normal((d, f), d ** -0.5, dtype),
        "wg": ini.normal((d, f), d ** -0.5, dtype),
        "wo": ini.normal((f, d), f ** -0.5, dtype),
    }


def mlp_apply(p: Dict, x: jnp.ndarray, act: str) -> jnp.ndarray:
    """Gated MLP: SwiGLU (silu) or GeGLU (gelu)."""
    h = x @ p["wi"].astype(x.dtype)
    g = x @ p["wg"].astype(x.dtype)
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    return (h * g) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def _rope_angles(positions: jnp.ndarray, dim: int, theta: float
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * freq     # (..., dim/2)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    D = x.shape[-1]
    sin, cos = _rope_angles(positions, D, theta)      # (B, S, D/2)
    if sin.ndim == 2:                                  # (S, D/2) -> batch dim
        sin, cos = sin[None], cos[None]
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
