"""Pure-jnp oracle for the Mamba-2 SSD (state-space duality) operator.

Sequential-over-time reference:

    s_t = exp(log_a_t) * s_{t-1} + x_t (outer) B_t   s: (P, N) per (batch, head)
    y_t = s_t @ C_t

with x: (B, S, H, P), log_a: (B, S, H) the log decay (float32, <= 0;
Mamba-2's -dt * exp(A_log)), B/C: (B, S, N) shared across heads (single SSD
group, as in mamba2).  A decay strong enough to underflow exp(log_a) to 0
stays exact.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ssd_reference", "ssd_step_reference"]


def ssd_reference(
    x: jnp.ndarray,                     # (B, S, H, P)
    log_a: jnp.ndarray,                 # (B, S, H)
    B_mat: jnp.ndarray,                 # (B, S, N)
    C_mat: jnp.ndarray,                 # (B, S, N)
    initial_state: Optional[jnp.ndarray] = None,   # (B, H, P, N)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y: (B, S, H, P), final_state: (B, H, P, N))."""
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    xf = x.astype(jnp.float32)
    af = jnp.exp(log_a.astype(jnp.float32))
    Bf = B_mat.astype(jnp.float32)
    Cf = C_mat.astype(jnp.float32)
    s0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(state, inputs):
        x_t, a_t, b_t, c_t = inputs            # (B,H,P) (B,H) (B,N) (B,N)
        state = state * a_t[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", x_t, b_t)
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t)
        return state, y_t

    xs = (xf.transpose(1, 0, 2, 3), af.transpose(1, 0, 2),
          Bf.transpose(1, 0, 2), Cf.transpose(1, 0, 2))
    final, ys = jax.lax.scan(step, s0, xs)
    y = ys.transpose(1, 0, 2, 3).astype(x.dtype)   # (B, S, H, P)
    return y, final.astype(jnp.float32)


def ssd_step_reference(
    state: jnp.ndarray,                 # (B, H, P, N) f32
    x_t: jnp.ndarray,                   # (B, H, P)
    log_a_t: jnp.ndarray,               # (B, H)
    b_t: jnp.ndarray,                   # (B, N)
    c_t: jnp.ndarray,                   # (B, N)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single decode step; returns (y_t: (B, H, P), new_state)."""
    a_t = jnp.exp(log_a_t.astype(jnp.float32))
    state = state * a_t[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", x_t.astype(jnp.float32), b_t.astype(jnp.float32))
    y_t = jnp.einsum("bhpn,bn->bhp", state, c_t.astype(jnp.float32))
    return y_t.astype(x_t.dtype), state
