"""Public SSD op with implementation dispatch (pallas / xla-chunked / ref).

Every implementation takes the decay as ``log_a`` (float32, <= 0), the log
of the per-step, per-head factor: Mamba-2's ``-dt * exp(A_log)``.  The
chunked forms cumsum it directly; nothing exponentiates it and takes the
log again, so a decay strong enough to underflow ``exp`` stays finite.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import ssd_pallas
from .ref import ssd_reference, ssd_step_reference

_NO_TPU = ("impl='pallas' needs a TPU backend; "
           "impl='pallas_interpret' runs the kernel in interpret mode")

__all__ = ["ssd", "ssd_step"]


def ssd(
    x: jnp.ndarray,                     # (B, S, H, P)
    log_a: jnp.ndarray,                 # (B, S, H) log decay, <= 0
    B_mat: jnp.ndarray,                 # (B, S, N)
    C_mat: jnp.ndarray,                 # (B, S, N)
    initial_state: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 256,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked state-space duality scan.  Returns (y, final_state)."""
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((Bsz, H, P, N), jnp.float32)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "ref":
        return ssd_reference(x, log_a, B_mat, C_mat, initial_state)
    if impl in ("pallas", "pallas_interpret"):
        if impl == "pallas" and jax.default_backend() != "tpu":
            raise RuntimeError(_NO_TPU)
        return ssd_pallas(
            x, log_a, B_mat, C_mat, initial_state, chunk=chunk,
            interpret=impl == "pallas_interpret")
    if impl == "xla":
        return _ssd_xla(x, log_a, B_mat, C_mat, initial_state, chunk=chunk)
    raise ValueError(f"unknown impl {impl!r}")


def ssd_step(state, x_t, log_a_t, b_t, c_t):
    """Single-token decode step (pure jnp; the op is tiny)."""
    return ssd_step_reference(state, x_t, log_a_t, b_t, c_t)


def _ssd_xla(x, log_a, B_mat, C_mat, initial_state, *, chunk):
    """Blocked SSD in pure jnp: scan over chunks, matmuls within.

    Same math as the Pallas kernel; used for CPU dry-run lowering so the
    compiled HLO reflects the blocked algorithm (chunk-quadratic intra +
    state passing), not a length-S sequential scan.
    """
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk

    xf = x.astype(jnp.float32).reshape(Bsz, n_chunks, chunk, H, P)
    Bf = B_mat.astype(jnp.float32).reshape(Bsz, n_chunks, chunk, N)
    Cf = C_mat.astype(jnp.float32).reshape(Bsz, n_chunks, chunk, N)

    la = jnp.cumsum(log_a.astype(jnp.float32).reshape(Bsz, n_chunks, chunk, H),
                    axis=2)                              # (B, nc, c, H)
    total = la[:, :, -1, :]                              # (B, nc, H)

    # Intra-chunk, all chunks in parallel (they don't depend on the state).
    scores = jnp.einsum("bgtn,bgrn->bgtr", Cf, Bf)       # (B, nc, c, c)
    t_idx = jnp.arange(chunk)
    causal = (t_idx[:, None] >= t_idx[None, :])
    # Mask the exponent, not the exp: above the diagonal la_t - la_r > 0
    # overflows at long chunks, and inf * 0 in the backward is NaN.
    m = jnp.exp(jnp.where(causal[None, None, :, :, None],
                          la[:, :, :, None, :] - la[:, :, None, :, :],
                          -jnp.inf))                      # (B,nc,c,c,H)
    y_intra = jnp.einsum("bgtr,bgtrh,bgrhp->bgthp", scores, m, xf)

    # Chunk -> state contribution (independent per chunk).
    w = jnp.exp(total[:, :, None, :] - la)               # (B, nc, c, H)
    dstate = jnp.einsum("bgthp,bgtn->bghpn", xf * w[..., None], Bf)

    # Sequential state passing across chunks.
    def step(state, inputs):                             # state: (B, H, P, N)
        tot_g, dstate_g, la_g, C_g = inputs
        y_inter = jnp.exp(la_g)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", C_g, state)                # (B, c, H, P)
        state = jnp.exp(tot_g)[:, :, None, None] * state + dstate_g
        return state, y_inter

    xs = (total.transpose(1, 0, 2), dstate.transpose(1, 0, 2, 3, 4),
          la.transpose(1, 0, 2, 3), Cf.transpose(1, 0, 2, 3))
    final, y_inter = jax.lax.scan(step, initial_state.astype(jnp.float32), xs)
    y = y_intra + y_inter.transpose(1, 0, 2, 3, 4)       # (B, nc, c, H, P)
    return y.reshape(Bsz, S, H, P).astype(x.dtype), final
