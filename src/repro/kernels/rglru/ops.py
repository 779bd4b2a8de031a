"""Public RG-LRU op with implementation dispatch."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import rglru_pallas
from .ref import RGLRU_C, rglru_reference, rglru_step_reference

_NO_TPU = ("impl='pallas' needs a TPU backend; "
           "impl='pallas_interpret' runs the kernel in interpret mode")

__all__ = ["rglru", "rglru_step"]


def rglru(
    x: jnp.ndarray,                     # (B, S, W)
    r: jnp.ndarray,
    i: jnp.ndarray,
    lam: jnp.ndarray,                   # (W,)
    initial_h: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 256,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, W = x.shape
    if initial_h is None:
        initial_h = jnp.zeros((B, W), jnp.float32)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "ref":
        return rglru_reference(x, r, i, lam, initial_h)
    if impl in ("pallas", "pallas_interpret"):
        if impl == "pallas" and jax.default_backend() != "tpu":
            raise RuntimeError(_NO_TPU)
        return rglru_pallas(
            x, r, i, lam, initial_h, chunk=chunk,
            interpret=impl == "pallas_interpret")
    if impl == "xla":
        return _rglru_xla(x, r, i, lam, initial_h)
    raise ValueError(f"unknown impl {impl!r}")


def rglru_step(h, x_t, r_t, i_t, lam):
    return rglru_step_reference(h, x_t, r_t, i_t, lam)


def _rglru_xla(x, r, i, lam, initial_h):
    """Associative-scan formulation (log-depth; XLA-friendly).

    h_t = a_t h_{t-1} + u_t is associative under
    (a1,u1) ∘ (a2,u2) = (a1*a2, u1*a2 + u2).
    An arbitrary initial h folds in as an extra leading element.
    """
    B, S, W = x.shape
    log_a = -RGLRU_C * jax.nn.softplus(lam.astype(jnp.float32))[None, None, :] \
        * jax.nn.sigmoid(r.astype(jnp.float32))
    a = jnp.exp(log_a)
    u = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * jax.nn.sigmoid(
        i.astype(jnp.float32)) * x.astype(jnp.float32)
    u = u.at[:, 0, :].add(a[:, 0, :] * initial_h.astype(jnp.float32))

    def combine(c1, c2):
        a1, u1 = c1
        a2, u2 = c2
        return a1 * a2, u1 * a2 + u2

    _, h = jax.lax.associative_scan(combine, (a, u), axis=1)
    return h.astype(x.dtype), h[:, -1, :]
