"""Pallas TPU kernel for chunked SSD (Mamba-2, state-space duality).

The SSD insight: the recurrence

    s_t = a_t s_{t-1} + x_t B_t^T ,   y_t = C_t s_t

is, within a chunk of length c, a *matmul*:

    y = (C B^T ⊙ M) x  +  exp(cumlog_a) * (C s_0^T)
    M[t, r] = exp(la_t - la_r)  for r <= t, else 0        (la = cumsum log a)

so the TPU-native formulation is: grid (B, H, n_chunks) with the chunk
dimension sequential ("arbitrary"), the running state (P, N) living in fp32
VMEM scratch across chunk iterations, and both the intra-chunk (c x c)(c x P)
and state (c x N)(N x P) products on the MXU.  The decay comes in as
log_a <= 0 and is only cumsummed, never logged: every exponent the kernel
keeps is <= 0, so the blocked form is stable in fp32 and a decay that
underflows exp stays finite.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_pallas"]


def _ssd_kernel(
    x_ref,         # (1, 1, c, P)
    la_ref,        # (1, 1, 1, c)  cumulative log-decay within the chunk
    b_ref,         # (1, c, N)
    c_ref,         # (1, c, N)
    s0_ref,        # (1, 1, P, N)  initial state for this (b, h)
    y_ref,         # (1, 1, c, P)
    sfin_ref,      # (1, 1, P, N)  final state out
    state_scr,     # (P, N) f32 scratch
    *,
    chunk: int,
    n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                # (c, P)
    la_row = la_ref[0, 0]                              # (1, c)
    bm = b_ref[0].astype(jnp.float32)                  # (c, N)
    cm = c_ref[0].astype(jnp.float32)                  # (c, N)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    r_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # The same values as a column: pick the diagonal of the broadcast row.
    la_col = jnp.sum(jnp.where(t_idx == r_idx, la_row, 0.0), axis=1,
                     keepdims=True)                    # (c, 1)
    total = jnp.sum(jnp.where(r_idx[:1] == chunk - 1, la_row, 0.0))  # la_{c-1}

    # Intra-chunk: (C B^T ⊙ M) X on the MXU.
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (c, c)
    m = jnp.where(t_idx >= r_idx, jnp.exp(la_col - la_row), 0.0)
    y = jax.lax.dot_general(scores * m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (c, P)

    # Inter-chunk: contribution of the carried state.
    state = state_scr[...]                                            # (P, N)
    y += jnp.exp(la_col) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                           # (c, P)

    # State update: s' = exp(total) s + sum_t exp(total - la_t) x_t B_t^T.
    w = jnp.exp(total - la_col)                                       # (c, 1)
    state_new = jnp.exp(total) * state + jax.lax.dot_general(
        x * w, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                           # (P, N)
    state_scr[...] = state_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        sfin_ref[0, 0] = state_new.astype(sfin_ref.dtype)


def ssd_pallas(
    x: jnp.ndarray,                     # (B, S, H, P)
    log_a: jnp.ndarray,                 # (B, S, H) log decay, <= 0
    B_mat: jnp.ndarray,                 # (B, S, N)
    C_mat: jnp.ndarray,                 # (B, S, N)
    initial_state: jnp.ndarray,         # (B, H, P, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    # Head-major layouts, so that every block's last two dims are either
    # tile-aligned or whole: x as (B, H, S, P), the within-chunk cumulative
    # log-decay as (B, H, 1, S).
    xt = x.transpose(0, 2, 1, 3)
    la = jnp.cumsum(
        log_a.astype(jnp.float32).transpose(0, 2, 1).reshape(
            Bsz, H, n_chunks, chunk), axis=-1).reshape(Bsz, H, 1, S)

    y, sfin = pl.pallas_call(
        kernel,
        grid=(Bsz, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ci: (b, h, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xt, la, B_mat, C_mat, initial_state)
    return y.transpose(0, 2, 1, 3), sfin
