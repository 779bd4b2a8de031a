"""Pallas TPU kernel for the RG-LRU linear recurrence.

Elementwise recurrence (VPU work, no MXU): the TPU-native win is keeping the
hidden state h (a (1, block_w) fp32 row) resident in VMEM scratch across
sequence chunks, streaming x/r/i blocks HBM->VMEM, and stepping through the
chunk's rows with ref-indexed loads and stores (``pl.ds``).

Grid: (B, W/block_w, S/chunk) — last dim sequential, h persists in scratch.
The width dimension is embarrassingly parallel, so block_w tiles map across
TPU lanes (128-aligned at production widths).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import RGLRU_C

__all__ = ["rglru_pallas"]


def _rglru_kernel(
    x_ref,        # (1, chunk, bw)
    r_ref,        # (1, chunk, bw)
    i_ref,        # (1, chunk, bw)
    lam_ref,      # (1, bw)
    h0_ref,       # (1, 1, bw)
    y_ref,        # (1, chunk, bw)
    hfin_ref,     # (1, 1, bw)
    a_scr,        # (chunk, bw) f32 scratch: decay a_t
    u_scr,        # (chunk, bw) f32 scratch: input u_t, overwritten by h_t
    h_scr,        # (1, bw) f32 scratch: h carried across chunks
    *,
    chunk: int,
    n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)       # (chunk, bw)
    r = r_ref[0].astype(jnp.float32)
    gi = i_ref[0].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)  # (1, bw)

    a = jnp.exp(-RGLRU_C * jax.nn.softplus(lam) * jax.nn.sigmoid(r))
    a_scr[...] = a
    u_scr[...] = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) \
        * jax.nn.sigmoid(gi) * x

    def body(t, h):
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + u_scr[row, :]
        u_scr[row, :] = h
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    y_ref[0] = u_scr[...].astype(y_ref.dtype)
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _final():
        hfin_ref[0] = h.astype(hfin_ref.dtype)


def rglru_pallas(
    x: jnp.ndarray,                     # (B, S, W)
    r: jnp.ndarray,
    i: jnp.ndarray,
    lam: jnp.ndarray,                   # (W,)
    initial_h: jnp.ndarray,             # (B, W)
    *,
    chunk: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, W = x.shape
    chunk = min(chunk, S)
    block_w = min(block_w, W)
    assert S % chunk == 0 and W % block_w == 0
    n_chunks = S // chunk
    n_w = W // block_w

    kernel = functools.partial(_rglru_kernel, chunk=chunk, n_chunks=n_chunks)
    # Per-channel vectors get a unit dim so that their blocks' last two dims
    # are (whole, lane-aligned).
    y, hfin = pl.pallas_call(
        kernel,
        grid=(B, n_w, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, ci: (b, ci, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, ci: (b, ci, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, ci: (b, ci, w)),
            pl.BlockSpec((1, block_w), lambda b, w, ci: (0, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, ci: (b, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, ci: (b, ci, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, ci: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((chunk, block_w), jnp.float32),
                        pltpu.VMEM((chunk, block_w), jnp.float32),
                        pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, r, i, lam.reshape(1, W), initial_h.reshape(B, 1, W))
    return y, hfin.reshape(B, W)
