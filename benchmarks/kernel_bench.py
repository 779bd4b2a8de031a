"""Kernel micro-benchmarks (CPU host): wall time of the jitted XLA paths +
interpret-mode correctness deltas vs the oracles.

Real kernel perf is a TPU measurement; on a CPU host the meaningful
numbers are (a) the XLA-path throughput and (b) max|err| vs the pure-jnp
oracle, proving the Pallas kernels' math.
"""

from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import attention_reference, flash_attention
from repro.kernels.rglru import rglru, rglru_reference
from repro.kernels.ssd import ssd, ssd_reference


def _time(fn, *args, repeat=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6


def run() -> List[Tuple[str, float, str]]:
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention: XLA chunked path wall time + pallas-interpret error
    B, S, Hq, Hkv, D = 1, 1024, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    fa_xla = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, impl="xla", block_q=128, block_k=128))
    us = _time(fa_xla, q, k, v)
    flops = 4 * B * Hq * S * S / 2 * D
    rows.append((f"flash_xla_b{B}_s{S}", us,
                 f"{flops / (us / 1e6) / 1e9:.1f}GFLOP/s"))
    ref = attention_reference(q, k, v)
    out = flash_attention(q, k, v, impl="pallas_interpret",
                          block_q=128, block_k=128)
    err = float(jnp.abs(out - ref).max())
    rows.append(("flash_pallas_interpret_maxerr", err, "vs oracle"))

    # SSD
    B2, S2, H, P, N = 1, 2048, 8, 64, 64
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B2, S2, H, P), jnp.float32)
    log_a = jnp.log(
        jax.nn.sigmoid(jax.random.normal(ks[1], (B2, S2, H))) * 0.5 + 0.5)
    Bm = jax.random.normal(ks[2], (B2, S2, N)) * 0.3
    Cm = jax.random.normal(ks[3], (B2, S2, N)) * 0.3
    ssd_xla = jax.jit(lambda *args: ssd(*args, chunk=256, impl="xla")[0])
    us = _time(ssd_xla, x, log_a, Bm, Cm)
    rows.append((f"ssd_xla_s{S2}_chunk256", us,
                 f"{B2 * S2 / (us / 1e6) / 1e6:.2f}Mtok/s"))
    y_ref, _ = ssd_reference(x[:, :256], log_a[:, :256], Bm[:, :256],
                             Cm[:, :256])
    y, _ = ssd(x[:, :256], log_a[:, :256], Bm[:, :256], Cm[:, :256],
               chunk=64, impl="pallas_interpret")
    rows.append(("ssd_pallas_interpret_maxerr",
                 float(jnp.abs(y - y_ref).max()), "vs oracle"))

    # RG-LRU
    W = 512
    ks = jax.random.split(key, 4)
    xw = jax.random.normal(ks[0], (1, 2048, W), jnp.float32)
    r = jax.random.normal(ks[1], (1, 2048, W), jnp.float32)
    i = jax.random.normal(ks[2], (1, 2048, W), jnp.float32)
    lam = jax.random.normal(ks[3], (W,), jnp.float32)
    rg_xla = jax.jit(lambda *args: rglru(*args, impl="xla")[0])
    us = _time(rg_xla, xw, r, i, lam)
    rows.append((f"rglru_xla_s2048_w{W}", us,
                 f"{2048 / (us / 1e6) / 1e6:.2f}Mtok/s"))
    y_ref, _ = rglru_reference(xw[:, :256], r[:, :256], i[:, :256], lam)
    y, _ = rglru(xw[:, :256], r[:, :256], i[:, :256], lam, chunk=64,
                 impl="pallas_interpret")
    rows.append(("rglru_pallas_interpret_maxerr",
                 float(jnp.abs(y - y_ref).max()), "vs oracle"))
    return rows
