"""Mixture-of-Experts layer: GShard-style capacity-based top-k dispatch.

Train/prefill: tokens are split into groups of ``rt.moe_group_size``;
within a group, each token's top-k experts receive it up to a per-group
expert capacity C = ceil(g * k * capacity_factor / E).  Dispatch/combine are
one-hot einsums — MXU-friendly and GSPMD-shardable (expert dim can live on a
mesh axis => the dispatched-activations einsum lowers to an all-to-all).
Tokens over capacity are dropped for that expert (standard GShard
semantics); the router is computed in fp32.

Decode: B is small and most experts are hit anyway, so we compute all
experts densely and combine with the top-k gate weights (decode is
memory-bandwidth-bound on the expert weights regardless).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import Initializer, RuntimeConfig

__all__ = ["moe_init", "moe_apply", "moe_decode"]


def moe_init(ini: Initializer, cfg: ModelConfig, dtype) -> Dict:
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    return {
        "router": ini.normal((D, E), D ** -0.5, jnp.float32),
        "wi": ini.normal((E, D, F), D ** -0.5, dtype),
        "wg": ini.normal((E, D, F), D ** -0.5, dtype),
        "wo": ini.normal((E, F, D), F ** -0.5, dtype),
    }


def _route(params, x, cfg: ModelConfig):
    """Router logits/top-k in fp32.  x: (..., D)."""
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, idx, probs


def moe_apply(params: Dict, x: jnp.ndarray, cfg: ModelConfig,
              rt: RuntimeConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss).  Capacity-based dispatch."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    T = B * S
    g = min(rt.moe_group_size, T)
    while T % g:          # static shapes: largest divisor of T <= group_size
        g -= 1
    G = T // g
    C = max(1, int(-(-g * K * cfg.capacity_factor // E)))   # ceil

    xg = x.reshape(G, g, D)
    gate, idx, probs = _route(params, xg, cfg)               # (G,g,K)

    # Load-balancing auxiliary loss (Switch-style).
    me = probs.mean(axis=(0, 1))                             # (E,)
    ce = jax.nn.one_hot(idx[..., 0], E).mean(axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # Dispatch/combine one-hots with per-expert positions.
    counts = jnp.zeros((G, 1, E), jnp.float32)
    dispatch = jnp.zeros((G, g, E, C), jnp.float32)
    combine = jnp.zeros((G, g, E, C), jnp.float32)
    for k_i in range(K):                                     # K is 2: unrolled
        oh = jax.nn.one_hot(idx[..., k_i], E)                # (G,g,E)
        pos = jnp.cumsum(oh, axis=1) - oh + counts           # (G,g,E)
        keep = (pos < C) * oh
        slot = jax.nn.one_hot(pos.astype(jnp.int32), C)      # (G,g,E,C)
        disp_k = keep[..., None] * slot
        dispatch = dispatch + disp_k
        combine = combine + disp_k * gate[..., k_i][..., None, None]
        counts = counts + oh.sum(axis=1, keepdims=True)

    cd = x.dtype
    xd = jnp.einsum("gtec,gtd->gecd", dispatch.astype(cd), xg)  # (G,E,C,D)
    h = jnp.einsum("gecd,edf->gecf", xd, params["wi"].astype(cd))
    gt = jnp.einsum("gecd,edf->gecf", xd, params["wg"].astype(cd))
    h = h * jax.nn.silu(gt)
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(cd))
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(cd), ye)
    return y.reshape(B, S, D), aux


def moe_apply_shardmap(params: Dict, x: jnp.ndarray, cfg: ModelConfig,
                       rt: RuntimeConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism with EXPLICIT collectives (shard_map).

    GSPMD cannot be coaxed into all-to-all dispatch for the capacity
    einsums (measured: it replicates + all-reduces, §Perf) — so this path
    writes the communication pattern by hand:

      per shard: route -> local capacity dispatch -> (E, C_loc, D)
      lax.all_to_all over the expert axis   (tokens travel to their experts)
      local expert FFN (E_local experts, d_ff sharded over tp)
      psum over tp for the down-projection partials
      lax.all_to_all back -> local combine

    Requires rules (mesh) via rt.act_sharding; batch must be divisible by
    the expert axis.  Falls back to the GSPMD path otherwise.
    """
    rules = rt.act_sharding.rules
    mesh = rules.mesh
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map as _sm

        def shard_map(f, *, mesh, in_specs, out_specs, **_):
            return _sm(f, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    except ImportError:  # older jax
        from jax.experimental.shard_map import shard_map as _sme

        def shard_map(f, *, mesh, in_specs, out_specs, **_):
            return _sme(f, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_rep=False)

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    ea = rules.expert_axis or "data"
    tp = rules.tp_axis
    n_ep = rules.size(ea)
    n_tp = rules.size(tp) if tp else 1
    E_local = E // n_ep
    F = cfg.moe_d_ff
    b_axes = rules.batch_spec_axes(B)

    x_spec = P(b_axes, None, None)
    wi_spec = P(ea, None, None)
    wo_spec = P(ea, None, None)

    def local_fn(xl, router, wi, wg, wo):
        # xl: (B_loc, S, D); wi/wg: (E_local, D, F); wo: (E_local, F, D).
        # Token-groups are additionally SPLIT over the tp axis (each tp
        # rank routes/dispatches its own slice) so the all-to-alls are not
        # replicated tp-fold; outputs are re-assembled with an all-gather.
        Bl = xl.shape[0]
        T = Bl * S
        g = min(rt.moe_group_size, T)
        gg = g
        while T % gg:
            gg -= 1
        G = T // gg
        C = max(1, int(-(-gg * K * cfg.capacity_factor // E)))
        xg = xl.reshape(G, gg, D)
        if tp and n_tp > 1 and G % n_tp == 0:
            mi = jax.lax.axis_index(tp)
            G = G // n_tp
            xg = jax.lax.dynamic_slice_in_dim(xg, mi * G, G, axis=0)
        logits = xg.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, K)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=(0, 1))
        ce = jax.nn.one_hot(idx[..., 0], E).mean(axis=(0, 1))
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, ea)
        if tp:
            aux = jax.lax.pmean(aux, tp)

        counts = jnp.zeros((G, 1, E), jnp.float32)
        dispatch = jnp.zeros((G, gg, E, C), jnp.float32)
        combine = jnp.zeros((G, gg, E, C), jnp.float32)
        for k_i in range(K):
            oh = jax.nn.one_hot(idx[..., k_i], E)
            pos = jnp.cumsum(oh, axis=1) - oh + counts
            keep = (pos < C) * oh
            slot = jax.nn.one_hot(pos.astype(jnp.int32), C)
            disp_k = keep[..., None] * slot
            dispatch = dispatch + disp_k
            combine = combine + disp_k * gate[..., k_i][..., None, None]
            counts = counts + oh.sum(axis=1, keepdims=True)

        cd = xl.dtype
        xd = jnp.einsum("gtec,gtd->gecd", dispatch.astype(cd), xg)
        xd = xd.transpose(1, 0, 2, 3).reshape(E, G * C, D)
        # tokens -> their experts' shards: (E, GC, D) -> (E_loc, n_ep*GC, D)
        xd = jax.lax.all_to_all(xd, ea, split_axis=0, concat_axis=1,
                                tiled=True)
        h = jnp.einsum("ecd,edf->ecf", xd, wi.astype(cd))
        gt = jnp.einsum("ecd,edf->ecf", xd, wg.astype(cd))
        ye = jnp.einsum("ecf,efd->ecd", h * jax.nn.silu(gt), wo.astype(cd))
        ye = jax.lax.all_to_all(ye, ea, split_axis=1, concat_axis=0,
                                tiled=True)
        ye = ye.reshape(E, G, C, D).transpose(1, 0, 2, 3)
        y = jnp.einsum("gtec,gecd->gtd", combine.astype(cd), ye)
        if tp and n_tp > 1 and (T // gg) % n_tp == 0:
            y = jax.lax.all_gather(y, tp, axis=0, tiled=True)
        return y.reshape(Bl, S, D), aux

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), wi_spec, wi_spec, wo_spec),
        out_specs=(x_spec, P()),
        check_rep=False,
    )
    return fn(x, params["router"], params["wi"], params["wg"], params["wo"])


def moe_decode(params: Dict, x: jnp.ndarray, cfg: ModelConfig,
               rt: RuntimeConfig) -> jnp.ndarray:
    """x: (B, 1, D).  Dense all-expert compute, top-k combine."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    gate, idx, _ = _route(params, x, cfg)                    # (B,1,K)
    cd = x.dtype
    h = jnp.einsum("btd,edf->btef", x, params["wi"].astype(cd))
    g = jnp.einsum("btd,edf->btef", x, params["wg"].astype(cd))
    ye = jnp.einsum("btef,efd->bted", h * jax.nn.silu(g),
                    params["wo"].astype(cd))                 # (B,1,E,D)
    w = jnp.zeros((B, S, E), jnp.float32)
    for k_i in range(K):
        w = w + jax.nn.one_hot(idx[..., k_i], E) * gate[..., k_i][..., None]
    return jnp.einsum("bte,bted->btd", w.astype(cd), ye)
