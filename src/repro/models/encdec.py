"""Encoder-decoder LM (seamless-m4t backbone).

Encoder: bidirectional self-attention over precomputed frame embeddings
(the speech frontend is a STUB: the batch carries (B, S_enc, d_model)
frames as ``frontend_embeds``).  Decoder: causal self-attention +
cross-attention + MLP.  Both stacks are scan-stacked.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import attn_apply, attn_decode, attn_init, init_kv_cache
from .common import (Initializer, RuntimeConfig, mlp_apply, mlp_init,
                     norm_apply, norm_init, softcap)
from .decoder import _remat

__all__ = ["EncDecLM"]

PyTree = Any


class EncDecLM:
    def __init__(self, cfg: ModelConfig, rt: RuntimeConfig):
        assert cfg.is_encoder_decoder
        self.cfg = cfg
        self.rt = rt

    # ------------------------------------------------------------------ init

    def _enc_block(self, ini: Initializer) -> Dict:
        cfg, dt = self.cfg, self.rt.param_dtype
        return {
            "norm1": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "attn": attn_init(ini, cfg, dt),
            "norm2": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "mlp": mlp_init(ini, cfg.d_model, cfg.d_ff, dt),
        }

    def _dec_block(self, ini: Initializer) -> Dict:
        cfg, dt = self.cfg, self.rt.param_dtype
        return {
            "norm1": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "self_attn": attn_init(ini, cfg, dt),
            "norm2": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "cross_attn": attn_init(ini, cfg, dt),
            "norm3": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "mlp": mlp_init(ini, cfg.d_model, cfg.d_ff, dt),
        }

    def init(self, key) -> PyTree:
        cfg, dt = self.cfg, self.rt.param_dtype
        k_e, k_enc, k_dec, k_h = jax.random.split(key, 4)
        ini = Initializer(k_e)
        params: Dict[str, Any] = {
            "embed": ini.normal((cfg.padded_vocab, cfg.d_model), 1.0, dt),
            "enc_final_norm": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "final_norm": norm_init(ini, cfg.d_model, cfg.norm, dt),
            "lm_head": ini.normal((cfg.d_model, cfg.padded_vocab),
                                  cfg.d_model ** -0.5, dt),
        }
        params["encoder"] = jax.vmap(
            lambda k: self._enc_block(Initializer(k)))(
            jax.random.split(k_enc, cfg.n_encoder_layers))
        params["decoder"] = jax.vmap(
            lambda k: self._dec_block(Initializer(k)))(
            jax.random.split(k_dec, cfg.n_layers))
        return params

    def init_abstract(self) -> PyTree:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------ encoder

    def encode(self, params, frames: jnp.ndarray) -> jnp.ndarray:
        """frames: (B, S_enc, D) precomputed frontend embeddings."""
        cfg, rt = self.cfg, self.rt
        x = frames.astype(rt.compute_dtype)

        def block(carry, p):
            y = carry
            h = norm_apply(p["norm1"], y, cfg.norm)
            y = y + attn_apply(p["attn"], h, cfg, rt, causal=False)
            h = norm_apply(p["norm2"], y, cfg.norm)
            y = y + mlp_apply(p["mlp"], h, cfg.act)
            return rt.hidden(y), None

        x, _ = jax.lax.scan(_remat(block, rt.remat), x, params["encoder"])
        return norm_apply(params["enc_final_norm"], x, cfg.norm)

    # ------------------------------------------------------------------ train

    def _dec_trunk(self, params, x, enc_out):
        cfg, rt = self.cfg, self.rt

        def block(carry, p):
            y = carry
            h = norm_apply(p["norm1"], y, cfg.norm)
            y = y + attn_apply(p["self_attn"], h, cfg, rt, causal=True)
            h = norm_apply(p["norm2"], y, cfg.norm)
            y = y + attn_apply(p["cross_attn"], h, cfg, rt, kv_x=enc_out)
            h = norm_apply(p["norm3"], y, cfg.norm)
            y = y + mlp_apply(p["mlp"], h, cfg.act)
            return rt.hidden(y), None

        x, _ = jax.lax.scan(_remat(block, rt.remat), x, params["decoder"])
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        x = norm_apply(params["final_norm"], x, cfg.norm)
        logits = (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)
        logits = softcap(logits, cfg.final_softcap)
        if cfg.padded_vocab != cfg.vocab_size:
            iota = jax.lax.broadcasted_iota(
                jnp.int32, (cfg.padded_vocab,), 0)
            logits = jnp.where(iota < cfg.vocab_size, logits, -1e30)
        return self.rt.logits_constraint(logits)

    def forward(self, params, batch) -> jnp.ndarray:
        enc_out = self.encode(params, batch["frontend_embeds"])
        x = params["embed"].astype(self.rt.compute_dtype)[batch["tokens"]]
        x = self._dec_trunk(params, x, enc_out)
        return self._logits(params, x)

    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        from .decoder import xent_loss

        logits = self.forward(params, batch)
        return xent_loss(logits, batch["labels"])

    # ------------------------------------------------------------------ serve

    def init_cache(self, batch: int, enc_out: Optional[jnp.ndarray] = None
                   ) -> PyTree:
        """Self-attn KV rings + per-layer cross K/V from the encoder."""
        cfg, rt = self.cfg, self.rt
        L = cfg.n_layers

        def stack(make):
            return jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[make() for _ in range(L)])

        cache = {"self": stack(lambda: init_kv_cache(
            cfg, batch, rt.max_cache_len, rt.compute_dtype))}
        if enc_out is not None:
            cache["cross"] = self._cross_kv(None, enc_out)
        return cache

    def _cross_kv(self, params, enc_out):
        """Precompute (K, V) of the encoder output for every decoder layer."""
        cfg, rt = self.cfg, self.rt
        Hkv, dh = cfg.n_kv_heads, cfg.head_dim

        def per_layer(p):
            B, S, _ = enc_out.shape
            k = (enc_out @ p["cross_attn"]["wk"]["w"].astype(enc_out.dtype))
            v = (enc_out @ p["cross_attn"]["wv"]["w"].astype(enc_out.dtype))
            if "b" in p["cross_attn"]["wk"]:
                k = k + p["cross_attn"]["wk"]["b"].astype(enc_out.dtype)
                v = v + p["cross_attn"]["wv"]["b"].astype(enc_out.dtype)
            return {"k": k.reshape(B, S, Hkv, dh), "v": v.reshape(B, S, Hkv, dh)}

        return jax.vmap(per_layer)(params)

    def prefill(self, params, frames, tokens):
        """Encode + run decoder prompt; returns (logits, cache, pos)."""
        cfg, rt = self.cfg, self.rt
        enc_out = self.encode(params, frames)
        B, S_dec = tokens.shape
        x = params["embed"].astype(rt.compute_dtype)[tokens]
        positions = jnp.broadcast_to(jnp.arange(S_dec), (B, S_dec))
        self_cache = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[init_kv_cache(cfg, B, rt.max_cache_len, rt.compute_dtype)
              for _ in range(cfg.n_layers)])
        cross = self._cross_kv(params["decoder"], enc_out)

        def block(carry, xs):
            y = carry
            p, sc, cr = xs
            h = norm_apply(p["norm1"], y, cfg.norm)
            mix, (k, v) = attn_apply(p["self_attn"], h, cfg, rt,
                                     positions=positions, causal=True,
                                     return_kv=True)
            y = y + mix
            new_sc = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    sc["k"], k.astype(sc["k"].dtype), 0, axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    sc["v"], v.astype(sc["v"].dtype), 0, axis=1)}
            h = norm_apply(p["norm2"], y, cfg.norm)
            y = y + _cross_apply(p["cross_attn"], h, cr, cfg)
            h = norm_apply(p["norm3"], y, cfg.norm)
            y = y + mlp_apply(p["mlp"], h, cfg.act)
            return y, new_sc

        x, filled = jax.lax.scan(block, x,
                                 (params["decoder"], self_cache, cross))
        logits = self._logits(params, x[:, -1:, :])
        return logits, {"self": filled, "cross": cross}, S_dec

    def decode_step(self, params, cache, token, pos):
        cfg, rt = self.cfg, self.rt
        x = params["embed"].astype(rt.compute_dtype)[token]

        def block(carry, xs):
            y = carry
            p, sc, cr = xs
            h = norm_apply(p["norm1"], y, cfg.norm)
            mix, new_sc = attn_decode(p["self_attn"], h, sc, pos, cfg, rt)
            y = y + mix
            h = norm_apply(p["norm2"], y, cfg.norm)
            y = y + _cross_apply(p["cross_attn"], h, cr, cfg)
            h = norm_apply(p["norm3"], y, cfg.norm)
            y = y + mlp_apply(p["mlp"], h, cfg.act)
            return y, new_sc

        x, new_self = jax.lax.scan(
            block, x, (params["decoder"], cache["self"], cache["cross"]))
        logits = self._logits(params, x)
        return logits, {"self": new_self, "cross": cache["cross"]}


def _cross_apply(p, x, cross_kv, cfg):
    """Cross-attention against precomputed encoder K/V (decode/prefill)."""
    B, S, _ = x.shape
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]["w"].astype(x.dtype))
    if "b" in p["wq"]:
        q = q + p["wq"]["b"].astype(x.dtype)
    q = q.reshape(B, S, Hq, dh)
    k, v = cross_kv["k"], cross_kv["v"]
    group = Hq // Hkv
    qf = q.astype(jnp.float32) * (dh ** -0.5)
    kf = k.astype(jnp.float32)
    q5 = qf.reshape(B, S, Hkv, group, dh)
    s = jnp.einsum("bsngd,bknd->bsngk", q5, kf)
    pattr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bsngk,bknd->bsngd", pattr, v.astype(jnp.float32))
    out = out.reshape(B, S, Hq * dh).astype(x.dtype)
    return out @ p["wo"]["w"].astype(x.dtype)
