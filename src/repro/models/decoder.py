"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layer structure is a repeating *superblock* given by ``cfg.pattern`` (e.g.
gemma3: 5 local + 1 global; recurrentgemma: rec, rec, local).  Superblocks
are ``jax.lax.scan``-stacked (params carry a leading repeat dim) so HLO size
and compile time are O(1) in depth; remainder layers (38 = 12x3 + 2) live in
an unscanned ``tail``.  Remat policy wraps the scan body.

Modality frontends are STUBS per the assignment: ``vlm``/``audio`` inputs
arrive as precomputed patch/frame embeddings that occupy the sequence prefix.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import attn_apply, attn_decode, attn_init, init_kv_cache
from .common import (Initializer, RuntimeConfig, mlp_apply, mlp_init,
                     norm_apply, norm_init, softcap)
from .moe import moe_apply, moe_apply_shardmap, moe_decode, moe_init
from .recurrent_block import init_rec_cache, rec_apply, rec_decode, rec_init
from .ssm_block import init_ssm_cache, ssm_apply, ssm_decode, ssm_init

__all__ = ["DecoderLM"]

PyTree = Any


def _block_window(kind: str, cfg: ModelConfig) -> Optional[int]:
    if kind == "local":
        return cfg.local_window
    if kind in ("attn", "global"):
        return cfg.sliding_window     # mixtral SWA; None for full attention
    return None


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(f"unknown remat mode {mode!r}")


class DecoderLM:
    """Functional decoder-only LM.  All methods are jit/pjit-compatible."""

    def __init__(self, cfg: ModelConfig, rt: RuntimeConfig):
        self.cfg = cfg
        self.rt = rt
        self.pattern = cfg.pattern
        self.k = len(self.pattern)
        self.n_repeats = cfg.n_layers // self.k
        self.n_tail = cfg.n_layers % self.k

    # ------------------------------------------------------------------ init

    def _init_block(self, ini: Initializer, kind: str) -> Dict:
        cfg, dtype = self.cfg, self.rt.param_dtype
        D = cfg.d_model
        p: Dict[str, Any] = {"norm1": norm_init(ini, D, cfg.norm, dtype)}
        if kind == "ssm":
            p["ssm"] = ssm_init(ini, cfg, dtype)
            return p
        if kind == "rec":
            p["rec"] = rec_init(ini, cfg, dtype)
        else:
            p["attn"] = attn_init(ini, cfg, dtype)
        if cfg.post_norms:
            p["post_norm1"] = norm_init(ini, D, cfg.norm, dtype)
        p["norm2"] = norm_init(ini, D, cfg.norm, dtype)
        if cfg.n_experts:
            p["moe"] = moe_init(ini, cfg, dtype)
            if cfg.dense_residual:
                p["mlp"] = mlp_init(ini, D, cfg.d_ff, dtype)
        else:
            p["mlp"] = mlp_init(ini, D, cfg.d_ff, dtype)
        if cfg.post_norms:
            p["post_norm2"] = norm_init(ini, D, cfg.norm, dtype)
        return p

    def _init_superblock(self, key) -> Dict:
        ini = Initializer(key)
        return {f"pos{j}": self._init_block(ini, kind)
                for j, kind in enumerate(self.pattern)}

    def init(self, key) -> PyTree:
        cfg, dtype = self.cfg, self.rt.param_dtype
        k_embed, k_blocks, k_tail, k_head = jax.random.split(key, 4)
        ini = Initializer(k_embed)
        params: Dict[str, Any] = {
            "embed": ini.normal((cfg.padded_vocab, cfg.d_model), 1.0, dtype),
            "final_norm": norm_init(ini, cfg.d_model, cfg.norm, dtype),
        }
        if self.n_repeats:
            keys = jax.random.split(k_blocks, self.n_repeats)
            params["blocks"] = jax.vmap(self._init_superblock)(keys)
        if self.n_tail:
            ini_t = Initializer(k_tail)
            params["tail"] = {
                f"tail{j}": self._init_block(ini_t, self.pattern[j])
                for j in range(self.n_tail)}
        if not cfg.tie_embeddings:
            ini_h = Initializer(k_head)
            params["lm_head"] = ini_h.normal(
                (cfg.d_model, cfg.padded_vocab), cfg.d_model ** -0.5, dtype)
        return params

    def init_abstract(self) -> PyTree:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------ fwd

    def _apply_block(self, kind: str, p: Dict, x, *, positions, segments):
        cfg, rt = self.cfg, self.rt
        h = norm_apply(p["norm1"], x, cfg.norm)
        if kind == "ssm":
            with jax.named_scope("ssm"):
                return x + ssm_apply(p["ssm"], h, cfg, rt)
        if kind == "rec":
            with jax.named_scope("rec"):
                mix = rec_apply(p["rec"], h, cfg, rt)
        else:
            mix = attn_apply(
                p["attn"], h, cfg, rt, positions=positions,
                causal=True, window=_block_window(kind, cfg),
                segments=segments)
        if cfg.post_norms:
            mix = norm_apply(p["post_norm1"], mix, cfg.norm)
        x = x + mix
        h2 = norm_apply(p["norm2"], x, cfg.norm)
        with jax.named_scope("mlp"):
            if cfg.n_experts:
                moe_fn = (moe_apply_shardmap if rt.moe_impl == "shard_map"
                          else moe_apply)
                y, _aux = moe_fn(p["moe"], h2, cfg, rt)
                if cfg.dense_residual:
                    y = y + mlp_apply(p["mlp"], h2, cfg.act)
            else:
                y = mlp_apply(p["mlp"], h2, cfg.act)
        if cfg.post_norms:
            y = norm_apply(p["post_norm2"], y, cfg.norm)
        return self.rt.hidden(x + y)

    @jax.named_scope("embed")
    def _embed(self, params, tokens, frontend_embeds):
        cfg = self.cfg
        x = params["embed"].astype(self.rt.compute_dtype)[tokens]
        if cfg.scale_embed:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if frontend_embeds is not None:
            fe = frontend_embeds.astype(x.dtype)
            x = jnp.concatenate([fe, x], axis=1)
        return self.rt.hidden(x)

    @jax.named_scope("layers")
    def _trunk(self, params, x, *, positions, segments):
        """Scanned superblocks + tail."""

        def superblock(carry, layer_params):
            y = carry
            for j, kind in enumerate(self.pattern):
                y = self._apply_block(kind, layer_params[f"pos{j}"], y,
                                      positions=positions, segments=segments)
            return y, None

        if self.n_repeats:
            body = _remat(superblock, self.rt.remat)
            x, _ = jax.lax.scan(body, x, params["blocks"])
        for j in range(self.n_tail):
            x = self._apply_block(self.pattern[j], params["tail"][f"tail{j}"],
                                  x, positions=positions, segments=segments)
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        x = norm_apply(params["final_norm"], x, cfg.norm)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head.astype(x.dtype)
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
        # mask padded vocab entries (elementwise -> stays vocab-sharded)
        if cfg.padded_vocab != cfg.vocab_size:
            iota = jax.lax.broadcasted_iota(
                jnp.int32, (cfg.padded_vocab,), 0)
            logits = jnp.where(iota < cfg.vocab_size, logits, -1e30)
        return self.rt.logits_constraint(logits)

    def forward(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Training/eval forward -> fp32 logits (B, S_total, V_pad)."""
        return self._logits(params, self._hidden(params, batch))

    def _hidden(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Embedding and trunk -> the last layer's output (B, S_total, D)."""
        tokens = batch["tokens"]
        B, S_text = tokens.shape
        positions = batch.get("positions")
        segments = batch.get("segments")
        fe = batch.get("frontend_embeds")
        x = self._embed(params, tokens, fe)
        S_total = x.shape[1]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S_total), (B, S_total))
        return self._trunk(params, x, positions=positions, segments=segments)

    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Next-token cross entropy; labels < 0 are masked."""
        x = self._hidden(params, batch)
        with jax.named_scope("head_loss"):
            logits = self._logits(params, x)
            labels = batch["labels"]
            # frontend prefix positions produce logits we do not supervise
            S_text = labels.shape[1]
            logits = logits[:, -S_text:, :]
            return xent_loss(logits, labels)

    # ------------------------------------------------------------------ serve

    def _init_block_cache(self, kind: str, batch: int) -> Dict:
        cfg, rt = self.cfg, self.rt
        dtype = rt.compute_dtype
        if kind == "ssm":
            return init_ssm_cache(cfg, batch, dtype)
        if kind == "rec":
            return init_rec_cache(cfg, batch, dtype)
        window = _block_window(kind, cfg)
        length = rt.max_cache_len
        if window is not None:
            length = min(length, _cache_round(window))
        return init_kv_cache(cfg, batch, length, dtype)

    def init_cache(self, batch: int) -> PyTree:
        """Allocate the decode cache (window-bounded layers allocate only
        the window)."""
        def stack(make):
            return jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[make() for _ in range(self.n_repeats)])

        cache: Dict[str, Any] = {}
        if self.n_repeats:
            cache["blocks"] = {
                f"pos{j}": stack(functools.partial(
                    self._init_block_cache, kind, batch))
                for j, kind in enumerate(self.pattern)}
        for j in range(self.n_tail):
            cache[f"tail{j}"] = self._init_block_cache(self.pattern[j], batch)
        return cache

    def _decode_block(self, kind: str, p, x_t, cache, pos,
                      context_start=None):
        cfg, rt = self.cfg, self.rt
        h = norm_apply(p["norm1"], x_t, cfg.norm)
        if kind == "ssm":
            y, new_cache = ssm_decode(p["ssm"], h, cache, cfg, rt)
            return x_t + y, new_cache
        if kind == "rec":
            mix, new_cache = rec_decode(p["rec"], h, cache, cfg, rt)
        else:
            window = _block_window(kind, cfg)
            mix, new_cache = attn_decode(
                p["attn"], h, cache, pos, cfg, rt, window=window,
                context_start=context_start)
        if cfg.post_norms:
            mix = norm_apply(p["post_norm1"], mix, cfg.norm)
        x = x_t + mix
        h2 = norm_apply(p["norm2"], x, cfg.norm)
        if cfg.n_experts:
            y = moe_decode(p["moe"], h2, cfg, rt)
            if cfg.dense_residual:
                y = y + mlp_apply(p["mlp"], h2, cfg.act)
        else:
            y = mlp_apply(p["mlp"], h2, cfg.act)
        if cfg.post_norms:
            y = norm_apply(p["post_norm2"], y, cfg.norm)
        return x + y, new_cache

    def decode_step(self, params, cache, token, pos, context_start=None):
        """token: (B, 1) int32; pos: scalar int32.  Returns (logits, cache).

        For window-bounded KV layers the cache is a ring buffer of the
        window length; ``pos`` is the absolute position (RoPE uses it).
        ``context_start``: optional (B,) first-valid-slot (left-padded
        serving waves).
        """
        x = self._embed(params, token, None)
        if self.cfg.scale_embed:
            pass  # already applied in _embed

        new_cache: Dict[str, Any] = {}
        if self.n_repeats:
            def body(carry, xs):
                y = carry
                layer_params, layer_cache = xs
                updates = {}
                for j, kind in enumerate(self.pattern):
                    y, updates[f"pos{j}"] = self._decode_block(
                        kind, layer_params[f"pos{j}"], y,
                        layer_cache[f"pos{j}"], pos, context_start)
                return y, updates

            x, new_cache["blocks"] = jax.lax.scan(
                body, x, (params["blocks"], cache["blocks"]))
        for j in range(self.n_tail):
            x, new_cache[f"tail{j}"] = self._decode_block(
                self.pattern[j], params["tail"][f"tail{j}"], x,
                cache[f"tail{j}"], pos, context_start)
        logits = self._logits(params, x)
        return logits, new_cache

    def prefill(self, params, tokens, frontend_embeds=None, positions=None,
                segments=None):
        """Run the full prompt, return (last-position logits, cache, length).

        ``segments`` enables left-padded batched prompts (pad tokens get a
        different segment id, so content never attends padding).
        Implemented as forward + cache construction via decode-compatible
        state extraction: for attention layers we recompute K/V (cheap
        relative to the prompt forward) and write them into the ring cache.
        """
        B, S_text = tokens.shape
        x = self._embed(params, tokens, frontend_embeds)
        S = x.shape[1]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        cache = self.init_cache(B)

        filled: Dict[str, Any] = {}
        if self.n_repeats:
            def body(carry, xs):
                y = carry
                layer_params, layer_cache = xs
                updates = {}
                for j, kind in enumerate(self.pattern):
                    y, updates[f"pos{j}"] = self._prefill_block(
                        kind, layer_params[f"pos{j}"], y,
                        layer_cache[f"pos{j}"], positions, segments)
                return y, updates

            x, filled["blocks"] = jax.lax.scan(
                body, x, (params["blocks"], cache["blocks"]))
        for j in range(self.n_tail):
            x, filled[f"tail{j}"] = self._prefill_block(
                self.pattern[j], params["tail"][f"tail{j}"], x,
                cache[f"tail{j}"], positions, segments)
        logits = self._logits(params, x[:, -1:, :])
        return logits, filled, S

    def _prefill_block(self, kind: str, p, x, cache, positions,
                       segments=None):
        cfg, rt = self.cfg, self.rt
        h = norm_apply(p["norm1"], x, cfg.norm)
        if kind == "ssm":
            y, state = ssm_apply(p["ssm"], h, cfg, rt, return_state=True)
            state["conv"] = state["conv"].astype(cache["conv"].dtype)
            return x + y, state
        if kind == "rec":
            mix, state = rec_apply(p["rec"], h, cfg, rt, return_state=True)
            state["conv"] = state["conv"].astype(cache["conv"].dtype)
            new_cache = state
        else:
            window = _block_window(kind, cfg)
            mix, (k, v) = attn_apply(
                p["attn"], h, cfg, rt, positions=positions, causal=True,
                window=window, segments=segments, return_kv=True)
            new_cache = _write_ring(cache, k, v)
        if cfg.post_norms:
            mix = norm_apply(p["post_norm1"], mix, cfg.norm)
        x = x + mix
        h2 = norm_apply(p["norm2"], x, cfg.norm)
        if cfg.n_experts:
            moe_fn = (moe_apply_shardmap if rt.moe_impl == "shard_map"
                      else moe_apply)
            y, _ = moe_fn(p["moe"], h2, cfg, rt)
            if cfg.dense_residual:
                y = y + mlp_apply(p["mlp"], h2, cfg.act)
        else:
            y = mlp_apply(p["mlp"], h2, cfg.act)
        if cfg.post_norms:
            y = norm_apply(p["post_norm2"], y, cfg.norm)
        return x + y, new_cache


def xent_loss(logits: jnp.ndarray, labels: jnp.ndarray):
    """Sharding-friendly masked cross entropy.

    Never gathers the (B, S, V) logits: the label logit is extracted with a
    fused one-hot reduction (partial per vocab shard + small all-reduce)
    instead of ``take_along_axis`` (which forces GSPMD to all-gather the
    full vocab axis — measured 100+ GiB of wire traffic on the 16x16 mesh).
    """
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(safe, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.sum(logits * onehot, axis=-1)
    nll = lse - label_logit
    denom = jnp.maximum(mask.sum(), 1)
    loss = jnp.where(mask, nll, 0.0).sum() / denom
    return loss, {"loss": loss, "n_tokens": denom}


def _cache_round(n: int, m: int = 128) -> int:
    return ((n + m - 1) // m) * m


def _write_ring(cache, k, v):
    """Write prompt K/V into the (possibly window-sized ring) cache."""
    L = cache["k"].shape[1]
    S = k.shape[1]
    if S >= L:
        # keep the last L positions; ring phase = S % L so that absolute
        # position p lands at slot p % L.
        tail_k, tail_v = k[:, -L:], v[:, -L:]
        shift = (S % L)
        tail_k = jnp.roll(tail_k, shift, axis=1)
        tail_v = jnp.roll(tail_v, shift, axis=1)
        return {"k": tail_k.astype(cache["k"].dtype),
                "v": tail_v.astype(cache["v"].dtype)}
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
    return {"k": ck, "v": cv}
