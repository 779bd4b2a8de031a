"""Per-architecture smoke tests: reduced same-family configs on CPU.

For each of the 10 assigned architectures: one forward + one train step
(grad + update) asserting output shapes and no NaNs, plus prefill/decode
consistency against the full forward (the serving path).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import RuntimeConfig, build_model

RT = RuntimeConfig(compute_dtype=jnp.float32, attn_impl="naive",
                   ssd_impl="xla", rglru_impl="xla", max_cache_len=64,
                   moe_group_size=16)
B, S = 2, 16


def _batch(cfg, key=1):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    tokens = jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": labels}
    if cfg.is_encoder_decoder or cfg.frontend == "audio":
        batch["frontend_embeds"] = jax.random.normal(
            ks[2], (B, S, cfg.d_model), jnp.float32) * 0.1
    elif cfg.frontend == "vision":
        batch["frontend_embeds"] = jax.random.normal(
            ks[2], (B, cfg.frontend_tokens, cfg.d_model), jnp.float32) * 0.1
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg, RT)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = model.forward(params, batch)
    S_total = S + (cfg.frontend_tokens if cfg.frontend == "vision" else 0)
    if cfg.is_encoder_decoder:
        S_total = S
    assert logits.shape == (B, S_total, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits[..., :cfg.vocab_size]).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_loss(arch):
    """One SGD step on a repeated batch must reduce the loss (and produce
    finite grads) — catches dead gradients and NaN paths per family."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg, RT)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(p):
        return model.loss(p, batch)[0]

    loss0, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss0))
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
    assert float(gnorm) > 0.0
    lr = 0.5 / max(float(gnorm), 1.0)
    params2 = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                           params, grads)
    loss1 = loss_fn(params2)
    assert float(loss1) < float(loss0), (float(loss0), float(loss1))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    if cfg.n_experts:
        # exact-match requires no capacity drops (see moe.py docstring)
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    model = build_model(cfg, RT)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    full = model.forward(params, batch)
    if cfg.is_encoder_decoder:
        lp, cache, pos = model.prefill(params, fe, tokens[:, :S - 1])
        lg, _ = model.decode_step(params, cache, tokens[:, S - 1:S],
                                  jnp.asarray(pos, jnp.int32))
        tgt_p, tgt_d = full[:, S - 2], full[:, S - 1]
    else:
        lp, cache, pos = model.prefill(params, tokens[:, :S - 1], fe)
        lg, _ = model.decode_step(params, cache, tokens[:, S - 1:S],
                                  jnp.asarray(pos, jnp.int32))
        tgt_p, tgt_d = full[:, -2], full[:, -1]
    np.testing.assert_allclose(np.asarray(lp[:, 0]), np.asarray(tgt_p),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(tgt_d),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "recurrentgemma-9b",
                                  "gemma2-9b", "gemma3-12b"])
def test_windowed_decode_ring_cache(arch):
    """Decode far past the window: ring cache must keep matching the full
    forward (the window bounds what attention sees either way)."""
    cfg = get_smoke_config(arch)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    # tiny window so S exceeds it; global (non-windowed) layers still need
    # max_cache_len >= S_long, ring layers are bounded by the window anyway
    has_global = "global" in cfg.pattern
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=8)
    else:
        cfg = dataclasses.replace(cfg, local_window=8)
    # all-windowed archs: L=8 ring actually wraps; global layers need >= S
    rt = RT.with_(max_cache_len=32 if has_global else 8)
    model = build_model(cfg, rt)
    params = model.init(jax.random.PRNGKey(0))
    S_long = 24
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S_long), 0,
                                cfg.vocab_size)
    full = model.forward(params, {"tokens": tokens, "labels": tokens})
    cache = model.init_cache(B)
    errs = []
    for t in range(S_long):
        lg, cache = model.decode_step(params, cache, tokens[:, t:t + 1],
                                      jnp.asarray(t, jnp.int32))
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 2e-3, errs


def test_configs_match_assignment():
    """The exact numbers from the assignment block."""
    q = get_config("qwen2.5-32b")
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.d_ff,
            q.vocab_size, q.qkv_bias) == (64, 5120, 40, 8, 27648, 152064, True)
    st = get_config("stablelm-1.6b")
    assert (st.n_layers, st.d_model, st.n_heads, st.n_kv_heads, st.d_ff,
            st.vocab_size) == (24, 2048, 32, 32, 5632, 100352)
    g3 = get_config("gemma3-12b")
    assert (g3.n_layers, g3.d_model, g3.n_heads, g3.n_kv_heads, g3.d_ff,
            g3.vocab_size) == (48, 3840, 16, 8, 15360, 262144)
    assert g3.pattern.count("local") == 5 and g3.pattern.count("global") == 1
    g2 = get_config("gemma2-9b")
    assert (g2.n_layers, g2.d_model, g2.n_heads, g2.n_kv_heads, g2.d_ff,
            g2.vocab_size) == (42, 3584, 16, 8, 14336, 256000)
    assert g2.attn_softcap == 50.0 and g2.final_softcap == 30.0
    ar = get_config("arctic-480b")
    assert (ar.n_layers, ar.d_model, ar.n_heads, ar.n_kv_heads, ar.d_ff,
            ar.vocab_size) == (35, 7168, 56, 8, 4864, 32000)
    assert (ar.n_experts, ar.experts_per_token, ar.dense_residual) == (128, 2, True)
    mx = get_config("mixtral-8x22b")
    assert (mx.n_layers, mx.d_model, mx.n_heads, mx.n_kv_heads,
            mx.vocab_size) == (56, 6144, 48, 8, 32768)
    assert (mx.n_experts, mx.experts_per_token, mx.moe_d_ff) == (8, 2, 16384)
    assert mx.sliding_window is not None
    sm = get_config("seamless-m4t-medium")
    assert (sm.n_layers, sm.d_model, sm.n_heads, sm.n_kv_heads, sm.d_ff,
            sm.vocab_size) == (12, 1024, 16, 16, 4096, 256206)
    assert sm.is_encoder_decoder
    rg = get_config("recurrentgemma-9b")
    assert (rg.n_layers, rg.d_model, rg.n_heads, rg.n_kv_heads, rg.d_ff,
            rg.vocab_size) == (38, 4096, 16, 1, 12288, 256000)
    assert rg.pattern == ("rec", "rec", "local")
    m2 = get_config("mamba2-1.3b")
    assert (m2.n_layers, m2.d_model, m2.vocab_size, m2.ssm_state) == (
        48, 2048, 50280, 128)
    iv = get_config("internvl2-2b")
    assert (iv.n_layers, iv.d_model, iv.n_heads, iv.n_kv_heads, iv.d_ff,
            iv.vocab_size) == (24, 2048, 16, 8, 8192, 92553)


def test_param_counts_in_expected_band():
    """6ND accounting sanity: totals should be near the names on the tin."""
    expect = {
        "qwen2.5-32b": (28e9, 40e9),
        "stablelm-1.6b": (1.2e9, 2.2e9),
        "gemma3-12b": (9e9, 15e9),
        "gemma2-9b": (8e9, 12e9),
        "arctic-480b": (420e9, 540e9),
        "mixtral-8x22b": (120e9, 160e9),
        "mamba2-1.3b": (1.0e9, 1.6e9),
        "recurrentgemma-9b": (7e9, 12e9),
        "internvl2-2b": (1.6e9, 2.6e9),
        "seamless-m4t-medium": (0.7e9, 1.6e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).n_params()
        assert lo < n < hi, (arch, n)
