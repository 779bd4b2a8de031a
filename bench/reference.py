"""Plain float32 reference of the two model families and their training.

Written from the published descriptions and imports nothing of the
program:

- Mamba-2 (arXiv:2405.21060): in-projection to [z, x, B, C, dt], causal
  depthwise conv + SiLU over (x, B, C), the SSD scan computed as in the
  paper's minimal listing (block decomposition with ``segsum``), the D
  skip, gated RMSNorm, out-projection; residual pre-norm blocks, tied
  embeddings.
- Dense decoder (Llama 2, as TinyLlama): pre-norm blocks (RMSNorm or
  LayerNorm, as the configuration states), grouped-query attention with
  rotary embeddings on per-document positions, causal and same-document
  masking, SwiGLU MLP, untied head.
- Next-token cross entropy over the real vocabulary, mean over labelled
  tokens; global-norm clipping; AdamW with bias correction, as the
  configuration file's ``optimizer`` group states it.

Every matmul runs at ``precision="highest"``.  ``quant="fp8"`` rounds both
operands of every matmul to float8 e4m3 with a per-tensor scale first: the
control, one precision step below the bfloat16 the configurations state.

Gradients are taken over blocks of rows and summed, so that the reference
fits beside nothing else on one chip whatever the batch.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from weights import leaf_norms

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale (max maps to 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ein(quant: Optional[str]):
    def ein(spec, *ops):
        if quant == "fp8":
            ops = [_q8(o) for o in ops]
        return jnp.einsum(spec, *ops, precision=HI)
    return ein


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + scale)


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _norm(p, x, m, eps):
    if m["norm"] == "rmsnorm":
        return rmsnorm(x, p["scale"], eps)
    return layernorm(x, p["scale"], p["bias"], eps)


# ---------------------------------------------------------------- Mamba-2

def segsum(x):
    """x: (..., T) -> (..., T, T) with out[i, j] = sum x[j+1..i], -inf
    above the diagonal (the paper's stable segment sum)."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_minimal(X, logA, Bm, Cm, block, ein):
    """The Mamba-2 paper's minimal SSD.  X: (b, l, h, p) (already times
    dt), logA: (b, l, h), Bm/Cm: (b, l, n) shared by all heads."""
    b, l, h, p = X.shape
    c = l // block
    X = X.reshape(b, c, block, h, p)
    Bm = Bm.reshape(b, c, block, -1)
    Cm = Cm.reshape(b, c, block, -1)
    A = logA.reshape(b, c, block, h).transpose(0, 3, 1, 2)   # b h c l
    A_cs = jnp.cumsum(A, -1)
    Lmat = jnp.exp(segsum(A))                                 # b h c l s
    scores = ein("bcln,bcsn->bcls", Cm, Bm)
    Y_diag = ein("bcls,bhcls,bcshp->bclhp", scores, Lmat, X)
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)              # b h c l
    states = ein("bcln,bhcl,bclhp->bchpn", Bm, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    Y_off = ein("bcln,bchpn,bhcl->bclhp", Cm, states, jnp.exp(A_cs))
    return (Y_diag + Y_off).reshape(b, l, h, p)


def mamba2_mixer(p, x, m, ein):
    D = m["d_model"]
    Din = m["ssm_expand"] * D
    N, P, W = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv_width"]
    H = Din // P
    b, l, _ = x.shape
    zxbcdt = ein("bld,de->ble", x, p["in_proj"])
    z = zxbcdt[..., :Din]
    xbc = zxbcdt[..., Din:2 * Din + 2 * N]
    dt = zxbcdt[..., 2 * Din + 2 * N:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + l] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, Bm, Cm = xbc[..., :Din], xbc[..., Din:Din + N], xbc[..., Din + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # b l h
    logA = -dt * jnp.exp(p["A_log"])
    xh = xs.reshape(b, l, H, P)
    y = ssd_minimal(xh * dt[..., None], logA, Bm, Cm, m["ssm_chunk"], ein)
    y = (y + p["D_skip"][:, None] * xh).reshape(b, l, Din)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_scale"], m["norm_eps"])
    return ein("ble,ed->bld", y, p["out_proj"])


# ------------------------------------------------------------------ dense

def rope(x, pos, theta):
    """Rotary embedding, rotate-half convention over the whole head."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * freq           # b l d/2
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, seg, pos, m, ein):
    b, l, _ = x.shape
    H, Hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = ein("bld,de->ble", x, p["wq"]["w"]).reshape(b, l, H, dh)
    k = ein("bld,de->ble", x, p["wk"]["w"]).reshape(b, l, Hkv, dh)
    v = ein("bld,de->ble", x, p["wv"]["w"]).reshape(b, l, Hkv, dh)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    idx = jnp.arange(l)
    mask = (idx[:, None] >= idx[None, :])[None] & (
        seg[:, :, None] == seg[:, None, :])
    s = jnp.where(mask[:, None], s, NEG)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return ein("ble,ed->bld", o.reshape(b, l, H * dh), p["wo"]["w"])


def swiglu(p, x, ein):
    h = ein("bld,df->blf", x, p["wi"])
    g = ein("bld,df->blf", x, p["wg"])
    return ein("blf,fd->bld", h * jax.nn.silu(g), p["wo"])


# ------------------------------------------------------------------- model

def nll_sum(params, rows, m, quant=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum of next-token losses, number of labelled tokens) for rows
    {tokens, labels, segments, positions} of shape (b, l)."""
    ein = _ein(quant)
    eps = m["norm_eps"]
    x = params["embed"][rows["tokens"]]
    if quant == "fp8":
        x = _q8(x)

    @jax.checkpoint
    def layer(x, p):
        h = _norm(p["norm1"], x, m, eps)
        if m["pattern"][0] == "ssm":
            return x + mamba2_mixer(p["ssm"], h, m, ein), None
        x = x + attention(p["attn"], h, rows["segments"], rows["positions"],
                          m, ein)
        return x + swiglu(p["mlp"], _norm(p["norm2"], x, m, eps), ein), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["pos0"])
    x = _norm(params["final_norm"], x, m, eps)
    V = m["vocab_size"]
    head = (params["embed"][:V].T if m["tie_embeddings"]
            else params["lm_head"][:, :V])
    logits = ein("bld,dv->blv", x, head)
    labels = rows["labels"]
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(mask, lse - tgt, 0.0)), jnp.sum(mask)


def loss_and_grad(params, batch, m, quant=None, block_rows=1):
    """Mean loss over the batch and its gradient, summed over blocks of
    ``block_rows`` rows so that only one block's activations live."""
    n = batch["tokens"].shape[0]
    blocks = jax.tree.map(
        lambda a: a.reshape((n // block_rows, block_rows) + a.shape[1:]),
        batch)
    g_fn = jax.value_and_grad(
        lambda p, r: nll_sum(p, r, m, quant), has_aux=True)

    def body(carry, rows):
        tot, cnt, g = carry
        (s, c), gb = g_fn(params, rows)
        return (tot + s, cnt + c, jax.tree.map(jnp.add, g, gb)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (tot, cnt, g), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.int32(0), zero), blocks)
    cnt = jnp.maximum(cnt, 1).astype(jnp.float32)
    return tot / cnt, jax.tree.map(lambda x: x / cnt, g)


# --------------------------------------------------------------- optimizer

def lr_at(o: Dict, t):
    """Learning rate of update ``t`` (1-based), as the optimizer group
    states: linear warm-up ``min(1, (t + 1) / warmup_steps)`` times a cosine
    from 1 to ``min_lr_ratio`` over ``warmup_steps..total_steps``."""
    t = jnp.asarray(t, jnp.float32)
    warm = jnp.minimum(1.0, (t + 1) / o["warmup_steps"])
    frac = jnp.clip((t - o["warmup_steps"])
                    / (o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
    r = o["min_lr_ratio"]
    return o["lr"] * warm * (r + (1 - r) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))


def clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(o: Dict, params, grads, m1, m2, t):
    """One AdamW update ``t`` (1-based).  Weight decay applies to leaves of
    rank >= ``decay_min_rank`` as stored."""
    lr = lr_at(o, t)
    b1, b2 = o["b1"], o["b2"]
    m1 = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m1, grads)
    m2 = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, m2, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        d = (m / c1) / (jnp.sqrt(v / c2) + o["eps"])
        if p.ndim >= o["decay_min_rank"]:
            d = d + o["weight_decay"] * p
        return p - lr * d

    return jax.tree.map(upd, params, m1, m2), m1, m2


@functools.partial(jax.jit, static_argnames=("m", "o", "quant", "block_rows"),
                   donate_argnums=(0, 2, 3))
def train_step(params, batch, m1, m2, t, *, m, o, quant, block_rows):
    m, o = dict(m), dict(o)
    loss, g = loss_and_grad(params, batch, m, quant, block_rows)
    g = clip(g, o["grad_clip"])
    params, m1, m2 = adamw(o, params, g, m1, m2, t)
    return params, m1, m2, loss, leaf_norms(g)
