"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, so that the plain reference can make the
same ones without taking anything from the program.  The tree is laid out
as the program's decoder stores it (a leading layer axis on every block
leaf); the harness checks it against the program's own abstract tree.

Initialisation follows common practice for the two families: normal 0.02
for embeddings and input projections, 0.02 / sqrt(2 n_layers) for the
projections back into the residual stream, unit LayerNorm scales, and
Mamba-2's published A and dt initialisation (A in [1, 16], dt log-uniform
in [1e-3, 1e-1], dt_bias the softplus inverse of dt).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

STD = 0.02


def padded_vocab(m: Dict) -> int:
    mult = 256
    return -(-m["vocab_size"] // mult) * mult


def root_key(seed: int) -> jax.Array:
    """A key for any seed up to 2**63: PRNGKey alone keeps 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _norm(m: Dict, d: int, L: int) -> Dict:
    if m["norm"] == "rmsnorm":       # applied as (1 + scale)
        return {"scale": jnp.zeros((L, d) if L else (d,), jnp.float32)}
    shape = (L, d) if L else (d,)
    return {"scale": jnp.ones(shape, jnp.float32),
            "bias": jnp.zeros(shape, jnp.float32)}


class _Keys:
    def __init__(self, key):
        self.key, self.n = key, 0

    def __call__(self):
        self.n += 1
        return jax.random.fold_in(self.key, self.n)


def _ssm_block(m: Dict, L: int, k: _Keys) -> Dict:
    D = m["d_model"]
    Din = m["ssm_expand"] * D
    N, P, W = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv_width"]
    H = Din // P
    conv = Din + 2 * N
    out_std = STD / (2 * L) ** 0.5
    bound = W ** -0.5
    dt = jnp.exp(jax.random.uniform(k(), (L, H), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": jax.random.normal(k(), (L, D, 2 * Din + 2 * N + H)) * STD,
        "conv_w": jax.random.uniform(k(), (L, W, conv), jnp.float32,
                                     -bound, bound),
        "conv_b": jax.random.uniform(k(), (L, conv), jnp.float32,
                                     -bound, bound),
        "A_log": jnp.log(jax.random.uniform(k(), (L, H), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D_skip": jnp.ones((L, H), jnp.float32),
        "norm_scale": jnp.zeros((L, Din), jnp.float32),
        "out_proj": jax.random.normal(k(), (L, Din, D)) * out_std,
    }


def _attn_block(m: Dict, L: int, k: _Keys) -> Dict:
    D, F = m["d_model"], m["d_ff"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    out_std = STD / (2 * L) ** 0.5

    def w(shape, std):
        return {"w": jax.random.normal(k(), (L,) + shape) * std}

    return {
        "attn": {"wq": w((D, Hq * dh), STD), "wk": w((D, Hkv * dh), STD),
                 "wv": w((D, Hkv * dh), STD), "wo": w((Hq * dh, D), out_std)},
        "norm2": _norm(m, D, L),
        "mlp": {"wi": w((D, F), STD)["w"], "wg": w((D, F), STD)["w"],
                "wo": w((F, D), out_std)["w"]},
    }


def make_params(m: Dict, key: jax.Array) -> Dict[str, Any]:
    """The parameter tree of model config ``m`` (the config file's
    ``model`` group), float32."""
    if list(m["pattern"]) not in (["ssm"], ["attn"]):
        raise ValueError(f"no weight layout for pattern {m['pattern']}")
    k = _Keys(key)
    D, L, V = m["d_model"], m["n_layers"], padded_vocab(m)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(k(), (V, D)) * STD,
        "final_norm": _norm(m, D, 0),
    }
    block: Dict[str, Any] = {"norm1": _norm(m, D, L)}
    if m["pattern"][0] == "ssm":
        block["ssm"] = _ssm_block(m, L, k)
    else:
        block.update(_attn_block(m, L, k))
    params["blocks"] = {"pos0": block}
    if not m["tie_embeddings"]:
        params["lm_head"] = jax.random.normal(k(), (D, V)) * STD
    return params


def is_stacked(path) -> bool:
    """Leaves under ``blocks`` carry a leading layer axis."""
    return getattr(path[0], "key", None) == "blocks"


def leaf_norms(tree) -> jnp.ndarray:
    """L2 norm of every leaf, one per layer for stacked leaves, in a fixed
    order: a flat float32 vector."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if is_stacked(path):
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(out)
