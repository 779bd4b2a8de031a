"""Model FLOPs per trained token, from a configuration's ``model`` group.

Forward and backward count as three forwards; the recompute of
``remat`` is not counted.  A multiply-add is two FLOPs.

- ``ssm`` (Mamba-2): the in- and out-projections, the LM head over the
  padded vocabulary (tied or not, it is one matmul), and the SSD
  chunked-scan terms at the configured chunk: the chunk's C.B^T scores,
  their product with x, the chunk states x^T.B and the state read-out C.h.
  The depthwise conv and elementwise work are not counted.
- ``attn`` (dense decoder): the Q, K, V and O projections, the gated MLP,
  the LM head over the padded vocabulary, and causal attention (Q.K^T and
  P.V) over the whole packed sequence: a token attends to all tokens before
  it in its row.  Same-document masking is not counted as saved work; the
  program computes every block today.
"""

from __future__ import annotations

from typing import Dict

from weights import padded_vocab


def ssm_layer(m: Dict, seq_len: int) -> float:
    D = m["d_model"]
    Din = m["ssm_expand"] * D
    N, P = m["ssm_state"], m["ssm_head_dim"]
    H = Din // P
    c = min(m["ssm_chunk"], seq_len)
    proj = 2 * D * (2 * Din + 2 * N + H) + 2 * Din * D
    ssd = 2 * c * N + 2 * c * Din + 2 * Din * N + 2 * Din * N
    return proj + ssd


def attn_layer(m: Dict, seq_len: int) -> float:
    D, F = m["d_model"], m["d_ff"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    proj = 2 * D * (Hq + 2 * Hkv) * dh + 2 * Hq * dh * D
    mlp = 3 * 2 * D * F
    keys = (seq_len + 1) / 2                      # mean causal context
    attn = 2 * 2 * Hq * dh * keys
    return proj + mlp + attn


LAYERS = {"ssm": ssm_layer, "attn": attn_layer}


def per_token(m: Dict, seq_len: int) -> float:
    """Training FLOPs per token: 3 x forward."""
    layers = sum(LAYERS[kind](m, seq_len) for kind in m["pattern"])
    layers *= m["n_layers"] / len(m["pattern"])
    head = 2 * m["d_model"] * padded_vocab(m)
    return 3.0 * (layers + head)
