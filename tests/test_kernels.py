"""Per-kernel validation: Pallas (interpret=True) and XLA paths vs the
pure-jnp oracles, with hypothesis-driven shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.kernels.flash_attention import attention_reference, flash_attention
from repro.kernels.flash_attention.ops import _Blocks
from repro.kernels.rglru import rglru, rglru_reference, rglru_step
from repro.kernels.ssd import ssd, ssd_reference, ssd_step

IMPLS = ["xla", "pallas_interpret"]


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(
        atol=3e-4, rtol=3e-4)


def _assert_close(a, b, dtype):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def _qkv(key, B, Sq, Sk, Hq, Hkv, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", [
    # (B, Sq, Sk, Hq, Hkv, D, causal, window, softcap)
    (1, 128, 128, 4, 4, 32, True, None, None),     # MHA causal
    (2, 128, 128, 8, 2, 32, True, None, None),     # GQA
    (1, 256, 256, 4, 1, 64, True, None, None),     # MQA
    (2, 128, 128, 4, 2, 32, True, 64, None),       # sliding window
    (1, 128, 128, 4, 2, 32, True, None, 30.0),     # softcap (gemma2)
    (1, 128, 128, 4, 2, 32, False, None, None),    # bidirectional (encoder)
    (2, 128, 128, 4, 2, 32, True, 32, 50.0),       # window + softcap
])
def test_flash_matches_reference(impl, case):
    B, Sq, Sk, Hq, Hkv, D, causal, window, softcap = case
    q, k, v = _qkv(jax.random.PRNGKey(0), B, Sq, Sk, Hq, Hkv, D, jnp.float32)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, impl=impl, block_q=64, block_k=64)
    _assert_close(out, ref, jnp.float32)


@pytest.mark.parametrize("impl", IMPLS)
def test_flash_packed_segments(impl):
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(1), B, S, S, Hq, Hkv, D, jnp.float32)
    segs = jnp.cumsum(
        (jax.random.uniform(jax.random.PRNGKey(2), (B, S)) < 0.02), axis=1
    ).astype(jnp.int32)
    ref = attention_reference(q, k, v, causal=True, q_segments=segs,
                              kv_segments=segs)
    out = flash_attention(q, k, v, causal=True, q_segments=segs,
                          kv_segments=segs, impl=impl, block_q=64, block_k=64)
    _assert_close(out, ref, jnp.float32)


@pytest.mark.parametrize("impl", IMPLS)
def test_flash_bf16(impl):
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 128, 128, 4, 2, 64, jnp.bfloat16)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, impl=impl,
                          block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    _assert_close(out, ref, jnp.bfloat16)


@pytest.mark.parametrize("impl", IMPLS)
def test_flash_q_offset_decode_chunk(impl):
    """Attention for a q chunk positioned mid-sequence (chunked prefill)."""
    B, Sq, Sk, Hq, Hkv, D = 1, 64, 256, 4, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(4), B, Sq, Sk, Hq, Hkv, D, jnp.float32)
    off = 128
    ref = attention_reference(q, k, v, causal=True, q_offset=off)
    out = flash_attention(q, k, v, causal=True, q_offset=off, impl=impl,
                          block_q=32, block_k=64)
    _assert_close(out, ref, jnp.float32)


_GRAD_CASES = {
    # (B, Sq, Sk, Hq, Hkv, D, causal, window, softcap, segments, q_offset,
    #  block_q, block_k, dtype)
    "mha": (1, 128, 128, 4, 4, 32, True, None, None, False, 0, 64, 64,
            jnp.float32),
    "gqa": (2, 128, 128, 8, 2, 32, True, None, None, False, 0, 64, 64,
            jnp.float32),
    "mqa": (1, 256, 256, 4, 1, 64, True, None, None, False, 0, 64, 64,
            jnp.float32),
    "window": (2, 128, 128, 4, 2, 32, True, 64, None, False, 0, 64, 64,
               jnp.float32),
    "softcap": (1, 128, 128, 4, 2, 32, True, None, 30.0, False, 0, 64, 64,
                jnp.float32),
    "bidirectional": (1, 128, 128, 4, 2, 32, False, None, None, False, 0,
                      64, 64, jnp.float32),
    "window_softcap": (2, 128, 128, 4, 2, 32, True, 32, 50.0, False, 0, 64,
                       64, jnp.float32),
    "segments": (2, 256, 256, 4, 2, 32, True, None, None, True, 0, 64, 64,
                 jnp.float32),
    "q_offset": (1, 64, 256, 4, 2, 32, True, None, None, False, 128, 32, 64,
                 jnp.float32),
    "bf16": (2, 128, 128, 4, 2, 64, True, None, None, True, 0, 64, 64,
             jnp.bfloat16),
    # A 4 x 2 grid, as in the train step's default blocks at S = 2048.
    "rect_blocks": (2, 256, 256, 4, 2, 32, True, None, None, True, 0, 64,
                    128, jnp.float32),
    # The same grid with products at bfloat16.
    "rect_blocks_bf16": (2, 256, 256, 4, 2, 32, True, None, None, True, 0,
                         64, 128, jnp.bfloat16),
    # The window leaves out pairs below the diagonal as well as above it.
    "window_rect": (2, 256, 256, 4, 2, 32, True, 48, None, False, 0, 32, 64,
                    jnp.float32),
    "q_offset_rect": (1, 128, 256, 4, 2, 32, True, None, None, False, 128,
                      32, 64, jnp.float32),
    # The last q block's window lies wholly past the last key.
    "dead_q_block": (2, 256, 128, 4, 2, 32, True, 32, None, False, 0, 64, 64,
                     jnp.float32),
}


@pytest.mark.parametrize("case", list(_GRAD_CASES.values()),
                         ids=list(_GRAD_CASES))
def test_flash_xla_grad_matches_reference(case):
    """The chunked path's forward and its own backward against the oracle
    and autodiff of it, with several q and kv blocks so the block loops
    run."""
    (B, Sq, Sk, Hq, Hkv, D, causal, window, softcap, segments, q_offset,
     block_q, block_k, dtype) = case
    q, k, v = _qkv(jax.random.PRNGKey(6), B, Sq, Sk, Hq, Hkv, D, dtype)
    kw = dict(causal=causal, window=window, softcap=softcap,
              q_offset=q_offset)
    if segments:
        segs = jnp.cumsum(
            (jax.random.uniform(jax.random.PRNGKey(7), (B, Sq)) < 0.02),
            axis=1).astype(jnp.int32)
        kw.update(q_segments=segs, kv_segments=segs)
    d_out = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * d_out)

    def attn(q, k, v):
        return flash_attention(q, k, v, impl="xla", block_q=block_q,
                               block_k=block_k, **kw)

    grads = jax.jit(jax.grad(loss(attn), (0, 1, 2)))(q, k, v)
    refs = jax.jit(jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, **kw)), (0, 1, 2)))(q, k, v)
    assert Sq // block_q > 1 and Sk // block_k > 1
    _assert_close(jax.jit(attn)(q, k, v), attention_reference(q, k, v, **kw),
                  dtype)
    for g, r in zip(grads, refs):
        assert g.dtype == dtype
        _assert_close(g, r, dtype)


def test_flash_xla_vjp_keeps_no_score_block():
    """What the forward keeps for the backward is q, k, v, the output and a
    logsumexp per query row: no residual has a score block's B·Hq·bq·bk
    elements (here more than q's), let alone a stack of them."""
    B, S, Hq, Hkv, D, bq, bk = 1, 256, 4, 2, 16, 64, 128
    q, k, v = _qkv(jax.random.PRNGKey(9), B, S, S, Hq, Hkv, D, jnp.float32)
    segs = jnp.zeros((B, S), jnp.int32)
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, q_segments=segs, kv_segments=segs,
        impl="xla", block_q=bq, block_k=bk), q, k, v)
    sizes = [x.size for x in jax.tree.leaves(vjp)]
    assert sizes and max(sizes) < B * Hq * bq * bk, sizes


def _dot_generals(jaxpr):
    """Every dot_general of a jaxpr and of the jaxprs its equations hold
    (scan bodies, custom-VJP calls, nested jits)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _dot_generals(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_xla_products_take_the_input_dtype(dtype):
    """Each product of the chunked path and of its backward (QK^T and PV,
    then the recomputed QK^T, dO V^T, dS K, dS^T Q and P^T dO) takes both
    operands at the input dtype, the score-sized P and dS among them, and
    accumulates in float32: at bfloat16 no operand is float32, and at
    float32 every one is, as before the rule."""
    B, S, Hq, Hkv, D, bq, bk = 2, 256, 4, 2, 32, 64, 128
    q, k, v = _qkv(jax.random.PRNGKey(11), B, S, S, Hq, Hkv, D, dtype)
    segs = jnp.zeros((B, S), jnp.int32).at[:, S // 3:].set(1)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, q_segments=segs, kv_segments=segs,
            impl="xla", block_q=bq, block_k=bk).astype(jnp.float32))

    dots = list(_dot_generals(
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr))
    assert len(dots) == 7
    score = B * Hq * bq * bk
    sizes = {x.aval.size for eqn in dots for x in eqn.invars}
    assert score in sizes and B * bq * Hq * D in sizes
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [jnp.dtype(dtype)] * 2
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


def _exhaustive_live_pairs(spec, n_q, n_k):
    return [(qi, ki) for qi in range(n_q) for ki in range(n_k)
            if bool(spec.mask(qi, ki, None, None).any())]


@pytest.mark.parametrize("bq, bk", [(32, 64), (64, 32), (64, 128)])
@pytest.mark.parametrize("q_offset", [0, 97])
@pytest.mark.parametrize("window", [None, 24, 35, 200])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_xla_live_pairs_match_the_static_mask(causal, window, q_offset,
                                                    bq, bk):
    """The pairs the chunked path runs are exactly those in which the static
    mask (causal, window, q offset; no segments) holds somewhere, q-major.
    At q offset 97, some pairs see one key alone, at a corner: with blocks
    of 32 and 64, q block 0 ends on kv block 2's first key, and the window
    of 35 reaches q block 0's first query to kv block 0's last key."""
    Sq, Sk = 128, 256
    spec = _Blocks(causal, window, None, q_offset, 1.0, bq, bk, False)
    n_q, n_k = Sq // bq, Sk // bk
    pairs = spec.live_pairs(n_q, n_k)
    assert pairs == _exhaustive_live_pairs(spec, n_q, n_k)
    if not causal and window is None:
        assert len(pairs) == n_q * n_k


def test_flash_xla_live_pairs_at_the_train_steps_grid():
    """S = 2048 in q blocks of 512 and kv blocks of 1024: the causal mask
    leaves 6 of the 8 pairs."""
    spec = _Blocks(True, None, None, 0, 1.0, 512, 1024, False)
    pairs = spec.live_pairs(4, 2)
    assert pairs == _exhaustive_live_pairs(spec, 4, 2)
    assert pairs == [(0, 0), (1, 0), (2, 0), (2, 1), (3, 0), (3, 1)]


def test_flash_xla_q_block_with_no_live_pair():
    """A q block that no pair reaches keeps output 0, as do the rows of a
    live block that see no key, and no gradient is NaN."""
    B, Sq, Sk, Hq, Hkv, D, window, bq, bk = 2, 256, 128, 4, 2, 32, 32, 64, 64
    spec = _Blocks(True, window, None, 0, 1.0, bq, bk, False)
    assert not any(qi == 3 for qi, _ in spec.live_pairs(Sq // bq, Sk // bk))
    q, k, v = _qkv(jax.random.PRNGKey(10), B, Sq, Sk, Hq, Hkv, D,
                   jnp.float32)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               impl="xla", block_q=bq, block_k=bk)

    out = np.asarray(attn(q, k, v))
    _assert_close(out, attention_reference(q, k, v, causal=True,
                                           window=window), jnp.float32)
    blind = np.arange(Sq) - window >= Sk - 1       # rows that see no key
    assert blind[3 * bq:].all() and blind.sum() > bq
    assert np.all(out[:, blind] == 0) and np.abs(out[:, ~blind]).max() > 0
    grads = jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) ** 2),
                     (0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(1, 2),
    log_s=st.integers(5, 8),
    hkv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 4]),
    log_d=st.integers(4, 6),
    causal=st.booleans(),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_property_flash_shape_sweep(b, log_s, hkv, group, log_d, causal, dtype):
    S, D = 2 ** log_s, 2 ** log_d
    Hq = hkv * group
    q, k, v = _qkv(jax.random.PRNGKey(5), b, S, S, Hq, hkv, D, dtype)
    ref = attention_reference(q, k, v, causal=causal)
    for impl in IMPLS:
        out = flash_attention(q, k, v, causal=causal, impl=impl,
                              block_q=32, block_k=32)
        assert out.shape == q.shape and out.dtype == dtype
        _assert_close(out, ref, dtype)


# ---------------------------------------------------------------------------
# SSD (mamba2)
# ---------------------------------------------------------------------------


def _ssd_inputs(key, B, S, H, P, N, dtype=jnp.float32):
    """x, the log decay log(a) for a in [0.5, 1), B, C and a start state."""
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32).astype(dtype)
    a = (jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, H))) * 0.5 + 0.5)
    Bm = (jax.random.normal(ks[2], (B, S, N), jnp.float32) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[3], (B, S, N), jnp.float32) * 0.3).astype(dtype)
    s0 = jax.random.normal(ks[4], (B, H, P, N), jnp.float32) * 0.1
    return x, jnp.log(a.astype(jnp.float32)), Bm, Cm, s0


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_ssd_matches_reference(impl, chunk):
    x, a, Bm, Cm, s0 = _ssd_inputs(jax.random.PRNGKey(0), 2, 128, 4, 16, 32)
    y_ref, sf_ref = ssd_reference(x, a, Bm, Cm, s0)
    y, sf = ssd(x, a, Bm, Cm, s0, chunk=chunk, impl=impl)
    _assert_close(y, y_ref, jnp.float32)
    _assert_close(sf, sf_ref, jnp.float32)


@pytest.mark.parametrize("impl", IMPLS)
def test_ssd_zero_initial_state(impl):
    x, a, Bm, Cm, _ = _ssd_inputs(jax.random.PRNGKey(1), 1, 64, 2, 16, 16)
    y_ref, sf_ref = ssd_reference(x, a, Bm, Cm)
    y, sf = ssd(x, a, Bm, Cm, chunk=16, impl=impl)
    _assert_close(y, y_ref, jnp.float32)
    _assert_close(sf, sf_ref, jnp.float32)


def test_ssd_xla_grad_finite_under_strong_decay():
    """A 256-long chunk with strong decay: exp(la_t - la_r) above the
    diagonal overflows, and must not turn the gradient into NaN."""
    x, _, Bm, Cm, s0 = _ssd_inputs(jax.random.PRNGKey(7), 1, 256, 2, 8, 8)
    a = jnp.log(jnp.full((1, 256, 2), 0.3, jnp.float32))

    def loss(x, a):
        return jnp.sum(ssd(x, a, Bm, Cm, s0, chunk=256, impl="xla")[0] ** 2)

    gx, ga = jax.grad(loss, argnums=(0, 1))(x, a)
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(ga)).all()
    y, _ = ssd(x, a, Bm, Cm, s0, chunk=256, impl="xla")
    _assert_close(y, ssd_reference(x, a, Bm, Cm, s0)[0], jnp.float32)


def test_ssd_decode_chain_equals_scan():
    x, a, Bm, Cm, s0 = _ssd_inputs(jax.random.PRNGKey(2), 2, 16, 4, 16, 32)
    state = s0
    ys = []
    for t in range(16):
        y_t, state = ssd_step(state, x[:, t], a[:, t], Bm[:, t], Cm[:, t])
        ys.append(y_t)
    y_ref, sf_ref = ssd_reference(x, a, Bm, Cm, s0)
    _assert_close(jnp.stack(ys, 1), y_ref, jnp.float32)
    _assert_close(state, sf_ref, jnp.float32)


def test_ssd_prefill_then_decode_continuity():
    """State from chunked prefill continues correctly into decode."""
    x, a, Bm, Cm, _ = _ssd_inputs(jax.random.PRNGKey(3), 1, 96, 2, 16, 16)
    y_full, sf_full = ssd_reference(x, a, Bm, Cm)
    _, s_mid = ssd(x[:, :64], a[:, :64], Bm[:, :64], Cm[:, :64],
                   chunk=32, impl="xla")
    state = s_mid
    for t in range(64, 96):
        y_t, state = ssd_step(state, x[:, t], a[:, t], Bm[:, t], Cm[:, t])
    _assert_close(state, sf_full, jnp.float32)


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 2),
    nc=st.integers(1, 4),
    chunk=st.sampled_from([8, 16, 32]),
    h=st.sampled_from([1, 2, 4]),
    p=st.sampled_from([8, 16]),
    n=st.sampled_from([8, 16, 32]),
)
def test_property_ssd_shape_sweep(b, nc, chunk, h, p, n):
    S = nc * chunk
    x, a, Bm, Cm, s0 = _ssd_inputs(jax.random.PRNGKey(6), b, S, h, p, n)
    y_ref, sf_ref = ssd_reference(x, a, Bm, Cm, s0)
    for impl in IMPLS:
        y, sf = ssd(x, a, Bm, Cm, s0, chunk=chunk, impl=impl)
        assert y.shape == x.shape
        _assert_close(y, y_ref, jnp.float32)
        _assert_close(sf, sf_ref, jnp.float32)


SSD_WITNESS_IMPLS = ["xla", "ref", "pallas_interpret"]


def _witness_log_a(key, B, S, H):
    """Log decays past exp's float32 underflow (about -87.3): moderate
    decays, a few positions per head near -90 and one at -120."""
    k1, k2, k3 = jax.random.split(key, 3)
    log_a = -jax.random.uniform(k1, (B, S, H), minval=0.0, maxval=1.0)
    strong = jax.random.uniform(k2, (B, S, H)) < 0.05
    near_90 = -jax.random.uniform(k3, (B, S, H), minval=88.0, maxval=92.0)
    log_a = jnp.where(strong, near_90, log_a)
    return log_a.at[:, S // 3, 0].set(-120.0)


def _ssd_witness(key, S=256, chunk=64):
    x, _, Bm, Cm, s0 = _ssd_inputs(key, 1, S, 2, 8, 8)
    log_a = _witness_log_a(jax.random.fold_in(key, 1), 1, S, 2)
    # The witness is one: a = exp(log_a) underflows, so log(a) is not finite.
    with np.errstate(divide="ignore"):
        assert not np.isfinite(np.log(np.exp(np.asarray(log_a)))).all()
    return x, log_a, Bm, Cm, s0, chunk


@pytest.mark.parametrize("impl", SSD_WITNESS_IMPLS)
def test_ssd_witness_underflowing_decay(impl):
    """Decays that underflow exp: outputs and final state finite and equal
    to the sequential reference's."""
    x, log_a, Bm, Cm, s0, chunk = _ssd_witness(jax.random.PRNGKey(11))
    y_ref, sf_ref = ssd_reference(x, log_a, Bm, Cm, s0)
    y, sf = ssd(x, log_a, Bm, Cm, s0, chunk=chunk, impl=impl)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(sf)).all()
    _assert_close(y, y_ref, jnp.float32)
    _assert_close(sf, sf_ref, jnp.float32)


@pytest.mark.parametrize("impl", ["xla", "ref"])
def test_ssd_witness_grads(impl):
    """Gradients with respect to x, log_a, B and C at the witness: finite
    and equal to autodiff of the sequential reference.  (The Pallas kernel
    is forward-only; its witness case is the test above.)"""
    x, log_a, Bm, Cm, s0, chunk = _ssd_witness(jax.random.PRNGKey(12))
    g = jax.random.normal(jax.random.PRNGKey(13), x.shape)
    gs = jax.random.normal(jax.random.PRNGKey(14), s0.shape)

    def loss(fn):
        def f(x, log_a, Bm, Cm):
            y, sf = fn(x, log_a, Bm, Cm)
            return jnp.sum(y * g) + jnp.sum(sf * gs)
        return jax.grad(f, argnums=(0, 1, 2, 3))

    got = loss(lambda *a: ssd(*a, s0, chunk=chunk, impl=impl))(
        x, log_a, Bm, Cm)
    want = loss(lambda *a: ssd_reference(*a, s0))(x, log_a, Bm, Cm)
    for name, gv, wv in zip(("x", "log_a", "B", "C"), got, want):
        assert np.isfinite(np.asarray(gv)).all(), name
        _assert_close(gv, wv, jnp.float32)


def _linear_decay_scan(x, a, Bm, Cm, s0):
    """The recurrence with the decay a itself as input, in numpy float64:
    s_t = a_t s_{t-1} + x_t B_t^T, y_t = s_t C_t."""
    x, a, Bm, Cm, s = (np.asarray(v, np.float64) for v in (x, a, Bm, Cm, s0))
    ys = []
    for t in range(x.shape[1]):
        s = s * a[:, t, :, None, None] + np.einsum(
            "bhp,bn->bhpn", x[:, t], Bm[:, t])
        ys.append(np.einsum("bhpn,bn->bhp", s, Cm[:, t]))
    return np.stack(ys, 1), s


@pytest.mark.parametrize("impl", SSD_WITNESS_IMPLS + ["step"])
def test_ssd_log_decay_agrees_with_linear_decay(impl):
    """For decays a in (0, 1], passing log(a) gives what the recurrence in
    a gives: a = 1 (log 0), tiny and ordinary decays."""
    x, _, Bm, Cm, s0 = _ssd_inputs(jax.random.PRNGKey(15), 2, 64, 3, 8, 16)
    a = jax.random.uniform(jax.random.PRNGKey(16), (2, 64, 3),
                           minval=0.05, maxval=1.0)
    a = a.at[:, ::7, 0].set(1.0).at[:, 5::11, 1].set(1e-30)
    a = a.at[:, :, 2].set(0.999)
    y_want, sf_want = _linear_decay_scan(x, a, Bm, Cm, s0)
    log_a = jnp.log(a)
    if impl == "step":
        state, ys = s0, []
        for t in range(x.shape[1]):
            y_t, state = ssd_step(state, x[:, t], log_a[:, t], Bm[:, t],
                                  Cm[:, t])
            ys.append(y_t)
        y, sf = jnp.stack(ys, 1), state
    else:
        y, sf = ssd(x, log_a, Bm, Cm, s0, chunk=16, impl=impl)
    _assert_close(y, y_want, jnp.float32)
    _assert_close(sf, sf_want, jnp.float32)


@pytest.mark.parametrize("ssd_impl", ["xla", "ref"])
def test_ssm_apply_grads_finite_past_decay_underflow(ssd_impl):
    """A whole Mamba-2 mixer whose dt_bias drives half the heads'
    dt * exp(A_log) past 88, where exp(-dt * exp(A_log)) underflows: the
    loss and the gradient of every parameter are finite."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import RuntimeConfig
    from repro.models.common import Initializer
    from repro.models.ssm_block import (_gates, _split_proj, ssm_apply,
                                        ssm_init)

    cfg = dataclasses.replace(get_smoke_config("mamba2-1.3b"), ssm_chunk=32)
    rt = RuntimeConfig(compute_dtype=jnp.float32, ssd_impl=ssd_impl)
    params = ssm_init(Initializer(jax.random.PRNGKey(21)), cfg, jnp.float32)
    H = cfg.ssm_heads
    strong = jnp.arange(H) % 2 == 0
    params["dt_bias"] = jnp.where(strong, 100.0, 0.0)
    params["A_log"] = jnp.where(strong, 0.0, params["A_log"])
    x = jax.random.normal(jax.random.PRNGKey(22), (2, 64, cfg.d_model))

    dt_raw = _split_proj(cfg, x @ params["in_proj"])[2]
    rate = -np.asarray(_gates(params, cfg, dt_raw)[1])
    assert rate[..., ::2].min() > 88.0       # exp(-rate) underflows there

    def loss(p):
        return jnp.mean(ssm_apply(p, x, cfg, rt) ** 2)

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    for name, g in grads.items():
        assert np.isfinite(np.asarray(g)).all(), name


# ---------------------------------------------------------------------------
# RG-LRU (recurrentgemma)
# ---------------------------------------------------------------------------


def _rglru_inputs(key, B, S, W, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    mk = lambda i: jax.random.normal(ks[i], (B, S, W), jnp.float32).astype(dtype)
    lam = jax.random.normal(ks[3], (W,), jnp.float32)
    h0 = jax.random.normal(ks[4], (B, W), jnp.float32) * 0.2
    return mk(0), mk(1), mk(2), lam, h0


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [16, 64])
def test_rglru_matches_reference(impl, chunk):
    x, r, i, lam, h0 = _rglru_inputs(jax.random.PRNGKey(0), 2, 128, 64)
    y_ref, hf_ref = rglru_reference(x, r, i, lam, h0)
    y, hf = rglru(x, r, i, lam, h0, chunk=chunk, impl=impl)
    _assert_close(y, y_ref, jnp.float32)
    _assert_close(hf, hf_ref, jnp.float32)


def test_rglru_decode_chain():
    x, r, i, lam, h0 = _rglru_inputs(jax.random.PRNGKey(1), 2, 16, 32)
    h = h0
    ys = []
    for t in range(16):
        y_t, h = rglru_step(h, x[:, t], r[:, t], i[:, t], lam)
        ys.append(y_t)
    y_ref, hf_ref = rglru_reference(x, r, i, lam, h0)
    _assert_close(jnp.stack(ys, 1), y_ref, jnp.float32)
    _assert_close(h, hf_ref, jnp.float32)


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 2),
    log_s=st.integers(4, 7),
    w=st.sampled_from([32, 64, 128]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_property_rglru_shape_sweep(b, log_s, w, dtype):
    S = 2 ** log_s
    x, r, i, lam, h0 = _rglru_inputs(jax.random.PRNGKey(2), b, S, w, dtype)
    y_ref, hf_ref = rglru_reference(x, r, i, lam, h0)
    for impl in IMPLS:
        y, hf = rglru(x, r, i, lam, h0, chunk=16, impl=impl)
        assert y.shape == x.shape and y.dtype == dtype
        _assert_close(y, y_ref, dtype)
        _assert_close(hf, hf_ref, dtype)


def test_rglru_forgets_long_past():
    """Stability property: with strong decay the state forgets its init."""
    B, S, W = 1, 512, 32
    x, r, i, lam, _ = _rglru_inputs(jax.random.PRNGKey(3), B, S, W)
    lam = jnp.abs(lam) + 2.0  # strong decay
    h_a = jnp.zeros((B, W), jnp.float32)
    h_b = jnp.ones((B, W), jnp.float32) * 10.0
    _, hf_a = rglru_reference(x, r, i, lam, h_a)
    _, hf_b = rglru_reference(x, r, i, lam, h_b)
    np.testing.assert_allclose(np.asarray(hf_a), np.asarray(hf_b), atol=1e-3)
