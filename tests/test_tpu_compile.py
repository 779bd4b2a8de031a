"""Compile the three Pallas kernels for a described TPU v5e chip.

Interpret mode accepts block shapes and in-kernel indexing that the chip's
compiler refuses, so these tests lower each kernel at real widths, batch 2,
for one chip of a ``v5e:2x2`` topology that is described, not attached.
Nothing runs; a kernel that compiles shows up as ``tpu_custom_call``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rglru.kernel import rglru_pallas
from repro.kernels.ssd.kernel import ssd_pallas

BATCH = 2
SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_ssd_compiles_at_mamba2_widths(one_chip):
    # mamba2-1.3b: d_inner 4096 = 64 heads of 64, state 128, chunk 256.
    H, P, N = 64, 64, 128
    text = _compiled_text(
        lambda x, la, b, c, s0: ssd_pallas(x, la, b, c, s0, chunk=256),
        [((BATCH, SEQ, H, P), jnp.bfloat16), ((BATCH, SEQ, H), jnp.float32),
         ((BATCH, SEQ, N), jnp.bfloat16), ((BATCH, SEQ, N), jnp.bfloat16),
         ((BATCH, H, P, N), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_qwen_widths(one_chip):
    # qwen2.5-32b: 40 query heads, 8 KV heads, head dim 128; packed segments.
    Hq, Hkv, D = 40, 8, 128

    def fn(q, k, v, seg):
        return flash_attention_pallas(q, k, v, causal=True, q_segments=seg,
                                      kv_segments=seg)

    text = _compiled_text(
        fn, [((BATCH, SEQ, Hq, D), jnp.bfloat16),
             ((BATCH, SEQ, Hkv, D), jnp.bfloat16),
             ((BATCH, SEQ, Hkv, D), jnp.bfloat16),
             ((BATCH, SEQ), jnp.int32)], one_chip)
    assert "tpu_custom_call" in text


def test_rglru_compiles_at_recurrentgemma_width(one_chip):
    W = 4096                                    # recurrentgemma-9b lru_width
    text = _compiled_text(
        lambda x, r, i, lam, h0: rglru_pallas(x, r, i, lam, h0),
        [((BATCH, SEQ, W), jnp.bfloat16)] * 3
        + [((W,), jnp.float32), ((BATCH, W), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text
