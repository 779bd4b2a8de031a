"""The chip smoke script's CPU rehearsal, and kernels that never interpret
unless asked to."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru
from repro.kernels.ssd import ssd

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


@pytest.fixture
def keep_cache_dir():
    """``main`` places the persistent compilation cache; undo that for the
    tests that share this process."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_tiny_rehearsal_ends_with_ok_line(capsys, keep_cache_dir):
    assert chip_smoke.main(["--tiny"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": jax.devices()[0].platform,
                              "kind": jax.devices()[0].device_kind,
                              "count": len(jax.devices())}
    for phase in ("(a)", "(b)", "(c)", "(d)", "(e) ssd",
                  "(e) flash_attention", "(e) rglru"):
        assert any(line.startswith(phase) for line in lines), phase


def test_without_tpu_or_tiny_exits_nonzero(capsys, keep_cache_dir):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("call", [
    lambda: flash_attention(*[jnp.zeros((1, 128, 2, 32))] * 3,
                            impl="pallas"),
    lambda: ssd(jnp.zeros((1, 32, 2, 16)), jnp.zeros((1, 32, 2)),
                jnp.zeros((1, 32, 16)), jnp.zeros((1, 32, 16)),
                chunk=16, impl="pallas"),
    lambda: rglru(*[jnp.zeros((1, 32, 128))] * 3, jnp.zeros((128,)),
                  impl="pallas"),
], ids=["flash_attention", "ssd", "rglru"])
def test_pallas_impl_raises_off_tpu(call):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        call()
