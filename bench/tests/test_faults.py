"""The check catches a broken timed path.

Each test skips the look for a chip and drives the rest of a --tiny run
in this process with one fault planted underneath, and sees ``correct``
come out false:

- a step that returns its state unchanged;
- half of the batch left out, the mean taken over the rest;
- a step whose parameters turn NaN partway through the window;
- a token altered where the loader decodes it.
"""

import time

import jax
import jax.numpy as jnp
import pytest

import harness
import spec
from conftest import BENCH

import repro.data.loader
import repro.train

ROOT = BENCH.parent
real_make = repro.train.make_train_step


def tiny_run(cell):
    c = spec.cell(spec.load_benchmark(ROOT), ROOT, cell)
    return harness.run(c, seed=424242, seconds=0.5, trace=False, tiny=True,
                       t_start=time.perf_counter(), root=ROOT)


def unchanged(model, cfg):
    step = real_make(model, cfg)

    def fault(p, o, b):
        return (p, o, step(p, o, b)[2])
    return fault


def half_batch(model, cfg):
    step = real_make(model, cfg)

    def fault(p, o, b):
        n = b["labels"].shape[0] // 2
        return step(p, o, {**b, "labels": b["labels"].at[n:].set(-1)})
    return fault


def nan_later(model, cfg):
    """From the sixth update on, the loss and every parameter are NaN; the
    three checked steps are sound."""
    step = real_make(model, cfg)

    def fault(p, o, b):
        p, o, m = step(p, o, b)
        bad = o["step"] > 5
        poison = jnp.where(bad, jnp.nan, 0.0)
        p = jax.tree.map(lambda v: v + poison.astype(v.dtype), p)
        return p, o, {**m, "loss": m["loss"] + poison}
    return fault


def altered_token(decode):
    def fault(data):
        tokens, segments, positions = decode(data)
        tokens = tokens.copy()
        tokens[7] = 3 + (tokens[7] + 1) % 256
        return tokens, segments, positions
    return fault


CELL = "tinyllama.steady"


def test_sound_run_is_correct():
    assert tiny_run(CELL)["correct"] is True


@pytest.mark.parametrize("fault", [unchanged, half_batch, nan_later])
def test_broken_step_is_caught(monkeypatch, fault):
    monkeypatch.setattr(repro.train, "make_train_step", fault)
    out = tiny_run(CELL)
    assert out["correct"] is False, out["checks"]


def test_altered_token_is_caught(monkeypatch):
    monkeypatch.setattr(repro.data.loader, "decode_packed",
                        altered_token(repro.data.loader.decode_packed))
    out = tiny_run(CELL)
    assert out["correct"] is False
    assert out["checks"]["rows_bad"]["value"] > 0
