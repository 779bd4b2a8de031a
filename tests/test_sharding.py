"""Sharding-rule unit tests (no big meshes needed)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import RuntimeConfig, build_model
from repro.train.sharding import ShardingRules, batch_specs, param_specs


class FakeMesh:
    """Just enough Mesh interface for rule evaluation."""

    def __init__(self, shape_map):
        self.shape = shape_map
        self.axis_names = tuple(shape_map)


@pytest.fixture
def rules():
    return ShardingRules(FakeMesh({"data": 16, "model": 16}))


@pytest.fixture
def rules_mp():
    return ShardingRules(FakeMesh({"pod": 2, "data": 16, "model": 16}))


def _abstract_params(arch):
    cfg = get_config(arch)
    model = build_model(cfg, RuntimeConfig())
    return cfg, model.init_abstract()


def test_param_specs_qwen(rules):
    cfg, params = _abstract_params("qwen2.5-32b")
    specs = param_specs(params, rules)
    # embed (V, D): vocab on model, d_model on data
    assert specs["embed"] == P("model", "data")
    blk = specs["blocks"]["pos0"]
    # stacked leading dim never sharded; wq (R, D, H*dh)
    assert blk["attn"]["wq"]["w"] == P(None, "data", "model")
    assert blk["attn"]["wo"]["w"] == P(None, "model", "data")
    assert blk["mlp"]["wi"] == P(None, "data", "model")
    assert blk["mlp"]["wo"] == P(None, "model", "data")
    # norms replicated
    assert blk["norm1"]["scale"] == P(None, None)
    assert specs["lm_head"] == P("data", "model")


def test_param_specs_moe_expert_parallel(rules):
    cfg, params = _abstract_params("arctic-480b")
    specs = param_specs(params, rules)
    moe = specs["blocks"]["pos0"]["moe"]
    # 128 experts / 16 = 8 per shard -> expert-parallel over data
    assert moe["wi"] == P(None, "data", None, "model")
    assert moe["wo"] == P(None, "data", "model", None)


def test_param_specs_moe_small_expert_count(rules):
    cfg, params = _abstract_params("mixtral-8x22b")
    specs = param_specs(params, rules)
    moe = specs["blocks"]["pos0"]["moe"]
    # 8 experts < 16-way axis: experts unsharded, d_model/d_ff sharded
    assert moe["wi"] == P(None, None, "data", "model")
    assert moe["wo"] == P(None, None, "model", "data")


def test_param_specs_never_invalid_divisibility(rules, rules_mp):
    """No spec may shard a dim that the axis size does not divide."""
    for arch in ["qwen2.5-32b", "arctic-480b", "mamba2-1.3b",
                 "recurrentgemma-9b", "seamless-m4t-medium", "gemma3-12b"]:
        cfg, params = _abstract_params(arch)
        for r in (rules, rules_mp):
            specs = param_specs(params, r)
            flat_p = jax.tree.leaves(params)
            flat_s = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            for leaf, spec in zip(flat_p, flat_s):
                for dim, axis in zip(leaf.shape, tuple(spec)):
                    if axis is None:
                        continue
                    axes = axis if isinstance(axis, tuple) else (axis,)
                    size = 1
                    for a in axes:
                        size *= r.size(a)
                    assert dim % size == 0, (arch, leaf.shape, spec)


def test_batch_specs_shard_batch(rules, rules_mp):
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    assert batch_specs(batch, rules)["tokens"] == P(("data",), None)
    assert batch_specs(batch, rules_mp)["tokens"] == P(("pod", "data"), None)
    # batch=1: replicated
    one = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    assert batch_specs(one, rules)["tokens"] == P(None, None)
    # batch=32 on multi-pod: 32 == pod*data -> both axes
    b32 = {"tokens": jax.ShapeDtypeStruct((32, 10), jnp.int32)}
    assert batch_specs(b32, rules_mp)["tokens"] == P(("pod", "data"), None)


def test_vocab_padding_divisible():
    for arch in ["seamless-m4t-medium", "mamba2-1.3b", "internvl2-2b"]:
        cfg = get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mamba2-1.3b"])
def test_data_parallel_rules_replicate_params_and_shard_batch(arch):
    """The layout of the benchmark cells and of ``launch/train.py:train``:
    a 1-D "data" mesh, no FSDP or tensor axis."""
    r = ShardingRules(FakeMesh({"data": 4}), batch_axes=("data",),
                      fsdp_axis=None, tp_axis=None)
    _, params = _abstract_params(arch)
    specs = param_specs(params, r)
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert all(axis is None for axis in spec), spec
    batch = {k: jax.ShapeDtypeStruct((8, 16), jnp.int32)
             for k in ("tokens", "labels", "segments", "positions")}
    assert batch_specs(batch, r) == {k: P("data", None) for k in batch}
