"""shard_map MoE must match the GSPMD capacity path numerically."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import RuntimeConfig, build_model
from repro.models.moe import moe_apply, moe_apply_shardmap, moe_init
from repro.models.common import Initializer
from repro.train.sharding import ActivationSharding, ShardingRules


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_shardmap_moe_matches_gspmd_path():
    cfg = get_smoke_config("mixtral-8x22b")
    mesh = _mesh11()
    rules = ShardingRules(mesh)
    rt = RuntimeConfig(compute_dtype=jnp.float32, moe_group_size=32,
                       act_sharding=ActivationSharding(rules))
    ini = Initializer(jax.random.PRNGKey(0))
    params = moe_init(ini, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = moe_apply(params, x, cfg, rt)
    y_sm, aux_sm = moe_apply_shardmap(params, x, cfg, rt)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux_sm), float(aux_ref), rtol=1e-5)


def test_shardmap_moe_grads_match():
    cfg = get_smoke_config("arctic-480b")
    mesh = _mesh11()
    rules = ShardingRules(mesh)
    rt = RuntimeConfig(compute_dtype=jnp.float32, moe_group_size=16,
                       act_sharding=ActivationSharding(rules))
    ini = Initializer(jax.random.PRNGKey(0))
    params = moe_init(ini, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)

    def loss_g(p):
        return jnp.sum(moe_apply(p, x, cfg, rt)[0] ** 2)

    def loss_s(p):
        return jnp.sum(moe_apply_shardmap(p, x, cfg, rt)[0] ** 2)

    g_ref = jax.grad(loss_g)(params)
    g_sm = jax.grad(loss_s)(params)
    for k in g_ref:
        np.testing.assert_allclose(np.asarray(g_sm[k]), np.asarray(g_ref[k]),
                                   atol=2e-4, rtol=2e-4)


def test_decoder_with_shardmap_moe_end_to_end():
    cfg = get_smoke_config("mixtral-8x22b")
    mesh = _mesh11()
    rules = ShardingRules(mesh)
    rt = RuntimeConfig(compute_dtype=jnp.float32, attn_impl="naive",
                       moe_group_size=16, moe_impl="shard_map",
                       act_sharding=ActivationSharding(rules))
    rt_ref = rt.with_(moe_impl="gspmd")
    model = build_model(cfg, rt)
    model_ref = build_model(cfg, rt_ref)
    params = model.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                     cfg.vocab_size),
    }
    l_sm, _ = model.loss(params, batch)
    l_ref, _ = model_ref.loss(params, batch)
    np.testing.assert_allclose(float(l_sm), float(l_ref), rtol=1e-5)
