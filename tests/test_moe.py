"""MoE invariants: shard_map dispatch == local dispatch == the served
dropless layer (at no-drop capacity); drop behaviour bounded; router
normalization."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import ShardingPolicy, use_policy
from repro.models.moe import (_moe_forward_local, _moe_forward_shardmap,
                              init_moe, moe_forward, moe_served)


def _cfg(cf=8.0, e=4, k=2):
    return ModelConfig(name="m", arch_type="moe", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                       moe=MoEConfig(num_experts=e, top_k=k, expert_d_ff=96,
                                     capacity_factor=cf)).validate()


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    return cfg, params, x


def test_shardmap_matches_local(setup):
    cfg, params, x = setup
    mesh = make_debug_mesh(1)
    policy = ShardingPolicy(mesh, batch=2, seq_parallel=False)
    out_l, aux_l = _moe_forward_local(params, cfg, x)
    with use_policy(policy):
        out_s, aux_s = _moe_forward_shardmap(params, cfg, x, policy)
    np.testing.assert_allclose(np.asarray(out_l), np.asarray(out_s),
                               atol=2e-5)
    assert abs(float(aux_l) - float(aux_s)) < 1e-5


def test_forward_matches_decode_at_no_drop(setup):
    cfg, params, x = setup
    out_f, _ = _moe_forward_local(params, cfg, x)
    out_d = jnp.concatenate(
        [moe_served(params, cfg, x[:, t:t + 1])[0]
         for t in range(x.shape[1])], axis=1)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=2e-5)


def test_dispatch_path_selection(setup):
    cfg, params, x = setup
    # no policy active -> local path (identical results by definition)
    out1, _ = moe_forward(params, cfg, x)
    out2, _ = _moe_forward_local(params, cfg, x)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_drops_bounded_at_tight_capacity():
    cfg = _cfg(cf=0.5)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    out, aux = _moe_forward_local(params, cfg, x)
    assert bool(jnp.all(jnp.isfinite(out)))
    # dropped tokens produce zero update, so norm is below no-drop norm
    cfg2 = _cfg(cf=8.0)
    out2, _ = _moe_forward_local(params, cfg2, x)
    assert float(jnp.linalg.norm(out)) <= float(jnp.linalg.norm(out2)) + 1e-4


def test_aux_loss_balanced_router_lower():
    """Property: a perfectly balanced router has aux ~= coef (its minimum)."""
    cfg = _cfg()
    params = init_moe(jax.random.PRNGKey(3), cfg)
    # force balanced routing with uniform router weights
    params = dict(params)
    params["router"] = jnp.zeros_like(params["router"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    _, aux_uniform = _moe_forward_local(params, cfg, x)
    params["router"] = jnp.ones_like(params["router"]) * 5.0  # degenerate
    _, aux_skew = _moe_forward_local(params, cfg, x)
    assert float(aux_uniform) <= float(aux_skew) + 1e-6
