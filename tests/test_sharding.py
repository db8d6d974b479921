"""Multi-device sharding tests on the virtual 8-device CPU mesh.

The invariant: sharded execution is numerically the same computation — TP/EP/
DP sharded forwards must match the single-device result to float tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.models.config import TINY_LLAMA, TINY_MIXTRAL
from polykey_tpu.models.transformer import forward, init_params, unembed
from polykey_tpu.parallel.mesh import AXIS_NAMES, MeshConfig, create_mesh
from polykey_tpu.parallel.sharding import (
    batch_sharding,
    param_shardings,
    shard_params,
)

# Widened tiny config so tp=4 divides heads/hidden cleanly.
CFG = dataclasses.replace(
    TINY_LLAMA, hidden_size=128, intermediate_size=256, num_heads=8,
    num_kv_heads=4, head_dim=16,
)

MOE_CFG = dataclasses.replace(
    TINY_MIXTRAL, hidden_size=128, intermediate_size=256, num_heads=8,
    num_kv_heads=4, head_dim=16,
)


def _logits(cfg, params, tokens, positions):
    hidden, _ = forward(params, cfg, tokens, positions, None)
    return unembed(params, cfg, hidden)


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, CFG.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(16), (4, 16)).astype(jnp.int32)
    return tokens, positions


@pytest.mark.parametrize(
    "mesh_config",
    [
        MeshConfig(tp=4),
        MeshConfig(dp=4),
        MeshConfig(dp=2, tp=2),
        MeshConfig(dp=2, tp=4),
        MeshConfig(pp=2, tp=2),
    ],
    ids=lambda m: "x".join(f"{n}{s}" for n, s in zip(AXIS_NAMES, m.shape) if s > 1),
)
def test_sharded_forward_matches_single_device(mesh_config, batch):
    assert jax.device_count() >= mesh_config.num_devices, "need 8 CPU devices"
    tokens, positions = batch
    params = init_params(jax.random.PRNGKey(0), CFG, jnp.float32)
    expected = np.asarray(_logits(CFG, params, tokens, positions))

    mesh = create_mesh(mesh_config, jax.devices()[: mesh_config.num_devices])
    sharded = shard_params(params, CFG, mesh)
    tokens_s = jax.device_put(tokens, batch_sharding(mesh, 2))
    positions_s = jax.device_put(positions, batch_sharding(mesh, 2))

    got = jax.jit(lambda p, t, pos: _logits(CFG, p, t, pos))(
        sharded, tokens_s, positions_s
    )
    np.testing.assert_allclose(expected, np.asarray(got), rtol=2e-4, atol=2e-4)


def test_moe_ep_sharded_matches_single_device(batch):
    tokens, positions = batch
    params = init_params(jax.random.PRNGKey(2), MOE_CFG, jnp.float32)
    expected = np.asarray(_logits(MOE_CFG, params, tokens, positions))

    mesh = create_mesh(MeshConfig(dp=2, ep=2, tp=2), jax.devices()[:8])
    sharded = shard_params(params, MOE_CFG, mesh)
    tokens_s = jax.device_put(tokens, batch_sharding(mesh, 2))
    positions_s = jax.device_put(positions, batch_sharding(mesh, 2))

    got = jax.jit(lambda p, t, pos: _logits(MOE_CFG, p, t, pos))(
        sharded, tokens_s, positions_s
    )
    np.testing.assert_allclose(expected, np.asarray(got), rtol=3e-4, atol=3e-4)


def test_param_shardings_cover_all_leaves():
    for cfg in (CFG, MOE_CFG):
        mesh = create_mesh(MeshConfig(tp=2), jax.devices()[:2])
        shardings = param_shardings(cfg, mesh)
        params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        assert jax.tree_util.tree_structure(
            shardings
        ) == jax.tree_util.tree_structure(params)


def test_tp_actually_shards_weights():
    """TP must reduce per-device parameter bytes, not just relabel them."""
    mesh = create_mesh(MeshConfig(tp=4), jax.devices()[:4])
    params = shard_params(
        init_params(jax.random.PRNGKey(0), CFG, jnp.float32), CFG, mesh
    )
    wq = params["layers"]["attn"]["wq"]
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[-1] == wq.shape[-1] // 4


def test_mesh_validation():
    with pytest.raises(ValueError):
        create_mesh(MeshConfig(tp=3), jax.devices()[:8])


@pytest.mark.parametrize("cfg, mesh_config, dtype, bits", [
    (TINY_LLAMA, MeshConfig(pp=2, tp=2), jnp.bfloat16, 8),
    (TINY_MIXTRAL, MeshConfig(ep=2, tp=2), jnp.float32, 4),
])
def test_init_sharded_params_is_init_params_bit_for_bit(
        cfg, mesh_config, dtype, bits):
    """The engine's start (each layer drawn alone, straight into its final
    dtype and sharding) yields exactly the tree the old path built by
    materialising everything first: same values to the last bit, same
    dtypes, same shardings — int8 and int4 trees, dense and MoE, pp/ep/tp
    meshes (the unquantized tree is the same draws without the last step).
    (float32 would expose a jitted generation: XLA folds
    (sqrt2·erfinv(u))·scale and x/127 into differently-rounded forms.)"""
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.parallel.sharding import init_sharded_params

    n = (mesh_config.dp * mesh_config.pp * mesh_config.sp
         * mesh_config.ep * mesh_config.tp)
    mesh = create_mesh(mesh_config, devices=jax.devices()[:n])
    key = jax.random.PRNGKey(3)
    want = init_params(key, cfg, dtype)
    if bits:
        want = quantize_params(want, cfg, bits=bits)
    want = shard_params(want, cfg, mesh)
    got = init_sharded_params(key, cfg, mesh, dtype, quantize_bits=bits)

    want_leaves, want_def = jax.tree.flatten(want)
    got_leaves, got_def = jax.tree.flatten(got)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.sharding == w.sharding
        assert np.array_equal(
            np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
        )
