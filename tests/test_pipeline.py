"""Pipeline-parallel schedule tests (parallel/pipeline.py).

The GPipe microbatch schedule must be a pure re-ordering of the unsharded
computation: forward hidden states, loss, and gradients all match the
single-device stack exactly (same math, different placement).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.models.config import TINY_GEMMA, TINY_LLAMA, TINY_MIXTRAL
from polykey_tpu.models.transformer import forward, init_params
from polykey_tpu.parallel.mesh import MeshConfig, create_mesh
from polykey_tpu.parallel.pipeline import pipeline_forward
from polykey_tpu.parallel.sharding import shard_params
from polykey_tpu.train import cross_entropy_loss, make_train_step

CFG = dataclasses.replace(
    TINY_LLAMA, hidden_size=64, intermediate_size=128, num_layers=4,
    num_heads=4, num_kv_heads=2, head_dim=16,
)


def _batch(key, B=4, T=16, cfg=CFG):
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    return tokens, positions


def _ref_hidden(params, cfg, tokens, positions):
    return forward(params, cfg, tokens, positions, None)[0]


@pytest.mark.parametrize("pp,microbatches", [(2, 2), (2, 4), (4, 4)])
def test_pipeline_forward_matches_unsharded(pp, microbatches):
    params = init_params(jax.random.PRNGKey(0), CFG, jnp.float32)
    tokens, positions = _batch(jax.random.PRNGKey(1))
    ref = _ref_hidden(params, CFG, tokens, positions)

    mesh = create_mesh(MeshConfig(pp=pp), jax.devices()[:pp])
    sharded = shard_params(params, CFG, mesh)
    out = pipeline_forward(sharded, CFG, tokens, positions, mesh, microbatches)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_respects_global_layer_indices():
    """Gemma-2 interleaves sliding-window (even) and global (odd) layers by
    absolute index; a stage that restarted indices at 0 would flip the
    pattern for stage 1's layers and diverge."""
    cfg = dataclasses.replace(
        TINY_GEMMA, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=8,
    )
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    tokens, positions = _batch(jax.random.PRNGKey(1), T=24, cfg=cfg)
    ref = _ref_hidden(params, cfg, tokens, positions)

    mesh = create_mesh(MeshConfig(pp=2), jax.devices()[:2])
    out = pipeline_forward(
        shard_params(params, cfg, mesh), cfg, tokens, positions, mesh, 2
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_moe_matches_unsharded():
    cfg = dataclasses.replace(
        TINY_MIXTRAL, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    tokens, positions = _batch(jax.random.PRNGKey(1), cfg=cfg)
    ref = _ref_hidden(params, cfg, tokens, positions)

    mesh = create_mesh(MeshConfig(pp=2), jax.devices()[:2])
    out = pipeline_forward(
        shard_params(params, cfg, mesh), cfg, tokens, positions, mesh, 2
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_gradients_match_unsharded():
    """The backward schedule falls out of autodiff through ppermute/scan;
    gradients must equal the unsharded stack's."""
    params = init_params(jax.random.PRNGKey(0), CFG, jnp.float32)
    tokens, positions = _batch(jax.random.PRNGKey(1))
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)

    ref_loss, ref_grads = jax.value_and_grad(cross_entropy_loss)(
        params, CFG, tokens, targets, positions
    )

    mesh = create_mesh(MeshConfig(pp=2), jax.devices()[:2])
    sharded = shard_params(params, CFG, mesh)
    pp_loss, pp_grads = jax.value_and_grad(cross_entropy_loss)(
        sharded, CFG, tokens, targets, positions, pp_mesh=mesh,
        pp_microbatches=2,
    )
    assert abs(float(ref_loss) - float(pp_loss)) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4
        ),
        ref_grads, pp_grads,
    )


def test_train_step_improves_under_pp():
    """Full 3D train step: dp=2 x pp=2 x tp=2 — the pipeline composes with
    data and tensor parallelism (tp stays GSPMD-automatic inside stages)."""
    mesh = create_mesh(MeshConfig(dp=2, pp=2, tp=2), jax.devices()[:8])
    init_state, train_step, shard_batch = make_train_step(
        CFG, mesh, pp_microbatches=2
    )
    state = init_state(init_params(jax.random.PRNGKey(0), CFG, jnp.float32))
    tokens, positions = _batch(jax.random.PRNGKey(1))
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    batch = shard_batch(tokens, targets, positions)

    losses = []
    for _ in range(6):
        state, loss = train_step(state, *batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_pipeline_validates_divisibility():
    mesh = create_mesh(MeshConfig(pp=2), jax.devices()[:2])
    cfg = dataclasses.replace(CFG, num_layers=3)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    tokens, positions = _batch(jax.random.PRNGKey(1), cfg=cfg)
    with pytest.raises(ValueError, match="divide num_layers"):
        pipeline_forward(params, cfg, tokens, positions, mesh, 2)
    with pytest.raises(ValueError, match="divide batch"):
        pipeline_forward(
            init_params(jax.random.PRNGKey(0), CFG, jnp.float32),
            CFG, tokens, positions, mesh, 3,
        )
