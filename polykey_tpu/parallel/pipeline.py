"""GPipe-style pipeline parallelism over the mesh's ``pp`` axis.

SURVEY.md §2b lists pipeline parallelism among the axes the framework owes
the north star; round 1 shipped only the memory distribution (layers stacked
on a leading axis sharded over ``pp``, parallel/sharding.py). This module
adds the actual stage schedule: microbatches enter at stage 0, flow
stage-to-stage over the ICI via ``lax.ppermute``, and every stage computes a
different microbatch concurrently.

Design (TPU-first, not a port — the reference has no ML code at all):

- **Partial-manual shard_map**: the stage loop is manual over ``pp`` only
  (``axis_names={"pp"}``); every other mesh axis (dp/tp/ep/sp) stays under
  GSPMD, so Megatron TP inside a stage keeps its compiler-inserted
  collectives — no hand-written all-reduces in the layer body.
- **One compiled schedule**: the tick loop is a ``lax.scan`` over
  M + P - 1 ticks (M microbatches, P stages). Stage p processes microbatch
  m = t - p at tick t; invalid (m out of range) lanes compute garbage that
  is never written — occupancy is data, not control flow, exactly like the
  engine's slot masks.
- **Same math as the unsharded stack**: stages run
  models.transformer.apply_layer — the identical block body ``lax.scan``
  uses — over their local layer slice, with global layer indices so
  Gemma-2's sliding-window interleaving lands on the right layers.
- **Autodiff = backward schedule**: ``ppermute``/``scan`` transpose cleanly,
  so ``jax.grad`` through this forward yields the mirrored reverse
  pipeline (grads flow stage P-1 → 0); no hand-written backward pass.

Bubble fraction is the GPipe (P-1)/(M+P-1); choose M ≥ ~4·P to amortize.
The collected outputs live on the last stage and are replicated with one
masked ``psum`` over ``pp`` — at [B, T, H] this is the layout where the
final-norm/unembed (vocab-sharded over tp) runs everywhere; a production
multi-pod layout would instead keep logits on the last stage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.transformer import (
    apply_layer,
    embed_tokens,
    make_causal_attend,
)
from ..models.layers import rms_norm


def pipeline_forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, T] int32
    positions: jax.Array,     # [B, T] int32
    mesh: Mesh,
    num_microbatches: int,
) -> jax.Array:
    """Run the transformer stack pipelined over ``mesh``'s pp axis.

    Returns hidden states [B, T, H] after the final norm — the same
    contract as ``forward(...)[0]`` on the no-cache path, so callers
    (train/train.py) unembed identically. Requires num_layers % pp == 0
    and batch % num_microbatches == 0.
    """
    n_stages = mesh.shape["pp"]
    M = num_microbatches
    B, T = tokens.shape
    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"pp={n_stages} must divide num_layers={cfg.num_layers}"
        )
    if B % M != 0:
        raise ValueError(f"microbatches={M} must divide batch={B}")
    norm_offset = 1.0 if cfg.scale_embeddings else 0.0

    x = embed_tokens(params, cfg, tokens)               # [B, T, H]
    hidden = _staged(cfg, mesh, M, B, T)(params["layers"], x, positions)

    return rms_norm(
        hidden, params["final_norm"], cfg.rms_norm_eps, norm_offset
    )


@functools.lru_cache(maxsize=32)
def _staged(cfg: ModelConfig, mesh: Mesh, M: int, B: int, T: int):
    """Jitted pipelined stack, memoized per (cfg, mesh, M, B, T) so eager
    callers hit the jit cache instead of re-tracing the schedule per call
    (cfg and Mesh are hashable; the layer pytree is a runtime argument)."""
    n_stages = mesh.shape["pp"]
    layers_per_stage = cfg.num_layers // n_stages

    def stage_fn(local_layers, x, positions):
        # Manual over pp: local_layers is this stage's [L/P, ...] slice;
        # x/positions are pp-replicated (dp/tp shardings stay automatic).
        p = lax.axis_index("pp")

        xs = x.reshape(M, B // M, T, -1)
        pos = positions.reshape(M, B // M, T)

        def run_local(x_in, pos_in):
            attend = make_causal_attend(cfg, pos_in)

            def body(h, scanned):
                lp, idx = scanned
                h, _ = apply_layer(lp, idx, h, pos_in, cfg, attend, None)
                return h, None

            idxs = p * layers_per_stage + jnp.arange(
                layers_per_stage, dtype=jnp.int32
            )
            h, _ = lax.scan(body, x_in, (local_layers, idxs))
            return h

        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        x_state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            x_state, outs = carry
            m = t - p                                   # this stage's microbatch
            m_c = jnp.clip(m, 0, M - 1)
            valid = jnp.logical_and(m >= 0, m < M)
            inject = jnp.logical_and(p == 0, t < M)     # stage 0 feeds in
            x_in = jnp.where(
                inject,
                lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, M - 1), 0, False),
                x_state,
            )
            # Positions are pp-replicated input — index the local copy by
            # microbatch instead of rotating them over the ICI.
            pos_in = lax.dynamic_index_in_dim(pos, m_c, 0, False)
            y = run_local(x_in, pos_in)
            # Last stage banks finished microbatches.
            write = jnp.logical_and(valid, p == n_stages - 1)
            prev = lax.dynamic_index_in_dim(outs, m_c, 0, False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, prev), m_c, 0
            )
            # Rotate activations to the next stage.
            x_next = lax.ppermute(y, "pp", perm)
            return (x_next, outs), None

        (x_state, outs), _ = lax.scan(
            tick,
            (x_state, outs),
            jnp.arange(M + n_stages - 1, dtype=jnp.int32),
        )
        # Results live on the last stage only; masked psum replicates.
        outs = jnp.where(p == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = lax.psum(outs, "pp")
        return outs.reshape(B, T, -1)

    # Partial-manual shard_map (manual pp, auto dp/tp/ep) only traces under
    # jit — eager mode rejects out_specs that leave auto axes unmentioned.
    # The jit is inlined when callers are already tracing (train_step).
    return jax.jit(jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P("pp"), P(), P()),
        out_specs=P(),
        axis_names=frozenset({"pp"}),
        check_vma=False,
    ))
