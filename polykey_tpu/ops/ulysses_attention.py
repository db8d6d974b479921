"""Ulysses attention: head-sharded sequence parallelism via all-to-all.

The second long-context formulation SURVEY.md §5 owes (alongside ring
attention): instead of rotating KV chunks around the ring, one all-to-all
over the `sp` axis re-shards activations from sequence-sharded
[B, T/sp, H, D] to head-sharded [B, T, H/sp, D]; each device then runs
ordinary *local* full attention for its head subset over the whole
sequence, and a second all-to-all restores sequence sharding. Two
collectives per layer versus ring's sp-1 ppermutes — the better trade when
the head count covers the axis (H % sp == 0) and T fits per-device HBM at
H/sp heads; ring remains the fallback for very long T or few heads.

Masking is by absolute position (gathered alongside the exchange), so the
math is exactly the reference attention's — verified against it and
against the ring path in tests/test_ulysses.py.

No reference analog (the reference has no attention at all — SURVEY.md §5
long-context: "Absent"); design follows the DeepSpeed-Ulysses pattern from
PAPERS.md, re-expressed as jax.lax collectives under shard_map.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .attention import attention


def ulysses_attention(
    q: jax.Array,             # [B, T_local, Hq, D] sequence-sharded
    k: jax.Array,             # [B, T_local, Hk, D]
    v: jax.Array,
    q_positions: jax.Array,   # [B, T_local] absolute positions
    kv_positions: jax.Array,  # [B, T_local]
    *,
    axis_name: str,
    axis_size: int,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-device Ulysses body (call inside shard_map).

    Requires Hq % axis_size == 0 and Hk % axis_size == 0 (head counts as
    seen inside the map, i.e. after any tp sharding).
    """
    B, T_local, Hq, D = q.shape
    Hk = k.shape[2]
    if Hq % axis_size or Hk % axis_size:
        raise ValueError(
            f"Ulysses needs head counts divisible by the sp axis: "
            f"Hq={Hq}, Hk={Hk}, sp={axis_size} (use ring attention instead)"
        )

    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            tiled=True)
    # seq-sharded → head-sharded full sequence: [B, T, H/sp, D]
    q = a2a(q, split_axis=2, concat_axis=1)
    k = a2a(k, split_axis=2, concat_axis=1)
    v = a2a(v, split_axis=2, concat_axis=1)
    # Positions for the whole sequence travel with a (cheap) all-gather;
    # chunks concatenate in device order, matching the a2a's sequence
    # reassembly, so absolute-position masking is layout-independent.
    q_pos = jax.lax.all_gather(q_positions, axis_name, axis=1, tiled=True)
    kv_pos = jax.lax.all_gather(kv_positions, axis_name, axis=1, tiled=True)

    mask = kv_pos[:, None, :] <= q_pos[:, :, None]          # [B, T, T]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        mask &= (w <= 0) | (kv_pos[:, None, :] > q_pos[:, :, None] - w)

    ctx = attention(q, k, v, mask, scale=scale, logit_softcap=logit_softcap)

    # head-sharded → seq-sharded: [B, T_local, Hq, D]
    return a2a(ctx, split_axis=1, concat_axis=2)


def ulysses_attention_spmd(
    q: jax.Array,             # [B, T, Hq, D] (global shapes)
    k: jax.Array,             # [B, T, Hk, D]
    v: jax.Array,
    q_positions: jax.Array,   # [B, T]
    kv_positions: jax.Array,  # [B, T]
    mesh: Mesh,
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
    seq_axis: str = "sp",
    batch_axis: str = "dp",
    head_axis: str = "tp",
) -> jax.Array:
    """shard_map wrapper with the framework's standard axes (same contract
    as ring_attention_spmd: batch over dp, sequence over sp, heads over tp).
    """
    axis_size = mesh.shape[seq_axis]
    qkv_spec = P(batch_axis, seq_axis, head_axis, None)
    pos_spec = P(batch_axis, seq_axis)

    inner = functools.partial(
        ulysses_attention,
        axis_name=seq_axis,
        axis_size=axis_size,
        scale=scale,
        logit_softcap=logit_softcap,
        window=window,
    )
    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec, pos_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, q_positions, kv_positions)
