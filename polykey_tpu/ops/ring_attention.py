"""Ring attention: sequence-parallel attention over the ICI ring.

Long-context prefill/training beyond one chip's HBM (SURVEY.md §5's
long-context obligation): queries stay put, KV chunks rotate around the
`sp` mesh axis via `lax.ppermute`, and each device folds every visiting
chunk into online-softmax state (running max m, denominator l, fp32
accumulator — the same recurrence as ops/flash_attention.py, one ring hop
per block). Peak memory per device is O(T_local·D + S_local·D); the full
[T, S] logits matrix never exists anywhere.

Two entry points:
- `ring_attention` — the per-device body; call it inside `shard_map` with
  the KV/sequence dimension sharded over `axis_name`.
- `ring_attention_spmd` — convenience wrapper that builds the `shard_map`
  over a mesh with the framework's standard axes (batch over dp, sequence
  over sp, heads over tp; parallel/mesh.py).

Masking is by absolute position (q_positions / kv_positions travel with
their chunks), so causality is independent of how the ring is laid out.
XLA overlaps the ppermute with the block compute where the schedule allows;
collectives ride ICI by construction (sp is an ICI mesh axis).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_update(
    q,            # [B, T, Hq, D] (original dtype; math in fp32)
    k, v,         # [B, S, Hk, D] current chunk
    q_pos,        # [B, T]
    kv_pos,       # [B, S]
    m, l, acc,    # [B, Hq, T], [B, Hq, T], [B, T, Hq, D] fp32
    *,
    scale: float,
    logit_softcap: Optional[float],
    window: Optional[jax.Array],
):
    B, T, Hq, D = q.shape
    Hk = k.shape[2]
    g = Hq // Hk

    qg = q.reshape(B, T, Hk, g, D)
    s = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32
    ) * scale                                           # [B, Hk, g, T, S]
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)

    mask = kv_pos[:, None, :] <= q_pos[:, :, None]      # [B, T, S]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        mask &= (w <= 0) | (kv_pos[:, None, :] > q_pos[:, :, None] - w)
    s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)

    s = s.reshape(B, Hq, T, -1)
    m_cur = jnp.max(s, axis=-1)                         # [B, Hq, T]
    m_new = jnp.maximum(m, m_cur)
    # Explicit zero where masked: a fully-masked chunk has s == m_new ==
    # _NEG_INF and exp(0) would add spurious mass to l.
    p = jnp.exp(s - m_new[..., None])                   # [B, Hq, T, S]
    p = jnp.where(mask[:, None, :, :], p, 0.0)
    corr = jnp.exp(m - m_new)                           # [B, Hq, T]
    l_new = corr * l + jnp.sum(p, axis=-1)

    pg = p.reshape(B, Hk, g, T, -1)
    pv = jnp.einsum(
        "bhgts,bshd->bthgd", pg, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).reshape(B, T, Hq, D)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,             # [B, T_local, Hq, D]
    k: jax.Array,             # [B, S_local, Hk, D]
    v: jax.Array,
    q_positions: jax.Array,   # [B, T_local] absolute positions
    kv_positions: jax.Array,  # [B, S_local]
    *,
    axis_name: str,
    axis_size: int,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-device ring attention body (call inside shard_map).

    Rotates (k, v, kv_positions) `axis_size - 1` times around `axis_name`;
    returns [B, T_local, Hq, D] in q.dtype.
    """
    B, T, Hq, D = q.shape

    m0 = jnp.full((B, Hq, T), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, T), jnp.float32)
    acc0 = jnp.zeros((B, T, Hq, D), jnp.float32)

    update = functools.partial(
        _block_update, scale=scale, logit_softcap=logit_softcap, window=window
    )
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, i):
        k_c, v_c, kvp_c, m, l, acc = carry
        m, l, acc = update(q, k_c, v_c, q_positions, kvp_c, m, l, acc)

        def rotate(args):
            k_c, v_c, kvp_c = args
            return (
                jax.lax.ppermute(k_c, axis_name, perm),
                jax.lax.ppermute(v_c, axis_name, perm),
                jax.lax.ppermute(kvp_c, axis_name, perm),
            )

        k_c, v_c, kvp_c = jax.lax.cond(
            i < axis_size - 1, rotate, lambda a: a, (k_c, v_c, kvp_c)
        )
        return (k_c, v_c, kvp_c, m, l, acc), None

    (_, _, _, m, l, acc), _ = jax.lax.scan(
        step,
        (k, v, kv_positions, m0, l0, acc0),
        jnp.arange(axis_size),
    )

    l = jnp.maximum(l, 1e-9).transpose(0, 2, 1)[..., None]  # [B, T, Hq, 1]
    return (acc / l).astype(q.dtype)


def ring_attention_spmd(
    q: jax.Array,             # [B, T, Hq, D] (global shapes)
    k: jax.Array,             # [B, S, Hk, D]
    v: jax.Array,
    q_positions: jax.Array,   # [B, T]
    kv_positions: jax.Array,  # [B, S]
    mesh: Mesh,
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
    seq_axis: str = "sp",
    batch_axis: str = "dp",
    head_axis: str = "tp",
) -> jax.Array:
    """shard_map wrapper: batch over dp, sequence over sp, heads over tp.

    GQA constraint: num_kv_heads must be divisible by the tp axis size (the
    same constraint parallel/sharding.py places on the projections).
    """
    axis_size = mesh.shape[seq_axis]
    qkv_spec = P(batch_axis, seq_axis, head_axis, None)
    pos_spec = P(batch_axis, seq_axis)

    inner = functools.partial(
        ring_attention,
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
