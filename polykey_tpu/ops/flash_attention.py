"""Pallas flash attention (TPU): blockwise online-softmax prefill kernel.

Replaces the O(T·S) materialized-logits reference (ops/attention.py) on the
prefill hot path: logits never leave VMEM, softmax statistics (running max m,
running denominator l) and the output accumulator live in per-block scratch,
and the S dimension streams through the innermost grid axis — HBM traffic is
O(T·D + S·D) instead of O(T·S).

Covers everything the served families need (models/config.py): GQA, causal
masking by absolute position, Gemma-2 attention-logit soft-capping and
(dynamic, per-layer) sliding windows. Numerics: q·kᵀ and the softmax run in
fp32 (preferred_element_type), matching the reference oracle; tests compare
the two directly.

The wrapper pads T/S to block multiples and falls back to the reference
implementation off-TPU or for tiny shapes, so every call site can use
`flash_attention` unconditionally.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import attention, make_attention_mask

_NEG_INF = -1e30
# Lane width: the m/l scratch rows are (bq, 128) with the statistic
# replicated across the lane dimension (min tile constraint).
_LANES = 128


def _kernel(
    # inputs (blocked)
    q_ref,        # [1, 1, bq, D]
    k_ref,        # [1, 1, bk, D]
    v_ref,        # [1, 1, bk, D]
    qpos_ref,     # [1, 1, 1, bq] int32 (VMEM; shaped for tiling rules)
    win_ref,      # [1, 1] int32 (SMEM) — sliding window, <=0 means global
    # outputs
    out_ref,      # [1, 1, bq, D]
    # scratch
    m_ref,        # [bq, 128] fp32
    l_ref,        # [bq, 128] fp32
    acc_ref,      # [bq, D] fp32
    *,
    scale: float,
    logit_softcap: Optional[float],
    kv_len: int,  # true (unpadded) S
    bk: int,
    native: bool = False,  # products on the operands' own dtype
):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bq = q_ref.shape[2]
    q_pos = qpos_ref[0, 0, 0][:, None]                        # [bq, 1]
    kv_pos = j * bk + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), dimension=1
    )                                                         # [bq, bk]
    window = win_ref[0, 0]

    # Skip blocks fully outside [q_pos - window, q_pos]: no query row in this
    # q block can see any key in this k block (saves MXU work; the causal
    # upper-right triangle of blocks is ~half the grid).
    max_qpos = jnp.max(q_pos)
    min_qpos = jnp.min(jnp.where(q_pos < 0, jnp.int32(2**30), q_pos))
    block_lo, block_hi = j * bk, j * bk + bk - 1
    needed = (block_lo <= max_qpos) & (
        (window <= 0) | (block_hi > min_qpos - window)
    )

    @pl.when(needed)
    def _block():
        if native:
            s = scale * jax.lax.dot_general(
                q_ref[0, 0], k_ref[0, 0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            q = q_ref[0, 0].astype(jnp.float32) * scale
            k = k_ref[0, 0]
            s = jax.lax.dot_general(
                q, k.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                 # [bq, bk]
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        mask = (kv_pos <= q_pos) & (kv_pos < kv_len)
        mask &= (window <= 0) | (kv_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                                 # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Explicit mask on p: when a block is fully masked, s - m_new == 0
        # everywhere and exp would contribute bk spurious units to l.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)          # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                        # [bq, 1]

        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if native:
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                p, v_ref[0, 0].astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-9)
        out_ref[0, 0] = (acc_ref[:] / l).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "logit_softcap", "kv_len", "block_q", "block_k", "interpret",
        "native",
    ),
)
def _flash_bhsd(
    q: jax.Array,             # [B, Hq, Tp, D]
    k: jax.Array,             # [B, Hk, Sp, D]
    v: jax.Array,
    q_positions: jax.Array,   # [B, nq, 1, bq] int32 (padding rows = -1)
    window: jax.Array,        # [1, 1] int32 (<=0 → global)
    *,
    scale: float,
    logit_softcap: Optional[float],
    kv_len: int,
    block_q: int,
    block_k: int,
    interpret: bool,
    native: bool = False,
) -> jax.Array:
    B, Hq, Tp, D = q.shape
    Hk, Sp = k.shape[1], k.shape[2]
    groups = Hq // Hk
    nq, nk = Tp // block_q, Sp // block_k

    grid = (B * Hq, nq, nk)
    kernel = functools.partial(
        _kernel,
        scale=scale,
        logit_softcap=logit_softcap,
        kv_len=kv_len,
        bk=block_k,
        native=native,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D),
                lambda bh, i, j: (bh // Hq, bh % Hq, i, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda bh, i, j: (bh // Hq, (bh % Hq) // groups, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda bh, i, j: (bh // Hq, (bh % Hq) // groups, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, block_q), lambda bh, i, j: (bh // Hq, i, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1), lambda bh, i, j: (0, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda bh, i, j: (bh // Hq, bh % Hq, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * Hq * Tp * Sp * D,
            bytes_accessed=(
                q.size + k.size + v.size + q.size
            ) * q.dtype.itemsize,
            transcendentals=B * Hq * Tp * Sp,
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v, q_positions, window)


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# Head dims proven against Mosaic's 128-lane tiling (the served families
# use 64/128/256; an odd D like 40 or 72 must take the reference fallback
# rather than risk a kernel compile failure on hardware — ADVICE r1).
_FLASH_HEAD_DIMS = frozenset({64, 128, 256})


def use_flash(T: int, S: int, head_dim: int) -> bool:
    """Dispatch policy: the kernel wins when the logits matrix is large
    enough that not materializing it matters; the reference path keeps tiny
    shapes (decode against short caches, unit tests), unusual head dims,
    and non-TPU backends."""
    return (
        jax.default_backend() == "tpu"
        and T >= 128
        and S >= 128
        and head_dim in _FLASH_HEAD_DIMS
    )


def flash_attention(
    q: jax.Array,             # [B, T, Hq, D]
    k: jax.Array,             # [B, S, Hk, D]
    v: jax.Array,
    q_positions: jax.Array,   # [B, T] absolute positions
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,   # scalar; None/<=0 → global
    block_q: int = 512,
    block_k: int = 1024,
    interpret: bool = False,
    force_kernel: bool = False,
    mesh=None,                # serving mesh → shard_map the kernel
    native: bool = False,
) -> jax.Array:
    """Blockwise attention; same contract as the reference `attention` but
    masking is derived from positions in-kernel. Returns [B, T, Hq, D].

    `native` (one device only): both products take their operands in the
    dtype they arrive in (bf16 on the MXU, float32 sums) instead of
    float32 copies, and the scale goes on the sums — for a caller whose
    attention is arithmetic-bound at these widths (the latent layers'
    absorbed form, ops/paged_attention.latent_attention).

    With a mesh whose sp/tp extents exceed 1 the kernel runs under
    shard_map: the query/time axis shards over sp (each shard computes
    its query block against the FULL key window — masks come from the
    global positions, so blockwise attention is embarrassingly parallel
    over T), heads over tp. GSPMD cannot partition an opaque pallas_call
    and would otherwise all-gather the sharded operands.
    """
    B, T, Hq, D = q.shape
    S = k.shape[1]

    if not (force_kernel or interpret or use_flash(T, S, D)):
        mask = make_attention_mask(q_positions, S)
        if window is not None:
            kv_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
            w = jnp.asarray(window, jnp.int32)
            mask &= (w <= 0) | (kv_pos > q_positions[:, :, None] - w)
        return attention(
            q, k, v, mask, scale=scale, logit_softcap=logit_softcap
        )

    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if (sp > 1 or tp > 1) and mesh.shape.get("pp", 1) > 1:
        # Per-layer activations are stage-local under pp, not replicated —
        # the shard_map specs below would be wrong (and check_vma=False
        # would hide it). The masked reference path is GSPMD-partitionable
        # as-is, so pp>1 meshes take it.
        mask = make_attention_mask(q_positions, S)
        if window is not None:
            kv_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
            w = jnp.asarray(window, jnp.int32)
            mask &= (w <= 0) | (kv_pos > q_positions[:, :, None] - w)
        return attention(
            q, k, v, mask, scale=scale, logit_softcap=logit_softcap
        )
    if sp > 1 or tp > 1:
        if T % sp or Hq % tp or k.shape[2] % tp:
            # Never fall through to an unwrapped pallas_call on sharded
            # operands — GSPMD would all-gather them (or fail to compile)
            # with no pointer at the real cause.
            raise ValueError(
                f"flash kernel on mesh: T={T} %% sp={sp}, Hq={Hq} / "
                f"Hk={k.shape[2]} %% tp={tp} must divide evenly"
            )
        from jax.sharding import PartitionSpec as P

        def inner(q, k, v, qpos, w):
            # window passes as an explicit operand (it can be a traced
            # per-layer scalar — shard_map must not close over tracers);
            # the kernel treats w <= 0 as global attention.
            return flash_attention(
                q, k, v, qpos,
                scale=scale, logit_softcap=logit_softcap, window=w,
                block_q=block_q, block_k=block_k, interpret=interpret,
                force_kernel=True,  # dispatch decided here, global shapes
            )

        w = (jnp.zeros((1,), jnp.int32) if window is None
             else jnp.asarray(window, jnp.int32).reshape(1))
        sm = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                P(None, "sp", "tp", None),    # q
                P(None, None, "tp", None),    # k (full window per shard)
                P(None, None, "tp", None),    # v
                P(None, "sp"),                # q_positions
                P(None),                      # window
            ),
            out_specs=P(None, "sp", "tp", None),
            check_vma=False,
        )
        return sm(q, k, v, q_positions, w)

    # Shrink blocks toward small shapes, staying on 128-multiples (the
    # wrapper pads T/S up to one block in that case). Benchmarked on v5e:
    # 512x1024 blocks run ~26x faster than 128x128 (MXU utilization).
    def _fit(block: int, size: int) -> int:
        return min(block, ((size + 127) // 128) * 128)

    block_q = _fit(block_q, T)
    block_k = _fit(block_k, S)

    qt = _pad_to(jnp.transpose(q, (0, 2, 1, 3)), 2, block_q)
    kt = _pad_to(jnp.transpose(k, (0, 2, 1, 3)), 2, block_k)
    vt = _pad_to(jnp.transpose(v, (0, 2, 1, 3)), 2, block_k)
    qpos = _pad_to(q_positions.astype(jnp.int32), 1, block_q, value=-1)
    qpos = qpos.reshape(B, -1, 1, block_q)
    if window is None:
        win = jnp.zeros((1, 1), jnp.int32)
    else:
        win = jnp.asarray(window, jnp.int32).reshape(1, 1)

    out = _flash_bhsd(
        qt, kt, vt, qpos, win,
        scale=scale,
        logit_softcap=logit_softcap,
        kv_len=S,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        native=native,
    )
    return jnp.transpose(out[:, :, :T], (0, 2, 1, 3))
