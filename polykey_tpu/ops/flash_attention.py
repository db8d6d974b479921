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
    # scalar prefetch: read by the grid and the K/V index maps, not by
    # the body
    steps_ref,    # [1] int32 (SMEM)
    first_ref,    # [B * nq] int32 (SMEM)
    last_ref,     # [B * nq] int32 (SMEM)
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
    window = win_ref[0, 0]

    # Skip blocks fully outside [q_pos - window, q_pos]: no query row in this
    # q block can see any key in this k block (saves MXU work; the causal
    # upper-right triangle of blocks is ~half the grid). The K/V index maps
    # hold such a step on the nearest needed block (`_needed_blocks`), so
    # it is not fetched either.
    max_qpos = jnp.max(q_pos)
    min_qpos = jnp.min(jnp.where(q_pos < 0, jnp.int32(2**30), q_pos))
    block_lo, block_hi = j * bk, j * bk + bk - 1
    needed = (block_lo <= max_qpos) & (
        (window <= 0) | (block_hi > min_qpos - window)
    )
    # A block whose SECOND half no query can see — a window at the head of
    # its table under a key block twice its size — is multiplied by its
    # first half alone; every other needed block in one pass (wide blocks
    # are what keep the MXU fed at long contexts).
    half = bk // 2 if bk % 256 == 0 else bk
    head_only = block_lo + half > max_qpos

    def attend(keys: int):
        """One online-softmax step over the block's leading `keys` keys."""
        kv_pos = block_lo + jax.lax.broadcasted_iota(
            jnp.int32, (bq, keys), dimension=1
        )                                                     # [bq, keys]
        if native:
            s = scale * jax.lax.dot_general(
                q_ref[0, 0], k_ref[0, 0, :keys],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            q = q_ref[0, 0].astype(jnp.float32) * scale
            k = k_ref[0, 0, :keys]
            s = jax.lax.dot_general(
                q, k.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                 # [bq, keys]
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
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)          # [bq, keys]
        corr = jnp.exp(m_prev - m_new)                        # [bq, 1]

        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if native:
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0, :keys],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                p, v_ref[0, 0, :keys].astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if half == bk:
        pl.when(needed)(lambda: attend(bk))
    else:
        pl.when(needed & head_only)(lambda: attend(half))
        pl.when(needed & jnp.logical_not(head_only))(lambda: attend(bk))

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
    steps: jax.Array,         # [1] int32: `_needed_blocks`
    first: jax.Array,         # [B * nq] int32
    last: jax.Array,
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
    nq = Tp // block_q

    # The key axis is walked as far as the furthest query of the CALL sees
    # (a dynamic bound: a window at the head of a long table costs its own
    # blocks' steps, not the table's); within that, each query block is
    # held on the blocks it needs.
    grid = (B * Hq, nq, steps[0])
    kernel = functools.partial(
        _kernel,
        scale=scale,
        logit_softcap=logit_softcap,
        kv_len=kv_len,
        bk=block_k,
        native=native,
    )

    def kv_block(bh, i, j, steps, first, last):
        # A key block no query of this query block can see is not worth
        # a fetch: the step is held on the nearest block that is needed,
        # and a block index that does not change is not fetched again.
        at = (bh // Hq) * nq + i
        return (bh // Hq, (bh % Hq) // groups,
                jnp.clip(j, first[at], last[at]), 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_q, D),
                    lambda bh, i, j, *_: (bh // Hq, bh % Hq, i, 0),
                ),
                pl.BlockSpec((1, 1, block_k, D), kv_block),
                pl.BlockSpec((1, 1, block_k, D), kv_block),
                pl.BlockSpec(
                    (1, 1, 1, block_q),
                    lambda bh, i, j, *_: (bh // Hq, i, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1), lambda bh, i, j, *_: (0, 0),
                    memory_space=pltpu.SMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, block_q, D),
                lambda bh, i, j, *_: (bh // Hq, bh % Hq, i, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
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
    )(steps, first, last, q, k, v, q_positions, window)


def _needed_blocks(q_positions, window, block_k: int, nk: int):
    """(steps, first, last): the key blocks the call walks, [1] int32, and
    the first and last key block each query block needs, [B * nq] int32
    each — the blocks that hold positions min_qpos - window + 1 ..
    max_qpos, by the kernel's own `needed` rule (a padded query row, -1,
    sees nothing; a query block of padding alone needs no block and is
    held on block 0). `steps` is the furthest `last` + 1."""
    B, nq = q_positions.shape[:2]
    hi = jnp.max(q_positions, axis=(2, 3))
    lo = jnp.min(
        jnp.where(q_positions < 0, jnp.int32(2**30), q_positions), axis=(2, 3)
    )
    last = jnp.clip(hi // block_k, 0, nk - 1)
    w = window[0, 0]
    first = jnp.where(w > 0, jnp.maximum(lo - w + 1, 0) // block_k, 0)
    first = jnp.minimum(first, last)
    steps = (jnp.max(last) + 1).reshape(1)
    return steps, first.reshape(B * nq), last.reshape(B * nq)


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


def runs_kernel(T: int, S: int, head_dim: int, *, mesh=None,
                force_kernel: bool = False, interpret: bool = False) -> bool:
    """Whether `flash_attention` at these shapes runs the kernel or the
    masked reference (`use_flash`, unless forced; a mesh that shards sp or
    tp under pp > 1 takes the reference: per-layer activations are
    stage-local there, not replicated, and the masked reference is
    GSPMD-partitionable as it is). A caller that lays K and V out for
    their reader asks this first (ops/paged_attention.py `prefill_stage`)."""
    if not (force_kernel or interpret or use_flash(T, S, head_dim)):
        return False
    shape = mesh.shape if mesh is not None else {}
    sharded = shape.get("sp", 1) > 1 or shape.get("tp", 1) > 1
    return not (sharded and shape.get("pp", 1) > 1)


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
    kv_heads_major: bool = False,
) -> jax.Array:
    """Blockwise attention; same contract as the reference `attention` but
    masking is derived from positions in-kernel. Returns [B, T, Hq, D].

    `kv_heads_major`: k and v arrive as [B, Hk, S, D], the layout the
    kernel reads (a caller that fills them in place, block by block:
    ops/paged_attention.py `gather_needed_pages`); what lies past the
    queries' positions is masked, and past the furthest query's block not
    even walked.

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
    S, Hk = (k.shape[2], k.shape[1]) if kv_heads_major else k.shape[1:3]

    def reference():
        kr, vr = ((jnp.transpose(x, (0, 2, 1, 3)) for x in (k, v))
                  if kv_heads_major else (k, v))
        mask = make_attention_mask(q_positions, S)
        if window is not None:
            kv_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
            w = jnp.asarray(window, jnp.int32)
            mask &= (w <= 0) | (kv_pos > q_positions[:, :, None] - w)
        return attention(
            q, kr, vr, mask, scale=scale, logit_softcap=logit_softcap
        )

    if not runs_kernel(T, S, D, mesh=mesh, force_kernel=force_kernel,
                       interpret=interpret):
        return reference()

    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if sp > 1 or tp > 1:
        if T % sp or Hq % tp or Hk % tp:
            # Never fall through to an unwrapped pallas_call on sharded
            # operands — GSPMD would all-gather them (or fail to compile)
            # with no pointer at the real cause.
            raise ValueError(
                f"flash kernel on mesh: T={T} %% sp={sp}, Hq={Hq} / "
                f"Hk={Hk} %% tp={tp} must divide evenly"
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
                kv_heads_major=kv_heads_major,
            )

        w = (jnp.zeros((1,), jnp.int32) if window is None
             else jnp.asarray(window, jnp.int32).reshape(1))
        kv_spec = (P(None, "tp", None, None) if kv_heads_major
                   else P(None, None, "tp", None))
        sm = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                P(None, "sp", "tp", None),    # q
                kv_spec,                      # k (full window per shard)
                kv_spec,                      # v
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
    kt, vt = (
        _pad_to(x if kv_heads_major else jnp.transpose(x, (0, 2, 1, 3)),
                2, block_k)
        for x in (k, v)
    )
    qpos = _pad_to(q_positions.astype(jnp.int32), 1, block_q, value=-1)
    qpos = qpos.reshape(B, -1, 1, block_q)
    if window is None:
        win = jnp.zeros((1, 1), jnp.int32)
    else:
        win = jnp.asarray(window, jnp.int32).reshape(1, 1)

    out = _flash_bhsd(
        qt, kt, vt, qpos, win,
        *_needed_blocks(qpos, win, block_k, kt.shape[2] // block_k),
        scale=scale,
        logit_softcap=logit_softcap,
        kv_len=S,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        native=native,
    )
    return jnp.transpose(out[:, :, :T], (0, 2, 1, 3))
