"""Ragged paged attention: one kernel, one dispatch, for mixed
prefill+decode (ISSUE 12; PAPERS.md "Ragged Paged Attention").

The decode kernel (ops/paged_attention_kernel.py) serves ONE token per
sequence per dispatch, and prefill windows take a separate bucketed
gather dispatch — so every engine-loop iteration with admissions pays
two executables and the bucket table's padding. This kernel consumes a
FLAT token stream `[T, Hq, D]` covering both phases at once: each
sequence s owns the contiguous row range
``[seq_starts[s], seq_starts[s] + seq_lens[s])`` (a decode lane is a
ragged sequence of length 1; a prefill chunk is one of length `take`),
attends over its own paged KV window ``[0, kv_lens[s])`` through its
page-table row, and rows outside every range are padding that computes
masked garbage. One grid dimension tiles the token stream in
``token_tile``-row tiles; a tile may span several sequences (scalar-
prefetched ``tile_lo/tile_hi`` name the overlap range), so decode
singles PACK — 48 decode lanes cost ceil(48/tile) programs, not 48.

Per (tile, sequence) the kernel streams that sequence's visible pages
HBM → VMEM in double-buffered GROUPS of ``pages_per_block`` exactly as
the decode kernel does (per-page DMA latency amortizes G×, the group's
attention block is MXU-shaped), accumulating online-softmax state
(running max m, denominator l, fp32 accumulator) per (row, head). Rows
that do not belong to the sequence being processed see all-masked
logits, so their state passes through untouched — the row-disjointness
that makes a multi-sequence tile correct. The query position of row i
in sequence s is ``kv_lens[s] - seq_lens[s] + (i - seq_starts[s])``;
causal masking within a sequence's new tokens, GQA, logit soft-capping,
dynamic sliding windows, and the int8-KV quantized variant (scale-page
DMA + in-kernel dequant) all follow the decode kernel's recurrences.

Output is NORMALIZED ``[T, Hq, D]`` — the ragged batch is not
context-parallel-sharded (the engine's ragged mode serves tp-only
meshes; dp/sp route through the gather path), so no cross-shard
softmax merge is needed.

Falls back to the gather implementation off-TPU (`use_ragged_kernel`
gate, POLYKEY_DISABLE_RAGGED_KERNEL kill-switch — the
POLYKEY_DISABLE_PAGED_KERNEL pattern); the gather path
(`ragged_gather_attention`) reuses ops/paged_attention.paged_attention
with one row per token, which is the bit-identity reference: per token
it is EXACTLY the computation the bucketed engine paths run, so greedy
streams match token-for-token (tests/test_ragged.py pins this).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Default token-tile width: flat streams must be a multiple of this.
# Load-bearing beyond this module — the engine pads its ragged stream
# width against it (engine.py _ragged_width) and graphlint's contracts
# use it; change it HERE, not at call sites.
TOKEN_TILE = 8


def _ragged_kernel(
    # scalar prefetch
    starts_ref,    # [S] int32 flat-stream row where each sequence begins
    lens_ref,      # [S] int32 new-token count per sequence
    kv_ref,        # [S] int32 KV length per sequence (new tokens incl.)
    pt_ref,        # [S, P] int32 page tables
    tile_ref,      # [nT, 2] int32 per-tile sequence overlap [lo, hi)
    win_ref,       # [1] int32 sliding window (<=0 → global)
    # then positionally (arity varies with `quantized`):
    # inputs: q [TT, Hq, D] VMEM tile; k/v pages [N, ps, Hk·D] HBM
    #         (+ ks/vs scale pages [N, ps, Hk] when quantized)
    # outputs: out [TT, Hq, D] f32, normalized
    # scratch: k/v bufs [2, G, ps, Hk·D] (+ scale bufs) + DMA semaphores
    *refs,
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    num_tables: int,        # P — static max pages per sequence
    groups: int,            # Hq // Hk
    pages_per_block: int,   # G — pages per buffer slot (DMAs in flight)
    token_tile: int,        # TT — flat-stream rows per grid program
    quantized: bool = False,
):
    if quantized:
        (q_ref, k_pages_ref, v_pages_ref, ks_pages_ref, vs_pages_ref,
         out_ref,
         k_buf, v_buf, ks_buf, vs_buf,
         k_sems, v_sems, ks_sems, vs_sems) = refs
    else:
        (q_ref, k_pages_ref, v_pages_ref, out_ref,
         k_buf, v_buf, k_sems, v_sems) = refs
        ks_pages_ref = vs_pages_ref = None
        ks_buf = vs_buf = ks_sems = vs_sems = None
    t = pl.program_id(0)
    s_lo = tile_ref[t, 0]
    s_hi = tile_ref[t, 1]
    window = win_ref[0]
    TT = token_tile
    G = pages_per_block
    W = G * page_size
    n_groups = (num_tables + G - 1) // G            # static
    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hk = Hq // groups
    g = groups

    # Per-head query blocks [TT·g, D]: head h's group of g query heads,
    # rows ordered (token, group-head) so a contiguous reshape recovers
    # [TT, g, D] at write-out. Mosaic lowers plain 2D matmuls only (the
    # decode kernel's constraint), so heads unroll statically.
    q_scaled = q_ref[...].astype(jnp.float32) * scale     # [TT, Hq, D]
    q_heads = [
        q_scaled[:, h * g:(h + 1) * g, :].reshape(TT * g, D)
        for h in range(Hk)
    ]
    # Flat-stream row index of each tile row, and its expansion over the
    # per-head row blocks (row r of a [TT·g, ·] block belongs to token
    # r // g).
    row_ids1 = t * TT + jax.lax.broadcasted_iota(
        jnp.int32, (TT, 1), dimension=0
    )                                                     # [TT, 1]
    rows_g = t * TT + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (TT * g, 1), dimension=0), g
    )                                                     # [TT·g, 1]

    def page_dma(s, p, slot, j, pages_ref, buf, sems):
        return pltpu.make_async_copy(
            pages_ref.at[pt_ref[s, p]], buf.at[slot, j], sems.at[slot, j]
        )

    def start_group(s, blk, slot, lo, hi):
        for j in range(G):
            p = blk * G + j

            @pl.when((p >= lo) & (p < hi))
            def _go(p=p, j=j):
                page_dma(s, p, slot, j, k_pages_ref, k_buf, k_sems).start()
                page_dma(s, p, slot, j, v_pages_ref, v_buf, v_sems).start()
                if quantized:
                    page_dma(s, p, slot, j, ks_pages_ref, ks_buf,
                             ks_sems).start()
                    page_dma(s, p, slot, j, vs_pages_ref, vs_buf,
                             vs_sems).start()

    def wait_group(s, blk, slot, lo, hi):
        for j in range(G):
            p = blk * G + j

            @pl.when((p >= lo) & (p < hi))
            def _wait(p=p, j=j):
                page_dma(s, p, slot, j, k_pages_ref, k_buf, k_sems).wait()
                page_dma(s, p, slot, j, v_pages_ref, v_buf, v_sems).wait()
                if quantized:
                    page_dma(s, p, slot, j, ks_pages_ref, ks_buf,
                             ks_sems).wait()
                    page_dma(s, p, slot, j, vs_pages_ref, vs_buf,
                             vs_sems).wait()

    def seq_body(s, carry):
        # Rows of sequence s inside this tile, and their query positions
        # (kv_len - seq_len + row - seq_start). Unselected rows carry
        # garbage positions that the all-masked logits neutralize.
        start = starts_ref[s]
        length = lens_ref[s]
        kv_len = kv_ref[s]
        sel1 = (row_ids1 >= start) & (row_ids1 < start + length)  # [TT,1]
        pos1 = kv_len - length + (row_ids1 - start)               # [TT,1]
        pos_g = kv_len - length + (rows_g - start)                # [TT·g,1]
        sel_g = (rows_g >= start) & (rows_g < start + length)

        # Visible page range for THIS tile's rows of s: the newest
        # selected row bounds hi, the oldest (minus the window) bounds
        # lo. No selected rows → max_pos = -1 → empty range, loop skips.
        max_pos = jnp.max(jnp.where(sel1, pos1, -1))
        min_pos = jnp.min(jnp.where(sel1, pos1, jnp.int32(2 ** 30)))
        hi = jnp.minimum(
            jax.lax.div(max_pos, page_size) + 1, num_tables
        )
        hi = jnp.maximum(hi, 0)
        lo = jnp.where(
            window > 0,
            jnp.maximum(jax.lax.div(min_pos - window + 1, page_size), 0),
            0,
        )
        blo = jax.lax.div(lo, G)
        bhi = jax.lax.div(hi + G - 1, G)

        @pl.when(lo < hi)
        def _first():
            start_group(s, blo, blo % 2, lo, hi)

        def group_body(blk, carry):
            def run(carry):
                slot = blk % 2

                @pl.when(blk + 1 < bhi)
                def _next():
                    start_group(s, blk + 1, (blk + 1) % 2, lo, hi)

                wait_group(s, blk, slot, lo, hi)
                k = k_buf[slot].reshape(W, Hk * D)
                v = v_buf[slot].reshape(W, Hk * D)
                if quantized:
                    ks2 = ks_buf[slot].reshape(W, Hk).astype(jnp.float32)
                    vs2 = vs_buf[slot].reshape(W, Hk).astype(jnp.float32)

                kv_pos1 = blk * W + jax.lax.broadcasted_iota(
                    jnp.int32, (W, 1), dimension=0
                )                                             # [W, 1]
                valid1 = (
                    (kv_pos1 >= lo * page_size)
                    & (kv_pos1 < hi * page_size)
                )
                # Rows of pages never DMA'd hold stale VMEM; zero V (and
                # its scales) there so masked weights cannot multiply
                # NaN garbage — 0·NaN would poison the accumulator.
                v = jnp.where(valid1, v.astype(jnp.float32), 0.0)
                if quantized:
                    vs2 = jnp.where(valid1, vs2, 0.0)

                kv_pos_row = blk * W + jax.lax.broadcasted_iota(
                    jnp.int32, (TT * g, W), dimension=1
                )
                mask = sel_g & (kv_pos_row <= pos_g)
                mask &= (window <= 0) | (kv_pos_row > pos_g - window)
                mask &= valid1.reshape(1, W)

                new_carry = []
                for h in range(Hk):
                    m, l, acc = carry[h]
                    kk = k[:, h * D:(h + 1) * D].astype(jnp.float32)
                    vv = v[:, h * D:(h + 1) * D]
                    if quantized:
                        kk = kk * ks2[:, h:h + 1]
                        vv = vv * vs2[:, h:h + 1]
                    s_h = jax.lax.dot_general(
                        q_heads[h], kk,
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )                                         # [TT·g, W]
                    if logit_softcap is not None:
                        s_h = logit_softcap * jnp.tanh(s_h / logit_softcap)
                    s_h = jnp.where(mask, s_h, _NEG_INF)
                    # Online-softmax update. Rows outside sequence s are
                    # all-masked: m_cur = -inf → m_new = m, corr = 1,
                    # pexp = 0 → their state passes through untouched.
                    m_cur = jnp.max(s_h, axis=1, keepdims=True)
                    m_new = jnp.maximum(m, m_cur)
                    pexp = jnp.where(mask, jnp.exp(s_h - m_new), 0.0)
                    corr = jnp.exp(m - m_new)
                    l_new = corr * l + jnp.sum(pexp, axis=1, keepdims=True)
                    pv = jax.lax.dot_general(
                        pexp, vv,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )                                         # [TT·g, D]
                    new_carry.append((m_new, l_new, acc * corr + pv))
                return tuple(new_carry)

            return jax.lax.cond(
                (lo < hi) & (blk >= blo) & (blk < bhi),
                run, lambda c: c, carry,
            )

        return jax.lax.fori_loop(0, n_groups, group_body, carry)

    init = tuple(
        (
            jnp.full((TT * g, 1), _NEG_INF, jnp.float32),
            jnp.zeros((TT * g, 1), jnp.float32),
            jnp.zeros((TT * g, D), jnp.float32),
        )
        for _ in range(Hk)
    )
    final = jax.lax.fori_loop(s_lo, s_hi, seq_body, init)
    for h in range(Hk):
        _, l, acc = final[h]
        # Padding rows (no sequence) keep l = 0 → output 0.
        out = (acc / jnp.maximum(l, 1e-9)).reshape(TT, g, D)
        out_ref[:, h * g:(h + 1) * g, :] = out


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "logit_softcap", "interpret", "pages_per_block",
        "token_tile",
    ),
)
def _ragged_call(
    q: jax.Array,             # [T, Hq, D] flat token stream (tile-padded)
    k_pages,                  # [N, ps, Hk·D], or (values, scales) pairs
    v_pages,                  #   for int8 KV (scales [N, ps, Hk] bf16)
    page_tables: jax.Array,   # [S, P] int32
    seq_starts: jax.Array,    # [S] int32
    seq_lens: jax.Array,      # [S] int32
    kv_lens: jax.Array,       # [S] int32
    window: jax.Array,        # [1] int32
    *,
    scale: float,
    logit_softcap: Optional[float],
    interpret: bool,
    pages_per_block: int = 0,   # 0 → auto
    token_tile: int = TOKEN_TILE,
):
    """Returns NORMALIZED attention [T, Hq, D] f32 for every row that
    belongs to a sequence (padding rows read 0). T must be a multiple of
    `token_tile`; sequences must occupy ascending, non-overlapping row
    ranges (the engine's ragged batch builder guarantees both)."""
    quantized = isinstance(k_pages, tuple)
    if quantized:
        (k_pages, ks_pages), (v_pages, vs_pages) = k_pages, v_pages
    T, Hq, D = q.shape
    _, ps, folded = k_pages.shape          # stored layout: Hk·D in lanes
    Hk = folded // D
    S, P = page_tables.shape
    TT = token_tile
    if T % TT:
        raise ValueError(
            f"ragged token stream T={T} must be a multiple of "
            f"token_tile={TT} (the engine pads the stream)"
        )
    if pages_per_block <= 0:
        pages_per_block = max(1, min(P, 128 // ps if ps <= 128 else 1))
    G = min(pages_per_block, P)
    n_tiles = T // TT
    # Per-tile sequence overlap [lo, hi): tile t covers rows
    # [t·TT, (t+1)·TT); sequences with start < tile_end and end > tile
    # start overlap. Ranges are ascending, so two searchsorteds give the
    # bounds (O(nT·logS) on host-side values, traced here as jnp ops).
    seq_ends = seq_starts + seq_lens
    tile_row_lo = jnp.arange(n_tiles, dtype=jnp.int32) * TT
    tile_row_hi = tile_row_lo + TT
    tile_lo = jnp.searchsorted(seq_ends, tile_row_lo, side="right")
    tile_hi = jnp.searchsorted(seq_starts, tile_row_hi, side="left")
    tiles = jnp.stack(
        [tile_lo.astype(jnp.int32),
         jnp.maximum(tile_hi, tile_lo).astype(jnp.int32)], axis=1
    )                                                      # [nT, 2]

    kernel = functools.partial(
        _ragged_kernel,
        scale=scale,
        logit_softcap=logit_softcap,
        page_size=ps,
        num_tables=P,
        groups=Hq // Hk,
        pages_per_block=G,
        token_tile=TT,
        quantized=quantized,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((TT, Hq, D), lambda t, *_: (t, 0, 0)),
        any_spec,
        any_spec,
    ]
    scratch = [
        pltpu.VMEM((2, G, ps, folded), k_pages.dtype),
        pltpu.VMEM((2, G, ps, folded), k_pages.dtype),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        in_specs += [any_spec, any_spec]
        scratch += [
            pltpu.VMEM((2, G, ps, Hk), ks_pages.dtype),
            pltpu.VMEM((2, G, ps, Hk), vs_pages.dtype),
        ]
        operands = [q, k_pages, v_pages, ks_pages, vs_pages]
    n_sems = 4 if quantized else 2
    scratch += [pltpu.SemaphoreType.DMA((2, G))] * n_sems
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((TT, Hq, D), lambda t, *_: (t, 0, 0))],
        scratch_shapes=scratch,
    )
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, Hq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="ragged_paged_attention",
    )(
        seq_starts.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        page_tables.astype(jnp.int32),
        tiles,
        window,
        *operands,
    )
    return out


def use_ragged_kernel(num_kv_heads: int, head_dim: int) -> bool:
    """Gate for the ragged kernel path: TPU hardware, 128-aligned folded
    head-lane dimension (the DMA-tiling rule shared with the decode
    kernel), and the POLYKEY_DISABLE_RAGGED_KERNEL kill-switch — the
    ragged kernel is a separate Mosaic lowering surface from the decode
    kernel, so a regression there must be containable without taking the
    working decode path down (the gather fallback serves everything)."""
    import os

    if os.environ.get(
        "POLYKEY_DISABLE_RAGGED_KERNEL", ""
    ).lower() in ("1", "true"):
        return False
    from .paged_attention_kernel import use_paged_kernel

    return use_paged_kernel(num_kv_heads, head_dim)


def ragged_gather_attention(
    q: jax.Array,             # [T, Hq, D] flat token stream
    k_pages,                  # [N, ps, Hk·D] or int8 (values, scales)
    v_pages,
    token_tables: jax.Array,  # [T, P] int32 — each token's table row
    q_positions: jax.Array,   # [T] int32 absolute positions
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
) -> jax.Array:
    """The gather reference: one batch row per token through the
    existing paged_attention (B=T, T=1) — per token EXACTLY the math the
    bucketed engine paths run (decode gather fallback and prefill window
    attention reduce to the same per-row softmax over the same gathered
    window), which is what makes greedy streams bit-identical between
    the ragged and bucketed engine modes off-TPU."""
    from .paged_attention import paged_attention

    out = paged_attention(
        q[:, None], k_pages, v_pages, token_tables,
        q_positions[:, None].astype(jnp.int32),
        scale=scale, logit_softcap=logit_softcap, window=window,
    )
    return out[:, 0]


def ragged_paged_attention(
    q: jax.Array,             # [T, Hq, D] flat token stream (tile-padded)
    k_pages,                  # [N, ps, Hk·D] or int8 (values, scales)
    v_pages,
    page_tables: jax.Array,   # [S, P] int32 per-sequence tables
    seq_starts: jax.Array,    # [S] int32 row range starts (ascending)
    seq_lens: jax.Array,      # [S] int32 new-token counts
    kv_lens: jax.Array,       # [S] int32 KV lengths (new tokens incl.)
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
    interpret: bool = False,
    force_kernel: bool = False,
    pages_per_block: int = 0,
    token_tile: int = TOKEN_TILE,
) -> jax.Array:
    """Ragged paged attention over the flat stream; returns [T, Hq, D]
    in q.dtype. Kernel on TPU-eligible geometry (or `force_kernel` /
    `interpret`); gather fallback everywhere else. Rows outside every
    sequence range are padding (output unspecified — the engine masks
    them)."""
    quantized = isinstance(k_pages, tuple)
    data_pool = k_pages[0] if quantized else k_pages
    D = q.shape[2]
    Hk = data_pool.shape[2] // D
    if window is None:
        win = jnp.zeros((1,), jnp.int32)
    else:
        win = jnp.asarray(window, jnp.int32).reshape(1)

    if force_kernel or interpret or use_ragged_kernel(Hk, D):
        out = _ragged_call(
            q, k_pages, v_pages, page_tables,
            seq_starts, seq_lens, kv_lens, win,
            scale=scale, logit_softcap=logit_softcap,
            interpret=interpret, pages_per_block=pages_per_block,
            token_tile=token_tile,
        )
        return out.astype(q.dtype)

    # Gather fallback: per-token table rows + positions from the
    # sequence metadata (ranges are ascending and non-overlapping).
    T = q.shape[0]
    rows = jnp.arange(T, dtype=jnp.int32)
    sid = jnp.clip(
        jnp.searchsorted(seq_starts, rows, side="right") - 1,
        0, page_tables.shape[0] - 1,
    ).astype(jnp.int32)
    in_seq = (rows >= seq_starts[sid]) & (
        rows < seq_starts[sid] + seq_lens[sid]
    )
    pos = kv_lens[sid] - seq_lens[sid] + (rows - seq_starts[sid])
    pos = jnp.where(in_seq, pos, 0)
    token_tables = jnp.where(
        in_seq[:, None], page_tables[sid],
        jnp.zeros_like(page_tables[sid]),
    )
    return ragged_gather_attention(
        q, k_pages, v_pages, token_tables, pos,
        scale=scale, logit_softcap=logit_softcap, window=window,
    )
