"""Pallas paged-attention decode kernel (TPU).

The gather path (ops/paged_attention.py) materializes each sequence's KV
window in HBM every decode step: `kv_pages[2 * page_tables]` reads the pages
AND writes a [B, P·page_size, Hk, D] copy, so the cache crosses HBM twice. This
kernel reads each valid page exactly once: ONE program walks the call's
sequences (all of them where their q and output blocks fit the scoped VMEM,
`_program_lanes`), a double-buffered DMA loop streams each sequence's pages
HBM → VMEM while the pages before them accumulate into online-softmax state
(running max m, denominator l, fp32 accumulator) — the same recurrence as
ops/flash_attention.py — and the program writes one normalised [S, Hq, D]
block in the activations' dtype.

A page is its PARTS, adjacent entries of the pool fetched under one
descriptor: K and V (`parts` 2: what the text below says of K and V), or
the ONE latent row a token of a latent-attention model (`parts` 1,
`mla_latent_decode`: the row is every head's key and, in its leading
`v_width` columns, every head's value, so a tile is one product of all
query heads against the rows and one against their leading columns, on
the pool's own dtype). The walk — lanes, blocks and tiles by bytes, runs
of waits, the live blocks — is one and the same.

Pages stream in BLOCKS of `pages_per_block` (G): each buffer slot holds G
pages, whose DMAs all go out together, so per-page DMA latency amortizes
G×. A block is awaited and computed by ROW TILES of Gt pages (`_tile_pages`),
each with a semaphore of its own: a tile's attention is [Gt·page_size] wide
— MXU-shaped work — and runs as soon as ITS pages have landed, while the
rest of the block and the next one are still on their way; only the tiles
that hold a fetched page are computed, so a sequence with one page does one
tile. Gt consecutive page table entries cover contiguous positions, so the
tile's mask is one iota. G and Gt follow BYTES (`_BLOCK_BYTES`,
`_TILE_BYTES`): the same bytes in flight a slot, and the same bytes a tile's
dependent chain of products, maximum, exponential and products is spread
over, at every folded width.

The two buffer slots alternate ACROSS sequences, not only inside one:
while a sequence's last block computes, the first block of the next
sequence that has any visible page is already on its way into the other
slot, so one fetch a call is waited on cold, not one a sequence; and a
sequence walks only its live blocks [blo, bhi), not the table's whole
width. That hand-over is the carry of the loop over a program's sequences
and lives in SMEM only from one program to the next. What a call costs
beyond its bytes, measured on a v5e (PERF.md §5): ~0.45 µs a sequence of
one tile's dependent chain and the scalar core's schedule (nothing of it
is the grid step), and ~30 ns of scalar work a page started — which is
the call on a narrow tp shard, whose page is 16 KB, 20 ns of bytes. So a
call issues as few descriptors as the pages allow: K and V of a page lie
side by side in the pool (engine/kv_cache.py; here as its page halves, 2p
and 2p + 1) and come in under ONE start, and a tile's pages are awaited by
RUNS — every page of a tile signals the tile's semaphore and a wait looks
only at the semaphore and a byte count, so the n pages in flight are
awaited as one wait of 1, 2, 4 … pages for each set bit of n (`_wait_runs`):
a full tile of Gt = 2^k pages is one wait.

Invalid page-table tails (the reserved garbage page 0) are never DMA'd:
the loop bound is ceil((position+1)/page_size), data-dependent per
sequence, and Gemma-2 sliding-window layers also skip pages wholly below
position - window. In the tile that straddles an end of [lo, hi) the rows
of pages outside it hold stale VMEM; their logits are masked, and V is
zeroed on those rows so masked weights never multiply uninitialized data
(0·NaN would poison the accumulator). Tiles wholly outside are never read.

Where no merge follows, the kernel divides by max(l, 1e-9) itself. For
context-parallel decode (mesh sp>1) it emits the UNNORMALIZED state
(acc, m, l) over a page sub-range instead: each sp shard covers a
contiguous slice of every sequence's pages and the partial states merge
via pmax/psum before normalizing (see paged_attention_decode).

Covers GQA, logit soft-capping, and dynamic sliding windows; falls back to
the gather implementation off-TPU (`use_kernel` dispatch in
paged_attention_decode).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _div(x, n: int):
    """x // n on the scalar core for a static n: a shift where n is a power
    of two (pages, blocks and tiles are) — the core divides in software,
    and the schedule's divisions were 6 % of a tp shard's call (PERF.md
    §5). A shift rounds down; every use either has x >= 0 or clamps the
    result at 0, where that agrees with a division rounding toward zero."""
    if n & (n - 1) == 0:
        return jax.lax.shift_right_arithmetic(x, n.bit_length() - 1)
    return jax.lax.div(x, n)


def _kernel(
    # scalar prefetch
    pt_ref,        # [B, P] int32 page tables
    pos_ref,       # [B] int32 decode position per sequence
    win_ref,       # [1] int32 sliding window (<=0 → global)
    rng_ref,       # [2] int32 page sub-range [rlo, rhi) — CP shard's slice
    # then, positionally (arity varies with `quantized` and `state`):
    # inputs: q [S, Hq, D] VMEM block — the program's S sequences; kv page
    #         halves [2N, ps, Hk·D] HBM (the stored layout: page p's K at
    #         2p, its V at 2p + 1, heads in lanes; manual DMA; N may be the
    #         whole stack's L·num_pages, the table's ids offset by the
    #         layer); quantized adds ks/vs scale pages [N, ps, Hk] HBM (bf16)
    # outputs: ONE normalised block [S, Hq, D] in the activations' dtype,
    #         or with `state` the unnormalised online-softmax state for a
    #         merge across CP shards (acc/l scale by exp(m - m_global)):
    #         acc [S, Hq, D] f32, m/l [S, Hq, 1] f32
    # scratch: kv buf [2, G, 2, ps, Hk·D] VMEM (+ two [2, G, ps, Hk]
    #         scale bufs when quantized), DMA semaphores [2, G // Gt] for
    #         each (every page of a tile signals its slot's and tile's),
    #         and the schedule's state [2] int32 SMEM, which outlives a
    #         program
    *refs,
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    groups: int,       # Hq // Hk
    pages_per_block: int,   # G — pages per buffer slot (DMAs in flight)
    pages_per_tile: int,    # Gt — pages a row tile awaits and computes;
                            #   divides G
    quantized: bool = False,
    state: bool = False,
    parts: int = 2,         # entries a page holds: K and V, or 1 latent row
    v_width: int = 0,       # parts == 1: a row's leading columns are its "V"
):
    def kv_halves(page):      # a page's parts: adjacent entries of the pool
        return kv_pages_ref.at[pl.ds(parts * page, parts)]

    # A stream: (a page id → that page in HBM, its buffer, its semaphores).
    n_out = 3 if state else 1
    if quantized:
        q_ref, kv_pages_ref, ks_pages_ref, vs_pages_ref = refs[:4]
        out_refs = refs[4:4 + n_out]
        (kv_buf, ks_buf, vs_buf,
         kv_sems, ks_sems, vs_sems, state_ref) = refs[4 + n_out:]
        streams = ((kv_halves, kv_buf, kv_sems),
                   (lambda page: ks_pages_ref.at[page], ks_buf, ks_sems),
                   (lambda page: vs_pages_ref.at[page], vs_buf, vs_sems))
    else:
        q_ref, kv_pages_ref = refs[:2]
        out_refs = refs[2:2 + n_out]
        kv_buf, kv_sems, state_ref = refs[2 + n_out:]
        streams = ((kv_halves, kv_buf, kv_sems),)
        ks_buf = vs_buf = None
    S, Hq, D = q_ref.shape
    Dv = v_width if parts == 1 else D     # an output head's width
    first = pl.program_id(0) * S          # the program's first sequence
    B = pl.num_programs(0) * S
    window = win_ref[0]
    G = pages_per_block
    Gt = pages_per_tile
    T = Gt * page_size                    # a row tile's positions

    def page_span(seq):
        # Pages [lo, hi) hold positions visible to seq's query, intersected
        # with this shard's page sub-range (context-parallel decode: each
        # sp shard covers a contiguous page range; [0, P) when unsharded).
        pos = pos_ref[seq]
        hi = jnp.minimum(_div(pos, page_size) + 1, rng_ref[1])
        lo = jnp.where(
            window > 0,
            jnp.maximum(_div(pos - window + 1, page_size), 0),
            0,
        )
        return jnp.maximum(lo, rng_ref[0]), hi

    def block_pages(blk, lo, hi):
        # The pages of G-page block `blk` inside [lo, hi): the only ones
        # fetched (none when the span is empty) — the rest of the slot
        # holds stale rows, never computed or masked below.
        return jnp.maximum(lo, blk * G), jnp.minimum(hi, (blk + 1) * G)

    def start_block(seq, blk, slot, lo, hi):
        # All page DMAs of the block go out together (latency overlaps):
        # one a page and stream, K and V of the page in it.
        def go(p, _):
            at = p - blk * G
            for page_at, buf, sems in streams:
                pltpu.make_async_copy(
                    page_at(pt_ref[seq, p]), buf.at[slot, at],
                    sems.at[slot, _div(at, Gt)],
                ).start()
            return _

        jax.lax.fori_loop(*block_pages(blk, lo, hi), go, None)

    def wait_tile(slot, t, first_at, end_at):
        # The n pages started of tile `t` (of the slot's pages [first_at,
        # end_at) that were fetched) are awaited by runs: a wait looks
        # only at the tile's semaphore and a byte count, so the slot's
        # first `run` pages stand for source and destination alike; one
        # wait for each set bit of n adds up to the n pages' bytes.
        n = (jnp.minimum(end_at, (t + 1) * Gt)
             - jnp.maximum(first_at, t * Gt))
        for run in _wait_runs(Gt):
            @pl.when((n & run) != 0)
            def _():
                for _, buf, sems in streams:
                    landed = buf.at[slot, pl.ds(0, run)]
                    pltpu.make_async_copy(
                        landed, landed, sems.at[slot, t]).wait()

    def has_no_page(seq):
        slo, shi = page_span(jnp.minimum(seq, B - 1))
        return (seq < B) & (slo >= shi)

    def tile_rows(buf, slot, page0, half=None):
        # A row tile of a slot: Gt pages from `page0`, one [T, ·] block
        # (the pages cover contiguous positions). A block that is one tile
        # is read whole, with no dynamic slice.
        pages = slice(None) if Gt == G else pl.ds(page0, Gt)
        rows = buf[slot, pages] if half is None else buf[slot, pages, half]
        return rows.reshape(T, -1)

    def sequence(j, carry):
        # The schedule (module docstring). Blocks [blo, blo + n_blocks) are
        # the G-page groups overlapping this sequence's pages; they
        # alternate between the two buffer slots, and the alternation runs
        # on across sequences: the carry = (slot of the block in flight,
        # sequence it is for). Only the first live sequence of a call
        # starts (and waits on) a cold fetch; one with no visible page
        # starts and waits on nothing.
        slot0, in_flight = carry
        b = first + j
        q_pos = pos_ref[b]
        lo, hi = page_span(b)
        live = lo < hi
        blo = _div(lo, G)
        n_blocks = jnp.where(live, _div(hi + G - 1, G) - blo, 0)

        @pl.when(live & (in_flight != b))
        def _cold():
            start_block(b, blo, slot0, lo, hi)

        # Only a live sequence hands over, so only it looks for its
        # successor.
        nxt = jax.lax.while_loop(
            has_no_page, lambda seq: seq + 1, jnp.where(live, b + 1, B)
        )
        nxt_seq = jnp.minimum(nxt, B - 1)
        nxt_lo, nxt_hi = page_span(nxt_seq)
        nxt_hi = jnp.where(nxt < B, nxt_hi, nxt_lo)  # no next: an empty span

        if parts == 1:
            # Every head reads the one row: the products run in the
            # pool's own dtype (bf16 operands, float32 sums — 240 FLOP a
            # byte of row at long contexts leaves no room for float32
            # passes), and the scale goes on the sums.
            q = q_ref[j].astype(kv_buf.dtype)                 # [Hq, D]
        else:
            q = q_ref[j].astype(jnp.float32) * scale          # [Hq, D]

        def block(i, carry):
            blk = blo + i
            slot = (slot0 + i) % 2
            # What streams in behind this block: this sequence's next
            # block, or after the last one the next live sequence's first.
            last = i + 1 == n_blocks
            start_block(
                jnp.where(last, nxt_seq, b),
                jnp.where(last, _div(nxt_lo, G), blk + 1),
                1 - slot,
                jnp.where(last, nxt_lo, lo),
                jnp.where(last, nxt_hi, hi),
            )

            first_at, end_at = (
                page - blk * G for page in block_pages(blk, lo, hi))

            def tile(t, carry):
                # The buffer holds [G, 2, ps, Hk*D] (heads folded into
                # lanes so the DMA slice stays 128-aligned for any
                # head_dim); a tile's Gt pages flatten to one [T, Hk*D]
                # block with a single iota mask.
                m, l, acc = carry
                wait_tile(slot, t, first_at, end_at)
                page0 = t * Gt
                k = tile_rows(kv_buf, slot, page0, 0)
                # A one-part page's row is every head's key, and its
                # leading v_width columns every head's value.
                v = (k[:, :v_width] if parts == 1
                     else tile_rows(kv_buf, slot, page0, 1))
                num_kv = k.shape[1] // D
                if quantized:
                    # Per-(position, head) dequant scales for this tile —
                    # applied on the per-head slices below, so the int8
                    # pages stream at half the bf16 bytes and dequant
                    # rides the matmul operand load.
                    ks2 = tile_rows(ks_buf, slot, page0).astype(jnp.float32)
                    vs2 = tile_rows(vs_buf, slot, page0).astype(jnp.float32)

                pos0 = (blk * G + page0) * page_size
                kv_pos1 = pos0 + jax.lax.broadcasted_iota(
                    jnp.int32, (T, 1), dimension=0
                )                                             # [T, 1]
                valid1 = ((kv_pos1 >= lo * page_size)
                          & (kv_pos1 < hi * page_size))
                # Rows of pages that were never DMA'd hold stale VMEM (the
                # tile that straddles an end of the span); zero V there so
                # masked-out weights cannot multiply NaN garbage.
                v = jnp.where(valid1, v.astype(jnp.float32), 0.0)
                if parts == 1:
                    v = v.astype(k.dtype)
                if quantized:
                    # The V-side matmul SUMS over rows, so stale scale
                    # rows must be zeroed like v itself — 0·NaN from a
                    # stale bf16 pattern would poison every output.
                    # K-side NaNs stay confined to their own masked logit
                    # column.
                    vs2 = jnp.where(valid1, vs2, 0.0)

                # Mosaic lowers only plain 2D matmuls — unroll over kv
                # heads (q head h ↔ kv head h//groups, heads grouped
                # contiguously).
                def k_head(h):
                    kk = k[:, h * D:(h + 1) * D].astype(jnp.float32)
                    if quantized:
                        kk = kk * ks2[:, h:h + 1]
                    return kk

                if parts == 1:
                    s = scale * jax.lax.dot_general(
                        q, k,
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                else:
                    s = jnp.concatenate(
                        [
                            jax.lax.dot_general(
                                q[h * groups:(h + 1) * groups],   # [g, D]
                                k_head(h),
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )
                            for h in range(num_kv)
                        ],
                        axis=0,
                    )                                         # [Hq, T]
                if logit_softcap is not None:
                    s = logit_softcap * jnp.tanh(s / logit_softcap)

                kv_pos = pos0 + jax.lax.broadcasted_iota(
                    jnp.int32, (Hq, T), dimension=1
                )
                mask = kv_pos <= q_pos
                mask &= (window <= 0) | (kv_pos > q_pos - window)
                mask &= valid1.reshape(1, T)
                s = jnp.where(mask, s, _NEG_INF)

                m_cur = jnp.max(s, axis=1, keepdims=True)     # [Hq, 1]
                m_new = jnp.maximum(m, m_cur)
                pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # [Hq, T]
                corr = jnp.exp(m - m_new)
                l_new = corr * l + jnp.sum(pexp, axis=1, keepdims=True)

                def v_head(h):
                    vv = v[:, h * D:(h + 1) * D]
                    if quantized:
                        vv = vv * vs2[:, h:h + 1]
                    return vv

                if parts == 1:
                    pv = jax.lax.dot_general(
                        pexp.astype(v.dtype), v,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                else:
                    pv = jnp.concatenate(
                        [
                            jax.lax.dot_general(
                                pexp[h * groups:(h + 1) * groups],  # [g, T]
                                v_head(h),
                                dimension_numbers=(((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )
                            for h in range(num_kv)
                        ],
                        axis=0,
                    )                                         # [Hq, Dv]
                return m_new, l_new, acc * corr + pv

            # Only the tiles that hold a fetched page are computed: a lane
            # with one page does one tile, a full block all G // Gt, and
            # stale VMEM outside them is never read.
            return jax.lax.fori_loop(
                _div(first_at, Gt), _div(end_at + Gt - 1, Gt), tile, carry)

        m0 = jnp.full((Hq, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((Hq, 1), jnp.float32)
        acc0 = jnp.zeros((Hq, Dv), jnp.float32)
        # Only the live blocks are walked: ~4 turns at ~450-token contexts,
        # not one turn (and a branch) for each of the table's P // G groups.
        m, l, acc = jax.lax.fori_loop(0, n_blocks, block, (m0, l0, acc0))

        if state:
            acc_ref, m_ref, l_ref = out_refs
            acc_ref[j] = acc
            m_ref[j] = m
            l_ref[j] = l
        else:
            (o_ref,) = out_refs
            o_ref[j] = (acc / jnp.maximum(l, 1e-9)).astype(o_ref.dtype)
        return (jnp.where(live, (slot0 + n_blocks) % 2, slot0),
                jnp.where(live, nxt, in_flight))

    @pl.when(pl.program_id(0) == 0)
    def _reset():
        state_ref[0] = 0
        state_ref[1] = -1

    # The hand-over is the loop's carry inside a program and lives in SMEM
    # only from one program to the next.
    slot, in_flight = jax.lax.fori_loop(
        0, S, sequence, (state_ref[0], state_ref[1]))
    state_ref[0] = slot
    state_ref[1] = in_flight


_BLOCK_BYTES = 1024 * 1024  # K's bytes (and V's as many) in flight a slot
_TILE_BYTES = 256 * 1024    # K's bytes a row tile computes at once
# What a program's q and output blocks may take of the scoped VMEM (16 MiB
# on a v5e), the pipeline's second buffer of each counted: beside them stand
# the two K/V slots (4 × _BLOCK_BYTES = 4 MiB) and a tile's float32 copies
# of K and V (2 × 2 × _TILE_BYTES = 1 MiB from bf16 pools).
_LANE_BLOCK_BYTES = 4 * 1024 * 1024


def _block_pages(pages_per_block: int, row_bytes: int, page_size: int,
                 table_pages: int) -> int:
    """G, the pages a buffer slot holds: `pages_per_block` if given (> 0),
    else from the bytes a block moves; never more than the table has."""
    if pages_per_block <= 0:
        # A block keeps _BLOCK_BYTES of K and as many of V in flight,
        # whatever the folded width it is handed, between 128 positions
        # (one MXU tile of rows) and 512 (measured on a v5e, PERF.md §5:
        # 512 on a chip's 1024 lanes and on a tp shard's 256; past it a
        # cold fetch grows and nothing else moves). Two slots of it, and
        # the f32 copies a row tile's matmuls take, stay inside the scoped
        # VMEM.
        rows = _BLOCK_BYTES // row_bytes
        pages_per_block = min(max(rows, 128), 512) // page_size
    return max(1, min(pages_per_block, table_pages))


def _tile_pages(pages_per_block: int, row_bytes: int, page_size: int) -> int:
    """Gt, the pages a row tile waits for and computes at once:
    _TILE_BYTES of K, whatever the folded width — a tile's arithmetic is
    one dependent chain (products, maximum, exponential, products), so a
    narrow row takes more positions to be worth one: 128 positions on 1024
    lanes of bf16, 256 on 512, the whole block of 512 on a tp shard's 256
    (measured, PERF.md §5) — where that divides the block evenly, else the
    whole block."""
    pages = max(_TILE_BYTES // row_bytes, 128) // page_size
    if pages < 1 or pages_per_block % pages:
        return pages_per_block
    return pages


def _program_lanes(lanes: int, lane_bytes: int) -> int:
    """S, the sequences a program walks: all of them when their q and
    output blocks (`lane_bytes` a sequence, twice for the pipeline's second
    buffer) fit _LANE_BLOCK_BYTES, else the largest divisor of `lanes`
    that does."""
    fit = max(1, _LANE_BLOCK_BYTES // (2 * lane_bytes))
    return max(s for s in range(1, lanes + 1) if lanes % s == 0 and s <= fit)


def _wait_runs(pages_per_block: int) -> tuple:
    """The run lengths, in pages, that `wait_block` awaits a block's pages
    by: the powers of two up to G. A block with n pages in flight takes
    the runs that are the set bits of n, so the waits consume exactly the
    bytes the n starts signalled — one wait for a full block of 2^k
    pages, at most ⌊log2 G⌋ + 1 for any other n."""
    return tuple(1 << j for j in range(pages_per_block.bit_length()))


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "logit_softcap", "interpret", "pages_per_block", "state",
        "parts", "v_width", "name"),
)
def _decode_call(
    q: jax.Array,             # [B, Hq, D]
    kv_pages,                 # [2N, ps, Hk·D], or the int8 (values,
                              #   k scales, v scales) triple (scales
                              #   [N, ps, Hk] bf16)
    page_tables: jax.Array,   # [B, P] int32
    positions: jax.Array,     # [B] int32
    window: jax.Array,        # [1] int32
    page_range: jax.Array,    # [2] int32 — page sub-range [rlo, rhi)
    *,
    scale: float,
    logit_softcap: Optional[float],
    interpret: bool,
    state: bool,
    pages_per_block: int = 0,   # 0 → auto
    parts: int = 2,
    v_width: int = 0,
    name: str = "paged_attention_decode",
):
    """Attention over the pages in `page_range`: [B, Hq, D] in q's dtype,
    normalised inside the kernel — or, with `state`, the UNNORMALIZED
    online-softmax state (acc [B,Hq,D] f32, m [B,Hq,1], l [B,Hq,1]) for a
    caller that first merges partial states across context-parallel shards
    (acc/l scale by exp(m - m_global)).

    The pool is taken as it is stored (engine/kv_cache.py: a page's parts
    side by side — K and V at entries 2p and 2p + 1 here, a latent row at
    p — heads folded into lanes, every page DMA 128-aligned for any
    head_dim) and stays in HBM
    (`pl.ANY`): nothing here reshapes or copies a pool."""
    quantized = isinstance(kv_pages, tuple)
    if quantized:
        kv_pages, ks_pages, vs_pages = kv_pages
    B, Hq, D = q.shape
    _, ps, folded = kv_pages.shape
    Hk = folded // D
    Dv = v_width if parts == 1 else D
    row_bytes = folded * kv_pages.dtype.itemsize
    G = _block_pages(pages_per_block, row_bytes, ps, page_tables.shape[1])
    Gt = _tile_pages(G, row_bytes, ps)
    if state:
        out_shapes = [((Hq, Dv), jnp.float32), ((Hq, 1), jnp.float32),
                      ((Hq, 1), jnp.float32)]
    else:
        out_shapes = [((Hq, Dv), q.dtype)]
    S = _program_lanes(B, Hq * D * q.dtype.itemsize + sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize
        for shape, dtype in out_shapes))

    kernel = functools.partial(
        _kernel,
        scale=scale,
        logit_softcap=logit_softcap,
        page_size=ps,
        groups=Hq // Hk,
        pages_per_block=G,
        pages_per_tile=Gt,
        quantized=quantized,
        state=state,
        parts=parts,
        v_width=v_width,
    )

    def lanes_spec(shape):
        return pl.BlockSpec((S, *shape), lambda b, *_: (b, 0, 0))

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [lanes_spec((Hq, D)), any_spec]
    scratch = [pltpu.VMEM((2, G, parts, ps, folded), kv_pages.dtype)]
    operands = [q, kv_pages]
    if quantized:
        in_specs += [any_spec, any_spec]
        scratch += [
            pltpu.VMEM((2, G, ps, Hk), ks_pages.dtype),
            pltpu.VMEM((2, G, ps, Hk), vs_pages.dtype),
        ]
        operands += [ks_pages, vs_pages]
    scratch += [pltpu.SemaphoreType.DMA((2, G // Gt))] * (len(operands) - 1)
    scratch += [pltpu.SMEM((2,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B // S,),
        in_specs=in_specs,
        out_specs=[lanes_spec(shape) for shape, _ in out_shapes],
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, *shape), dtype)
            for shape, dtype in out_shapes
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name=name,
    )(
        page_tables.astype(jnp.int32),
        positions.astype(jnp.int32),
        window,
        page_range.astype(jnp.int32),
        *operands,
    )
    return tuple(out) if state else out[0]


# What Mosaic says to every int8-KV stage (decode read, write)
# on TPU v5e with jax 0.9.0 / libtpu 0.0.34 (scripts/tpu_kernel_check.py,
# 2026-09-26): the scale pages are [ps, Hk] slabs with Hk (8 or 16) in the
# lane dimension, and a DMA slice must be a multiple of the 128-lane tile.
# The engine refuses kv_dtype="int8" at start on TPU with this message
# rather than serve it from the gather path; the fix (scales laid out with
# positions in lanes, or pre-gathered per sequence) is its own change.
INT8_KV_MOSAIC_ERROR = (
    "Mosaic failed to compile TPU kernel: Slice shape along dimension 2 "
    "must be aligned to tiling (128), but is 8 (the [page_size, "
    "num_kv_heads] scale-page DMA of the int8-KV read and write kernels)"
)


def use_paged_kernel(num_kv_heads: int, head_dim: int) -> bool:
    """The DMA kernels (the reads here, the page write) need TPU hardware,
    and a row of a page's part — num_kv_heads · head_dim folded columns,
    or a latent pool's one row (1, width) — must be whole 128-lane tiles
    for DMA tiling."""
    return jax.default_backend() == "tpu" and (num_kv_heads * head_dim) % 128 == 0


def paged_attention_decode(
    q: jax.Array,             # [B, 1, Hq, D] (single decode step)
    kv_pages,                 # [2N, ps, Hk·D] (or the int8 (values,
                              #   k scales, v scales) triple)
    page_tables: jax.Array,   # [B, P]
    q_positions: jax.Array,   # [B, 1] absolute positions
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
    interpret: bool = False,
    force_kernel: bool = False,
    pages_per_block: int = 0,   # 0 → auto (from the bytes a block moves)
    mesh=None,                  # serving mesh → shard_map the kernel
) -> jax.Array:
    """Decode-step paged attention; returns [B, 1, Hq, D].

    Same contract as ops/paged_attention.paged_attention restricted to T=1.

    With a mesh whose dp/tp/sp extents exceed 1, the kernel runs under
    shard_map: batch (and page tables/positions) shard over dp, heads
    over tp — the engine's layout (parallel/sharding.py: the pools'
    folded last dimension over tp, whole heads a shard; decode batch
    over dp). GSPMD cannot
    partition an opaque pallas_call, so without this it would all-gather
    the head-sharded pools. Attention is embarrassingly parallel over
    batch and (GQA-aligned) heads, so each shard runs the same kernel on
    its slice. sp > 1 context-parallelizes the page axis: each sp shard
    covers a contiguous page sub-range of every sequence (pools are
    sp-replicated — this shards the attention READS) and the partial
    online-softmax states merge via pmax/psum over sp. ep stays an
    unmentioned axis with replicated operands.
    """
    quantized = isinstance(kv_pages, tuple)
    B = q.shape[0]
    data_pool = kv_pages[0] if quantized else kv_pages
    D = q.shape[3]
    Hk = data_pool.shape[2] // D

    if not (force_kernel or interpret or use_paged_kernel(Hk, D)):
        from .paged_attention import paged_attention

        return paged_attention(
            q, kv_pages, page_tables, q_positions,
            scale=scale, logit_softcap=logit_softcap, window=window,
        )

    if window is None:
        win = jnp.zeros((1,), jnp.int32)
    else:
        win = jnp.asarray(window, jnp.int32).reshape(1)

    inner = functools.partial(
        _decode_call,
        scale=scale, logit_softcap=logit_softcap, interpret=interpret,
        pages_per_block=pages_per_block,
    )
    P_tables = page_tables.shape[1]

    dp = mesh.shape.get("dp", 1) if mesh is not None else 1
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    if (dp > 1 or tp > 1 or sp > 1) and mesh.shape.get("pp", 1) > 1:
        # Under pp the per-layer pool slice is stage-local, not replicated
        # across pp — the shard_map specs below would be wrong. The gather
        # path is GSPMD-partitionable as-is, so pp>1 meshes take it.
        # Decided position (PERF.md "pp in serving"): pp is a capacity/
        # prefill axis; the ~3× attention-read traffic here is accepted,
        # and >HBM models should serve tp(+sp)-first instead.
        from .paged_attention import paged_attention

        return paged_attention(
            q, kv_pages, page_tables, q_positions,
            scale=scale, logit_softcap=logit_softcap, window=window,
        )
    if dp > 1 or tp > 1 or sp > 1:
        if B % dp or Hk % tp or q.shape[2] % tp:
            # Never fall through to an unwrapped pallas_call on sharded
            # operands — GSPMD would all-gather the head-sharded pools
            # every layer/step (or fail Mosaic compilation) with no
            # pointer at the real cause. The engine validates these up
            # front; direct callers get the explicit error.
            raise ValueError(
                f"paged decode kernel on mesh: B={B} %% dp={dp}, "
                f"Hk={Hk} / Hq={q.shape[2]} %% tp={tp} must divide evenly"
            )
        from jax.sharding import PartitionSpec as P

        def inner_sm(q2, kv2, pt2, pos2, win2):
            # Context-parallel decode: each sp shard covers a contiguous
            # page sub-range of every sequence (pools are sp-replicated,
            # so this shards the attention READS — the long-context
            # bandwidth bound — sp-fold), then partial online-softmax
            # states merge with a max/psum pair. sp=1 degenerates to the
            # full range and no collectives.
            if sp == 1:
                return inner(q2, kv2, pt2, pos2, win2,
                             jnp.array([0, P_tables], jnp.int32), state=False)
            s = jax.lax.axis_index("sp")
            chunk = -(-P_tables // sp)
            rlo = (s * chunk).astype(jnp.int32)
            rhi = jnp.minimum(P_tables, rlo + chunk).astype(jnp.int32)
            acc, m, l = inner(
                q2, kv2, pt2, pos2, win2, jnp.stack([rlo, rhi]), state=True)
            m_g = jax.lax.pmax(m, "sp")
            corr = jnp.exp(m - m_g)
            l = jax.lax.psum(l * corr, "sp")
            acc = jax.lax.psum(acc * corr, "sp")
            return (acc / jnp.maximum(l, 1e-9)).astype(q2.dtype)

        # The int8 form is a (values, k scales, v scales) triple: its spec
        # is a pytree matching that structure. Data [2N, ps, Hk·D] and
        # scale pools [N, ps, Hk] all head-shard on their last dimension.
        pool_spec = P(None, None, "tp")
        if quantized:
            pool_spec = (pool_spec,) * 3
        sm = jax.shard_map(
            inner_sm,
            mesh=mesh,
            in_specs=(
                P("dp", "tp", None),          # q [B, Hq, D]
                pool_spec,                    # kv_pages
                P("dp", None),                # page_tables
                P("dp"),                      # positions
                P(None),                      # window
            ),
            out_specs=P("dp", "tp", None),
            check_vma=False,
        )
        out = sm(
            q[:, 0], kv_pages, page_tables,
            q_positions[:, 0].astype(jnp.int32), win,
        )
    else:
        out = inner(
            q[:, 0], kv_pages, page_tables,
            q_positions[:, 0].astype(jnp.int32), win,
            jnp.array([0, P_tables], jnp.int32), state=False,
        )
    return out[:, None]


def mla_latent_decode(
    q: jax.Array,             # [B, 1, Hq, W]: absorbed query heads
    rows: jax.Array,          # [N, ps, W]: one-part pages, a latent row a token
    page_tables: jax.Array,   # [B, P]
    q_positions: jax.Array,   # [B, 1] absolute positions
    *,
    scale: float,
    v_width: int,
    interpret: bool = False,
    pages_per_block: int = 0,   # 0 → auto (from the bytes a block moves)
) -> jax.Array:
    """Decode-step attention of a latent (MLA) layer in its absorbed form:
    every query head reads the token's ONE row — the row is its key, the
    row's leading `v_width` columns its value — so a page is one part and
    is fetched once for all heads. Returns [B, 1, Hq, v_width].

    The walk is `_kernel`'s (one program over the lanes, blocks and tiles
    by bytes, runs of waits, the live blocks [blo, bhi)); only a tile's two
    products differ. Off-TPU: the gather path
    (ops/paged_attention.latent_attention). One device: the engine refuses
    a mesh axis over a latent pool (engine/config.py)."""
    if not (interpret or use_paged_kernel(1, rows.shape[-1])):
        from .paged_attention import latent_attention

        return latent_attention(
            q, rows, page_tables, q_positions, scale=scale, v_width=v_width)
    out = _decode_call(
        q[:, 0], rows, page_tables, q_positions[:, 0].astype(jnp.int32),
        jnp.zeros((1,), jnp.int32),
        jnp.array([0, page_tables.shape[1]], jnp.int32),
        scale=scale, logit_softcap=None, interpret=interpret, state=False,
        pages_per_block=pages_per_block, parts=1, v_width=v_width,
        name="mla_latent_decode",
    )
    return out[:, None]
