"""Pallas paged-attention decode kernel (TPU).

The gather path (ops/paged_attention.py) materializes each sequence's KV
window in HBM every decode step: `kv_pages[2 * page_tables]` reads the pages
AND writes a [B, P·page_size, Hk, D] copy, so the cache crosses HBM twice. This
kernel reads each valid page exactly once: one grid program per sequence,
a double-buffered DMA loop streams that sequence's pages HBM → VMEM while
the previous block's attention accumulates into online-softmax state
(running max m, denominator l, fp32 accumulator) — the same recurrence as
ops/flash_attention.py.

Pages stream in GROUPS of `pages_per_block` (G): each buffer slot holds G
pages, whose DMAs are all in flight together, so per-page DMA latency
(~µs for a 32 KB page — the dominant cost of a one-page-at-a-time loop)
amortizes G× and the per-group attention block is [G·page_size] wide —
MXU-shaped work instead of page_size-sliver matmuls. G consecutive page
table entries cover contiguous positions, so the group's mask is one iota.
G follows the BYTES a block moves (`_decode_call`): the same bytes in
flight a slot at every folded width.

The two buffer slots alternate ACROSS sequences, not only inside one (the
grid runs in order): while a sequence's last block computes, the first
block of the next sequence that has any visible page is already on its way
into the other slot, so one fetch a call is waited on cold, not one a
sequence; and a sequence walks only its live blocks [blo, bhi), not the
table's whole width. What a call costs beyond its bytes, measured on a v5e
(PERF.md §5): ~0.5 µs a sequence (the grid step, its q and output blocks)
and ~5–8 ns a DMA descriptor started, ~4.5 awaited, whatever it carries —
which weighs on a narrow tp shard, whose page halves are 8 KB, 10 ns of
bytes. So a call issues as few as the pages allow: K and V of a page lie
side by side in the pool (engine/kv_cache.py; here as its page halves, 2p
and 2p + 1) and come in under ONE start,
and a block's pages are awaited by RUNS — every page of a block signals its
slot's semaphore and a wait looks only at the semaphore and a byte count,
so the n pages in flight are awaited as one wait of 1, 2, 4 … pages for
each set bit of n (`_wait_runs`): a full block of G = 2^k pages is one wait.

Invalid page-table tails (the reserved garbage page 0) are never DMA'd:
the loop bound is ceil((position+1)/page_size), data-dependent per
sequence, and Gemma-2 sliding-window layers also skip pages wholly below
position - window. Buffer regions for pages outside [lo, hi) hold stale
VMEM; their logits are masked, and V is zeroed on those rows so masked
weights never multiply uninitialized data (0·NaN would poison the
accumulator).

The kernel emits UNNORMALIZED online-softmax state (acc, m, l) over a
page sub-range: the wrapper normalizes locally, or — context-parallel
decode, mesh sp>1 — each sp shard covers a contiguous slice of every
sequence's pages and partial states merge via pmax/psum before
normalizing (see paged_attention_decode).

Covers GQA, logit soft-capping, and dynamic sliding windows; falls back to
the gather implementation off-TPU (`use_kernel` dispatch in
paged_attention_decode, with the POLYKEY_DISABLE_PAGED_KERNEL
kill-switch).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    pt_ref,        # [B, P] int32 page tables
    pos_ref,       # [B] int32 decode position per sequence
    win_ref,       # [1] int32 sliding window (<=0 → global)
    rng_ref,       # [2] int32 page sub-range [rlo, rhi) — CP shard's slice
    # then, positionally (arity varies with `quantized`):
    # inputs: q [1, Hq, D] VMEM block; kv page halves [2N, ps, Hk·D] HBM
    #         (the stored layout: page p's K at 2p, its V at 2p + 1,
    #         heads in lanes; manual DMA; N may be the whole stack's
    #         L·num_pages, the table's ids offset by the layer);
    #         quantized adds ks/vs scale pages [N, ps, Hk] HBM (bf16)
    # outputs: unnormalized online-softmax state — the wrapper
    #         normalizes, or merges across CP shards first (acc/l scale
    #         by exp(m - m_global)): acc [1, Hq, D] f32, m/l
    #         [1, Hq, MINOR] f32
    # scratch: kv buf [2, G, 2, ps, Hk·D] VMEM (+ two [2, G, ps, Hk]
    #         scale bufs when quantized), one DMA semaphore a slot for
    #         each (every page of a block signals its slot's), and the
    #         schedule's state [2] int32 SMEM, which outlives a program
    *refs,
    scale: float,
    logit_softcap: Optional[float],
    page_size: int,
    groups: int,       # Hq // Hk
    pages_per_block: int,   # G — pages per buffer slot (DMAs in flight)
    quantized: bool = False,
):
    def kv_halves(page):      # a page's K and V: two adjacent entries
        return kv_pages_ref.at[pl.ds(2 * page, 2)]

    # A stream: (a page id → that page in HBM, its buffer, its semaphores).
    if quantized:
        (q_ref, kv_pages_ref, ks_pages_ref, vs_pages_ref,
         acc_ref, m_ref, l_ref,
         kv_buf, ks_buf, vs_buf,
         kv_sems, ks_sems, vs_sems, state_ref) = refs
        streams = ((kv_halves, kv_buf, kv_sems),
                   (lambda page: ks_pages_ref.at[page], ks_buf, ks_sems),
                   (lambda page: vs_pages_ref.at[page], vs_buf, vs_sems))
    else:
        (q_ref, kv_pages_ref, acc_ref, m_ref, l_ref,
         kv_buf, kv_sems, state_ref) = refs
        streams = ((kv_halves, kv_buf, kv_sems),)
        ks_buf = vs_buf = None
    b = pl.program_id(0)
    B = pl.num_programs(0)
    q_pos = pos_ref[b]
    window = win_ref[0]
    G = pages_per_block

    def page_span(seq):
        # Pages [lo, hi) hold positions visible to seq's query, intersected
        # with this shard's page sub-range (context-parallel decode: each
        # sp shard covers a contiguous page range; [0, P) when unsharded).
        pos = pos_ref[seq]
        hi = jnp.minimum(jax.lax.div(pos, page_size) + 1, rng_ref[1])
        lo = jnp.where(
            window > 0,
            jnp.maximum(jax.lax.div(pos - window + 1, page_size), 0),
            0,
        )
        return jnp.maximum(lo, rng_ref[0]), hi

    def block_pages(blk, lo, hi):
        # The pages of G-page block `blk` inside [lo, hi): the only ones
        # fetched (none when the span is empty) — the rest of the slot
        # holds stale rows, masked below.
        return jnp.maximum(lo, blk * G), jnp.minimum(hi, (blk + 1) * G)

    def start_block(seq, blk, slot, lo, hi):
        # All page DMAs of the block go out together (latency overlaps):
        # one a page and stream, K and V of the page in it.
        def go(p, _):
            for page_at, buf, sems in streams:
                pltpu.make_async_copy(
                    page_at(pt_ref[seq, p]), buf.at[slot, p - blk * G],
                    sems.at[slot],
                ).start()
            return _

        jax.lax.fori_loop(*block_pages(blk, lo, hi), go, None)

    def wait_block(blk, slot, lo, hi):
        # The n pages started are awaited by runs: a wait looks only at
        # its slot's semaphore and a byte count, so the slot's first `run`
        # pages stand for source and destination alike; one wait for each
        # set bit of n adds up to the n pages' bytes.
        first, end = block_pages(blk, lo, hi)
        n = jnp.maximum(end - first, 0)
        for run in _wait_runs(G):
            @pl.when((n & run) != 0)
            def _():
                for _, buf, sems in streams:
                    landed = buf.at[slot, pl.ds(0, run)]
                    pltpu.make_async_copy(
                        landed, landed, sems.at[slot]).wait()

    # The schedule (module docstring). Blocks [blo, blo + n_blocks) are the
    # G-page groups overlapping this sequence's pages; they alternate
    # between the two buffer slots, and the alternation runs on across
    # sequences: state_ref = [slot of the block in flight, sequence it is
    # for]. Only the first live sequence of a call starts (and waits on) a
    # cold fetch; one with no visible page starts and waits on nothing.
    lo, hi = page_span(b)
    live = lo < hi
    blo = jax.lax.div(lo, G)
    n_blocks = jnp.where(live, jax.lax.div(hi + G - 1, G) - blo, 0)

    @pl.when(b == 0)
    def _reset():
        state_ref[0] = 0
        state_ref[1] = -1

    slot0 = state_ref[0]

    @pl.when(live & (state_ref[1] != b))
    def _cold():
        start_block(b, blo, slot0, lo, hi)

    def has_no_page(seq):
        slo, shi = page_span(jnp.minimum(seq, B - 1))
        return (seq < B) & (slo >= shi)

    # Only a live sequence hands over, so only it looks for its successor.
    nxt = jax.lax.while_loop(
        has_no_page, lambda seq: seq + 1, jnp.where(live, b + 1, B)
    )
    nxt_seq = jnp.minimum(nxt, B - 1)
    nxt_lo, nxt_hi = page_span(nxt_seq)
    nxt_hi = jnp.where(nxt < B, nxt_hi, nxt_lo)    # no next: an empty span

    @pl.when(live)
    def _hand_over():
        state_ref[0] = (slot0 + n_blocks) % 2
        state_ref[1] = nxt

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    W = G * page_size                               # group window width
    q = q_ref[0].astype(jnp.float32) * scale                  # [Hq, D]

    def body(i, carry):
        m, l, acc = carry
        blk = blo + i
        slot = (slot0 + i) % 2
        # What streams in behind this block: this sequence's next block,
        # or after the last one the next live sequence's first.
        last = i + 1 == n_blocks
        start_block(
            jnp.where(last, nxt_seq, b),
            jnp.where(last, jax.lax.div(nxt_lo, G), blk + 1),
            1 - slot,
            jnp.where(last, nxt_lo, lo),
            jnp.where(last, nxt_hi, hi),
        )
        wait_block(blk, slot, lo, hi)
        # The buffer holds [G, 2, ps, Hk*D] (heads folded into lanes so
        # the DMA slice stays 128-aligned for any head_dim); the G pages
        # cover contiguous positions, so each half flattens to one
        # [W, Hk*D] block with a single iota mask.
        k = kv_buf[slot, :, 0].reshape(W, -1)
        v = kv_buf[slot, :, 1].reshape(W, -1)
        num_kv = k.shape[1] // D
        if quantized:
            # Per-(position, head) dequant scales for this group —
            # applied on the per-head slices below, so the int8
            # pages stream at half the bf16 bytes and dequant rides
            # the matmul operand load.
            ks2 = ks_buf[slot].reshape(W, num_kv).astype(jnp.float32)
            vs2 = vs_buf[slot].reshape(W, num_kv).astype(jnp.float32)

        kv_pos1 = blk * W + jax.lax.broadcasted_iota(
            jnp.int32, (W, 1), dimension=0
        )                                                 # [W, 1]
        valid1 = (kv_pos1 >= lo * page_size) & (kv_pos1 < hi * page_size)
        # Rows of pages that were never DMA'd hold stale VMEM; zero V
        # there so masked-out weights cannot multiply NaN garbage.
        v = jnp.where(valid1, v.astype(jnp.float32), 0.0)
        if quantized:
            # The V-side matmul SUMS over rows, so stale scale rows
            # must be zeroed like v itself — 0·NaN from a stale bf16
            # pattern would poison every output. K-side NaNs stay
            # confined to their own masked logit column.
            vs2 = jnp.where(valid1, vs2, 0.0)

        # Mosaic lowers only plain 2D matmuls — unroll over kv heads
        # (q head h ↔ kv head h//groups, heads grouped contiguously).
        def k_head(h):
            kk = k[:, h * D:(h + 1) * D].astype(jnp.float32)
            if quantized:
                kk = kk * ks2[:, h:h + 1]
            return kk

        s = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q[h * groups:(h + 1) * groups],       # [g, D]
                    k_head(h),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(num_kv)
            ],
            axis=0,
        )                                                 # [Hq, W]
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        kv_pos = blk * W + jax.lax.broadcasted_iota(
            jnp.int32, (Hq, W), dimension=1
        )
        mask = kv_pos <= q_pos
        mask &= (window <= 0) | (kv_pos > q_pos - window)
        mask &= valid1.reshape(1, W)
        s = jnp.where(mask, s, _NEG_INF)

        m_cur = jnp.max(s, axis=1, keepdims=True)         # [Hq, 1]
        m_new = jnp.maximum(m, m_cur)
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # [Hq, W]
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(pexp, axis=1, keepdims=True)

        def v_head(h):
            vv = v[:, h * D:(h + 1) * D]
            if quantized:
                vv = vv * vs2[:, h:h + 1]
            return vv

        pv = jnp.concatenate(
            [
                jax.lax.dot_general(
                    pexp[h * groups:(h + 1) * groups],    # [g, W]
                    v_head(h),
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(num_kv)
            ],
            axis=0,
        )                                                 # [Hq, D]
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((Hq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hq, 1), jnp.float32)
    acc0 = jnp.zeros((Hq, D), jnp.float32)
    # Only the live blocks are walked: ~4 turns at ~450-token contexts, not
    # one turn (and a branch) for each of the table's P // G groups.
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))

    acc_ref[0] = acc
    minor = m_ref.shape[2]
    m_ref[0] = jnp.broadcast_to(m, (Hq, minor))
    l_ref[0] = jnp.broadcast_to(l, (Hq, minor))


_STAT_MINOR = 128   # lane width for the m/l stat outputs (tile-aligned)
_BLOCK_BYTES = 512 * 1024   # K's bytes (and V's as many) in flight a slot


def _block_pages(pages_per_block: int, row_bytes: int, page_size: int,
                 table_pages: int) -> int:
    """G, the pages a buffer slot holds: `pages_per_block` if given (> 0),
    else from the bytes a block moves; never more than the table has."""
    if pages_per_block <= 0:
        # A block keeps _BLOCK_BYTES of K and as many of V in flight,
        # whatever the folded width it is handed (a tp shard's 256 lanes
        # take more positions than a chip's 1024), between 128 positions
        # (one MXU tile of rows) and 512 (a block is computed whole: past
        # the contexts served, wider is masked work). Two slots of it, and
        # the f32 copies the matmuls take, stay inside the scoped VMEM.
        rows = _BLOCK_BYTES // row_bytes
        pages_per_block = min(max(rows, 128), 512) // page_size
    return max(1, min(pages_per_block, table_pages))


def _wait_runs(pages_per_block: int) -> tuple:
    """The run lengths, in pages, that `wait_block` awaits a block's pages
    by: the powers of two up to G. A block with n pages in flight takes
    the runs that are the set bits of n, so the waits consume exactly the
    bytes the n starts signalled — one wait for a full block of 2^k
    pages, at most ⌊log2 G⌋ + 1 for any other n."""
    return tuple(1 << j for j in range(pages_per_block.bit_length()))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "logit_softcap", "interpret", "pages_per_block"),
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
    pages_per_block: int = 0,   # 0 → auto
):
    """Returns UNNORMALIZED online-softmax state (acc [B,Hq,D] f32,
    m [B,Hq,1], l [B,Hq,1]) over the pages in `page_range` — the caller
    normalizes, or first merges partial states across context-parallel
    shards (acc/l scale by exp(m - m_global)).

    The pool is taken as it is stored (engine/kv_cache.py: K and V of a
    page side by side — entries 2p and 2p + 1 here — heads folded into
    lanes, every page DMA 128-aligned for any head_dim) and stays in HBM
    (`pl.ANY`): nothing here reshapes or copies a pool."""
    quantized = isinstance(kv_pages, tuple)
    if quantized:
        kv_pages, ks_pages, vs_pages = kv_pages
    B, Hq, D = q.shape
    _, ps, folded = kv_pages.shape
    Hk = folded // D
    G = _block_pages(
        pages_per_block, folded * kv_pages.dtype.itemsize, ps,
        page_tables.shape[1])

    kernel = functools.partial(
        _kernel,
        scale=scale,
        logit_softcap=logit_softcap,
        page_size=ps,
        groups=Hq // Hk,
        pages_per_block=G,
        quantized=quantized,
    )
    stat_spec = pl.BlockSpec((1, Hq, _STAT_MINOR), lambda b, *_: (b, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)), any_spec]
    scratch = [pltpu.VMEM((2, G, 2, ps, folded), kv_pages.dtype)]
    operands = [q, kv_pages]
    if quantized:
        in_specs += [any_spec, any_spec]
        scratch += [
            pltpu.VMEM((2, G, ps, Hk), ks_pages.dtype),
            pltpu.VMEM((2, G, ps, Hk), vs_pages.dtype),
        ]
        operands += [ks_pages, vs_pages]
    scratch += [pltpu.SemaphoreType.DMA((2,))] * (len(operands) - 1)
    scratch += [pltpu.SMEM((2,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            stat_spec,
            stat_spec,
        ],
        scratch_shapes=scratch,
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, _STAT_MINOR), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, _STAT_MINOR), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_attention_decode",
    )(
        page_tables.astype(jnp.int32),
        positions.astype(jnp.int32),
        window,
        page_range.astype(jnp.int32),
        *operands,
    )
    return acc, m[..., :1], l[..., :1]


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


def use_quantized_paged_kernel(num_kv_heads: int, head_dim: int) -> bool:
    """Gate for the int8-KV kernel paths (read dequant stage + scale-page
    writes): same geometry rule as the data pools, plus the dedicated
    POLYKEY_DISABLE_KV_KERNEL kill-switch — the scale-page DMAs
    ([ps, Hk], minor dim far below lane width) are a separate Mosaic
    lowering surface, and a regression there must be containable without
    taking the WORKING fp kernels down with it (the quantized fallback
    is the int8 gather/scatter, still half the bf16 bytes)."""
    import os

    if os.environ.get("POLYKEY_DISABLE_KV_KERNEL", "").lower() in ("1", "true"):
        return False
    return use_paged_kernel(num_kv_heads, head_dim)


def use_paged_kernel(num_kv_heads: int, head_dim: int) -> bool:
    """The DMA kernel needs TPU hardware; the folded head-lane dimension
    (num_kv_heads · head_dim) must be 128-aligned for DMA tiling.
    POLYKEY_DISABLE_PAGED_KERNEL=1 is the operational kill-switch: the
    gather path serves every geometry, so a kernel-compile regression on
    new hardware must never take the whole TPU path down."""
    import os

    if os.environ.get("POLYKEY_DISABLE_PAGED_KERNEL", "").lower() in ("1", "true"):
        return False
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

    gate = use_quantized_paged_kernel if quantized else use_paged_kernel
    if not (force_kernel or interpret or gate(Hk, D)):
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

    def _normalize(acc, l, dtype):
        return (acc / jnp.maximum(l, 1e-9)).astype(dtype)

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
            if sp > 1:
                s = jax.lax.axis_index("sp")
                chunk = -(-P_tables // sp)
                rlo = (s * chunk).astype(jnp.int32)
                rhi = jnp.minimum(P_tables, rlo + chunk).astype(jnp.int32)
                rng = jnp.stack([rlo, rhi])
            else:
                rng = jnp.array([0, P_tables], jnp.int32)
            acc, m, l = inner(q2, kv2, pt2, pos2, win2, rng)
            if sp > 1:
                m_g = jax.lax.pmax(m, "sp")
                corr = jnp.exp(m - m_g)
                l = jax.lax.psum(l * corr, "sp")
                acc = jax.lax.psum(acc * corr, "sp")
            return _normalize(acc, l, q2.dtype)

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
        acc, _, l = inner(
            q[:, 0], kv_pages, page_tables,
            q_positions[:, 0].astype(jnp.int32), win,
            jnp.array([0, P_tables], jnp.int32),
        )
        out = _normalize(acc, l, q.dtype)
    return out[:, None]
