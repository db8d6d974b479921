"""Pallas paged-KV WRITE kernel (TPU) — the decode-step scatter, done as DMA.

Why this exists: the XLA scatter in ops/paged_attention.paged_write
(`k_pages.at[page_ids, offsets].set(k_new)`) lowers on TPU to a
sequential per-row update loop — for a decode step that is
2 (k,v) x num_layers x B tiny dynamic-update-slices, measured at ~10 ms
of the ~21 ms step at 1B/B=32 geometry
(PERF.md). The write itself moves only B x Hk x D x 2 bytes per layer
(~100 KB) — it is pure launch/serialization overhead.

A row cannot be DMA'd directly into its page: pool pages are tiled
(8, 128) in their last two dims, and DMA slices at arbitrary sublane
offsets (the row's position within the page) are illegal. So the kernel
does a two-wave page-granular read-modify-write, one program total:

  wave 1: start ALL page-read DMAs (pool page -> VMEM buffer) at once,
          across every pool and every lane;
  blend:  per lane (static unrolled loop), select the lane's row into
          the buffered page at its offset — pure vector ops;
  wave 2: start ALL page write-back DMAs, wait.

Every DMA in a wave is in flight concurrently, so the cost is ~two page
DMA latencies + B small vector blends, independent of B's serialization.
The pools are input_output_aliased — in place, no pool copy (the engine
donates the pool through every dispatch).

The kernel is generic over a LIST of (pool, rows) writes sharing one
(page, offset) index layout; a page of a pool is `span` consecutive
[ps, ·] entries of it, read and written back under ONE descriptor each
way. The fp path writes the ONE data pool ([2N, ps, Hk*D] — the stored
layout of engine/kv_cache.py as page halves: page p's K at 2p, its V at
2p + 1, span 2; heads folded into lanes, taken as it lies: no pool is
reshaped here or on return); the int8-KV path adds the two bf16 scale
pools [N, ps, Hk] (span 1) in the same waves. N is whatever the caller's
page ids address — the model step passes the whole stack, ids offset by
layer · num_pages, so the aliased output IS the donated stacked pool.

Garbage-page collisions are intended: inactive lanes all target page 0
(engine convention, engine.py "Inactive slots"); several lanes then RMW
page 0 concurrently and *some* full page wins — page 0 is never read
unmasked. Active lanes never share a page (allocator invariant: the
decode write is one row per lane, each lane a different sequence), so
their full-page write-backs cannot clobber each other; rows that share
a page must not be written as lanes of one wave.

Every lane's page stays in VMEM between the waves: the call raises its
VMEM limit where that is more than the compiler's default allows
(`_vmem_limit`; 64 lanes of 30 KV heads of 128) and is otherwise the call
it always was.

Hk*D must be 128-aligned for the folded data-pool DMA — the same
`use_paged_kernel` gate as the read kernel. Off-TPU
callers keep the XLA scatter.

Reference obligation: none — the reference has no KV cache at all
(SURVEY.md §2b "Paged KV cache" is north-star-owed); this is the
TPU-idiomatic half of that component.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# What a kernel's VMEM may hold unless the call says otherwise (the
# compiler's scoped default), and what this call asks for where its pages
# in flight need more.
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024


def _vmem_limit(pools: list, rows: list):
    """None — the call as it always was — while every lane's page and row
    fit the compiler's default with room to spare (64 lanes of K and V
    pages of 16 x 512 columns are 2 MB); twice their bytes where they do
    not (64 lanes x 30 KV heads of 128: 15.7 MB of pages in flight)."""
    held = sum(
        (r.shape[0] * r.shape[1] * p.shape[1] * p.shape[2] + r.size)
        * p.dtype.itemsize for p, r in zip(pools, rows))
    if held <= _SCOPED_VMEM_DEFAULT // 2:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=2 * held)


def _make_kernel(n_pools: int, B: int, ps: int):
    """Kernel body over `n_pools` (rows, pool_in, pool_out, buf, 2 sems)
    groups; arity varies with the pool list, so the body is built here."""

    def kernel(*refs):
        # Ref order: 2 scalar-prefetch, n rows, n pool inputs (aliased —
        # unused), n pool outputs, then scratch.
        pids_ref, offs_ref = refs[0], refs[1]
        rows = refs[2:2 + n_pools]
        outs = refs[2 + 2 * n_pools:2 + 3 * n_pools]
        scratch = refs[2 + 3 * n_pools:]
        bufs = scratch[:n_pools]
        r_sems = scratch[n_pools:2 * n_pools]
        w_sems = scratch[2 * n_pools:3 * n_pools]

        def page(i, b):           # lane b's page of pool i: `span` entries
            span = bufs[i].shape[1]
            return outs[i].at[pl.ds(span * pids_ref[b], span)]

        def read_dma(i, b):
            return pltpu.make_async_copy(
                page(i, b), bufs[i].at[b], r_sems[i].at[b]
            )

        def write_dma(i, b):
            return pltpu.make_async_copy(
                bufs[i].at[b], page(i, b), w_sems[i].at[b]
            )

        # Wave 1: every lane's page reads, all pools, all at once.
        for b in range(B):
            for i in range(n_pools):
                read_dma(i, b).start()

        sel = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
        for b in range(B):
            for i in range(n_pools):
                read_dma(i, b).wait()
                bufs[i][b] = jnp.where(
                    sel == offs_ref[b], rows[i][b], bufs[i][b]
                )
                # Wave 2 starts per lane as soon as its blend lands.
                write_dma(i, b).start()

        for b in range(B):
            for i in range(n_pools):
                write_dma(i, b).wait()

    return kernel


def paged_write_rows_kernel(
    pools: list,              # data [2N, ps, Hk*D] and/or scale [N, ps, Hk]
    rows: list,               # matching [B, span, 1, ·]: [B, 2, 1, Hk*D] /
                              # [B, 1, 1, Hk]; a row broadcasts against
                              # the positions of its entry of the page
    page_ids: jax.Array,      # [B] int32
    offsets: jax.Array,       # [B] int32
    *,
    interpret: bool = False,
) -> tuple:
    """In-place page RMW of each (pool, rows) pair at one shared
    (page, offset) per lane; returns the (aliased) pools, same order."""
    n = len(pools)
    B = rows[0].shape[0]
    ps = pools[0].shape[1]
    rows = [r.astype(p.dtype) for p, r in zip(pools, rows)]

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    row_specs = [
        pl.BlockSpec(r.shape, lambda *_: (0, 0, 0, 0),
                     memory_space=pltpu.VMEM)
        for r in rows
    ]
    outs = pl.pallas_call(
        _make_kernel(n, B, ps),
        out_shape=tuple(
            jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=row_specs + [any_spec] * n,
            out_specs=[any_spec] * n,
            scratch_shapes=(
                [pltpu.VMEM((B, r.shape[1], ps, p.shape[2]), p.dtype)
                 for p, r in zip(pools, rows)]
                + [pltpu.SemaphoreType.DMA((B,))] * (2 * n)
            ),
        ),
        # Flattened input positions incl. the 2 scalar-prefetch args:
        # pids=0 offs=1 rows=2..2+n-1 pools=2+n..2+2n-1.
        input_output_aliases={2 + n + i: i for i in range(n)},
        compiler_params=_vmem_limit(pools, rows),
        interpret=interpret,
        name="paged_kv_write",
    )(
        page_ids.astype(jnp.int32),
        offsets.astype(jnp.int32),
        *rows,
        *pools,
    )
    return tuple(outs)
