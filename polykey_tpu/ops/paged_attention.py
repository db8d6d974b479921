"""Paged attention: read KV through page-table indirection.

`paged_gather_kv` is the reference implementation (pure jnp): materialize the
KV of the table it is HANDED by gathering whole pages, then run the standard
masked attention. Correct everywhere, but every gathered position goes
through HBM twice more — so a decode step takes the Pallas kernel instead
(ops/paged_attention_kernel.py paged_attention_decode: per-page DMA, only
valid pages move), and a prefill dispatch gathers no more of a table than
its furthest query can see (`gather_needed_pages`: the leading pages that
hold positions 0 .. max(start) + T − 1 — a window's worth a turn of a
loop whose trip count is read from the positions — written in place into
a staging buffer in the blockwise kernel's own layout; the kernel walks
its key blocks as far as that position and no further, so the rest of a
`max_seq_len` table is neither gathered nor streamed).

Page-table convention (engine/kv_cache.py): page_tables[b, j] is the page id
holding positions [j*page_size, (j+1)*page_size); unused tail entries point
at the reserved garbage page 0 and are excluded by the position mask.

The pool arrives in the stored layout (engine/kv_cache.py) viewed as page
HALVES: ONE array [2N, page_size, Hk·D], page p's K at 2p and its V at
2p + 1, heads folded into the last dimension — the stored
[N, 2, page_size, Hk·D] with its leading dimensions merged; int8 KV is the
triple (values, k scales, v scales) with scale pools [N, page_size, Hk].
The XLA paths address a half by ONE index, as when K and V were pools
apart (a second, K / V index component made a prefill dispatch 0.7–3.5 ms
longer on a v5e: PERF.md §6, PR 46); the Pallas kernels take a page's two
halves under one descriptor. Every path here folds or unfolds the ROWS it
writes or the pages it gathered — never a pool. N may be the whole stack's
L·num_pages with the caller's page ids offset by layer · num_pages
(models/transformer.py `_run_paged_stack`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import struct


def paged_gather_kv(
    kv_pages: jax.Array,      # [2 · num_pages, page_size, Hk·D]
    page_tables: jax.Array,   # [B, P] int32
    head_dim: int,
) -> tuple[jax.Array, jax.Array]:
    """Materialize [B, P*page_size, Hk, D] K/V windows from the pool.

    Two gathers of page halves: one gather of whole pages followed by a
    slice would write every window a second time."""
    B, P = page_tables.shape
    page_size = kv_pages.shape[1]
    k = kv_pages[2 * page_tables]  # [B, P, page_size, Hk·D]
    v = kv_pages[2 * page_tables + 1]
    return (
        k.reshape(B, P * page_size, -1, head_dim),
        v.reshape(B, P * page_size, -1, head_dim),
    )


# The fewest keys a piece of a prefill dispatch's gather moves a row (a
# narrower window — a speculative verify's few tokens — gathers this
# many).
MIN_GATHER_KEYS = 128
def prefill_gather_keys(window: int, table_keys: int, page_size: int) -> int:
    """Keys a turn of a prefill dispatch's gather moves a row: the window's
    own, at least MIN_GATHER_KEYS, in whole pages, at most the table's."""
    keys = -(-max(window, MIN_GATHER_KEYS) // page_size) * page_size
    return min(keys, table_keys)


def prefill_gather_turns(keys, window: int, table_keys: int, page_size: int):
    """Turns of the gather that cover positions 0 .. `keys` − 1, where
    `keys` is max(start) + T over a dispatch's rows: a traced scalar in the
    program (`gather_needed_pages`' trip count), an int on the host (the
    engine's `prefill_keys_read_total`) — one rule in arithmetic both
    take, at no device dispatch on the host, so the two cannot drift."""
    piece = prefill_gather_keys(window, table_keys, page_size)
    turns, whole = (keys + piece - 1) // piece, -(-table_keys // piece)
    return whole + (turns - whole) * (turns < whole)       # min(turns, whole)


def prefill_keys_read(keys, window: int, table_keys: int, page_size: int):
    """Keys a row of a prefill dispatch gathers and streams a layer, where
    the dispatch's furthest query sees positions 0 .. `keys` − 1."""
    read = (prefill_gather_turns(keys, window, table_keys, page_size)
            * prefill_gather_keys(window, table_keys, page_size))
    return table_keys + (read - table_keys) * (read < table_keys)


def prefill_bounded(window: int, table_keys: int, page_size: int, width: int,
                    latent: bool, mesh=None) -> bool:
    """Whether a prefill dispatch's attention reads the keys its queries
    can see or its whole tables: the blockwise kernel walks what it is
    handed, so it is handed no more than is seen; the masked reference
    (off the chip, tiny windows, sp / tp under pp) multiplies every key of
    the table whatever lies there, and gathers it in one piece as it
    always did. The engine's `prefill_keys_read_total` asks the same."""
    from .flash_attention import runs_kernel

    if latent:
        return _latent_on_chip(width, window)
    piece = prefill_gather_keys(window, table_keys, page_size)
    return runs_kernel(
        window, -(-table_keys // piece) * piece, width, mesh=mesh)


@struct.dataclass
class PrefillStage:
    """The staging buffers of one prefill dispatch: `parts` (K and V, or a
    latent pool's one row), each the leading keys of every row's table.
    `bounded` (`prefill_bounded`): [rows, heads, S, width], the blockwise
    kernel's layout (its blocks are [keys, width] slabs of one head),
    filled as far as the dispatch's queries see; else the gather's own
    [rows, S, heads, width], the whole table, for the masked reference."""

    parts: tuple
    bounded: bool = struct.field(pytree_node=False)


def prefill_stage(rows: int, table_keys: int, window: int, page_size: int,
                  heads: int, width: int, parts: int, dtype,
                  mesh=None) -> PrefillStage:
    """A prefill dispatch's `PrefillStage`: `parts` buffers of zeros over
    the table's keys (in whole turns of the gather). Made once a dispatch
    and threaded through the layers beside the pool: every layer
    overwrites the same leading keys in place, and what lies past them
    stays zero (masked, never walked). One part is a latent pool's (its
    reader: `latent_prefill_attention`), two are K and V
    (`paged_prefill_attention`, under `mesh`)."""
    bounded = prefill_bounded(
        window, table_keys, page_size, width, parts == 1, mesh)
    piece = prefill_gather_keys(window, table_keys, page_size)
    keys = -(-table_keys // piece) * piece if bounded else table_keys
    shape = ((rows, heads, keys, width) if bounded
             else (rows, keys, heads, width))
    return PrefillStage(
        tuple(jnp.zeros(shape, dtype) for _ in range(parts)), bounded)


def gather_needed_pages(stage: PrefillStage, gather, page_tables: jax.Array,
                        keys, window: int, page_size: int) -> PrefillStage:
    """Fill `stage` with the keys a prefill dispatch's queries can see:
    positions 0 .. `keys` − 1 (`keys` = max position + 1, read from the
    input inside the one program), a window's worth a turn of a loop whose
    trip count is `prefill_gather_turns`'. `gather(tables)` materializes
    the pages of a slice of the table as a tuple of [B, slice keys, heads,
    width], one entry a part of `stage`; a turn writes it, heads major,
    over its keys of the stage in place (a few µs a turn on a v5e,
    whatever it moves). Positions past `keys` are masked for every row, so
    leaving them out changes which masked keys are MOVED, not the sum; a
    chunk that ends at the table's end gathers the whole table, as every
    dispatch did — and as a stage that is not `bounded` still does, in one
    piece."""
    if not stage.bounded:
        return stage.replace(parts=tuple(
            rows.astype(part.dtype)
            for part, rows in zip(stage.parts, gather(page_tables))))
    P = page_tables.shape[1]
    piece = prefill_gather_keys(window, P * page_size, page_size)
    pages = piece // page_size
    # A table that is no whole number of turns ends on the garbage page.
    tables = jnp.pad(
        page_tables, ((0, 0), (0, stage.parts[0].shape[2] // page_size - P)))

    def turn(i, parts):
        got = gather(
            jax.lax.dynamic_slice_in_dim(tables, i * pages, pages, axis=1))
        return tuple(
            _row_major(jax.lax.dynamic_update_slice_in_dim(
                part, jnp.transpose(rows, (0, 2, 1, 3)).astype(part.dtype),
                i * piece, axis=2))
            for part, rows in zip(parts, got)
        )

    turns = prefill_gather_turns(keys, window, P * page_size, page_size)
    return stage.replace(
        parts=jax.lax.fori_loop(0, turns, turn, stage.parts))


def _row_major(x: jax.Array) -> jax.Array:
    """`x`, laid out in memory as its shape reads. The stage is written a
    turn's rows at a time and read by the blockwise kernel, which takes
    its operands row-major: left to itself XLA lays the buffer out for the
    writer (keys major) and re-lays the WHOLE of it out for the kernel,
    every layer."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


def _gathered_kv(kv_pages, page_tables: jax.Array, head_dim: int, dtype):
    """(k, v) [B, P·page_size, Hk, D] of the pages of `page_tables`, an
    int8 pool's dequantized into `dtype`."""
    if not isinstance(kv_pages, tuple):
        return paged_gather_kv(kv_pages, page_tables, head_dim)
    # int8 KV: gather values and scales, dequantize into the compute
    # dtype — the dequant is an elementwise producer XLA fuses into
    # the window consumers, and the pool-side HBM read stays int8.
    values, ks_pool, vs_pool = kv_pages
    k, v = paged_gather_kv(values, page_tables, head_dim)
    B, P = page_tables.shape
    ps, Hk = ks_pool.shape[1], ks_pool.shape[2]
    ks = ks_pool[page_tables].reshape(B, P * ps, Hk)
    vs = vs_pool[page_tables].reshape(B, P * ps, Hk)
    return dequantize_kv(k, ks, dtype), dequantize_kv(v, vs, dtype)


def paged_attention(
    q: jax.Array,             # [B, T, Hq, D]
    kv_pages,                 # [2 · num_pages, page_size, Hk·D], or the
                              # int8 (values, k scales, v scales) triple
    page_tables: jax.Array,   # [B, P]
    q_positions: jax.Array,   # [B, T] absolute positions of the queries
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Attention over the paged KV of the WHOLE table it is handed; returns
    [B, T, Hq, D]. The reference form (tests, a decode step off the chip):
    a prefill dispatch takes `paged_prefill_attention`, which gathers and
    streams the keys its queries can see and no more.

    Slot j of the gathered pages holds position j, so the absolute-position
    causal mask simultaneously hides unwritten slots and garbage-page tails —
    which also makes the gathered pages a valid input for the blockwise
    flash kernel (ops/flash_attention.py): on TPU at prefill widths it takes
    the O(T·D + S·D)-traffic path instead of materializing [.., T, S] logits;
    off-TPU / tiny shapes it falls back to the reference mask internally.
    """
    from .flash_attention import flash_attention

    k, v = _gathered_kv(kv_pages, page_tables, q.shape[-1], q.dtype)
    return flash_attention(
        q, k, v, q_positions,
        scale=scale, logit_softcap=logit_softcap, window=window, mesh=mesh,
    )


def paged_prefill_attention(
    q: jax.Array,             # [B, T, Hq, D]
    kv_pages,                 # as `paged_attention`
    stage: PrefillStage,      # `prefill_stage`: K and V
    page_tables: jax.Array,   # [B, P]
    q_positions: jax.Array,   # [B, T]
    keys,                     # max position of the dispatch + 1
    *,
    scale: float,
    logit_softcap: Optional[float] = None,
    window: Optional[jax.Array] = None,
    mesh=None,
):
    """`paged_attention` of a prefill dispatch over the keys its queries
    can see: the needed pages gathered into `stage` (`gather_needed_pages`),
    the blockwise kernel over the stage as far as the furthest query.
    Returns ([B, T, Hq, D], the stage as this layer leaves it)."""
    from .flash_attention import flash_attention

    page_size = (kv_pages[0] if isinstance(kv_pages, tuple)
                 else kv_pages).shape[1]
    stage = gather_needed_pages(
        stage,
        lambda tables: _gathered_kv(kv_pages, tables, q.shape[-1], q.dtype),
        page_tables, keys, q.shape[1], page_size,
    )
    ctx = flash_attention(
        q, *stage.parts, q_positions,
        scale=scale, logit_softcap=logit_softcap, window=window, mesh=mesh,
        kv_heads_major=stage.bounded,
    )
    return ctx, stage


# The blockwise kernel's blocks for `latent_attention`, query rows and key
# rows alike: a 640-wide row makes the kernel's own 512 x 1024 pair too
# large for the scoped VMEM, and a key block of 512 lets a first window
# skip the table's later blocks.
LATENT_BLOCK = 512


def latent_attention(
    q: jax.Array,             # [B, T, Hq, W]: absorbed query heads
    rows: jax.Array,          # [N, page_size, W]: one-part pages
    page_tables: jax.Array,   # [B, P]
    q_positions: jax.Array,   # [B, T]
    *,
    scale: float,
    v_width: int,
) -> jax.Array:
    """Attention of a latent (MLA) layer in its absorbed form over the
    WHOLE gathered table; returns [B, T, Hq, v_width]. A token's ONE row is
    the key of every head and, in its leading `v_width` columns, the value:
    the gathered window is handed to the blockwise kernel as K AND as V of
    ONE head whose query rows are the (token, head) pairs — a query block
    is a few tokens' heads, so a key block is fetched once for all of
    them, and the blocks past those tokens' positions are skipped — and
    the output's columns past `v_width` (the weighted sum of the rotary
    key and the padding) are dropped. The reference form (tests, a decode
    step off the chip); a prefill dispatch takes
    `latent_prefill_attention`, and a decode step on the chip reads the
    pages where they lie (paged_attention_kernel.mla_latent_decode)."""
    B, _, _, width = q.shape
    table = rows[page_tables].reshape(B, -1, 1, width)
    return _latent_over(q, table, q_positions, scale, v_width, False)


def latent_prefill_attention(
    q: jax.Array,             # [B, T, Hq, W]
    rows: jax.Array,          # [N, page_size, W]
    stage: PrefillStage,      # `prefill_stage`: the rows, one part
    page_tables: jax.Array,   # [B, P]
    q_positions: jax.Array,   # [B, T]
    keys,                     # max position of the dispatch + 1
    *,
    scale: float,
    v_width: int,
):
    """`latent_attention` of a prefill dispatch over the keys its queries
    can see: the needed pages' rows gathered into `stage`
    (`gather_needed_pages`), the blockwise kernel over the stage as far as
    the furthest query (on the chip on bf16 operands: 128 heads against
    one row is arithmetic-bound). Returns ([B, T, Hq, v_width], the stage
    as this layer leaves it)."""
    B, T, _, width = q.shape
    stage = gather_needed_pages(
        stage, lambda tables: (rows[tables].reshape(B, -1, 1, width),),
        page_tables, keys, T, rows.shape[1],
    )
    out = _latent_over(
        q, stage.parts[0], q_positions, scale, v_width, stage.bounded)
    return out, stage


def _latent_on_chip(width: int, window: int) -> bool:
    """Whether a latent prefill of `window` tokens takes the blockwise
    kernel (on the chip, on bf16 operands) or the masked reference."""
    from .paged_attention_kernel import use_paged_kernel

    return use_paged_kernel(1, width) and window >= 128


def _latent_over(q, table, q_positions, scale, v_width, heads_major: bool):
    """The absorbed heads of `q` against `table`, a lane's rows as ONE
    head's keys and values ([B, S, 1, W], or `heads_major` [B, 1, S, W])."""
    from .flash_attention import flash_attention

    B, T, heads, width = q.shape
    on_chip = _latent_on_chip(width, T)
    out = flash_attention(
        q.reshape(B, T * heads, 1, width), table, table,
        jnp.repeat(q_positions, heads, axis=1), scale=scale,
        block_q=LATENT_BLOCK, block_k=LATENT_BLOCK,
        force_kernel=on_chip, native=on_chip, kv_heads_major=heads_major,
    )
    return out.reshape(B, T, heads, width)[..., :v_width]


def quantize_kv_rows(rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(token, head) int8 quantization of KV rows
    [..., Hk, D] → (int8 values, bf16 scales [..., Hk]).

    Quantization divides by the bf16-ROUNDED scale — the value dequant
    will actually multiply by — so the scale's own rounding adds no
    systematic error (only the unavoidable LSB from the bf16 absmax
    step, vs up to 127·|Δscale| if q were computed from the f32 scale)."""
    absmax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
    scale = (jnp.maximum(absmax, 1e-8) / 127.0).astype(jnp.bfloat16)
    q = jnp.clip(
        jnp.round(rows.astype(jnp.float32) / scale[..., None].astype(jnp.float32)),
        -127, 127,
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(values: jax.Array, scales: jax.Array, dtype) -> jax.Array:
    """[..., Hk, D] int8 + [..., Hk] scales → dtype."""
    return (values.astype(dtype) * scales[..., None].astype(dtype))


def paged_write(
    kv_pages,                 # [2 · num_pages, page_size, Hk·D], or the
                              # int8 (values, k scales, v scales) triple
    k_new: jax.Array,         # [B, T, Hk, D]
    v_new: Optional[jax.Array],
    page_tables: jax.Array,   # [B, P]
    positions: jax.Array,     # [B, T] absolute position of each new token
    mesh=None,
):
    """Write new KV into their pages at (page_table[pos // ps], pos % ps);
    returns the pool in the form it came.

    `v_new` None: a ONE-part page (a latent pool, [num_pages, page_size,
    W]): `k_new` [B, T, 1, W] is the token's one row, and page p is entry
    p — every path below indexes `parts · page + part`.

    With int8 KV (the triple — engine/kv_cache.py PagedKV.quantized) the
    rows quantize at write time and the scale pools [N, ps, Hk] take the
    same write path as the data.

    Three paths, fastest applicable wins:
    - T == 1 on TPU: the Pallas DMA write kernel
      (ops/paged_write_kernel.py) — per-lane page RMW into the aliased
      pool. The XLA scatter here lowers to a sequential per-row update
      loop that measured ~10 ms/step of a ~21 ms 1B decode step
      — the kernel makes it ~free.
    - T > 1 with page-aligned consecutive rows (every engine prefill
      chunk: buckets and chunk starts are multiples of page_size): a
      page-granular scatter — T/ps big row updates per lane instead of
      T tiny ones. Picked by a runtime lax.cond so arbitrary callers
      (tests, non-bucket positions) still get exact semantics.
    - otherwise: the per-token XLA scatter.
    """
    quantized = isinstance(kv_pages, tuple)
    Hk, D = k_new.shape[2], k_new.shape[3]

    def fold(rows):           # [B, T, Hk, D] → [B, T, Hk·D], the pool's rows
        return rows.reshape(*rows.shape[:2], Hk * D)

    # The data pool takes a K row and a V row a position, in the two
    # halves of its page; each int8 scale pool one row, sharing the
    # (page, offset) index layout.
    if quantized:
        data, *scale_pools = kv_pages
        k8, k_s = quantize_kv_rows(k_new)
        v8, v_s = quantize_kv_rows(v_new)
        kv_rows = (fold(k8), fold(v8))
        scale_rows = tuple(
            r.astype(p.dtype) for p, r in zip(scale_pools, (k_s, v_s)))
    else:
        data, scale_pools, scale_rows = kv_pages, (), ()
        kv_rows = tuple(
            fold(r) for r in (k_new, v_new) if r is not None)
    parts = len(kv_rows)

    page_size = data.shape[1]
    B, T = positions.shape
    P = page_tables.shape[1]
    batch_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    page_ids = page_tables[batch_idx, positions // page_size]   # [B, T]
    offsets = positions % page_size                             # [B, T]

    pools_in = (data, *scale_pools)

    def repack(pools):
        return tuple(pools) if quantized else pools[0]

    if T == 1:
        from .paged_attention_kernel import use_paged_kernel

        pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        if use_paged_kernel(Hk, D) and pp == 1:
            # A lane's rows as the kernel blends them into the entries of
            # its page: [B, parts, 1, Hk·D] (the two halves of a K/V page),
            # [B, 1, 1, Hk].
            return repack(_write_decode_kernel(
                list(pools_in),
                [jnp.stack(kv_rows, axis=1), *(r[:, None] for r in scale_rows)],
                page_ids[:, 0], offsets[:, 0], mesh, Hk,
            ))

    # The XLA paths: K and V are two scatters into the one pool, each of
    # the parent's form (rows stacked to match a whole page would be
    # copied once more on the way).
    def token_scatter(pools):
        data, *scales = pools
        for half, rows in enumerate(kv_rows):
            data = data.at[parts * page_ids + half, offsets].set(rows)
        return (data, *(
            p.at[page_ids, offsets].set(r) for p, r in zip(scales, scale_rows)
        ))

    if T > 1 and T % page_size == 0:
        n_pg = T // page_size
        consecutive = jnp.all(
            positions == positions[:, :1] + jnp.arange(T, dtype=positions.dtype)
        )
        aligned = jnp.all(positions[:, 0] % page_size == 0) & consecutive

        def pages(rows):      # [B, T, ·] → [B, n_pg, page_size, ·]
            return rows.reshape(B, n_pg, page_size, *rows.shape[2:])

        def page_scatter(pools):
            first = positions[:, 0] // page_size                 # [B]
            pg_idx = first[:, None] + jnp.arange(n_pg, dtype=jnp.int32)
            pg_ids = jnp.take_along_axis(
                page_tables, jnp.clip(pg_idx, 0, P - 1), axis=1
            )                                                    # [B, n_pg]
            data, *scales = pools
            for half, rows in enumerate(kv_rows):
                data = data.at[parts * pg_ids + half].set(pages(rows))
            return (data, *(
                p.at[pg_ids].set(pages(r)) for p, r in zip(scales, scale_rows)
            ))

        return repack(jax.lax.cond(
            aligned, page_scatter, token_scatter, pools_in
        ))

    return repack(token_scatter(pools_in))


def _write_decode_kernel(pools, rows, page_ids, offsets, mesh, Hk):
    """Dispatch the Pallas write kernel over pools and their lanes' rows,
    under shard_map when the mesh shards batch (dp) or heads (tp). Pools are
    replicated over dp/sp, so every replica must apply every lane's
    write: the dp-local updates all-gather (tiny — B rows) before the
    kernel writes the full batch into the local head shard. Mirrors
    paged_attention_decode's specs. The data pool is [2N, ps, Hk·D] with
    rows [B, 2, 1, Hk·D], int8 KV adds scale pools [N, ps, Hk] with rows
    [B, 1, 1, Hk]: tp shards the last dimension of all of them (heads are
    major in the fold, so a shard is Hk/tp whole heads)."""
    from .paged_write_kernel import paged_write_rows_kernel

    dp = mesh.shape.get("dp", 1) if mesh is not None else 1
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if dp <= 1 and tp <= 1:
        return paged_write_rows_kernel(pools, rows, page_ids, offsets)
    B = rows[0].shape[0]
    if B % dp or Hk % tp:
        # Same curated error as the read kernel (paged_attention_kernel
        # .py) — never let uneven sharding surface as an opaque shard_map
        # trace error with no pointer at the real cause.
        raise ValueError(
            f"paged write kernel on mesh: B={B} % dp={dp} and "
            f"Hk={Hk} % tp={tp} must divide evenly"
        )

    from jax.sharding import PartitionSpec as Pspec

    pool_spec = Pspec(None, None, "tp")
    row_spec = Pspec("dp", None, None, "tp")

    def inner(pools_l, rows_l, pid, off):
        if dp > 1:
            rows_l = [
                jax.lax.all_gather(r, "dp", axis=0, tiled=True)
                for r in rows_l
            ]
            pid = jax.lax.all_gather(pid, "dp", axis=0, tiled=True)
            off = jax.lax.all_gather(off, "dp", axis=0, tiled=True)
        return paged_write_rows_kernel(pools_l, rows_l, pid, off)

    sm = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            [pool_spec] * len(pools),
            [row_spec] * len(rows),
            Pspec("dp"),
            Pspec("dp"),
        ),
        out_specs=(pool_spec,) * len(pools),
        check_vma=False,
    )
    return sm(pools, rows, page_ids, offsets)
