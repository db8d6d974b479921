"""Pallas kernels of the hybrid stacks' decode-bound layers (TPU).

`ssm_state_update` — one decode step of the Mamba-2 recurrence for every
lane: h ← dA · h + (Δ·x) ⊗ B, y = h · C, with the float32 state
[B, H, P, N] aliased in place. Memory-bound: the state is read once and
written once (64 lanes × 4 MiB a layer), everything else is kilobytes.
Δ·x reaches the kernel transposed, channels on sublanes and heads on
lanes ([B, G, P, Hg]), so that a head's factors are a [P, 1] column that
broadcasts along the state's lanes, and y leaves the same way (XLA does
those small transposes); a head's decay is a scalar read from SMEM, and
h · C runs on the otherwise idle MXU. Alone on the chip at the published
shape (my chip runs, PR 43): 0.906 ms a call against 0.656 of bytes; the
form with the decay broadcast from a column and the reduction over lanes
on the vector units read 1.167, XLA's own fusion 0.871.

`gated_delta_state_update` — one decode step of the gated delta rule for
every lane: S ← e^g S + k̃ ⊗ β (v − e^g Sᵀk̃), o = Sᵀq̃ of the NEW state,
with the float32 state aliased in place. Both contractions are taken from
the state as it was read (m = e^g Sᵀk̃; o = e^g Sᵀq̃ + (q̃·k̃) d), so the
state is read once and written once (64 lanes × 2 MiB a layer) and
everything else is kilobytes. k̃ and q̃ reach the kernel transposed
([B, Dk, Hk]: a key head's vector is a [Dk, 1] column that broadcasts
along the state's lanes, the value dims); the contractions over Dk are
sums down the sublanes on the vector units. The state is stored
[B, Hv ÷ n, Dk, n · Dv] (`pack_heads`): n value heads side by side in a
row, so that a value dim which is not whole 128-lane tiles (192: laid out
256 wide, a third more bytes moved than held) makes rows that are (two
heads: 384). n = 1 is [B, Hv, Dk, Dv] itself, and the kernel is then the
one written for it, operation for operation; with n > 1 a row's per-head
factors — decay, β, q̃·k̃, the k̃ and q̃ columns — are spread over the row's
lanes by selects on the lane index, and v and o are [Hv ÷ n, n · Dv], the
same bytes as [Hv, Dv].

`moe_held_experts_grouped` — the held experts of an expert layer as the
chip runs them at EVERY row count (a decode step's 64 rows, a one-window
prefill, the wide dispatches): for every held expert e some row chose,
a(v, e) · w[:, e] · W_down[e], summed over e into a float32 [rows, width
of v]. Two instances, chosen by static arguments: un-gated, a = relu(v ·
W_up[e])² (the experts of a latent layer), and gated, a = silu(v ·
W_gate[e]) ⊙ (v · W_up[e]) (experts on the full hidden: three matrices an
expert). The (row, chosen held expert) pairs are put in order of expert (a
counting sort over the dense combine weights), each expert's run padded
to a row tile, and ONE call walks the tiles with the tile → expert map
scalar-prefetched, gathering a tile's rows and adding its results back
inside the kernel. What is read is the weights of the experts SOME ROW
CHOSE, once each (an expert nobody chose has no tile; the grid's steps
past the live tiles stay on the last live block and fetch nothing); every
pair is computed once, no token is dropped, and a choice of an expert not
held is left out as a zero weight leaves it out. A row whose weights are
all zero has no pairs: the decode step zeroes an idle lane's, so the
call reads exactly the experts `ops/moe.py` `held_experts_hit` counts. A
step's work follows the routing it is given; no row count decides
anything.

`moe_held_experts` — the same sum as ONE masked pass: every row against
every held expert, the combine weight of an expert a row did not choose
being 0, so every held expert's weights are read whatever the routing
chose and the arithmetic is rows × experts held. Rows tile outermost; a
dispatch wider than one row tile re-reads the weights once per tile. No
step the chip serves calls it: where the lanes choose alike it reads half
its bytes for nobody (PERF.md §5). It is the grouped form's yardstick in
the interpret-mode tests and in scripts/tpu_kernel_check.py.

Off-TPU all run the same mathematics in jax.numpy (`*_jnp`); the tests
run the kernels in interpret mode against them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.layers import _activate

_VMEM_LIMIT = 64 * 1024 * 1024
MOE_ROW_TILE = 512
# The grouped form's row tile: a tile's arithmetic stays under an expert's
# weight stream, so padding each expert's run to a tile costs nothing the
# stream does not hide.
MOE_GROUP_TILE = 128
# Rows of one grouped call: they and their float32 result stay in VMEM,
# and the one-hot products that gather and add them grow with the count.
MOE_GROUP_ROWS = 1024
# What those rows and their result may take of _VMEM_LIMIT, two buffers
# each, beside the weights' blocks: rows wider than 2,730 bf16 columns run
# as calls of fewer (`_group_rows`; 7,680 wide: 256).
_GROUP_RESIDENT_BYTES = 32 * 1024 * 1024


def use_kernels() -> bool:
    return jax.default_backend() == "tpu"


# -- Mamba-2 decode state update -------------------------------------------


def ssm_state_update_jnp(h, dA, xdt, Bm, Cm):
    """h [B, H, P, N] f32, dA [B, H], xdt [B, H, P], Bm / Cm [B, G, N]
    (all float32) → (h_new, y [B, H, P])."""
    B, H, P, N = h.shape
    G = Bm.shape[1]
    hg = h.reshape(B, G, H // G, P, N)
    new = (dA.reshape(B, G, H // G, 1, 1) * hg
           + xdt.reshape(B, G, H // G, P, 1) * Bm[:, :, None, None, :])
    y = jnp.sum(new * Cm[:, :, None, None, :], axis=-1)
    return new.reshape(B, H, P, N), y.reshape(B, H, P)


def _ssm_kernel(da_ref, x_ref, b_ref, c_ref, h_ref, out_ref, y_ref):
    # da [B, H] whole, in SMEM (a head's decay is a scalar); blocks of one
    # lane: x [G, P, Hg], b / c [G, N], h / out [G·Hg, P, N], y [G, P, Hg].
    G, N = b_ref.shape
    Hg = y_ref.shape[-1]
    lane = pl.program_id(0)

    def group(g, carry):
        xs = x_ref[g]                              # [P, Hg]
        bn = b_ref[pl.ds(g, 1), :]                 # [1, N]
        # h · C on the MXU, which is idle here: every column of the
        # product is the same y; float32 through the multi-pass form.
        cmat = jnp.broadcast_to(c_ref[pl.ds(g, 1), :], (N, N))
        for j in range(Hg):
            head = g * Hg + j
            new = da_ref[lane, head] * h_ref[head] + xs[:, j:j + 1] * bn
            out_ref[head] = new
            y = jax.lax.dot_general(
                new, cmat, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            y_ref[g, :, j:j + 1] = y[:, j:j + 1]
        return carry

    jax.lax.fori_loop(0, G, group, 0)


def ssm_state_update(h, dA, xdt, Bm, Cm, *, interpret: bool = False):
    """The kernel form of `ssm_state_update_jnp`; `h` is updated in place
    (aliased), so the caller's donated state buffer is the output."""
    B, H, P, N = h.shape
    G = Bm.shape[1]
    Hg = H // G
    # [B, G, P, Hg]: column j is head j's Δ·x over P.
    cols = xdt.reshape(B, G, Hg, P).transpose(0, 1, 3, 2)
    lane = lambda b: (b, 0, 0, 0)  # noqa: E731
    new, y = pl.pallas_call(
        _ssm_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, G, P, Hg), lane),
            pl.BlockSpec((None, G, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, G, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, H, P, N), lane),
        ],
        out_specs=[
            pl.BlockSpec((None, H, P, N), lane),
            pl.BlockSpec((None, G, P, Hg), lane),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(h.shape, h.dtype),
            jax.ShapeDtypeStruct((B, G, P, Hg), jnp.float32),
        ],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ssm_state_update",
    )(dA, cols, Bm, Cm, h)
    return new, y.transpose(0, 1, 3, 2).reshape(B, H, P)


# -- gated delta rule decode state update ----------------------------------


def pack_heads(S, per_row: int):
    """[.., Hv, Dk, Dv] → the stored layout [.., Hv ÷ n, Dk, n · Dv]: row
    r holds heads r·n .. r·n + n − 1 side by side, head j of them in
    columns j·Dv .. (j + 1)·Dv − 1. n = 1: S itself."""
    if per_row == 1:
        return S
    *lead, heads, Dk, Dv = S.shape
    return S.reshape(*lead, heads // per_row, per_row, Dk, Dv).swapaxes(
        -3, -2).reshape(*lead, heads // per_row, Dk, per_row * Dv)


def unpack_heads(S, per_row: int):
    """The inverse of `pack_heads`."""
    if per_row == 1:
        return S
    *lead, rows, Dk, width = S.shape
    return S.reshape(*lead, rows, Dk, per_row, width // per_row).swapaxes(
        -3, -2).reshape(*lead, rows * per_row, Dk, width // per_row)


def gated_delta_state_update_jnp(S, decay, beta, k, q, v):
    """S the stored state (`pack_heads` of [B, Hv, Dk, Dv]; the heads a
    row holds are read off its width against v's), decay = e^g and beta
    [B, Hv], k / q [B, Hk, Dk] (L2-normed, q scaled; value head j reads
    key head j div Hv/Hk), v [B, Hv, Dv], all float32 → (S_new in the
    stored layout, o [B, Hv, Dv]). A lane with decay 1 and beta 0 keeps
    its state bit for bit."""
    per_row = S.shape[-1] // v.shape[-1]
    S = unpack_heads(S, per_row)
    rep = S.shape[1] // k.shape[1]
    kh, qh = jnp.repeat(k, rep, axis=1), jnp.repeat(q, rep, axis=1)
    hi = jax.lax.Precision.HIGHEST
    m = decay[..., None] * jnp.einsum("bhkv,bhk->bhv", S, kh, precision=hi)
    d = beta[..., None] * (v - m)
    o = (decay[..., None] * jnp.einsum("bhkv,bhk->bhv", S, qh, precision=hi)
         + jnp.sum(qh * kh, axis=-1, keepdims=True) * d)
    new = decay[..., None, None] * S + kh[..., :, None] * d[..., None, :]
    return pack_heads(new, per_row), o


def _delta_kernel(decay_ref, beta_ref, qk_ref, kt_ref, qt_ref, v_ref, s_ref,
                  out_ref, o_ref, *, per_row: int):
    # decay / beta [B, Hv] and qk = q̃·k̃ [B, Hk] whole, in SMEM (scalars of
    # a head); blocks of one lane: kt / qt [Dk, Hk], v / o [rows, n · Dv],
    # s / out [rows, Dk, n · Dv], n = per_row heads side by side in a row.
    rows, width = v_ref.shape
    Hk = kt_ref.shape[1]
    rep = rows * per_row // Hk
    lane = pl.program_id(0)
    # Where each head of a row but the first begins, as a mask of lanes.
    starts = [
        jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) >= j * width // per_row
        for j in range(1, per_row)]

    def spread(of_head):
        """One factor a head of the row → that factor on the head's own
        columns (n = 1: the factor itself)."""
        out = of_head[0]
        for begins, mine in zip(starts, of_head[1:]):
            out = jnp.where(begins, mine, out)
        return out

    for row in range(rows):
        heads = range(row * per_row, (row + 1) * per_row)
        kc = spread([kt_ref[:, h // rep:h // rep + 1] for h in heads])
        qc = spread([qt_ref[:, h // rep:h // rep + 1] for h in heads])
        decay = spread([decay_ref[lane, h] for h in heads])
        S = s_ref[row]
        m = decay * jnp.sum(S * kc, axis=0, keepdims=True)     # [1, n · Dv]
        d = spread([beta_ref[lane, h] for h in heads]) \
            * (v_ref[row:row + 1, :] - m)
        o_ref[row:row + 1, :] = (
            decay * jnp.sum(S * qc, axis=0, keepdims=True)
            + spread([qk_ref[lane, h // rep] for h in heads]) * d)
        out_ref[row] = decay * S + kc * d


def gated_delta_state_update(S, decay, beta, k, q, v, *,
                             interpret: bool = False):
    """The kernel form of `gated_delta_state_update_jnp`; `S` is updated
    in place (aliased), so the caller's donated state buffer is the
    output."""
    B, rows, Dk, width = S.shape
    Hk, (Hv, Dv) = k.shape[1], v.shape[1:]
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    column = pl.BlockSpec((None, Dk, Hk), lambda b: (b, 0, 0))
    row = pl.BlockSpec((None, rows, width), lambda b: (b, 0, 0))
    state = pl.BlockSpec((None, rows, Dk, width), lambda b: (b, 0, 0, 0))
    new, o = pl.pallas_call(
        functools.partial(_delta_kernel, per_row=width // Dv),
        grid=(B,),
        in_specs=[scalars, scalars, scalars, column, column, row, state],
        out_specs=[state, row],
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, rows, width), jnp.float32)],
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="gated_delta_state_update",
    )(decay, beta, jnp.sum(q * k, axis=-1), k.transpose(0, 2, 1),
      q.transpose(0, 2, 1), v.reshape(B, rows, width), S)
    return new, o.reshape(B, Hv, Dv)


# -- held experts of an expert layer ---------------------------------------

def moe_held_experts_jnp(v, up, down, weights, *, gate=None,
                         activation: str = "relu2"):
    """v [R, L], up [E, L, I], down [E, I, L], weights [R, E] float32
    (0 where a row did not choose the expert) → float32 [R, L]. `gate`
    [E, L, I]: the gated form, activation(v · gate) ⊙ (v · up)."""
    h = jnp.einsum("rl,eli->eri", v, up, preferred_element_type=jnp.float32)
    if gate is None:
        a = _activate(h, activation)
    else:
        a = _activate(jnp.einsum("rl,eli->eri", v, gate,
                                 preferred_element_type=jnp.float32),
                      activation) * h
    a = a * weights.T[:, :, None]
    return jnp.einsum("eri,eil->rl", a.astype(v.dtype), down,
                      preferred_element_type=jnp.float32)


def _moe_kernel(*refs, activation: str, gated: bool):
    if gated:
        v_ref, w_ref, gate_ref, up_ref, down_ref, out_ref = refs
    else:
        v_ref, w_ref, up_ref, down_ref, out_ref = refs
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = v_ref[...]
    h = jnp.dot(v, up_ref[...], preferred_element_type=jnp.float32)
    if gated:
        a = _activate(jnp.dot(v, gate_ref[...],
                              preferred_element_type=jnp.float32),
                      activation) * h
    else:
        a = _activate(h, activation)
    a = a * w_ref[...]
    out_ref[...] += jnp.dot(a.astype(v.dtype), down_ref[...],
                            preferred_element_type=jnp.float32)


_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024


def _inner_tile(inner: int, row_bytes: int = 0) -> int:
    """The widest 128-aligned divisor of the experts' width up to 1024
    (2688 → 896, 1536 → 768) whose block of a weight — `row_bytes` a
    column of it: the width the experts read, in bytes — stays within
    _WEIGHT_BLOCK_BYTES (7,680 wide: 2048 → 256; three matrices' blocks,
    two buffers each, stand in VMEM beside the rows)."""
    best = None
    for t in range(128, min(inner, 1024) + 1, 128):
        if inner % t == 0 and (best is None
                               or t * row_bytes <= _WEIGHT_BLOCK_BYTES):
            best = t
    return inner if best is None else best


def moe_held_experts(v, up, down, weights, *, gate=None,
                     activation: str = "relu2", interpret: bool = False):
    """The kernel form of `moe_held_experts_jnp`."""
    R, L = v.shape
    E, _, inner = up.shape
    tile = R if R <= MOE_ROW_TILE else MOE_ROW_TILE
    pad = -R % tile
    if pad:
        v = jnp.pad(v, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    rows = R + pad
    it = _inner_tile(inner, L * up.dtype.itemsize)
    # [E, rows, 1]: an expert's weights as a column that broadcasts along
    # the activation's lanes.
    wcol = weights.astype(jnp.float32).T[:, :, None]
    into = pl.BlockSpec((None, L, it), lambda r, e, i: (e, 0, i))
    ins = [up] if gate is None else [gate, up]
    out = pl.pallas_call(
        functools.partial(_moe_kernel, activation=activation,
                          gated=gate is not None),
        grid=(rows // tile, E, inner // it),
        in_specs=[
            pl.BlockSpec((tile, L), lambda r, e, i: (r, 0)),
            pl.BlockSpec((None, tile, 1), lambda r, e, i: (e, r, 0)),
            *[into] * len(ins),
            pl.BlockSpec((None, it, L), lambda r, e, i: (e, i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, L), lambda r, e, i: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_held_experts",
    )(v, wcol, *ins, down)
    return out[:R]


def group_rows_by_expert(weights, chosen: int, tile: int):
    """The expert-sorted, tile-padded order of a routing, as a counting
    sort leaves it — no element gathered, scattered or compared with
    another one by one. `weights` [R, E] float32: a row's combine weight
    for each held expert, 0 where it did not choose it (such a pair adds
    nothing in any form, so it is not a pair); `chosen`: the most experts
    a row has a weight for. Returns (rank [E, 1, R] int32: a row's place
    in its expert's run, the rows in order, -1 where the row is not in it;
    tile_expert [tiles] and tile_rank [tiles]: the expert a tile belongs
    to and the run's place its first row has; live [1]: the tiles in use)
    — tiles: the static bound; one past the live count repeats the last
    live one, so it fetches nothing new."""
    R, E = weights.shape
    chose = weights != 0
    tiles = min(-(-R * min(chosen, E) // tile) + E, -(-R // tile) * E)
    # Rows above a row that chose the same expert: a product with the
    # strict lower triangle, exact in float32 (counts up to R).
    above = jnp.dot(jnp.tri(R, k=-1, dtype=jnp.bfloat16),
                    chose.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
    rank = jnp.where(chose, above, -1).T[:, None, :]
    counts = jnp.sum(chose, axis=0, dtype=jnp.int32)
    tiles_of = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    live = tile_end[-1]
    at = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                     jnp.maximum(live - 1, 0))
    # The expert whose tiles include `at`: the experts that end at or
    # before it, counted (no table look-up).
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= at[:, None], axis=1, dtype=jnp.int32),
        E - 1)
    started = jnp.max(jnp.where(tile_start[None, :] <= at[:, None],
                                tile_start[None, :], 0), axis=1)
    return (rank, tile_expert, ((at - started) * tile).astype(jnp.int32),
            live.astype(jnp.int32).reshape(1))


def _moe_grouped_kernel(expert_ref, rank_ref, live_ref, *refs,
                        activation: str, gated: bool):
    del expert_ref                       # the index maps read it
    if gated:
        (v_ref, place_ref, w_ref, gate_ref, up_ref, down_ref,
         out_ref, x_ref, y_ref, col_ref) = refs
    else:
        (v_ref, place_ref, w_ref, up_ref, down_ref,
         out_ref, x_ref, y_ref, col_ref) = refs
    R = v_ref.shape[0]
    tile = x_ref.shape[0]
    # (program ids are read out here: interpret mode has none inside a
    # branch)
    t, i = pl.program_id(0), pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1

    @pl.when((t == 0) & first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t < live_ref[0])
    def _():
        # pick[j, r]: row r is the tile's j-th row (its place in the
        # expert's run is the tile's first + j); a place past the run's
        # end picks none.
        pick = place_ref[...] == rank_ref[t] + jax.lax.broadcasted_iota(
            jnp.int32, (tile, R), 0)

        @pl.when(first)
        def _():
            # The tile's rows, gathered on the MXU: a one-hot row picks
            # one bf16 row exactly. Their combine weights likewise.
            x_ref[...] = jnp.dot(
                pick.astype(v_ref.dtype), v_ref[...],
                preferred_element_type=jnp.float32).astype(x_ref.dtype)
            col_ref[...] = jnp.sum(jnp.where(pick, w_ref[...], 0.0),
                                   axis=1, keepdims=True)

        x = x_ref[...]
        h = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        if gated:
            a = _activate(jnp.dot(x, gate_ref[...],
                                  preferred_element_type=jnp.float32),
                          activation) * h
        else:
            a = _activate(h, activation)
        a = a * col_ref[...]
        y = jnp.dot(a.astype(x.dtype), down_ref[...],
                    preferred_element_type=jnp.float32)

        @pl.when(first)
        def _():
            y_ref[...] = y

        @pl.when(jnp.logical_not(first))
        def _():
            y_ref[...] += y

        @pl.when(last)
        def _():
            # Each place's float32 result added to its row, on the MXU
            # too: the one-hot transposed times the result split into
            # three bf16 parts (24 bits of mantissa), summed in float32.
            put = pick.astype(jnp.bfloat16)
            rest = y_ref[...]
            for _ in range(3):
                part = rest.astype(jnp.bfloat16)
                out_ref[...] += jax.lax.dot_general(
                    put, part, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                rest = rest - part.astype(jnp.float32)


def moe_held_experts_grouped(v, up, down, weights, *, chosen: int,
                             gate=None, activation: str = "relu2",
                             interpret: bool = False):
    """`moe_held_experts_jnp`'s sum, each (row, chosen held expert) pair
    computed once (`chosen`: the most held experts a row may have chosen,
    which bounds the pairs): the grid walks the expert-sorted row tiles
    (group_rows_by_expert), the rows and the float32 result stay in VMEM
    for the whole call, and a tile's rows are gathered — its results added
    to their rows — by one-hot products on the MXU, under the weight
    stream (XLA's own gather of the sorted rows cost more than the
    experts' bytes). More than `_group_rows` rows run as calls of that
    many."""
    width_bytes = v.shape[1] * v.dtype.itemsize
    call = functools.partial(
        _grouped_call, chosen=chosen, activation=activation,
        tile=MOE_GROUP_TILE, inner_tile=_inner_tile(up.shape[2], width_bytes),
        interpret=interpret)
    rows = _group_rows(v.shape[1], v.dtype.itemsize)
    return jnp.concatenate([
        call(v[r:r + rows], up, down, weights[r:r + rows], gate)
        for r in range(0, v.shape[0], rows)])


def _group_rows(width: int, itemsize: int) -> int:
    """Rows of one grouped call: MOE_GROUP_ROWS where rows of `width`
    columns and their float32 result fit _GROUP_RESIDENT_BYTES, else the
    whole tiles of 128 that do."""
    fit = _GROUP_RESIDENT_BYTES // (2 * width * (itemsize + 4))
    return min(MOE_GROUP_ROWS, max(fit // 128, 1) * 128)


# Jitted by itself: a prefill module calls it once an expert layer (and once
# a thousand rows), and traces and lowers the kernel once for all of them.
@functools.partial(jax.jit, static_argnames=(
    "chosen", "activation", "tile", "inner_tile", "interpret"))
def _grouped_call(v, up, down, weights, gate, *, chosen: int, activation: str,
                  tile: int, inner_tile: int, interpret: bool):
    R, L = v.shape
    E, _, inner = up.shape
    pad = -R % 128                  # the one-hot products' contraction
    if pad:
        v = jnp.pad(v, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    rows = R + pad
    weights = weights.astype(jnp.float32)
    rank, tile_expert, tile_rank, live = group_rows_by_expert(
        weights, chosen, tile)
    it = inner_tile
    last = inner // it - 1

    def whole(t, i, *_):
        return 0, 0

    def of_expert(t, i, expert_ref, rank_ref, live_ref):
        return expert_ref[t], 0, 0

    def block(t, i, live_ref):
        # Odd tiles walk the experts' width backwards, so a run longer
        # than a tile keeps the block at the turn; a tile past the live
        # count stays on the last live step's blocks.
        on = jnp.minimum(t, jnp.maximum(live_ref[0] - 1, 0))
        i = jnp.where(t < live_ref[0], i, last)
        return jnp.where(on % 2 == 1, last - i, i)

    into = pl.BlockSpec(
        (None, L, it),
        lambda t, i, expert_ref, rank_ref, live_ref: (
            expert_ref[t], 0, block(t, i, live_ref)))
    ins = [up] if gate is None else [gate, up]
    out = pl.pallas_call(
        functools.partial(_moe_grouped_kernel, activation=activation,
                          gated=gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tile_expert.shape[0], last + 1),
            in_specs=[
                pl.BlockSpec((rows, L), whole),
                pl.BlockSpec((None, 1, rows), of_expert),
                pl.BlockSpec((None, 1, rows), of_expert),
                *[into] * len(ins),
                pl.BlockSpec(
                    (None, it, L),
                    lambda t, i, expert_ref, rank_ref, live_ref: (
                        expert_ref[t], block(t, i, live_ref), 0)),
            ],
            out_specs=pl.BlockSpec((rows, L), whole),
            scratch_shapes=[pltpu.VMEM((tile, L), v.dtype),
                            pltpu.VMEM((tile, L), jnp.float32),
                            pltpu.VMEM((tile, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_held_experts_grouped",
    )(tile_expert, tile_rank, live, v, rank, weights.T[:, None, :],
      *ins, down)
    return out[:R]
