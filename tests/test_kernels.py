"""Pallas kernel tests (interpret mode on CPU; compiled path runs on TPU).

Each kernel is checked against the pure-jnp reference oracle
(ops/attention.py, ops/paged_attention.py) across the feature matrix the
served families need: GQA, soft-capping (Gemma-2), sliding windows,
offset/ragged positions, and padding-producing shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.ops.attention import attention, make_attention_mask
from polykey_tpu.ops.flash_attention import flash_attention
from polykey_tpu.ops.paged_attention import paged_attention
from polykey_tpu.ops.paged_attention_kernel import paged_attention_decode

TOL = 2e-5


def _qkv(B, T, S, Hq, Hk, D, dtype=jnp.float32):
    return (
        jax.random.normal(jax.random.PRNGKey(0), (B, T, Hq, D), dtype),
        jax.random.normal(jax.random.PRNGKey(1), (B, S, Hk, D), dtype),
        jax.random.normal(jax.random.PRNGKey(2), (B, S, Hk, D), dtype),
    )


@pytest.mark.parametrize("softcap,win", [
    (None, None), (50.0, None), (None, 48), (30.0, 48),
])
def test_flash_matches_reference(softcap, win):
    B, T, S, Hq, Hk, D = 2, 160, 192, 8, 2, 64
    q, k, v = _qkv(B, T, S, Hq, Hk, D)
    qpos = jnp.broadcast_to(jnp.arange(T), (B, T)) + 16

    mask = make_attention_mask(qpos, S, sliding_window=win)
    ref = attention(q, k, v, mask, scale=0.125, logit_softcap=softcap)
    w = None if win is None else jnp.int32(win)
    out = flash_attention(
        q, k, v, qpos, scale=0.125, logit_softcap=softcap, window=w,
        interpret=True,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_flash_block_padding_and_ragged_positions():
    """T/S not block multiples + per-row position offsets (decode-style)."""
    B, T, S, Hq, Hk, D = 3, 72, 200, 4, 4, 32
    q, k, v = _qkv(B, T, S, Hq, Hk, D)
    starts = jnp.array([0, 17, 101], jnp.int32)
    qpos = starts[:, None] + jnp.arange(T)[None, :]

    ref = attention(
        q, k, v, make_attention_mask(qpos, S), scale=0.2
    )
    out = flash_attention(q, k, v, qpos, scale=0.2, interpret=True)
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_flash_fallback_off_tpu_matches():
    """Without force/interpret, CPU dispatch must take the reference path
    and still honor the window argument."""
    B, T, S, Hq, Hk, D = 1, 32, 32, 2, 1, 16
    q, k, v = _qkv(B, T, S, Hq, Hk, D)
    qpos = jnp.broadcast_to(jnp.arange(T), (B, T))
    ref = attention(
        q, k, v, make_attention_mask(qpos, S, sliding_window=8), scale=0.25
    )
    out = flash_attention(q, k, v, qpos, scale=0.25, window=jnp.int32(8))
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def _paged_case(B, Hq, Hk, D, ps, P, positions):
    N = B * P + 1
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, Hq, D), jnp.float32)
    # The pool in the stored layout (engine/kv_cache.py), as the ops take
    # it: page halves [2N, ps, Hk·D], page p's K at 2p and its V at 2p + 1,
    # heads folded.
    kp = jax.random.normal(jax.random.PRNGKey(1), (N, ps, Hk * D), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(2), (N, ps, Hk * D), jnp.float32)
    kvp = jnp.stack([kp, vp], axis=1).reshape(2 * N, ps, Hk * D)
    pts = np.zeros((B, P), np.int32)
    page = 1
    for b in range(B):
        needed = positions[b][0] // ps + 1
        for j in range(needed):
            pts[b, j] = page
            page += 1
    return q, kvp, jnp.asarray(pts), jnp.asarray(positions, jnp.int32)


@pytest.mark.parametrize("softcap,win", [
    (None, None), (50.0, None), (None, 24), (30.0, 24),
])
def test_paged_decode_kernel_matches_gather(softcap, win):
    q, kvp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(
        q, kvp, pt, pos, scale=0.125, logit_softcap=softcap, window=w
    )
    out = paged_attention_decode(
        q, kvp, pt, pos, scale=0.125, logit_softcap=softcap, window=w,
        interpret=True,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("g", [1, 2, 3])
@pytest.mark.parametrize("win", [None, 24])
def test_paged_decode_kernel_multi_group(g, win):
    """Force small page groups so the group loop runs multiple blocks,
    including a partial last group (P=8 with G=3) and a window whose lo
    lands mid-group (non-DMA'd rows inside a live group must be masked)."""
    q, kvp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(q, kvp, pt, pos, scale=0.125, window=w)
    out = paged_attention_decode(
        q, kvp, pt, pos, scale=0.125, window=w,
        interpret=True, pages_per_block=g,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_paged_decode_kernel_no_gqa_single_page():
    q, kvp, pt, pos = _paged_case(1, 2, 2, 32, 16, 4, [[5]])
    ref = paged_attention(q, kvp, pt, pos, scale=0.125)
    out = paged_attention_decode(
        q, kvp, pt, pos, scale=0.125, interpret=True
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


# What the page-streaming schedule can get wrong (fetches kept in flight
# ACROSS sequences, only the live blocks walked, the block width taken from
# the folded width): (B, Hk, positions, window, split, pages_per_block).
# Pages are 16 positions, tables 64 pages, D = 128, so Hk = 2 / 8 are the
# 256 / 1024 folded lanes of a tp = 4 shard and of a whole chip: f32 pools
# there take 32- and 8-page blocks (512 and 128 positions). `split` = r runs
# the kernel on the page sub-ranges [0, r) and [r, 64) — a context-parallel
# shard's view, in which a short sequence has NO visible page — and merges
# the two unnormalised states the way the sp axis does.
_SCHEDULE_CASES = {
    "neighbours-of-very-different-lengths":
        (4, 2, [3, 1000, 17, 700], None, None, 0),
    "no-visible-page-between-two-live-ones":
        (4, 2, [300, 50, 20, 400], None, 8, 0),
    "first-and-last-have-no-visible-page":
        (4, 2, [20, 300, 500, 30], None, 8, 0),
    "nobody-has-a-visible-page": (3, 2, [20, 100, 60], None, 8, 0),
    "only-sequence": (1, 2, [333], None, None, 0),
    "only-sequence-cut-by-a-split": (1, 2, [333], None, 16, 0),
    "context-ends-on-a-block-edge":
        (4, 2, [511, 512, 1023, 15], None, None, 0),
    "window-lifts-lo-past-whole-blocks":
        (4, 2, [700, 90, 300, 1000], 100, None, 0),
    "window-and-split": (4, 2, [700, 90, 300, 1000], 200, 40, 0),
    "folded-width-256": (3, 2, [600, 130, 1023], None, None, 0),
    "folded-width-1024": (3, 8, [600, 130, 1023], None, None, 0),
    "pages-per-block-given-3": (3, 2, [600, 130, 1023], None, None, 3),
    "pages-per-block-given-5-split": (3, 2, [600, 130, 1023], 300, 23, 5),
}


@pytest.mark.parametrize("case", _SCHEDULE_CASES)
def test_paged_decode_kernel_schedule(case):
    from polykey_tpu.ops import paged_attention_kernel as pak

    B, Hk, positions, win, split, ppb = _SCHEDULE_CASES[case]
    ps, P, D, groups = 16, 64, 128, 2
    q, kvp, pt, pos = _paged_case(
        B, Hk * groups, Hk, D, ps, P, [[p] for p in positions])
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(q, kvp, pt, pos, scale=0.09, window=w)

    def state(rlo, rhi):
        return pak._decode_call(
            q[:, 0], kvp, pt, pos[:, 0],
            jnp.asarray([0 if win is None else win], jnp.int32),
            jnp.asarray([rlo, rhi], jnp.int32),
            scale=0.09, logit_softcap=None, interpret=True, state=True,
            pages_per_block=ppb,
        )

    if split is None:
        acc, m, den = state(0, P)
    else:
        (a1, m1, l1), (a2, m2, l2) = state(0, split), state(split, P)
        # A shard with no visible page hands back the empty state, which
        # the merge weighs with exp(-1e30 - m) = 0.
        empty = np.asarray(positions) // ps < split
        assert np.all(np.asarray(l2)[empty] == 0.0)
        assert np.all(np.asarray(a2)[empty] == 0.0)
        m = jnp.maximum(m1, m2)
        den = l1 * jnp.exp(m1 - m) + l2 * jnp.exp(m2 - m)
        acc = a1 * jnp.exp(m1 - m) + a2 * jnp.exp(m2 - m)
    out = (acc / jnp.maximum(den, 1e-9))[:, None]
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


# Sequences walked inside a program, a block computed by row tiles, one
# normalised output. Pages are 16 positions, tables 64 pages, two KV heads
# of 128 (256 folded lanes): f32 pools there take 32-page blocks (512
# positions) of two 256-position row tiles. Positions (context − 1) at a
# page's, a row tile's and a block's edges (127 / 128 too: the tile of a
# chip's 1024 lanes), then the cells' mix.
_EDGES = [0, 15, 16, 127, 128, 254, 255, 256, 510, 511, 512, 640]
_P, _PS, _D = 64, 16, 128


def _lane_positions(B):
    mix = np.linspace(67, 859, max(B - len(_EDGES), 2)).astype(int)
    return [int(p) for p in (_EDGES + list(mix))[:B]]


def _programs_of(monkeypatch, lanes):
    """Hold the q and output blocks of a program to `lanes` sequences of the
    cases' shape (a fresh jit of the call: the cached trace of an unpatched
    test must not serve)."""
    from polykey_tpu.ops import paged_attention_kernel as pak

    state_bytes = 8 * _D * 4 * 2 + 2 * 8 * 4        # q + acc, m, l: f32
    monkeypatch.setattr(pak, "_LANE_BLOCK_BYTES", 2 * lanes * state_bytes)
    monkeypatch.setattr(pak, "_decode_call", jax.jit(
        pak._decode_call.__wrapped__,
        static_argnames=("scale", "logit_softcap", "interpret",
                         "pages_per_block", "state")))


def _int8_pool(kvp, D):
    from polykey_tpu.engine.kv_cache import fold_heads, unfold_heads
    from polykey_tpu.ops.paged_attention import quantize_kv_rows

    k8, ks = quantize_kv_rows(unfold_heads(kvp[0::2], D))
    v8, vs = quantize_kv_rows(unfold_heads(kvp[1::2], D))
    return (jnp.stack([fold_heads(k8), fold_heads(v8)], axis=1).reshape(
        kvp.shape), ks, vs)


# (sequences, sequences a program may hold — None: what the bytes allow).
_LANES = {"1": (1, None), "3": (3, None), "16": (16, None), "64": (64, None),
          "6-by-3": (6, 4), "16-by-4": (16, 4)}
# (window, soft-cap, split, int8): `split` = r runs the page sub-ranges
# [0, r) and [r, P) for the unnormalised state and merges them as the sp
# axis does — past r a short lane has no visible page between live ones.
_FORMS = {"plain": (None, None, None, False),
          "window": (100, None, None, False),
          "softcap-window": (200, 30.0, None, False),
          "split": (None, None, 8, False),
          "int8": (None, None, None, True)}


@pytest.mark.parametrize("form", _FORMS)
@pytest.mark.parametrize("lanes", _LANES)
def test_paged_decode_kernel_lanes_tiles_and_output(lanes, form, monkeypatch):
    from polykey_tpu.ops import paged_attention_kernel as pak

    B, held = _LANES[lanes]
    win, softcap, split, int8 = _FORMS[form]
    if held is not None:
        _programs_of(monkeypatch, held)
    q, kvp, pt, pos = _paged_case(
        B, 8, 2, _D, _PS, _P, [[p] for p in _lane_positions(B)])
    if int8:
        kvp = _int8_pool(kvp, _D)
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(
        q, kvp, pt, pos, scale=0.125, logit_softcap=softcap, window=w)
    if split is None:
        out = paged_attention_decode(
            q, kvp, pt, pos, scale=0.125, logit_softcap=softcap, window=w,
            interpret=True)
        assert out.dtype == q.dtype
    else:
        (a1, m1, l1), (a2, m2, l2) = (
            pak._decode_call(
                q[:, 0], kvp, pt, pos[:, 0], jnp.zeros((1,), jnp.int32),
                jnp.asarray(rng, jnp.int32), scale=0.125,
                logit_softcap=softcap, interpret=True, state=True)
            for rng in ([0, split], [split, _P]))
        assert m1.shape == l1.shape == (B, 8, 1) and a1.dtype == jnp.float32
        m = jnp.maximum(m1, m2)
        den = l1 * jnp.exp(m1 - m) + l2 * jnp.exp(m2 - m)
        acc = a1 * jnp.exp(m1 - m) + a2 * jnp.exp(m2 - m)
        out = (acc / jnp.maximum(den, 1e-9))[:, None]
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("position", _EDGES)
def test_paged_decode_kernel_one_sequence_at_every_edge(position):
    """B = 1 at each edge by itself: the only sequence's last tile is the
    call's last, with nothing in flight behind it."""
    q, kvp, pt, pos = _paged_case(1, 8, 2, _D, _PS, _P, [[position]])
    ref = paged_attention(q, kvp, pt, pos, scale=0.125)
    out = paged_attention_decode(q, kvp, pt, pos, scale=0.125, interpret=True)
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_paged_decode_kernel_stale_rows_past_the_last_tile_are_never_read():
    """Under the TPU interpreter uninitialised VMEM is NaN and a page lands
    only when it is awaited: one page a lane beside full blocks, so every
    slot holds rows no DMA wrote — a tile computed past the last fetched
    page, or a straddling tile's stale V rows left unzeroed, reads NaN."""
    from jax.experimental.pallas import tpu as pltpu

    q, kvp, pt, pos = _paged_case(
        6, 8, 2, _D, _PS, _P, [[3], [1000], [0], [257], [15], [640]])
    ref = paged_attention(q, kvp, pt, pos, scale=0.125)
    out = paged_attention_decode(
        q, kvp, pt, pos, scale=0.125,
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait"))
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("B,Hq,D,state,budget,want", [
    (16, 32, 128, False, None, 16),     # mistral-7b: one program
    (16, 8, 128, False, None, 16),      # a Mixtral tp = 4 shard
    (64, 32, 64, False, None, 64),      # lfm2: 64 sequences, 64-wide heads
    (64, 16, 256, False, None, 64),     # qwen3-next: head width 256
    (64, 32, 128, True, None, 64),      # the state for a merge: f32 acc, m, l
    (512, 32, 128, True, None, 64),     # past the budget: a divisor that fits
    (6, 8, 64, False, 4, 3),            # a B the fitting block does not divide
    (7, 8, 64, False, 4, 1),            # … and a prime one
    (1, 8, 64, False, None, 1),
])
def test_paged_decode_lanes_a_program_follow_the_bytes(B, Hq, D, state,
                                                       budget, want,
                                                       monkeypatch):
    """The sequences a program walks come from the VMEM arithmetic on the
    shapes it is handed — read off the q block and the grid it asks for."""
    from polykey_tpu.ops import paged_attention_kernel as pak

    if budget is not None:
        monkeypatch.setattr(
            pak, "_LANE_BLOCK_BYTES", 2 * budget * Hq * D * 2 * 2)
    ps, P = 16, 256
    jaxpr = jax.make_jaxpr(
        lambda *a: pak._decode_call.__wrapped__(
            *a, scale=1.0, logit_softcap=None, interpret=False, state=state)
    )(
        jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((128, ps, Hq // 4 * D), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
    )
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (B // want,)
    blocks = [tuple(getattr(d, "block_size", d) for d in b.block_shape)
              for b in call.params["grid_mapping"].block_mappings]
    assert (want, Hq, D) in blocks
    outs = [(tuple(v.aval.shape), str(v.aval.dtype)) for v in call.outvars]
    if state:
        assert outs == [((B, Hq, D), "float32"), ((B, Hq, 1), "float32"),
                        ((B, Hq, 1), "float32")]
    else:
        assert outs == [((B, Hq, D), "bfloat16")]


@pytest.mark.parametrize("G", [1, 2, 3, 8, 12, 32])
def test_paged_decode_wait_runs_add_up_to_the_pages_started(G):
    """A block's n pages in flight are awaited as one wait for each set bit
    of n: for every n in 1 … G the runs taken add up to exactly n pages —
    the bytes the n starts signalled — and a full power-of-two block is
    ONE wait."""
    from polykey_tpu.ops import paged_attention_kernel as pak

    runs = pak._wait_runs(G)
    assert len(runs) == G.bit_length() and max(runs) <= G
    for n in range(1, G + 1):
        taken = [run for run in runs if n & run]
        assert sum(taken) == n
    if G & (G - 1) == 0:
        assert [run for run in runs if G & run] == [G]


@pytest.mark.parametrize("n", range(1, 9))
def test_paged_decode_waits_consume_what_the_starts_signalled(n):
    """The kernel under the TPU interpreter with DMAs that land only when
    they are AWAITED and semaphores counted in bytes: every block size
    n = 1 … G pages, as a sequence's only block, behind a full block, and
    handed over from the sequence before it. A wait short of the bytes
    started leaves pages unlanded (the interpreter's uninitialised VMEM is
    NaN) and a residue on the slot's semaphore for the next block to trip
    over; a wait beyond them has nothing to wake it."""
    from jax.experimental.pallas import tpu as pltpu

    from polykey_tpu.ops import paged_attention_kernel as pak

    G, ps, P = 8, 16, 24
    positions = [n * ps - 1, (G + n) * ps - 3, 5, (2 * G + n) * ps - 1]
    q, kvp, pt, pos = _paged_case(4, 4, 2, 64, ps, P, [[p] for p in positions])
    ref = paged_attention(q, kvp, pt, pos, scale=0.125)
    acc, _, den = pak._decode_call(
        q[:, 0], kvp, pt, pos[:, 0], jnp.zeros((1,), jnp.int32),
        jnp.asarray([0, P], jnp.int32), scale=0.125, logit_softcap=None,
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait"),
        state=True, pages_per_block=G,
    )
    out = (acc / jnp.maximum(den, 1e-9))[:, None]
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("folded,itemsize,ppb,want,tiles", [
    (1024, 2, 0, 32, 4),    # one chip of mistral-7b: 1 MB, 512 positions,
                            #   in row tiles of 256 KB: 128 positions
    (512, 2, 0, 32, 2),     # 512 lanes: never over 512 positions; tiles of 256
    (256, 2, 0, 32, 1),     # a tp = 4 shard: the whole block is one tile
    (1024, 1, 0, 32, 2),    # int8 pools: half the bytes a position
    (2048, 2, 0, 16, 2),    # 2048 lanes: 256 positions, tiles of 128
    (8192, 2, 0, 8, 1),     # never under 128 positions
    (256, 2, 3, 3, 1),      # given explicitly: honoured, one tile
    (1024, 2, 12, 12, 1),   # … a tile that does not divide it: one tile
    (256, 2, 500, 256, 8),  # bounded by the table
])
def test_paged_decode_block_width_follows_the_bytes(folded, itemsize, ppb,
                                                    want, tiles):
    """The block the kernel streams is sized in bytes in flight and the row
    tile it waits for and computes in bytes too, from the shape it is handed
    — read off the scratch buffers and the semaphores (one a slot and tile)
    it asks for."""
    from polykey_tpu.ops import paged_attention_kernel as pak

    dtype = {2: jnp.bfloat16, 1: jnp.int8}[itemsize]
    B, ps, P, D = 2, 16, 256, 128
    Hk = folded // D
    pool = jax.ShapeDtypeStruct((128, ps, folded), dtype)
    if itemsize == 1:
        scales = jax.ShapeDtypeStruct((64, ps, Hk), jnp.bfloat16)
        pool = (pool, scales, scales)
    jaxpr = jax.make_jaxpr(
        lambda *a: pak._decode_call.__wrapped__(     # the jit's function
            *a, scale=1.0, logit_softcap=None, interpret=False,
            state=False, pages_per_block=ppb)
    )(
        jax.ShapeDtypeStruct((B, Hk, D), jnp.bfloat16), pool,
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
    )
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "paged_attention_decode"
    (scratch,) = [
        x.aval.shape for x in call.params["jaxpr"].invars
        if len(x.aval.shape) == 5
    ]
    assert scratch == (2, want, 2, ps, folded), scratch
    sems = [x.aval.shape for x in call.params["jaxpr"].invars
            if "semaphore" in str(x.aval).lower()]
    assert sems == [(2, tiles)] * (3 if itemsize == 1 else 1), sems


def test_paged_decode_kernel_shard_mapped_on_mesh():
    """The decode kernel under shard_map on a dp=2 x tp=2 mesh (GSPMD
    cannot partition a pallas_call — parallel/sharding.py layout: batch
    over dp, pool heads over tp) must match the unsharded gather
    reference. Interpret mode on the virtual CPU mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = create_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])

    q, kvp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    ref = paged_attention(q, kvp, pt, pos, scale=0.125)

    q_s = jax.device_put(q, NamedSharding(mesh, P("dp", None, "tp", None)))
    kvp_s = jax.device_put(kvp, NamedSharding(mesh, P(None, None, "tp")))
    pt_s = jax.device_put(pt, NamedSharding(mesh, P("dp", None)))
    pos_s = jax.device_put(pos, NamedSharding(mesh, P("dp", None)))

    out = paged_attention_decode(
        q_s, kvp_s, pt_s, pos_s, scale=0.125,
        interpret=True, mesh=mesh,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_paged_decode_kernel_shard_mapped_256_lanes_a_shard():
    """A Mixtral tp = 4 shard's call: 16 sequences, 8 of 32 query heads and
    2 of 8 KV heads of 128 a shard (256 folded lanes), every edge and the
    cells' mix among the lanes — one program a shard, one normalised
    output, against the unsharded gather."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = create_mesh(MeshConfig(tp=4), devices=jax.devices()[:4])
    q, kvp, pt, pos = _paged_case(
        16, 32, 8, 128, _PS, _P, [[p] for p in _lane_positions(16)])
    ref = paged_attention(q, kvp, pt, pos, scale=0.09)
    rep = NamedSharding(mesh, P())
    out = paged_attention_decode(
        jax.device_put(q, NamedSharding(mesh, P(None, None, "tp", None))),
        jax.device_put(kvp, NamedSharding(mesh, P(None, None, "tp"))),
        jax.device_put(pt, rep), jax.device_put(pos, rep),
        scale=0.09, interpret=True, mesh=mesh,
    )
    assert out.shape == q.shape
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("softcap,win", [(None, None), (30.0, 24)])
def test_paged_decode_kernel_context_parallel(softcap, win):
    """Context-parallel decode (sp=2): each shard covers half the page
    range and partial online-softmax states merge via pmax/psum. Rows
    include a short sequence whose pages fall entirely in shard 0 (the
    empty-shard guard must contribute zero, not NaN) and long sequences
    spanning both shards."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = create_mesh(MeshConfig(sp=2, tp=2), devices=jax.devices()[:4])

    q, kvp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [99], [127]]
    )
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(
        q, kvp, pt, pos, scale=0.125, logit_softcap=softcap, window=w
    )

    rep = NamedSharding(mesh, P())
    out = paged_attention_decode(
        jax.device_put(q, NamedSharding(mesh, P(None, None, "tp"))),
        jax.device_put(kvp, NamedSharding(mesh, P(None, None, "tp"))),
        jax.device_put(pt, rep), jax.device_put(pos, rep),
        scale=0.125, logit_softcap=softcap, window=w,
        interpret=True, mesh=mesh,
    )
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_flash_kernel_shard_mapped_on_mesh():
    """Flash prefill under shard_map on an sp=2 x tp=2 mesh: each shard's
    query block attends the full key window with global positions, so the
    sharded kernel must match the unsharded reference (incl. a sliding
    window that crosses shard boundaries)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = create_mesh(MeshConfig(sp=2, tp=2), devices=jax.devices()[:4])

    B, T, S, Hq, Hk, D = 2, 160, 192, 8, 2, 64
    q, k, v = _qkv(B, T, S, Hq, Hk, D)
    qpos = jnp.broadcast_to(jnp.arange(T), (B, T)) + 16
    ref = attention(
        q, k, v, make_attention_mask(qpos, S, sliding_window=48), scale=0.125
    )

    q_s = jax.device_put(q, NamedSharding(mesh, P(None, "sp", "tp", None)))
    k_s = jax.device_put(k, NamedSharding(mesh, P(None, None, "tp")))
    v_s = jax.device_put(v, NamedSharding(mesh, P(None, None, "tp")))
    pos_s = jax.device_put(qpos, NamedSharding(mesh, P(None, "sp")))

    out = flash_attention(
        q_s, k_s, v_s, pos_s, scale=0.125, window=jnp.int32(48),
        interpret=True, mesh=mesh,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("name", [
    "POLYKEY_DISABLE_FLASH",
    "POLYKEY_DISABLE_PAGED_KERNEL",
    "POLYKEY_DISABLE_KV_KERNEL",
])
def test_kernel_gates_ignore_the_environment(monkeypatch, name):
    """The kernels' gates decide from the backend and the geometry alone:
    the three kill switches that used to be read here are gone, and
    setting one changes nothing. The backend is patched to "tpu" (on the
    CPU every gate is False and the asserts would be vacuous)."""
    from polykey_tpu.ops import flash_attention as fa
    from polykey_tpu.ops import paged_attention_kernel as pak

    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pak.jax, "default_backend", lambda: "tpu")
    # The int8-KV paths consult the same gate as the fp ones.
    assert not hasattr(pak, "use_quantized_paged_kernel")
    for value in ("1", "true"):
        monkeypatch.setenv(name, value)
        assert fa.use_flash(512, 512, 128)
        assert pak.use_paged_kernel(8, 128)
    # The geometry rule still holds with the name set.
    assert not pak.use_paged_kernel(3, 40)
    assert not fa.use_flash(64, 64, 128)


def test_paged_decode_fallback_off_tpu():
    q, kvp, pt, pos = _paged_case(2, 4, 2, 24, 8, 4, [[3], [19]])
    ref = paged_attention(q, kvp, pt, pos, scale=0.3)
    out = paged_attention_decode(q, kvp, pt, pos, scale=0.3)
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_pp_mesh_routes_to_gather_path(monkeypatch):
    """Decided position (PERF.md "pp in serving"): under pp>1 the decode
    wrapper must take the GSPMD-partitionable gather path — the kernel's
    shard_map specs have no pp dimension and the per-layer pool slice is
    stage-local — and the result must still match the reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P_

    import polykey_tpu.ops.paged_attention_kernel as pak
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")

    q, kvp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    ref = paged_attention(q, kvp, pt, pos, scale=0.125)

    mesh = create_mesh(MeshConfig(pp=2, tp=2), devices=jax.devices()[:4])
    from polykey_tpu.ops import paged_attention as pa_mod

    calls = {"gather": 0}
    real = pa_mod.paged_attention

    def spy(*args, **kwargs):
        calls["gather"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(
        "polykey_tpu.ops.paged_attention.paged_attention", spy
    )
    out = pak.paged_attention_decode(
        jax.device_put(q, NamedSharding(mesh, P_(None, None, "tp"))),
        jax.device_put(kvp, NamedSharding(mesh, P_(None, None, "tp"))),
        jax.device_put(pt, NamedSharding(mesh, P_())),
        jax.device_put(pos, NamedSharding(mesh, P_())),
        scale=0.125, interpret=True, mesh=mesh,
    )
    assert calls["gather"] == 1
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("win", [None, 24])
def test_paged_decode_kernel_quantized_matches_gather(win):
    """int8-KV pools through the DMA kernel's in-kernel dequant stage
    (scale pages stream alongside data pages; stale scale rows zeroed on
    the V side) vs the quantized gather path. Both dequantize with the
    same stored bf16 scales, so agreement is fp-tolerance, not
    quantization-tolerance."""
    from polykey_tpu.engine.kv_cache import fold_heads, unfold_heads
    from polykey_tpu.ops.paged_attention import quantize_kv_rows

    q, kvp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    k8, ks = quantize_kv_rows(unfold_heads(kvp[0::2], 64))
    v8, vs = quantize_kv_rows(unfold_heads(kvp[1::2], 64))
    kvq = (jnp.stack([fold_heads(k8), fold_heads(v8)], axis=1).reshape(
        kvp.shape), ks, vs)
    ref = paged_attention(q, kvq, pt, pos, scale=0.125,
                          window=None if win is None else jnp.int32(win))
    out = paged_attention_decode(
        q, kvq, pt, pos, scale=0.125,
        window=None if win is None else jnp.int32(win),
        interpret=True,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL
