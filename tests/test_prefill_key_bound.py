"""A prefill dispatch gathers and streams the keys its furthest query can
see, not its whole page table (ops/paged_attention.py `gather_needed_pages`,
ops/flash_attention.py `_needed_blocks`; ISSUE 59).

- PARITY: the prefill read equals the whole-table read — bit for bit on the
  reference path (which gathers the table in one piece, as it always did,
  since it multiplies every key whatever it is handed), and bounded,
  through the blockwise kernel in interpret mode —
  over the shapes the engine dispatches (a first window, a chunk at
  `start` > 0, a cover's rows on one table, rows that end apart, a padded
  group row whose table is the garbage page, a chunk that ends at the
  table's end) for a bf16-layout pool, int8 KV, a sliding-window layer and
  the latent one-part pool.
- ONE RULE: the keys the program's gather moves are the keys the host
  counts (`prefill_keys_read`), for every furthest position.
- The kernel's key stream: it walks the key axis as far as the call's
  furthest query, and within that holds a query block on the blocks it
  needs.
- The engine: same tokens as with a gather of the whole table, and
  `prefill_keys_read_total` / `prefill_keys_table_total` in engine_stats.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.ops import flash_attention as fa
from polykey_tpu.ops import paged_attention as pa
from polykey_tpu.ops import paged_attention_kernel as pak

PS, PAGES, T = 16, 64, 128            # a 1,024-position table, 128-row windows

# starts of a dispatch's rows, whether the rows share one table, and the
# rows whose table is the garbage page.
DISPATCHES = {
    "start-0": ((0, 0), False, ()),
    "chunk": ((256, 256), False, ()),
    "cover-rows": ((0, 128), True, ()),
    "rows-end-apart": ((0, 384), False, ()),
    "padded-row": ((128, 0), False, (1,)),
    "table-end": ((896,), False, ()),
}
POOLS = ["bf16", "int8", "window", "latent"]


def _reads(dispatch: str, pool: str, seed: int = 0):
    """(whole, bounded, tables, positions): the attention of the dispatch
    over its whole tables and over the keys its queries can see, each a
    function of (tables, positions), a pool of noise under them (what lies
    past a row's positions is whatever the pages' last owners left)."""
    starts, shared, garbage = DISPATCHES[dispatch]
    B = len(starts)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    tables = 1 + np.arange(B * PAGES, dtype=np.int32).reshape(B, PAGES)
    if shared:
        tables[:] = tables[0]
    for row in garbage:
        tables[row] = 0
    positions = np.asarray(starts, np.int32)[:, None] + np.arange(T)
    N = 1 + B * PAGES
    stage = partial(pa.prefill_stage, B, PAGES * PS, T, PS, dtype=jnp.float32)
    if pool == "latent":
        W, heads = 128, 2
        rows = jax.random.normal(keys[0], (N, PS, W), jnp.float32)
        q = jax.random.normal(keys[1], (B, T, heads, W), jnp.float32)
        args = dict(scale=0.2, v_width=64)

        def whole(tables, positions):
            return pa.latent_attention(q, rows, tables, positions, **args)

        def bounded(tables, positions):
            return pa.latent_prefill_attention(
                q, rows, stage(heads=1, width=W, parts=1), tables, positions,
                jnp.max(positions) + 1, **args)[0]
    else:
        Hq, Hk, D = 4, 2, 64
        q = jax.random.normal(keys[1], (B, T, Hq, D), jnp.float32)
        kv = jax.random.normal(keys[0], (2 * N, PS, Hk * D), jnp.float32)
        if pool == "int8":
            values, scales = pa.quantize_kv_rows(kv.reshape(2 * N, PS, Hk, D))
            kv = (values.reshape(2 * N, PS, Hk * D), scales[0::2],
                  scales[1::2])
        args = dict(scale=0.125,
                    window=jnp.int32(200) if pool == "window" else None)

        def whole(tables, positions):
            return pa.paged_attention(q, kv, tables, positions, **args)

        def bounded(tables, positions):
            return pa.paged_prefill_attention(
                q, kv, stage(heads=Hk, width=D, parts=2), tables, positions,
                jnp.max(positions) + 1, **args)[0]
    return whole, bounded, jnp.asarray(tables), jnp.asarray(positions)


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("dispatch", list(DISPATCHES))
def test_bounded_read_is_the_whole_table_read(dispatch, pool):
    """The reference path (off the chip: `prefill_bounded` says no) through
    the stage: no bit of the result changes."""
    whole, bounded, tables, positions = _reads(dispatch, pool)
    want = jax.jit(whole)(tables, positions)
    got = jax.jit(bounded)(tables, positions)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """The blockwise kernel in interpret mode through the dispatch the chip
    takes (`paged_prefill_attention`'s and `latent_prefill_attention`'s
    own calls)."""
    flash = fa.flash_attention

    def interpreted(*args, **kw):
        kw.pop("force_kernel", None)
        return flash(*args, **{**kw, "interpret": True})

    monkeypatch.setattr(fa, "flash_attention", interpreted)
    monkeypatch.setattr(fa, "use_flash", lambda T, S, D: True)
    monkeypatch.setattr(pak, "use_paged_kernel", lambda Hk, D: True)


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("dispatch", list(DISPATCHES))
def test_bounded_kernel_read_is_the_reference_read(
        dispatch, pool, kernel_interpreted, monkeypatch):
    """The kernel over the staged keys against the REFERENCE over the whole
    table (the kernel sums block by block: float32 rounding apart)."""
    whole, bounded, tables, positions = _reads(dispatch, pool)
    got = jax.jit(bounded)(tables, positions)
    monkeypatch.undo()
    want = jax.jit(whole)(tables, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window,table,page_size,piece", [
    (128, 4096, 16, 128), (512, 4096, 16, 512), (5, 4096, 16, 128),
    (16, 64, 8, 64), (100, 192, 16, 128), (130, 4096, 16, 144),
    (1024, 512, 16, 512),
])
def test_a_gather_turn_is_the_window_in_whole_pages(
        window, table, page_size, piece):
    assert pa.prefill_gather_keys(window, table, page_size) == piece


@pytest.mark.parametrize("window,pages,page_size", [
    (128, 64, 16), (512, 256, 16), (100, 20, 16), (16, 8, 8), (5, 256, 16),
    (130, 256, 16)])
def test_host_count_and_program_gather_agree(window, pages, page_size):
    """`prefill_keys_read` (the engine's counter) is what the program's
    loop writes into the stage, for every furthest position a dispatch can
    hold — each turn's first and last key, and past the table's end — and
    never less than the queries see, nor a whole turn more."""
    table = pages * page_size
    piece = pa.prefill_gather_keys(window, table, page_size)
    stage = pa.PrefillStage(
        (jnp.zeros((1, 1, -(-table // piece) * piece, 8)),), bounded=True)
    written = jax.jit(lambda keys: jnp.sum(pa.gather_needed_pages(
        stage,
        lambda tables: (jnp.ones((1, tables.shape[1] * page_size, 1, 8)),),
        jnp.zeros((1, pages), jnp.int32), keys, window, page_size,
    ).parts[0][0, 0, :, 0]))
    edges = {window, table, table + window}
    for k in range(piece, table + piece, piece):
        edges |= {k - 1, k, k + 1}
    for keys in sorted(k for k in edges if k >= window):
        got = int(written(jnp.int32(keys)))
        assert min(got, table) == pa.prefill_keys_read(
            keys, window, table, page_size)
        assert got >= min(keys, table) and got - piece < keys


def test_kernel_walks_and_holds_by_the_queries_positions():
    """`_needed_blocks`: the key blocks the call walks (to its furthest
    query) and the first and last key block of each query block, by the
    kernel's own rule; the index maps clip the walk to them, so a block no
    query can see is never the block fetched."""
    qpos = np.full((2, 2, 1, 128), -1, np.int32)
    qpos[0, 0, 0] = 300 + np.arange(128)        # positions 300..427
    qpos[0, 1, 0, :40] = 428 + np.arange(40)    # 428..467, then padding
    qpos[1, 0, 0] = np.arange(128)              # 0..127; [1, 1]: padding
    none = jnp.zeros((1, 1), jnp.int32)
    steps, first, last = fa._needed_blocks(jnp.asarray(qpos), none, 128, 8)
    assert int(steps[0]) == 4
    np.testing.assert_array_equal(first, [0, 0, 0, 0])
    np.testing.assert_array_equal(last, [3, 3, 0, 0])
    steps, first, last = fa._needed_blocks(
        jnp.asarray(qpos), jnp.full((1, 1), 100), 128, 8)
    assert int(steps[0]) == 4
    np.testing.assert_array_equal(first, [1, 2, 0, 0])   # 201.., 329..
    np.testing.assert_array_equal(last, [3, 3, 0, 0])
    steps, _, last = fa._needed_blocks(jnp.asarray(qpos + 2000), none, 128, 8)
    assert int(steps[0]) == 8
    np.testing.assert_array_equal(last, [7, 7, 7, 7])
    steps, first, last = fa._needed_blocks(
        jnp.asarray(np.full((1, 1, 1, 128), -1, np.int32)), none, 128, 8)
    assert (int(steps[0]), int(first[0]), int(last[0])) == (1, 0, 0)


@pytest.mark.parametrize("block_k", [128, 256, 512])
@pytest.mark.parametrize("heads_major", [False, True])
@pytest.mark.parametrize("window", [None, 200])
def test_kernel_over_a_long_table_skips_what_the_mask_hides(
        window, heads_major, block_k):
    """The kernel on the whole 1,024-key table of windows at 256..383 and
    640..767 (key blocks of 128: eight, six walked, one to three of them
    visible to a query block; of 256 and 512: blocks whose second half no
    query sees are multiplied by their first half alone), K and V in
    either layout: what it sums is the reference's."""
    from polykey_tpu.ops.attention import attention, make_attention_mask

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (2, T, 4, 64), jnp.float32)
    k = jax.random.normal(keys[1], (2, 1024, 2, 64), jnp.float32)
    v = jax.random.normal(keys[2], (2, 1024, 2, 64), jnp.float32)
    qpos = jnp.asarray([[256], [640]]) + jnp.arange(T)
    want = attention(
        q, k, v, make_attention_mask(qpos, 1024, sliding_window=window),
        scale=0.125)
    if heads_major:
        k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in (k, v))
    got = fa.flash_attention(
        q, k, v, qpos, scale=0.125, block_k=block_k, interpret=True,
        window=None if window is None else jnp.int32(window),
        kv_heads_major=heads_major)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- the engine -----------------------------------------------------------------

_ENGINE = dict(
    model="tiny-llama", dtype="float32", max_decode_slots=2, page_size=8,
    num_pages=48, max_seq_len=128, prefill_buckets=(16, 32),
    decode_block_steps=2, adaptive_block=False, max_new_tokens_cap=6,
    default_max_new_tokens=6, supervise=False,
)
PROMPTS = ["a tool turn", "a prompt of some thirty tokens or so",
           "a long prompt that goes through the engine one chunk at a time, "
           "each chunk further into its page table than the one before it"]


def _served(monkeypatch, whole: bool):
    """(tokens of each prompt, engine_stats) of one engine's life, its
    gather a 16-key turn at a time into a heads-major stage or, `whole`
    (what an engine off the chip does by itself), the table in one."""
    from polykey_tpu.engine import engine as engine_mod

    monkeypatch.setattr(pa, "MIN_GATHER_KEYS", 16)
    for module in (pa, engine_mod):     # the program's rule and the host's
        monkeypatch.setattr(
            module, "prefill_bounded", lambda *a, **kw: not whole)
    engine = InferenceEngine(EngineConfig(**_ENGINE))
    try:
        tokens = []
        for prompt in PROMPTS:
            request = GenRequest(prompt=prompt, max_new_tokens=6)
            engine.submit(request)
            got = []
            while True:
                kind, value = request.out.get(timeout=120)
                if kind == "token":
                    got.append(int(value))
                elif kind == "done":
                    break
                else:
                    raise AssertionError(value)
            tokens.append(got)
        return tokens, engine.stats()
    finally:
        engine.shutdown()


def test_engine_serves_the_same_tokens_and_counts_the_keys(monkeypatch):
    """Three prompts — one window, a wider one, one that chunks to the far
    end of a 128-position table — give the tokens of an engine that gathers
    every table whole; the counters say how much of the tables the first
    read."""
    tokens, stats = _served(monkeypatch, whole=False)
    whole_tokens, whole_stats = _served(monkeypatch, whole=True)
    assert tokens == whole_tokens and all(tokens)
    table = stats["prefill_keys_table_total"]
    assert table == whole_stats["prefill_keys_table_total"] > 0
    assert table == whole_stats["prefill_keys_read_total"]
    assert 0 < stats["prefill_keys_read_total"] < table
