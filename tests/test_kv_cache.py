"""Block allocator + paged-KV correctness tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine.kv_cache import (
    AllocationError,
    BlockAllocator,
    fold_heads,
    init_paged_kv,
)
from polykey_tpu.models.config import TINY_LLAMA
from polykey_tpu.models.transformer import forward, forward_paged, init_params
from polykey_tpu.ops.paged_attention import paged_gather_kv, paged_write


@pytest.fixture
def allocator_factory():
    return BlockAllocator


def test_alloc_release_cycle(allocator_factory):
    alloc = allocator_factory(8)
    assert alloc.num_free == 7  # page 0 reserved
    pages = alloc.alloc(3)
    assert len(pages) == 3
    assert 0 not in pages
    assert alloc.num_free == 4
    alloc.release_all(pages)
    assert alloc.num_free == 7


def test_alloc_all_or_nothing(allocator_factory):
    alloc = allocator_factory(4)
    alloc.alloc(2)
    with pytest.raises(AllocationError):
        alloc.alloc(2)  # only 1 free
    assert alloc.num_free == 1  # failed alloc took nothing


def test_refcount_sharing(allocator_factory):
    alloc = allocator_factory(4)
    (page,) = alloc.alloc(1)
    alloc.retain(page)
    alloc.release(page)
    assert alloc.num_free == 2  # still held by the second reference
    alloc.release(page)
    assert alloc.num_free == 3


def test_double_release_rejected(allocator_factory):
    alloc = allocator_factory(4)
    (page,) = alloc.alloc(1)
    alloc.release(page)
    with pytest.raises(ValueError):
        alloc.release(page)
    with pytest.raises(ValueError):
        alloc.release(0)  # garbage page is never client-owned


def test_unique_pages(allocator_factory):
    alloc = allocator_factory(64)
    pages = alloc.alloc(63)
    assert len(set(pages)) == 63
    with pytest.raises(AllocationError):
        alloc.alloc(1)


def test_paged_write_and_gather_roundtrip():
    Hk, D, page_size = 2, 4, 4
    pool = jnp.zeros((2 * 8, page_size, Hk * D), dtype=jnp.float32)
    # One sequence using pages [3, 5]: positions 0..7.
    page_tables = jnp.array([[3, 5]], dtype=jnp.int32)
    positions = jnp.arange(8, dtype=jnp.int32)[None, :]
    k_new = jax.random.normal(jax.random.PRNGKey(0), (1, 8, Hk, D))
    v_new = jax.random.normal(jax.random.PRNGKey(1), (1, 8, Hk, D))
    pool = paged_write(pool, k_new, v_new, page_tables, positions)
    k_out, v_out = paged_gather_kv(pool, page_tables, D)
    np.testing.assert_allclose(np.asarray(k_out[0]), np.asarray(k_new[0]))
    np.testing.assert_allclose(np.asarray(v_out[0]), np.asarray(v_new[0]))


def test_prefill_gather_reads_k_and_v_as_two_gathers():
    """K and V are two gathers of page halves: one gather of whole
    [2, ps, Hk·D] pages followed by a slice would write every window a
    second time (16 MB a row and layer at the cells' table width)."""
    B, P, ps, folded, D = 2, 6, 4, 32, 8
    jaxpr = jax.make_jaxpr(lambda kv, pt: paged_gather_kv(kv, pt, D))(
        jnp.zeros((2 * 16, ps, folded)), jnp.zeros((B, P), jnp.int32))
    gathers = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "gather"]
    assert [tuple(e.outvars[0].aval.shape) for e in gathers] == [
        (B, P, ps, folded)] * 2
    assert not any(                       # no window of whole K+V pages
        v.aval.shape == (B, P, 2, ps, folded)
        for e in jaxpr.jaxpr.eqns for v in e.outvars)


def test_forward_paged_matches_contiguous():
    """The paged path must produce identical hidden states to the contiguous
    cache path — the oracle every kernel change is checked against."""
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T, page_size = 2, 8, 4

    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)

    hidden_ref, _ = forward(params, cfg, tokens, positions, None)

    paged = init_paged_kv(cfg, num_pages=16, page_size=page_size, dtype=jnp.float32)
    # Row 0 → pages [1, 2]; row 1 → pages [7, 4] (deliberately non-contiguous).
    page_tables = jnp.array([[1, 2, 0], [7, 4, 0]], dtype=jnp.int32)
    hidden_paged, paged = forward_paged(
        params, cfg, tokens, positions, paged, page_tables
    )
    np.testing.assert_allclose(
        np.asarray(hidden_ref), np.asarray(hidden_paged), rtol=2e-4, atol=2e-4
    )


def test_forward_paged_incremental_decode():
    """Prefill + paged decode steps == one-shot no-cache forward."""
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    T, page_size = 6, 4

    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (1, T)).astype(jnp.int32)
    hidden_ref, _ = forward(params, cfg, tokens, positions, None)

    paged = init_paged_kv(cfg, num_pages=8, page_size=page_size, dtype=jnp.float32)
    page_tables = jnp.array([[2, 5]], dtype=jnp.int32)

    # Prefill the first 3 tokens.
    hidden, paged = forward_paged(
        params, cfg, tokens[:, :3], positions[:, :3], paged, page_tables
    )
    # Decode the rest one token at a time.
    for t in range(3, T):
        hidden, paged = forward_paged(
            params, cfg, tokens[:, t : t + 1], positions[:, t : t + 1],
            paged, page_tables,
        )
    np.testing.assert_allclose(
        np.asarray(hidden_ref[:, -1]), np.asarray(hidden[:, 0]),
        rtol=2e-4, atol=2e-4,
    )


def _scatter_reference(kv_pages, k_new, v_new, page_tables, positions):
    """The original per-token XLA scatter over a K pool and a V pool of
    their own, kept as the oracle for the write paths (token scatter,
    page-granular cond path, Pallas DMA kernel): the stored pool's two
    halves must read as those two pools."""
    ps = kv_pages.shape[1]
    bi = jnp.arange(page_tables.shape[0], dtype=jnp.int32)[:, None]
    page_ids = page_tables[bi, positions // ps]
    offsets = positions % ps
    return (
        kv_pages[0::2].at[page_ids, offsets].set(fold_heads(k_new)),
        kv_pages[1::2].at[page_ids, offsets].set(fold_heads(v_new)),
    )


def _assert_pool_is(kv_pages, want_k, want_v):
    np.testing.assert_array_equal(np.asarray(kv_pages[0::2]), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(kv_pages[1::2]), np.asarray(want_v))


def _write_fixture(B, T, P, start, seed=0):
    cfg = TINY_LLAMA
    ps = 16
    rng = np.random.default_rng(seed)
    pools = init_paged_kv(cfg, num_pages=1 + B * P, page_size=ps)
    kp = jnp.asarray(
        rng.normal(size=pools.kv[0, :, 0].shape).astype(np.float32), jnp.bfloat16
    )
    # One layer's pool as the ops take it: page halves [2N, ps, Hk·D].
    kvp = jnp.stack([kp, kp * 2], axis=1).reshape(-1, *kp.shape[1:])
    k_new = jnp.asarray(
        rng.normal(size=(B, T, cfg.num_kv_heads, cfg.head_dim)), jnp.bfloat16
    )
    v_new = k_new + 1
    pt = np.zeros((B, P), np.int32)
    for b in range(B):
        pt[b] = np.arange(P) + 1 + b * P
    positions = start[:, None] + np.arange(T)[None, :]
    return kvp, k_new, v_new, jnp.asarray(pt), jnp.asarray(positions, jnp.int32)


def _kernel_rows(k_new, v_new):
    """A decode step's [B, 1, Hk, D] rows as the write kernel blends them
    into the two halves of a page: [B, 2, 1, Hk·D]."""
    return jnp.stack([fold_heads(k_new), fold_heads(v_new)], axis=1)


def test_paged_write_aligned_prefill_matches_scatter():
    """The page-granular cond path (aligned, consecutive rows — every
    engine prefill chunk) must be byte-identical to the token scatter."""
    B, T, P = 3, 32, 4
    start = np.array([0, 16, 32])          # all page-aligned
    kvp, kn, vn, pt, pos = _write_fixture(B, T, P, start)
    _assert_pool_is(
        paged_write(kvp, kn, vn, pt, pos),
        *_scatter_reference(kvp, kn, vn, pt, pos))


def test_paged_write_unaligned_prefill_matches_scatter():
    """Unaligned starts must fall back (runtime cond) to exact scatter."""
    B, T, P = 3, 32, 4
    start = np.array([0, 8, 17])           # rows 1, 2 unaligned
    kvp, kn, vn, pt, pos = _write_fixture(B, T, P, start)
    _assert_pool_is(
        paged_write(kvp, kn, vn, pt, pos),
        *_scatter_reference(kvp, kn, vn, pt, pos))


def test_paged_write_decode_kernel_interpret_matches_scatter():
    """The Pallas DMA write kernel (interpret mode on CPU) must match the
    scatter for a decode step, including the garbage-page-0 convention
    (inactive lanes all target page 0 — any value may land there)."""
    from polykey_tpu.ops.paged_write_kernel import paged_write_rows_kernel

    B, P = 4, 3
    start = np.array([5, 16, 31, 47])
    kvp, kn, vn, pt, pos = _write_fixture(B, 1, P, start)
    ps = kvp.shape[1]
    bi = jnp.arange(B, dtype=jnp.int32)[:, None]
    page_ids = pt[bi, pos // ps][:, 0]
    offsets = (pos % ps)[:, 0]
    (got,) = paged_write_rows_kernel(
        [kvp], [_kernel_rows(kn, vn)], page_ids, offsets, interpret=True,
    )
    _assert_pool_is(got, *_scatter_reference(kvp, kn, vn, pt, pos))


def test_paged_write_mesh_kernel_path_matches_scatter(monkeypatch):
    """The shard_map dispatch of the write kernel (dp all-gather of lane
    rows + tp head sharding) against the scatter oracle, on the virtual
    CPU mesh in interpret mode. On hardware this is the path every
    dp/tp-meshed decode step takes; nothing else exercises its
    collective wiring pre-hardware."""
    from functools import partial

    import polykey_tpu.ops.paged_attention as pa
    from polykey_tpu.ops import paged_write_kernel as pwk
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(tp=2, dp=2, sp=2))
    B, P = 4, 3
    start = np.array([5, 16, 31, 40])
    kvp, kn, vn, pt, pos = _write_fixture(B, 1, P, start)
    ps = kvp.shape[1]
    bi = jnp.arange(B, dtype=jnp.int32)[:, None]
    page_ids = pt[bi, pos // ps][:, 0]
    offsets = (pos % ps)[:, 0]

    monkeypatch.setattr(
        pwk, "paged_write_rows_kernel",
        partial(pwk.paged_write_rows_kernel, interpret=True),
    )
    (got,) = pa._write_decode_kernel(
        [kvp], [_kernel_rows(kn, vn)], page_ids, offsets, mesh,
        TINY_LLAMA.num_kv_heads,
    )
    _assert_pool_is(got, *_scatter_reference(kvp, kn, vn, pt, pos))


# ---- int8 KV cache ----


def test_quantize_kv_rows_roundtrip():
    from polykey_tpu.ops.paged_attention import (
        dequantize_kv,
        quantize_kv_rows,
    )

    rows = jax.random.normal(jax.random.PRNGKey(11), (3, 5, 4, 16))
    q, s = quantize_kv_rows(rows)
    assert q.dtype == jnp.int8 and s.shape == (3, 5, 4)
    back = dequantize_kv(q, s, jnp.float32)
    # q is computed against the bf16-ROUNDED scale (the one dequant
    # multiplies by), so per-entry error <= stored_scale/2; the stored
    # scale itself is within bf16 rounding of absmax/127.
    stored = np.asarray(s.astype(jnp.float32))
    err = np.asarray(jnp.abs(back - rows))
    assert (err <= stored[..., None] * 0.51 + 1e-7).all()


def test_forward_paged_int8_kv_tracks_fp():
    """Prefill + decode through int8 KV pools stay within quantization
    tolerance of the fp pools (the serving accuracy gate for
    EngineConfig.kv_dtype='int8')."""
    from polykey_tpu.models.transformer import forward_paged, init_params

    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T, P, ps = 2, 16, 4, 16
    pt = np.zeros((B, P), np.int32)
    for b in range(B):
        pt[b] = np.arange(P) + 1 + b * P
    pt = jnp.asarray(pt)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)

    pool_fp = init_paged_kv(cfg, 1 + B * P, ps, jnp.float32)
    pool_q = init_paged_kv(cfg, 1 + B * P, ps, jnp.float32,
                           kv_dtype=jnp.int8)
    assert pool_q.quantized and pool_q.kv.dtype == jnp.int8
    h_fp, pool_fp = forward_paged(params, cfg, tokens, positions, pool_fp, pt)
    h_q, pool_q = forward_paged(params, cfg, tokens, positions, pool_q, pt)
    scale = float(jnp.max(jnp.abs(h_fp))) + 1e-6
    assert float(jnp.max(jnp.abs(h_fp - h_q))) / scale < 0.05

    last = tokens[:, -1:]
    dpos = jnp.full((B, 1), T, jnp.int32)
    d_fp, _ = forward_paged(params, cfg, last, dpos, pool_fp, pt)
    d_q, _ = forward_paged(params, cfg, last, dpos, pool_q, pt)
    scale = float(jnp.max(jnp.abs(d_fp))) + 1e-6
    assert float(jnp.max(jnp.abs(d_fp - d_q))) / scale < 0.05


def test_paged_write_rows_kernel_with_scale_pools():
    """The generalized RMW kernel over three pools (the int8 data pool,
    K and V of a page side by side, + two bf16 scale pools) matches
    per-pool scatter in interpret mode."""
    from polykey_tpu.ops.paged_write_kernel import paged_write_rows_kernel

    B, P, ps, Hk, D = 4, 3, 16, 4, 32
    N = 1 + B * P
    rng = np.random.default_rng(5)
    kq = jnp.asarray(rng.integers(-127, 128, (N, ps, Hk * D)), jnp.int8)
    vq = kq * -1
    ks = jnp.asarray(rng.normal(size=(N, ps, Hk)), jnp.bfloat16)
    vs = ks + 1
    k8 = jnp.asarray(rng.integers(-127, 128, (B, 1, Hk * D)), jnp.int8)
    v8 = -k8
    ksr = jnp.asarray(rng.normal(size=(B, 1, Hk)), jnp.bfloat16)
    vsr = ksr * 2
    page_ids = jnp.asarray(rng.permutation(N - 1)[:B].astype(np.int32) + 1)
    offsets = jnp.asarray(rng.integers(0, ps, B).astype(np.int32))

    kvq, ks_out, vs_out = paged_write_rows_kernel(
        [jnp.stack([kq, vq], axis=1).reshape(2 * N, ps, Hk * D), ks, vs],
        [jnp.stack([k8, v8], axis=1), ksr[:, None], vsr[:, None]],
        page_ids, offsets, interpret=True,
    )
    for pool, rows, got in zip(
            [kq, vq, ks, vs], [k8, v8, ksr, vsr],
            [kvq[0::2], kvq[1::2], ks_out, vs_out]):
        want = pool.at[page_ids, offsets].set(rows[:, 0])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
