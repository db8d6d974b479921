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
    # Pools in the stored layout (engine/kv_cache.py): heads folded.
    kp = jax.random.normal(jax.random.PRNGKey(1), (N, ps, Hk * D), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(2), (N, ps, Hk * D), jnp.float32)
    pts = np.zeros((B, P), np.int32)
    page = 1
    for b in range(B):
        needed = positions[b][0] // ps + 1
        for j in range(needed):
            pts[b, j] = page
            page += 1
    return q, kp, vp, jnp.asarray(pts), jnp.asarray(positions, jnp.int32)


@pytest.mark.parametrize("softcap,win", [
    (None, None), (50.0, None), (None, 24), (30.0, 24),
])
def test_paged_decode_kernel_matches_gather(softcap, win):
    q, kp, vp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(
        q, kp, vp, pt, pos, scale=0.125, logit_softcap=softcap, window=w
    )
    out = paged_attention_decode(
        q, kp, vp, pt, pos, scale=0.125, logit_softcap=softcap, window=w,
        interpret=True,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


@pytest.mark.parametrize("g", [1, 2, 3])
@pytest.mark.parametrize("win", [None, 24])
def test_paged_decode_kernel_multi_group(g, win):
    """Force small page groups so the group loop runs multiple blocks,
    including a partial last group (P=8 with G=3) and a window whose lo
    lands mid-group (non-DMA'd rows inside a live group must be masked)."""
    q, kp, vp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(q, kp, vp, pt, pos, scale=0.125, window=w)
    out = paged_attention_decode(
        q, kp, vp, pt, pos, scale=0.125, window=w,
        interpret=True, pages_per_block=g,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


def test_paged_decode_kernel_no_gqa_single_page():
    q, kp, vp, pt, pos = _paged_case(1, 2, 2, 32, 16, 4, [[5]])
    ref = paged_attention(q, kp, vp, pt, pos, scale=0.125)
    out = paged_attention_decode(
        q, kp, vp, pt, pos, scale=0.125, interpret=True
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL


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

    q, kp, vp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    ref = paged_attention(q, kp, vp, pt, pos, scale=0.125)

    q_s = jax.device_put(q, NamedSharding(mesh, P("dp", None, "tp", None)))
    kp_s = jax.device_put(kp, NamedSharding(mesh, P(None, None, "tp")))
    vp_s = jax.device_put(vp, NamedSharding(mesh, P(None, None, "tp")))
    pt_s = jax.device_put(pt, NamedSharding(mesh, P("dp", None)))
    pos_s = jax.device_put(pos, NamedSharding(mesh, P("dp", None)))

    out = paged_attention_decode(
        q_s, kp_s, vp_s, pt_s, pos_s, scale=0.125,
        interpret=True, mesh=mesh,
    )
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

    q, kp, vp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [99], [127]]
    )
    w = None if win is None else jnp.int32(win)
    ref = paged_attention(
        q, kp, vp, pt, pos, scale=0.125, logit_softcap=softcap, window=w
    )

    rep = NamedSharding(mesh, P())
    out = paged_attention_decode(
        jax.device_put(q, NamedSharding(mesh, P(None, None, "tp"))),
        jax.device_put(kp, NamedSharding(mesh, P(None, None, "tp"))),
        jax.device_put(vp, NamedSharding(mesh, P(None, None, "tp"))),
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


def test_kernel_kill_switches(monkeypatch):
    """POLYKEY_DISABLE_PAGED_KERNEL / POLYKEY_DISABLE_FLASH force the jnp
    paths regardless of backend — the operational escape hatch if a
    Mosaic compile regresses on new hardware. The backend is patched to
    "tpu" so the env check is what flips the result (on CPU both
    predicates are False anyway and the asserts would be vacuous)."""
    from polykey_tpu.ops import flash_attention as fa
    from polykey_tpu.ops import paged_attention_kernel as pak

    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pak.jax, "default_backend", lambda: "tpu")
    assert pak.use_paged_kernel(8, 128)
    assert fa.use_flash(512, 512, 128)
    for v in ("1", "true"):
        monkeypatch.setenv("POLYKEY_DISABLE_PAGED_KERNEL", v)
        monkeypatch.setenv("POLYKEY_DISABLE_FLASH", v)
        assert not pak.use_paged_kernel(8, 128)
        assert not fa.use_flash(512, 512, 128)
    monkeypatch.delenv("POLYKEY_DISABLE_PAGED_KERNEL")
    monkeypatch.delenv("POLYKEY_DISABLE_FLASH")
    assert pak.use_paged_kernel(8, 128)


def test_paged_decode_fallback_off_tpu():
    q, kp, vp, pt, pos = _paged_case(2, 4, 2, 24, 8, 4, [[3], [19]])
    ref = paged_attention(q, kp, vp, pt, pos, scale=0.3)
    out = paged_attention_decode(q, kp, vp, pt, pos, scale=0.3)
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

    q, kp, vp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    ref = paged_attention(q, kp, vp, pt, pos, scale=0.125)

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
        jax.device_put(kp, NamedSharding(mesh, P_(None, None, "tp"))),
        jax.device_put(vp, NamedSharding(mesh, P_(None, None, "tp"))),
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

    q, kp, vp, pt, pos = _paged_case(
        4, 8, 2, 64, 16, 8, [[5], [37], [63], [100]]
    )
    k8, ks = quantize_kv_rows(unfold_heads(kp, 64))
    v8, vs = quantize_kv_rows(unfold_heads(vp, 64))
    kq, vq = (fold_heads(k8), ks), (fold_heads(v8), vs)
    ref = paged_attention(q, kq, vq, pt, pos, scale=0.125,
                          window=None if win is None else jnp.int32(win))
    out = paged_attention_decode(
        q, kq, vq, pt, pos, scale=0.125,
        window=None if win is None else jnp.int32(win),
        interpret=True,
    )
    assert float(jnp.max(jnp.abs(ref - out))) < TOL
