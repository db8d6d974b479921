"""Compile-and-compare check of every Pallas kernel on the attached TPU.

Interpret-mode tests (tests/test_kernels.py) prove the
math on the CPU; this proves Mosaic LOWERING at serving geometry
(page_size 16, 32 lanes, 4k-position tables; 8B Hq=32/Hk=8/D=128, 1B
D=64, Gemma-2 D=256 with softcap and window): each kernel is compiled on
the chip and compared with its jnp reference path.

    fp decode · flash prefill · fp write · int8-KV read and write stages
    · packed-int4 qdot

One line per (kernel, geometry): PASS with the max abs error, or FAIL
with the head of the compiler's message (the whole message goes to
chiprun_out/kernel_check.txt). Exits non-zero on any failure and when
there is no TPU. It times two things: the one-off probe that
jax.block_until_ready really blocks, and the paged decode kernel alone
(`decode-time` rows: µs a call beside its K/V bytes ÷ the chip's HBM
bandwidth at the two shapes the benchmark's cells run, 16 and 64
sequences a call, the rest of it by sequence, and the DMA
descriptors the call starts and awaits, so that a call's cost can be
split into bytes ÷ bandwidth + the rest without a server; in no cell —
what the users pay is the benchmark's to say). `--timing` also times the
held experts' product (`held-time` rows: the masked one-pass kernel and
the grouped one the chip serves at every width, both instances at their
published shapes, 64 to 1,024 rows at a balanced routing, where nearly
every held expert is hit and nothing can be skipped, and the decode
step's 64 rows of all four cells' shapes at a routing of a STATED hit
share — the share of the held experts some row chose, which is all the
grouped form reads; µs a call beside the held experts' bytes ÷ the
bandwidth, whole and hit) and compares grouped against masked and the jnp
twin there (`held-compare`).

Run: python scripts/tpu_kernel_check.py   (one chip; ~2-4 min cold)
     python scripts/tpu_kernel_check.py --timing   (the decode-time and
       held-time rows alone, ~3 min; --sweep adds lanes, table width and
       block width of the decode kernel varied one at a time)
     python scripts/tpu_kernel_check.py --held-experts   (the held-time and
       held-compare rows alone)
     python scripts/tpu_kernel_check.py --prefill-read   (the blockwise
       prefill kernel's checks and the `prefill-read` rows alone: µs a
       layer of a prefill dispatch's attention over the whole table and
       over the pages its queries can see)
     python scripts/tpu_kernel_check.py --delta-state   (the gated delta
       rule's decode update alone: `delta-compare` against its jnp form,
       `delta-time` µs a call beside the state's bytes ÷ the bandwidth)
     python scripts/tpu_kernel_check.py --mha   (the paged decode read,
       the page write and the blockwise prefill kernel at 30 query heads on
       30 KV heads of 128 — groups of ONE — on 64 lanes: the attending
       layers of olmo-hybrid-7b-pp2)
     python scripts/tpu_kernel_check.py --sampler-head   (no Pallas
       kernel: the exact sampler's full-vocabulary sort beside every exact
       way to take a row's W largest values, at [64, 32768] and
       [64, 65536] — `head-time` rows: device µs a call out of one
       profiler capture and the operations it was made of, by name; ~2 min)
     JAX_PLATFORMS=cpu python scripts/tpu_kernel_check.py --interpret
       rehearses the script itself at small tables in Pallas interpret
       mode — it proves nothing about lowering and exits 2 like any run
       without a TPU.
"""

from __future__ import annotations

import os
import sys
import time
import traceback
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

PS, LANES, TABLE = 16, 32, 256          # page size, decode lanes, 4k/16
PREFILL = 512                           # prefill bucket
KERNEL = {"force_kernel": True}         # --interpret swaps in interpret=True

# (label, Hq, Hk, D, softcap, window) — the served families' head shapes.
GEOMETRIES = [
    ("8b", 32, 8, 128, None, None),
    ("1b-d64", 32, 8, 64, None, None),
    ("gemma9b-d256", 16, 8, 256, 50.0, 1024),
    ("gemma27b", 32, 16, 128, 50.0, 1024),
]

RESULTS: list = []      # (kernel, geometry, ok, detail)
FULL_LOG: list = []


def case(kernel: str, geometry: str, fn) -> None:
    """Run one check; a failure keeps the other cases' evidence."""
    t0 = time.monotonic()
    try:
        detail = fn()
        ok = True
    except Exception as e:      # compiler errors are the point: record them
        ok = False
        message = f"{type(e).__name__}: {e}"
        FULL_LOG.append(f"=== {kernel} {geometry}\n{traceback.format_exc()}")
        detail = " ".join(message.split())[:300]
    RESULTS.append((kernel, geometry, ok, detail))
    print(f"{kernel:<14} {geometry:<22} {'PASS' if ok else 'FAIL'} {detail} "
          f"[{time.monotonic() - t0:.1f}s]", flush=True)


def max_err(got, want) -> float:
    return float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32))))


def assert_close(got, want, tol: float) -> str:
    err = max_err(got, want)
    if not np.isfinite(err) or err >= tol:
        raise AssertionError(f"max abs err {err:.3e} >= tol {tol:.1e}")
    return f"err={err:.2e}"


def paged_inputs(B, Hq, Hk, D, P, dtype, seed=0):
    """Disjoint per-row page tables; row b's context grows with b up to
    the full P·ps window, so partial last groups and full tables both
    run in the one launch."""
    N = B * P + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, 1, Hq, D), dtype)
    # The pool in the stored layout (engine/kv_cache.py) as the ops take
    # it: page halves [2N, PS, Hk·D], page p's K at 2p, its V at 2p + 1.
    kvp = jnp.stack([jax.random.normal(kk, (N, PS, Hk * D), dtype),
                     jax.random.normal(kv, (N, PS, Hk * D), dtype)],
                    axis=1).reshape(2 * N, PS, Hk * D)
    positions = np.linspace(5, P * PS - 1, B).astype(np.int32).reshape(B, 1)
    tables = np.zeros((B, P), np.int32)
    page = 1
    for b in range(B):
        for j in range(int(positions[b, 0]) // PS + 1):
            tables[b, j] = page
            page += 1
    return q, kvp, jnp.asarray(tables), jnp.asarray(positions)


def quantized_pool(pool, D):
    """An fp pool of page halves [2N, PS, Hk·D] as the int8 (values,
    k scales, v scales) triple: values laid out alike, scales [N, PS, Hk]."""
    from polykey_tpu.engine.kv_cache import fold_heads, unfold_heads
    from polykey_tpu.ops.paged_attention import quantize_kv_rows

    values, scales = quantize_kv_rows(unfold_heads(pool, D))
    return fold_heads(values), scales[0::2], scales[1::2]


def check_decode(quantized: bool) -> None:
    from polykey_tpu.ops.paged_attention import paged_attention
    from polykey_tpu.ops.paged_attention_kernel import paged_attention_decode

    for label, Hq, Hk, D, softcap, window in GEOMETRIES:
        q, kvp, tables, positions = paged_inputs(
            LANES, Hq, Hk, D, TABLE, jnp.bfloat16)
        kw = dict(scale=D ** -0.5, logit_softcap=softcap,
                  window=None if window is None else jnp.int32(window))

        def fp(kvp=kvp, kw=kw):
            want = paged_attention(q, kvp, tables, positions, **kw)
            got = paged_attention_decode(
                q, kvp, tables, positions, **KERNEL, **kw)
            return assert_close(got, want, 8e-2)

        def int8(kvp=kvp, kw=kw, D=D):
            kvq = quantized_pool(kvp, D)
            want = paged_attention(q, kvq, tables, positions, **kw)
            got = paged_attention_decode(
                q, kvq, tables, positions, **KERNEL, **kw)
            return assert_close(got, want, 8e-2)

        case("decode-int8kv" if quantized else "decode-fp",
             f"{label} B={LANES} ctx={TABLE * PS}", int8 if quantized else fp)


def check_flash() -> None:
    """A 512-token prefill bucket against the gathered 4k window — the
    shape forward_paged hands the kernel."""
    from polykey_tpu.ops.attention import attention, make_attention_mask
    from polykey_tpu.ops.flash_attention import flash_attention

    B, T, S = 2, PREFILL, TABLE * PS
    for label, Hq, Hk, D, softcap, window in GEOMETRIES:
        def run(Hq=Hq, Hk=Hk, D=D, softcap=softcap, window=window):
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
            q = jax.random.normal(kq, (B, T, Hq, D), jnp.bfloat16)
            k = jax.random.normal(kk, (B, S, Hk, D), jnp.bfloat16)
            v = jax.random.normal(kv, (B, S, Hk, D), jnp.bfloat16)
            # Queries sit a quarter into the window so the causal mask, the
            # window and the never-written tail are all exercised.
            qpos = jnp.broadcast_to(S // 4 + jnp.arange(T), (B, T))
            want = attention(
                q, k, v,
                make_attention_mask(qpos, S, sliding_window=window),
                scale=D ** -0.5, logit_softcap=softcap,
            )
            got = flash_attention(
                q, k, v, qpos, scale=D ** -0.5, logit_softcap=softcap,
                window=None if window is None else jnp.int32(window),
                **KERNEL,
            )
            return assert_close(got, want, 8e-2)

        case("flash", f"{label} T={T} S={S}", run)
    check_flash_served()


def prefill_read_inputs(B, T, heads, Hk, D, starts, latent: bool, seed=2):
    """(q, pool, tables, positions) of a prefill dispatch's attention as
    `forward_slots_counted` hands it over: B rows of T queries at `starts`
    over whole TABLE-page tables on a pool of noise, a K/V pool of page
    halves or, `latent`, the one-part pool of D-wide rows."""
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    N = B * TABLE + 1
    q = jax.random.normal(kq, (B, T, heads, D), jnp.bfloat16)
    shape = (N, PS, D) if latent else (2 * N, PS, Hk * D)
    pool = jax.random.normal(kp, shape, jnp.bfloat16)
    tables = 1 + np.arange(B * TABLE, dtype=np.int32).reshape(B, TABLE)
    positions = np.asarray(starts, np.int32)[:, None] + np.arange(T)
    return q, pool, jnp.asarray(tables), jnp.asarray(positions)


def prefill_read(latent: bool, bounded: bool, reference: bool = False):
    """read(q, pool, positions, tables, stage) -> (attention, stage): one
    layer's attention of a prefill dispatch — `bounded`, as the engine
    dispatches it (`paged_prefill_attention` / `latent_prefill_attention`:
    the keys the queries can see, gathered into `stage`); else over the
    whole gathered table (`paged_attention` / `latent_attention`, every
    dispatch's form before ISSUE 59; `stage` passes through) — by the
    blockwise kernel (in interpret mode in a rehearsal), or by the jnp
    `reference`."""
    from polykey_tpu.ops import flash_attention as fa
    from polykey_tpu.ops import paged_attention as pa
    from polykey_tpu.ops.attention import attention, make_attention_mask

    flash = fa.flash_attention

    def masked(q, k, v, qpos, *, scale, **_):
        return attention(q, k, v, make_attention_mask(qpos, k.shape[1]),
                         scale=scale)

    def interpreted(*args, **kw):
        kw.pop("force_kernel", None)
        return flash(*args, **{**kw, "interpret": True})

    def read(q, pool, positions, tables, stage):
        # The ops look `flash_attention` up when they are traced.
        if reference:
            fa.flash_attention = masked
        elif "interpret" in KERNEL:
            fa.flash_attention = interpreted
        kw = (dict(scale=0.07, v_width=512) if latent
              else dict(scale=q.shape[-1] ** -0.5))
        try:
            if bounded:
                op = (pa.latent_prefill_attention if latent
                      else pa.paged_prefill_attention)
                return op(q, pool, stage, tables, positions,
                          jnp.max(positions) + 1, **kw)
            op = pa.latent_attention if latent else pa.paged_attention
            return op(q, pool, tables, positions, **kw), stage
        finally:
            fa.flash_attention = flash

    return read


def prefill_read_stage(q, latent: bool, Hk: int):
    from polykey_tpu.ops import paged_attention as pa

    B, T, _, D = q.shape
    return pa.prefill_stage(
        B, TABLE * PS, T, PS, heads=1 if latent else Hk, width=D,
        parts=1 if latent else 2, dtype=q.dtype)


def check_flash_served() -> None:
    """The shape the engine serves: a 4,096-position table, a dispatch's
    queries at positions < 512 (one row at 0, one a window further), the
    K/V pool in bf16 and the latent one-part pool in the `native` form.
    The whole-table read (the kernel walks the table's key blocks as far
    as the furthest query) and the bounded read (the needed pages alone,
    gathered into the stage) against the jnp reference over the whole
    table."""
    T = 128
    shapes = [("8b", False, 32, 8, 128), ("latent-native", True, 16, 1, 640)]
    for label, latent, heads, Hk, D in shapes:
        q, pool, tables, positions = prefill_read_inputs(
            2, T, heads, Hk, D, (0, 512 - T), latent)
        stage = prefill_read_stage(q, latent, Hk)
        want, _ = jax.jit(prefill_read(latent, False, reference=True))(
            q, pool, positions, tables, stage)
        for bounded in (False, True):
            def run(bounded=bounded, latent=latent, want=want,
                    args=(q, pool, positions, tables, stage)):
                got, _ = jax.jit(prefill_read(latent, bounded))(*args)
                return assert_close(got, want, 8e-2)

            case("flash-served",
                 f"{label} T={T} table={TABLE * PS} keys<512 "
                 f"{'bounded' if bounded else 'whole'}", run)


# (label, latent, heads, Hk, D, rows, T, starts): the benchmark's prefill
# dispatches, one layer's attention each.
PREFILL_READS = [
    ("mistral [2,512] start 0", False, 32, 8, 128, 2, 512, (0, 0)),
    ("mistral [1,128] start 0", False, 32, 8, 128, 1, 128, (0,)),
    ("mistral [8,128] cover", False, 32, 8, 128, 8, 128,
     (0, 128, 256, 384, 0, 128, 256, 384)),
    ("mistral [1,512] start 1536", False, 32, 8, 128, 1, 512, (1536,)),
    ("mistral [1,512] start 3584", False, 32, 8, 128, 1, 512, (3584,)),
    ("latent [2,512] start 0", True, 128, 1, 640, 2, 512, (0, 0)),
    ("latent [4,128] start 0", True, 128, 1, 640, 4, 128, (0,) * 4),
]


def check_prefill_read_timing() -> None:
    """`prefill-read` rows: µs a layer of a prefill dispatch's attention
    (the gather of the table's pages and the blockwise kernel), over the
    whole 4,096-position table and over the keys the queries can see (the
    stage threaded from call to call, as from layer to layer), beside the
    keys each moves."""
    from polykey_tpu.ops import paged_attention as pa

    interpret = "interpret" in KERNEL
    calls = 2 if interpret else 16
    for label, latent, heads, Hk, D, B, T, starts in PREFILL_READS:
        if interpret:
            heads, T = max(4, Hk), 128
            starts = tuple(min(s, 128) for s in starts)

        def run(latent=latent, heads=heads, Hk=Hk, D=D, B=B, T=T,
                starts=starts):
            q, pool, tables, positions = prefill_read_inputs(
                B, T, heads, Hk, D, starts, latent)
            stage = prefill_read_stage(q, latent, Hk)
            shifts = jnp.arange(calls, dtype=jnp.bfloat16)

            def scan_of(step):
                def one(carry, shift):
                    total, stage = carry
                    out, stage = step(q + shift, pool, positions, tables,
                                      stage)
                    return (total + jnp.sum(out.astype(jnp.float32)),
                            stage), None

                return jax.jit(lambda q, pool, tables, positions, stage:
                               jax.lax.scan(one, (jnp.float32(0), stage),
                                            shifts)[0][0])

            def seconds(fn):
                args = (q, pool, tables, positions, stage)
                jax.block_until_ready(fn(*args))
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    best = min(best, time.perf_counter() - t0)
                return best

            keys = pa.prefill_keys_read(max(starts) + T, T, TABLE * PS, PS)
            times = [seconds(scan_of(step)) for step in (
                lambda q, pool, positions, tables, stage:
                    (q[..., :8], stage),
                prefill_read(latent, False), prefill_read(latent, True))]
            if interpret:
                return (f"ran (interpret mode on the host: no device time); "
                        f"{TABLE * PS} and {keys} keys a row")
            us = [(s - times[0]) / calls * 1e6 for s in times[1:]]
            return (f"whole table {us[0]:.0f} us/layer ({TABLE * PS} keys a "
                    f"row), bounded {us[1]:.0f} us/layer ({keys} keys a row)")

        case("prefill-read", label, run)


def check_write(quantized: bool) -> None:
    from polykey_tpu.ops.paged_write_kernel import paged_write_rows_kernel

    N = LANES * 8 + 1
    rng = np.random.default_rng(3)
    # Distinct pages per lane (allocator invariant), arbitrary offsets.
    page_ids = jnp.asarray(rng.permutation(N - 1)[:LANES].astype(np.int32) + 1)
    offsets = jnp.asarray(rng.integers(0, PS, LANES).astype(np.int32))

    def compare(pools, rows):
        """`pools`: the data pool as page halves [2N, PS, Hk·D] with rows
        [B, 2, 1, Hk·D], then any scale pools [N, PS, Hk] with rows
        [B, 1, 1, Hk]."""
        got = paged_write_rows_kernel(
            pools, rows, page_ids, offsets,
            interpret=KERNEL.get("interpret", False))
        for pool, row, out in zip(pools, rows, got):
            span = row.shape[1]
            want = pool
            for half in range(span):
                want = want.at[span * page_ids + half, offsets].set(
                    row[:, half, 0])
            if not bool(jnp.array_equal(out, want)):
                raise AssertionError("written pool differs from the scatter")
        return "equal"

    for label, _, Hk, D, _, _ in GEOMETRIES:
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        kvp = jax.random.normal(k1, (2 * N, PS, Hk * D), jnp.bfloat16)
        kvn = jax.random.normal(k2, (LANES, 2, 1, Hk * D), jnp.bfloat16)

        def fp(kvp=kvp, kvn=kvn):
            return compare([kvp], [kvn])

        def int8(Hk=Hk, D=D, k3=k3, k2=k2):
            k8p = jnp.asarray(np.random.default_rng(1).integers(
                -127, 128, (2 * N, PS, Hk * D)), jnp.int8)
            ksp = jax.random.normal(k3, (N, PS, Hk), jnp.bfloat16)
            k8r = jnp.asarray(np.random.default_rng(2).integers(
                -127, 128, (LANES, 2, 1, Hk * D)), jnp.int8)
            ksr = jax.random.normal(k2, (LANES, 1, 1, Hk), jnp.bfloat16)
            return compare([k8p, ksp, ksp * 0.5], [k8r, ksr, ksr + 1])

        case("write-int8kv" if quantized else "write-fp",
             f"{label} B={LANES}", int8 if quantized else fp)


def check_int4() -> None:
    """Packed-uint8 int4 weights through qdot at the 8B MLP shape, under
    jit (the unpack must fuse or at least lower), plus what this machine
    does with a native jnp.int4 operand (models/quant.py says why the
    packed form exists)."""
    from polykey_tpu.models.quant import dequantize, qdot, quantize

    def packed():
        kx, kw = jax.random.split(jax.random.PRNGKey(9))
        x = jax.random.normal(kx, (LANES, 4096), jnp.bfloat16)
        w = quantize(
            jax.random.normal(kw, (4096, 14336), jnp.bfloat16) * 0.02, bits=4)
        got = jax.jit(qdot)(x, w)
        want = jnp.dot(
            x.astype(jnp.float32), dequantize(w, jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return assert_close(got, want, 0.25)

    def native():
        w = jnp.asarray(np.random.default_rng(0).integers(
            -7, 8, (4096, 1024)), jnp.int4)
        x = jnp.ones((LANES, 4096), jnp.bfloat16)
        got = jax.jit(lambda x, w: jnp.dot(
            x, w.astype(x.dtype), preferred_element_type=jnp.float32))(x, w)
        want = jnp.sum(w.astype(jnp.float32), axis=0)[None, :]
        return assert_close(got, jnp.broadcast_to(want, got.shape), 1e-3)

    case("int4-qdot", "packed-uint8 32x4096x14336", packed)
    case("int4-native", "jnp.int4 32x4096x1024", native)


# The decode kernel as the benchmark's cells call it: 16 lanes on 4k-position
# tables; one chip of mistral-7b holds all 8 KV heads (1024 folded lanes), a
# tp = 4 shard of mixtral-8x7b 2 of them (256).
TIMED_SHAPES = [("1024-lanes", 32, 8, 128), ("256-lanes", 8, 2, 128)]
TIMED_CONTEXTS = (PS, 128, 512, 2048)   # PS: one page a lane
TIMED_SEQUENCES = (16, 64)
# --sweep: the hybrid cells' page halves of 512 lanes, in 64-wide heads
# (lfm2) and in 256-wide ones (qwen3-next), 64 sequences a call.
SWEPT_SHAPES = [("512-lanes-d64", 32, 8, 64), ("512-lanes-d256", 16, 2, 256)]
TIMED_CALLS = 128                       # kernel calls inside one jitted scan


def hbm_bytes_per_s() -> float:
    """The attached chip's published HBM bandwidth, from the benchmark's one
    table of peaks (a device that is not in it is an error)."""
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    import peaks

    return peaks.row(jax.devices()[0].device_kind)["hbm_bytes_per_s"]


def time_decode(B, Hq, Hk, D, P, contexts, pages_per_block=0) -> str:
    """µs a call of the decode kernel on bf16 pools, every lane's pages
    scattered over a pool no cache could hold, beside the least time its
    K/V bytes allow and the DMA descriptors it starts and awaits (counted
    from the kernel's own block and tile widths and wait runs, not measured).
    `contexts`: one length for every lane, or one a lane.
    The calls run back to back inside one jitted scan (a new q each, so
    nothing is hoisted) and the scan's own turn, measured empty, is taken
    off."""
    from polykey_tpu.ops import paged_attention_kernel as pak

    contexts = np.broadcast_to(np.asarray(contexts, np.int32), (B,))
    interpret = "interpret" in KERNEL       # a rehearsal: small and few
    N = B * P + 1 if interpret else max(B * P + 1, 8192)
    rng = np.random.default_rng(11)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    calls = 2 if interpret else TIMED_CALLS
    qs = jax.random.normal(kq, (calls, B, 1, Hq, D), jnp.bfloat16)
    kvp = jnp.stack([jax.random.normal(kk, (N, PS, Hk * D), jnp.bfloat16),
                     jax.random.normal(kv, (N, PS, Hk * D), jnp.bfloat16)],
                    axis=1).reshape(2 * N, PS, Hk * D)
    tables = np.zeros((B, P), np.int32)
    pages = rng.permutation(N - 1)[:B * P].reshape(B, P) + 1
    for b in range(B):
        used = (int(contexts[b]) + PS - 1) // PS
        tables[b, :used] = pages[b, :used]
    tables = jnp.asarray(tables)
    positions = jnp.asarray(contexts - 1).reshape(B, 1)

    def scan_of(step):
        # The pool goes in as an argument: closed over, it would be compiled
        # into the program as half a gigabyte of constants.
        return jax.jit(lambda qs, kvp: jax.lax.scan(
            lambda c, q: (c + step(q, kvp).astype(jnp.float32), None),
            jnp.zeros((B, 1, Hq, D), jnp.float32), qs)[0])

    def seconds(fn):
        jax.block_until_ready(fn(qs, kvp))    # compile
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(qs, kvp))
            best = min(best, time.perf_counter() - t0)
        return best

    kernel = scan_of(lambda q, kvp: pak.paged_attention_decode(
        q, kvp, tables, positions, scale=D ** -0.5,
        pages_per_block=pages_per_block, **KERNEL))
    empty = scan_of(lambda q, kvp: q)
    us = (seconds(kernel) - seconds(empty)) / calls * 1e6
    kv_bytes = 2 * int(contexts.sum()) * Hk * D * 2
    # One start a page (K and V under it); a row tile's n pages awaited as
    # one wait for each set bit of n (the kernel's own G, Gt, `_wait_runs`).
    G = pak._block_pages(pages_per_block, Hk * D * 2, PS, P)
    Gt = pak._tile_pages(G, Hk * D * 2, PS)
    starts = waits = 0
    for ctx in contexts:
        pages = (int(ctx) + PS - 1) // PS
        starts += pages
        waits += sum(
            sum(1 for run in pak._wait_runs(Gt) if n & run)
            for n in [Gt] * (pages // Gt) + [pages % Gt])
    counted = f"{starts} starts + {waits} waits"
    if interpret:
        return (f"ran (interpret mode on the host: no device time); "
                f"K/V {kv_bytes / 1e6:.2f} MB; {counted}")
    peak = hbm_bytes_per_s()
    least = kv_bytes / peak * 1e6
    return (f"{us:.1f} us/call; K/V {kv_bytes / 1e6:.2f} MB = {least:.1f} us "
            f"at {peak / 1e9:.0f} GB/s ({100 * least / us:.1f} %), "
            f"rest {us - least:.1f} us = {(us - least) / B:.2f} us a sequence; "
            f"{counted}")


def check_decode_timing(sweep: bool) -> None:
    for label, Hq, Hk, D in TIMED_SHAPES:
        timed = partial(time_decode, 16, Hq, Hk, D)
        # 16 sequences as the dense cells send them, 64 as the hybrid ones;
        # one page a lane is the call that moves almost nothing: what it
        # costs is what a sequence costs whatever it moves.
        for B in TIMED_SEQUENCES:
            for ctx in TIMED_CONTEXTS:
                ctx = min(ctx, TABLE * PS)
                case("decode-time", f"{label} B={B} ctx={ctx}",
                     partial(time_decode, B, Hq, Hk, D, TABLE, ctx))
            # The cells' own mix: every lane another length, 68 to 860.
            mixed = np.minimum(
                np.linspace(68, 860, B), TABLE * PS).astype(int)
            case("decode-time", f"{label} B={B} ctx=68..860",
                 partial(time_decode, B, Hq, Hk, D, TABLE, mixed))
        if not sweep:
            continue
        ctx = min(448, TABLE * PS)
        mixed = np.minimum(np.linspace(68, 860, 16), TABLE * PS).astype(int)
        for B in (1, 4, 8, 32):
            case("decode-time", f"{label} B={B} ctx={ctx}",
                 partial(time_decode, B, Hq, Hk, D, TABLE, ctx))
        for P in (32, 64, 1024):
            case("decode-time", f"{label} B=16 ctx={ctx} table={P}",
                 partial(timed, P, min(ctx, P * PS)))
        for ppb in (4, 8, 16, 32, 64):
            case("decode-time", f"{label} B=16 ctx={ctx} pages/block={ppb}",
                 partial(timed, TABLE, ctx, ppb))
            case("decode-time", f"{label} B=16 ctx=68..860 pages/block={ppb}",
                 partial(timed, TABLE, mixed, ppb))
        for ctx in (1, 17):
            case("decode-time", f"{label} B=16 ctx={ctx}",
                 partial(timed, TABLE, ctx))
    for label, Hq, Hk, D in SWEPT_SHAPES if sweep else ():
        mixed = np.minimum(np.linspace(68, 860, 64), TABLE * PS).astype(int)
        for name, ctx in ((PS, PS), ("68..860", mixed)):
            case("decode-time", f"{label} B=64 ctx={name}",
                 partial(time_decode, 64, Hq, Hk, D, TABLE, ctx))


# (label, hidden the router reads, width the experts read, experts' width,
# experts published, held, top-k, activation, gated): the two hybrid
# configurations of the benchmark as one chip holds them.
HELD_SHAPES = [
    ("lfm2 64x(2048x1536) top-4/64", 2048, 2048, 1536, 64, 64, 4, "silu", True),
    ("nemotron 128x(1024x2688) top-22/512", 4096, 1024, 2688, 512, 128, 22,
     "relu2", False),
]
HELD_ROWS = (64, 128, 256, 512, 1024)
HELD_CALLS = 16
# The decode step's call (64 rows) of each 64-slot cell's shape, with the
# share of the held experts that the routing may choose from: the cells'
# seeded routers make the lanes choose alike, and the ledger's PR 55 lines
# read 96.7 / 47.6 / 52.3 / 61.9 % hit (nemotron 17.9 % at one seed, PR 54).
HELD_DECODE = [
    (HELD_SHAPES[0], 1.0),
    (HELD_SHAPES[1], 0.48),
    (HELD_SHAPES[1], 0.18),
    (("qwen3-next 128x(2048x512) top-10/512", 2048, 2048, 512, 512, 128, 10,
      "silu", True), 0.52),
    (("openpangu 8x(7680x2048) top-8/256", 7680, 7680, 2048, 256, 8, 8,
      "silu", True), 0.62),
]


def held_shape(shape):
    """The shape as checked: the published one, or a toy under --interpret
    (a rehearsal of the script)."""
    if "interpret" not in KERNEL:
        return shape
    return (shape[0], 64, 64, 256, 8, 4, 2, *shape[7:])


def held_inputs(shape, rows: int, calls: int, open_share: float = 1.0):
    """Seeded experts, and for each of `calls` calls seeded rows routed by
    a sigmoid router of the published width: (weights, v [calls, rows, L],
    the combine weights of the held experts [calls, rows, held], 0 off
    the chosen). `open_share`: the leading share of the held experts a row
    may choose; the rest are held and chosen by nobody."""
    _, H, L, inner, published, held, k, _, gated = shape
    key = jax.random.split(jax.random.PRNGKey(rows), 6)
    weights = {
        "up": jax.random.normal(key[0], (held, L, inner), jnp.bfloat16)
        * L ** -0.5,
        "down": jax.random.normal(key[1], (held, inner, L), jnp.bfloat16)
        * inner ** -0.5,
    }
    if gated:
        weights["gate"] = jax.random.normal(
            key[2], (held, L, inner), jnp.bfloat16) * L ** -0.5
    tokens = jax.random.normal(key[3], (calls, rows, H), jnp.bfloat16)
    router = jax.random.normal(key[4], (H, published), jnp.bfloat16) * H ** -0.5
    scores = jax.nn.sigmoid(jnp.einsum(
        "crh,he->cre", tokens, router, preferred_element_type=jnp.float32))
    at = jnp.arange(published)
    scores = jnp.where((at >= round(open_share * held)) & (at < held),
                       -1.0, scores)
    chosen, idx = jax.lax.top_k(scores, k)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(idx, published) * chosen[..., None],
                    axis=-2)[..., :held]
    v = tokens if L == H else jax.random.normal(
        key[5], (calls, rows, L), jnp.bfloat16)
    return weights, v, dense


def time_held(shape, rows: int, grouped: bool,
              open_share: float = 1.0) -> str:
    """µs a call of the held experts' product, masked or grouped (the
    grouped call with its sort, gather and combine), beside the least time
    the held experts' bytes allow, all of them and the hit ones (those
    some row of the call chose: the grouped form's whole read). Calls run
    back to back inside one jitted scan, each on rows and a routing of its
    own (the grouped call with its counting sort; the router's own work is
    in neither)."""
    from polykey_tpu.ops import hybrid_kernels as hk

    shape = held_shape(shape)
    _, _, L, inner, _, held, k, activation, _ = shape
    interpret = "interpret" in KERNEL
    calls = 2 if interpret else HELD_CALLS
    weights, v, dense = held_inputs(shape, rows, calls, open_share)

    def product(v, dense, up, down, gate):
        how = {"gate": gate, "activation": activation, "interpret": interpret}
        if grouped:
            return hk.moe_held_experts_grouped(
                v, up, down, dense, chosen=min(k, held), **how)
        return hk.moe_held_experts(v, up, down, dense, **how)

    def scan_of(step):
        return jax.jit(lambda xs, up, down, gate: jax.lax.scan(
            lambda c, x: (c + step(*x, up, down, gate), None),
            jnp.zeros((rows, L), jnp.float32), xs)[0])

    def seconds(fn):
        args = ((v, dense), weights["up"], weights["down"],
                weights.get("gate"))
        jax.block_until_ready(fn(*args))      # compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    empty = scan_of(lambda v, dense, up, down, gate:
                    v.astype(jnp.float32) * jnp.sum(dense))
    us = (seconds(scan_of(product)) - seconds(empty)) / calls * 1e6
    nbytes = sum(w.size * w.dtype.itemsize for w in weights.values())
    pairs = float(jnp.mean(jnp.sum(dense > 0, axis=(1, 2))))
    hit = float(jnp.mean(jnp.any(dense > 0, axis=1)))
    flops = 2 * L * inner * len(weights) * (
        pairs if grouped else rows * held)
    if interpret:
        return (f"ran (interpret mode on the host: no device time); "
                f"experts {nbytes / 1e6:.2f} MB")
    peak = hbm_bytes_per_s()
    least = nbytes / peak * 1e6
    return (f"{us:.1f} us/call; experts {nbytes / 1e6:.1f} MB = {least:.1f} us "
            f"at {peak / 1e9:.0f} GB/s ({100 * least / us:.1f} %); "
            f"hit {100 * hit:.1f} % of them = {hit * least:.1f} us "
            f"({100 * hit * least / us:.1f} %); "
            f"{pairs:.0f} held pairs of {rows * k}, "
            f"{flops / 1e9:.1f} GFLOP computed")


def compare_held(shape, rows: int) -> str:
    """Grouped against masked and both against the jnp twin on one seeded
    routing at the published shape: the largest |difference|, beside the
    masked kernel's own distance from its twin (what summation order in
    float32 and a bf16 cast of the activation give), and row by row
    relative to the row's largest value — a row left out or computed
    twice reads 1."""
    from polykey_tpu.ops import hybrid_kernels as hk

    shape = held_shape(shape)
    interpret = "interpret" in KERNEL
    weights, v, dense = held_inputs(shape, rows, 1)
    how = {"activation": shape[7], "gate": weights.get("gate")}
    args = (v[0], weights["up"], weights["down"])
    twin = jax.jit(partial(hk.moe_held_experts_jnp, **how))(*args, dense[0])
    masked = jax.jit(partial(hk.moe_held_experts, interpret=interpret, **how))(
        *args, dense[0])
    grouped = jax.jit(partial(
        hk.moe_held_experts_grouped, chosen=min(shape[5], shape[6]),
        interpret=interpret, **how))(*args, dense[0])
    scale = float(jnp.max(jnp.abs(twin)))
    own = max_err(masked, twin)
    err = max(max_err(grouped, masked), max_err(grouped, twin))
    by_row = float(jnp.max(
        jnp.max(jnp.abs(grouped - masked), axis=-1)
        / jnp.maximum(jnp.max(jnp.abs(masked), axis=-1), 1e-6 * scale)))
    quiet = jnp.all(dense[0] == 0, axis=-1)
    if bool(jnp.any(jnp.where(quiet[:, None], grouped, 0.0) != 0)):
        raise AssertionError("a row with no held choice is not zero")
    # Twice the masked kernel's own distance from its twin, or 2^-8 of the
    # scale where that distance is 0 (one bf16 rounding of an activation).
    tol = max(2 * own, scale * 2.0 ** -8)
    if not np.isfinite(err) or err > tol or by_row > 0.05:
        raise AssertionError(
            f"grouped differs by {err:.3e} (masked vs twin {own:.3e}, scale "
            f"{scale:.3e}), worst row {by_row:.3e}")
    return (f"grouped vs masked/twin {err:.2e}; masked vs twin {own:.2e}; "
            f"scale {scale:.2e}; worst row {by_row:.2e}; "
            f"{int(jnp.sum(quiet))} rows with no held choice")


def check_held_experts() -> None:
    # (served): the form `ops/moe.py` `_held_product` takes on the chip —
    # the grouped one at every row count.
    forms = ((False, "masked"), (True, "grouped (served)"))
    for shape in HELD_SHAPES:
        for rows in (64, 512, 1024):
            case("held-compare", f"{shape[0]} rows={rows}",
                 partial(compare_held, shape, rows))
        for rows in HELD_ROWS:
            for grouped, form in forms:
                case("held-time", f"{shape[0]} rows={rows} {form}",
                     partial(time_held, shape, rows, grouped))
    for shape, open_share in HELD_DECODE:
        for grouped, form in forms:
            case("held-time",
                 f"{shape[0]} decode rows=64 open {100 * open_share:.0f} % "
                 f"{form}",
                 partial(time_held, shape, 64, grouped, open_share))


# -- the gated delta rule's decode state update -------------------------------

# lanes, key heads, value heads, Dk, Dv; the value heads side by side in a
# row of the stored state (ops/hybrid_kernels.py `pack_heads`); the calls
# timed back to back: two decode steps of the cell's linear layers.
DELTA_SHAPES = (
    ((64, 16, 32, 128, 128), 1, 18),    # qwen3-next-80b-a3b-ep4
    ((64, 30, 30, 96, 192), 1, 24),     # olmo-hybrid-7b-pp2, heads apart:
                                        # 192 is held 256 wide
    ((64, 30, 30, 96, 192), 2, 24),     # ... as served: rows of 384
)


def delta_inputs(shape, seed: int = 0):
    B, Hk, Hv, Dk, Dv = shape
    k = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return (jax.random.normal(k[0], (B, Hv, Dk, Dv), jnp.float32),
            jnp.exp(-jax.random.uniform(k[1], (B, Hv), jnp.float32)),
            jax.nn.sigmoid(jax.random.normal(k[2], (B, Hv), jnp.float32)),
            unit(jax.random.normal(k[3], (B, Hk, Dk), jnp.float32)),
            unit(jax.random.normal(k[4], (B, Hk, Dk), jnp.float32)) * Dk ** -0.5,
            jax.random.normal(k[5], (B, Hv, Dv), jnp.float32))


def check_delta_state() -> None:
    """`gated_delta_state_update` at the published shapes, in the stored
    layout and — where that puts heads side by side — with the heads apart
    too: against its jax.numpy form (an inactive lane bit for bit), and
    microseconds a call — calls back to back inside one jitted scan that
    carries S, as the decode step's linear layers do — beside the least
    time the PUBLISHED bytes allow, and the bytes the state takes on the
    device."""
    from polykey_tpu.engine.kv_cache import resident_nbytes
    from polykey_tpu.ops import hybrid_kernels as hk

    interpret = "interpret" in KERNEL
    shapes = (((4, 2, 4, 8, 16), 1, 2), ((4, 2, 4, 8, 64), 2, 2)) \
        if interpret else DELTA_SHAPES
    kernel = partial(hk.gated_delta_state_update, interpret=interpret)

    def compare(shape, per_row):
        S, decay, beta, k, q, v = delta_inputs(shape)
        S = hk.pack_heads(S, per_row)
        decay, beta = decay.at[1].set(1.0), beta.at[1].set(0.0)
        want = jax.jit(hk.gated_delta_state_update_jnp)(S, decay, beta, k, q, v)
        got = jax.jit(kernel)(S, decay, beta, k, q, v)
        if not bool(jnp.all(got[0][1] == S[1])):
            raise AssertionError("an inactive lane's state moved")
        return (f"S {assert_close(got[0], want[0], 1e-4)}, "
                f"o {assert_close(got[1], want[1], 1e-4)}, lane 1 untouched")

    def timed(update, shape, per_row, calls):
        S, decay, beta, k, q, v = delta_inputs(shape)
        S = hk.pack_heads(S, per_row)
        run = jax.jit(lambda S, *rest: jax.lax.scan(
            lambda S, _: update(S, *rest), S, None, length=calls),
            donate_argnums=0)
        S, _ = jax.block_until_ready(run(S, decay, beta, k, q, v))  # compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            S, _ = jax.block_until_ready(run(S, decay, beta, k, q, v))
            best = min(best, time.perf_counter() - t0)
        if interpret:
            return "rehearsed"
        least = 2 * S.nbytes / hbm_bytes_per_s()
        return (f"{best / calls * 1e6:.1f} us a call; state read + written "
                f"{least * 1e6:.1f} us ({100 * least * calls / best:.1f} %); "
                f"{S.nbytes / 1e6:.1f} MB published, "
                f"{resident_nbytes(S) / 1e6:.1f} MB on the device")

    for shape, per_row, calls in shapes:
        geometry = "x".join(map(str, shape)) + (
            f" {per_row} heads a row" if per_row > 1 else "")
        case("delta-compare", geometry, partial(compare, shape, per_row))
        case("delta-time", geometry + " kernel",
             partial(timed, kernel, shape, per_row, calls))
        case("delta-time", geometry + " jnp", partial(
            timed, hk.gated_delta_state_update_jnp, shape, per_row, calls))


def head_forms(V: int):
    """(name, fn(x [B, V], top_p [B], top_k [B]) -> a small array,
    ((label, top_p, top_k), ...)) for the sampler's full sort and each
    exact form of a row's W largest values in descending order; the
    settings are arguments, so that the compiler folds neither branch of
    the sampler's away, and a form timed under several is ONE program."""
    from polykey_tpu.engine import sampling

    forms = []

    def add(name, fn, settings=(("", 1.0, 8),)):
        fn.__name__ = name        # the capture's program: a function each
        forms.append((name, jax.jit(fn), settings))

    def loop(W, x):
        """W turns of row maximum + mask that one index."""
        def turn(i, carry):
            x, head = carry
            at = jnp.argmax(x, axis=-1)
            top = jnp.take_along_axis(x, at[:, None], axis=-1)
            mask = jnp.arange(x.shape[-1])[None, :] == at[:, None]
            return (jnp.where(mask, -jnp.inf, x),
                    jax.lax.dynamic_update_slice(head, top, (0, i)))
        head = jnp.zeros((x.shape[0], W), x.dtype)
        return jax.lax.fori_loop(0, W, turn, (x, head))[1]

    def tiles(W, tile, x):
        """Top-W of each tile of the vocabulary, then of the survivors."""
        rows = x.shape[0]
        per_tile = jax.lax.top_k(x.reshape(rows, -1, tile), W)[0]
        return jax.lax.top_k(per_tile.reshape(rows, -1), W)[0]

    # The parent's rule: one sort of every row, two numbers read.
    add(f"full_sort_v{V}", lambda *a: sampling._full_sort_thresholds(*a))
    for W in (8, 32, 64, 128):
        add(f"top_k_w{W}_v{V}", lambda x, *_, W=W: jax.lax.top_k(x, W)[0])
    for W in (8, 32):
        add(f"loop_w{W}_v{V}", lambda x, *_, W=W: loop(W, x))
    for tile in (1024, 4096):
        if tile < V:
            add(f"tiles{tile}_w64_v{V}",
                lambda x, *_, tile=tile: tiles(64, tile, x))
    for W in (8, 32, 64, 128):
        add(f"groups_w{W}_v{V}",
            lambda x, *_, W=W: sampling._sorted_head(x, min(W, V)))
    # The thresholds as the sampler takes them (head + logsumexp + the
    # branch): answered from the head, and sorted for a top_k past it.
    past = sampling.HEAD_WIDTH + 1
    add(f"thresholds_v{V}", lambda *a: sampling._trunc_thresholds(*a),
        (("top_k 8", 1.0, 8), ("top_p 0.5", 0.5, 0),
         (f"top_k {past}", 1.0, past)))
    return forms


def check_sampler_head() -> None:
    """`head-time` rows: the device time of each of head_forms' programs
    (a capture's `XLA Modules` line, so no dispatch is counted) and the
    three longest operations inside it by name — a `sort` over
    [rows, vocabulary] under a `top_k` means nothing was gained."""
    import tempfile

    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    import trace_reduce

    interpret = "interpret" in KERNEL
    rows, calls = (8, 2) if interpret else (64, 20)
    for V in ((1024,) if interpret else (32768, 65536)):
        # A served head's logits: a few far above a wide flat rest.
        x = 3.0 * jax.random.normal(jax.random.PRNGKey(V), (rows, V))
        x = x.at[:, :8].add(12.0)
        forms = [(name, [(label, partial(fn, x, jnp.full((rows,), top_p),
                                         jnp.full((rows,), top_k, jnp.int32)))
                         for label, top_p, top_k in settings])
                 for name, fn, settings in head_forms(V)]
        want = jnp.sort(x, axis=-1)[:, ::-1]
        wrong = set()
        for name, runs in forms:
            for _, fn in runs:
                got = jax.block_until_ready(fn())           # compile
                if "_w" in name and not bool(
                        jnp.all(got == want[:, :got.shape[-1]])):
                    wrong.add(name)
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _, runs in forms:
                    for _, fn in runs:
                        for _ in range(calls):
                            out = fn()
                        jax.block_until_ready(out)
            reduced = trace_reduce.reduce(trace_reduce.extract(
                trace_reduce.find_xplane(trace_dir)))
        for name, runs in forms:
            def row(name=name, runs=runs):
                if name in wrong:
                    raise AssertionError("not the row's sorted head")
                if interpret:
                    return "rehearsed"
                # The program's executions in the order they were asked
                # for: `calls` under each of its settings.
                program = reduced["modules"][f"jit_{name}"]
                took = program["durations_s"]
                if len(took) != calls * len(runs):
                    raise AssertionError(f"{len(took)} executions captured")
                each = " / ".join(
                    f"{label} {sum(took[i * calls:(i + 1) * calls]) / calls * 1e6:.1f}".strip()
                    for i, (label, _) in enumerate(runs))
                ops = sorted(
                    ((k.split("/", 1)[1], v["total_s"] / v["count"], v["count"])
                     for k, v in reduced["ops"].items()
                     if k.startswith(f"jit_{name}/")),
                    key=lambda kv: -kv[1] * kv[2])[:3]
                return (f"{each} us a call ({calls} calls each): " + "; ".join(
                    f"{op} {s * 1e6:.1f} us x {int(n)}" for op, s, n in ops))
            case("head-time", f"{rows}x{V} {name}", row)


def check_block_until_ready() -> None:
    """Does jax.block_until_ready block here? A long dependent matmul
    chain is dispatched; the call returning in a sliver of the time the
    blocked run takes means dispatch is asynchronous AND the wait is
    real. (An earlier deployment's backend made it a no-op.)"""
    def run():
        n = 512 if "interpret" in KERNEL else 4096
        x = jnp.ones((n, n), jnp.bfloat16)

        @jax.jit
        def chain(x):
            return jax.lax.fori_loop(
                0, 200, lambda _, y: (y @ x) * 1e-3, x)

        jax.block_until_ready(chain(x))          # compile
        t0 = time.monotonic()
        y = chain(x)
        dispatched = time.monotonic() - t0
        jax.block_until_ready(y)
        blocked = time.monotonic() - t0
        if not blocked > 5 * dispatched:
            raise AssertionError(
                f"dispatch {dispatched * 1e3:.1f} ms vs blocked "
                f"{blocked * 1e3:.1f} ms: the wait did not wait")
        return (f"dispatch returned in {dispatched * 1e3:.1f} ms, "
                f"block_until_ready after {blocked * 1e3:.1f} ms: blocks")

    case("block_until_ready", "200-matmul dependent chain", run)


def main() -> int:
    global LANES, TABLE, PREFILL, KERNEL
    from polykey_tpu.engine.config import enable_persistent_compile_cache
    from polykey_tpu.engine.device import device_identity

    print(f"compile cache: {enable_persistent_compile_cache()}")
    identity = device_identity()        # raises on an unknown TPU kind
    print(f"device: {identity}")
    interpret = "--interpret" in sys.argv[1:]
    if interpret:
        LANES, TABLE, PREFILL, KERNEL = 8, 16, 128, {"interpret": True}
    elif identity["platform"] != "tpu":
        print("no TPU: nothing to check", file=sys.stderr)
        return 2
    # The smoke's default path first; the kernels that have never run on
    # hardware last, so a hang there costs no other case its evidence.
    held_only = "--held-experts" in sys.argv[1:]
    delta_only = "--delta-state" in sys.argv[1:]
    if "--mha" in sys.argv[1:]:
        GEOMETRIES[:] = [("mha-30x128", 30, 30, 128, None, None)]
        LANES = 8 if interpret else 64
        check_decode(quantized=False)
        check_write(quantized=False)
        check_flash()
        return report(identity, interpret)
    if "--sampler-head" in sys.argv[1:]:
        check_sampler_head()
        return report(identity, interpret)
    if "--prefill-read" in sys.argv[1:]:
        check_flash()
        check_prefill_read_timing()
        return report(identity, interpret)
    timing_only = held_only or "--timing" in sys.argv[1:]
    check_block_until_ready()
    if delta_only:
        check_delta_state()
        return report(identity, interpret)
    if not timing_only:
        check_flash()
        check_decode(quantized=False)
        check_write(quantized=False)
        check_int4()
    if not held_only:
        check_decode_timing(sweep="--sweep" in sys.argv[1:])
    check_held_experts()
    check_delta_state()
    if not timing_only:
        check_decode(quantized=True)
        check_write(quantized=True)
    return report(identity, interpret)


def report(identity, interpret: bool) -> int:
    failed = [r for r in RESULTS if not r[2]]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.txt"), "w") as f:
        f.write(f"device: {identity}\njax {jax.__version__}\n\n")
        for kernel, geometry, ok, detail in RESULTS:
            f.write(f"{kernel} | {geometry} | "
                    f"{'PASS' if ok else 'FAIL'} | {detail}\n")
        f.write("\n" + "\n".join(FULL_LOG))
    print(f"{len(RESULTS) - len(failed)} passed, {len(failed)} failed")
    if interpret:
        print("interpret-mode rehearsal: no lowering was checked",
              file=sys.stderr)
        return 2
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
