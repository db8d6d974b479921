"""Time the engine's REAL decode-block function on device, in isolation.

profile_step_device.py measures bare components (its scan discards the
updated KV pool, so paged_write may be dead-code-eliminated); this script
times `_decode_fn` exactly as the engine dispatches it — same jit wrapper,
same donation, pool chained block-to-block — via the backpressure slope:
dispatch M blocks chained, sync once on the final packed tokens, and
report (wall_2M - wall_M) / M per block. The sync is np.asarray of the
small [K, B] output.

Variants: K=16 vs K=1 (fixed-vs-marginal split), donation on vs off
(pool-copy cost), all on the attention path the environment selects (the
output says which; POLYKEY_DISABLE_PAGED_KERNEL=1 from outside gives the
gather path).

Usage: python scripts/profile_block_device.py [model] [batch] [ctx] [K]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = sys.argv[1] if len(sys.argv) > 1 else "llama-1b-bench"
    B = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    ctx = int(sys.argv[3]) if len(sys.argv) > 3 else 512
    K = int(sys.argv[4]) if len(sys.argv) > 4 else 16

    from polykey_tpu.engine.engine import _decode_fn
    from polykey_tpu.engine.kv_cache import init_paged_kv, kv_pool_bytes
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import init_params

    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind}; {model} B={B} ctx={ctx} K={K}")

    cfg = get_config(model)
    params = init_params(jax.random.PRNGKey(0), cfg)

    page_size = 16
    pages_per_seq = (ctx + 256 + page_size - 1) // page_size  # headroom to decode into
    total_pages = B * pages_per_seq + 1
    kv_int8 = os.environ.get("POLYKEY_PROFILE_KV", "") == "int8"
    kv_q = jnp.int8 if kv_int8 else None
    paged = init_paged_kv(
        cfg, total_pages, page_size, dtype=jnp.bfloat16, kv_dtype=kv_q,
    )
    pool_gb = kv_pool_bytes(
        cfg, total_pages, page_size, dtype=jnp.bfloat16, kv_dtype=kv_q,
    ) / 1e9
    log(f"pool: {pool_gb:.2f} GB kv={'int8' if kv_int8 else 'bf16'}")

    pt = np.zeros((B, pages_per_seq), np.int32)
    for b in range(B):
        pt[b] = np.arange(pages_per_seq, dtype=np.int32) + 1 + b * pages_per_seq
    page_tables = jnp.asarray(pt)

    def fresh_state():
        return dict(
            last_tokens=jnp.ones((B,), jnp.int32),
            seq_lens=jnp.full((B,), ctx, jnp.int32),
            active=jnp.ones((B,), bool),
            caps=jnp.full((B,), ctx + 250, jnp.int32),
            seeds=jnp.zeros((B, 2), jnp.uint32),
            temperature=jnp.zeros((B,), jnp.float32),
            top_p=jnp.ones((B,), jnp.float32),
            top_k=jnp.zeros((B,), jnp.int32),
        )

    results = {"model": model, "batch": B, "ctx": ctx, "K": K,
               "platform": dev.platform, "pool_gb": round(pool_gb, 2),
               "kv": "int8" if kv_int8 else "bf16"}

    def run_variant(name, steps, donate):
        jit_kw = dict(static_argnames=(
            "cfg", "greedy", "steps", "eos_id", "candidates", "mesh"))
        if donate:
            jit_kw["donate_argnames"] = ("paged",)
        fn = jax.jit(_decode_fn, **jit_kw)

        def run(M, pool):
            st = fresh_state()
            seq = st.pop("seq_lens")
            last = st.pop("last_tokens")
            act = st.pop("active")
            packed = None
            t0 = time.monotonic()
            for _ in range(M):
                packed, last, seq, act, pool, _ = fn(
                    params, cfg, pool, last, seq, page_tables, act,
                    st["caps"], st["seeds"], st["temperature"],
                    st["top_p"], st["top_k"],
                    greedy=True, steps=steps, eos_id=2, candidates=0,
                    mesh=None,
                )
            np.asarray(packed)
            return time.monotonic() - t0, pool

        pool = paged
        _, pool = run(1, pool)      # compile
        w4, pool = run(4, pool)
        w8, pool = run(8, pool)
        per_block = (w8 - w4) / 4 * 1000
        log(f"{name}: {per_block:.1f} ms/block -> {per_block/steps:.2f} ms/step "
            f"(wall M4={w4*1000:.0f} M8={w8*1000:.0f})")
        return round(per_block, 1), pool

    # Whichever attention path the environment selects, named in the
    # output; POLYKEY_DISABLE_PAGED_KERNEL=1 from outside gives the gather
    # path (nothing in code sets a kill switch).
    from polykey_tpu.ops.paged_attention_kernel import use_paged_kernel

    results["paged_kernel"] = use_paged_kernel(cfg.num_kv_heads, cfg.head_dim)
    path = "kernel" if results["paged_kernel"] else "gather"
    results["block_ms"], paged = run_variant(
        f"K={K} {path} donate", K, True)
    results["block_k1_ms"], paged = run_variant(
        f"K=1 {path} donate", 1, True)
    results["block_nodonate_ms"], paged = run_variant(
        f"K={K} {path} NO-donate", K, False)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
