"""Decode-path component profiler (run on TPU when diagnosing throughput).

Answers PERF.md's open questions with wall-times per component at serving
geometry, printed as one JSON line (stderr carries progress):

- forward_paged decode step (the paged-attention kernel path) vs the
  gather fallback, at [B, 1] decode shapes;
- unembed (vocab matmul) in bf16 vs int8-quantized weights;
- sample_dynamic (sort path) vs greedy argmax;
- a K-step blocked decode through the real jitted engine step;
- host<->device roundtrip floor.

Usage:
    python scripts/profile_decode.py [model] [batch] [block]
e.g.
    python scripts/profile_decode.py llama-1b-bench 32 16
    POLYKEY_PROFILE_QUANT=1 python scripts/profile_decode.py llama-3-8b 16 16
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def timeit(name, fn, *args, n=10):
    import jax

    t0 = time.monotonic()
    out = fn(*args)
    jax.block_until_ready(out)
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.monotonic() - t0) / n * 1000
    log(f"{name}: {ms:.2f} ms (compile+1st {compile_s:.1f}s)")
    return ms, out


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "llama-1b-bench"
    B = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    K = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    quant = os.environ.get("POLYKEY_PROFILE_QUANT", "") in ("1", "true")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from polykey_tpu.engine import engine as eng_mod
    from polykey_tpu.engine.kv_cache import init_paged_kv
    from polykey_tpu.engine.sampling import sample_dynamic
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.models.transformer import forward_paged, init_params, unembed
    from polykey_tpu.ops import paged_attention_kernel as pak

    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind}")
    cfg = get_config(model)
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32

    results: dict = {
        "model": model, "batch": B, "block": K,
        "platform": dev.platform, "quantized": quant,
    }

    # Roundtrip floor.
    t0 = time.monotonic()
    for _ in range(5):
        np.asarray(jax.device_put(np.zeros((1,), np.int32)))
    results["roundtrip_ms"] = round((time.monotonic() - t0) / 5 * 1000, 2)
    log(f"roundtrip: {results['roundtrip_ms']} ms")

    log("building params...")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype)
    if quant:
        params = quantize_params(params, cfg)
    params = jax.block_until_ready(params)

    ps, pages = 16, max(2 * B * (512 // 16), 64)
    paged = init_paged_kv(cfg, pages, ps, dtype)
    pt = np.zeros((B, 512 // ps), np.int32)
    per = 512 // ps
    for b in range(B):
        pt[b, : per // 2] = np.arange(1 + b * (per // 2), 1 + (b + 1) * (per // 2))
    page_tables = jnp.asarray(pt)
    last = jnp.zeros((B,), jnp.int32)
    seq = jnp.full((B,), 200, jnp.int32)

    # --- forward_paged decode (kernel) vs gather fallback. ---
    @jax.jit
    def fwd(params, paged, last, seq, page_tables):
        positions = jnp.maximum(seq - 1, 0)[:, None]
        hidden, paged = forward_paged(
            params, cfg, last[:, None], positions, paged, page_tables
        )
        return hidden[:, 0], paged

    ms, (h, paged) = timeit("forward_paged decode (kernel path)", fwd,
                            params, paged, last, seq, page_tables)
    results["decode_fwd_ms"] = round(ms, 2)

    orig = pak.use_paged_kernel
    try:
        pak.use_paged_kernel = lambda *a, **k: False

        @jax.jit
        def fwd_gather(params, paged, last, seq, page_tables):
            positions = jnp.maximum(seq - 1, 0)[:, None]
            hidden, paged = forward_paged(
                params, cfg, last[:, None], positions, paged, page_tables
            )
            return hidden[:, 0], paged

        ms, _ = timeit("forward_paged decode (gather fallback)", fwd_gather,
                       params, paged, last, seq, page_tables)
        results["decode_fwd_gather_ms"] = round(ms, 2)
    except Exception as e:
        log(f"gather fallback probe failed: {e}")
        results["decode_fwd_gather_ms"] = None
    finally:
        pak.use_paged_kernel = orig

    # --- unembed. ---
    ms, logits = timeit("unembed", jax.jit(
        lambda p, h: unembed(p, cfg, h)), params, h)
    results["unembed_ms"] = round(ms, 2)

    # --- sampling. ---
    key = jax.random.PRNGKey(1)
    temp0 = jnp.zeros((B,), jnp.float32)
    topp1 = jnp.ones((B,), jnp.float32)
    ms, _ = timeit("sample_dynamic (sort path)", jax.jit(sample_dynamic),
                   logits, key, temp0, topp1)
    results["sample_sort_ms"] = round(ms, 2)
    ms, _ = timeit("argmax", jax.jit(lambda l: jnp.argmax(l, -1)), logits)
    results["sample_argmax_ms"] = round(ms, 2)

    # --- the real K-step blocked decode fn. ---
    caps = jnp.full((B,), 512, jnp.int32)
    active = jnp.ones((B,), bool)
    step = jax.jit(
        eng_mod._decode_fn,
        static_argnames=("cfg", "greedy", "steps", "eos_id"),
        donate_argnames=("paged",),
    )

    def run_block(paged):
        seeds = jnp.zeros((B, 2), jnp.int32)
        topk0 = jnp.zeros((B,), jnp.int32)
        return step(params, cfg, paged, last, seq, page_tables, active,
                    caps, seeds, temp0, topp1, topk0,
                    greedy=True, steps=K, eos_id=-1)

    t0 = time.monotonic()
    outs = run_block(paged)
    jax.block_until_ready(outs)
    log(f"block compile+1st: {time.monotonic() - t0:.1f}s")
    paged = outs[-2]
    t0 = time.monotonic()
    n = 5
    for _ in range(n):
        outs = run_block(paged)
        paged = outs[-2]
        jax.block_until_ready(outs[0])
    ms = (time.monotonic() - t0) / n * 1000
    log(f"decode block (K={K}): {ms:.2f} ms -> {ms / K:.2f} ms/step, "
        f"{B * K / (ms / 1000):.0f} tok/s")
    results["block_ms"] = round(ms, 2)
    results["per_step_ms"] = round(ms / K, 2)
    results["tok_s"] = round(B * K / (ms / 1000), 1)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
