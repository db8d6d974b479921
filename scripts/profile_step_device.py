"""Precise on-device decode-step component profiler.

Per-call timing from the host includes dispatch and sync overhead, so
this profiler removes the host twice over:
- each component runs in a lax.scan of N iterations inside ONE jit
  (one dispatch, one sync), with iteration-dependent inputs (scan xs
  feeds the op) so XLA cannot hoist the body out of the loop;
- the reported per-iteration time is the SLOPE between an N-iteration
  and a 2N-iteration run: (wall_2N - wall_N) / N, which cancels the
  constant dispatch+sync overhead entirely.
A profiler trace (ROADMAP Queue 1 item 5) supersedes this once the
benchmark's trace reduction exists.

Components, at serving geometry (defaults: llama-1b-bench, B=32, ctx=512):
- HBM bandwidth floor: one full read of every param byte per iteration;
- forward_paged decode on the attention path the environment selects
  (the output says which; POLYKEY_DISABLE_PAGED_KERNEL=1 from outside
  gives the gather path);
- unembed, unembed+argmax.

Usage: python scripts/profile_step_device.py [model] [batch] [ctx]
Env: POLYKEY_PROFILE_N (default 25)
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
    N = int(os.environ.get("POLYKEY_PROFILE_N", "25"))

    from polykey_tpu.engine.kv_cache import init_paged_kv
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import forward_paged, init_params, unembed

    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind}; model={model} B={B} ctx={ctx} N={N}")

    cfg = get_config(model)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"param bytes: {n_bytes/1e9:.2f} GB")

    page_size = 16
    pages_per_seq = (ctx + page_size - 1) // page_size
    total_pages = B * pages_per_seq + 1
    paged = init_paged_kv(cfg, total_pages, page_size, dtype=jnp.bfloat16)

    pt = np.zeros((B, pages_per_seq), np.int32)
    for b in range(B):
        pt[b] = np.arange(pages_per_seq, dtype=np.int32) + 1 + b * pages_per_seq
    page_tables = jnp.asarray(pt)
    tokens = jnp.ones((B, 1), jnp.int32)
    positions = jnp.full((B, 1), ctx - 1, jnp.int32)

    def timed(name, fn, *args):
        """fn(x_scalar_int32, *args) -> pytree; x varies per iteration."""
        def make(n):
            @jax.jit
            def loop(*a):
                def body(c, x):
                    out = fn(x, *a)
                    s = jax.tree.reduce(
                        lambda p, q: p + q,
                        jax.tree.map(
                            lambda t: t.astype(jnp.float32).sum(), out
                        ),
                    )
                    return c + s, None
                acc, _ = jax.lax.scan(
                    body, jnp.float32(0), jnp.arange(n, dtype=jnp.int32)
                )
                return acc
            return loop

        # Sync on the scalar result (np.asarray waits for the device).
        loop1, loop2 = make(N), make(2 * N)
        np.asarray(loop1(*args))
        np.asarray(loop2(*args))
        walls = []
        for loop in (loop1, loop2, loop1, loop2):
            t0 = time.monotonic()
            np.asarray(loop(*args))
            walls.append(time.monotonic() - t0)
        w1 = min(walls[0], walls[2])
        w2 = min(walls[1], walls[3])
        ms = (w2 - w1) / N * 1000
        log(f"{name}: {ms:.3f} ms/iter  (wall N={w1*1000:.0f} ms, 2N={w2*1000:.0f} ms)")
        return round(ms, 3)

    results = {"model": model, "batch": B, "ctx": ctx, "N": N,
               "platform": dev.platform,
               "param_gb": round(n_bytes / 1e9, 3)}

    # HBM floor: every param byte read once per iteration; the x-scaled
    # multiply keeps the read inside the loop.
    results["param_read_ms"] = timed(
        "param-read (HBM floor)",
        lambda x, p: jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(
                lambda t: (t.astype(jnp.float32) * (1.0 + x)).sum(), p
            ),
        ),
        params,
    )

    def fwd(x, p, tok, pos, pg, ptbl):
        t = (tok + x) % 97 + 1
        return forward_paged(p, cfg, t, pos, pg, ptbl)[0]

    # Whichever attention path the environment selects, named in the
    # output. For the kernel-vs-gather comparison run the script twice,
    # the second time with POLYKEY_DISABLE_PAGED_KERNEL=1 set outside:
    # nothing in code sets a kill switch.
    from polykey_tpu.ops.paged_attention_kernel import use_paged_kernel

    results["paged_kernel"] = use_paged_kernel(cfg.num_kv_heads, cfg.head_dim)
    results["fwd_ms"] = timed(
        "forward_paged "
        + ("kernel" if results["paged_kernel"] else "gather"), fwd,
        params, tokens, positions, paged, page_tables)

    h = jnp.ones((B, cfg.hidden_size), jnp.bfloat16)
    results["unembed_ms"] = timed(
        "unembed",
        lambda x, p, hh: unembed(p, cfg, hh * (1.0 + x).astype(hh.dtype)),
        params, h)
    results["unembed_argmax_ms"] = timed(
        "unembed+argmax",
        lambda x, p, hh: jnp.argmax(
            unembed(p, cfg, hh * (1.0 + x).astype(hh.dtype)), axis=-1),
        params, h)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
