"""Chip check of the prefill cover: a prompt prefilled as consecutive rows
of ONE group dispatch against the same prompt in one wide window.

The engine covers a prompt with the fewest rows its compiled windows
allow (engine.prefill_cover): 129..256 tokens on buckets (128, 512) are
two rows of a [2, 128] dispatch, starts (0, 128) on the SAME page table,
where they used to be one row of [1, 512]. That rests on the order inside
a layer — every row's K/V is scattered into the pools before any row's
attention gathers — and is proven on the CPU in float32
(tests/test_engine.py). This runs it where it is served: the
configuration's own widths, weights and precision, the flash prefill
path at a start above 0.

Compared, on the benchmark's `mistral-7b` (int8 weights, bf16 pools):
the logits at the last real position, the K/V pages written for the
prompt's positions, and the engine's own `_jit_prefill` token; then each
prefill shape the cover trades between is timed alone (ms a dispatch,
host clock around block_until_ready, the median of --reps).

Run: python scripts/tpu_prefill_cover_check.py          (one chip, ~3 min)
     JAX_PLATFORMS=cpu python scripts/tpu_prefill_cover_check.py --tiny
       rehearses the script at the configuration's tiny size in float32
       (exact agreement expected; its times mean nothing).
Exit 0 when tokens agree and the differences are inside --tolerance.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def window_logits(params, cfg, paged, tokens, start, last_rel, tables, *,
                  mesh=None):
    """_prefill_fn up to the logits it samples from."""
    from polykey_tpu.models.transformer import forward_paged, unembed

    n, t = tokens.shape
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    hidden, paged = forward_paged(
        params, cfg, tokens, positions, paged, tables, mesh=mesh
    )
    return unembed(params, cfg, hidden[jnp.arange(n), last_rel]), paged


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default=os.path.join(
        ROOT, "perfbench", "configs", "mistral-7b.json"))
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--tokens", type=int, default=0,
                        help="prompt length (default: 200, tiny 25)")
    parser.add_argument("--seed", type=int, default=41)
    parser.add_argument("--reps", type=int, default=7)
    parser.add_argument("--tolerance", type=float, default=0.25,
                        help="largest logit difference taken for rounding")
    args = parser.parse_args(argv)

    import server_child
    from polykey_tpu.engine.config import enable_persistent_compile_cache
    from polykey_tpu.engine.engine import InferenceEngine, prefill_cover
    from polykey_tpu.models.config import MODEL_REGISTRY

    with open(args.config) as f:
        spec = json.load(f)
    model_cfg = server_child.model_config_from(spec, args.tiny)
    config = dataclasses.replace(
        server_child.engine_config_from(spec, args.tiny),
        compile_warmup=False, supervise=False, signals_interval_s=0,
    )
    MODEL_REGISTRY[config.model] = model_cfg
    enable_persistent_compile_cache()
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind} "
          f"x{jax.device_count()}", flush=True)
    if not args.tiny and device.platform != "tpu":
        print("no TPU: nothing is proven here")
        return 2

    small, wide = (16, 64) if args.tiny else (128, 512)
    n_tokens = args.tokens or (25 if args.tiny else 200)
    cover = prefill_cover(n_tokens, 0, (small, wide), (1, 2, 4, 8),
                          config.page_size)
    print(f"cover of {n_tokens} tokens on ({small}, {wide}): {cover}")
    assert cover == [(small, 0), (small, small)], cover

    engine = InferenceEngine(config)
    engine.shutdown()                   # the loop has ended: driven by hand
    put = partial(jax.device_put, device=engine._repl)
    rng = np.random.default_rng(args.seed)
    ids = rng.integers(32, 127, size=n_tokens).astype(np.int32)
    pages = -(-wide // config.page_size)

    def table(first_page: int) -> np.ndarray:
        row = np.zeros((config.pages_per_seq,), np.int32)
        row[:pages] = first_page + np.arange(pages)
        return row

    def operands(width: int, first_page: int):
        """(tokens, start, last_rel, tables) of the prompt as rows of
        `width` on one table whose pages start at `first_page`."""
        k = -(-n_tokens // width)
        tokens = np.zeros((k, width), np.int32)
        tokens.reshape(-1)[:n_tokens] = ids
        start = (np.arange(k) * width).astype(np.int32)
        last_rel = np.full((k,), width - 1, np.int32)
        last_rel[-1] = n_tokens - 1 - start[-1]
        return tokens, start, last_rel, np.tile(table(first_page), (k, 1))

    def placed(tokens, *rest):
        return (jax.device_put(tokens, engine._prefill_tok), *map(put, rest))

    logits_fn = jax.jit(
        window_logits, static_argnames=("cfg", "mesh"),
        donate_argnames=("paged",),
        out_shardings=(engine._repl, engine._pool_sharding),
    )
    # One wide row on pages 1.., then two narrow rows on pages after them.
    one, engine.paged = logits_fn(
        engine.params, engine.model_cfg, engine.paged,
        *placed(*operands(wide, 1)), mesh=engine.mesh,
    )
    two, engine.paged = logits_fn(
        engine.params, engine.model_cfg, engine.paged,
        *placed(*operands(small, 1 + pages)), mesh=engine.mesh,
    )
    one = np.asarray(one, np.float32)[-1]
    two = np.asarray(two, np.float32)[-1]
    logit_diff = float(np.max(np.abs(one - two)))
    order = np.sort(one)
    print(f"logits at position {n_tokens - 1}: max |one - two| "
          f"{logit_diff:.6f} (spread of the logits {order[-1] - order[0]:.3f}, "
          f"top-1 margin {order[-1] - order[-2]:.6f}); argmax "
          f"{int(one.argmax())} / {int(two.argmax())}")

    live = -(-n_tokens // config.page_size)
    real = n_tokens - (live - 1) * config.page_size   # rows of the last page
    # K and V of the pages alike: [L, live, 2, page_size, Hk·D].
    a = np.array(engine.paged.kv[:, 1:1 + live], np.float32)
    b = np.array(engine.paged.kv[:, 1 + pages:1 + pages + live], np.float32)
    a[:, -1, :, real:] = b[:, -1, :, real:] = 0.0     # padding rows differ
    kv_diff = float(np.max(np.abs(a - b)))
    kv_max = float(np.max(np.abs(a)))
    print(f"K/V of positions 0..{n_tokens - 1}, {live} pages x "
          f"{engine.model_cfg.num_layers} layers: max |one - two| "
          f"{kv_diff:.6f} (largest entry {kv_max:.3f})")

    def prefill(width: int, rows: int, first_page: int):
        """The engine's own executable on `rows` rows of `width`: the
        prompt's cover first, garbage-page rows after it."""
        k = min(-(-n_tokens // width), rows)    # [1, small]: one window alone
        tokens, start, last_rel, tables = (
            x[:k] for x in operands(width, first_page)
        )
        pad = rows - k
        tokens = np.concatenate([tokens, np.zeros((pad, width), np.int32)])
        start = np.concatenate([start, np.zeros((pad,), np.int32)])
        last_rel = np.concatenate([last_rel, np.zeros((pad,), np.int32)])
        tables = np.concatenate(
            [tables, np.zeros((pad, config.pages_per_seq), np.int32)]
        )
        n = len(start)
        out, engine.paged, _ = engine._jit_prefill(
            engine.params, engine.model_cfg, engine.paged,
            *placed(tokens, start, last_rel, tables),
            put(np.zeros((n, 2), np.int32)),
            put(np.zeros((n,), np.float32)), put(np.ones((n,), np.float32)),
            put(np.zeros((n,), np.int32)),
            greedy=True, candidates=config.top_p_candidates,
            mesh=engine.mesh,
        )
        return out, k

    token_one, _ = prefill(wide, 1, 1)
    token_two, k = prefill(small, 2, 1 + pages)
    token_one = int(np.asarray(token_one)[0])
    token_two = int(np.asarray(token_two)[k - 1])
    print(f"_jit_prefill first token: {token_one} / {token_two}")

    print("ms a dispatch (host clock around block_until_ready, median of "
          f"{args.reps}; rows x width):")
    for width, rows in ((small, 1), (small, 2), (small, 4), (small, 8),
                        (wide, 1), (wide, 2)):
        prefill(width, rows, 1)
        jax.block_until_ready(engine.paged)
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out, _ = prefill(width, rows, 1)
            jax.block_until_ready((out, engine.paged))
            times.append((time.perf_counter() - t0) * 1e3)
        print(f"  [{rows}, {width}] {statistics.median(times):8.3f} "
              f"(min {min(times):.3f}, max {max(times):.3f})", flush=True)

    ok = (token_one == token_two and logit_diff <= args.tolerance
          and kv_diff <= args.tolerance)
    print("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
