"""Automatic prefix caching (engine/prefix_cache.py).

The acceptance bar is exact greedy equality: a cached engine must produce
the same streams as an uncached one for repeated prompts, shared-prefix
prompts, and prefix-of-each-other prompts — sharing pages must be
invisible to the math. Lifetime: cache refs + slot refs account for every
page (no leaks, eviction under pressure works).
"""

import dataclasses
import queue
import time

import numpy as np

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.kv_cache import BlockAllocator
from polykey_tpu.engine.prefix_cache import PrefixCache

CFG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=4,
    page_size=8,
    num_pages=128,
    max_seq_len=128,
    prefill_buckets=(16, 32),
    prefill_chunk=16,
    max_new_tokens_cap=16,
    prefix_cache=True,
)


def _collect(request, timeout=60.0):
    tokens, done, error = [], None, None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            kind, value = request.out.get(timeout=deadline - time.monotonic())
        except queue.Empty:
            break
        if kind == "token":
            tokens.append(value)
        elif kind == "done":
            done = value
            break
        else:
            error = value
            break
    return tokens, done, error


def _serve(config, prompts, max_new=8):
    eng = InferenceEngine(config)
    outs = []
    try:
        for p in prompts:           # sequential: later prompts see cache
            r = GenRequest(prompt=p, max_new_tokens=max_new)
            eng.submit(r)
            tokens, done, error = _collect(r)
            assert error is None, error
            assert done is not None
            outs.append(tokens)
        return outs, eng.stats()
    finally:
        eng.shutdown()


# --- unit tier: the cache structure itself -------------------------------


def test_cache_lookup_never_matches_full_prompt():
    alloc = BlockAllocator(32)
    cache = PrefixCache(alloc, page_size=4, capacity_pages=8)
    ids = np.arange(8, dtype=np.int32)          # exactly 2 pages
    pages = alloc.alloc(2)
    cache.insert(ids, pages)
    # Only page 0 of the prompt is insertable/matchable ((8-1)//4 == 1).
    assert len(cache) == 1
    assert len(cache.lookup(ids)) == 1
    # A 9-token prompt sharing both pages can match both... but only one
    # is cached; extend the cache with a longer prompt's pages.
    ids9 = np.arange(9, dtype=np.int32)
    p9 = alloc.alloc(3)
    cache.insert(ids9, p9)                      # caches page keys 0,1
    assert len(cache.lookup(ids9)) == 2


def test_cache_divergent_prefixes_do_not_collide():
    alloc = BlockAllocator(32)
    cache = PrefixCache(alloc, page_size=4, capacity_pages=8)
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9], dtype=np.int32)
    b = np.array([1, 2, 3, 4, 9, 9, 9, 9, 9], dtype=np.int32)  # page 1 differs
    pa = alloc.alloc(3)
    cache.insert(a, pa)
    hit = cache.lookup(b)
    assert len(hit) == 1 and hit[0] == pa[0]    # shared page 0 only


def test_cache_eviction_frees_pages():
    alloc = BlockAllocator(16)
    cache = PrefixCache(alloc, page_size=4, capacity_pages=4)
    free0 = alloc.num_free
    for seed in range(4):
        ids = np.full((9,), seed, dtype=np.int32)
        pages = alloc.alloc(2)
        cache.insert(ids, pages)
        alloc.release_all(pages)                # slot done; cache ref holds
    assert alloc.num_free == free0 - 4          # 4 cached first-pages
    cache.evict_for(free0)                      # demand everything back
    assert alloc.num_free == free0


# --- engine tier: equality + lifetime ------------------------------------


def test_repeated_prompt_matches_uncached_engine():
    prompts = ["the same long-ish prompt body repeated", ] * 3
    ref, _ = _serve(dataclasses.replace(CFG, prefix_cache=False), prompts)
    out, stats = _serve(CFG, prompts)
    assert out == ref
    assert out[0] == out[1] == out[2]
    assert stats["prefix_hit_tokens"] > 0


def test_shared_prefix_prompts_match_uncached_engine():
    header = "system: you are a helpful polykey test fixture. "
    prompts = [header + tail for tail in ("alpha", "beta", "gamma delta")]
    ref, _ = _serve(dataclasses.replace(CFG, prefix_cache=False), prompts)
    out, stats = _serve(CFG, prompts)
    assert out == ref
    assert stats["prefix_hit_tokens"] > 0


def test_prefix_of_each_other_prompts_match():
    base = "incremental prompt growth check 0123456789"
    prompts = [base[:20], base[:33], base]      # each extends the last
    ref, _ = _serve(dataclasses.replace(CFG, prefix_cache=False), prompts)
    out, _ = _serve(CFG, prompts)
    assert out == ref


def test_pages_accounted_after_idle():
    eng = InferenceEngine(CFG)
    try:
        for i in range(6):
            r = GenRequest(
                prompt=f"shared head for accounting {i % 2}",
                max_new_tokens=6,
            )
            eng.submit(r)
            _collect(r)
        deadline = time.monotonic() + 10
        while eng.busy and time.monotonic() < deadline:
            time.sleep(0.05)
        stats = eng.stats()
        # Every page is either free or held by the cache (page 0 reserved).
        assert (
            stats["pages_free"] + stats["prefix_cache_pages"]
            == CFG.num_pages - 1
        )
    finally:
        eng.shutdown()


def test_eviction_under_pool_pressure_serves_everything():
    tight = dataclasses.replace(
        CFG, num_pages=20, max_seq_len=64, prefix_cache_pages=64
    )
    outs, stats = _serve(
        tight, [f"pressure prompt number {i} padded out a bit" for i in range(8)]
    )
    assert all(len(t) >= 1 for t in outs)


def test_spec_engine_with_prefix_cache_matches_uncached():
    """Spec + prefix cache compose: spec prefill writes BOTH pools for
    every window, so cached pages carry target and draft prefix KV; a
    cached spec engine must reproduce the uncached spec engine's greedy
    streams (which themselves equal the plain engine's — test_engine_spec)."""
    spec_cfg = dataclasses.replace(
        CFG, draft_model="tiny-llama", spec_gamma=3, prefix_cache=False
    )
    header = "spec shared header for cache composition. "
    prompts = [header + t for t in ("one", "two", "three and longer")]
    ref, _ = _serve(spec_cfg, prompts)
    out, stats = _serve(
        dataclasses.replace(spec_cfg, prefix_cache=True), prompts
    )
    assert out == ref
    assert stats["prefix_hit_tokens"] > 0


def test_spec_prefix_hit_long_suffix_chunks():
    """A cache hit whose suffix exceeds the largest bucket chunk-prefills
    from the offset through the spec path."""
    spec_cfg = dataclasses.replace(
        CFG, draft_model="tiny-llama", spec_gamma=3, prefix_cache=True,
        max_seq_len=256, num_pages=256,
    )
    header = "h" * 24
    prompts = [header + "first tail", header + "x" * 60]
    ref, _ = _serve(
        dataclasses.replace(spec_cfg, prefix_cache=False), prompts
    )
    out, _ = _serve(spec_cfg, prompts)
    assert out == ref


def test_int8_kv_prefix_hit_matches_uncached():
    """Prefix caching with int8 KV pools: cached pages hold quantized
    values + scales in parallel pools indexed by the same page ids, so a
    warm hit must reproduce the uncached int8-KV engine's tokens
    exactly (int8-KV vs int8-KV — the quantization is deterministic)."""
    cfg_q = dataclasses.replace(CFG, kv_dtype="int8")
    prompts = ["the same long-ish prompt body repeated", ] * 3
    ref, _ = _serve(
        dataclasses.replace(cfg_q, prefix_cache=False), prompts)
    out, stats = _serve(cfg_q, prompts)
    assert out == ref
    assert out[0] == out[1] == out[2]
    assert stats["prefix_hit_tokens"] > 0
