"""Ragged-path smoke (ISSUE 12): exercised on every commit.

Three fast gates, CPU-only:
1. KERNEL: the ragged Pallas kernel runs under interpret mode (the
   actual kernel body, not the gather fallback) and matches the
   per-token gather reference on a mixed prefill+decode stream — fp
   and int8-KV variants.
2. ENGINE: a tiny ragged engine serves a mixed burst (admissions,
   chunked long prompt, concurrent decode) with greedy streams
   BIT-IDENTICAL to the bucketed engine at the same seed.
3. ACCOUNTING: tokens_useful/tokens_dispatched is populated and sane
   in both modes (the soak's padding-waste ratio).

Exit nonzero on any mismatch — `make ragged-smoke`, wired into
ci-check and CI.
"""

import dataclasses
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def kernel_smoke() -> None:
    import jax.numpy as jnp

    from polykey_tpu.ops.paged_attention import quantize_kv_rows
    from polykey_tpu.ops.ragged_paged_attention_kernel import (
        ragged_gather_attention,
        ragged_paged_attention,
    )

    rng = np.random.default_rng(0)
    N, ps, Hk, Hq, D, P = 32, 8, 2, 4, 32, 8
    seq_lens = np.array([1, 11, 1, 5], np.int32)
    kv_lens = np.array([37, 20, 5, 48], np.int32)
    starts = np.concatenate([[0], np.cumsum(seq_lens)[:-1]]).astype(np.int32)
    T = 24
    # Pools in the stored layout (engine/kv_cache.py): heads folded.
    kp = jnp.asarray(rng.normal(size=(N, ps, Hk * D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, Hk * D)), jnp.float32)
    tables = rng.integers(1, N, size=(4, P)).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
    rows = np.arange(T)
    sid = np.clip(np.searchsorted(starts, rows, side="right") - 1, 0, 3)
    in_seq = (rows >= starts[sid]) & (rows < starts[sid] + seq_lens[sid])
    pos = np.where(
        in_seq, kv_lens[sid] - seq_lens[sid] + rows - starts[sid], 0
    )
    tok_tables = np.where(in_seq[:, None], tables[sid], 0)

    out_k = ragged_paged_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(seq_lens), jnp.asarray(kv_lens),
        scale=0.125, logit_softcap=30.0, window=jnp.int32(24),
        interpret=True,
    )
    out_g = ragged_gather_attention(
        q, kp, vp, jnp.asarray(tok_tables), jnp.asarray(pos),
        scale=0.125, logit_softcap=30.0, window=jnp.int32(24),
    )
    err = float(np.abs(np.asarray(out_k) - np.asarray(out_g))[in_seq].max())
    assert err < 2e-5, f"ragged kernel vs gather: max err {err}"
    log(f"kernel fp parity OK (max err {err:.2e})")

    k8, ks = quantize_kv_rows(kp.reshape(N, ps, Hk, D))
    v8, vs = quantize_kv_rows(vp.reshape(N, ps, Hk, D))
    k8, v8 = k8.reshape(kp.shape), v8.reshape(vp.shape)
    out_q = ragged_paged_attention(
        q, (k8, ks), (v8, vs), jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(seq_lens), jnp.asarray(kv_lens),
        scale=0.125, interpret=True,
    )
    out_qg = ragged_gather_attention(
        q, (k8, ks), (v8, vs), jnp.asarray(tok_tables), jnp.asarray(pos),
        scale=0.125,
    )
    qerr = float(np.abs(np.asarray(out_q) - np.asarray(out_qg))[in_seq].max())
    assert qerr < 2e-5, f"int8 ragged kernel vs int8 gather: max err {qerr}"
    log(f"kernel int8 parity OK (max err {qerr:.2e})")


def _serve(config, specs, seed=0):
    from polykey_tpu.engine.engine import GenRequest, InferenceEngine

    engine = InferenceEngine(config, seed=seed)
    try:
        requests = [GenRequest(**s) for s in specs]
        for r in requests:
            engine.submit(r)
        outs = []
        for r in requests:
            tokens = []
            deadline = time.monotonic() + 120
            while True:
                kind, value = r.out.get(timeout=deadline - time.monotonic())
                if kind == "token":
                    tokens.append(value)
                elif kind == "done":
                    break
                else:
                    raise RuntimeError(f"request failed: {value}")
            outs.append(tokens)
        stats = engine.stats()
    finally:
        engine.shutdown()
    return outs, stats


def engine_smoke() -> None:
    from polykey_tpu.engine.config import EngineConfig

    base = EngineConfig(
        model="tiny-llama", tokenizer="byte", dtype="float32",
        max_decode_slots=4, page_size=8, num_pages=64, max_seq_len=64,
        prefill_buckets=(16, 32), max_new_tokens_cap=16,
        decode_block_steps=4, lookahead_blocks=2,
        compile_warmup=False, supervise=False, signals_interval_s=0,
    )
    specs = [
        dict(prompt="hi", max_new_tokens=8, seed=11),
        dict(prompt="abcdefgh" * 2, max_new_tokens=8, seed=11),
        dict(prompt="abcdefgh" * 6, max_new_tokens=8, seed=11),  # chunked
        dict(prompt="xyz", max_new_tokens=8, seed=11),
    ]
    bucketed, bstats = _serve(base, specs)
    ragged, rstats = _serve(
        dataclasses.replace(base, ragged_dispatch=True), specs
    )
    assert ragged == bucketed, (
        f"greedy streams diverged:\nbucketed={bucketed}\nragged={ragged}"
    )
    log("engine greedy bit-identity OK (4 streams, chunked incl.)")
    for name, stats in (("bucketed", bstats), ("ragged", rstats)):
        frac = stats["tokens_useful_fraction"]
        assert frac is not None and 0.0 < frac <= 1.0, (name, frac)
        log(f"{name}: tokens_useful/dispatched = {frac}")
    assert rstats["ragged"] is True


def main() -> int:
    kernel_smoke()
    engine_smoke()
    log("ragged-smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
