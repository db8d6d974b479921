"""Speculative-path smoke (ISSUE 19): exercised on every commit.

Three fast gates, CPU-only:
1. ACCEPT/MERGE: the fused device-resident accept/merge core
   (spec_decode._accept_merge — acceptance, bonus/residual draw, EOS/cap
   truncation, per-lane gamma dial) produces IDENTICAL packed rows and
   slot state jitted vs eager (`jax.disable_jit()`), over a batch mixing
   greedy and sampled rows, an inactive lane, a lane about to hit its
   cap, and mixed per-lane dials — both with and without the top-p
   truncation path (candidates 0 / 8). A numpy reference independently
   checks the greedy rows' acceptance/emit columns.
2. ENGINE: greedy streams are BIT-IDENTICAL between the plain and the
   speculative engine at the same seed, with a chunked long prompt in
   the mix.
3. ACCOUNTING: the spec engine actually speculated (drafts_proposed
   > 0) and exports the per-lane dial stats the autopilot reads.

Exit nonzero on any mismatch — `make spec-smoke`, wired into ci-check
and CI.
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


def accept_merge_smoke() -> None:
    import functools

    import jax.numpy as jnp

    from polykey_tpu.engine.spec_decode import _accept_merge, _lane_tagger

    B, gamma, V = 4, 4, 32
    gamma_low, gamma_max, eos_id = 2, 4, 31
    rng = np.random.default_rng(42)

    t_logits = rng.normal(size=(B, gamma + 1, V)).astype(np.float32)
    drafts = rng.integers(0, V - 1, size=(B, gamma)).astype(np.int32)
    # Row 0 (greedy): force full acceptance so the bonus path runs.
    t_logits[0] = -10.0
    for j in range(gamma):
        t_logits[0, j, drafts[0, j]] = 10.0
    # Row 3 (greedy): force rejection at position 1.
    t_logits[3] = -10.0
    t_logits[3, 0, drafts[3, 0]] = 10.0
    t_logits[3, 1, (drafts[3, 1] + 1) % V] = 10.0
    d_logits = rng.normal(size=(B, gamma, V)).astype(np.float32)
    d_dists = np.exp(d_logits)
    d_dists /= d_dists.sum(-1, keepdims=True)

    last_tokens = np.array([3, 7, 11, 2], np.int32)
    seq_lens = np.array([5, 9, 3, 7], np.int32)
    active = np.array([True, True, False, True])
    caps = np.array([64, 11, 64, 64], np.int32)      # row 1: near its cap
    accept_ewma = np.array([0.9, 0.5, 0.4, 0.2], np.float32)
    gamma_lane = np.array([4, 2, 4, 4], np.int32)    # mixed dials
    pos = np.maximum(seq_lens - 1, 0)
    greedy_row = np.array([True, False, True, True])
    temp = np.where(greedy_row, 1e-6, 0.8).astype(np.float32)
    top_p = np.where(greedy_row, 1.0, 0.9).astype(np.float32)
    top_k = np.zeros(B, np.int32)
    seeds = np.stack([np.arange(B, dtype=np.uint32),
                      np.full(B, 9, np.uint32)], axis=1)

    for candidates in (0, 8):
        def core(tl, dr, dd, lt, sl, ac, cp, ew, gl, ps, gr, tm, tp, tk, sd):
            return _accept_merge(
                tl, dr, dd, lt, sl, ac, cp, ew, gl, ps, gr, tm, tp, tk,
                _lane_tagger(sd), gamma=gamma, gamma_low=gamma_low,
                gamma_max=gamma_max, eos_id=eos_id, candidates=candidates,
            )

        args = (t_logits, drafts, d_dists, last_tokens, seq_lens, active,
                caps, accept_ewma, gamma_lane, pos, greedy_row, temp,
                top_p, top_k, seeds)
        jitted = [np.asarray(x) for x in jax.jit(core)(*args)]
        with jax.disable_jit():
            eager = [np.asarray(x) for x in core(*args)]

        names = ("packed", "new_last", "new_seq_lens", "new_active",
                 "new_ewma", "new_gamma_lane")
        for name, a, b in zip(names, jitted, eager):
            if name == "new_ewma":
                assert np.allclose(a, b, atol=1e-6), (candidates, name, a, b)
            else:
                assert np.array_equal(a, b), (candidates, name, a, b)

        packed, _, new_seq_lens, new_active = jitted[:4]
        emit = packed[:, : gamma + 1]
        # Numpy reference for the deterministic greedy rows.
        t_choice = t_logits.argmax(-1)
        # Row 0: all gamma drafts match -> gamma accepted + bonus argmax.
        assert list(emit[0, :gamma]) == list(drafts[0])
        assert emit[0, gamma] == t_choice[0, gamma]
        assert packed[0, gamma + 1] == gamma          # acc_rows
        assert packed[0, gamma + 2] == gamma          # prop_rows (dial 4)
        # Row 3: mismatch at draft 1 -> 1 accepted + target's correction.
        assert emit[3, 0] == drafts[3, 0]
        assert emit[3, 1] == t_choice[3, 1]
        assert list(emit[3, 2:]) == [-1, -1, -1]
        # Row 2 inactive: emits nothing, state frozen.
        assert list(emit[2]) == [-1] * (gamma + 1)
        assert new_seq_lens[2] == seq_lens[2] and not new_active[2]
        # Row 1: cap 11 at seq_len 9 -> at most 2 emitted, then stopped.
        n_out1 = int((emit[1] >= 0).sum())
        assert n_out1 <= 2 and new_seq_lens[1] <= caps[1]
        if new_seq_lens[1] == caps[1]:
            assert not new_active[1]
        # Dial column is the new gamma_lane, within the ladder.
        assert np.array_equal(packed[:, gamma + 4], jitted[5])
        assert np.all((jitted[5] >= gamma_low) & (jitted[5] <= gamma_max))
        log(f"accept/merge jit-vs-eager parity OK (candidates={candidates})")


def _serve(config, specs, depth=None, seed=0):
    from polykey_tpu.engine.engine import GenRequest, InferenceEngine

    if depth is not None:
        os.environ["POLYKEY_DISPATCH_LOOKAHEAD"] = str(depth)
    try:
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
                    kind, value = r.out.get(
                        timeout=deadline - time.monotonic())
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
    finally:
        os.environ.pop("POLYKEY_DISPATCH_LOOKAHEAD", None)
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
    # The seed+2-initialised draft is a BAD draft on purpose: greedy
    # bit-identity must hold for ANY draft model (acceptance only moves
    # throughput), and a bad draft exercises the rejection/correction
    # path far harder than a good one.
    spec = dataclasses.replace(base, draft_model="tiny-llama", spec_gamma=3)
    specs = [
        dict(prompt="hi", max_new_tokens=8, seed=11),
        dict(prompt="abcdefgh" * 2, max_new_tokens=8, seed=11),
        dict(prompt="abcdefgh" * 6, max_new_tokens=8, seed=11),  # chunked
        dict(prompt="xyz", max_new_tokens=8, seed=11),
    ]
    plain, _ = _serve(base, specs)
    for depth in (1, 2):
        streams, stats = _serve(spec, specs, depth=depth)
        assert streams == plain, (
            f"depth {depth}: spec diverged from plain:\n"
            f"plain={plain}\nspec={streams}"
        )
        assert stats["drafts_proposed"] > 0, (depth, stats)
        assert stats["spec_gamma"] >= 1, (depth, stats)
        log(f"depth {depth}: greedy bit-identity plain == spec OK "
            f"(proposed {stats['drafts_proposed']} drafts)")


def main() -> int:
    accept_merge_smoke()
    engine_smoke()
    log("spec-smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
