"""Speculative-decode acceptance curves on CPU (VERDICT r4 #7).

Spec-decode quality was structural, not empirical: tests assert the
machinery (exact greedy equality, rejection sampling) but no measured
acceptance-rate curve existed anywhere, so BASELINE config 5's speedup
was unquantified. This sweep measures acceptance alpha as a function of
(gamma, temperature) for a genuinely CORRELATED target/draft pair and
writes perf/spec_acceptance.json (+ a markdown table to stdout) — the
pre-registered prediction PERF.md cites before hardware measures it.

Method: random-init pairs have uncorrelated predictions (alpha ~ 1/vocab
— a degenerate curve), so both models are TRAINED on the same synthetic
order-2 Markov byte corpus (train/train.py's real train step). The draft
is a quarter-width single-layer model of the same family: it learns the
corpus's low-order structure, the target learns more — the same shape as
a production 1B-draft/8B-target pair. Acceptance comes from the engine's
own spec counters (metrics.on_spec via engine.stats()), i.e. the exact
serving path phase C runs on hardware.

Run:  JAX_PLATFORMS=cpu python scripts/spec_acceptance_sweep.py
Env:  SWEEP_TRAIN_STEPS (default 400), SWEEP_REQUESTS (default 8),
      SWEEP_MAX_NEW (default 48), SWEEP_GAMMAS, SWEEP_TEMPS.
"""

import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir))

import jax
import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_corpus_sampler(seed: int = 0):
    """Order-2 Markov chain over 26 lowercase letters with PEAKED rows
    (mean top transition prob ≈ 0.83 at scale 4.0): enough structure
    that a 1-layer model learns most of it and a 2-layer model learns
    more — the gap IS the acceptance curve's subject. At scale 2.0 the
    rows were too flat: neither model's argmax converged to the chain's
    mode in a few hundred steps and greedy agreement sat below 0.1,
    measuring training noise instead of the draft/target capacity gap."""
    rng = np.random.default_rng(seed)
    k = 26
    logits = rng.gumbel(size=(k, k, k)) * 4.0
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)

    def sample(n: int, rng: np.random.Generator) -> str:
        out = list(rng.integers(0, k, 2))
        for _ in range(n - 2):
            p = probs[out[-2], out[-1]]
            out.append(rng.choice(k, p=p))
        return "".join(chr(97 + c) for c in out)

    return sample


def train_model(cfg, corpus_fn, steps: int, seed: int) -> dict:
    """Train `cfg` on the corpus with the framework's real train step
    (single-device mesh); returns host params (float32)."""
    import jax.numpy as jnp

    from polykey_tpu.engine.tokenizer import ByteTokenizer
    from polykey_tpu.models.transformer import init_params
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh
    from polykey_tpu.train.train import make_train_step

    import optax

    tok = ByteTokenizer()
    mesh = create_mesh(MeshConfig(), jax.devices()[:1])
    # make_train_step's default LR (1e-4) is sized for real pretraining
    # runs; at tiny-model scale it leaves the pair at ~3.5 nats after
    # 300 steps — far off the corpus's ~1 nat — and argmax agreement
    # measures init noise. 3e-3 converges both models onto the chain's
    # modes (target ≈0.7 nats, draft ≈1.0) in the same step budget.
    init_state, train_step, shard_batch = make_train_step(
        cfg, mesh,
        optimizer=optax.adamw(learning_rate=3e-3, weight_decay=0.01),
    )
    params = init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    state = init_state(params)

    rng = np.random.default_rng(seed + 1)
    B, T = 16, 64
    first = last = None
    for step in range(steps):
        batch = np.stack([
            np.asarray(tok.encode(corpus_fn(T + 1, rng)))[: T + 1]
            for _ in range(B)
        ])
        tokens, targets = batch[:, :-1], batch[:, 1:]
        positions = np.broadcast_to(np.arange(T), (B, T))
        state, loss = train_step(
            state, *shard_batch(tokens, targets, positions))
        loss = float(loss)
        first = first if first is not None else loss
        last = loss
        if step % 100 == 0:
            log(f"  [{cfg.name}] step {step}: loss {loss:.4f}")
    log(f"  [{cfg.name}] trained {steps} steps: {first:.4f} -> {last:.4f}")
    assert last < first, "training did not reduce loss"
    return jax.device_get(state.params)


def serve(config, params, draft_params, prompts, max_new, temperature,
          sample_gamma: bool = False):
    """Serve prompts on a fresh engine; returns (stats, tok_s). With
    sample_gamma, the per-lane gamma dial (stats spec_gamma_mean) is
    sampled on every received token while lanes are LIVE — a drained
    engine resets the dials optimistic, so the end-of-run snapshot
    cannot see where the dial actually sat (ISSUE 19); the mean of the
    live samples can. Reported as stats['spec_gamma_dial_mean']."""
    from polykey_tpu.engine.engine import GenRequest, InferenceEngine

    eng = InferenceEngine(config, params=params, draft_params=draft_params)
    try:
        # Warm request OUTSIDE the timed window: compile_warmup is off
        # (dozens of tiny-engine configs in one sweep), so without this
        # every config's dt is dominated by its own XLA compiles and the
        # tok/s column measures the compiler, not serving.
        warm = GenRequest(prompt=prompts[0], max_new_tokens=4,
                          temperature=temperature,
                          top_p=0.95 if temperature > 0 else 1.0)
        eng.submit(warm)
        while warm.out.get(timeout=600.0)[0] == "token":
            pass
        reqs = [
            GenRequest(prompt=p, max_new_tokens=max_new,
                       temperature=temperature,
                       top_p=0.95 if temperature > 0 else 1.0)
            for p in prompts
        ]
        t0 = time.monotonic()
        for r in reqs:
            eng.submit(r)
        total = 0
        gamma_samples = []
        for r in reqs:
            while True:
                kind, value = r.out.get(timeout=600.0)
                if kind == "done":
                    total += value.completion_tokens
                    break
                if kind == "error":
                    raise RuntimeError(value)
                if sample_gamma:
                    g = eng.stats().get("spec_gamma_mean")
                    if g is not None:
                        gamma_samples.append(g)
        dt = time.monotonic() - t0
        stats = eng.stats()
        if sample_gamma:
            stats["spec_gamma_dial_mean"] = (
                round(float(np.mean(gamma_samples)), 3)
                if gamma_samples else None)
        return stats, total / dt
    finally:
        eng.shutdown()


def prepare_trained_pair(steps: int):
    """Register `tiny-llama-draft` and train the correlated target/draft
    pair on the Markov corpus. Returns
    (target_cfg, draft_cfg, target_params, draft_params, corpus_fn)."""
    from polykey_tpu.models.config import MODEL_REGISTRY, TINY_LLAMA

    target_cfg = TINY_LLAMA
    draft_cfg = dataclasses.replace(
        TINY_LLAMA, name="tiny-llama-draft",
        num_layers=1, num_heads=2, num_kv_heads=1,
        hidden_size=32, intermediate_size=64,
    )
    MODEL_REGISTRY["tiny-llama-draft"] = draft_cfg

    corpus = make_corpus_sampler()
    log(f"training target ({target_cfg.name}) and draft "
        f"({draft_cfg.name}) on the Markov corpus, {steps} steps each...")
    target_params = train_model(target_cfg, corpus, steps, seed=3)
    draft_params = train_model(draft_cfg, corpus, steps, seed=5)
    return target_cfg, draft_cfg, target_params, draft_params, corpus


def main() -> None:
    from polykey_tpu.engine.config import EngineConfig

    steps = int(os.environ.get("SWEEP_TRAIN_STEPS", "400"))
    n_req = int(os.environ.get("SWEEP_REQUESTS", "8"))
    max_new = int(os.environ.get("SWEEP_MAX_NEW", "48"))
    gammas = [int(g) for g in os.environ.get(
        "SWEEP_GAMMAS", "2,4,8").split(",")]
    temps = [float(t) for t in os.environ.get(
        "SWEEP_TEMPS", "0.0,0.5,1.0").split(",")]

    (target_cfg, draft_cfg, target_params, draft_params,
     corpus) = prepare_trained_pair(steps)

    prompt_rng = np.random.default_rng(17)
    prompts = [corpus(48, prompt_rng) for _ in range(n_req)]

    base = EngineConfig(
        model="tiny-llama",
        tokenizer="byte",
        dtype="float32",
        max_decode_slots=4,
        page_size=8,
        num_pages=128,
        max_seq_len=128,
        prefill_buckets=(64,),
        max_new_tokens_cap=max_new,
        compile_warmup=False,
        # Without the top-k prefilter, spec engines route any top_p<1
        # batch through the PLAIN decode step (engine._dispatch_step's
        # all_untruncated gate) — the sampled-temperature rows would
        # measure the fallback and report alpha=None. 32 candidates at a
        # 259-vocab byte model keeps truncated rejection sampling exact
        # in practice while exercising the REAL spec serving path.
        top_p_candidates=32,
    )

    results = {"train_steps": steps, "requests": n_req, "max_new": max_new,
               "target": target_cfg.name, "draft": draft_cfg.name,
               "draft_param_frac": round(
                   draft_cfg.num_params() / target_cfg.num_params(), 4),
               "plain": {}, "sweep": []}

    # Unrounded plain rates for the speedup division; the artifact keeps
    # the rounded display value. Dividing by the rounded figure loses a
    # pathologically slow host's whole sweep to round(0.04, 1) == 0.0
    # (ADVICE r5).
    plain_raw: dict[str, float] = {}
    for temp in temps:
        _, tok_s = serve(base, target_params, None, prompts, max_new, temp)
        plain_raw[str(temp)] = tok_s
        results["plain"][str(temp)] = {"tok_s": round(tok_s, 1)}
        log(f"plain T={temp}: {tok_s:.1f} tok/s")

    for gamma in gammas:
        for temp in temps:
            cfg = dataclasses.replace(
                base, draft_model="tiny-llama-draft", spec_gamma=gamma,
                adaptive_gamma=False)
            stats, tok_s = serve(
                cfg, target_params, draft_params, prompts, max_new, temp)
            alpha = stats.get("spec_acceptance")
            plain_tok_s = plain_raw[str(temp)]
            entry = {
                "gamma": gamma,
                "temperature": temp,
                "acceptance": alpha,
                "tok_s": round(tok_s, 1),
                "cpu_speedup_vs_plain": (
                    round(tok_s / plain_tok_s, 3)
                    if plain_tok_s > 0 else None
                ),
                "drafts_proposed": stats.get("drafts_proposed"),
                "drafts_accepted": stats.get("drafts_accepted"),
            }
            # Expected accepted tokens per round from measured alpha,
            # modeling per-position acceptance as iid Bernoulli(alpha):
            # E = (1-a^(g+1))/(1-a) (counts the bonus token). On hardware
            # the speedup is E / (g*c + 1) with c = draft/target step
            # cost; c is chip-specific and pre-registered in PERF.md.
            if alpha is not None and alpha < 1.0:
                entry["expected_tokens_per_round"] = round(
                    (1 - alpha ** (gamma + 1)) / (1 - alpha), 3)
            # Per-lane dial leg (ISSUE 19): the same row under the
            # engine default adaptive_gamma=True — where each lane's
            # acceptance EWMA drives its own dial. The column is the
            # mean dial observed while lanes were live; at the alphas
            # this weak pair measures, it should sit near the LOW rung.
            acfg = dataclasses.replace(cfg, adaptive_gamma=True)
            astats, _ = serve(
                acfg, target_params, draft_params, prompts, max_new,
                temp, sample_gamma=True)
            entry["per_lane_gamma_mean"] = astats.get(
                "spec_gamma_dial_mean")
            entry["acceptance_per_lane"] = astats.get("spec_acceptance")
            results["sweep"].append(entry)
            speedup = entry["cpu_speedup_vs_plain"]
            log(f"gamma={gamma} T={temp}: alpha={alpha} "
                f"{tok_s:.1f} tok/s "
                f"({f'{speedup}x' if speedup is not None else 'n/a'}) "
                f"per-lane dial {entry['per_lane_gamma_mean']}")

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "perf", "spec_acceptance.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    log(f"wrote {out_path}")

    # Markdown table (PERF.md's source).
    print("| gamma | T | acceptance | E[tok/round] | per-lane γ̄ | "
          "CPU tok/s | vs plain |")
    print("|---|---|---|---|---|---|---|")
    for e in results["sweep"]:
        speedup = e["cpu_speedup_vs_plain"]
        print(f"| {e['gamma']} | {e['temperature']} | "
              f"{e['acceptance']} | "
              f"{e.get('expected_tokens_per_round', '—')} | "
              f"{e.get('per_lane_gamma_mean', '—')} | "
              f"{e['tok_s']} | "
              f"{f'{speedup}x' if speedup is not None else '—'} |")


if __name__ == "__main__":
    main()
