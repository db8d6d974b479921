"""The plain reference of both configurations, and the comparison with it.

The forward pass of a Llama-style decoder (RMSNorm, rotary embedding in the
rotate-half convention, grouped-query causal attention, SwiGLU) and of its
Mixtral variant (top-k routed experts, gates softmaxed over the k chosen)
in straightforward jax.numpy: float32, matmul precision "highest", no
kernel, no cache, no batching, one layer at a time so that a 7 B or 47 B
int8 tree never has to exist in float32. It reads the served tree only
through `f32()` below (an int8 weight is q * scale per output channel),
and imports nothing from the package.

Departures from the published models: none in the mathematics. The MoE
reference computes every chosen expert for every token (no capacity, no
drops); the sample is sized so the package's capacity-bucketed dispatch
drops nothing either (perfbench/run.py SAMPLE_*).

The comparison is on logits, not on sampled tokens (random weights: the
largest logit changes on rounding): the reference is teacher-forced with
the served tokens, and at every generated position the served token's
reference logit must be within `max_margin` of the reference's best logit
over the ids the narrowed head allows, and at least `min_exact_share` of
the tokens must be the reference's exact argmax. Both limits are the
configuration file's ("reference" group), with the measurements behind
them in PERF.md section 4. Logits here have a standard deviation of about
1 and the best candidates lie ~0.3 apart, so a path that dropped a layer,
a head or the rotary embedding picks among 95 ids at random: margins of
2-3 and an exact share near 1%. The dense model is held to 0.25 (measured
0.0, all tokens exact): half a mantissa less would fail it. The MoE model
cannot be held that tightly: with random weights a router's top-2 choice
flips on bf16 rounding in some of the 32 x 56 (layer, token) decisions,
and each flip swaps an expert's whole output (measured margins up to 0.42,
9-13 of 16 exact).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np



def f32(w):
    """A served weight in float32: int8 q times its per-column scale."""
    if hasattr(w, "q"):
        if getattr(w, "bits", 8) != 8:
            raise ValueError("the reference reads int8 or float weights only")
        return w.q.astype(jnp.float32) * w.s[..., None, :].astype(jnp.float32)
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rotary(x, positions, theta):
    """x [T, heads, dim]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(h, p):
    return (jax.nn.silu(h @ f32(p["gate"])) * (h @ f32(p["up"]))) @ f32(p["down"])


def layer(x, p, dims):
    """One decoder block on x [T, hidden]."""
    heads, kv_heads, head_dim, theta, eps, top_k = dims
    T = x.shape[0]
    positions = jnp.arange(T)
    h = rms_norm(x, p["ln1"], eps)
    a = p["attn"]
    q = rotary((h @ f32(a["wq"])).reshape(T, heads, head_dim), positions, theta)
    k = rotary((h @ f32(a["wk"])).reshape(T, kv_heads, head_dim), positions, theta)
    v = (h @ f32(a["wv"])).reshape(T, kv_heads, head_dim)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(T, heads * head_dim) @ f32(a["wo"])
    h = rms_norm(x, p["ln2"], eps)
    if "experts" not in p:
        return x + swiglu(h, p["mlp"])
    logits = h @ p["router"].astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)
    mixed = jnp.zeros_like(h)
    for e in range(logits.shape[-1]):
        expert = jax.tree.map(lambda w: w[e], p["experts"])
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        mixed = mixed + weight[:, None] * swiglu(h, expert)
    return x + mixed


def forward(params, cfg, tokens) -> np.ndarray:
    """Float32 logits [T, vocab] for one sequence of token ids."""
    dims = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            float(cfg.rope_theta), float(cfg.rms_norm_eps),
            int(cfg.num_experts_per_tok))
    step = jax.jit(layer, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: f32(e)[t])(params["embed"], np.asarray(tokens))
        for i in range(cfg.num_layers):
            x = step(x, jax.tree.map(lambda w: w[i], params["layers"]), dims)
        logits = jax.jit(
            lambda x, norm, head: rms_norm(x, norm, dims[4]) @ f32(head)
        )(x, params["final_norm"], params["lm_head"])
    return np.asarray(logits)


def compare(params, cfg, sample: dict, limits: dict) -> dict:
    """Teacher-force the reference with the served tokens; see module doc.
    `limits` is the configuration file's "reference" group."""
    tolerance, min_exact = limits["max_margin"], limits["min_exact_share"]
    prompt, served = sample["prompt_ids"], sample["output_ids"]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    logits = forward(params, cfg, prompt + served[:-1])
    rows = logits[len(prompt) - 1:]
    margins, exact = [], 0
    for row, token in zip(rows, served):
        best = float(np.max(np.where(allowed, row, -np.inf)))
        margins.append(best - float(row[token]))
        exact += int(margins[-1] <= 0.0)
    return {
        "ok": bool(max(margins) <= tolerance
                   and exact >= min_exact * len(served)
                   and all(allowed[t] for t in served)),
        "tokens": len(served),
        "exact": exact,
        "max_margin": max(margins),
        "mean_margin": float(np.mean(margins)),
        "tolerance": tolerance,
        "logit_std": float(np.std(rows[:, allowed])),
    }
