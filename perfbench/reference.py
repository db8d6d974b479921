"""The plain reference of both configurations, and the comparison with it.

(A configuration of another architecture brings its own `forward` as
perfbench/references/<file>.py, named in its "reference" group; `compare`
and `judge` below decide for it too. Contract: docstring of perfbench/run.py.)

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
the served tokens, and every generated position yields one margin, the
reference's best logit over the ids the narrowed head allows minus the
served token's reference logit (0 where the served token is the
reference's argmax). `judge` decides from those margins alone, by four
clauses whose limits are the configuration file's ("reference" group; the
readings behind them are in PERF.md section 4):

- at most `max_outliers` tokens (absent: 0) have a margin over `max_margin`;
- the mean margin is at most `max_mean_margin` (absent: no limit);
- at least `min_exact_share` of the tokens are the reference's exact argmax;
- every served id lies inside the narrowed head.

Logits here have a standard deviation of about 1 and the best candidates
lie ~0.3 apart. The dense model is held to 0.25 with no outlier allowed
(measured: 28-32 of 32 exact, largest margin 0.051): half a mantissa less
would fail it. The MoE model cannot be held by its LARGEST margin: with
random weights a router's top-2 choice flips on bf16 rounding near a tie in
some of the 32 x 56 (layer, token) decisions, each flip swaps an expert's
whole output, and the one token it lands on can read a margin of the order
of the logits' spread (measured over 60+ seeds: 17-32 of 32 exact, mean
margin up to 0.125, largest single margin 0.95). That is sound behaviour of
bf16 serving against a float32 reference, and it touches a token or two. So
Mixtral's file allows two outliers over 1.0 and holds the mean, which one
flipped token moves by 1/32 of its margin and an error on every token moves
whole.

What the 32 tokens can see was measured with controls laid over this
reference at the cell's own size, on six seeds
(tests/perfbench/reference_controls.py; readings in PERF.md section 4).
Refused on every seed, by this rule as by the one before: another seed's
tree (17-32 of 32 margins over 1.0, mean 1.6-3.4, at most 3 exact),
attention switched off or one KV head zeroed in every layer (13-32
outliers, mean 0.95-3.6), one expert per token instead of two (mean
0.33-1.4, at most 12 exact), int4 weights (mean 0.27-0.74). NOT seen on
most seeds, by either rule: a fault in ONE of the 32 layers (the layer
skipped, one KV head zeroed there), the rotary embedding off, every
expert's output scaled by 1.02. Random weights attend almost evenly, one
layer is a thirty-second of the residual stream, and the served path's own
bf16 and routing noise is as large: their readings lie inside the sound
range, and a limit on the largest margin refused them only on the seeds
where one token happened to pass 1.0, as it refused sound runs.
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


def judge(margins: list, outside_head: int, limits: dict) -> dict:
    """The verdict on one served sample from its per-token margins and the
    count of served ids outside the narrowed head; see the module text.
    `limits` is the configuration file's "reference" group. `why` names
    each clause that failed, in words, and is empty when `ok`."""
    threshold = limits["max_margin"]
    max_outliers = limits.get("max_outliers", 0)
    max_mean = limits.get("max_mean_margin")
    min_exact = limits["min_exact_share"] * len(margins)
    outliers = sum(m > threshold for m in margins)
    exact = sum(m <= 0.0 for m in margins)
    mean = float(np.mean(margins))
    # Each number compared beside its limit; a clause that failed -> `why`.
    clauses = [
        (outliers <= max_outliers,
         f"outliers {outliers} (limit {max_outliers}, margins over "
         f"{threshold:g}; largest {max(margins):.4g})"),
        (max_mean is None or mean <= max_mean,
         f"mean_margin {mean:.4g} (limit {max_mean})"),
        (exact >= min_exact,
         f"exact {exact} (at least {min_exact:g} of {len(margins)})"),
        (outside_head == 0, f"outside_head {outside_head} (limit 0)"),
    ]
    why = [text for held, text in clauses if not held]
    return {
        "ok": not why,
        "why": why,
        "checks": ", ".join(text for _, text in clauses),
        "tokens": len(margins),
        "exact": exact,
        "outliers": outliers,
        "max_outliers": max_outliers,
        "max_margin": max(margins),
        "tolerance": threshold,
        "mean_margin": mean,
        "max_mean_margin": max_mean,
        "outside_head": outside_head,
        "margins": margins,
    }


def sampled_margins(rows, served: list, allowed, top_k: int) -> list:
    """Per served token of a request SAMPLED under `top_k`: how far its
    reference logit lies below the reference's `top_k`-th largest over the
    ids the narrowed head allows (0 where it is among them). The greedy
    margin above is this number at `top_k` 1."""
    margins = []
    for row, token in zip(rows, served):
        kth = float(np.sort(row[allowed])[-top_k])
        margins.append(max(0.0, kth - float(row[token])))
    return margins


def judge_sampled(forward_fn, params, cfg, sample: dict, limits: dict) -> dict:
    """The verdict on the sampled companion of the served sample
    (`sample["sampled"]`: its ids and the `top_k` it was drawn under; a
    window of sampled requests runs the sampled variant of every step, and
    a greedy token cannot show its truncation or its draw): the reference
    teacher-forced with the companion's tokens, `sampled_margins`, and the
    same limits as `judge` holds a greedy margin to — a served token that
    the truncation should have cut reads the distance to the cut, an id
    outside the narrowed head is counted. No clause on the exact share: a
    draw is not an argmax."""
    part = sample["sampled"]
    prompt, served = part["prompt_ids"], part["output_ids"]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    rows = forward_fn(params, cfg, prompt + served[:-1])[len(prompt) - 1:]
    margins = sampled_margins(rows, served, allowed, int(part["top_k"]))
    threshold = limits["max_margin"]
    max_outliers = limits.get("max_outliers", 0)
    max_mean = limits.get("max_mean_margin")
    outliers = sum(m > threshold for m in margins)
    mean = float(np.mean(margins))
    outside = sum(not allowed[t] for t in served)
    clauses = [
        (outliers <= max_outliers,
         f"sampled_outliers {outliers} (limit {max_outliers}, margins under "
         f"the top-{part['top_k']} cut over {threshold:g}; largest "
         f"{max(margins):.4g})"),
        (max_mean is None or mean <= max_mean,
         f"sampled_mean_margin {mean:.4g} (limit {max_mean})"),
        (outside == 0, f"sampled_outside_head {outside} (limit 0)"),
    ]
    why = [text for held, text in clauses if not held]
    return {"ok": not why, "why": why,
            "checks": ", ".join(text for _, text in clauses),
            "margins": margins, "tokens": len(margins)}


def compare(params, cfg, sample: dict, limits: dict, forward_fn=None) -> dict:
    """Teacher-force the reference with the served tokens and judge the
    margins; see module doc. `forward_fn`: the `forward` of the
    configuration's own reference module (absent: the one above)."""
    prompt, served = sample["prompt_ids"], sample["output_ids"]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    logits = (forward_fn or forward)(params, cfg, prompt + served[:-1])
    rows = logits[len(prompt) - 1:]
    margins = [float(np.max(np.where(allowed, row, -np.inf))) - float(row[token])
               for row, token in zip(rows, served)]
    outside_head = sum(not allowed[t] for t in served)
    return {**judge(margins, outside_head, limits),
            "logit_std": float(np.std(rows[:, allowed]))}
