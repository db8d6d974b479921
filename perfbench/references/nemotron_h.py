"""The plain reference of a Nemotron-H style hybrid stack (the configuration
`nemotron-3-super-ep4`), as one chip of its expert-parallel group holds it.

float32, matmul precision "highest", one sequence, one layer at a time, no
kernel, no cache, no batching; the Mamba-2 layer is the token-by-token
recurrence, not the chunked form. The layers and `forward` import nothing
of the package and nothing of the harness; they read the served tree
(`params["layers"][kind]` a tuple of per-layer trees, bf16 leaves) and the
package's ModelConfig by attribute only. `compare`, at the end, is the
verdict on a served sample: it alone reaches for the harness's `judge`
and for the program's replayed logits (the configuration's adapter).
tests/reference_nemotron_h.py is the same file, for the package's own
tests (tests/test_hybrid.py holds the two byte-identical).

Layer l of kind pattern[l]: x <- x + f(RMSNorm_l(x)), one norm a layer.

  "M"  [z | xBC | dt] = W_in u;  xBC <- silu(causal depthwise conv_K(xBC) + b)
       x [H, P], B, C [G, N] (H/G heads share a group's B and C)
       D_t = softplus(dt_t + dt_bias),  A = -exp(A_log)
       h_t = exp(D_t A) h_{t-1} + D_t x_t (x) B_t;   y_t = h_t C_t + D x_t
       out = W_out RMSNorm_grouped(y . silu(z))          (G groups, gain)
  "*"  GQA, causal, scale head_dim^-1/2, no bias, no position embedding
       (rotary only where cfg.use_rope: the published family has none)
  "E"  s = sigmoid(W_r u); chosen = top-k of s + bias; w = s[chosen],
       w <- scale . w / sum_chosen w  (the sum over ALL chosen, held or not)
       v = W_fc1 u;  expert e: W_down,e relu(W_up,e v)^2
       out = W_fc2 sum_{chosen and held} w_e expert_e(v)
             + W_sd relu(W_su u)^2                      (shared, full hidden)
       Held: experts first_expert .. first_expert + experts_held - 1; what
       the absent ones would add is left out, as in the program.

Departures from the published model: none in the mathematics of the layers
above; the multi-token-prediction module is left out (it does not enter the
main model's logits).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def rotary(x, positions, theta):
    """x [T, heads, dim]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def history(xbc, taps):
    """The conv's input with the K-1 columns before the sequence: zeros."""
    return jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])


def recur(h, decay, add):
    """One step of the state: h_t from h_{t-1}."""
    return decay * h + add


def mamba_layer(x, p, cfg):
    T = x.shape[0]
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, taps = cfg.ssm_groups, cfg.ssm_state_size, cfg.conv_kernel
    inner, gn = H * P, G * N
    u = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    zxbcdt = u @ f32(p["w_in"])
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + inner + 2 * gn]
    dt = zxbcdt[:, inner + inner + 2 * gn:]
    ext = history(xbc, taps)
    w = f32(p["conv_w"])                                   # [K, C]
    xbc = jax.nn.silu(
        sum(ext[k:k + T] * w[k] for k in range(taps)) + f32(p["conv_b"]))
    xs = xbc[:, :inner].reshape(T, H, P)
    Bs = jnp.repeat(xbc[:, inner:inner + gn].reshape(T, G, N), H // G, axis=1)
    Cs = jnp.repeat(xbc[:, inner + gn:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))           # [T, H]
    A = -jnp.exp(f32(p["A_log"]))

    def step(h, inputs):
        x_t, B_t, C_t, dt_t = inputs
        decay = jnp.exp(dt_t * A)[:, None, None]
        add = (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        h = recur(h, decay, add)
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, Bs, Cs, dt))
    y = y + f32(p["D"])[:, None] * xs
    g = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    g = g * jax.lax.rsqrt(
        jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg.rms_norm_eps)
    return x + (g.reshape(T, inner) * f32(p["gate_norm"])) @ f32(p["w_out"])


def attention_layer(x, p, cfg):
    T = x.shape[0]
    heads, kv_heads, dim = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    positions = jnp.arange(T)
    u = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    q = (u @ f32(p["wq"])).reshape(T, heads, dim)
    k = (u @ f32(p["wk"])).reshape(T, kv_heads, dim)
    v = (u @ f32(p["wv"])).reshape(T, kv_heads, dim)
    if cfg.use_rope:
        q = rotary(q, positions, float(cfg.rope_theta))
        k = rotary(k, positions, float(cfg.rope_theta))
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * dim ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return x + attn.reshape(T, heads * dim) @ f32(p["wo"])


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def expert_layer(x, p, cfg):
    u = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    s = jax.nn.sigmoid(u @ f32(p["router"]))               # [T, routed]
    _, chosen = jax.lax.top_k(s + f32(p["router_bias"]),
                              cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg.routed_scaling_factor * w / jnp.sum(w, axis=-1, keepdims=True)
    v = u @ f32(p["fc1"])
    mixed = jnp.zeros_like(v)
    for e in range(cfg.experts_held):
        mine = jnp.sum(jnp.where(chosen == cfg.first_expert + e, w, 0.0), -1)
        mixed = mixed + mine[:, None] * (
            relu2(v @ f32(p["up"][e])) @ f32(p["down"][e]))
    shared = relu2(u @ f32(p["shared_up"])) @ f32(p["shared_down"])
    return x + mixed @ f32(p["fc2"]) + shared


LAYERS = {"mamba": mamba_layer, "attention": attention_layer,
          "moe": expert_layer}


def forward(params, cfg, tokens) -> np.ndarray:
    """Float32 logits [T, vocab] for one sequence of token ids."""
    seen = {kind: 0 for kind in LAYERS}
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: f32(e)[t])(params["embed"], np.asarray(tokens))
        for ch in cfg.layer_pattern:
            kind = KINDS[ch]
            p = params["layers"][kind][seen[kind]]
            seen[kind] += 1
            x = jax.jit(LAYERS[kind], static_argnums=2)(x, p, cfg)
        logits = jax.jit(
            lambda x, norm, head: rms_norm(x, norm, cfg.rms_norm_eps) @ f32(head)
        )(x, params["final_norm"], params["lm_head"])
    return np.asarray(logits)


def compare(params, cfg, sample: dict, limits: dict, replayed=None) -> dict:
    """The verdict on one served sample: the harness's margins and `judge`
    (perfbench/reference.py) on the reference teacher-forced with the
    served tokens, and three clauses more, because 32 served TOKENS cannot
    tell bf16 weights from int8 ones (a token says only which logit was
    largest; PERF.md section 6, PR 43). They compare LOGITS: the program's
    own for the same sample (the configuration's adapter `replay`: the
    prompt through the program's prefill form, the served tokens fed back
    through its decode step, on a pool and per-slot state of the engine's
    geometry) against the reference's, over the ids the narrowed head
    allows, each position centred, as |program - reference| / |reference|
    in percent, one number a position:

    - `logit_floor` <= `max_logit_floor`: the mean of the lowest eighth of
      the positions. A position reads the rounding of the served precision
      (0.6-0.9 % for bf16 against float32) unless one of its top-k routing
      choices fell the other way near a tie, which moves it, and through
      the state the positions after it, by 5-25 %: sound behaviour of a
      bf16 path, on some positions. The lowest eighth holds none of those
      and reads the arithmetic itself; a fault on every token (weights a
      precision lower, a layer computed wrongly) raises every position;
    - `logit_distance` <= `max_logit_distance`: the root mean square over
      all positions, which the routing flips dominate;
    - `replayed` >= `min_replayed_share` of the tokens: the replay's
      largest allowed logit IS the served token, so the logits compared
      are those the served tokens were chosen from.

    `limits["replay"]`: {"adapter", "lanes", "page_size", "window"};
    `replayed`: the program's logits where the caller already has them
    (tests/perfbench/nemotron_h_controls.py lays many faults over the
    reference beside one replay)."""
    import extension            # the harness's loader and judge: only here,
    import reference            # the layers above import nothing of either

    prompt, served = sample["prompt_ids"], sample["output_ids"]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    if replayed is None:
        how = dict(limits["replay"])
        adapter = extension.load("adapters", how.pop("adapter"))
        replayed = adapter.replay(params, cfg, prompt, served, **how)
    replayed = replayed[:, allowed]
    rows = forward(params, cfg, prompt + served[:-1])[len(prompt) - 1:]
    margins = [float(np.max(np.where(allowed, row, -np.inf))) - float(row[token])
               for row, token in zip(rows, served)]
    verdict = reference.judge(
        margins, sum(not allowed[t] for t in served), limits)
    rows = rows[:, allowed]
    want = rows - rows.mean(axis=1, keepdims=True)
    got = replayed - replayed.mean(axis=1, keepdims=True)
    apart = 100.0 * (np.sqrt(np.mean(np.square(got - want), axis=1))
                     / np.sqrt(np.mean(np.square(want), axis=1)))
    floor = float(np.mean(np.sort(apart)[:max(len(apart) // 8, 1)]))
    distance = float(np.sqrt(np.mean(np.square(apart))))
    ids = np.flatnonzero(allowed)[np.argmax(replayed, axis=1)]
    same = int(np.sum(ids == np.asarray(served)))
    least = limits["min_replayed_share"] * len(served)
    clauses = [
        (floor <= limits["max_logit_floor"],
         f"logit_floor {floor:.4g} % (limit {limits['max_logit_floor']:g})"),
        (distance <= limits["max_logit_distance"],
         f"logit_distance {distance:.4g} % (limit "
         f"{limits['max_logit_distance']:g})"),
        (same >= least,
         f"replayed {same} (at least {least:g} of {len(served)})"),
    ]
    why = verdict["why"] + [text for held, text in clauses if not held]
    return {
        **verdict, "ok": not why, "why": why,
        "checks": ", ".join([verdict["checks"]] + [t for _, t in clauses]),
        "logit_floor": floor, "logit_distance": distance,
        "logit_distance_by_token": [float(a) for a in apart],
        "replayed": same, "logit_std": float(np.std(rows)),
    }
