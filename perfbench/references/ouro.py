"""The plain reference of a looped decoder of the Ouro family (the
configuration `ouro-2.6b`): ONE stack of sandwich-normed multi-head layers
run `loop_steps` times a token with the same weights, the one final norm
closing every pass, an exit gate on every pass's output and the exit rule
over the passes choosing the hidden state the head reads.

float32, matmul precision "highest", one sequence, one layer's weights
upcast at a time (5.34 GB of bf16 weights stay beside it: the tree never
exists whole in float32), no kernel, no cache, no chunking, no batching:
every pass attends over the keys and values IT computed, whole. The layers
and `forward` import nothing of the package and nothing of the harness;
they read the served tree (`params["layers"]` stacked on a leading layer
axis, bf16 leaves) only through `f32()`, and the package's ModelConfig by
its sizes only (`loop_steps`, `early_exit_threshold`, heads, theta, eps) —
none of its switches: the sandwich block, the norm between the passes and
the exit rule are written out here. `compare`, at the end, is the verdict
on a served sample: it alone reaches for the harness (its loader and
`judge`). tests/reference_ouro.py is the same file, for the package's own
tests (tests/test_ouro.py holds the two byte-identical).

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, plain gains. A layer, four
gains, no bias anywhere, no q/k norm:

  a = x + N2(Attn(N1(x)))        y = a + N4(MLP(N3(a)))
  Attn: heads = KV heads (no grouping; a grouped tree repeats its KV heads),
        rotate-half RoPE at theta on q and k, causal, scale head_dim^-1/2
  MLP(h) = (silu(h Wg) . h Wu) Wd

The stack, U = loop_steps passes over the SAME L layers:

  h^0 = Embed(tokens);  h^{u+1} = Nf(Layer_{L-1}(... Layer_0(h^u)))
  — the ONE final norm Nf closes every pass, and the next pass reads its
  output. Pass u of layer l attends over its own keys and values (in a
  served cache: cache layer u L + l; a cache shared by the passes is
  another function).

The exit gate and rule, a position at a time:

  lambda_u = sigmoid(w_g . h^{u+1} + b_g)
  p_u = lambda_u prod_{j<u}(1 - lambda_j) for u < U - 1,
  p_{U-1} = prod_{j<U-1}(1 - lambda_j)
  exit = the first u whose cumulative sum_{j<=u} p_j >= threshold, else U - 1
  logits = Head(h^{exit+1})

All U passes always run; the rule only chooses which pass's hidden state
the head reads. `forward(..., threshold=)` takes the threshold as an
argument (absent: the ModelConfig's `early_exit_threshold`);
`forward_passes` returns every pass's logits, the gates and the exits.

Departures from the published modelling code: none in the mathematics.
Activations are float32 here (the published code holds them in bfloat16),
and the cumulative sum runs in float32.

`keep` marks what a layer hands on or a served cache stores — q, k, v, the
residual stream after each add, a closed pass — and is the identity here.
`compare` runs the reference a second time with `served` in its place (each
of those values rounded to bfloat16, nothing else changed): how far THAT
moves this seed's logits is the yardstick the program's own distance is
held to (see `compare`: a stack applied 192 times to its own output moves
by rounding alone, by an amount that differs tenfold from seed to seed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def keep(x):
    """A value as a layer hands it on: as computed, float32."""
    return x


def served(x):
    """`keep` of the bfloat16 twin: rounded as the served path stores it."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def post_norm(y, weight, eps):
    """N2 and N4: the norm on a body's OUTPUT, before the residual add."""
    return rms_norm(y, weight, eps)


def closing_norm(x, weight, eps, u, last):
    """Nf after pass `u` of 0..`last`: every pass, not the last alone."""
    return rms_norm(x, weight, eps)


def passes(cfg) -> int:
    return cfg.loop_steps


def own_cache(u, layer, k, v):
    """The keys and values pass `u` of `layer` attends over: the ones it
    just computed (a served cache keeps them in cache layer u L + layer)."""
    return k, v


def rotary(x, positions, theta):
    """x [T, heads, dim]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(x, p, dims):
    """N1, the three projections and the rotary embedding of one layer on
    x [T, hidden]: q [T, heads, dim], k and v [T, kv_heads, dim]."""
    heads, kv_heads, head_dim, theta, eps, keep = dims
    T = x.shape[0]
    positions = jnp.arange(T)
    h = rms_norm(x, p["ln1"], eps)
    a = p["attn"]
    q = rotary((h @ f32(a["wq"])).reshape(T, heads, head_dim), positions, theta)
    k = rotary((h @ f32(a["wk"])).reshape(T, kv_heads, head_dim), positions,
               theta)
    v = (h @ f32(a["wv"])).reshape(T, kv_heads, head_dim)
    return keep(q), keep(k), keep(v)


def finish(x, q, k, v, p, dims):
    """The rest of the layer: causal attention of q over (k, v), W_o, N2,
    the residual; N3, the gated MLP, N4, the residual."""
    heads, kv_heads, head_dim, theta, eps, keep = dims
    T = x.shape[0]
    positions = jnp.arange(T)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    mix = attn.reshape(T, heads * head_dim) @ f32(p["attn"]["wo"])
    a = keep(x + post_norm(mix, p["post_ln1"], eps))
    h = rms_norm(a, p["ln2"], eps)
    m = p["mlp"]
    out = (jax.nn.silu(h @ f32(m["gate"])) * (h @ f32(m["up"]))) @ f32(m["down"])
    return keep(a + post_norm(out, p["post_ln2"], eps))


def gate(x, p):
    """lambda = sigmoid(w_g . x + b_g) a position, x [T, hidden]."""
    return jax.nn.sigmoid((x @ f32(p["w"]))[:, 0] + f32(p["b"])[0])


def exit_steps(lambdas: np.ndarray, threshold: float) -> np.ndarray:
    """The exit rule on lambdas [U, T] (float32): the exit pass a position."""
    lambdas = np.asarray(lambdas, np.float32)
    last = lambdas.shape[0] - 1
    stay = np.ones(lambdas.shape[1], np.float32)
    pdf = []
    for u in range(last + 1):
        pdf.append(stay if u == last else lambdas[u] * stay)
        stay = stay * (np.float32(1.0) - lambdas[u])
    reached = np.cumsum(np.stack(pdf), axis=0, dtype=np.float32) >= np.float32(
        threshold)
    return np.where(reached.any(axis=0), np.argmax(reached, axis=0), last)


def forward_passes(params, cfg, tokens, threshold=None, keep=keep):
    """(logits [U, T, vocab] of every pass's output, lambdas [U, T],
    exits [T]) for one sequence of token ids, float32."""
    dims = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            float(cfg.rope_theta), float(cfg.rms_norm_eps), keep)
    eps = dims[4]
    first = jax.jit(project, static_argnums=2)
    rest = jax.jit(finish, static_argnums=5)
    close = jax.jit(closing_norm, static_argnums=(2, 3, 4))
    head = jax.jit(lambda x, w: x @ f32(w))
    last = passes(cfg) - 1
    logits, lambdas = [], []
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: f32(e)[t])(params["embed"], np.asarray(tokens))
        for u in range(last + 1):
            for i in range(cfg.num_layers):
                p = jax.tree.map(lambda w: w[i], params["layers"])
                q, k, v = first(x, p, dims)
                k, v = own_cache(u, i, k, v)
                x = rest(x, q, k, v, p, dims)
            x = keep(close(x, params["final_norm"], eps, u, last))
            lambdas.append(np.asarray(jax.jit(gate)(x, params["exit_gate"])))
            logits.append(np.asarray(head(x, params["lm_head"])))
    lambdas = np.stack(lambdas)
    if threshold is None:
        threshold = cfg.early_exit_threshold
    return np.stack(logits), lambdas, exit_steps(lambdas, threshold)


def forward(params, cfg, tokens, threshold=None, keep=keep) -> np.ndarray:
    """Float32 logits [T, vocab] for one sequence of token ids: each
    position's, from the pass the exit rule chose for it."""
    logits, _, exits = forward_passes(params, cfg, tokens, threshold, keep)
    return logits[exits, np.arange(logits.shape[1])]


def apart(got, want) -> np.ndarray:
    """How far `got` [T, ids] lies from `want`, one number a position:
    each position centred, |got - want| / |want| in percent."""
    want = want - want.mean(axis=1, keepdims=True)
    got = got - got.mean(axis=1, keepdims=True)
    return 100.0 * (np.sqrt(np.mean(np.square(got - want), axis=1))
                    / np.sqrt(np.mean(np.square(want), axis=1)))


def floor(by_token: np.ndarray) -> float:
    """The mean of the lowest eighth of the positions."""
    return float(np.mean(np.sort(by_token)[:max(len(by_token) // 8, 1)]))


def compare(params, cfg, sample: dict, limits: dict, replayed=None,
            twin=None) -> dict:
    """The verdict on one served sample: the harness's margins and `judge`
    (perfbench/reference.py) on the reference teacher-forced with the
    served tokens, and three clauses on LOGITS (32 served tokens cannot
    tell bf16 weights from int8 ones): the program's own logits for the
    sample (the `replay` of the adapter the limits name: the prompt
    through the program's prefill, the served tokens fed back through its
    decode step, over a pool of the engine's depth) against the
    reference's, over the ids the narrowed head allows, as `apart`.

    The sibling configurations hold that distance to a fixed percentage.
    Here no fixed percentage separates a sound bf16 program from a fault:
    the stack is applied to its own output 4 x 48 times, rounding alone
    moves the logits by 0.8 to 6 % (lowest eighth) from one seed to the
    next, and every fault scales with the same seed (int8 weights: 14 to
    164 %). So the yardstick is taken on the sample's own seed: the
    reference once more with `served` for `keep` (q, k, v, the residual
    stream and each closed pass rounded to bfloat16: `twin`), and

    - `logit_floor` <= `max_floor_ratio` x the twin's: the mean of the
      lowest eighth of the positions, which reads the arithmetic itself;
    - `logit_median` <= `max_median_ratio` x the twin's: the middle
      position (the root mean square is left to the record: one position
      that rounding sent another way carries it);
    - `replayed` >= `min_replayed_share` of the tokens: the replay's
      largest allowed logit IS the served token.

    `replayed`, `twin`: the program's logits, and the twin's distance a
    position, where the caller already has them (tests/perfbench/
    ouro_controls.py lays many faults over the reference beside one replay
    and the SOUND twin's distances: a fault there stands for the
    program's, and the yardstick a faulty program is held to is the sound
    function's)."""
    import extension            # the harness's loader and judge: only here,
    import reference            # the layers above import nothing of either

    prompt, out = sample["prompt_ids"], sample["output_ids"]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    if replayed is None:
        how = dict(limits["replay"])
        adapter = extension.load("adapters", how.pop("adapter"))
        replayed = adapter.replay(params, cfg, prompt, out, **how)
    replayed = replayed[:, allowed]
    fed, first = prompt + out[:-1], len(prompt) - 1
    rows = forward(params, cfg, fed)[first:]
    margins = [float(np.max(np.where(allowed, row, -np.inf))) - float(row[token])
               for row, token in zip(rows, out)]
    verdict = reference.judge(
        margins, sum(not allowed[t] for t in out), limits)
    rows = rows[:, allowed]
    if twin is None:
        twin = apart(
            forward(params, cfg, fed, keep=served)[first:, allowed], rows)
    ours, theirs = apart(replayed, rows), np.asarray(twin)
    ids = np.flatnonzero(allowed)[np.argmax(replayed, axis=1)]
    same = int(np.sum(ids == np.asarray(out)))
    least = limits["min_replayed_share"] * len(out)
    most_floor = limits["max_floor_ratio"] * floor(theirs)
    most_median = limits["max_median_ratio"] * float(np.median(theirs))
    clauses = [
        (floor(ours) <= most_floor,
         f"logit_floor {floor(ours):.4g} % (limit {most_floor:.4g}: "
         f"{limits['max_floor_ratio']:g} x the bf16 twin's "
         f"{floor(theirs):.4g})"),
        (float(np.median(ours)) <= most_median,
         f"logit_median {np.median(ours):.4g} % (limit {most_median:.4g}: "
         f"{limits['max_median_ratio']:g} x the bf16 twin's "
         f"{np.median(theirs):.4g})"),
        (same >= least,
         f"replayed {same} (at least {least:g} of {len(out)})"),
    ]
    why = verdict["why"] + [text for held, text in clauses if not held]
    return {
        **verdict, "ok": not why, "why": why,
        "checks": ", ".join([verdict["checks"]] + [t for _, t in clauses]),
        "logit_floor": floor(ours), "logit_median": float(np.median(ours)),
        "logit_distance": float(np.sqrt(np.mean(np.square(ours)))),
        "twin_floor": floor(theirs), "twin_median": float(np.median(theirs)),
        "twin_distance": float(np.sqrt(np.mean(np.square(theirs)))),
        "logit_distance_by_token": [float(a) for a in ours],
        "twin_distance_by_token": [float(a) for a in theirs],
        "replayed": same, "logit_std": float(np.std(rows)),
    }
