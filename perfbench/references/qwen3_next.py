"""The plain reference of a Qwen3-Next style stack (the configuration
`qwen3-next-80b-a3b-ep4`): Gated DeltaNet linear-attention layers and
output-gated softmax attention by a pattern, each followed by softmax-routed
gated experts with a gated shared expert, under zero-centred RMSNorms.

float32, matmul precision "highest", one sequence, one entry of the pattern
at a time (10.9 GB of bf16 weights stay beside it: a float32 copy exists of
one entry's leaves only), no kernel, no cache, no chunking, no batching:
THE DELTA RULE RUNS TOKEN BY TOKEN. The layers and `forward` import nothing
of the package and nothing of the harness; they read the served tree
(`params["layers"][kind]` a tuple of per-entry trees, bf16 leaves) and the
package's ModelConfig by attribute only. `compare`, at the end, is the
verdict on a served sample: it alone reaches for the harness.
tests/reference_qwen3_next.py is the same file, for the package's own tests
(tests/test_qwen3_next.py holds the two byte-identical).

Norm(x) = x / rms(x) * (norm_offset + w): norm_offset 1 is the family's
zero-centred norm (w starts at 0), for the two norms of a layer, the final
norm and the q / k norms. Published layer l of 48 is TWO entries of the
pattern, each x <- x + f(Norm(x)) with a gain of its own:

  operator: "*" where (l + 1) mod full_attention_interval = 0, else "L"
  "L"  [q | k | v | z] = W_qkvz h (Hk Dk | Hk Dk | Hv Dv | Hv Dv columns),
       [b | a] = W_ba h (Hv | Hv); no bias. c = silu(causal depthwise conv
       of K = conv_kernel taps over q|k|v, zeros before the sequence, no
       bias). Value head j reads key head j div (Hv / Hk):
         q~ = q / sqrt(sum q^2 + 1e-6) / sqrt(Dk),  k~ = k / sqrt(sum k^2 + 1e-6)
         beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
       and a matrix S [Dk, Dv] a head, zero at the sequence's start:
         S <- exp(g_t) S;  m = S^T k~_t;  d = beta_t (v_t - m);
         S <- S + k~_t (x) d;  o_t = S^T q~_t
       out = W_out (RMSNorm over each head's Dv of o, times ONE gain vector
       of Dv (plain, starts at 1), THEN . silu(z))
  "*"  W_q h = per head [query | gate] of head_dim each; k, v = W_k h, W_v h
       (no bias); Norm over the head_dim of each q head and each k head
       (one gain vector each, shared by the heads) BEFORE the rotary
       embedding, which turns the FIRST head_dim * partial_rotary_factor
       dims of a head (rotate-half inside them, theta = rope_theta) and
       passes the rest; GQA, causal, scale head_dim^-1/2;
       out = W_o (attn . sigmoid(gate))
  feed-forward part, every layer
  "E"  p = softmax(W_r h) over ALL the published experts, float32; chosen =
       top-k of p; w_e = p_e / sum_chosen p (held here or not);
       out = sum_{chosen and held} w_e W_down,e (silu(W_gate,e h) . W_up,e h)
             + sigmoid(w_s . h) W_down,s (silu(W_gate,s h) . W_up,s h)
       Held: experts first_expert .. first_expert + experts_held - 1; what
       absent ones would add is left out, as in the program; the shared
       expert is whole on every chip.

After the last entry one Norm, then logits = x W_head (untied), over the
vocabulary slice the tree holds.

Assumed, where the catalog's config cannot confirm it (each also in the
configuration file's `assumed`): the projections' column order flat
q | k | v | z and b | a (the published checkpoint interleaves them by
key-head group: a loader's permutation, not another function); the L2
norm's 1e-6 inside the root; the gated norm's order (norm, gain, then the
gate); no bias on any projection; the multi-token-prediction module left
out. Departures from the published model: S, the conv and every activation
in float32 here (the published code holds activations in bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"L": "delta", "*": "attention", "E": "moe"}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps, offset=0.0):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (offset + f32(weight))


def rotary(x, positions, theta, turned):
    """x [T, heads, dim]; rotate-half over the first `turned` dims."""
    half = turned // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:turned], x[..., turned:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def history(cols, taps):
    """The conv's input with the K-1 columns before the sequence: zeros."""
    return jnp.concatenate(
        [jnp.zeros((taps - 1, cols.shape[1]), cols.dtype), cols])


def unit(x):
    """L2 norm over the last axis."""
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def shared_by(x, times):
    """A key head's vector for each of its value heads: [T, Hk, D] ->
    [T, Hk * times, D], value head j reading key head j div times."""
    return jnp.repeat(x, times, axis=1)


def strength(b):
    """beta: how much of the correction is written."""
    return jax.nn.sigmoid(b)


def decay(g):
    return jnp.exp(g)


def correction(v, m, beta):
    """What a token writes under its key: the delta rule."""
    return beta * (v - m)


def carried(S):
    """The state as it is held from one token to the next."""
    return S


def gated_norm(o, z, gain, eps):
    """RMSNorm over the last axis, the gain, THEN the gate."""
    return rms_norm(o, gain, eps) * jax.nn.silu(z)


def delta_layer(x, p, cfg):
    T = x.shape[0]
    Hk, Hv = cfg.delta_key_heads, cfg.delta_value_heads
    Dk, Dv, taps = cfg.delta_key_dim, cfg.delta_value_dim, cfg.conv_kernel
    kw, vw = Hk * Dk, Hv * Dv
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps, cfg.norm_offset)
    qkvz, ba = h @ f32(p["w_qkvz"]), h @ f32(p["w_ba"])
    z = qkvz[:, 2 * kw + vw:].reshape(T, Hv, Dv)
    ext = history(qkvz[:, :2 * kw + vw], taps)
    w = f32(p["conv_w"])                                   # [K, C]
    c = jax.nn.silu(sum(ext[k:k + T] * w[k] for k in range(taps)))
    q = shared_by(unit(c[:, :kw].reshape(T, Hk, Dk)) * Dk ** -0.5, Hv // Hk)
    k = shared_by(unit(c[:, kw:2 * kw].reshape(T, Hk, Dk)), Hv // Hk)
    v = c[:, 2 * kw:].reshape(T, Hv, Dv)
    beta = strength(ba[:, :Hv])                            # [T, Hv]
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
        ba[:, Hv:] + f32(p["dt_bias"]))

    def token(S, inputs):
        q_t, k_t, v_t, beta_t, g_t = inputs
        S = decay(g_t)[:, None, None] * S
        m = jnp.einsum("hkv,hk->hv", S, k_t)
        d = correction(v_t, m, beta_t[:, None])
        S = carried(S + k_t[:, :, None] * d[:, None, :])
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, Dk, Dv), jnp.float32),
                        (q, k, v, beta, g))
    y = gated_norm(o, z, p["gate_norm"], cfg.rms_norm_eps)
    return x + y.reshape(T, vw) @ f32(p["w_out"])


def out_gate(attn, gate):
    return attn * jax.nn.sigmoid(gate)


def attention_layer(x, p, cfg):
    T = x.shape[0]
    heads, kv_heads, dim = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    turned = int(dim * cfg.partial_rotary_factor)
    eps, offset = cfg.rms_norm_eps, cfg.norm_offset
    positions = jnp.arange(T)
    h = rms_norm(x, p["norm"], eps, offset)
    qg = (h @ f32(p["wq"])).reshape(T, heads, 2 * dim)
    q, gate = qg[..., :dim], qg[..., dim:]
    k = (h @ f32(p["wk"])).reshape(T, kv_heads, dim)
    v = (h @ f32(p["wv"])).reshape(T, kv_heads, dim)
    q = rms_norm(q, p["q_norm"], eps, offset)
    k = rms_norm(k, p["k_norm"], eps, offset)
    q = rotary(q, positions, float(cfg.rope_theta), turned)
    k = rotary(k, positions, float(cfg.rope_theta), turned)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * dim ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return x + out_gate(attn, gate).reshape(T, heads * dim) @ f32(p["wo"])


def gated(h, gate, up, down):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def scores(logits):
    """The router's: a softmax over every published expert."""
    return jax.nn.softmax(logits, axis=-1)


def expert_layer(x, p, cfg):
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps, cfg.norm_offset)
    s = scores(h @ f32(p["router"]))                       # [T, routed]
    w, chosen = jax.lax.top_k(s, cfg.num_experts_per_tok)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    mixed = jnp.zeros_like(x)
    for e in range(cfg.experts_held):
        mine = jnp.sum(jnp.where(chosen == cfg.first_expert + e, w, 0.0), -1)
        mixed = mixed + mine[:, None] * gated(
            h, p["gate"][e], p["up"][e], p["down"][e])
    shared = gated(h, p["shared"]["gate"], p["shared"]["up"],
                   p["shared"]["down"])
    if cfg.shared_expert_gate:
        shared = shared * jax.nn.sigmoid(h @ f32(p["shared_score"]))[:, None]
    return x + mixed + shared


LAYERS = {"delta": delta_layer, "attention": attention_layer,
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
            lambda x, norm, head: rms_norm(
                x, norm, cfg.rms_norm_eps, cfg.norm_offset) @ f32(head)
        )(x, params["final_norm"], params["lm_head"])
    return np.asarray(logits)


def compare(params, cfg, sample: dict, limits: dict, replayed=None) -> dict:
    """The verdict on one served sample: the sibling hybrid configuration's
    own `compare` (perfbench/references/nemotron_h.py: the harness's
    margins and `judge`, and `logit_floor`, `logit_distance`, `replayed` on
    the program's logits replayed through `forward_slots` + `unembed` by
    the adapter the limits name), with THIS module's `forward` as the
    reference it teacher-forces. The clause arithmetic is that file's, not
    a copy: its function runs over its own globals with `forward` replaced,
    and the loaded module is left as it was."""
    import types

    import extension

    theirs = extension.load("references", "nemotron_h.py").compare
    mine = types.FunctionType(
        theirs.__code__, {**theirs.__globals__, "forward": forward},
        "compare", theirs.__defaults__)
    return mine(params, cfg, sample, limits, replayed)
