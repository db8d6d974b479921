"""The plain reference of an Olmo-Hybrid style stack (the configuration
`olmo-hybrid-7b-pp2`): Gated DeltaNet linear-attention layers whose write
strength reaches 2 (negative eigenvalues allowed) and full multi-head
softmax attention without a position embedding by a pattern, each followed
by a dense gated MLP, every body POST-normed and none pre-normed.

float32, matmul precision "highest", one sequence, one entry of the pattern
at a time (8.2 GB of bf16 weights stay beside it: a float32 copy exists of
one entry's leaves only), no kernel, no cache, no chunking, no batching:
THE DELTA RULE RUNS TOKEN BY TOKEN. The layers and `forward` import nothing
of the package and nothing of the harness; they read the served tree
(`params["layers"][kind]` a tuple of per-entry trees, bf16 leaves) and the
package's ModelConfig by its sizes only — none of its switches: the block
order, the span of the q/k norm and β's range are written out here.
`compare`, at the end, is the verdict on a served sample: it alone reaches
for the harness. tests/reference_olmo_hybrid.py is the same file, for the
package's own tests (tests/test_olmo_hybrid.py holds the two
byte-identical).

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, plain gains. Published layer l
of 32 is TWO entries of the pattern, each x <- x + RMSNorm(f(x)) with a
gain of its own — the body reads the residual stream AS IT COMES, the norm
is on what it returns (the family's reordered norm); the embedding feeds
layer 0 un-normed:

  mixer: "*" where (l + 1) mod 4 = 0, else "L"
  "L"  [q | k | v | z] = W_qkvz x (H Dk | H Dk | H Dv | H Dv columns),
       [b | a] = W_ba x (H | H); no bias. c = silu(causal depthwise conv of
       K = conv_kernel taps over q|k|v, zeros before the sequence, no
       bias). As many key heads as value heads (H = 30): head j reads its
       own q and k.
         q~ = q / sqrt(sum q^2 + 1e-6) / sqrt(Dk),  k~ = k / sqrt(sum k^2 + 1e-6)
         beta_t = 2 sigmoid(b_t)   (in (0, 2): `linear_allow_neg_eigval`)
         g_t = -exp(A_log) softplus(a_t + dt_bias)
       and a matrix S [Dk, Dv] a head, zero at the sequence's start:
         S <- exp(g_t) S;  m = S^T k~_t;  d = beta_t (v_t - m);
         S <- S + k~_t (x) d;  o_t = S^T q~_t
       The transition exp(g) (I - beta k~ k~^T) has the eigenvalue
       exp(g) (1 - beta) along k~: negative where beta > 1.
       mix = W_out (RMSNorm over each head's Dv of o, times ONE gain vector
       of Dv, THEN . silu(z))
  "*"  q = RMSNorm(W_q x), k = RMSNorm(W_k x): ONE norm over the whole
       projection (the mean square over all heads x head_dim columns, one
       gain a column), before the split into heads; v = W_v x; no bias; NO
       position embedding; as many KV heads as query heads; causal,
       scale head_dim^-1/2; mix = W_o attn
  feed-forward part, every layer
  "D"  W_down (silu(W_gate x) . W_up x)

After the last entry one RMSNorm, then logits = x W_head (untied), over the
vocabulary the tree holds.

Assumed, where the catalog's config cannot confirm it (each also in the
configuration file's `assumed`): no position embedding on the attending
layers (`rope_parameters.rope_theta` null); the block order and the
full-width q/k norm for both layer kinds (the family's convention); head
width hidden / heads; the projections' column order flat q | k | v | z and
b | a; the L2 norm's 1e-6 inside the root; the gated norm's order (norm,
gain, then the gate); no bias on any projection. Departures from the
published model: S, the conv and every activation in float32 here (the
published code holds activations in bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"L": "delta", "*": "attention", "D": "dense"}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def read(x, eps):
    """What a body reads of the residual stream: the stream itself."""
    return x


def history(cols, taps):
    """The conv's input with the K-1 columns before the sequence: zeros."""
    return jnp.concatenate(
        [jnp.zeros((taps - 1, cols.shape[1]), cols.dtype), cols])


def unit(x):
    """L2 norm over the last axis."""
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def strength(b):
    """beta: how much of the correction is written, in (0, 2)."""
    return 2.0 * jax.nn.sigmoid(b)


def carried(S):
    """The state as it is held from one token to the next."""
    return S


def gated_norm(o, z, gain, eps):
    """RMSNorm over the last axis, the gain, THEN the gate."""
    return rms_norm(o, gain, eps) * jax.nn.silu(z)


def delta_mix(x, p, cfg):
    T = x.shape[0]
    H = cfg.delta_value_heads
    Dk, Dv, taps = cfg.delta_key_dim, cfg.delta_value_dim, cfg.conv_kernel
    kw, vw = H * Dk, H * Dv
    qkvz, ba = x @ f32(p["w_qkvz"]), x @ f32(p["w_ba"])
    z = qkvz[:, 2 * kw + vw:].reshape(T, H, Dv)
    ext = history(qkvz[:, :2 * kw + vw], taps)
    w = f32(p["conv_w"])                                   # [K, C]
    c = jax.nn.silu(sum(ext[k:k + T] * w[k] for k in range(taps)))
    q = unit(c[:, :kw].reshape(T, H, Dk)) * Dk ** -0.5
    k = unit(c[:, kw:2 * kw].reshape(T, H, Dk))
    v = c[:, 2 * kw:].reshape(T, H, Dv)
    beta = strength(ba[:, :H])                             # [T, H]
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
        ba[:, H:] + f32(p["dt_bias"]))

    def token(S, inputs):
        q_t, k_t, v_t, beta_t, g_t = inputs
        S = jnp.exp(g_t)[:, None, None] * S
        m = jnp.einsum("hkv,hk->hv", S, k_t)
        d = beta_t[:, None] * (v_t - m)
        S = carried(S + k_t[:, :, None] * d[:, None, :])
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, Dk, Dv), jnp.float32),
                        (q, k, v, beta, g))
    y = gated_norm(o, z, p["gate_norm"], cfg.rms_norm_eps)
    return y.reshape(T, vw) @ f32(p["w_out"])


def qk_normed(y, gain, heads, eps):
    """y [T, heads x dim], the projection whole: ONE norm over all of it."""
    return rms_norm(y, gain, eps)


def positioned(x, positions):
    """q or k [T, heads, dim] with its position embedding: none."""
    return x


def attention_mix(x, p, cfg):
    T = x.shape[0]
    heads, dim = cfg.num_heads, cfg.head_dim
    positions = jnp.arange(T)
    q = qk_normed(x @ f32(p["wq"]), p["q_norm"], heads, cfg.rms_norm_eps)
    k = qk_normed(x @ f32(p["wk"]), p["k_norm"], heads, cfg.rms_norm_eps)
    q = positioned(q.reshape(T, heads, dim), positions)
    k = positioned(k.reshape(T, heads, dim), positions)
    v = (x @ f32(p["wv"])).reshape(T, heads, dim)
    scores = jnp.einsum("thd,shd->hts", q, k) * dim ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, heads * dim) @ f32(p["wo"])


def mlp_mix(x, p, cfg):
    return (jax.nn.silu(x @ f32(p["gate"])) * (x @ f32(p["up"]))) \
        @ f32(p["down"])


def entry(mix):
    """x <- x + RMSNorm(mix(x)): no norm before the body, one after."""
    def layer(x, p, cfg):
        out = mix(read(x, cfg.rms_norm_eps), p, cfg)
        return x + rms_norm(out, p["post_norm"], cfg.rms_norm_eps)
    return layer


LAYERS = {"delta": entry(delta_mix), "attention": entry(attention_mix),
          "dense": entry(mlp_mix)}


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
            lambda x, norm, head: rms_norm(x, norm, cfg.rms_norm_eps)
            @ f32(head)
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
