"""The plain reference of an LFM2-MoE style stack (the configuration
`lfm2-24b-a2b-pp4`): gated short-convolution operators and QK-normed RoPE
attention by a pattern, each followed by a feed-forward part — a dense
gated MLP or sigmoid-routed gated experts — under a norm of its own.

float32, matmul precision "highest", one sequence, one entry of the pattern
at a time (10.5 GB of bf16 weights stay beside it: a float32 copy exists of
one entry's leaves only), no kernel, no cache, no batching. The layers and
`forward` import nothing of the package and nothing of the harness; they
read the served tree (`params["layers"][kind]` a tuple of per-entry trees,
bf16 leaves) and the package's ModelConfig by attribute only. `compare`,
at the end, is the verdict on a served sample: it alone reaches for the
harness. tests/reference_lfm2_moe.py is the same file, for the package's
own tests (tests/test_lfm2.py holds the two byte-identical).

Published layer l of 40 is TWO entries of the pattern, each
x <- x + f(RMSNorm(x)) with a gain of its own:

  operator, by layer_types[l]
  "C"  [B | C | u] = W_in h (hidden -> 3 hidden, split in that order)
       z = B . u;  c_t = sum_{k<K} w[k] . z_{t-(K-1)+k}   (causal depthwise
       conv of K = conv_L_cache taps, zeros before the sequence, no bias,
       no activation);  out = W_out (C . c)
  "*"  q, k, v = W_q h, W_k h, W_v h (no bias); RMSNorm over the head_dim
       of each q head and each k head (gains q_norm, k_norm) BEFORE the
       rotary embedding (whole head, rotate-half, theta = rope_theta);
       GQA, causal, scale head_dim^-1/2;  out = W_o attn
  feed-forward part
  "D"  W_down (silu(W_gate h) . W_up h)      (layers < num_dense_layers)
  "E"  s = sigmoid(W_r h) in float32; chosen = top-k of s + expert_bias
       (the bias chooses, it does not weigh); w_e = s_e / (sum_chosen s +
       router_norm_eps) * routed_scaling_factor;
       out = sum_{chosen and held} w_e W_down,e (silu(W_gate,e h) . W_up,e h)
       Held: experts first_expert .. first_expert + experts_held - 1 (this
       configuration holds all 64); what absent ones would add is left
       out, as in the program.

After the last entry one RMSNorm, then logits = x E^T over the embedding
matrix itself (tied).

Assumed, where the catalog's config cannot confirm it (each also in the
configuration file's `assumed`): tied embeddings; head_dim = hidden /
heads; q/k norm before rotary; no activation inside the conv operator;
the 1e-6 in the router's denominator. Departures from the published
model: none in the mathematics above.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"C": "conv", "*": "attention", "E": "moe", "D": "dense"}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def rotary(x, positions, theta):
    """x [T, heads, dim]; rotate-half convention over the whole head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def history(z, taps):
    """The conv's input with the K-1 columns before the sequence: zeros."""
    return jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])


def in_gate(b, u):
    """What the conv runs over."""
    return b * u


def out_gate(c, y):
    """What W_out projects."""
    return c * y


def conv_layer(x, p, cfg):
    T, hidden = x.shape
    taps = cfg.conv_kernel
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    bcu = h @ f32(p["w_in"])
    b, c, u = bcu[:, :hidden], bcu[:, hidden:2 * hidden], bcu[:, 2 * hidden:]
    ext = history(in_gate(b, u), taps)
    w = f32(p["conv_w"])                                   # [K, hidden]
    y = sum(ext[k:k + T] * w[k] for k in range(taps))
    return x + out_gate(c, y) @ f32(p["w_out"])


def head_norm(x, weight, eps):
    """RMSNorm over the head_dim of each head: x [T, heads, dim]."""
    return rms_norm(x, weight, eps)


def attention_layer(x, p, cfg):
    T = x.shape[0]
    heads, kv_heads, dim = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    positions = jnp.arange(T)
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    q = (h @ f32(p["wq"])).reshape(T, heads, dim)
    k = (h @ f32(p["wk"])).reshape(T, kv_heads, dim)
    v = (h @ f32(p["wv"])).reshape(T, kv_heads, dim)
    if cfg.qk_norm:
        q = head_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = head_norm(k, p["k_norm"], cfg.rms_norm_eps)
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


def gated(h, gate, up, down):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def dense_layer(x, p, cfg):
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    return x + gated(h, p["gate"], p["up"], p["down"])


def choice(s, bias, k):
    """The experts chosen: top-k of score + bias."""
    return jax.lax.top_k(s + bias, k)[1]


def expert_layer(x, p, cfg):
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    s = jax.nn.sigmoid(h @ f32(p["router"]))               # [T, routed]
    chosen = choice(s, f32(p["router_bias"]), cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg.routed_scaling_factor * w / (
        jnp.sum(w, axis=-1, keepdims=True) + cfg.router_norm_eps)
    mixed = jnp.zeros_like(x)
    for e in range(cfg.experts_held):
        mine = jnp.sum(jnp.where(chosen == cfg.first_expert + e, w, 0.0), -1)
        mixed = mixed + mine[:, None] * gated(
            h, p["gate"][e], p["up"][e], p["down"][e])
    return x + mixed


LAYERS = {"conv": conv_layer, "attention": attention_layer,
          "moe": expert_layer, "dense": dense_layer}


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
            lambda x, norm, embed: rms_norm(x, norm, cfg.rms_norm_eps)
            @ f32(embed).T
        )(x, params["final_norm"], params["embed"])
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
