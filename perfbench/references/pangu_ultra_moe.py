"""The plain reference of an openPangu-Ultra-MoE style stack (the
configuration `openpangu-ultra-moe-ep32`): multi-head latent attention
(MLA) in its EXPANDED, published form, sandwich norms, a leading dense
layer, then sigmoid-routed gated experts with one plain shared expert.

float32, matmul precision "highest", one sequence, one entry of the pattern
at a time (9.3 GB of bf16 weights stay beside it: a float32 copy exists of
one entry's leaves only), no kernel, no cache, no paging, no absorption:
EVERY HEAD'S KEY AND VALUE ARE BUILT from the token's latent, which is what
"published" means and what the program never does. The layers and `forward`
import nothing of the package and nothing of the harness; they read the
served tree (`params["layers"][kind]` a tuple of per-entry trees, bf16
leaves) and the package's ModelConfig by attribute only. `compare`, at the
end, is the verdict on a served sample: it alone reaches for the harness.

Norm(x) = x / rms(x) * w. Published layer l is TWO entries of the pattern,
each x <- x + Norm_post(f(Norm_pre(x))) with two gains of its own (the
sandwich norm: the post-norm stands on the body's OUTPUT, before the
residual add):

  "A"  c_q = Norm(W_dq h) over q_lora_rank; head i's query
       [q_nope,i | q_rope,i] = W_uq,i c_q (qk_nope_head_dim |
       qk_rope_head_dim columns); q_rope <- RoPE(q_rope) (rotate-half over
       the qk_rope_head_dim, theta = rope_theta).
       [c | k_r] = W_dkv h (kv_lora_rank | qk_rope_head_dim columns);
       c <- Norm(c); k_r <- RoPE(k_r): ONE rotary key for all heads.
       Head i: k_i,s = [W_uk,i c_s | k_r,s], v_i,s = W_uv,i c_s;
       o_i = softmax_s(q_i . k_i,s / sqrt(nope + rope), causal) v_i,s;
       out = W_o concat_i o_i.
       The served tree holds W_ukv's two halves a head at a time: w_uk
       [heads, nope, rank] (k_nope = w_uk c), w_uv [heads, rank, v].
  "D"  W_down (silu(W_gate h) . W_up h)
  "E"  s = sigmoid(W_r h) over ALL the published experts, float32; chosen =
       top-k of s (no correction bias, no group limit); w_e = s_e /
       sum_chosen s x routed_scaling_factor (held here or not);
       out = sum_{chosen and held} w_e W_down,e (silu(W_gate,e h) . W_up,e h)
             + W_down,s (silu(W_gate,s h) . W_up,s h)
       Held: experts first_expert .. first_expert + experts_held - 1; what
       absent ones would add is left out, as in the program; the shared
       expert is whole on every chip.

After the last entry one Norm, then logits = x W_head (untied), over the
vocabulary slice the tree holds.

Assumed, where the catalog's config cannot confirm it (each also in the
configuration file's `assumed`): the norms on c_q and c (the family's
q_a_layernorm / kv_a_layernorm); rotate-half pairing (a loader's column
permutation); no YaRN factor in the scale; the post-norm on the sublayer's
output; the router's sigmoid without a correction bias or groups (the
tree's `router_bias` leaf is not read: the adapter fills it with zeros);
the multi-token-prediction module left out. Departure from the published
model: every activation in float32 here (the published code holds them in
bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"A": "latent", "D": "dense", "E": "moe"}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def post(out, p, cfg):
    """The sandwich's second norm, on a body's output."""
    if not cfg.sandwich_norm:
        return out
    return rms_norm(out, p["post_norm"], cfg.rms_norm_eps)


def rotary(x, positions, theta):
    """x [T, heads, dim]; rotate-half over the whole dim."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def cached(row):
    """The token's row as the cache holds it between steps."""
    return row


def rope_score(q_rope, k_r):
    """The rotary part of a head's score: every head against ONE key."""
    return jnp.einsum("thd,sd->hts", q_rope, k_r)


def latent_layer(x, p, cfg):
    T = x.shape[0]
    heads, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, turned = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps, theta = cfg.rms_norm_eps, float(cfg.rope_theta)
    positions = jnp.arange(T)
    h = rms_norm(x, p["norm"], eps)
    c_q = rms_norm(h @ f32(p["w_dq"]), p["q_norm"], eps)
    q = (c_q @ f32(p["w_uq"])).reshape(T, heads, nope + turned)
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], positions, theta)
    down = h @ f32(p["w_dkv"])
    c = rms_norm(down[:, :rank], p["kv_norm"], eps)
    k_r = rotary(down[:, None, rank:], positions, theta)[:, 0]
    row = cached(jnp.concatenate([c, k_r], axis=-1))
    c, k_r = row[:, :rank], row[:, rank:]
    k_nope = jnp.einsum("sc,hnc->shn", c, f32(p["w_uk"]))
    v = jnp.einsum("sc,hcv->shv", c, f32(p["w_uv"]))
    scores = (jnp.einsum("thn,shn->hts", q_nope, k_nope)
              + rope_score(q_rope, k_r)) * (nope + turned) ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, axis=-1), v)
    out = attn.reshape(T, heads * cfg.v_head_dim) @ f32(p["wo"])
    return x + post(out, p, cfg)


def gated(h, gate, up, down):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def dense_layer(x, p, cfg):
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    return x + post(gated(h, p["gate"], p["up"], p["down"]), p, cfg)


def expert_layer(x, p, cfg):
    h = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    s = jax.nn.sigmoid(h @ f32(p["router"]))               # [T, routed]
    w, chosen = jax.lax.top_k(s, cfg.num_experts_per_tok)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scaling_factor
    mixed = jnp.zeros_like(x)
    for e in range(cfg.experts_held):
        mine = jnp.sum(jnp.where(chosen == cfg.first_expert + e, w, 0.0), -1)
        mixed = mixed + mine[:, None] * gated(
            h, p["gate"][e], p["up"][e], p["down"][e])
    shared = gated(h, p["shared"]["gate"], p["shared"]["up"],
                   p["shared"]["down"])
    return x + post(mixed + shared, p, cfg)


LAYERS = {"latent": latent_layer, "dense": dense_layer, "moe": expert_layer}


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
