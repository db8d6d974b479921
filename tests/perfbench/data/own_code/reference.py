"""The plain reference of the test configuration `own-code` (installed as
perfbench/references/own_code.py): a decoder block with an RMSNorm before
AND after attention and feed-forward, a tanh-GELU gate, and logits capped
at cap * tanh(logits / cap). float32, precision "highest", no kernel, no
cache, one layer at a time; imports nothing of the package."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import f32, rms_norm, rotary


def layer(x, p, dims):
    heads, kv_heads, head_dim, theta, eps = dims
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
    attn = attn.reshape(T, heads * head_dim) @ f32(a["wo"])
    x = x + rms_norm(attn, p["post_ln1"], eps)
    h = rms_norm(x, p["ln2"], eps)
    m = p["mlp"]
    gated = jax.nn.gelu(h @ f32(m["gate"]), approximate=True) * (h @ f32(m["up"]))
    return x + rms_norm(gated @ f32(m["down"]), p["post_ln2"], eps)


def forward(params, cfg, tokens) -> np.ndarray:
    """Float32 logits [T, vocab] for one sequence of token ids."""
    dims = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            float(cfg.rope_theta), float(cfg.rms_norm_eps))
    cap = float(cfg.final_logit_softcap)
    step = jax.jit(layer, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: f32(e)[t])(params["embed"], np.asarray(tokens))
        for i in range(cfg.num_layers):
            x = step(x, jax.tree.map(lambda w: w[i], params["layers"]), dims)
        logits = jax.jit(
            lambda x, norm, head: rms_norm(x, norm, dims[4]) @ f32(head)
        )(x, params["final_norm"], params["lm_head"])
    return np.asarray(cap * np.tanh(np.asarray(logits) / cap))
