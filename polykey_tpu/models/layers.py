"""Transformer layer primitives shared across model families.

Functional style: parameters are dict pytrees, every function is pure. All
linear weights use the [in_features, out_features] convention so matmuls are
plain `x @ w` and shard naturally under Megatron-style TP partition specs
(parallel/sharding.py). Layers are stacked on a leading axis and driven by
`lax.scan` in the family forward functions — one compiled block regardless of
depth, and a natural unit for pipeline-stage sharding.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from .quant import qdot


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, offset: float = 0.0
) -> jax.Array:
    """RMSNorm with fp32 accumulation. Gemma stores weights as (1 + w), which
    callers express via offset=1.0."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (offset + weight.astype(jnp.float32))).astype(x.dtype)


def rope(
    x: jax.Array,                # [B, T, H, D]
    positions: jax.Array,        # [B, T]
    theta: float,
    rotary_dim: Optional[int] = None,
) -> jax.Array:
    """Rotary position embedding, half-split (rotate-half) convention;
    over the leading `rotary_dim` of the head where that is given (a
    partial rotary: the dims after it pass as they are)."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = rope(x[..., :rotary_dim], positions, theta)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    half = x.shape[-1] // 2
    freqs = theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )                                                    # [half]
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]                 # [B, T, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _activate(x: jax.Array, activation: str) -> jax.Array:
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {activation!r}")


def mlp(p: dict, x: jax.Array, activation: str) -> jax.Array:
    """Gated MLP (SwiGLU / GeGLU): act(x@gate) * (x@up) @ down."""
    gate = _activate(qdot(x, p["gate"]), activation)
    return qdot(gate * qdot(x, p["up"]), p["down"])


def qkv_project(
    p: dict, x: jax.Array, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q, k, v of one attention layer, split into heads: [B, T, heads, D]
    (q [B, T, heads, 2 D] where `cfg.attn_output_gate`: each head's query,
    then its gate — one product, as wide again).

    Each product is held FLAT ([B, T, heads · D]) behind an optimization
    barrier of its own until it is done, and only then split. Without it,
    wherever a dimension of 1 stands beside the rows — [B, 1, H] in the
    decode step, [1, T, H] in a one-row prefill — the TPU compiler folds
    the head split of q and k (whose consumer is `rope`) INTO the product:
    a convolution with a window over heads (`dim_labels=bf0_0oi->b0f`) on
    the weight viewed [heads, D, H]. That form wants the weight stack
    relaid `{1,2,0}` on every dispatch (640 MB of `wq` + `wk` a decode
    block at mistral-7b's widths), stages each layer's whole `wq` / `wk`
    in VMEM through a fusion of its own and runs the product from there
    with nothing to overlap. Flat, q and k are what `wv`, `wo` and the FFN
    are: one fusion whose matmul streams the layer's weight from HBM in
    the layout it is stored in. One barrier a product, not one around the
    three: a joint barrier keeps all three results live together, and the
    multi-row prefill modules — which never folded — then come out of the
    compiler's memory assignment with a third more operations a layer.
    The arithmetic is `qdot`'s, bit for bit; tests/test_paged_layout.py
    holds the compiled decode and prefill steps to this
    (scripts/decode_step_census.py prints them)."""
    B, T, _ = x.shape

    def heads(name: str, n: int, width: int = cfg.head_dim) -> jax.Array:
        flat = jax.lax.optimization_barrier(qdot(x, p[name]))
        return flat.reshape(B, T, n, width)

    return (
        heads("wq", cfg.num_heads,
              cfg.head_dim * (2 if cfg.attn_output_gate else 1)),
        heads("wk", cfg.num_kv_heads),
        heads("wv", cfg.num_kv_heads),
    )


def init_attention_params(
    key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16
) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, d = cfg.hidden_size, cfg.head_dim
    scale = h**-0.5
    return {
        "wq": jax.random.normal(kq, (h, cfg.num_heads * d), dtype) * scale,
        "wk": jax.random.normal(kk, (h, cfg.num_kv_heads * d), dtype) * scale,
        "wv": jax.random.normal(kv, (h, cfg.num_kv_heads * d), dtype) * scale,
        "wo": jax.random.normal(ko, (cfg.num_heads * d, h), dtype)
        * (cfg.num_heads * d) ** -0.5,
    }


def init_mlp_params(
    key: jax.Array, hidden: int, intermediate: int, dtype=jnp.bfloat16
) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "gate": jax.random.normal(kg, (hidden, intermediate), dtype) * hidden**-0.5,
        "up": jax.random.normal(ku, (hidden, intermediate), dtype) * hidden**-0.5,
        "down": jax.random.normal(kd, (intermediate, hidden), dtype)
        * intermediate**-0.5,
    }


def layer_sliding_window(cfg: ModelConfig, layer_idx: jax.Array) -> Optional[jax.Array]:
    """Gemma-2 interleaves sliding-window (even) and global (odd) layers.

    Returns a per-layer window size as a traced scalar (or None when the
    config has no window). Global layers get window = max_seq_len, which is
    equivalent to no window.
    """
    if cfg.sliding_window is None:
        return None
    return jnp.where(layer_idx % 2 == 0, cfg.sliding_window, cfg.max_seq_len)
