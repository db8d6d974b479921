"""Int8 / int4 weight-only quantization for serving.

One v5e chip has 16 GiB HBM; Llama-3-8B in bf16 is ~16 GiB of weights alone,
so the single-chip serving story for 8B-class models (BASELINE.md config 2)
is int8 weights: per-output-channel symmetric scales, dequantized on the fly
inside the matmul (`(x @ q) * s` — XLA fuses the int8→bf16 cast into the
MXU feed, so HBM traffic halves, which is the whole game for bandwidth-bound
decode). Activations stay bf16; norms/router stay fp (negligible bytes).

int4 (POLYKEY_QUANTIZE=int4) halves weight traffic again — the lever for
beating, not just meeting, the weight-bandwidth-bound throughput target.
Because 4-bit symmetric ([-7, 7]) is too coarse for a whole contraction
axis, int4 uses GROUP-WISE scales (group_size along the contraction axis,
AWQ/GPTQ granularity): q stores two nibbles per uint8 byte, packed in
PAIRS ALONG THE CONTRACTION AXIS ([..., in/2, out]). A native jnp.int4
operand does compile and compute exactly on a v5e with jax 0.9.0
(scripts/tpu_kernel_check.py "int4-native", 2026-09-26); packed uint8
stays because every byte-oriented consumer (loader, sharding specs,
params_bytes, the KV-independent wire paths) already handles it and the
manual unpack (mask/shift/sign-extend) is elementwise like an s4→bf16
cast. Which of the two XLA fuses better into the dot's operand load is
not measured; the int4 cell decides. s is
[..., in/g, out], and dequantization happens on the weight side
(`x @ (q·s)`). The embedding and lm_head stay int8: the embedding is a
sparse gather (bandwidth-irrelevant), and the unembed keeps its exact
narrow-operand fp32-accumulate path.

Representation: a `QuantizedTensor` pytree leaf-pair (int values + fp32
scales) that flows through jit/sharding like any array pair. The matmul
seam is `qdot` — every linear in layers.py/transformer.py routes through it
and dispatches on type, so the same forward serves fp, int8, and int4
trees. Group-wise `s` has the same rank as `q` with the group axis in the
contraction position, so row-parallel (Megatron) sharding of the
contraction axis shards the groups consistently.

The reference has no quantization (25 Go files, no ML — SURVEY.md §2); this
is owed to the north star's single-chip 8B serving target.
"""

from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp
from flax import struct

from .config import ModelConfig


@struct.dataclass
class QuantizedTensor:
    """Int8/int4 weights with fp32 scales.

    q: bits=8: int8 [..., in, out] (weight shape); bits=4: uint8
       [..., in/2, out] — nibble pairs packed along the contraction axis
       (row 2i in the low nibble, row 2i+1 in the high nibble).
    s: fp32 scales —
       bits=8: [..., out], per-output-channel over the contraction axis;
       bits=4: [..., in/group, out], group-wise along the contraction
       axis (same rank as q, group axis in the contraction position).
    act_dtype: the pre-quantization weight dtype; dequantization targets it
    so an fp32-configured model is not silently narrowed to bf16 (and
    callers sizing KV caches off params["embed"].dtype see the activation
    dtype, not the fp32 scales).
    """

    q: jax.Array
    s: jax.Array
    act_dtype: jnp.dtype = struct.field(pytree_node=False, default=jnp.bfloat16)
    bits: int = struct.field(pytree_node=False, default=8)

    @property
    def shape(self):
        if self.bits == 4:
            # Logical weight shape — the packed contraction axis unfolds.
            return (*self.q.shape[:-2], self.q.shape[-2] * 2,
                    self.q.shape[-1])
        return self.q.shape

    @property
    def dtype(self):
        return jnp.dtype(self.act_dtype)


def quantize(
    w: jax.Array, bits: int = 8, group_size: int = 128
) -> QuantizedTensor:
    """Symmetric quantization of [..., in, out].

    bits=8: per-output-channel scales. bits=4: group-wise scales along
    the contraction axis (group_size, shrunk to the full axis when it
    doesn't divide — tiny test models)."""
    if bits == 8:
        absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)  # [..., out]
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        q = jnp.clip(
            jnp.round(w.astype(jnp.float32) / scale[..., None, :]), -127, 127
        ).astype(jnp.int8)
        return QuantizedTensor(q=q, s=scale, act_dtype=jnp.dtype(w.dtype))
    if bits != 4:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    cin = w.shape[-2]
    if cin % 2:
        raise ValueError(
            f"int4 needs an even contraction axis to nibble-pack, got {cin}"
        )
    g = group_size if cin % group_size == 0 else cin
    wf = w.astype(jnp.float32)
    grouped = wf.reshape(*w.shape[:-2], cin // g, g, w.shape[-1])
    absmax = jnp.max(jnp.abs(grouped), axis=-2)            # [..., G, out]
    scale = jnp.maximum(absmax, 1e-8) / 7.0
    q = jnp.clip(
        jnp.round(grouped / scale[..., None, :]), -7, 7
    ).reshape(w.shape).astype(jnp.int8)
    # Nibble-pack contraction-axis pairs: row 2i → low, row 2i+1 → high
    # (two's-complement nibbles via the uint8 wrap).
    pairs = q.reshape(*w.shape[:-2], cin // 2, 2, w.shape[-1])
    packed = (
        (pairs[..., 0, :].astype(jnp.uint8) & 0xF)
        | ((pairs[..., 1, :].astype(jnp.uint8) & 0xF) << 4)
    )
    return QuantizedTensor(
        q=packed, s=scale, act_dtype=jnp.dtype(w.dtype), bits=4
    )


def dequantize(w: QuantizedTensor, dtype=jnp.bfloat16) -> jax.Array:
    if w.bits == 4:
        # One group-layout implementation only — qdot's fused path and
        # this reference must never drift apart.
        return _deq_weight(w, jnp.float32).astype(dtype)
    return (w.q.astype(jnp.float32) * w.s[..., None, :]).astype(dtype)


WeightLike = Union[jax.Array, QuantizedTensor]


def _deq_weight(w: QuantizedTensor, dtype) -> jax.Array:
    """Weight-side group-wise dequantization in the activation dtype — an
    elementwise producer (unpack + scale) XLA fuses into the consuming
    dot's operand load, so HBM traffic stays packed nibbles + small
    scales."""
    p = w.q                                       # [..., in/2, out] uint8
    low = (p & 0xF).astype(jnp.int8)
    high = (p >> 4).astype(jnp.int8)
    low = jnp.where(low > 7, low - 16, low)       # sign-extend the nibble
    high = jnp.where(high > 7, high - 16, high)
    q = jnp.stack([low, high], axis=-2)           # [..., in/2, 2, out]
    shape = w.shape                               # logical [..., in, out]
    q = q.reshape(shape)
    G = w.s.shape[-2]
    cin, cout = shape[-2], shape[-1]
    grouped = q.reshape(*shape[:-2], G, cin // G, cout).astype(dtype)
    return (grouped * w.s[..., None, :].astype(dtype)).reshape(shape)


def qdot(x: jax.Array, w: WeightLike) -> jax.Array:
    """x @ w with on-the-fly dequantization for QuantizedTensor weights.

    int8 scales fold AFTER the matmul (per-output-channel); int4 scales
    vary along the contraction axis, so dequantization moves to the
    weight side of the dot."""
    if isinstance(w, QuantizedTensor):
        if w.bits == 4:
            return x @ _deq_weight(w, x.dtype)
        y = x @ w.q.astype(x.dtype)
        return y * w.s.astype(x.dtype)
    return x @ w


def qeinsum_expert(
    pattern: str, x: jax.Array, w: WeightLike, e_axis: int, **kwargs
):
    """Expert-stacked einsum: int8 scales are [E, out]; `e_axis` names the
    expert axis in the OUTPUT (out is always last). Covers both MoE
    formulations: 'bth,ehi->beti' (e_axis=1) and the dispatch path
    'ech,ehi->eci' (e_axis=0). int4 dequantizes weight-side (group axis
    inside the expert stack)."""
    if isinstance(w, QuantizedTensor):
        if w.bits == 4:
            return jnp.einsum(pattern, x, _deq_weight(w, x.dtype), **kwargs)
        y = jnp.einsum(pattern, x, w.q.astype(x.dtype), **kwargs)
        shape = [1] * y.ndim
        shape[e_axis] = w.s.shape[0]
        shape[-1] = w.s.shape[-1]
        return y * w.s.reshape(shape).astype(y.dtype)
    return jnp.einsum(pattern, x, w, **kwargs)


def embed_lookup(embed: WeightLike, tokens: jax.Array) -> jax.Array:
    """Embedding row lookup; scales are per hidden channel ([H] — the same
    axis the tied unembed contracts, so one tensor serves both uses)."""
    if isinstance(embed, QuantizedTensor):
        rows = embed.q[tokens]                         # int8 [..., H]
        return rows.astype(embed.dtype) * embed.s.astype(embed.dtype)
    return embed[tokens]


def unembed_logits(hidden: jax.Array, embed_or_head: WeightLike, tied: bool):
    """fp32 vocab logits from either a tied embedding ('...h,vh->...v') or an
    lm_head ('...h,hv->...v'), quantized or not."""
    if isinstance(embed_or_head, QuantizedTensor):
        # int8 values (|q| <= 127) are exact in bf16, so the vocab matmul —
        # the hottest step at 128k-256k vocab — keeps narrow operands and
        # accumulates fp32 via preferred_element_type, like the fp path.
        wdt = embed_or_head.dtype
        if tied:
            # Tied: q is [V, H], scales are [H] (contraction axis) — fold the
            # scale into the activation before the matmul.
            scaled = hidden.astype(jnp.float32) * embed_or_head.s
            return jnp.einsum(
                "...h,vh->...v", scaled.astype(wdt),
                embed_or_head.q.astype(wdt),
                preferred_element_type=jnp.float32,
            )
        y = jnp.einsum(
            "...h,hv->...v", hidden.astype(wdt),
            embed_or_head.q.astype(wdt),
            preferred_element_type=jnp.float32,
        )
        return y * embed_or_head.s
    if tied:
        return jnp.einsum(
            "...h,vh->...v", hidden, embed_or_head,
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "...h,hv->...v", hidden, embed_or_head,
        preferred_element_type=jnp.float32,
    )


_QUANT_LEAVES = ("wq", "wk", "wv", "wo", "gate", "up", "down", "lm_head")


def quantize_linears(node, bits: int = 8):
    """Quantize every linear weight under `node` (a params subtree: the
    whole tree, or one layer); norms, router, and biases stay fp. Leading
    stack axes ([L, ...], [L, E, ...]) pass through — quantize() reduces
    the contraction axis (-2) only — so a single layer quantizes to
    exactly the rows the stacked tree would hold for it. With bits=4 the
    block linears go int4 group-wise; lm_head stays int8 (the exact
    narrow-operand unembed path — see module docstring)."""
    if not isinstance(node, dict):
        return node
    out = {}
    for name, child in node.items():
        if name in _QUANT_LEAVES and isinstance(child, jax.Array):
            out[name] = quantize(child, bits=8 if name == "lm_head" else bits)
        else:
            out[name] = quantize_linears(child, bits)
    return out


def quantize_embed(embed: jax.Array) -> QuantizedTensor:
    """int8 embedding [V, H], quantized per hidden channel so the same
    tensor serves lookup and (tied) unembedding."""
    absmax = jnp.max(jnp.abs(embed.astype(jnp.float32)), axis=0)  # [H]
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(embed.astype(jnp.float32) / scale[None, :]), -127, 127
    ).astype(jnp.int8)
    return QuantizedTensor(q=q, s=scale, act_dtype=jnp.dtype(embed.dtype))


def quantize_params(params: dict, cfg: ModelConfig, bits: int = 8) -> dict:
    """Quantize a whole params tree: linears via quantize_linears, the
    embedding via quantize_embed (int8 at either width — sparse gather +
    the exact narrow-operand unembed path)."""
    out = quantize_linears(params, bits)
    out["embed"] = quantize_embed(params["embed"])
    return out


def params_bytes(params) -> int:
    """Total parameter storage in bytes (quantized trees count q + s).
    int4 leaves are packed uint8 (two nibbles per byte), so plain
    size x itemsize is already the HBM truth."""
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
