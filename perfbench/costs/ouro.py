"""Least bytes and operations of a looped decoder of the Ouro family as
ONE chip serves it whole, from the sizes in the configuration's file;
plain Python, for one chip.

Counted for a decode step, by part (`decode_step_parts`):

  layer_reads   every matrix of every layer, `total_ut_steps` TIMES
  head          the output head, once
  kv_read       K and V of the live tokens in every cache layer: a layer a
                pass, `total_ut_steps` x `num_hidden_layers` of them

Why `total_ut_steps` reads of the same matrices are the LEAST: the layers'
matrices (4.93 GB here) are many times what the chip keeps on it between
kernels (128 MiB of VMEM), so a pass reads them from HBM; and pass u + 1
of a token cannot start before pass u of that token has ended (it reads
its normed output), so the passes of one step cannot share a read of a
layer. Reading a layer once for all four passes would take four tokens of
one stream in flight at once, which decoding one token at a time has not.

Not counted: the embedding lookup (a gather of a few rows), gains, the
exit gate (2,049 numbers), activations, and the step's own K and V rows
written (8,192 B a lane and cache layer: a thousandth of a step).
"""

from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
KV_BYTES = 2         # bfloat16 K and V


def _dims(spec: dict) -> dict:
    heads = spec["num_attention_heads"]
    return {
        "hidden": spec["hidden_size"],
        "ffn": spec["intermediate_size"],
        "layers": spec["num_hidden_layers"],
        "loops": spec["total_ut_steps"],
        "heads": heads,
        "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec.get("head_dim") or spec["hidden_size"] // heads,
        "vocab": spec["vocab_size"],
    }


def layer_params(spec: dict) -> int:
    """The matrices of one layer: q, k, v, o and the gated MLP's three."""
    d = _dims(spec)
    attn = d["hidden"] * d["head_dim"] * (2 * d["heads"] + 2 * d["kv_heads"])
    return attn + 3 * d["hidden"] * d["ffn"]


def layer_bytes(spec: dict) -> int:
    return WEIGHT_BYTES * layer_params(spec)


def model_params(spec: dict) -> int:
    """Everything the chip holds: the layers once (their four gains too),
    the embedding and the head, the final norm and the exit gate."""
    d = _dims(spec)
    return (d["layers"] * (layer_params(spec) + 4 * d["hidden"])
            + 2 * d["vocab"] * d["hidden"] + 2 * d["hidden"] + 1)


def decode_weight_bytes(spec: dict) -> int:
    """The weights a step reads, each counted ONCE: the layers' matrices
    and the head (what the chip must hold to decode; `decode_step_bytes`
    counts the layers' re-reads)."""
    d = _dims(spec)
    return (d["layers"] * layer_bytes(spec)
            + WEIGHT_BYTES * d["hidden"] * d["vocab"])


def cache_layers(spec: dict) -> int:
    d = _dims(spec)
    return d["loops"] * d["layers"]


def kv_bytes_per_token_layer(spec: dict) -> int:
    d = _dims(spec)
    return 2 * d["kv_heads"] * d["head_dim"] * KV_BYTES


def kv_bytes_per_token(spec: dict) -> int:
    return cache_layers(spec) * kv_bytes_per_token_layer(spec)


def decode_step_parts(spec: dict, live_tokens: float) -> dict:
    d = _dims(spec)
    return {
        "layer_reads": d["loops"] * d["layers"] * layer_bytes(spec),
        "head": WEIGHT_BYTES * d["hidden"] * d["vocab"],
        "kv_read": live_tokens * kv_bytes_per_token(spec),
    }


def decode_step_bytes(spec: dict, live_tokens: float) -> float:
    return sum(decode_step_parts(spec, live_tokens).values())


def reread_bytes(spec: dict, layer_passes_a_step: float) -> float:
    """Of a step's layer reads, the bytes that are the same matrices read
    AGAIN: `layer_passes_a_step` layer applications (the program's count)
    less one of each layer."""
    d = _dims(spec)
    return max(layer_passes_a_step - d["layers"], 0.0) * layer_bytes(spec)

