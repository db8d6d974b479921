"""Operations and bytes the algorithm needs, computed from shapes.

Kept with the benchmark so that no later change to the program can move
the yardstick. `spec` is a configuration file (perfbench/configs/*.json);
every function returns what ONE chip must do when the model is split `tp`
ways over heads and feed-forward columns (tp = 1: the whole model).
Padding, recomputation and relayouts do not count: these are the least
bytes and operations the mathematics asks for.

The formulas below reckon a GQA decoder with dense or Mixtral-style
SwiGLU blocks. A configuration of another architecture names a module of
its own, perfbench/costs/<file>.py (contract: docstring of
perfbench/run.py); a reader that works for every configuration goes
through `for_spec`.
"""

from __future__ import annotations

import sys

import extension

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def for_spec(spec: dict):
    """The module that reckons this configuration's bytes and operations:
    the file its "costs" key names, or this one."""
    if "costs" not in spec:
        return sys.modules[__name__]
    return extension.load("costs", spec["costs"])


def _dims(spec: dict) -> dict:
    heads = spec["num_attention_heads"]
    return {
        "hidden": spec["hidden_size"],
        "ffn": spec["intermediate_size"],
        "layers": spec["num_hidden_layers"],
        "heads": heads,
        "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec.get("head_dim") or spec["hidden_size"] // heads,
        "vocab": spec["vocab_size"],
        "experts": spec.get("num_local_experts", 0),
        "tp": spec["engine"].get("tp", 1),
        "weight_bytes": 1 if spec["engine"].get("quantize") == "int8"
        else DTYPE_BYTES[spec["engine"]["dtype"]],
        "kv_bytes": DTYPE_BYTES[spec["engine"]["dtype"]],
    }


def decode_weight_bytes(spec: dict) -> float:
    """Weight bytes one decode step must read on one chip: every linear of
    every layer and the output head (the embedding is a gather of a few
    rows). A 16-lane top-2 batch touches all 8 experts of a layer with
    probability 1 - 8 * (3/4)**16 > 0.9, so every expert counts."""
    d = _dims(spec)
    attn = d["hidden"] * d["head_dim"] * (2 * d["heads"] + 2 * d["kv_heads"])
    mlp = 3 * d["hidden"] * d["ffn"] * max(1, d["experts"])
    router = d["hidden"] * d["experts"]
    total = d["layers"] * (attn + mlp + router) + d["hidden"] * d["vocab"]
    return total * d["weight_bytes"] / d["tp"]


def kv_bytes_per_token_layer(spec: dict) -> float:
    """K and V of one token in one layer, on one chip."""
    d = _dims(spec)
    return 2 * d["kv_heads"] * d["head_dim"] * d["kv_bytes"] / d["tp"]


def decode_step_bytes(spec: dict, live_tokens: float) -> float:
    """Least bytes of one decode step: the weights once and the K/V of
    every live token in every layer."""
    d = _dims(spec)
    return (decode_weight_bytes(spec)
            + live_tokens * d["layers"] * kv_bytes_per_token_layer(spec))


def paged_decode_call(spec: dict, live_tokens: float, lanes: float) -> dict:
    """One call of the paged decode attention kernel (one layer, one step):
    reads K/V of the live tokens, q and the output of each lane."""
    d = _dims(spec)
    heads = d["heads"] / d["tp"]
    qo = 2 * lanes * heads * d["head_dim"] * d["kv_bytes"]
    return {
        "bytes": live_tokens * kv_bytes_per_token_layer(spec) + qo,
        # q.k and p.v: 2 multiply-adds per head, token and dimension.
        "flops": 4 * heads * d["head_dim"] * live_tokens,
    }


def flash_prefill_call(spec: dict, prompt_tokens: int) -> dict:
    """Causal self-attention of one prompt in one layer."""
    d = _dims(spec)
    heads, kv_heads = d["heads"] / d["tp"], d["kv_heads"] / d["tp"]
    t = prompt_tokens
    return {
        "bytes": t * (2 * heads + 2 * kv_heads) * d["head_dim"] * d["kv_bytes"],
        "flops": 4 * heads * d["head_dim"] * t * t / 2,
    }


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops > by_bytes else (by_bytes, "memory")
