"""Least bytes and operations of an Olmo-Hybrid style stack as ONE chip of a
two-stage pipeline runs its stage, from the sizes in the configuration's
file; plain Python, for one chip.

Counted for a decode step: every matrix of every layer once and the whole
head; the delta rule's float32 state S of every slot read and written, the
conv columns likewise (the program reads every lane) — at their PUBLISHED
size, 30 x 96 x 192 a slot and layer, however the program lays them out:
what a layout pads is lost roofline, not work; and K and V of the live
tokens in the layers that attend (30 KV heads: 15,360 B a token and
layer). Not counted: the embedding lookup (a gather of a few rows), gains,
A_log and dt_bias, activations.
"""

from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
ACT_BYTES = 2        # bfloat16 activations, conv columns, K and V
STATE_BYTES = 4      # float32 delta-rule state


def _dims(spec: dict) -> dict:
    mixers = spec["layer_types"]
    heads = spec["num_attention_heads"]
    return {
        "hidden": spec["hidden_size"],
        "delta": mixers.count("linear_attention"),
        "attn": mixers.count("full_attention"),
        "mlps": len(mixers),
        "heads": heads,
        "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec.get("head_dim") or spec["hidden_size"] // heads,
        "key": spec["linear_num_key_heads"] * spec["linear_key_head_dim"],
        "value": (spec["linear_num_value_heads"]
                  * spec["linear_value_head_dim"]),
        "value_heads": spec["linear_num_value_heads"],
        "state": (spec["linear_num_value_heads"] * spec["linear_key_head_dim"]
                  * spec["linear_value_head_dim"]),      # H x Dk x Dv
        "taps": spec["linear_conv_kernel_dim"],
        "mlp": spec["intermediate_size"],
        "vocab": spec["vocab_size"],
        "slots": spec["engine"]["max_decode_slots"],
    }


def delta_layer_params(spec: dict) -> float:
    """W_qkvz, W_ba, the conv's taps and W_out."""
    d = _dims(spec)
    conv = 2 * d["key"] + d["value"]
    return (d["hidden"] * (conv + d["value"] + 2 * d["value_heads"])
            + conv * d["taps"] + d["value"] * d["hidden"])


def attention_layer_params(spec: dict) -> float:
    d = _dims(spec)
    return d["hidden"] * d["head_dim"] * (2 * d["heads"] + 2 * d["kv_heads"])


def mlp_params(spec: dict) -> float:
    d = _dims(spec)
    return 3 * d["hidden"] * d["mlp"]


def stage_params(spec: dict) -> float:
    """Every matrix this chip holds: its layers, the embedding, the head."""
    d = _dims(spec)
    return (d["delta"] * delta_layer_params(spec)
            + d["attn"] * attention_layer_params(spec)
            + d["mlps"] * mlp_params(spec) + 2 * d["hidden"] * d["vocab"])


def decode_weight_bytes(spec: dict) -> float:
    """What a step reads of them: all but the embedding."""
    d = _dims(spec)
    return WEIGHT_BYTES * (stage_params(spec) - d["hidden"] * d["vocab"])


def state_bytes_per_slot_layer(spec: dict) -> float:
    """S of every head as published, and the conv's last K-1 columns of
    q|k|v."""
    d = _dims(spec)
    return (d["state"] * STATE_BYTES
            + (d["taps"] - 1) * (2 * d["key"] + d["value"]) * ACT_BYTES)


def kv_bytes_per_token_layer(spec: dict) -> float:
    d = _dims(spec)
    return 2 * d["kv_heads"] * d["head_dim"] * ACT_BYTES


def decode_step_bytes(spec: dict, live_tokens: float) -> float:
    d = _dims(spec)
    state = 2 * d["slots"] * d["delta"] * state_bytes_per_slot_layer(spec)
    kv = live_tokens * d["attn"] * kv_bytes_per_token_layer(spec)
    return decode_weight_bytes(spec) + state + kv


def gated_delta_state_update(spec: dict, lanes: float) -> dict:
    """One call (one layer, one step): every lane's S read and written at
    its published size; per element a multiply-add for each of S^T k and
    S^T q and two for the decayed state plus k (x) d. The per-head vectors
    (k, q, v, o) are kilobytes."""
    d = _dims(spec)
    elements = lanes * d["state"]
    small = lanes * (2 * d["key"] + 2 * d["value"]) * STATE_BYTES
    return {"bytes": 2 * elements * STATE_BYTES + small,
            "flops": 6 * elements}
