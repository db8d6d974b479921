"""Least bytes and operations of a Qwen3-Next style stack as ONE chip of a
four-chip expert-parallel pipeline stage runs it, from the sizes in the
configuration's file; plain Python, for one chip.

Counted for a decode step: every matrix of every layer once — of an
expert layer's held experts, three matrices each, the share that some
live lane chose (`hit_share`: an expert nobody chose is not work, whoever
reads it; 1.0, all of them, where the program does not count the hits —
top-10 of 512 at 64 lanes leaves 28 % of the 128 held unchosen) — and the
head's slice; the delta rule's
float32 state S of every slot read and written, the conv columns likewise
(the program reads every lane); and K and V of the live tokens in the
layers that attend. Not counted: the embedding lookup (a gather of a few
rows), gains, A_log and dt_bias, activations.
"""

from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
ACT_BYTES = 2        # bfloat16 activations, conv columns, K and V
STATE_BYTES = 4      # float32 delta-rule state


def _dims(spec: dict) -> dict:
    layers, every = spec["num_hidden_layers"], spec["full_attention_interval"]
    attn = layers // every
    key = spec["linear_num_key_heads"] * spec["linear_key_head_dim"]
    value = spec["linear_num_value_heads"] * spec["linear_value_head_dim"]
    return {
        "hidden": spec["hidden_size"],
        "delta": layers - attn, "attn": attn, "moe": layers,
        "heads": spec["num_attention_heads"],
        "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"],
        "key": key, "value": value,
        "value_heads": spec["linear_num_value_heads"],
        "state": value * spec["linear_key_head_dim"],   # Hv x Dk x Dv
        "taps": spec["linear_conv_kernel_dim"],
        "held": spec["num_experts"],
        "routed": spec["router_width"],
        "expert": spec["moe_intermediate_size"],
        "shared": spec["shared_expert_intermediate_size"],
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
    """W_q yields a gate beside the query: three head-widths a query head."""
    d = _dims(spec)
    return d["hidden"] * d["head_dim"] * (3 * d["heads"] + 2 * d["kv_heads"])


def held_experts(spec: dict) -> int:
    """Experts this chip holds in one expert layer."""
    return _dims(spec)["held"]


def held_experts_params(spec: dict) -> float:
    """One expert layer's held experts: three matrices each."""
    d = _dims(spec)
    return d["held"] * 3 * d["hidden"] * d["expert"]


def expert_layer_params(spec: dict, hit_share: float = 1.0) -> float:
    """The router, the three matrices of every held expert that was hit,
    the shared expert's three and its gate's vector."""
    d = _dims(spec)
    return (d["hidden"] * d["routed"]
            + hit_share * held_experts_params(spec)
            + 3 * d["hidden"] * d["shared"] + d["hidden"])


def decode_weight_bytes(spec: dict, hit_share: float = 1.0) -> float:
    d = _dims(spec)
    return WEIGHT_BYTES * (
        d["delta"] * delta_layer_params(spec)
        + d["attn"] * attention_layer_params(spec)
        + d["moe"] * expert_layer_params(spec, hit_share)
        + d["hidden"] * d["vocab"])


def state_bytes_per_slot_layer(spec: dict) -> float:
    """S of every value head, and the conv's last K-1 columns of q|k|v."""
    d = _dims(spec)
    return (d["state"] * STATE_BYTES
            + (d["taps"] - 1) * (2 * d["key"] + d["value"]) * ACT_BYTES)


def kv_bytes_per_token_layer(spec: dict) -> float:
    d = _dims(spec)
    return 2 * d["kv_heads"] * d["head_dim"] * ACT_BYTES


def decode_step_bytes(spec: dict, live_tokens: float,
                      hit_share: float = 1.0) -> float:
    d = _dims(spec)
    state = 2 * d["slots"] * d["delta"] * state_bytes_per_slot_layer(spec)
    kv = live_tokens * d["attn"] * kv_bytes_per_token_layer(spec)
    return decode_weight_bytes(spec, hit_share) + state + kv


def gated_delta_state_update(spec: dict, lanes: float) -> dict:
    """One call (one layer, one step): every lane's S read and written;
    per element a multiply-add for each of S^T k and S^T q and two for the
    decayed state plus k (x) d. The per-head vectors (k, q, v, o) are
    kilobytes."""
    d = _dims(spec)
    elements = lanes * d["state"]
    small = lanes * (2 * d["key"] + 2 * d["value"]) * STATE_BYTES
    return {"bytes": 2 * elements * STATE_BYTES + small,
            "flops": 6 * elements}


def chosen_pairs(spec: dict, rows: float) -> float:
    """The (row, held expert) pairs a call over `rows` tokens computes, in
    EXPECTATION under a balanced router: each row chooses top-k of the
    router's outputs, and the held are `held` of them — rows x top-k x
    held / router width (a row's choices that fall on another chip's
    experts are that chip's work)."""
    d = _dims(spec)
    return rows * spec["num_experts_per_tok"] * d["held"] / d["routed"]


def moe_held_experts(spec: dict, rows: float, hit_share: float = 1.0) -> dict:
    """One call over `rows` tokens: the three matrices of every held
    expert that was hit once, the hidden rows in and the float32 sum out,
    the combine weights; all three products for the CHOSEN (row, held
    expert) pairs, in expectation (`chosen_pairs`) — what the sorted
    kernel computes; the masked form's every-row-by-every-held-expert is
    computed by nothing since PR 56. Memory-bound at decode widths at
    every hit share."""
    d = _dims(spec)
    weights = hit_share * held_experts_params(spec) * WEIGHT_BYTES
    rows_io = rows * d["hidden"] * (ACT_BYTES + 4) + rows * d["held"] * 4
    return {"bytes": weights + rows_io,
            "flops": chosen_pairs(spec, rows) * 6 * d["hidden"] * d["expert"]}
