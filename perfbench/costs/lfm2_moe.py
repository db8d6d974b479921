"""Least bytes and operations of an LFM2-MoE style stack as the chip that
holds a whole pipeline stage runs it, from the sizes in the configuration's
file; plain Python, for one chip.

Counted for a decode step: every matrix of every layer once — of an
expert layer's held experts, three matrices each, the share that some
live lane chose (`hit_share`: an expert nobody chose is not work, whoever
reads it; 1.0, all of them, where the program does not count the hits —
at 64 lanes x top-4 of 64 a balanced router hits each with probability
98 %) — the tied vocabulary matrix once, as the head; the conv columns of every slot read and written;
and K and V of the live tokens in the layers that attend. Not counted: the
embedding lookup (a gather of a few rows of the same matrix), gains and
the router's bias, activations.
"""

from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
ACT_BYTES = 2        # bfloat16 activations, conv columns, K and V


def _dims(spec: dict) -> dict:
    ops = spec["layer_types"]
    layers = len(ops)
    dense = min(spec["num_dense_layers"], layers)
    heads = spec["num_attention_heads"]
    return {
        "hidden": spec["hidden_size"],
        "conv": ops.count("conv"), "attn": ops.count("full_attention"),
        "dense": dense, "moe": layers - dense,
        "heads": heads, "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec.get("head_dim") or spec["hidden_size"] // heads,
        "dense_width": spec["intermediate_size"],
        "held": spec["num_experts"],
        "expert": spec["moe_intermediate_size"],
        "taps": spec["conv_L_cache"],
        "vocab": spec["vocab_size"],
        "slots": spec["engine"]["max_decode_slots"],
    }


def conv_layer_params(spec: dict) -> float:
    """W_in (hidden -> 3 hidden) and W_out."""
    d = _dims(spec)
    return 4 * d["hidden"] * d["hidden"]


def attention_layer_params(spec: dict) -> float:
    d = _dims(spec)
    return d["hidden"] * d["head_dim"] * 2 * (d["heads"] + d["kv_heads"])


def dense_layer_params(spec: dict) -> float:
    d = _dims(spec)
    return 3 * d["hidden"] * d["dense_width"]


def held_experts(spec: dict) -> int:
    """Experts this chip holds in one expert layer."""
    return _dims(spec)["held"]


def held_experts_params(spec: dict) -> float:
    """One expert layer's held experts: three matrices each."""
    d = _dims(spec)
    return d["held"] * 3 * d["hidden"] * d["expert"]


def expert_layer_params(spec: dict, hit_share: float = 1.0) -> float:
    """The router and the three matrices of every held expert that was
    hit."""
    d = _dims(spec)
    return d["hidden"] * d["held"] + hit_share * held_experts_params(spec)


def decode_weight_bytes(spec: dict, hit_share: float = 1.0) -> float:
    d = _dims(spec)
    return WEIGHT_BYTES * (
        d["conv"] * conv_layer_params(spec)
        + d["attn"] * attention_layer_params(spec)
        + d["dense"] * dense_layer_params(spec)
        + d["moe"] * expert_layer_params(spec, hit_share)
        + d["hidden"] * d["vocab"])


def state_bytes_per_slot_layer(spec: dict) -> float:
    """The conv's last K-1 columns of B . u."""
    d = _dims(spec)
    return (d["taps"] - 1) * d["hidden"] * ACT_BYTES


def kv_bytes_per_token_layer(spec: dict) -> float:
    d = _dims(spec)
    return 2 * d["kv_heads"] * d["head_dim"] * ACT_BYTES


def decode_step_bytes(spec: dict, live_tokens: float,
                      hit_share: float = 1.0) -> float:
    d = _dims(spec)
    state = 2 * d["slots"] * d["conv"] * state_bytes_per_slot_layer(spec)
    kv = live_tokens * d["attn"] * kv_bytes_per_token_layer(spec)
    return decode_weight_bytes(spec, hit_share) + state + kv


def chosen_pairs(spec: dict, rows: float) -> float:
    """The (row, held expert) pairs a call over `rows` tokens computes, in
    EXPECTATION under a balanced router: each row chooses top-k of the
    router's outputs, and the held are `held` of them — rows x top-k x
    held / router width (a row's choices that fall on another chip's
    experts are that chip's work)."""
    d = _dims(spec)
    return rows * spec["num_experts_per_tok"] * d["held"] / spec.get("router_width", d["held"])


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
