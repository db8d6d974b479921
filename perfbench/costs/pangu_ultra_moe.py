"""Least bytes and operations of an openPangu-Ultra-MoE style stack as ONE
chip of a 32-way expert-parallel group runs it, from the sizes in the
configuration's file; plain Python, for one chip.

Counted for a decode step: every matrix of every layer once — a latent
attention layer's five (W_dq, W_uq, W_dkv, W_ukv's two halves, W_o), the
dense layer's three, of an expert layer the router, the shared expert's
three and, of the held experts, three matrices each, the share that some
live lane chose (`hit_share`: an expert nobody chose is not work, whoever
reads it; 1.0, all of them, where the program does not count the hits —
top-8 of 256 at 64 lanes leaves each of the 8 held unchosen with
probability 13 %) — and the head's slice; and the ONE latent row of every
live token in every layer, at the PUBLISHED width (kv_lora_rank +
qk_rope_head_dim columns: the program stores the row padded to whole
128-lane tiles, 640 for 576, and the padding is the program's cost, not
the mathematics'). Not counted: the embedding lookup (a gather of a few
rows), gains, activations.
"""

from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
ACT_BYTES = 2        # bfloat16 activations and latent rows
# The context a lane holds where a caller gives no live tokens: the mean of
# the cell's traffic (prompts 68-480, outputs 256-768, half served).
TYPICAL_CONTEXT = 450


def _dims(spec: dict) -> dict:
    layers = spec["num_hidden_layers"]
    dense = min(spec["first_k_dense_replace"], layers)
    return {
        "hidden": spec["hidden_size"],
        "layers": layers, "dense": dense, "moe": layers - dense,
        "heads": spec["num_attention_heads"],
        "q_rank": spec["q_lora_rank"], "kv_rank": spec["kv_lora_rank"],
        "nope": spec["qk_nope_head_dim"], "rope": spec["qk_rope_head_dim"],
        "v": spec["v_head_dim"],
        "dense_width": spec["intermediate_size"],
        "held": spec["n_routed_experts"],
        "routed": spec["router_width"],
        "expert": spec["moe_intermediate_size"],
        "shared": spec["n_shared_experts"] * spec["moe_intermediate_size"],
        "vocab": spec["vocab_size"],
    }


def latent_layer_params(spec: dict) -> float:
    """W_dq, W_uq, W_dkv, W_ukv (keys' and values' halves) and W_o."""
    d = _dims(spec)
    row = d["kv_rank"] + d["rope"]
    return (d["hidden"] * (d["q_rank"] + row)
            + d["heads"] * (d["q_rank"] * (d["nope"] + d["rope"])
                            + d["kv_rank"] * (d["nope"] + d["v"])
                            + d["v"] * d["hidden"]))


def held_experts(spec: dict) -> int:
    """Experts this chip holds in one expert layer."""
    return _dims(spec)["held"]


def held_experts_params(spec: dict) -> float:
    """One expert layer's held experts: three matrices each."""
    d = _dims(spec)
    return d["held"] * 3 * d["hidden"] * d["expert"]


def expert_layer_params(spec: dict, hit_share: float = 1.0) -> float:
    """The router, the three matrices of every held expert that was hit
    and the shared expert's three."""
    d = _dims(spec)
    return (d["hidden"] * d["routed"]
            + hit_share * held_experts_params(spec)
            + 3 * d["hidden"] * d["shared"])


def decode_weight_bytes(spec: dict, hit_share: float = 1.0) -> float:
    d = _dims(spec)
    return WEIGHT_BYTES * (
        d["layers"] * latent_layer_params(spec)
        + d["dense"] * 3 * d["hidden"] * d["dense_width"]
        + d["moe"] * expert_layer_params(spec, hit_share)
        + d["hidden"] * d["vocab"])


def kv_bytes_per_token_layer(spec: dict) -> float:
    """ONE row a token and layer: the latent beside the shared rotary key."""
    d = _dims(spec)
    return (d["kv_rank"] + d["rope"]) * ACT_BYTES


def decode_step_bytes(spec: dict, live_tokens: float,
                      hit_share: float = 1.0) -> float:
    d = _dims(spec)
    rows = live_tokens * d["layers"] * kv_bytes_per_token_layer(spec)
    return decode_weight_bytes(spec, hit_share) + rows


def mla_latent_decode(spec: dict, lanes: float,
                      live_tokens: float | None = None) -> dict:
    """One call (one layer, one step) over `lanes` lanes holding
    `live_tokens` tokens in all: each token's row read ONCE for all heads,
    each lane's absorbed query heads in (kv_rank + rope wide) and latent
    sums out (kv_rank wide); per token and head a multiply-add over the
    row for the score and one over its latent part for the sum. 157 FLOP a
    byte at 450 tokens a lane, 242 in the limit of long contexts against
    the chip's 240.5: memory-bound, on the ridge."""
    d = _dims(spec)
    if live_tokens is None:
        live_tokens = lanes * TYPICAL_CONTEXT
    row = d["kv_rank"] + d["rope"]
    heads_io = lanes * d["heads"] * (row + d["kv_rank"]) * ACT_BYTES
    return {
        "bytes": live_tokens * kv_bytes_per_token_layer(spec) + heads_io,
        "flops": live_tokens * d["heads"] * 2 * (row + d["kv_rank"]),
    }


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
