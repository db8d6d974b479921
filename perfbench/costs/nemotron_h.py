"""Least bytes and operations of a Nemotron-H style hybrid stack as one
chip of its expert-parallel group runs it, from the sizes in the
configuration's file; plain Python, for one chip.

Counted for a decode step: every matrix of every layer once — of an
expert layer's held experts the share that some live lane chose
(`hit_share`: an expert nobody chose is not work, whoever reads it; 1.0,
all of them, where the program does not count the hits — a balanced router
at 64 lanes hits 94 %) — the output head's slice, the recurrent state of
every slot read and written (float32 h, bfloat16 conv columns), and K and
V of the live tokens in the layers that attend. Not counted: the embedding
(a gather of a few rows), gains and biases, activations.
"""

from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
STATE_BYTES = 4      # float32 h
ACT_BYTES = 2        # bfloat16 activations, conv columns, K and V


def _dims(spec: dict) -> dict:
    pattern = spec["hybrid_override_pattern"]
    inner = spec["mamba_num_heads"] * spec["mamba_head_dim"]
    conv_dim = inner + 2 * spec["n_groups"] * spec["ssm_state_size"]
    return {
        "hidden": spec["hidden_size"],
        "mamba": pattern.count("M"), "attn": pattern.count("*"),
        "moe": pattern.count("E"),
        "inner": inner, "conv_dim": conv_dim,
        "state": inner * spec["ssm_state_size"],
        "heads": spec["num_attention_heads"],
        "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"],
        "held": spec["n_routed_experts"],
        "latent": spec["moe_latent_size"],
        "expert": spec["moe_intermediate_size"],
        "shared": spec["moe_shared_expert_intermediate_size"],
        "router": spec["router_width"],
        "vocab": spec["vocab_size"],
        "slots": spec["engine"]["max_decode_slots"],
    }


def mamba_layer_params(spec: dict) -> float:
    d = _dims(spec)
    return (d["hidden"] * (d["inner"] + d["conv_dim"] + spec["mamba_num_heads"])
            + d["inner"] * d["hidden"])


def attention_layer_params(spec: dict) -> float:
    d = _dims(spec)
    return d["hidden"] * d["head_dim"] * 2 * (d["heads"] + d["kv_heads"])


def held_experts(spec: dict) -> int:
    """Experts this chip holds in one expert layer."""
    return _dims(spec)["held"]


def held_experts_params(spec: dict) -> float:
    """One expert layer's held experts: two matrices each."""
    d = _dims(spec)
    return d["held"] * 2 * d["latent"] * d["expert"]


def expert_layer_params(spec: dict, hit_share: float = 1.0) -> float:
    """Router, latent projections, shared expert, and the held experts
    that were hit."""
    d = _dims(spec)
    outside = d["hidden"] * (d["router"] + 2 * d["latent"] + 2 * d["shared"])
    return outside + hit_share * held_experts_params(spec)


def decode_weight_bytes(spec: dict, hit_share: float = 1.0) -> float:
    d = _dims(spec)
    return WEIGHT_BYTES * (
        d["mamba"] * mamba_layer_params(spec)
        + d["attn"] * attention_layer_params(spec)
        + d["moe"] * expert_layer_params(spec, hit_share)
        + d["hidden"] * d["vocab"])


def state_bytes_per_slot_layer(spec: dict) -> float:
    d = _dims(spec)
    return (d["state"] * STATE_BYTES
            + (spec["conv_kernel"] - 1) * d["conv_dim"] * ACT_BYTES)


def kv_bytes_per_token_layer(spec: dict) -> float:
    d = _dims(spec)
    return 2 * d["kv_heads"] * d["head_dim"] * ACT_BYTES


def decode_step_bytes(spec: dict, live_tokens: float,
                      hit_share: float = 1.0) -> float:
    d = _dims(spec)
    state = 2 * d["slots"] * d["mamba"] * state_bytes_per_slot_layer(spec)
    kv = live_tokens * d["attn"] * kv_bytes_per_token_layer(spec)
    return decode_weight_bytes(spec, hit_share) + state + kv


def ssm_state_update(spec: dict, lanes: float) -> dict:
    """One call (one layer, one step): every lane's h read and written;
    a multiply-add for the decay and the input and one for h . C per
    element. The per-head factors and y are kilobytes."""
    d = _dims(spec)
    elements = lanes * d["state"]
    small = lanes * (2 * d["inner"] + 2 * d["conv_dim"]) * STATE_BYTES
    return {"bytes": 2 * elements * STATE_BYTES + small,
            "flops": 5 * elements}


def chosen_pairs(spec: dict, rows: float) -> float:
    """The (row, held expert) pairs a call over `rows` tokens computes, in
    EXPECTATION under a balanced router: each row chooses top-k of the
    router's outputs, and the held are `held` of them — rows x top-k x
    held / router width (a row's choices that fall on another chip's
    experts are that chip's work)."""
    d = _dims(spec)
    return rows * spec["num_experts_per_tok"] * d["held"] / d["router"]


def moe_held_experts(spec: dict, rows: float, hit_share: float = 1.0) -> dict:
    """One call over `rows` tokens: the two matrices of every held expert
    that was hit once, the latent rows in and the float32 sum out; both
    products for the CHOSEN (row, held expert) pairs, in expectation
    (`chosen_pairs`: 64 x 22 x 128 / 512 = 352 at decode) — what the
    sorted kernel computes; the masked form's every-row-by-every-held-
    expert (8,192 pairs) is computed by nothing since PR 56 and is not the
    mathematics' to ask for. Memory-bound at decode widths at every hit
    share."""
    d = _dims(spec)
    weights = hit_share * held_experts_params(spec) * WEIGHT_BYTES
    rows_io = rows * d["latent"] * (ACT_BYTES + 4) + rows * d["held"] * 4
    return {"bytes": weights + rows_io,
            "flops": chosen_pairs(spec, rows) * 4 * d["latent"] * d["expert"]}
