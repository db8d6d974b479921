"""Least bytes and operations of the test configuration `own-code`
(installed as perfbench/costs/own_code.py), from the sizes as ITS file
spells them; for one chip, plain Python."""

from __future__ import annotations

KV_BYTES = 2        # bf16 K and V
WEIGHT_BYTES = 1    # int8


def decode_step_bytes(spec: dict, live_tokens: float) -> float:
    """Every linear of every layer and the output head once, the four
    norm gains of a layer, and K and V of every live token."""
    width, head = spec["width"], spec["head_width"]
    attn = width * head * (2 * spec["q_heads"] + 2 * spec["kv_heads"])
    ffn = 3 * width * spec["ffn_width"]
    gains = 4 * width * KV_BYTES
    weights = (spec["depth"] * ((attn + ffn) * WEIGHT_BYTES + gains)
               + width * spec["vocab"] * WEIGHT_BYTES)
    kv = 2 * spec["kv_heads"] * head * KV_BYTES
    return weights + live_tokens * spec["depth"] * kv


def reshape_squeeze(spec: dict) -> dict:
    """One relayout of one layer's K or V pool: the folded pool written
    once (its read is not counted: the least a relayout must move)."""
    eng = spec["engine"]
    pool = (eng["num_pages"] * eng["page_size"] * spec["kv_heads"]
            * spec["head_width"] * KV_BYTES)
    return {"bytes": pool, "flops": 0}
