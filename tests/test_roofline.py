"""Roofline accounting (engine/roofline.py): the physics scorecard every
bench phase emits (VERDICT r4 #4). Pins the geometry math so a silent
formula regression can't skew every artifact's mbu/mfu at once."""

import pytest

from polykey_tpu.engine.roofline import (
    CHIP_SPECS,
    decode_flops_per_token,
    detect_chip,
    grade,
    kv_bytes_per_token,
    prefill_flops,
    weight_read_bytes,
)
from polykey_tpu.models.config import get_config


def test_8b_geometry():
    cfg = get_config("llama-3-8b")
    # ~8.03e9 params; int8 weight read ~= params minus the gathered-only
    # embedding table (~0.5 GB), i.e. ~7.5 GB.
    assert 8.0e9 < cfg.num_params() < 8.1e9
    w8 = weight_read_bytes(cfg, "bfloat16", True, 8)
    assert 7.4e9 < w8 < 7.6e9
    # bf16 doubles it; int4 halves the block weights but not the head.
    assert weight_read_bytes(cfg, "bfloat16", False, 8) == pytest.approx(
        2 * w8, rel=0.01)
    w4 = weight_read_bytes(cfg, "bfloat16", True, 4)
    assert 0.5 * w8 < w4 < 0.6 * w8
    # GQA KV: 2 * 32 layers * 8 kv heads * 128 dim * 2 B = 128 KiB/token.
    assert kv_bytes_per_token(cfg, "bfloat16") == 2 * 32 * 8 * 128 * 2
    assert kv_bytes_per_token(cfg, "int8") == 2 * 32 * 8 * 128
    # Decode FLOPs ~ 2 * params at short context.
    assert decode_flops_per_token(cfg, 0) == pytest.approx(
        2 * cfg.num_params(), rel=1e-6)
    # Prefill FLOPs scale superlinearly (attention P^2 term).
    assert prefill_flops(cfg, 2048) > 16 * prefill_flops(cfg, 128)
    # Dense weight reads are lane-independent.
    assert weight_read_bytes(cfg, "bfloat16", True, 8, lanes=32) == w8


def test_moe_active_params_and_step_reads():
    cfg = get_config("mixtral-8x7b")
    active = cfg.num_active_params()
    assert active < cfg.num_params() / 2     # top-2 of 8 experts
    assert active > cfg.num_params() / 8     # attn + 2 experts > 1/8
    # Per-STEP weight reads grow with lanes until every expert is hit
    # (batched MoE decode does NOT amortize experts the way dense does —
    # code-review r5), then saturate at the full expert set.
    w1 = weight_read_bytes(cfg, "bfloat16", True, 8, lanes=1)
    w4 = weight_read_bytes(cfg, "bfloat16", True, 8, lanes=4)
    w16 = weight_read_bytes(cfg, "bfloat16", True, 8, lanes=16)
    w64 = weight_read_bytes(cfg, "bfloat16", True, 8, lanes=64)
    assert w1 < w4 <= w16 == w64   # saturates at num_experts=8 by 4 lanes
    # At saturation every parameter streams: ~ num_params * 1 B (int8),
    # minus the gathered-only embedding table.
    assert w16 == pytest.approx(
        cfg.num_params() - cfg.vocab_size * cfg.hidden_size, rel=0.02)


def test_grade_tpu_fields():
    spec = CHIP_SPECS["tpu-v5e"]
    g = grade("llama-3-8b", "bfloat16", True, 8, "int8",
              tok_s=117.9, avg_lanes=7.1, avg_ctx=192,
              p50_ttft_ms=150.0, prompt_len=128, chip=spec)
    assert g["chip"] == "tpu-v5e"
    assert g["avg_lanes_source"] == "measured"
    # r3's measured 117.9 tok/s at 7.1 lanes grades to ~15% MBU — the
    # occupancy diagnosis (PERF.md) expressed as physics.
    assert 0.10 < g["mbu"] < 0.20
    assert 0 < g["mfu"] < 0.05
    # Weight amortization: more lanes -> higher roofline ceiling.
    g32 = grade("llama-3-8b", "bfloat16", True, 8, "int8",
                tok_s=117.9, avg_lanes=32, avg_ctx=192, chip=spec)
    assert g32["roofline_tok_s"] > 2 * g["roofline_tok_s"]
    # The north-star 2,000 tok/s is BELOW the 32-lane int8-KV roofline —
    # i.e. the target is physically reachable on one v5e chip.
    assert g32["roofline_tok_s"] > 2000


def test_grade_draft_and_chips():
    spec = CHIP_SPECS["tpu-v5e"]
    base = grade("llama-3-8b", "bfloat16", True, 8, "int8",
                 tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec)
    # draft == target doubles the weight stream (bench phase C shape).
    spec_g = grade("llama-3-8b", "bfloat16", True, 8, "int8",
                   tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec,
                   draft_model="llama-3-8b")
    assert spec_g["weight_read_bytes"] == pytest.approx(
        2 * base["weight_read_bytes"], rel=1e-6)
    assert spec_g["roofline_tok_s"] < base["roofline_tok_s"]
    # n_chips scales the roofline denominator (tp/ep phases).
    multi = grade("llama-3-8b", "bfloat16", True, 8, "int8",
                  tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec,
                  n_chips=4)
    assert multi["mbu"] == pytest.approx(base["mbu"] / 4, rel=1e-3)
    assert multi["roofline_tok_s"] == pytest.approx(
        4 * base["roofline_tok_s"], rel=1e-3)


def test_grade_unmeasured_lanes_flagged():
    # No loop-trace counter -> the scorecard says the occupancy is
    # assumed, never passing an unmeasured number off as data.
    g = grade("llama-3-8b", "bfloat16", True, 8, "int8",
              tok_s=100.0, avg_lanes=None, avg_ctx=192,
              chip=CHIP_SPECS["tpu-v5e"], assumed_lanes=32.0)
    assert g["avg_lanes_source"] == "assumed_full"
    assert g["avg_lanes"] == 32.0


def test_grade_cpu_null_utilization():
    g = grade("tiny-llama", "bfloat16", False, 8, "",
              tok_s=2900.0, avg_lanes=4, avg_ctx=24, chip=None)
    assert g["chip"] is None and g["mbu"] is None and g["mfu"] is None
    assert g["bytes_per_token"] > 0 and g["flops_per_token"] > 0


def test_detect_chip_off_tpu():
    # Tests force JAX_PLATFORMS=cpu (conftest), so detection returns None.
    assert detect_chip() is None


def test_grade_hbm_weight_fraction():
    spec = CHIP_SPECS["tpu-v5e"]
    g = grade("llama-3-8b", "bfloat16", True, 8, "int8",
              tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec)
    # ~8 GB of int8-resident weights on a 16 GiB chip: roughly half the
    # HBM is weights, the rest is the KV-page (decode slot) budget.
    assert 0.4 < g["hbm_weight_fraction"] < 0.6
    # bf16 doubles residency; the draft adds its own tree.
    g_bf16 = grade("llama-3-8b", "bfloat16", False, 8, "",
                   tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec)
    assert g_bf16["hbm_weight_fraction"] > 1.5 * g["hbm_weight_fraction"]
    g_draft = grade("llama-3-8b", "bfloat16", True, 8, "int8",
                    tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec,
                    draft_model="llama-3-8b")
    assert g_draft["hbm_weight_fraction"] == pytest.approx(
        2 * g["hbm_weight_fraction"], rel=0.01)
    # Off-chip runs have no capacity denominator.
    g_cpu = grade("tiny-llama", "bfloat16", False, 8, "",
                  tok_s=100.0, avg_lanes=4, avg_ctx=24, chip=None)
    assert "hbm_weight_fraction" not in g_cpu


def test_grade_resident_fraction_extends_without_breaking_replay():
    """ISSUE 17: passing the pool bytes folds device KV + scale pools
    into a full-residency fraction as NEW sibling fields —
    hbm_weight_fraction keeps its weights-only meaning and committed
    BENCH artifacts (graded without the pool) replay with the same
    schema."""
    from polykey_tpu.engine.roofline import kv_pool_bytes_spec
    from polykey_tpu.models.config import get_config

    spec = CHIP_SPECS["tpu-v5e"]
    base = grade("llama-3-8b", "bfloat16", True, 8, "int8",
                 tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec)
    assert "hbm_resident_fraction" not in base     # replay-compatible
    assert "hbm_kv_pool_bytes" not in base
    pool = kv_pool_bytes_spec(get_config("llama-3-8b"), 2048, 16, "int8")
    g = grade("llama-3-8b", "bfloat16", True, 8, "int8",
              tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec,
              kv_pool_bytes=pool)
    assert g["hbm_weight_fraction"] == base["hbm_weight_fraction"]
    assert g["hbm_kv_pool_bytes"] == round(pool)
    assert g["hbm_resident_fraction"] == pytest.approx(
        g["hbm_weight_fraction"] + pool / spec.hbm_bytes, abs=2e-4)
    assert g["hbm_resident_fraction"] < 1.0        # the config fits
    # Multi-chip: the pool shards with the weights.
    g4 = grade("llama-3-8b", "bfloat16", True, 8, "int8",
               tok_s=100.0, avg_lanes=8, avg_ctx=192, chip=spec,
               n_chips=4, kv_pool_bytes=pool)
    assert g4["hbm_resident_fraction"] == pytest.approx(
        g["hbm_resident_fraction"] / 4, rel=1e-3)
    # Off-chip runs still emit no capacity fields at all.
    g_cpu = grade("tiny-llama", "bfloat16", False, 8, "",
                  tok_s=100.0, avg_lanes=4, avg_ctx=24, chip=None,
                  kv_pool_bytes=pool)
    assert "hbm_resident_fraction" not in g_cpu


def test_detect_chip_unknown_tpu_kind_raises(monkeypatch):
    """Only explicit v5e/v5p kinds map. An unknown TPU kind must neither
    grade against another chip's roofline (ADVICE r5: old code silently
    picked v5p) nor silently skip grading: it raises until the table has
    its peaks. Off-TPU stays None."""
    import jax as _jax

    class _Dev:
        def __init__(self, kind, platform="tpu"):
            self.platform = platform
            self.device_kind = kind

    for kind, expected in (
        ("TPU v5 lite", "tpu-v5e"),
        ("TPU v5e", "tpu-v5e"),
        ("TPU v5p", "tpu-v5p"),
    ):
        monkeypatch.setattr(_jax, "devices", lambda k=kind: [_Dev(k)])
        assert detect_chip().name == expected, kind
    for kind in ("TPU v5x-mystery", "TPU v6e", "warp-drive"):
        monkeypatch.setattr(_jax, "devices", lambda k=kind: [_Dev(k)])
        with pytest.raises(ValueError, match="CHIP_SPECS"):
            detect_chip()
    monkeypatch.setattr(_jax, "devices", lambda: [_Dev("cpu", "cpu")])
    assert detect_chip() is None
