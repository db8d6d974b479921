"""Int8 weight-only quantization tests."""

import dataclasses

import jax
import jax.numpy as jnp

from polykey_tpu.engine.sampling import SamplingParams
from polykey_tpu.models.config import TINY_LLAMA, TINY_MIXTRAL, TINY_GEMMA
from polykey_tpu.models.generate import generate
from polykey_tpu.models.quant import (
    dequantize,
    params_bytes,
    qdot,
    quantize,
    quantize_params,
)
from polykey_tpu.models.transformer import forward, init_params


def test_quantize_roundtrip_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    qt = quantize(w)
    assert qt.q.dtype == jnp.int8
    back = dequantize(qt, jnp.float32)
    # Per-channel symmetric int8: error <= scale/2 per entry.
    per_chan = jnp.max(jnp.abs(w), axis=0) / 127.0
    assert (jnp.abs(back - w) <= per_chan[None, :] * 0.51 + 1e-7).all()


def test_qdot_matches_dequantized_matmul():
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32), jnp.float32)
    qt = quantize(w)
    ref = x @ dequantize(qt, jnp.float32)
    out = qdot(x, qt)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-4


def test_quantized_tree_halves_storage():
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    qparams = quantize_params(params, cfg)
    # bf16 → int8 (+small fp32 scales): comfortably under 0.62x.
    assert params_bytes(qparams) < 0.62 * params_bytes(params)


def _logit_agreement(cfg, seed=0):
    params = init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    qparams = quantize_params(params, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16)).astype(jnp.int32)
    h_fp, _ = forward(params, cfg, tokens, pos, None)
    h_q, _ = forward(qparams, cfg, tokens, pos, None)
    assert jnp.isfinite(h_q.astype(jnp.float32)).all()
    # Int8 per-channel keeps hidden states close at tiny scale.
    denom = jnp.maximum(jnp.abs(h_fp.astype(jnp.float32)), 1.0)
    rel = jnp.abs(h_fp.astype(jnp.float32) - h_q.astype(jnp.float32)) / denom
    assert float(jnp.mean(rel)) < 0.05, float(jnp.mean(rel))


def test_quantized_forward_tracks_fp_llama():
    _logit_agreement(TINY_LLAMA)


def test_quantized_forward_tracks_fp_mixtral_both_formulations():
    _logit_agreement(TINY_MIXTRAL)
    _logit_agreement(dataclasses.replace(TINY_MIXTRAL, moe_dispatch=True))


def test_quantized_forward_tracks_fp_gemma():
    _logit_agreement(TINY_GEMMA)


def test_quantized_greedy_generation_runs_end_to_end():
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    qparams = quantize_params(params, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    seq_lens = jnp.full((2,), 8, jnp.int32)
    sampling = SamplingParams(max_new_tokens=12, temperature=0.0)
    out, n = generate(
        qparams, cfg, tokens, seq_lens, jax.random.PRNGKey(2), sampling,
        max_len=32,
    )
    assert (n == 12).all()
    assert ((out >= 0) & (out < cfg.vocab_size)).all()


# ---- int4 (group-wise) ----


def test_int4_roundtrip_error_bound():
    """Group-wise symmetric int4: per-entry error <= group scale / 2."""
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 32), jnp.float32)
    qt = quantize(w, bits=4, group_size=128)
    # Packed storage: uint8 nibble pairs along the contraction axis
    # (why not jnp.int4: models/quant.py).
    assert qt.q.dtype == jnp.uint8 and qt.bits == 4
    assert qt.q.shape == (128, 32) and qt.shape == (256, 32)
    assert qt.s.shape == (2, 32)
    back = dequantize(qt, jnp.float32)
    grouped = w.reshape(2, 128, 32)
    per_group = jnp.max(jnp.abs(grouped), axis=1) / 7.0        # [2, 32]
    err = jnp.abs(back.reshape(2, 128, 32) - grouped)
    assert (err <= per_group[:, None, :] * 0.51 + 1e-7).all()


def test_int4_group_size_shrinks_to_axis():
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 16), jnp.float32)
    qt = quantize(w, bits=4, group_size=128)   # 64 % 128 != 0 → one group
    assert qt.s.shape == (1, 16)
    assert jnp.isfinite(dequantize(qt, jnp.float32)).all()


def test_int4_qdot_matches_dequantized_matmul():
    w = jax.random.normal(jax.random.PRNGKey(5), (256, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 256), jnp.float32)
    qt = quantize(w, bits=4)
    ref = x @ dequantize(qt, jnp.float32)
    out = qdot(x, qt)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-3


def test_int4_tree_quarters_block_storage():
    """bits=4 tree: block linears int4 (+fp32 group scales), embed and
    lm_head stay int8 — total well under the int8 tree's bytes."""
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    q8 = quantize_params(params, cfg)
    q4 = quantize_params(params, cfg, bits=4)
    assert q4["layers"]["attn"]["wq"].q.dtype == jnp.uint8
    assert q4["layers"]["attn"]["wq"].bits == 4
    assert q4["layers"]["mlp"]["down"].q.dtype == jnp.uint8
    assert q4["layers"]["mlp"]["down"].bits == 4
    assert q4["embed"].q.dtype == jnp.int8
    if "lm_head" in q4:
        assert q4["lm_head"].q.dtype == jnp.int8
    assert params_bytes(q4) < params_bytes(q8)


def test_int4_forward_tracks_fp_all_families():
    """Same hidden-state agreement gate as int8, at a looser int4
    tolerance; all three families, both MoE formulations."""
    for cfg in (TINY_LLAMA, TINY_MIXTRAL, TINY_GEMMA,
                dataclasses.replace(TINY_MIXTRAL, moe_dispatch=True)):
        params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        qparams = quantize_params(params, cfg, bits=4)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(16), (2, 16)).astype(jnp.int32)
        h_fp, _ = forward(params, cfg, tokens, pos, None)
        h_q, _ = forward(qparams, cfg, tokens, pos, None)
        assert jnp.isfinite(h_q.astype(jnp.float32)).all()
        denom = jnp.maximum(jnp.abs(h_fp.astype(jnp.float32)), 1.0)
        rel = jnp.abs(
            h_fp.astype(jnp.float32) - h_q.astype(jnp.float32)) / denom
        # Tiny models quantize COARSELY: hidden 64 < group_size collapses
        # to one group per column (per-channel int4), and 2-layer MoE
        # routing amplifies flips — real 128-group models track far
        # tighter. This is a sanity gate, not an accuracy claim.
        assert float(jnp.mean(rel)) < 0.35, (cfg.name, float(jnp.mean(rel)))
