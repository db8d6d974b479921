"""Model-core tests: forward correctness, cache consistency, generation.

Tiny configs on CPU (conftest forces an 8-device CPU platform). The key
invariant everywhere: the cached incremental path (prefill + decode steps)
must produce the same tokens as full no-cache forwards — this is the
correctness oracle for every later cache/kernels change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine.sampling import SamplingParams, sample
from polykey_tpu.models.config import TINY_GEMMA, TINY_LLAMA
from polykey_tpu.models.generate import decode_step, generate, prefill
from polykey_tpu.models.layers import qkv_project
from polykey_tpu.models.quant import qdot, quantize_params
from polykey_tpu.models.transformer import (
    forward,
    init_cache,
    init_params,
    unembed,
)
from polykey_tpu.parallel.mesh import MeshConfig, create_mesh
from polykey_tpu.parallel.sharding import shard_params


@pytest.fixture(scope="module")
def llama_setup():
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return cfg, params


def test_forward_shapes(llama_setup):
    cfg, params = llama_setup
    tokens = jnp.array([[1, 5, 9, 2], [1, 7, 0, 0]], dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(4), (2, 4)).astype(jnp.int32)
    hidden, cache = forward(params, cfg, tokens, positions, None)
    assert hidden.shape == (2, 4, cfg.hidden_size)
    assert cache is None
    logits = unembed(params, cfg, hidden)
    assert logits.shape == (2, 4, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_cached_matches_uncached(llama_setup):
    """Prefill-with-cache hidden states == no-cache forward hidden states."""
    cfg, params = llama_setup
    B, T = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)

    hidden_nc, _ = forward(params, cfg, tokens, positions, None)
    cache = init_cache(cfg, B, 16, jnp.float32)
    hidden_c, cache = forward(params, cfg, tokens, positions, cache)
    np.testing.assert_allclose(
        np.asarray(hidden_nc), np.asarray(hidden_c), rtol=2e-4, atol=2e-4
    )


def test_incremental_decode_matches_full_forward(llama_setup):
    """Token-by-token decode == one-shot forward over the whole sequence."""
    cfg, params = llama_setup
    B, T = 2, 6
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)

    full_hidden, _ = forward(params, cfg, tokens, positions, None)
    full_logits = unembed(params, cfg, full_hidden[:, -1])

    # Prefill the first 3 tokens, then decode the rest one at a time.
    cache = init_cache(cfg, B, T + 2, jnp.float32)
    seq_lens = jnp.full((B,), 3, dtype=jnp.int32)
    _, cache = prefill(params, cfg, tokens[:, :3], seq_lens, cache)
    logits = None
    for t in range(3, T):
        logits, cache = decode_step(
            params, cfg, tokens[:, t], jnp.full((B,), t, dtype=jnp.int32), cache
        )
    np.testing.assert_allclose(
        np.asarray(full_logits), np.asarray(logits), rtol=2e-4, atol=2e-4
    )


def test_prefill_respects_padding(llama_setup):
    """Right padding must not change the last-real-token logits."""
    cfg, params = llama_setup
    prompt = jnp.array([[1, 5, 9]], dtype=jnp.int32)
    padded = jnp.array([[1, 5, 9, 0, 0]], dtype=jnp.int32)
    lens3 = jnp.array([3], dtype=jnp.int32)

    cache_a = init_cache(cfg, 1, 8, jnp.float32)
    logits_a, _ = prefill(params, cfg, prompt, lens3, cache_a)
    cache_b = init_cache(cfg, 1, 8, jnp.float32)
    logits_b, _ = prefill(params, cfg, padded, lens3, cache_b)
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), rtol=2e-4, atol=2e-4
    )


def test_generate_greedy_deterministic(llama_setup):
    cfg, params = llama_setup
    tokens = jnp.array([[1, 10, 20, 0], [1, 30, 0, 0]], dtype=jnp.int32)
    seq_lens = jnp.array([3, 2], dtype=jnp.int32)
    sampling = SamplingParams(temperature=0.0, max_new_tokens=6)

    out1, n1 = generate(
        params, cfg, tokens, seq_lens, jax.random.PRNGKey(0), sampling, 16
    )
    out2, n2 = generate(
        params, cfg, tokens, seq_lens, jax.random.PRNGKey(7), sampling, 16
    )
    assert out1.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert (np.asarray(n1) == 6).all()  # no eos configured → all steps used


def test_generate_stops_at_eos(llama_setup):
    cfg, params = llama_setup
    tokens = jnp.array([[1, 10, 20, 0]], dtype=jnp.int32)
    seq_lens = jnp.array([3], dtype=jnp.int32)
    sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
    out, n = generate(
        params, cfg, tokens, seq_lens, jax.random.PRNGKey(0), sampling, 16
    )
    # Force an eos: pick the first greedy token as the eos id, so the row
    # finishes immediately and the remaining slots are filled with eos.
    eos = int(out[0, 0])
    out2, n2 = generate(
        params, cfg, tokens, seq_lens, jax.random.PRNGKey(0), sampling, 16,
        eos_id=eos,
    )
    assert int(n2[0]) == 1
    assert (np.asarray(out2)[0] == eos).all()


def test_gemma_features_forward():
    """Gemma-2 config exercises softcaps, post-norms, sliding window, tied
    embeddings, scaled embeddings."""
    cfg = TINY_GEMMA
    params = init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    assert "lm_head" not in params
    assert "post_ln1" in jax.tree_util.tree_map(lambda x: x, params["layers"])
    B, T = 2, 24  # longer than the tiny sliding window (16)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    hidden, _ = forward(params, cfg, tokens, positions, None)
    logits = unembed(params, cfg, hidden)
    caps = float(cfg.final_logit_softcap)
    arr = np.asarray(logits)
    assert np.isfinite(arr).all()
    assert (np.abs(arr) <= caps + 1e-3).all()  # final softcap bounds logits


def test_gemma_cached_matches_uncached():
    cfg = TINY_GEMMA
    params = init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    B, T = 1, 20
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    hidden_nc, _ = forward(params, cfg, tokens, positions, None)
    cache = init_cache(cfg, B, 32, jnp.float32)
    hidden_c, _ = forward(params, cfg, tokens, positions, cache)
    np.testing.assert_allclose(
        np.asarray(hidden_nc), np.asarray(hidden_c), rtol=3e-4, atol=3e-4
    )


def test_sampling_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.array([[0.0, 3.0, 1.0, -2.0]], dtype=jnp.float32)
    assert int(sample(logits, key, SamplingParams(temperature=0.0))[0]) == 1
    # top_k=1 is equivalent to greedy regardless of temperature.
    assert (
        int(sample(logits, key, SamplingParams(temperature=5.0, top_k=1))[0]) == 1
    )
    # top_p tiny keeps only the argmax.
    assert (
        int(sample(logits, key, SamplingParams(temperature=1.0, top_p=0.01))[0])
        == 1
    )
    # High temperature sampling stays within the vocab and varies with key.
    params = SamplingParams(temperature=2.0)
    draws = {
        int(sample(logits, jax.random.PRNGKey(i), params)[0]) for i in range(20)
    }
    assert draws.issubset({0, 1, 2, 3}) and len(draws) > 1


def test_mixtral_bench_fits_one_chip():
    """mixtral-bench (bench phase E) must keep the 8x7B architecture —
    8 experts, top-2, dispatch routing — while its int8 tree + KV fit a
    16 GiB v5e chip; a config drift that silently fattens it would turn
    the MoE hardware phase into an OOM."""
    import jax

    from polykey_tpu.models.config import MIXTRAL_8X7B, get_config
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.models.transformer import init_params

    cfg = get_config("mixtral-bench")
    assert cfg.num_experts == MIXTRAL_8X7B.num_experts == 8
    assert cfg.num_experts_per_tok == MIXTRAL_8X7B.num_experts_per_tok == 2
    assert cfg.moe_dispatch and MIXTRAL_8X7B.moe_dispatch

    tree = jax.eval_shape(
        lambda: quantize_params(
            init_params(jax.random.PRNGKey(0), cfg, "bfloat16"), cfg, bits=8))
    total = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    # int8 weights well under half the chip: leaves room for 16 slots of
    # KV pages, activations, and the compiler's scratch.
    assert total < 6 * 2**30, f"mixtral-bench int8 tree is {total/2**30:.1f} GiB"


def folded_qkv(p, x, cfg):
    """`qkv_project` as it stood before ISSUE 44: each product reshaped to
    heads at once (on a TPU the compiler folds that reshape into the q and
    k products wherever a dimension of 1 stands beside the rows)."""
    B, T, _ = x.shape
    return (
        qdot(x, p["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim),
        qdot(x, p["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim),
        qdot(x, p["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim),
    )


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("rows,width", [(3, 1), (1, 128)])
@pytest.mark.parametrize("leaves", ["bf16", "int8", "int4"])
def test_qkv_project_is_the_same_product_bit_for_bit(leaves, rows, width, tp):
    """Holding q, k, v flat behind a barrier until the products are done
    changes where the head split happens, not one bit of what it splits:
    decode ([B, 1, H]) and one-row prefill ([1, T, H]) shapes, every kind
    of weight leaf, one device and a tp = 2 mesh."""
    cfg = TINY_LLAMA
    params = init_params(jax.random.PRNGKey(3), cfg, jnp.bfloat16)
    if leaves != "bf16":
        params = quantize_params(params, cfg, bits=int(leaves[3:]))
    if tp > 1:
        mesh = create_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
        params = shard_params(params, cfg, mesh)
    x = jax.random.normal(
        jax.random.PRNGKey(4), (rows, width, cfg.hidden_size), jnp.bfloat16)

    def run(project):
        return jax.jit(lambda params, x: project(
            jax.tree.map(lambda leaf: leaf[1], params["layers"]["attn"]),
            x, cfg,
        ))(params, x)

    got, want = run(qkv_project), run(folded_qkv)
    heads = (cfg.num_heads, cfg.num_kv_heads, cfg.num_kv_heads)
    for g, w, n in zip(got, want, heads):
        assert g.shape == (rows, width, n, cfg.head_dim) and g.dtype == w.dtype
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
        )
