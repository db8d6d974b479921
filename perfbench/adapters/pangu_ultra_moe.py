"""The adapter of configurations of the openPangu-Ultra-MoE family
(multi-head latent attention in every layer, sandwich norms, leading dense
layers, then sigmoid-routed gated experts with one plain shared expert, an
untied head): their sizes in the source's own words -> the package's
ModelConfig, the seeded weights made on the device, and `release`, which
is the sibling hybrid configuration's (its file is loaded, not copied; the
configuration's `reference.replay` names that file for `replay` too).
Contract: the docstring of perfbench/run.py.

The pattern. The package walks ONE body an entry (models/hybrid.py), so a
published layer — latent attention and a feed-forward part, each between
two norms — is two entries: "A", then "D" for l < `first_k_dense_replace`,
else "E". The 7 layers here are `ADAEAEAEAEAEAE`.
"""

from __future__ import annotations

import extension

SIBLING = "nemotron_h.py"


def layer_pattern(layers: int, first_k_dense_replace: int) -> str:
    return "".join(
        "A" + ("D" if layer < first_k_dense_replace else "E")
        for layer in range(layers))


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    if not (spec["norm_topk_prob"] and spec["sandwich_norm"]):
        raise ValueError("the router here weighs a chosen expert by its "
                         "score over the chosen ones' sum, and every body "
                         "stands between two norms")
    if spec["n_shared_experts"] != 1 or spec["attention_bias"]:
        raise ValueError("one plain shared expert and no projection bias "
                         "are what is computed")
    pattern = layer_pattern(src["num_hidden_layers"],
                            src["first_k_dense_replace"])
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["moe_intermediate_size"],
        num_layers=len(pattern),
        num_heads=src["num_attention_heads"],
        # What the cache holds is ONE row a token: no K/V heads exist.
        num_kv_heads=1,
        head_dim=src["qk_nope_head_dim"] + src["qk_rope_head_dim"],
        max_seq_len=src["engine_max_positions"],
        rope_theta=float(spec["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=spec["tie_word_embeddings"],
        activation=spec["hidden_act"],
        layer_pattern=pattern,
        sandwich_norm=True,
        q_lora_rank=src["q_lora_rank"],
        kv_lora_rank=src["kv_lora_rank"],
        qk_nope_head_dim=src["qk_nope_head_dim"],
        qk_rope_head_dim=src["qk_rope_head_dim"],
        v_head_dim=src["v_head_dim"],
        dense_intermediate_size=src["intermediate_size"],
        n_routed_experts=src["router_width"],
        experts_held=src["n_routed_experts"],
        first_expert=src["first_expert"],
        num_experts_per_tok=src["num_experts_per_tok"],
        moe_shared_intermediate=(
            spec["n_shared_experts"] * src["moe_intermediate_size"]),
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        router_scoring="sigmoid",
    )


def without_router_bias(params):
    """The sigmoid router of the package chooses by score + bias; this
    family's config states no correction bias: the leaf is zeros."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map_with_path(
        lambda path, w: (jnp.zeros_like(w)
                         if path[-1].key == "router_bias" else w), params)


def weights(spec: dict, tiny: bool, engine_config, model_cfg, seed: int):
    """The package's own seeded init of the stack (one entry a jitted call
    on the device: no leaf ever exists in float32 or on the host), keyed
    by `seed`, the routers' bias zero."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.models.hybrid import init_params

    return without_router_bias(init_params(
        jax.random.PRNGKey(seed), model_cfg, jnp.dtype(engine_config.dtype)))


def release(engine) -> None:
    """The sibling adapter's: the paged pool (and the empty slot state)."""
    extension.load("adapters", SIBLING).release(engine)
