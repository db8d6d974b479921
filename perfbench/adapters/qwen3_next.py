"""The adapter of configurations of the Qwen3-Next family (Gated DeltaNet
linear-attention layers and output-gated softmax attention by
`full_attention_interval`, each followed by softmax-routed gated experts
with a gated shared expert, zero-centred norms, an untied head): their
sizes in the source's own words -> the package's ModelConfig, the seeded
weights made on the device, and `release`, which is the sibling hybrid
configuration's (its file is loaded, not copied; the configuration's
`reference.replay` names that file for `replay` too). Contract: the
docstring of perfbench/run.py.

The pattern. The package walks ONE body an entry (models/hybrid.py), so a
published layer — an operator and its expert layer under two norms — is two
entries: layer l is "*" (full attention) where (l + 1) mod
`full_attention_interval` = 0, else "L" (linear attention), then "E"
(`decoder_sparse_step` 1 and `mlp_only_layers` []: every layer has
experts). The first 12 published layers are `LELELE*E` three times.
"""

from __future__ import annotations

import extension

SIBLING = "nemotron_h.py"


def layer_pattern(layers: int, full_attention_interval: int) -> str:
    return "".join(
        ("*" if (layer + 1) % full_attention_interval == 0 else "L") + "E"
        for layer in range(layers))


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    if spec["decoder_sparse_step"] != 1 or spec["mlp_only_layers"]:
        raise ValueError("every layer here has experts: decoder_sparse_step "
                         "1, mlp_only_layers []")
    if not spec["norm_topk_prob"]:
        raise ValueError("the router here weighs a chosen expert by its "
                         "probability over the chosen ones' sum")
    pattern = layer_pattern(src["num_hidden_layers"],
                            spec["full_attention_interval"])
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["moe_intermediate_size"],
        num_layers=len(pattern),
        num_heads=src["num_attention_heads"],
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src["head_dim"],
        max_seq_len=src["engine_max_positions"],
        rope_theta=float(spec["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=spec["tie_word_embeddings"],
        activation=spec["hidden_act"],
        layer_pattern=pattern,
        use_rope=True,
        qk_norm=True,
        partial_rotary_factor=float(spec["partial_rotary_factor"]),
        attn_output_gate=True,
        norm_offset=1.0,
        delta_key_heads=src["linear_num_key_heads"],
        delta_value_heads=src["linear_num_value_heads"],
        delta_key_dim=src["linear_key_head_dim"],
        delta_value_dim=src["linear_value_head_dim"],
        conv_kernel=spec["linear_conv_kernel_dim"],
        n_routed_experts=src["router_width"],
        experts_held=src["num_experts"],
        first_expert=src["first_expert"],
        num_experts_per_tok=src["num_experts_per_tok"],
        moe_shared_intermediate=src["shared_expert_intermediate_size"],
        router_scoring="softmax",
        shared_expert_gate=True,
    )


def weights(spec: dict, tiny: bool, engine_config, model_cfg, seed: int):
    """The sibling adapter's: the package's own seeded init of a layer
    pattern, one entry a jitted call on the device, keyed by `seed`."""
    return extension.load("adapters", SIBLING).weights(
        spec, tiny, engine_config, model_cfg, seed)


def release(engine) -> None:
    """The sibling adapter's: the paged pool and the per-slot state."""
    extension.load("adapters", SIBLING).release(engine)
