"""The adapter of configurations of the Olmo-Hybrid family (Gated DeltaNet
linear-attention layers whose write strength reaches 2, and full multi-head
attention without a position embedding under ONE q/k norm over the whole
projection, by `layer_types`; each followed by a dense gated MLP; every
body post-normed and none pre-normed; an untied head): their sizes in the
source's own words -> the package's ModelConfig, the seeded weights made on
the device, and `release`, which is the sibling hybrid configuration's (its
file is loaded, not copied; the configuration's `reference.replay` names
that file for `replay` too). Contract: the docstring of perfbench/run.py.

The pattern. The package walks ONE body an entry (models/hybrid.py), so a
published layer — a mixer and its MLP, each under a norm of its own — is
two entries: `layer_types[l]` gives "L" (linear_attention) or "*"
(full_attention), then "D". The first 16 published layers are `LDLDLD*D`
four times.
"""

from __future__ import annotations

import extension

MIXERS = {"linear_attention": "L", "full_attention": "*"}
SIBLING = "nemotron_h.py"


def layer_pattern(layer_types: list) -> str:
    return "".join(MIXERS[mixer] + "D" for mixer in layer_types)


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    if len(src["layer_types"]) != src["num_hidden_layers"]:
        raise ValueError("layer_types must name num_hidden_layers layers")
    if spec["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the attending layers here carry no position "
                         "embedding: rope_parameters.rope_theta null")
    if spec["attention_bias"]:
        raise ValueError("no projection here has a bias")
    pattern = layer_pattern(src["layer_types"])
    heads = src["num_attention_heads"]
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["intermediate_size"],
        num_layers=len(pattern),
        num_heads=heads,
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src.get("head_dim") or src["hidden_size"] // heads,
        max_seq_len=src["engine_max_positions"],
        rms_norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=spec["tie_word_embeddings"],
        activation=spec["hidden_act"],
        layer_pattern=pattern,
        use_rope=False,
        qk_norm=True,
        qk_norm_span="projection",
        pre_norm=False,
        sandwich_norm=True,
        delta_key_heads=src["linear_num_key_heads"],
        delta_value_heads=src["linear_num_value_heads"],
        delta_key_dim=src["linear_key_head_dim"],
        delta_value_dim=src["linear_value_head_dim"],
        delta_beta_scale=2.0 if spec["linear_allow_neg_eigval"] else 1.0,
        conv_kernel=spec["linear_conv_kernel_dim"],
        dense_intermediate_size=src["intermediate_size"],
    )


def weights(spec: dict, tiny: bool, engine_config, model_cfg, seed: int):
    """The package's own seeded init of the stack (one entry a jitted call
    on the device: no leaf ever exists in float32 or on the host), keyed
    by `seed`. (No router to centre: the pattern has no expert layer.)"""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.models.hybrid import init_params

    return init_params(jax.random.PRNGKey(seed), model_cfg,
                       jnp.dtype(engine_config.dtype))


def release(engine) -> None:
    """The sibling adapter's: the paged pool and the per-slot state."""
    extension.load("adapters", SIBLING).release(engine)
