"""The adapter of configurations of the LFM2-MoE family (gated short-conv
operators and QK-normed RoPE attention by `layer_types`, each followed by a
dense or a sigmoid-routed gated-expert feed-forward part, one tied
matrix): their sizes in the source's own words -> the package's
ModelConfig, the seeded weights made on the device, and `release`, which
is the sibling hybrid configuration's (its file is loaded, not copied; the
configuration's `reference.replay` names that file for `replay` too).
Contract: the docstring of perfbench/run.py.

The pattern. The package walks ONE body an entry (models/hybrid.py), so a
published layer — an operator and a feed-forward part under two norms —
is two entries: `layer_types[l]` gives "C" (conv) or "*" (full_attention),
then "D" for l < `num_dense_layers`, else "E". The first 10 published
layers are `CDCD*ECECECE*ECECECE`.
"""

from __future__ import annotations

import extension

OPERATORS = {"conv": "C", "full_attention": "*"}
SIBLING = "nemotron_h.py"


def layer_pattern(layer_types: list, num_dense_layers: int) -> str:
    return "".join(
        OPERATORS[op] + ("D" if layer < num_dense_layers else "E")
        for layer, op in enumerate(layer_types))


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    if len(src["layer_types"]) != src["num_hidden_layers"]:
        raise ValueError("layer_types must name num_hidden_layers layers")
    if not (spec["use_expert_bias"] and spec["norm_topk_prob"]):
        raise ValueError("the router here chooses by score + bias and "
                         "weighs by score over the chosen scores' sum")
    pattern = layer_pattern(src["layer_types"], src["num_dense_layers"])
    heads = src["num_attention_heads"]
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["moe_intermediate_size"],
        num_layers=len(pattern),
        num_heads=heads,
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src.get("head_dim") or src["hidden_size"] // heads,
        max_seq_len=src["engine_max_positions"],
        rope_theta=float(spec["rope_parameters"]["rope_theta"]),
        rms_norm_eps=float(spec["norm_eps"]),
        tie_embeddings=True,
        activation="silu",
        layer_pattern=pattern,
        use_rope=True,
        qk_norm=True,
        conv_kernel=src["conv_L_cache"],
        dense_intermediate_size=src["intermediate_size"],
        n_routed_experts=src["num_experts"],
        experts_held=src["num_experts"],
        first_expert=0,
        num_experts_per_tok=src["num_experts_per_tok"],
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        router_norm_eps=1e-6,
    )


def narrowed_rows(embed):
    """The tied matrix with every row outside printable ASCII zero: as a
    head, what perfbench/server_child.py `narrow_head` makes of an untied
    one (those ids' logits are 0, so every generated token streams as one
    character); as a lookup table, ids the traffic never sends (BOS, id 1,
    is the one exception: it enters the stack as a zero row, in the
    program and in the reference alike)."""
    import jax
    import jax.numpy as jnp

    from traffic import FIRST_ID, LAST_ID

    ids = jnp.arange(embed.shape[0])[:, None]
    keep = (ids >= FIRST_ID) & (ids <= LAST_ID)
    return jax.jit(lambda e: e * keep.astype(e.dtype), donate_argnums=0)(embed)


def weights(spec: dict, tiny: bool, engine_config, model_cfg, seed: int):
    """The package's own seeded init of the stack (one entry a jitted call
    on the device: no leaf ever exists in float32 or on the host), keyed
    by `seed`, with the head narrowed HERE: `narrow_head` knows an untied
    `lm_head` leaf only and exits on a tree without one. The tree carries
    ONE vocabulary matrix, `embed`, read by the lookup and by the logits;
    `lm_head` is an EMPTY leaf ([0, vocab], no bytes, read by nothing in
    the program) that tells `narrow_head` there is nothing left for it to
    narrow. PERF.md section 7 asks a `benchmark` PR for a tied branch
    there, after which both lines below go."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.models.hybrid import init_params

    dtype = jnp.dtype(engine_config.dtype)
    params = init_params(jax.random.PRNGKey(seed), model_cfg, dtype)
    params["embed"] = narrowed_rows(params["embed"])
    params["lm_head"] = jnp.zeros((0, model_cfg.vocab_size), dtype)
    return params


def release(engine) -> None:
    """The sibling adapter's: the paged pool and the per-slot state."""
    extension.load("adapters", SIBLING).release(engine)
