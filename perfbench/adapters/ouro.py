"""The adapter of configurations of the Ouro family (looped language
models: ONE stack of sandwich-normed multi-head layers run `total_ut_steps`
times a token with the same weights, an exit gate on every pass): their
sizes in the source's own words -> the package's ModelConfig. Nothing else:
the weights are the package's own seeded init (the file's `engine.weights`
"package_init", as the dense sibling takes them: no `weights` here), the
engine's geometry the file's `engine` group, `release` the harness's own
(the paged pool), and the configuration's `reference.replay` names the
sibling hybrid configuration's adapter, whose `replay` drives
`forward_slots` + `unembed` for any model the package serves.
Contract: the docstring of perfbench/run.py.
"""

from __future__ import annotations


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = {**spec, **spec["tiny"]["model"]} if tiny else spec
    if set(spec["layer_types"]) != {"full_attention"}:
        raise ValueError("every layer here attends in full: layer_types "
                         "all full_attention")
    if spec["sliding_window"] is not None:
        raise ValueError("no layer here has a window: sliding_window null")
    if len(spec["layer_types"]) != spec["num_hidden_layers"]:
        raise ValueError("layer_types must name num_hidden_layers layers")
    heads = src["num_attention_heads"]
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["intermediate_size"],
        num_layers=src["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src.get("head_dim") or src["hidden_size"] // heads,
        max_seq_len=src["engine_max_positions"],
        rope_theta=float(spec["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=spec["tie_word_embeddings"],
        activation=spec["hidden_act"],
        use_post_norms=True,
        loop_steps=src["total_ut_steps"],
        early_exit_threshold=float(spec["early_exit_threshold"]),
    )

