"""The adapter of the test configuration `own-code` (installed as
perfbench/adapters/own_code.py by tests/perfbench/own_code.py): a
pre- and post-normed GeGLU decoder with capped logits, whose file spells
every size in its own words. Nothing under perfbench/ knows those words
or this block; they reach the engine through the hooks below."""

from __future__ import annotations


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab"],
        hidden_size=src["width"],
        intermediate_size=src["ffn_width"],
        num_layers=src["depth"],
        num_heads=src["q_heads"],
        num_kv_heads=src["kv_heads"],
        head_dim=src["head_width"],
        max_seq_len=src["positions"],
        rope_theta=float(src["rope_base"]),
        rms_norm_eps=float(src["norm_eps"]),
        activation="gelu_tanh",
        use_post_norms=True,
        final_logit_softcap=float(src["logit_cap"]),
    )


def weights(spec: dict, tiny: bool, engine_config, model_cfg, seed: int):
    """Made by the benchmark on the device; the post-norms' gains are
    named to it as leaves to fill with ones."""
    import jax
    import jax.numpy as jnp

    import weights as made
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(tp=engine_config.tp),
                       devices=jax.devices()[:engine_config.tp])
    return made.hashed_int8(model_cfg, mesh, jnp.dtype(engine_config.dtype),
                            seed, ones=("ln1", "ln2", "post_ln1", "post_ln2",
                                        "final_norm"))


def release(engine) -> None:
    """The two paged pools, and a third of a name only this file knows."""
    for pool in ("paged", "d_paged", "own_pool"):
        if hasattr(engine, pool):
            setattr(engine, pool, None)
