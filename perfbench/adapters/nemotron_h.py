"""The adapter of configurations of the Nemotron-H family (hybrid stacks:
Mamba-2 mixers, attention and latent expert layers by a pattern string):
their sizes in the source's own words -> the package's ModelConfig, the
seeded weights made on the device, what the engine holds beside its
parameters, and `replay`: the program's own logits for the served sample,
which the configuration's reference `compare` holds to the reference's.
Contract: the docstring of perfbench/run.py."""

from __future__ import annotations


def model_config(spec: dict, tiny: bool):
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["moe_intermediate_size"],
        num_layers=src["num_hidden_layers"],
        num_heads=src["num_attention_heads"],
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src["head_dim"],
        max_seq_len=src["engine_max_positions"],
        rope_theta=float(src["rope_theta"]),
        rms_norm_eps=float(src["layer_norm_epsilon"]),
        activation="relu2",
        layer_pattern=src["hybrid_override_pattern"],
        use_rope=False,
        mamba_num_heads=src["mamba_num_heads"],
        mamba_head_dim=src["mamba_head_dim"],
        ssm_state_size=src["ssm_state_size"],
        ssm_groups=src["n_groups"],
        conv_kernel=src["conv_kernel"],
        ssm_chunk=src["chunk_size"],
        n_routed_experts=src["router_width"],
        experts_held=src["n_routed_experts"],
        first_expert=src["first_expert"],
        num_experts_per_tok=src["num_experts_per_tok"],
        moe_latent_size=src["moe_latent_size"],
        moe_shared_intermediate=src["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(src["routed_scaling_factor"]),
    )


def weights(spec: dict, tiny: bool, engine_config, model_cfg, seed: int):
    """The package's own seeded init of a hybrid stack (one layer a jitted
    call on the device: no leaf ever exists in float32 or on the host),
    keyed by `seed`, its routers centred as training centres them over
    the configuration's own plain reference (perfbench/router_fill.py)."""
    import jax
    import jax.numpy as jnp

    import extension
    import router_fill
    from polykey_tpu.models.hybrid import init_params

    params = init_params(jax.random.PRNGKey(seed), model_cfg,
                         jnp.dtype(engine_config.dtype))
    return router_fill.centred(
        params, model_cfg, seed,
        extension.load("references", spec["reference"]["module"]))


def replay_logits(params, paged, state, tokens, table, fed, n, *, cfg):
    """`replay`'s device part: tokens [1, window] hold the prompt's `n`
    ids, `table` [lanes, pages] lane 0's pages, `fed` the served ids but
    the last. Returns float32 logits [1 + len(fed), vocab]."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.models.hybrid import FROM_ZERO, PrefillRows
    from polykey_tpu.models.transformer import forward_slots, unembed

    lane0 = jnp.arange(table.shape[0]) == 0
    rows = PrefillRows(*(jnp.asarray([v], jnp.int32)
                         for v in (0, FROM_ZERO, 0, n)))
    hidden, paged, state = forward_slots(
        params, cfg, tokens, jnp.arange(tokens.shape[1])[None], paged,
        table[:1], state, rows=rows)
    first = unembed(params, cfg, hidden[0, n - 1])

    def one(carry, inputs):
        paged, state = carry
        token, position = inputs
        hidden, paged, state = forward_slots(
            params, cfg, jnp.where(lane0, token, 0)[:, None],
            jnp.where(lane0, position, 0)[:, None], paged, table, state,
            active=lane0)
        return (paged, state), unembed(params, cfg, hidden[0, 0])

    _, rest = jax.lax.scan(
        one, (paged, state), (fed, n + jnp.arange(fed.shape[0])))
    return jnp.concatenate([first[None], rest]).astype(jnp.float32)


def replay(params, model_cfg, prompt_ids, served_ids, lanes: int,
           page_size: int, window: int):
    """The program's own logits for the served sample, float32
    [len(served_ids), vocab]: the prompt as ONE prefill row of `window`
    from zero state, then the served tokens fed back one decode step at a
    time to lane 0 of `lanes` (the other lanes inactive, as when the sample
    was served alone), through `forward_slots` and `unembed` with a pool
    and per-slot state of the engine's geometry - the calls of
    engine.engine `_prefill_fn` / `_decode_fn`, returning the logits those
    sample from. Row i is what the program chose served_ids[i] from."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polykey_tpu.engine.kv_cache import init_paged_kv, init_slot_state

    n, dtype = len(prompt_ids), params["embed"].dtype
    # The table covers the window's padded tail as the engine's does.
    pages = -(-max(n + len(served_ids), window) // page_size)
    table = jnp.zeros((lanes, pages), jnp.int32).at[0].set(
        jnp.arange(1, pages + 1))               # page 0: the garbage page
    tokens = jnp.zeros((1, window), jnp.int32).at[0, :n].set(
        jnp.asarray(prompt_ids, jnp.int32))
    run = jax.jit(replay_logits, static_argnames="cfg")
    return np.asarray(run(
        params, init_paged_kv(model_cfg, pages + 1, page_size, dtype),
        init_slot_state(model_cfg, lanes, dtype), tokens, table,
        jnp.asarray(served_ids[:-1], jnp.int32), jnp.int32(n),
        cfg=model_cfg))


def release(engine) -> None:
    """The paged pool and the per-slot recurrent state beside it."""
    for held in ("paged", "d_paged", "state"):
        if hasattr(engine, held):
            setattr(engine, held, None)
