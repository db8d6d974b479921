"""`perfbench/router_fill.py` (ISSUE 58): the seeded tree's routers lose
what every token's input has in common, so rows of different tokens choose
as independent rows do; no leaf but the routers' changes, and only in the
configurations whose files state it; the pass steps the configuration's
plain reference and nothing of the program. CPU, the configurations' toy
sizes, through each adapter's own `weights`."""

import json
import os

import numpy as np
import pytest

from perfbench_paths import BENCH

import extension
import router_fill

HYBRID = ["nemotron-3-super-ep4", "lfm2-24b-a2b-pp4",
          "qwen3-next-80b-a3b-ep4", "openpangu-ultra-moe-ep32"]
# lfm2 hit 97 % of its experts as seeded and openpangu's share did not
# follow its routers' mean (PERF.md sections 5 and 7): the parent's trees.
CENTRED = ["nemotron-3-super-ep4", "qwen3-next-80b-a3b-ep4"]
GROUP = 4          # rows a group: the toys' top-4 of 16 saturate at 64


class Engine:
    dtype = "float32"


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def built(name: str, seed: int):
    import jax

    from polykey_tpu.models.hybrid import init_params

    spec = spec_of(name)
    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, True)
    served = adapter.weights(spec, True, Engine, cfg, seed)
    seeded = init_params(jax.random.PRNGKey(seed), cfg, served["embed"].dtype)
    module = extension.load("references", spec["reference"]["module"])
    return cfg, served, seeded, module


def hit_shares(params, cfg, module, tokens):
    """Per expert layer: of the held experts, the share some row of a group
    of GROUP rows chose, averaged over the groups of `tokens`."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.ops.moe import held_weights

    shares = []
    seen = {kind: 0 for kind in module.LAYERS}
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[tokens]
        for ch in cfg.layer_pattern:
            kind = module.KINDS[ch]
            p = params["layers"][kind][seen[kind]]
            seen[kind] += 1
            if kind == "moe":
                u = router_fill.router_input(module, x, p["norm"], cfg)
                chosen = np.asarray(held_weights(
                    p, u.astype(params["embed"].dtype), cfg)) != 0
                groups = chosen.reshape(-1, GROUP, chosen.shape[-1]).any(axis=1)
                shares.append(float(groups.mean()))
            x = module.LAYERS[kind](x, p, cfg)
    return np.asarray(shares)


@pytest.mark.parametrize("seed", [11, 5800000207 % (2**31 - 1), 77])
@pytest.mark.parametrize("name", CENTRED)
def test_rows_of_different_tokens_choose_as_independent_rows_do(name, seed):
    cfg, served, seeded, module = built(name, seed)
    tokens = router_fill.calibration_tokens(seed + 1, 256)
    independent = 1 - (1 - cfg.num_experts_per_tok
                       / cfg.n_routed_experts) ** GROUP
    centred = hit_shares(served, cfg, module, tokens)
    plain = hit_shares(seeded, cfg, module, tokens)
    assert abs(centred.mean() - independent) < 0.10, (centred, independent)
    assert centred.mean() >= plain.mean() - 0.02, (centred, plain)


@pytest.mark.parametrize("name", HYBRID)
def test_no_leaf_but_the_stated_routers_differs(name):
    import jax

    cfg, served, seeded, _ = built(name, 11)
    if name.startswith("lfm2"):
        # The adapter narrows the tied matrix and adds an empty head leaf.
        served = {**served, "embed": seeded["embed"]}
        served.pop("lm_head")
    if name.startswith("openpangu"):
        # The adapter zeroes the routers' bias.
        seeded = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf * 0 if "router_bias" in str(path) else leaf,
            seeded)
    a, tree_a = jax.tree_util.tree_flatten_with_path(served)
    b, tree_b = jax.tree_util.tree_flatten_with_path(seeded)
    assert tree_a == tree_b
    moved = [jax.tree_util.keystr(path) for (path, x), (_, y) in zip(a, b)
             if not np.array_equal(np.asarray(x), np.asarray(y))]
    assert all(m.endswith("['router']") for m in moved), moved
    assert len(moved) == (cfg.layer_pattern.count("E")
                          if name in CENTRED else 0)
    stated = "perfbench/router_fill.py" in spec_of(name)["assumed"]["seeded_fills"]
    assert stated == (name in CENTRED)


@pytest.mark.parametrize("name", CENTRED)
def test_a_centred_router_gives_the_mean_input_no_logit(name):
    """What `centred` promises of each router: the mean of ITS input over
    the calibration sequence, met in order (the routers below it already
    centred), scores nothing."""
    import jax
    import jax.numpy as jnp

    cfg, served, _, module = built(name, 77)
    tokens = router_fill.calibration_tokens(77)
    seen = {kind: 0 for kind in module.LAYERS}
    with jax.default_matmul_precision("highest"):
        x = served["embed"].astype(jnp.float32)[tokens]
        for ch in cfg.layer_pattern:
            kind = module.KINDS[ch]
            p = served["layers"][kind][seen[kind]]
            seen[kind] += 1
            if kind == "moe":
                u = router_fill.router_input(module, x, p["norm"], cfg)
                mean = jnp.mean(u, axis=0)
                logits = mean @ p["router"].astype(jnp.float32)
                scale = jnp.linalg.norm(mean) * jnp.linalg.norm(
                    p["router"].astype(jnp.float32), axis=0)
                assert float(jnp.max(jnp.abs(logits) / scale)) < 1e-4
            x = module.LAYERS[kind](x, p, cfg)


def test_the_pass_steps_the_reference_not_the_program():
    with open(router_fill.__file__) as f:
        text = f.read()
    assert "import polykey_tpu" not in text and "from polykey_tpu" not in text
