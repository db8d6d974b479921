"""The seeded fill's routers, centred as training centres them (PR 58).

Every body of a layer pattern whose activation is not odd (a mixer's silu,
a shared expert's relu^2, a gated expert's silu) adds the SAME vector
`W_out . E[a]` to every token's residual; the norm keeps it, and a router
filled with seeded normals then gives every token the same offset per
expert: the top-k of 64 tokens that share nothing fall on the same
favoured experts (fed different random tokens, 64 lanes hit 53-56 % of
nemotron's 128 held experts where independent choices hit 94 %: PERF.md
section 5, PR 58). A trained router does not carry that offset: its
correction bias (sigmoid scoring) or auxiliary loss (softmax) is moved by
the load until the experts' loads agree, which cancels exactly the part of
the logits that is common to all tokens. `centred` does the same to a
seeded tree: for each expert layer in order, the mean of the router's
input over a calibration sequence made from the seed, and every router
column loses its component along it,

    W <- W - m m^T W,   m = mean(u) / |mean(u)|

so the logits keep what differs from token to token and lose what does
not, for softmax and sigmoid scoring alike. Only `router` leaves change; on
the device, in the tree's dtype.

The router's input comes from the configuration's PLAIN REFERENCE
(perfbench/references/<module>: float32, one layer a call), not from the
program: ONE pass over the stack, each expert layer's router centred
before that layer is stepped, because a centred router changes what the
layers above it see. So the served weights are a function of `--seed` and
of benchmark files alone: a change to the program's numerics moves no
router, and the pass compiles each kind of layer once.
"""

from __future__ import annotations

TOKENS = 512               # the calibration sequence, seeded


def without_mean_direction(router, inputs):
    """`router` [H, experts] less every column's component along the mean
    of `inputs` [tokens, H]."""
    import jax.numpy as jnp

    mean = jnp.mean(inputs, axis=0)
    m = mean / jnp.linalg.norm(mean)
    w = router.astype(jnp.float32)
    return (w - jnp.outer(m, m @ w)).astype(router.dtype)


def calibration_tokens(seed: int, count: int = TOKENS):
    """Uniformly drawn printable-ASCII ids, from the seed."""
    import jax

    from traffic import FIRST_ID, LAST_ID

    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 58),
                              (count,), FIRST_ID, LAST_ID + 1)


def router_input(module, x, norm, cfg):
    """What the reference's expert layer hands its router: the layer's
    norm of the residual `x` (zero-centred gains where the configuration
    has them)."""
    offset = (cfg.norm_offset,) if getattr(cfg, "norm_offset", 0.0) else ()
    return module.rms_norm(x, norm, cfg.rms_norm_eps, *offset)


def centred(params: dict, cfg, seed: int, module) -> dict:
    """`params` with the router of every expert layer centred (module
    text), stepping `module`'s layers (a file of perfbench/references/)
    over the calibration sequence; every other leaf is the tree's own."""
    import jax
    import jax.numpy as jnp

    tokens = calibration_tokens(seed)
    centre = jax.jit(lambda x, norm, router: without_mean_direction(
        router, router_input(module, x, norm, cfg)))
    experts = list(params["layers"]["moe"])
    seen = {kind: 0 for kind in module.LAYERS}
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(
            params["embed"], tokens)
        for ch in cfg.layer_pattern:
            kind = module.KINDS[ch]
            i = seen[kind]
            seen[kind] += 1
            p = params["layers"][kind][i]
            if kind == "moe":
                p = {**p, "router": centre(x, p["norm"], p["router"])}
                experts[i] = p
                if i + 1 == len(experts):
                    break           # nothing above the last router is read
            x = jax.jit(module.LAYERS[kind], static_argnums=2)(x, p, cfg)
    return {**params,
            "layers": {**params["layers"], "moe": tuple(experts)}}
