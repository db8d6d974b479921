"""Cases of the grouped held-experts product (ops/hybrid_kernels.py
`moe_held_experts_grouped`) against `moe_held_experts_jnp` on the dense
weights of the same routing, shared by tests/test_hybrid.py (the un-gated
instance) and tests/test_lfm2.py (the gated one), and the rule that
chooses between the forms of `moe_held`. Small shapes, the kernels in
interpret mode, float32: the forms differ in summation order only."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from polykey_tpu.ops import hybrid_kernels, moe

ROWS = (300, 512, 1024)
# name → (experts published, held, first held, top-k)
ROUTINGS = {
    "uniform": (8, 8, 0, 2),
    # Every row on ONE expert: a run longer than a tile.
    "one-expert": (8, 8, 0, 2),
    "unchosen-expert": (8, 8, 0, 2),
    # A third of the rows choose no held expert at all.
    "rows-without-held": (32, 8, 8, 6),
    "top-k-over-held": (16, 4, 4, 6),
}
L, INNER = 128, 256


def routing(name: str, rows: int, key):
    """(chosen [rows, k] over the published experts, weights [rows, k])."""
    published, held, first, k = ROUTINGS[name]
    scores = jax.random.uniform(key, (rows, published))
    if name == "one-expert":
        scores = scores.at[:, first + 1].set(2.0)
    elif name == "unchosen-expert":
        scores = scores.at[:, first + 2].set(-1.0)
    elif name == "rows-without-held":
        scores = scores.at[: rows // 3, first:first + held].set(-1.0)
    chosen, idx = jax.lax.top_k(scores, k)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def check(rows: int, name: str, gated: bool) -> None:
    published, held, first, k = ROUTINGS[name]
    key = jax.random.split(jax.random.PRNGKey(rows + 7 * k + gated), 5)
    v = jax.random.normal(key[0], (rows, L))
    up = jax.random.normal(key[1], (held, L, INNER)) * L ** -0.5
    down = jax.random.normal(key[2], (held, INNER, L)) * INNER ** -0.5
    how = {"activation": "relu2"}
    if gated:
        how = {"gate": jax.random.normal(key[3], (held, L, INNER)) * L ** -0.5,
               "activation": "silu"}
    idx, weight = routing(name, rows, key[4])
    dense = jnp.sum(jax.nn.one_hot(idx, published) * weight[..., None],
                    axis=-2)[:, first:first + held]
    if name == "one-expert":
        assert int(jnp.sum(dense[:, 1] > 0)) == rows > hybrid_kernels.MOE_GROUP_TILE
    elif name == "unchosen-expert":
        assert not bool(jnp.any(dense[:, 2] > 0))
    elif name == "rows-without-held":
        assert int(jnp.sum(jnp.all(dense == 0, axis=-1))) >= rows // 3
    want = hybrid_kernels.moe_held_experts_jnp(v, up, down, dense, **how)
    # The experts' width streams as two blocks: the accumulation over
    # blocks and a long run's walk back over them are on the path.
    with mock.patch.object(hybrid_kernels, "_inner_tile", lambda inner, *_: 128):
        got = hybrid_kernels.moe_held_experts_grouped(
            v, up, down, dense, chosen=min(k, held), interpret=True, **how)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4)   # summation order
    # No row missing, none doubled: a row's result is its own.
    quiet = np.asarray(jnp.all(dense == 0, axis=-1))
    assert not np.asarray(got)[quiet].any()


# (rows, the form `moe_held` must take): off the chip the jnp form at any
# width; on it — the gate answering as the chip would — the masked kernel
# for a decode step's 64 rows and a one-window prefill's 128, the grouped
# one from 512.
RULE = [(64, "masked"), (128, "masked"), (512, "grouped"), (512, "jnp")]


def check_rule(rows: int, form: str, p: dict, cfg, monkeypatch) -> None:
    """`moe_held` of the expert layer `p` takes `form` at `rows` rows, and
    gives what the jnp form gives."""
    h = jax.random.normal(jax.random.PRNGKey(rows),
                          (rows // 64, 64, cfg.hidden_size))
    want = moe.moe_held(p, h, cfg)
    ran = []

    def recorded(name, kernel):
        def call(*args, **kwargs):
            ran.append(name)
            return kernel(*args, interpret=True, **kwargs)
        return call

    if form != "jnp":
        monkeypatch.setattr(hybrid_kernels, "use_kernels", lambda: True)
    for name in ("moe_held_experts", "moe_held_experts_grouped"):
        monkeypatch.setattr(hybrid_kernels, name,
                            recorded(name, getattr(hybrid_kernels, name)))
    assert moe.held_experts_grouped(rows) == (form == "grouped")
    got = moe.moe_held(p, h, cfg)
    assert ran == {"masked": ["moe_held_experts"],
                   "grouped": ["moe_held_experts_grouped"], "jnp": []}[form]
    np.testing.assert_allclose(got, want, atol=1e-4)
