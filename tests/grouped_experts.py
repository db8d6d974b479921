"""Cases of the grouped held-experts product (ops/hybrid_kernels.py
`moe_held_experts_grouped`) against `moe_held_experts_jnp` on the dense
weights of the same routing, shared by tests/test_hybrid.py (the un-gated
instance) and tests/test_lfm2.py (the gated one), and the rule that
chooses between the forms of `moe_held`. Small shapes, the kernels in
interpret mode, float32: the forms differ in summation order only. And
the decode step's call (ISSUE 56): 64 rows of which some, one or none are
live, the idle ones' weights zeroed, in each cell's expert form at reduced
widths."""

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


def dense_weights(idx, weight, published: int, first: int, held: int):
    """The combine weights [rows, held] of the held experts, 0 off the
    chosen, from a routing's (chosen experts, their weights)."""
    return jnp.sum(jax.nn.one_hot(idx, published) * weight[..., None],
                   axis=-2)[:, first:first + held]


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
    dense = dense_weights(idx, weight, published, first, held)
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
# width; on it — the gate answering as the chip would — the grouped kernel
# at EVERY row count (ISSUE 56): a decode step's 64 rows, a one- and a
# two-window prefill's 128 and 256, a wide dispatch's 1,024. The masked
# kernel is never called.
RULE = [(64, "grouped"), (128, "grouped"), (256, "grouped"),
        (1024, "grouped"), (512, "jnp")]


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
    assert ran == {"grouped": ["moe_held_experts_grouped"], "jnp": []}[form]
    np.testing.assert_allclose(got, want, atol=1e-4)


# A decode call's shape in each 64-slot cell's expert form, widths reduced:
# name → (L the experts read, their width, the block it streams in — three,
# two, one and four blocks an expert —, experts published, held, top-k,
# gated, activation).
DECODE_FORMS = {
    "nemotron": (128, 384, 128, 64, 16, 6, False, "relu2"),
    "lfm2": (128, 256, 128, 8, 8, 4, True, "silu"),
    "qwen3-next": (128, 128, 128, 64, 16, 10, True, "silu"),
    "openpangu": (256, 512, 128, 32, 4, 8, True, "silu"),
}
DECODE_ROWS = 64
# Which of the 64 lanes are live.
LIVE = {"two-idle": [i not in (5, 40) for i in range(DECODE_ROWS)],
        "one-live": [i == 17 for i in range(DECODE_ROWS)],
        "none-live": [False] * DECODE_ROWS}


def decode_call(form: str, live: str):
    """(v, up, down, how, the combine weights with the idle lanes' rows
    zeroed as `run_stack` zeroes them, the live lanes, chosen)."""
    L, inner, _, published, held, k, gated, activation = DECODE_FORMS[form]
    key = jax.random.split(jax.random.PRNGKey(len(form) + len(live)), 5)
    v = jax.random.normal(key[0], (DECODE_ROWS, L))
    up = jax.random.normal(key[1], (held, L, inner)) * L ** -0.5
    down = jax.random.normal(key[2], (held, inner, L)) * inner ** -0.5
    how = {"activation": activation}
    if gated:
        how["gate"] = jax.random.normal(key[3], (held, L, inner)) * L ** -0.5
    chosen, idx = jax.lax.top_k(
        jax.random.uniform(key[4], (DECODE_ROWS, published)), k)
    dense = dense_weights(
        idx, chosen / jnp.sum(chosen, -1, keepdims=True), published, 0, held)
    active = jnp.asarray(LIVE[live])
    # The case bites: an idle lane chose a held expert before the zeroing.
    assert bool(jnp.any(dense[~active] != 0))
    return (v, up, down, how, jnp.where(active[:, None], dense, 0.0), active,
            min(k, held))


def check_decode_call(form: str, live: str) -> None:
    """The grouped kernel on a decode call = the jnp form on the same
    zeroed weights; an idle lane's row is exactly zero; with no live lane
    there is no tile and the output is zeros."""
    v, up, down, how, weights, active, chosen = decode_call(form, live)
    block = DECODE_FORMS[form][2]
    want = hybrid_kernels.moe_held_experts_jnp(v, up, down, weights, **how)
    with mock.patch.object(hybrid_kernels, "_inner_tile",
                           lambda inner, *_: block):
        got = hybrid_kernels.moe_held_experts_grouped(
            v, up, down, weights, chosen=chosen, interpret=True, **how)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-4)   # summation order
    assert not np.asarray(got)[~np.asarray(active)].any()
    if live != "none-live":
        assert np.asarray(got)[np.asarray(active)].any()


def check_read_is_the_count(form: str, live: str) -> None:
    """Rows within one tile: every hit expert is exactly one live tile, so
    the tiles the kernel walks (`group_rows_by_expert`'s `live`, over the
    call's rows padded as `_grouped_call` pads them) and the experts the
    decode program counts (`held_experts_hit`) are one number."""
    _, _, _, _, weights, active, chosen = decode_call(form, live)
    padded = jnp.pad(weights, ((0, -DECODE_ROWS % 128), (0, 0)))
    rank, tile_expert, tile_rank, tiles = hybrid_kernels.group_rows_by_expert(
        padded, chosen, hybrid_kernels.MOE_GROUP_TILE)
    hit = int(moe.held_experts_hit(weights, active))
    assert int(tiles[0]) == hit
    assert hit == int(np.count_nonzero(np.asarray(weights).any(axis=0)))
    if live == "none-live":
        assert hit == 0
    # The live tiles name the hit experts, each once, in order; the static
    # bound's tiles past them repeat the last live one (nothing new is
    # fetched), and every index stays inside the experts held.
    held = weights.shape[1]
    experts = np.asarray(tile_expert)
    assert ((experts >= 0) & (experts < held)).all()
    np.testing.assert_array_equal(
        experts[:hit], np.flatnonzero(np.asarray(weights).any(axis=0)))
    assert (experts[hit:] == experts[max(hit - 1, 0)]).all()
    assert not np.asarray(tile_rank).any()
    assert int(np.max(np.asarray(rank))) < DECODE_ROWS
