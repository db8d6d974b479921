"""Mixture-of-Experts layers.

Mixtral-style softmax top-k routing, in two formulations, and the held
experts of a layer pattern's expert layer (`moe_held`), in two forms:

- `moe_mlp` — einsum-dense: every token runs through every expert, weighted
  by the (sparse) combine matrix. Simple, fully differentiable, and shards
  cleanly: with the expert axis on ``ep`` (parallel/sharding.py), each device
  computes only its local experts' contributions and XLA reduces the combine
  over the ep axis — structurally the all-to-all-free "expert-replicated
  compute" layout. Cost: num_experts/top_k × the FLOPs of sparse dispatch
  (4× for Mixtral 8×7B's 8-choose-2) — acceptable for correctness paths and
  small batches.
- `moe_held` — one chip's share of an expert layer: the router over
  every published expert (`held_router_weights`: sigmoid scores chosen
  by score + bias, or a softmax over all of them, as the config states),
  and the held experts' part of the sum over rows sorted by expert
  (ops/hybrid_kernels.py `moe_held_experts_grouped`, on the chip at every
  row count: each chosen pair once, the weights of the experts some row
  chose read once, an expert nobody chose not read at all). No
  capacity, no drop. `moe_latent_held`: un-gated
  relu² experts inside a latent, plus a shared expert; `moe_gated_held`:
  gated experts on the full hidden, plus a gated shared expert where the
  config states one.
- `moe_mlp_dispatch` — capacity-bucketed sparse dispatch: tokens gather into
  per-expert buckets (static capacity, dropped on overflow like GShard/
  Switch), experts run batched matmuls on their buckets only, results
  scatter-combine back. With experts on ``ep`` under jit, XLA emits the
  token all-to-all over ICI. This is the serving path for real MoE sizes.

Router math in fp32; combine weights renormalized over the selected top-k
(Mixtral convention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.layers import _activate, mlp
from ..models.quant import qdot, qeinsum_expert
from . import hybrid_kernels


def _router_weights(
    layer_params: dict, h: jax.Array, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """Top-k routing: returns (combine [.., E] fp32, expert_idx [.., k])."""
    logits = jnp.einsum(
        "...h,he->...e", h, layer_params["router"],
        preferred_element_type=jnp.float32,
    )
    weights, idx = jax.lax.top_k(logits, cfg.num_experts_per_tok)   # [.., k]
    weights = jax.nn.softmax(weights, axis=-1)                      # renorm
    # Dense [.., E] combine matrix: one-hot scatter of the k weights.
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
    combine = jnp.sum(onehot * weights[..., None], axis=-2)
    return combine, idx


def moe_mlp(layer_params: dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Dense-compute MoE: [B, T, H] → [B, T, H]."""
    combine, _ = _router_weights(layer_params, h, cfg)              # [B,T,E]
    experts = layer_params["experts"]                               # stacked [E,...]

    up = qeinsum_expert("bth,ehi->beti", h, experts["up"], e_axis=1)
    gate = _activate(
        qeinsum_expert("bth,ehi->beti", h, experts["gate"], e_axis=1),
        cfg.activation,
    )
    out = qeinsum_expert(
        "beti,eih->beth", gate * up, experts["down"], e_axis=1
    )  # [B,E,T,H]
    return jnp.einsum(
        "beth,bte->bth", out.astype(jnp.float32), combine
    ).astype(h.dtype)


def moe_mlp_dispatch(
    layer_params: dict,
    h: jax.Array,                   # [B, T, H]
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
) -> jax.Array:
    """Capacity-bucketed sparse dispatch (GShard-style).

    Static shapes: each expert processes a fixed-capacity bucket
    C = ceil(tokens · k / E · capacity_factor); tokens beyond an expert's
    capacity are dropped (their combine weight contributes nothing — the
    residual connection carries them).
    """
    B, T, H = h.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    tokens = h.reshape(B * T, H)
    N = B * T
    capacity = max(1, int(N * k / E * capacity_factor))

    combine, idx = _router_weights(layer_params, tokens, cfg)       # [N,E],[N,k]

    # Position of each (token, choice) within its expert's bucket.
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)                # [N,k,E]
    flat_choice = onehot.reshape(N * k, E)
    position = jnp.cumsum(flat_choice, axis=0) * flat_choice - 1    # [N·k,E]
    position = position.reshape(N, k, E)
    slot = jnp.sum(position * onehot, axis=-1)                      # [N,k]
    expert = idx                                                    # [N,k]
    keep = slot < capacity

    # Gather tokens into buckets [E, C, H].
    buckets = jnp.zeros((E, capacity, H), h.dtype)
    flat_expert = expert.reshape(-1)
    flat_slot = jnp.where(keep, slot, capacity - 1).reshape(-1)
    flat_keep = keep.reshape(-1)
    src = jnp.repeat(tokens, k, axis=0)                             # [N·k,H]
    src = jnp.where(flat_keep[:, None], src, 0)
    buckets = buckets.at[flat_expert, flat_slot].add(src)

    # Expert compute on buckets.
    experts_p = layer_params["experts"]
    up = qeinsum_expert("ech,ehi->eci", buckets, experts_p["up"], e_axis=0)
    gate = _activate(
        qeinsum_expert("ech,ehi->eci", buckets, experts_p["gate"], e_axis=0),
        cfg.activation,
    )
    out = qeinsum_expert(
        "eci,eih->ech", gate * up, experts_p["down"], e_axis=0
    )  # [E,C,H]

    # Combine back: each (token, choice) reads its bucket slot.
    gathered = out[flat_expert, flat_slot].reshape(N, k, H)
    weight = jnp.take_along_axis(combine, expert, axis=-1)          # [N,k]
    weight = jnp.where(keep, weight, 0.0)
    mixed = jnp.sum(
        gathered.astype(jnp.float32) * weight[..., None], axis=1
    )                                                               # [N,H]
    return mixed.reshape(B, T, H).astype(h.dtype)


def held_router_weights(p: dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Combine weights [.., n_routed_experts] (float32, 0 off the chosen),
    by `cfg.router_scoring`: "sigmoid" scores, the top
    `num_experts_per_tok` of score + correction bias chosen; "softmax"
    over ALL the published experts, the top by score chosen, no bias.
    Each chosen expert takes its own score over the sum of ALL the chosen
    scores — held here or not — (+ `router_norm_eps` where the model adds
    one) times `routed_scaling_factor`."""
    logits = jnp.einsum(
        "...h,he->...e", h, p["router"], preferred_element_type=jnp.float32)
    if cfg.router_scoring == "softmax":
        scores = by = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        by = scores + p["router_bias"]
    _, idx = jax.lax.top_k(by, cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    if cfg.router_norm_eps:
        total = total + cfg.router_norm_eps
    chosen = chosen * (cfg.routed_scaling_factor / total)
    onehot = jax.nn.one_hot(idx, cfg.n_routed_experts, dtype=jnp.float32)
    return jnp.sum(onehot * chosen[..., None], axis=-2)


def held_experts_grouped(rows: int) -> bool:
    """Whether `moe_held` runs a call of `rows` rows (batch × window) as
    the grouped product: on the chip, whatever `rows` is — a decode step,
    a one-window prefill and the wide dispatches alike; the grouped form
    is the slower one at no width (PERF.md §5). The engine counts by the
    same function (prefill_rows_grouped_experts)."""
    del rows
    return hybrid_kernels.use_kernels()


def held_weights(p: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The combine weights of the experts this chip holds, float32
    [rows, experts_held]: `held_router_weights` of `tokens` [rows, H],
    cut to `[first_expert, first_expert + experts_held)`."""
    return held_router_weights(p, tokens, cfg)[
        :, cfg.first_expert:cfg.first_expert + cfg.experts_held
    ]


def held_experts_hit(weights: jax.Array, live: jax.Array) -> jax.Array:
    """How many held experts have a non-zero combine weight on at least
    one `live` row (int32 scalar): `weights` [rows, experts_held] as
    `held_weights` gives them, `live` [rows] bool. The part of the held
    experts' read that some row asked for; a row that is not live (an
    idle decode lane routes its garbage token all the same) counts for
    nothing."""
    return jnp.sum(
        jnp.any((weights != 0) & live[:, None], axis=0), dtype=jnp.int32)


def _held_product(p: dict, tokens: jax.Array, cfg: ModelConfig,
                  weights=None) -> jax.Array:
    """Σ over the chosen experts that are held of weight · a(v, e) ·
    W_down,e, float32 [rows, width of v]: the router
    (`held_weights`, or `weights` where the caller has taken them
    already) reads `tokens` [rows, H]; the experts read
    v = `tokens` through `fc1` where the config states a latent, else
    `tokens`, gated where the layer has a `gate`. Two forms of one sum
    over the same combine weights, chosen by the backend alone: off the
    chip `moe_held_experts_jnp`; on it the grouped kernel at every row
    count, which reads the experts that some row has a non-zero weight
    for and no other — a row whose weights are all zero (an idle decode
    lane's, zeroed by `run_stack`) costs nothing."""
    if weights is None:
        weights = held_weights(p, tokens, cfg)
    v = qdot(tokens, p["fc1"]) if cfg.moe_latent_size else tokens
    how = {"gate": p.get("gate"), "activation": cfg.activation}
    if held_experts_grouped(tokens.shape[0]):
        return hybrid_kernels.moe_held_experts_grouped(
            v, p["up"], p["down"], weights,
            chosen=min(cfg.num_experts_per_tok, cfg.experts_held), **how)
    return hybrid_kernels.moe_held_experts_jnp(
        v, p["up"], p["down"], weights, **how)


def moe_held(p: dict, h: jax.Array, cfg: ModelConfig,
             weights=None) -> jax.Array:
    """The expert layer of a layer pattern ("E"), by what the config
    states: a latent → `moe_latent_held`; none → `moe_gated_held`. A
    combination neither computes is refused here, not half-served.
    `weights`: the `held_weights` of h's rows, where the caller has taken
    them already (the decode step counts the experts they hit)."""
    if cfg.router_scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"router_scoring {cfg.router_scoring!r} is not "
                         "computed: sigmoid or softmax")
    if cfg.shared_expert_gate and (cfg.moe_latent_size
                                   or not cfg.moe_shared_intermediate):
        raise ValueError(
            "a gate on the shared expert is computed beside gated experts "
            "on the full hidden only: shared_expert_gate needs "
            "moe_shared_intermediate and no moe_latent_size")
    if cfg.moe_latent_size:
        return moe_latent_held(p, h, cfg, weights)
    return moe_gated_held(p, h, cfg, weights)


def moe_gated_held(p: dict, h: jax.Array, cfg: ModelConfig,
                   weights=None) -> jax.Array:
    """Gated experts on the full hidden as the chip that holds experts
    `[first_expert, first_expert + experts_held)` computes them:
    [B, T, H] → [B, T, H], Σ_e w_e · (act(h W_gate,e) ⊙ h W_up,e) W_down,e
    over the chosen experts that are held. The router and the product
    are `moe_latent_held`'s (`_held_product`): the three matrices of
    every held expert some row chose are read once, and no token is
    dropped. Where the config states `moe_shared_intermediate`, a shared
    expert of the same gated form is added on every chip (it is
    replicated, not held in shares), weighed by the scalar
    sigmoid(w_s · h) where `shared_expert_gate` says so."""
    B, T, H = h.shape
    tokens = h.reshape(B * T, H)
    out = _held_product(p, tokens, cfg, weights)
    if cfg.moe_shared_intermediate:
        shared = mlp(p["shared"], tokens, cfg.activation).astype(jnp.float32)
        if cfg.shared_expert_gate:
            shared = shared * jax.nn.sigmoid(jnp.einsum(
                "rh,h->r", tokens, p["shared_score"],
                preferred_element_type=jnp.float32))[:, None]
        out = out + shared
    return out.astype(h.dtype).reshape(B, T, H)


def moe_latent_held(p: dict, h: jax.Array, cfg: ModelConfig,
                    weights=None) -> jax.Array:
    """A latent expert layer as ONE chip of its expert-parallel group
    computes it: [B, T, H] → [B, T, H]. The router keeps its published
    width and top-k; the routed sum runs over the chosen experts that are
    HELD, `[first_expert, first_expert + experts_held)`, inside the latent
    (`fc1` down to it, `fc2` back); the shared expert runs on the full
    hidden. What the absent experts would add is left out, here and in
    the reference alike, and no token is dropped at any width: the
    weights of every held expert some row chose are read once
    (`_held_product`: the rows are sorted by expert). The experts are not
    gated:
    relu(up)² only."""
    if cfg.activation != "relu2":
        raise ValueError("the held-experts product computes relu(up)² only")
    B, T, H = h.shape
    tokens = h.reshape(B * T, H)
    routed = _held_product(p, tokens, cfg, weights)
    out = qdot(routed.astype(h.dtype), p["fc2"])
    shared = _activate(qdot(tokens, p["shared_up"]), cfg.activation)
    return (out + qdot(shared, p["shared_down"])).reshape(B, T, H)
