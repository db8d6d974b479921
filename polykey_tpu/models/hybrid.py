"""Hybrid stacks: a layer pattern that is data (ModelConfig.layer_pattern).

Each entry is `x ← x + f(x)` through the norms `cfg` gives it, with ONE of
the seven bodies below, chosen by its character of the pattern; a published
layer that is an operator and a feed-forward part under two norms is two
entries. The norms (each gain is `cfg.norm_offset + w`: 1 + w is the
zero-centred norm): `x ← x + f(RMSNorm(x))` by default;
`x ← x + RMSNorm_post(f(RMSNorm(x)))` where `cfg.sandwich_norm` adds a
second gain on the body's output before the residual add;
`x ← x + RMSNorm_post(f(x))` where `cfg.pre_norm` is off beside it (the
body reads the residual stream as it comes):

- "M", a Mamba-2 mixer: `[z | xBC | dt] = W_in u`; xBC through a causal
  depthwise conv and silu, split into x [H, P], B and C [G, N];
  Δ = softplus(dt + dt_bias), A = −exp(A_log); the recurrence
  `h_t = exp(Δ_t A) h_{t−1} + Δ_t x_t ⊗ B_t`, `y_t = h_t C_t + D x_t`;
  `W_out RMSNorm_grouped(y · silu(z))`. What a sequence carries between
  dispatches is h [H, P, N] (float32) and the conv's last K−1 columns:
  the per-slot state of engine/kv_cache.py `SlotState`.
- "C", a gated short convolution: `[B | C | u] = W_in h`; a causal
  depthwise conv of K taps over B ⊙ u, no bias, no activation;
  `W_out (C ⊙ conv)`. What a sequence carries is the conv's last K−1
  columns alone, through the helpers the mixer's conv uses
  (`_window_decode`, `_window_prefill`).
- "L", a gated delta-rule linear attention: `[q | k | v | z] = W_qkvz u`,
  `[b | a] = W_ba u`; q|k|v through a causal depthwise conv (no bias) and
  silu; per value head (its key head is shared by Hv / Hk of them)
  q̃ = q / ‖q‖ / √Dk, k̃ = k / ‖k‖, β = `cfg.delta_beta_scale` · sigmoid(b)
  (scale 2: β in (0, 2), a negative eigenvalue of the transition along k̃
  where β > 1), g = −exp(A_log) softplus(a + dt_bias); the recurrence over
  a matrix S [Dk, Dv]: `S ← e^g S`, `S ← S + k̃ ⊗ β (v − Sᵀk̃)`, `o = Sᵀq̃`;
  `W_out (RMSNorm_Dv(o) · gain ⊙ silu(z))`. What a sequence carries is S
  of every value head (float32; `cfg.delta_heads_per_row` heads side by
  side in a row of whole lane tiles, ops/hybrid_kernels.py `pack_heads`)
  and the conv's last K−1 columns.
- "*", attention over the paged K/V pool: the projections, the paged
  write and the kernels of models/transformer.py `forward_paged`, with
  an RMSNorm on q and k first where `cfg.qk_norm` says so (over each head,
  or over the whole projection before the split: `cfg.qk_norm_span`), the
  position embedding over the leading `cfg.rotary_dim` of a head (none
  where `cfg.use_rope` is off), and the context multiplied by the sigmoid
  of a gate that W_q yields beside the query where `cfg.attn_output_gate`.
- "A", latent attention (MLA) over a pool of ONE row a token:
  `c_q = RMSNorm(W_dq h)`, a head's query `[q_nope | q_rope] = W_uq,i c_q`
  with the rotary embedding on q_rope; `[c | k_r] = W_dkv h`,
  `c ← RMSNorm(c)`, `k_r ← RoPE(k_r)`, ONE rotary key for all heads. The
  row `[c | k_r]` is all the pool keeps (engine/kv_cache.py: a one-part
  page). A head's key is `[W_uk,i c | k_r]` and its value `W_uv,i c`, and
  neither is ever built: the query absorbs W_uk
  (`q̃_i = [W_uk,iᵀ q_nope,i | q_rope,i]`), the scores are `q̃_i · row /
  √(nope + rope)`, the context is `W_uv,i Σ_s p_s c_s` — the same
  function, with the token's one row read once for all heads
  (ops/paged_attention_kernel.py `mla_latent_decode` at a decode step,
  ops/paged_attention.py `latent_attention` over the gathered table for a
  prefill window). A pattern attends through "A" or through "*", not both:
  the pool has one geometry.
- "E", an expert layer (ops/moe.py `moe_held`): latent un-gated experts
  with a shared expert, or gated experts on the full hidden, with or
  without a gated shared expert.
- "D", a dense gated MLP (models/layers.py `mlp`).

Parameters are grouped by kind, `params["layers"][kind]` a tuple with one
tree per layer of that kind in pattern order, and the stack walks the
pattern unrolled. (Not stacked on a leading axis: a static slice of a
stacked leaf may be materialised, and an expert leaf here is 0.7 GB.)

A decode step (T = 1) advances the state one token for the active lanes
(the mixer's recurrence: `ssm_state_update`, the delta rule's:
`gated_delta_state_update`, ops/hybrid_kernels.py). A prefill dispatch
runs the mixer's chunked (SSD) form over chunks of `cfg.ssm_chunk` and the
delta rule's chunked form over chunks of `cfg.delta_chunk`; the
inter-chunk pass of either also carries state from one ROW of the dispatch
to the next when the rows are consecutive windows of one prompt
(`PrefillRows`); a conv's columns pass from row to row the same way.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import hybrid_kernels
from ..ops.moe import held_experts_hit, held_weights, moe_held
from .config import ModelConfig
from .layers import init_mlp_params, mlp, qkv_project, rms_norm, rope
from .quant import embed_lookup, qdot

KINDS = {"M": "mamba", "C": "conv", "L": "delta", "*": "attention",
         "A": "latent", "E": "moe", "D": "dense"}
_HIGHEST = jax.lax.Precision.HIGHEST

# Where a prefill row's state starts (PrefillRows.source).
FROM_ZERO, FROM_SLOT, FROM_PREVIOUS_ROW = 0, 1, 2
# The step Δ a seeded dt_bias stands for: the family's published
# time_step_min / time_step_max. Read by init_layer and by nothing served.
DT_INIT = (0.001, 0.1)


class PrefillRows(NamedTuple):
    """What each row [N] of a prefill dispatch does with the per-slot
    state. `slot`: whose stored state a FROM_SLOT row starts from.
    `source`: FROM_ZERO (an admission's first window), FROM_SLOT (a long
    prompt's next chunk), FROM_PREVIOUS_ROW (the next window of the same
    prompt in this dispatch: the row above ended where this one starts).
    `store`: the slot that keeps this row's end state, or an index past
    the last slot for a row whose end state nobody keeps (a padded row, a
    window with a successor in the dispatch). `length`: the row's real
    tokens; the positions after them never advance state."""

    slot: jax.Array
    source: jax.Array
    store: jax.Array
    length: jax.Array


def layer_kinds(cfg: ModelConfig) -> list[tuple[str, int]]:
    """(kind, index among the layers of its kind) for each layer."""
    seen: dict = {}
    out = []
    for ch in cfg.layer_pattern:
        kind = KINDS[ch]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


# -- parameters ------------------------------------------------------------


def _normal(key, shape, dtype, fan_in):
    return jax.random.normal(key, shape, dtype) * fan_in**-0.5


def init_layer(key: jax.Array, kind: str, cfg: ModelConfig, dtype) -> dict:
    h = cfg.hidden_size
    # The effective gain is 1 (w = 0 under the zero-centred norm).
    gain = jnp.full((h,), 1.0 - cfg.norm_offset, dtype)
    norms = {}
    if cfg.pre_norm:
        norms["norm"] = gain
    if cfg.sandwich_norm:
        norms["post_norm"] = gain
    k = jax.random.split(key, 8)

    def step_and_decay(heads):
        # Δ log-uniform in DT_INIT; dt_bias is its inverse softplus (the
        # published init); A = −exp(A_log) uniform in [−16, −1].
        lo, hi = (jnp.log(v) for v in DT_INIT)
        dt = jnp.exp(
            jax.random.uniform(k[2], (heads,), jnp.float32) * (hi - lo) + lo)
        return {
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(
                jax.random.uniform(k[5], (heads,), jnp.float32, 1.0, 16.0)),
        }

    if kind == "mamba":
        inner, heads = cfg.mamba_inner, cfg.mamba_num_heads
        return {
            **norms,
            "w_in": _normal(k[0], (h, inner + cfg.conv_dim + heads), dtype, h),
            "conv_w": _normal(k[1], (cfg.conv_kernel, cfg.conv_dim), dtype,
                              cfg.conv_kernel),
            "conv_b": jax.random.normal(k[4], (cfg.conv_dim,), dtype) * 0.1,
            **step_and_decay(heads),
            "D": jnp.ones((heads,), jnp.float32),
            "gate_norm": jnp.ones((inner,), dtype),
            "w_out": _normal(k[3], (inner, h), dtype, inner),
        }
    if kind == "conv":
        return {
            **norms,
            "w_in": _normal(k[0], (h, 3 * h), dtype, h),
            "conv_w": _normal(k[1], (cfg.conv_kernel, h), dtype,
                              cfg.conv_kernel),
            "w_out": _normal(k[2], (h, h), dtype, h),
        }
    if kind == "delta":
        channels = cfg.delta_conv_dim
        values = cfg.delta_value_heads * cfg.delta_value_dim
        return {
            **norms,
            "w_qkvz": _normal(k[0], (h, channels + values), dtype, h),
            "w_ba": _normal(k[4], (h, 2 * cfg.delta_value_heads), dtype, h),
            "conv_w": _normal(k[1], (cfg.conv_kernel, channels), dtype,
                              cfg.conv_kernel),
            **step_and_decay(cfg.delta_value_heads),
            "gate_norm": jnp.ones((cfg.delta_value_dim,), dtype),
            "w_out": _normal(k[3], (values, h), dtype, values),
        }
    if kind == "attention":
        q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        layer = {
            **norms,
            "wq": _normal(k[0], (h, q * (2 if cfg.attn_output_gate else 1)),
                          dtype, h),
            "wk": _normal(k[1], (h, kv), dtype, h),
            "wv": _normal(k[2], (h, kv), dtype, h),
            "wo": _normal(k[3], (q, h), dtype, q),
        }
        if cfg.qk_norm:
            whole = cfg.qk_norm_span == "projection"
            for name, width in (("q_norm", q), ("k_norm", kv)):
                layer[name] = jnp.full(
                    (width if whole else cfg.head_dim,),
                    1.0 - cfg.norm_offset, dtype)
        return layer
    if kind == "latent":
        heads, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, v = cfg.qk_nope_head_dim, cfg.v_head_dim
        return {
            **norms,
            "w_dq": _normal(k[0], (h, cfg.q_lora_rank), dtype, h),
            "q_norm": jnp.full((cfg.q_lora_rank,), 1.0 - cfg.norm_offset,
                               dtype),
            "w_uq": _normal(
                k[1], (cfg.q_lora_rank,
                       heads * (nope + cfg.qk_rope_head_dim)),
                dtype, cfg.q_lora_rank),
            "w_dkv": _normal(k[2], (h, cfg.latent_width), dtype, h),
            "kv_norm": jnp.full((rank,), 1.0 - cfg.norm_offset, dtype),
            # W_ukv's two halves, a head at a time, as the absorbed
            # products take them: keys' [heads, nope, rank] (the query
            # goes through its transpose), values' [heads, rank, v].
            "w_uk": _normal(k[3], (heads, nope, rank), dtype, rank),
            "w_uv": _normal(k[4], (heads, rank, v), dtype, rank),
            "wo": _normal(k[5], (heads * v, h), dtype, heads * v),
        }
    if kind == "dense":
        return {**norms,
                **init_mlp_params(k[0], h, cfg.dense_intermediate_size, dtype)}
    latent, inner = cfg.moe_latent_size, cfg.intermediate_size
    shared, held = cfg.moe_shared_intermediate, cfg.experts_held
    router = {
        **norms,
        "router": _normal(k[0], (h, cfg.n_routed_experts), dtype, h),
    }
    if cfg.router_scoring == "sigmoid":
        router["router_bias"] = jax.random.normal(
            k[7], (cfg.n_routed_experts,), jnp.float32) * 0.02
    if not latent:
        layer = {
            **router,
            "gate": _normal(k[1], (held, h, inner), dtype, h),
            "up": _normal(k[2], (held, h, inner), dtype, h),
            "down": _normal(k[3], (held, inner, h), dtype, inner),
        }
        if shared:
            layer["shared"] = init_mlp_params(k[4], h, shared, dtype)
        if cfg.shared_expert_gate:
            layer["shared_score"] = _normal(k[5], (h,), dtype, h)
        return layer
    return {
        **router,
        "fc1": _normal(k[1], (h, latent), dtype, h),
        "fc2": _normal(k[2], (latent, h), dtype, latent),
        "up": _normal(k[3], (held, latent, inner), dtype, latent),
        "down": _normal(k[4], (held, inner, latent), dtype, inner),
        "shared_up": _normal(k[5], (h, shared), dtype, h),
        "shared_down": _normal(k[6], (shared, h), dtype, shared),
    }


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """Seeded random parameters, grouped by kind (module text). One layer
    a jitted call: a leaf is born in `dtype` where it will live, and the
    temporaries are one layer's (at the published widths the bf16 tree is
    9.3 GB of a 16 GB chip, and an expert leaf alone 0.7 GB)."""
    from .transformer import init_top_params

    k_embed, k_layers, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, cfg.num_layers)
    make = jax.jit(init_layer, static_argnums=(1, 2, 3))
    groups: dict = {kind: [] for kind in KINDS.values()}
    for layer_key, (kind, _) in zip(keys, layer_kinds(cfg)):
        groups[kind].append(make(layer_key, kind, cfg, dtype))
    top = jax.jit(init_top_params, static_argnums=(2, 3))(
        k_embed, k_head, cfg, dtype)
    return {**top,
            "layers": {kind: tuple(trees) for kind, trees in groups.items()}}


# -- the Mamba-2 mixer -----------------------------------------------------


def _split_in(p: dict, u: jax.Array, cfg: ModelConfig):
    inner = cfg.mamba_inner
    zxbcdt = qdot(u, p["w_in"])
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + cfg.conv_dim],
            zxbcdt[..., inner + cfg.conv_dim:])


def _split_xbc(xbc: jax.Array, cfg: ModelConfig):
    inner, gn = cfg.mamba_inner, cfg.ssm_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    x = xbc[..., :inner].reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim)
    Bm = xbc[..., inner:inner + gn].reshape(
        *lead, cfg.ssm_groups, cfg.ssm_state_size)
    Cm = xbc[..., inner + gn:].reshape(
        *lead, cfg.ssm_groups, cfg.ssm_state_size)
    return x, Bm, Cm


def _taps(ext: jax.Array, p: dict, T: int) -> jax.Array:
    """The causal depthwise conv (float32) over `ext` [.., K−1+T, C],
    whose first K−1 columns are what came before."""
    w = p["conv_w"].astype(jnp.float32)
    return sum(
        ext[..., k:k + T, :].astype(jnp.float32) * w[k]
        for k in range(w.shape[0])
    )


def _conv(ext: jax.Array, p: dict, T: int) -> jax.Array:
    """The mixer's and the delta body's: silu(conv + bias where the layer
    has one)."""
    out = _taps(ext, p, T)
    if "conv_b" in p:
        out = out + p["conv_b"].astype(jnp.float32)
    return jax.nn.silu(out).astype(ext.dtype)


def _window_decode(conv, col, active):
    """One new column for every lane: conv [B, K−1, C] the stored
    columns, col [B, C]. Returns (ext [B, K, C], the stored columns after
    the step: shifted for the `active` lanes, as they were for the rest)."""
    ext = jnp.concatenate([conv, col[:, None, :]], axis=1)
    return ext, jnp.where(active[:, None, None], ext[:, 1:], conv)


def _window_prefill(conv, cols, rows: PrefillRows, taps: int):
    """N rows of T new columns: cols [N, T, C], conv the stored columns of
    the WHOLE slot batch. The K−1 columns before each row are nothing, the
    slot's, or the tail of the row above (a full window: every one of its
    columns is real). Returns (ext [N, K−1+T, C], the columns after each
    row's last REAL token [N, K−1, C]: columns length .. length+K−2 of
    ext — a shorter row reaches back into what came before)."""
    T, source = cols.shape[1], rows.source
    above = jnp.roll(cols[:, T - (taps - 1):], 1, axis=0)
    before = jnp.where(
        (source == FROM_PREVIOUS_ROW)[:, None, None], above,
        jnp.where((source == FROM_SLOT)[:, None, None], conv[rows.slot], 0),
    ).astype(cols.dtype)
    ext = jnp.concatenate([before, cols], axis=1)
    end = jax.vmap(
        lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, taps - 1, axis=0)
    )(ext, rows.length)
    return ext, end


def _store_rows(conv, end, rows: PrefillRows):
    """The end columns of every row that `rows.store` keeps, to its slot."""
    return conv.at[rows.store].set(end.astype(conv.dtype), mode="drop")


def _gated_out(p: dict, y, z, cfg: ModelConfig):
    """W_out · RMSNorm over each of the G groups of (y · silu(z))."""
    lead = y.shape[:-2]
    g = y.reshape(*lead, cfg.mamba_inner).astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(*lead, cfg.ssm_groups, -1)
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + cfg.rms_norm_eps)).reshape(g.shape)
    normed = normed * p["gate_norm"].astype(jnp.float32)
    return qdot(normed.astype(z.dtype), p["w_out"])


def mamba_decode(p: dict, u, cfg: ModelConfig, ssm, conv, active):
    """One token for every lane: u [B, hidden], ssm [B, H, P, N] f32,
    conv [B, K−1, C]. A lane that is not `active` keeps both unchanged
    (Δ = 0 leaves h as it is, bit for bit; the conv window does not
    shift). Returns (out [B, hidden], ssm, conv)."""
    z, xbc, dt = _split_in(p, u, cfg)
    ext, conv = _window_decode(conv, xbc, active)               # [B, K, C]
    x, Bm, Cm = _split_xbc(_conv(ext, p, 1)[:, 0], cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.where(active[:, None], dt, 0.0)                    # [B, H]
    dA = jnp.exp(dt * -jnp.exp(p["A_log"]))
    xf = x.astype(jnp.float32)
    update = (hybrid_kernels.ssm_state_update if hybrid_kernels.use_kernels()
              else hybrid_kernels.ssm_state_update_jnp)
    ssm, y = update(ssm, dA, xf * dt[..., None], Bm.astype(jnp.float32),
                    Cm.astype(jnp.float32))
    y = y + p["D"][:, None] * xf
    return _gated_out(p, y, z, cfg), ssm, conv


def _chunk_sources(kind, nc: int):
    """For each of the N · nc chunks of a dispatch, in dispatch order:
    where its state starts — a row's first chunk where the row's `kind`
    [N] says, every other chunk where the one before it ended — and the
    row it belongs to."""
    at = jnp.arange(kind.shape[0] * nc)
    return (jnp.where(at % nc == 0, jnp.repeat(kind, nc), FROM_PREVIOUS_ROW),
            at // nc)


def _chunk_start(source, prev_end, stored):
    """The state a chunk starts from: where the chunk before it ended,
    what the slot `stored`, or zero."""
    return jnp.where(source == FROM_PREVIOUS_ROW, prev_end,
                     jnp.where(source == FROM_SLOT, stored, 0.0))


def ssd_chunks(x, dt, A, Bm, Cm, h_first, kind, chunk: int):
    """The chunked (SSD) form of the recurrence over N rows of T tokens,
    equal to it token by token. x [N, T, H, P], dt [N, T, H] (0 where a
    position must not advance state), A [H], Bm / Cm [N, T, G, S], all
    float32; h_first [N, H, P, S] the state each row starts from when its
    `kind` [N] is FROM_SLOT (FROM_ZERO: zeros; FROM_PREVIOUS_ROW: where
    the row above ended). Returns (y [N, T, H, P] without the D term, the
    state at each row's end [N, H, P, S])."""
    N, T, H, P = x.shape
    G, S = Bm.shape[2:]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"a prefill window of {T} is not whole chunks of {Q}")
    nc = T // Q
    C = N * nc
    Hg = H // G
    # Per-head decays with the chunk's positions LAST ([C, H, Q], and
    # [C, H, Q, Q] for the pairs): the large temporaries then lie with
    # 128 positions on the lanes, not 16 heads.
    a = (dt * A).reshape(C, Q, H).transpose(0, 2, 1)
    xdt = (x * dt[..., None]).reshape(C, Q, G, Hg, P)
    Bc, Cc = Bm.reshape(C, Q, G, S), Cm.reshape(C, Q, G, S)
    cs = jnp.cumsum(a, axis=-1)                                 # [C, H, Q]
    total = cs[..., -1]                                         # [C, H]

    def per_token(f):           # [C, H, Q] → [C, Q, G, Hg, 1]
        return f.transpose(0, 2, 1).reshape(C, Q, G, Hg, 1)

    # Within a chunk: y_t = Σ_{s≤t} (C_t·B_s) exp(cs_t − cs_s) Δ_s x_s.
    diff = cs[..., :, None] - cs[..., None, :]                  # [C, H, t, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), diff,
                              -jnp.inf))
    cb = jnp.einsum("ctgn,csgn->cgts", Cc, Bc)
    scores = cb[:, :, None] * decay.reshape(C, G, Hg, Q, Q)
    y = jnp.einsum("cghts,csghp->ctghp", scores, xdt)

    # What a chunk adds to the state, and what it leaves of the old one.
    to_end = per_token(jnp.exp(total[..., None] - cs))
    added = jnp.einsum("cqghp,cqgn->cghpn", xdt * to_end, Bc)
    keep = jnp.exp(total).reshape(C, G, Hg)

    # Between chunks, in dispatch order.
    h_first = h_first.reshape(N, G, Hg, P, S)

    def step(prev_end, inputs):
        source, row, add, kp = inputs
        start = _chunk_start(source, prev_end, h_first[row])
        end = kp[..., None, None] * start + add
        return end, (start, end)

    _, (starts, ends) = jax.lax.scan(
        step, jnp.zeros_like(added[0]),
        (*_chunk_sources(kind, nc), added, keep),
    )
    y = y + jnp.einsum("cqgn,cghpn->cqghp", Cc, starts) \
        * per_token(jnp.exp(cs))
    return (y.reshape(N, T, H, P),
            ends[nc - 1::nc].reshape(N, H, P, S))


def mamba_prefill(p: dict, u, cfg: ModelConfig, ssm, conv,
                  rows: PrefillRows):
    """N windows of T tokens: u [N, T, hidden]; ssm / conv the stored
    state of the WHOLE slot batch. Returns (out, ssm, conv) with the end
    state of every row that `rows.store` keeps written to its slot."""
    N, T, _ = u.shape
    z, xbc, dt = _split_in(p, u, cfg)
    ext, conv_end = _window_prefill(conv, xbc, rows, cfg.conv_kernel)
    x, Bm, Cm = _split_xbc(_conv(ext, p, T), cfg)
    real = jnp.arange(T)[None, :] < rows.length[:, None]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.where(real[..., None], dt, 0.0)
    xf = x.astype(jnp.float32)
    y, ssm_end = ssd_chunks(
        xf, dt, -jnp.exp(p["A_log"]), Bm.astype(jnp.float32),
        Cm.astype(jnp.float32), ssm[rows.slot], rows.source, cfg.ssm_chunk,
    )
    y = y + p["D"][:, None] * xf
    ssm = ssm.at[rows.store].set(ssm_end, mode="drop")
    return _gated_out(p, y, z, cfg), ssm, _store_rows(conv, conv_end, rows)


# -- the gated delta-rule linear attention -----------------------------------


def _delta_in(p: dict, u, cfg: ModelConfig):
    """(q|k|v, the columns the conv runs over; z, the output's gate; b; a)."""
    channels, heads = cfg.delta_conv_dim, cfg.delta_value_heads
    qkvz, ba = qdot(u, p["w_qkvz"]), qdot(u, p["w_ba"])
    return (qkvz[..., :channels], qkvz[..., channels:],
            ba[..., :heads], ba[..., heads:])


def _delta_factors(p: dict, x, b, a, cfg: ModelConfig, live):
    """The conv's output x [.., q|k|v] and b, a [.., Hv] → float32
    q̃ [.., Hk, Dk] (L2-normed, scaled by Dk^−½), k̃ (L2-normed),
    v [.., Hv, Dv], β and g [.., Hv] — both 0 where not `live`
    [.., 1]: such a position leaves the state as it is."""
    lead, width = x.shape[:-1], cfg.delta_key_heads * cfg.delta_key_dim
    x = x.astype(jnp.float32)

    def unit(y):
        y = y.reshape(*lead, cfg.delta_key_heads, cfg.delta_key_dim)
        return y * jax.lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)

    q = unit(x[..., :width]) * cfg.delta_key_dim ** -0.5
    k = unit(x[..., width:2 * width])
    v = x[..., 2 * width:].reshape(
        *lead, cfg.delta_value_heads, cfg.delta_value_dim)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if cfg.delta_beta_scale != 1.0:
        beta = cfg.delta_beta_scale * beta
    beta = jnp.where(live, beta, 0.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"])
    return q, k, v, beta, jnp.where(live, g, 0.0)


def _delta_out(p: dict, o, z, cfg: ModelConfig):
    """W_out (RMSNorm over each value head's dims of o, times its gain,
    ⊙ silu(z)): the gate comes after the norm."""
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    normed = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) \
        * p["gate_norm"].astype(jnp.float32)
    y = normed.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    return qdot(y.astype(z.dtype), p["w_out"])


def delta_decode(p: dict, u, cfg: ModelConfig, S, conv, active):
    """One token for every lane: u [B, hidden], S the stored state
    (`pack_heads` of [B, Hv, Dk, Dv], float32), conv [B, K−1, C]. A lane
    that is not `active` keeps both unchanged
    (g = 0 and β = 0 leave S as it is, bit for bit; the conv window does
    not shift). Returns (out [B, hidden], S, conv)."""
    qkv, z, b, a = _delta_in(p, u, cfg)
    ext, conv = _window_decode(conv, qkv, active)               # [B, K, C]
    q, k, v, beta, g = _delta_factors(
        p, _conv(ext, p, 1)[:, 0], b, a, cfg, active[:, None])
    update = (hybrid_kernels.gated_delta_state_update
              if hybrid_kernels.use_kernels()
              else hybrid_kernels.gated_delta_state_update_jnp)
    S, o = update(S, jnp.exp(g), beta, k, q, v)
    return _delta_out(p, o, z, cfg), S, conv


def _unit_lower_inverse(X):
    """(I − X)^−1 for strictly lower-triangular X [.., Q, Q], float32, by
    forward substitution: row t of the inverse is e_t + X_t · (the rows
    before t), one row a turn. (Not the product of I + X^{2^i}, which is
    the same matrix on paper: X is nilpotent. Its terms are the powers of
    X, and where a chunk's keys lie close to one another — X near −β on
    the whole triangle, as the keys of a real prompt do — those powers
    reach 1e8 and more before they cancel: in float32 the product was off
    by 2.4 at β < 1 and overflowed to NaN at β < 2 at a correlation of 0.8
    between a chunk's keys, where every entry of the true inverse is at
    most 2. Forward substitution never forms them: 1e-7.)"""
    Q = X.shape[-1]

    def row(t, inverse):
        x_t = jax.lax.dynamic_slice_in_dim(X, t, 1, axis=-2)     # [.., 1, Q]
        return jax.lax.dynamic_update_slice_in_dim(
            inverse,
            jax.lax.dynamic_slice_in_dim(inverse, t, 1, axis=-2)
            + jnp.einsum("...ts,...su->...tu", x_t, inverse,
                         precision=_HIGHEST),
            t, axis=-2)

    return jax.lax.fori_loop(
        1, Q, row, jnp.broadcast_to(jnp.eye(Q, dtype=X.dtype), X.shape))


def delta_chunks(q, k, v, beta, g, S_first, kind, chunk: int):
    """The chunked form of the gated delta rule over N rows of T tokens,
    equal to it token by token. q / k [N, T, Hk, Dk] (as `_delta_factors`
    leaves them), v [N, T, Hv, Dv], beta / g [N, T, Hv] (0 where a
    position must not advance state), all float32; S_first [N, Hv, Dk, Dv]
    the state each row starts from when its `kind` [N] is FROM_SLOT
    (FROM_ZERO: zeros; FROM_PREVIOUS_ROW: where the row above ended).
    Returns (o [N, T, Hv, Dv], the state at each row's end).

    Inside a chunk, with c the running sum of g and Γ_ts = e^{c_t − c_s}
    (s ≤ t): the tokens' corrections solve (I + A) D = β (V − e^c K̃ S₀),
    A = strictLower(diag(β) (K̃K̃ᵀ ⊙ Γ)), a unit lower-triangular system
    whose inverse is taken row by row (`_unit_lower_inverse`: forward
    substitution). Then o = e^c Q̃ S₀ + (Q̃K̃ᵀ ⊙ Γ) D and the chunk leaves
    S = e^{c_Q} S₀ + (e^{c_Q − c} K̃)ᵀ D. Every product in float32 at the
    highest precision."""
    N, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    Q, r = min(chunk, T), Hv // Hk
    if T % Q:
        raise ValueError(f"a prefill window of {T} is not whole chunks of {Q}")
    nc = T // Q
    C = N * nc
    # A key head's r value heads lie beside it: [C, Hk, r, Q, ..].
    qc = q.reshape(C, Q, Hk, Dk).transpose(0, 2, 1, 3)
    kc = k.reshape(C, Q, Hk, Dk).transpose(0, 2, 1, 3)
    vc = v.reshape(C, Q, Hk, r, Dv).transpose(0, 2, 3, 1, 4)
    bc = beta.reshape(C, Q, Hk, r).transpose(0, 2, 3, 1)
    cs = jnp.cumsum(g.reshape(C, Q, Hk, r).transpose(0, 2, 3, 1), axis=-1)
    total = cs[..., -1:]                                        # [C, Hk, r, 1]
    gamma = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)),
                              cs[..., :, None] - cs[..., None, :], -jnp.inf))
    kk = jnp.einsum("cgtd,cgsd->cgts", kc, kc, precision=_HIGHEST)
    qk = jnp.einsum("cgtd,cgsd->cgts", qc, kc, precision=_HIGHEST)

    inverse = _unit_lower_inverse(
        -(bc[..., None] * kk[:, :, None] * gamma)
        * jnp.tri(Q, k=-1, dtype=jnp.float32))
    # D = U − W S₀: what the corrections are from zero state, and what a
    # start state takes from them.
    U = jnp.einsum("cgrts,cgrsv->cgrtv", inverse, bc[..., None] * vc,
                   precision=_HIGHEST)
    W = jnp.einsum("cgrts,cgrs,cgsd->cgrtd", inverse, bc * jnp.exp(cs), kc,
                   precision=_HIGHEST)
    to_end = jnp.exp(total - cs)[..., None] * kc[:, :, None]    # [C,Hk,r,Q,Dk]
    keep = jnp.exp(total)[..., None]                            # [C,Hk,r,1,1]

    # Between chunks, in dispatch order.
    S_first = S_first.reshape(N, Hk, r, Dk, Dv)

    def step(prev_end, inputs):
        source, row, w, u, te, kp = inputs
        start = _chunk_start(source, prev_end, S_first[row])
        d = u - jnp.einsum("grtd,grdv->grtv", w, start, precision=_HIGHEST)
        end = kp * start + jnp.einsum("grtd,grtv->grdv", te, d,
                                      precision=_HIGHEST)
        return end, (start, d, end)

    _, (starts, D, ends) = jax.lax.scan(
        step, jnp.zeros_like(S_first[0]),
        (*_chunk_sources(kind, nc), W, U, to_end, keep),
    )
    o = (jnp.einsum("cgtd,cgrdv->cgrtv", qc, starts, precision=_HIGHEST)
         * jnp.exp(cs)[..., None]
         + jnp.einsum("cgrts,cgrsv->cgrtv", qk[:, :, None] * gamma, D,
                      precision=_HIGHEST))
    return (o.transpose(0, 3, 1, 2, 4).reshape(N, T, Hv, Dv),
            ends[nc - 1::nc].reshape(N, Hv, Dk, Dv))


def delta_prefill(p: dict, u, cfg: ModelConfig, S, conv, rows: PrefillRows):
    """N windows of T tokens: u [N, T, hidden]; S / conv the stored state
    of the WHOLE slot batch. Returns (out, S, conv) with the end state of
    every row that `rows.store` keeps written to its slot. The chunked
    form works on the heads apart: the rows' states are taken out of the
    stored layout and put back into it here."""
    T, per_row = u.shape[1], cfg.delta_heads_per_row
    qkv, z, b, a = _delta_in(p, u, cfg)
    ext, conv_end = _window_prefill(conv, qkv, rows, cfg.conv_kernel)
    real = jnp.arange(T)[None, :] < rows.length[:, None]
    q, k, v, beta, g = _delta_factors(
        p, _conv(ext, p, T), b, a, cfg, real[..., None])
    o, S_end = delta_chunks(
        q, k, v, beta, g, hybrid_kernels.unpack_heads(S[rows.slot], per_row),
        rows.source, cfg.delta_chunk)
    S = S.at[rows.store].set(
        hybrid_kernels.pack_heads(S_end, per_row), mode="drop")
    # The stored state is an output of the whole program: left to itself
    # the compiler puts off the small writes above to the program's end and
    # keeps every layer's q|k|v columns (90 MB a layer at 8 x 512 rows of
    # 11,520) alive until then. Tied to the layer's output, they are done
    # when the layer is.
    return jax.lax.optimization_barrier(
        (_delta_out(p, o, z, cfg), S, _store_rows(conv, conv_end, rows)))


# -- the gated short convolution ---------------------------------------------


def _conv_in(p: dict, u):
    """(B ⊙ u, the columns the conv runs over; C, the gate of its output)."""
    h = u.shape[-1]
    bcu = qdot(u, p["w_in"])
    return bcu[..., :h] * bcu[..., 2 * h:], bcu[..., h:2 * h]


def conv_decode(p: dict, u, conv, active):
    """One token for every lane: u [B, hidden], conv [B, K−1, hidden]. A
    lane that is not `active` keeps its columns. Returns (out, conv)."""
    z, gate = _conv_in(p, u)
    ext, conv = _window_decode(conv, z, active)
    y = _taps(ext, p, 1)[:, 0].astype(u.dtype)
    return qdot(gate * y, p["w_out"]), conv


def conv_prefill(p: dict, u, cfg: ModelConfig, conv, rows: PrefillRows):
    """N windows of T tokens: u [N, T, hidden]; conv the stored columns of
    the WHOLE slot batch. Returns (out, conv) with the columns after the
    last real token of every row that `rows.store` keeps written."""
    z, gate = _conv_in(p, u)
    ext, end = _window_prefill(conv, z, rows, cfg.conv_kernel)
    y = _taps(ext, p, u.shape[1]).astype(u.dtype)
    return qdot(gate * y, p["w_out"]), _store_rows(conv, end, rows)


# -- the stack -------------------------------------------------------------


def attention_layer(p: dict, h, positions, cfg: ModelConfig, attend, idx,
                    pool):
    B, T, _ = h.shape
    q, k, v = qkv_project(p, h, cfg)
    if cfg.attn_output_gate:
        q, gate = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
    if cfg.qk_norm:
        def normed(y, gain):
            # Over a head's dims, or over the projection as it was before
            # the split (the heads folded back: the same bytes).
            whole = cfg.qk_norm_span == "projection"
            flat = y.reshape(B, T, -1) if whole else y
            return rms_norm(flat, gain, cfg.rms_norm_eps,
                            cfg.norm_offset).reshape(y.shape)

        q, k = normed(q, p["q_norm"]), normed(k, p["k_norm"])
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
    ctx, pool = attend(jnp.int32(idx), q, k, v, pool)
    if cfg.attn_output_gate:
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
    out = qdot(ctx.reshape(B, T, cfg.num_heads * cfg.head_dim), p["wo"])
    return out, pool


def latent_attention_layer(p: dict, h, positions, cfg: ModelConfig, attend,
                           idx, pool):
    """The "A" body (module text) in its absorbed form: hands `attend` the
    query heads [B, T, H, W] and the token's ONE row [B, T, 1, W] — W the
    pool's row width, zero columns after the published latent + rotary
    key — and no V; gets back Σ p · latent [B, T, H, rank]."""
    B, T, _ = h.shape
    heads, rank, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    eps, offset = cfg.rms_norm_eps, cfg.norm_offset
    c_q = rms_norm(qdot(h, p["w_dq"]), p["q_norm"], eps, offset)
    # Flat until it is done, as models/layers.py `qkv_project` holds its
    # products (a [B, 1, ·] step else has the head split folded into it).
    q = jax.lax.optimization_barrier(qdot(c_q, p["w_uq"])).reshape(
        B, T, heads, nope + cfg.qk_rope_head_dim)
    down = qdot(h, p["w_dkv"])
    c = rms_norm(down[..., :rank], p["kv_norm"], eps, offset)
    k_r = rope(down[:, :, None, rank:], positions, cfg.rope_theta)
    pad = cfg.kv_row_width - cfg.latent_width
    row = jnp.concatenate(
        [c[:, :, None], k_r, jnp.zeros((B, T, 1, pad), c.dtype)], axis=-1)
    absorbed = jnp.einsum("bthn,hnc->bthc", q[..., :nope], p["w_uk"])
    q = jnp.concatenate(
        [absorbed, rope(q[..., nope:], positions, cfg.rope_theta),
         jnp.zeros((B, T, heads, pad), q.dtype)], axis=-1)
    u, pool = attend(jnp.int32(idx), q, row, None, pool)
    ctx = jnp.einsum("bthc,hcv->bthv", u, p["w_uv"])
    return qdot(ctx.reshape(B, T, heads * cfg.v_head_dim), p["wo"]), pool


def run_stack(params, cfg: ModelConfig, tokens, positions, pool, attend,
              state=None, rows=None, active=None):
    """embed → the pattern's layers, unrolled → final norm. `pool` is the
    stacked page pool `attend` threads (models/transformer.py); `state` the
    per-slot SlotState of a stateful pattern; a prefill dispatch says
    what each row does with it (`rows`, a PrefillRows), a decode step
    (T = 1, one row a lane; `rows` None) which lanes are live (`active`).
    Returns (hidden, pool, state, hits): `hits` the held experts a live
    lane chose, summed over the expert layers of a decode step (int32
    scalar; None for a prefill dispatch and for a pattern without an
    expert layer — nothing is counted there)."""
    decode = rows is None
    eps, offset = cfg.rms_norm_eps, cfg.norm_offset
    x = embed_lookup(params["embed"], tokens)
    ssm = list(state.ssm) if state is not None else []
    conv = list(state.conv) if state is not None else []
    held = 0                    # stateful layers so far: the index in `conv`
    carried = 0                 # those with a recurrence: the index in `ssm`
    hits = None
    for kind, idx in layer_kinds(cfg):
        p = params["layers"][kind][idx]
        h = rms_norm(x, p["norm"], eps, offset) if cfg.pre_norm else x
        if kind in ("mamba", "delta"):
            one, many = ((mamba_decode, mamba_prefill) if kind == "mamba"
                         else (delta_decode, delta_prefill))
            if decode:
                out, ssm[carried], conv[held] = one(
                    p, h[:, 0], cfg, ssm[carried], conv[held], active)
                out = out[:, None]
            else:
                out, ssm[carried], conv[held] = many(
                    p, h, cfg, ssm[carried], conv[held], rows)
            held += 1
            carried += 1
        elif kind == "conv":
            if decode:
                out, conv[held] = conv_decode(p, h[:, 0], conv[held], active)
                out = out[:, None]
            else:
                out, conv[held] = conv_prefill(p, h, cfg, conv[held], rows)
            held += 1
        elif kind in ("attention", "latent"):
            layer = (attention_layer if kind == "attention"
                     else latent_attention_layer)
            out, pool = layer(p, h, positions, cfg, attend, idx, pool)
        elif kind == "dense":
            out = mlp(p, h, cfg.activation)
        elif active is not None:
            # A lane that is not live routes its garbage token all the
            # same, and its output is thrown away: with its weights zeroed
            # it has no pairs, and the product reads the experts `hit`
            # counts and no other.
            weights = jnp.where(active[:, None],
                                held_weights(p, h[:, 0], cfg), 0.0)
            hit = held_experts_hit(weights, active)
            hits = hit if hits is None else hits + hit
            out = moe_held(p, h, cfg, weights)
        else:
            out = moe_held(p, h, cfg)
        if cfg.sandwich_norm:
            out = rms_norm(out, p["post_norm"], eps, offset)
        x = x + out
    x = rms_norm(x, params["final_norm"], eps, offset)
    if state is not None:
        state = state.replace(ssm=tuple(ssm), conv=tuple(conv))
    return x, pool, state, hits
