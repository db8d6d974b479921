"""Config-driven transformer forward pass covering the served families.

One implementation handles Llama-3 (GQA + RoPE + SwiGLU), Gemma-2 (post
norms, logit soft-capping, interleaved sliding-window layers, scaled
embeddings), and — via the MoE hook — Mixtral. Families are selected by
config (models/config.py registry), not by per-family modules.

TPU-first design choices:
- layers stacked on a leading axis, driven by `lax.scan`: one compiled block,
  natural pipeline-stage unit;
- static shapes only: right-padded batches, masks computed from absolute
  positions (never data-dependent shapes);
- KV cache is a plain pytree carried through scan; slot s always holds the
  token at absolute position s, so causal masking doubles as garbage-slot
  masking (see ops/attention.make_attention_mask);
- bf16 weights/activations, fp32 softmax/norm accumulation, fp32 logits.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import struct

from ..ops.attention import attention
from .config import ModelConfig
from .quant import embed_lookup, qdot, unembed_logits
from .layers import (
    init_attention_params,
    init_mlp_params,
    mlp,
    qkv_project,
    rms_norm,
    rope,
)


@struct.dataclass
class KVCache:
    """Contiguous per-layer KV cache: [num_layers, B, S, num_kv_heads, head_dim]
    (a looped stack: one cache layer a layer and pass, pass-major).

    The simple serving path (fixed-geometry batch, fixed max length). The
    continuous-batching engine replaces this with the paged cache
    (engine/kv_cache.py + ops/paged_attention.py).
    """

    k: jax.Array
    v: jax.Array

    @property
    def num_slots(self) -> int:
        return self.k.shape[2]


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> KVCache:
    shape = (cfg.num_layers * cfg.loop_steps, batch, max_len,
             cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _norm_init(cfg: ModelConfig, dtype) -> jax.Array:
    # rms_norm computes gain = offset + w (offset 1.0 for the Gemma storage
    # convention, models with scale_embeddings; a layer pattern states its
    # own, ModelConfig.norm_offset). Init w so the effective gain is 1 —
    # zero gains would make every hidden state identically zero at init,
    # turning random-init tests vacuous.
    norm_offset = 1.0 if cfg.scale_embeddings else cfg.norm_offset
    return jnp.full((cfg.hidden_size,), 1.0 - norm_offset, dtype)


def init_layer_params(k: jax.Array, cfg: ModelConfig, dtype) -> dict:
    """One transformer block's random-init params (no stacked axis)."""
    norm_init = _norm_init(cfg, dtype)
    k_attn, k_mlp = jax.random.split(k)
    layer = {
        "attn": init_attention_params(k_attn, cfg, dtype),
        "ln1": norm_init,
        "ln2": norm_init,
    }
    if cfg.is_moe:
        k_router, k_experts = jax.random.split(k_mlp)
        layer["router"] = (
            jax.random.normal(k_router, (cfg.hidden_size, cfg.num_experts), dtype)
            * cfg.hidden_size**-0.5
        )
        layer["experts"] = jax.vmap(
            lambda kk: init_mlp_params(
                kk, cfg.hidden_size, cfg.intermediate_size, dtype
            )
        )(jax.random.split(k_experts, cfg.num_experts))
    else:
        layer["mlp"] = init_mlp_params(
            k_mlp, cfg.hidden_size, cfg.intermediate_size, dtype
        )
    if cfg.use_post_norms:
        layer["post_ln1"] = norm_init
        layer["post_ln2"] = norm_init
    return layer


def init_top_params(
    k_embed: jax.Array, k_head: jax.Array, cfg: ModelConfig, dtype
) -> dict:
    """The params outside the layer stack: embed, final norm, lm_head."""
    top = {
        "embed": jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.hidden_size), dtype
        )
        * cfg.hidden_size**-0.5,
        "final_norm": _norm_init(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        top["lm_head"] = (
            jax.random.normal(k_head, (cfg.hidden_size, cfg.vocab_size), dtype)
            * cfg.hidden_size**-0.5
        )
    if cfg.loop_steps > 1:
        # The exit gate of a looped stack: one Linear(hidden, 1) with bias.
        top["exit_gate"] = {
            "w": jax.random.normal(
                jax.random.fold_in(k_head, 1), (cfg.hidden_size, 1), dtype
            ) * cfg.hidden_size**-0.5,
            "b": jnp.zeros((1,), dtype),
        }
    return top


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """Random-init parameter pytree with layers stacked for scan.

    Materializes the whole tree in `dtype` on the default device — right
    for tests, training and small models. The serving engine starts from
    parallel/sharding.init_sharded_params instead, which draws the same
    values layer by layer straight into their final dtype and sharding.
    """
    if cfg.layer_pattern:
        from .hybrid import init_params as init_hybrid_params

        return init_hybrid_params(key, cfg, dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layers = jax.vmap(lambda k: init_layer_params(k, cfg, dtype))(
        jax.random.split(k_layers, cfg.num_layers)
    )
    return {**init_top_params(k_embed, k_head, cfg, dtype), "layers": layers}


def _moe_mlp(layer_params: dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    from ..ops.moe import moe_mlp, moe_mlp_dispatch  # deferred import

    if cfg.moe_dispatch:
        return moe_mlp_dispatch(layer_params, h, cfg)
    return moe_mlp(layer_params, h, cfg)


def _layer_window(cfg: ModelConfig, layer_idx: jax.Array):
    """Gemma-2 interleaving: even layers sliding-window, odd layers global."""
    if cfg.sliding_window is None:
        return None
    return jnp.where(layer_idx % 2 == 0, cfg.sliding_window, cfg.max_seq_len)


def embed_tokens(params: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """Token embedding lookup (+ Gemma's sqrt(H) scaling)."""
    x = embed_lookup(params["embed"], tokens)
    if cfg.scale_embeddings:
        x = (x.astype(jnp.float32) * cfg.hidden_size**0.5).astype(x.dtype)
    return x


def apply_layer(layer_params, layer_idx, x, positions, cfg: ModelConfig, attend, cache):
    """One transformer block at absolute layer index `layer_idx`.

    Norms, projections, RoPE, residuals, MLP/MoE, and Gemma post-norms live
    here; the KV mechanics are injected via
    `attend(layer_idx, q, k, v, cache) → (ctx, cache)`, `cache` whatever
    pytree the caller threads (a layer's contiguous (k, v), the paged
    stack, nothing). Shared by the
    scanned stack (_run_stack) and the pipeline-parallel stage bodies
    (parallel/pipeline.py), so a stage runs the exact computation the
    unsharded stack runs.
    """
    B, T = x.shape[:2]
    norm_offset = 1.0 if cfg.scale_embeddings else 0.0
    eps = cfg.rms_norm_eps

    h = rms_norm(x, layer_params["ln1"], eps, norm_offset)
    q, k, v = qkv_project(layer_params["attn"], h, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    ctx, cache = attend(layer_idx, q, k, v, cache)

    attn_out = ctx.reshape(B, T, cfg.num_heads * cfg.head_dim)
    attn_out = qdot(attn_out, layer_params["attn"]["wo"])
    if cfg.use_post_norms:
        attn_out = rms_norm(attn_out, layer_params["post_ln1"], eps, norm_offset)
    x = x + attn_out

    h = rms_norm(x, layer_params["ln2"], eps, norm_offset)
    if cfg.is_moe:
        mlp_out = _moe_mlp(layer_params, h, cfg)
    else:
        mlp_out = mlp(layer_params["mlp"], h, cfg.activation)
    if cfg.use_post_norms:
        mlp_out = rms_norm(mlp_out, layer_params["post_ln2"], eps, norm_offset)
    x = x + mlp_out

    return x, cache


def _run_stack(params, cfg: ModelConfig, tokens, positions, kv_scanned, attend):
    """Shared transformer stack: embed → scan(layer body) → final norm."""
    x = embed_tokens(params, cfg, tokens)

    def body(x, scanned):
        layer_params, layer_idx, cache = scanned
        return apply_layer(
            layer_params, layer_idx, x, positions, cfg, attend, cache
        )

    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    if cfg.loop_steps > 1:
        # Pass u over cache layers u·L .. u·L + L − 1 of `kv_scanned`
        # ([loop_steps · L, ...]: a contiguous cache, or the no-cache
        # path's empty pair).
        def layers(x, _, ids, cache):
            x, cache = jax.lax.scan(body, x, (params["layers"], ids, cache))
            return x, None, cache

        rule, _, (new_k, new_v) = _run_passes(
            params, cfg, x, None, layers, jax.tree.map(
                lambda c: c.reshape(
                    cfg.loop_steps, cfg.num_layers, *c.shape[1:]),
                kv_scanned))
        return (rule.chosen, new_k.reshape(kv_scanned[0].shape),
                new_v.reshape(kv_scanned[1].shape))
    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], layer_ids, kv_scanned)
    )
    return _pass_norm(params, cfg, x), new_k, new_v


@struct.dataclass
class ExitRule:
    """The exit rule of a looped stack as it runs beside the passes, one
    entry a position: `stay` = Π_{j<u}(1 − λ_j), `cumulative` = Σ_{j<u} p_j,
    `left` whether an earlier pass already reached the threshold, `chosen`
    the hidden state of the pass it left at, `exits` that pass."""

    stay: jax.Array          # [B, T] float32
    cumulative: jax.Array    # [B, T] float32
    left: jax.Array          # [B, T] bool
    chosen: jax.Array        # [B, T, H]
    exits: jax.Array         # [B, T] int32


def _exit_rule_init(x: jax.Array) -> ExitRule:
    shape = x.shape[:2]
    return ExitRule(
        stay=jnp.ones(shape, jnp.float32),
        cumulative=jnp.zeros(shape, jnp.float32),
        left=jnp.zeros(shape, bool),
        chosen=jnp.zeros_like(x),
        exits=jnp.zeros(shape, jnp.int32),
    )


def _pass_norm(params, cfg: ModelConfig, x):
    norm_offset = 1.0 if cfg.scale_embeddings else 0.0
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps, norm_offset)


def _exit_rule(params, cfg: ModelConfig, rule: ExitRule, x, u) -> ExitRule:
    """Pass `u` has ended in the normed `x` [B, T, H]: the gate's
    λ_u = sigmoid(w · x + b) in float32 (a multiply and a row sum: no
    matmul precision to choose), p_u = λ_u · stay — the last pass takes
    all that is left — and a position that has not left yet leaves here
    if its cumulative exit probability reaches `early_exit_threshold`, or
    this is the last pass: `chosen` takes x there."""
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(
        jnp.sum(x.astype(jnp.float32) * gate["w"][:, 0].astype(jnp.float32),
                axis=-1)
        + gate["b"].astype(jnp.float32)[0])
    last = u == cfg.loop_steps - 1
    cumulative = rule.cumulative + jnp.where(last, 1.0, lam) * rule.stay
    leaves = ~rule.left & (last | (cumulative >= cfg.early_exit_threshold))
    return ExitRule(
        stay=rule.stay * (1.0 - lam),
        cumulative=cumulative,
        left=rule.left | leaves,
        chosen=jnp.where(leaves[..., None], x, rule.chosen),
        exits=jnp.where(leaves, u, rule.exits),
    )


def _run_passes(params, cfg: ModelConfig, x, carried, layers, per_pass=None):
    """The passes of a looped stack: `layers(x, carried, layer_ids, xs)` →
    (x, carried, ys) is one walk over the SAME weights, called under a
    `lax.scan` over the passes with pass u's cache-layer ids (u·L + l) and
    its slice of `per_pass`; the final norm closes every pass and feeds the
    next, and the exit rule runs beside them. Returns (the rule as the
    last pass left it, `carried`, the passes' ys)."""
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def one_pass(carry, scanned):
        x, carried, rule = carry
        u, xs = scanned
        x, carried, ys = layers(x, carried, layer_ids + u * cfg.num_layers, xs)
        x = _pass_norm(params, cfg, x)
        return (x, carried, _exit_rule(params, cfg, rule, x, u)), ys

    (_, carried, rule), ys = jax.lax.scan(
        one_pass, (x, carried, _exit_rule_init(x)),
        (jnp.arange(cfg.loop_steps, dtype=jnp.int32), per_pass))
    return rule, carried, ys


def _run_paged_stack(params, cfg: ModelConfig, tokens, positions, paged,
                     attend, stage=None):
    """The stack over the paged KV pool: embed → scan(layer body) → final
    norm, with the WHOLE stacked pool in the scan carry — and, beside it,
    a prefill dispatch's `stage` (ops/paged_attention.py `prefill_stage`:
    `attend` then gets and returns the pair); returns (hidden, updated
    paged).

    The pool is stored [L, N, 2, page_size, Hk·D] (engine/kv_cache.py; a
    latent pool [L, N, 1, page_size, W]) and
    carried as its page halves, [L·N·2, page_size, Hk·D] — a merge of
    leading dimensions, a bitcast under any tiling, made once outside the
    scan. `attend(layer_idx, q, k, v, cache)` gets that
    whole stack as `cache` (the (values, k scales, v scales) triple for
    int8 KV) and addresses page (layer, page) as `layer_idx · N + page`
    (`_layer_tables`): the write kernel aliases the stack, the XLA scatters
    update it in place in the carry, the decode kernel DMAs single pages out
    of it. No layer's pool is ever sliced out, copied or written back, so a
    step moves the rows and pages it touches and nothing else
    (tests/test_paged_layout.py holds that in the compiled step).

    Not scanned as xs/ys (the way _run_stack scans a contiguous cache):
    that makes XLA build the updated stack in a second full-size buffer.

    A third value comes back: each position's exit pass of a looped stack
    (`_run_passes`), None for a stack of one pass."""
    x = embed_tokens(params, cfg, tokens)

    def body(carry, scanned):
        x, pool = carry
        layer_params, layer_idx = scanned
        return apply_layer(
            layer_params, layer_idx, x, positions, cfg, attend, pool
        ), None

    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    pool = _stacked(paged)
    held = pool if stage is None else (pool, stage)
    exits = None
    if cfg.loop_steps > 1:
        # A looped stack: the layer scan inside the scan over the passes,
        # the pool (and the stage) in the ONE carry of both, pass u on
        # pool layers u·L + l.
        def layers(x, held, ids, _):
            (x, held), _ = jax.lax.scan(
                body, (x, held), (params["layers"], ids))
            return x, held, None

        rule, held, _ = _run_passes(params, cfg, x, held, layers)
        x, exits = rule.chosen, rule.exits
    else:
        (x, held), _ = jax.lax.scan(
            body, (x, held), (params["layers"], layer_ids))
        x = _pass_norm(params, cfg, x)
    return x, _unstacked(paged, held if stage is None else held[0]), exits


def _stacked(paged):
    """The pool as the model step carries it and the ops take it: every
    leaf with its leading dimensions merged down to [·, page_size, ·] (a
    bitcast) — the kv array as page halves [L·N·2, page_size, Hk·D], page
    p's K at 2p and its V at 2p + 1 (a one-part latent pool:
    [L·N, page_size, W], page p at p) — alone, or in the int8 (values,
    k scales, v scales) triple the ops dispatch on."""
    def merge(p):
        return p.reshape(-1, *p.shape[-2:])

    if paged.quantized:
        return merge(paged.kv), merge(paged.ks), merge(paged.vs)
    return merge(paged.kv)


def _unstacked(paged, pool):
    """`_stacked`'s inverse: the carried pool back under `paged`'s shapes."""
    if paged.quantized:
        kv, ks, vs = pool
        return paged.replace(
            kv=kv.reshape(paged.kv.shape), ks=ks.reshape(paged.ks.shape),
            vs=vs.reshape(paged.vs.shape),
        )
    return paged.replace(kv=pool.reshape(paged.kv.shape))


def _layer_tables(paged, layer_idx, tables: jax.Array) -> jax.Array:
    """Page ids of one layer within the stack `_run_paged_stack` carries:
    page p of layer l lies at l · num_pages + p (the reserved garbage page 0
    becomes the layer's own garbage page); the ops scale a page id by the
    parts a page holds."""
    return tables + layer_idx * paged.num_pages


def make_causal_attend(cfg: ModelConfig, positions: jax.Array):
    """No-cache causal attention closure over `positions` [B, T]: attention
    spans the current tokens only, masked by position (with Gemma's
    per-layer sliding-window interleaving). The training/scoring attend;
    pipeline stages (parallel/pipeline.py) build one per microbatch."""
    q_pos = positions[:, :, None]                       # [B, T, 1]
    kv_pos = positions[:, None, :]                      # [B, 1, S]

    def attend(layer_idx, q, k, v, cache):
        mask = kv_pos <= q_pos
        window = _layer_window(cfg, layer_idx)
        if window is not None:
            mask &= kv_pos > q_pos - window
        ctx = attention(
            q, k, v, mask,
            scale=cfg.q_scale, logit_softcap=cfg.attn_logit_softcap,
        )
        return ctx, cache

    return attend


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,               # [B, T] int32, right-padded
    positions: jax.Array,            # [B, T] absolute positions
    cache: Optional[KVCache] = None,
    attn_override=None,              # (layer_idx, q, k, v) → ctx; no-cache only
) -> tuple[jax.Array, Optional[KVCache]]:
    """Run the stack; returns (hidden [B, T, H], updated cache).

    With a cache: new K/V are written at their absolute positions and
    attention spans all cache slots — prefill and decode share this path.
    Without a cache (training / one-shot scoring): attention spans the
    current sequence only; `attn_override` swaps the attention computation
    (the sequence-parallel ring path, ops/ring_attention.py, mounts here).
    """
    B = tokens.shape[0]
    use_cache = cache is not None
    if use_cache and attn_override is not None:
        raise ValueError(
            "attn_override applies to the no-cache path only (the cached "
            "path would silently ignore it and run full attention over the "
            "gathered cache, defeating the override's purpose)"
        )
    batch_idx = jnp.arange(B, dtype=jnp.int32)[:, None]

    if use_cache:
        # Inference-only path → flash kernel is safe (no VJP needed); it
        # falls back to the reference attention off-TPU and for tiny shapes.
        from ..ops.flash_attention import flash_attention

        def attend(layer_idx, q, k, v, cache):
            kc, vc = cache
            kc = kc.at[batch_idx, positions].set(k)
            vc = vc.at[batch_idx, positions].set(v)
            ctx = flash_attention(
                q, kc, vc, positions,
                scale=cfg.q_scale,
                logit_softcap=cfg.attn_logit_softcap,
                window=_layer_window(cfg, layer_idx),
            )
            return ctx, (kc, vc)

        kv_scanned = (cache.k, cache.v)
    else:
        causal = make_causal_attend(cfg, positions)

        def attend(layer_idx, q, k, v, cache):
            if attn_override is not None:
                return attn_override(layer_idx, q, k, v), cache
            return causal(layer_idx, q, k, v, cache)

        empty = jnp.zeros(
            (cfg.num_layers * cfg.loop_steps, 0), dtype=jnp.float32)
        kv_scanned = (empty, empty)

    x, new_k, new_v = _run_stack(params, cfg, tokens, positions, kv_scanned, attend)
    new_cache = KVCache(k=new_k, v=new_v) if use_cache else None
    return x, new_cache


def forward_paged(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,               # [B, T] int32, right-padded
    positions: jax.Array,            # [B, T] absolute positions
    paged,                           # engine.kv_cache.PagedKV
    page_tables: jax.Array,          # [B, P] int32
    mesh=None,                       # serving mesh → shard_map the kernels
):
    """Forward pass over the paged KV cache (serving path).

    Same computation as `forward`-with-cache, but KV lives in the shared page
    pools and is addressed through per-sequence page tables — the layout the
    continuous-batching engine composes decode batches from. Used both for
    prefill (T = prompt bucket) and batched decode (T = 1).

    `mesh` (static at the engine's jit boundary) lets the Pallas kernels
    run under shard_map when tp/dp/sp extents exceed 1 — GSPMD cannot
    partition an opaque pallas_call; the jnp paths need no help.
    """
    if cfg.stateful:
        raise ValueError(
            f"{cfg.name} carries per-slot recurrent state: forward_slots"
        )
    hidden, paged, _ = forward_slots(
        params, cfg, tokens, positions, paged, page_tables, None, mesh=mesh,
    )
    return hidden, paged


def forward_slots(params, cfg, tokens, positions, paged, page_tables, state,
                  rows=None, active=None, mesh=None):
    """`forward_slots_counted` without its counts: (hidden, paged, state)."""
    return forward_slots_counted(
        params, cfg, tokens, positions, paged, page_tables, state, rows,
        active, mesh,
    )[:3]


def forward_slots_counted(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,               # [B, T] int32, right-padded
    positions: jax.Array,            # [B, T] absolute positions
    paged,                           # engine.kv_cache.PagedKV
    page_tables: jax.Array,          # [B, P] int32
    state,                           # engine.kv_cache.SlotState (or None)
    rows=None,                       # prefill: hybrid.PrefillRows
    active=None,                     # decode: [B] bool, the live lanes
    mesh=None,
):
    """`forward_paged` over everything a slot holds: the paged pools and,
    for a stateful model, the per-slot recurrent state beside them, which
    a prefill's `rows` or a decode step's `active` lanes say how to use
    (models/hybrid.py `run_stack`). Returns
    (hidden, paged, state, hits, exits); a model without state hands
    `state` back as it came, `hits` is the held experts the live lanes of
    a decode step chose over a layer pattern's expert layers (None where
    nothing is counted: no expert layer, a prefill; the decode block sends
    it home, engine._decode_fn), and `exits` [B, T] the pass of a looped
    stack whose output each position's `hidden` is (None for a stack of
    one pass). The homogeneous families keep
    `_run_paged_stack`'s scan; a
    layer pattern walks its layers unrolled, its "*" layers on the same
    write and attention kernels over their own pool layers, its "A"
    layers on the same write paths and the latent read over a one-part
    pool."""
    from ..ops.paged_attention import (
        latent_prefill_attention,
        paged_prefill_attention,
        paged_write,
        prefill_stage,
    )
    from ..ops.paged_attention_kernel import (
        mla_latent_decode,
        paged_attention_decode,
    )

    decode = tokens.shape[1] == 1
    # Single-token steps take the DMA decode kernels (they read only valid
    # pages, where they lie). A prefill dispatch gathers pages and runs the
    # blockwise kernel over them (wide T amortizes the materialization) —
    # the leading pages that hold the positions its queries see, 0 ..
    # `keys` - 1 (read from the input: one replicated scalar for all
    # layers), not the whole max_seq_len table — into staging buffers made
    # once a dispatch and threaded through the layers beside the pool.
    keys = stage = None
    if not decode:
        keys = jnp.max(positions) + 1
        latent = cfg.latent_kv
        stage = prefill_stage(
            tokens.shape[0], page_tables.shape[1] * paged.page_size,
            tokens.shape[1], paged.page_size,
            heads=1 if latent else cfg.num_kv_heads,
            width=cfg.kv_row_width if latent else cfg.head_dim,
            parts=1 if latent else 2,
            dtype=(jax.eval_shape(
                lambda: embed_tokens(params, cfg, tokens)).dtype
                if paged.quantized else paged.kv.dtype),
            mesh=mesh,
        )

    def attend(layer_idx, q, k, v, held):
        # What the stack threads: the pool, a prefill's stage beside it.
        pool, stage = (held, None) if decode else held
        tables = _layer_tables(paged, layer_idx, page_tables)
        pool = paged_write(pool, k, v, tables, positions, mesh=mesh)
        if cfg.latent_kv:
            # A one-part pool: `k` was the token's one row and `v` None;
            # every head reads the row, its leading columns the value.
            args = dict(scale=cfg.q_scale, v_width=cfg.kv_lora_rank)
            if decode:
                return mla_latent_decode(
                    q, pool, tables, positions, **args), pool
            ctx, stage = latent_prefill_attention(
                q, pool, stage, tables, positions, keys, **args)
            return ctx, (pool, stage)
        args = dict(
            scale=cfg.q_scale,
            logit_softcap=cfg.attn_logit_softcap,
            window=_layer_window(cfg, layer_idx),
            mesh=mesh,
        )
        if decode:
            return paged_attention_decode(
                q, pool, tables, positions, **args), pool
        ctx, stage = paged_prefill_attention(
            q, pool, stage, tables, positions, keys, **args)
        return ctx, (pool, stage)

    if not cfg.layer_pattern:
        hidden, paged, exits = _run_paged_stack(
            params, cfg, tokens, positions, paged, attend, stage
        )
        return hidden, paged, state, None, exits
    if paged.quantized:
        raise ValueError("a layer pattern has no int8-KV path")
    from .hybrid import run_stack

    pool = _stacked(paged)
    if not decode:
        # A prefill's page write is a `lax.cond` over the pool
        # (ops/paged_attention.py `paged_write`). Unrolled, nothing stands
        # between the first and last of them and the reshapes on either
        # side of the walk, and the compiler moves those INTO the
        # conditional: its branches then return the pool in two shapes,
        # which is a copy of the whole pool every dispatch (3.75 GB of a
        # 4,096-page multi-head pool; tests/test_paged_layout.py). The
        # scanned stack has the loop's edge there.
        pool = jax.lax.optimization_barrier(pool)
    hidden, held, state, hits = run_stack(
        params, cfg, tokens, positions, pool if decode else (pool, stage),
        attend, state, rows, active,
    )
    if not decode:
        held = jax.lax.optimization_barrier(held)
    return (hidden, _unstacked(paged, held if decode else held[0]), state,
            hits, None)


def make_sp_override(
    cfg: ModelConfig, mesh, positions: jax.Array, impl: str = "ring"
):
    """Build an attn_override routing attention through a sequence-parallel
    path over the mesh's sp axis: ``impl="ring"`` rotates KV via ppermute
    (ops/ring_attention.py — any head count, sp-1 hops), ``impl="ulysses"``
    re-shards heads via all-to-all (ops/ulysses_attention.py — two
    collectives, needs per-device head counts divisible by sp).

    Lives here so the attention-parameter wiring (q_scale, soft-cap,
    per-layer window interleaving) stays in one module with the dense
    attend closures; callers (train/train.py) just mount the result.
    Returns None when the mesh has no sp extent.
    """
    if mesh is None or mesh.shape.get("sp", 1) <= 1:
        return None
    if impl == "ring":
        from ..ops.ring_attention import ring_attention_spmd as sp_attention
    elif impl == "ulysses":
        from ..ops.ulysses_attention import (
            ulysses_attention_spmd as sp_attention,
        )
    else:
        raise ValueError(f"unknown sp attention impl {impl!r}")

    def override(layer_idx, q, k, v):
        return sp_attention(
            q, k, v, positions, positions, mesh,
            scale=cfg.q_scale,
            logit_softcap=cfg.attn_logit_softcap,
            window=_layer_window(cfg, layer_idx),
        )

    return override


def make_ring_override(cfg: ModelConfig, mesh, positions: jax.Array):
    """Back-compat alias for make_sp_override(impl="ring")."""
    return make_sp_override(cfg, mesh, positions, impl="ring")


def unembed(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Project hidden states to vocab logits (fp32), applying Gemma's final
    soft-cap. Callers gather the positions they need *before* unembedding —
    at 128k-256k vocab the [B, T, V] matmul is the expensive part."""
    if cfg.tie_embeddings:
        logits = unembed_logits(hidden, params["embed"], tied=True)
    else:
        logits = unembed_logits(hidden, params["lm_head"], tied=False)
    if cfg.final_logit_softcap is not None:
        logits = cfg.final_logit_softcap * jnp.tanh(
            logits / cfg.final_logit_softcap
        )
    return logits
