"""Speculative decoding over the paged cache: the engine's draft/verify step.

models/speculative.py proves the draft/verify recurrence on the contiguous
cache; this module carries it into the serving path (measurement config 5 —
BASELINE.md: "server-streamed gRPC with speculative decode"). The cache-
rewind question the contiguous design dodges (VERDICT r1 weak #7) resolves
the same way for the paged layout: position p always maps to the same
physical slot (page_tables[p // page_size], p % page_size), so stale KV
written for rejected drafts at positions ≥ the accepted frontier is
overwritten by the next verify window's own writes *before* any query
attends it — the window starts exactly at the frontier and spans gamma+1
positions, which covers every stale slot (positions advance by ≤ gamma+1
per round). The engine allocates `gamma` extra positions of page slack per
request so the final window's overdraft lands in owned pages, never page 0.

The round is FULLY device-resident (ISSUE 19): acceptance, the extra-token
draw, EOS/cap truncation, per-row state advancement, AND the per-lane
adaptive-gamma dial all run inside one jitted step. The host reads ONE
packed int32 matrix per round — gamma+1 emit columns followed by
SPEC_STAT_COLS stat columns (accepted, proposed, acceptance EWMA in 1e-6
fixed point, next gamma dial) — through the same once-per-block D2H copy
the lookahead pipeline overlaps, instead of the old packed + stats pair.

Per-lane gamma: `gamma_lane` [B] rides the donated slot state. A lane at
dial g < gamma simply never offers drafts beyond g (force-masked in the
acceptance scan), so ONE executable per static `gamma` serves every mix of
dials; when every offered draft is accepted the extra token is the
Leviathan BONUS sample from the target's own distribution at the frontier
(the masked positions were never offered — taking the residual there would
charge the lane for a rejection that never happened). The dial itself
updates on device from a per-lane acceptance EWMA with the same hysteresis
band the old engine-global host ladder used (constants below).

Per-row sampling settings are data (temperature [B], top_p [B]): greedy
rows accept by exact argmax match; sampled rows use Leviathan-style
rejection sampling. top_p composes with speculation by truncating BOTH
distributions: the draft samples from its top-p-truncated dist q' and the
verify accepts against the top-p-truncated target p' — the rejection
identity (accept min(1, p'/q'), residual (p'-q')+) holds for any pair of
distributions, and p' is exactly what the plain sampled path draws from,
so outputs stay target-exact. Truncation uses the same top-k prefilter as
sampling.py (`candidates`; full-vocab probabilities via logsumexp, no
sort); candidates=0 disables the top-p path, and the engine then routes
top_p<1 batches through the plain decode step instead.

RNG: every draw keys on fold_in(lane seed key, token position) plus a
stream tag (draft sample / acceptance uniform / residual), so WITHIN the
spec path a seeded request's randomness is reproducible. Note the spec
path's sampled STREAM differs from the plain path's for the same seed
(drafts draw from the draft model's distribution before acceptance), and
which path a block takes can depend on batchmates (engine._dispatch_step
gates on the whole batch) — so spec-enabled engines guarantee greedy
exactness and distributional reproducibility, not draw-for-draw
batch-independence; plain engines guarantee the full contract.

All functions are pure; the engine jits them with its mesh out_shardings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.transformer import forward_paged, unembed
from .sampling import (
    _row_categorical,
    lane_keys,
    sample_tail,
    truncated_dist,
)

# --- Per-lane adaptive-gamma dial (ISSUE 19 tentpole c). -------------------
# The EWMA blend and hysteresis band live HERE because the update now runs
# inside the jitted round (device-resident, zero crossings); the engine and
# the autopilot import these so host-side reasoning about the dial cannot
# drift from what the device computes.
GAMMA_EWMA_BETA = 0.8        # the old host ladder's 0.8/0.2 blend
GAMMA_ACCEPT_FLOOR = 0.35    # EWMA below → lane dials down to gamma_low
GAMMA_ACCEPT_CEIL = 0.55     # EWMA above → lane dials back to gamma_max

# Stat columns appended after the gamma+1 emit columns of the packed row:
# [accepted, proposed, acceptance EWMA (1e-6 fixed point), next gamma
# dial]. ONE packed [B, gamma+1+SPEC_STAT_COLS] readback per round carries
# tokens, counts, and the dial — the collapse of the old separate stats
# vector readback.
SPEC_STAT_COLS = 4


def _lane_tagger(seeds):
    """Per-lane RNG roots; each draw keys on fold_in(base, token position)
    plus a stream tag, so draft sampling / acceptance / residual draws are
    independent AND a request's randomness is reproducible and
    batch-independent (same contract as the plain path's
    sampling.sample_tail). THE key-derivation scheme: acceptance uniforms
    and residual draws must use this same helper so the (seed, position,
    tag) contract cannot drift between streams."""
    base = lane_keys(seeds[:, 0], seeds[:, 1])            # [B, 2]

    def tagged(positions, tag):
        def one(base_row, p):
            return jax.random.fold_in(jax.random.fold_in(base_row, p), tag)

        if positions.ndim == 1:
            return jax.vmap(one)(base, positions)
        return jax.vmap(
            lambda b, ps: jax.vmap(lambda q: one(b, q))(ps)
        )(base, positions)

    return tagged


def _accept_merge(
    t_logits, drafts, d_dists, last_tokens, seq_lens, active, caps,
    accept_ewma, gamma_lane, pos, greedy_row, temp, eff_top_p, eff_top_k,
    tagged, *, gamma: int, gamma_low: int, gamma_max: int, eos_id: int,
    candidates: int,
):
    """The fused accept/merge core of spec_decode_fn (ISSUE 19 tentpole
    a): acceptance math, truncation and the gamma dial, callable on its
    own logits and drafts (scripts/spec_smoke.py holds it to a host
    reference).

    Acceptance: exact-match for greedy rows, rejection sampling else
    (shared math: models/speculative.py rejection_accept /
    residual_extra_dist — one implementation for both cache layouts).

    Device-side stopping mirrors engine._decode_fn / host _maybe_finish:
    n_out truncates at the first EOS and at the position cap, and
    `new_active` goes False for stopped rows — so a host-finished stream
    is already stopped here and stale lookahead rounds emit nothing and
    write only stationary garbage inside the row's own gamma page slack.

    Returns (packed [B, gamma+1+SPEC_STAT_COLS], new_last, new_seq_lens,
    new_active, new_ewma, new_gamma_lane)."""
    from ..models.speculative import rejection_accept, residual_extra_dist

    B = last_tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    t_choice = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B, γ+1]
    match = drafts == t_choice[:, :gamma]
    draft_idx = pos[:, None] + 1 + jnp.arange(gamma, dtype=jnp.int32)[None, :]

    if candidates:
        t_probs = truncated_dist(
            t_logits,
            jnp.broadcast_to(temp[:, None], t_logits.shape[:2]),
            jnp.broadcast_to(eff_top_p[:, None], t_logits.shape[:2]),
            jnp.broadcast_to(eff_top_k[:, None], t_logits.shape[:2]),
            candidates,
        )
    else:
        t_probs = jax.nn.softmax(t_logits / temp[:, None, None], axis=-1)
    u = jax.vmap(jax.vmap(lambda k: jax.random.uniform(k)))(
        tagged(draft_idx, 102)
    )                                                     # [B, gamma]
    accept_sampled = rejection_accept(t_probs, d_dists, drafts, u)

    accept = jnp.where(greedy_row[:, None], match, accept_sampled)
    # Per-lane dial: a lane at dial g < gamma never OFFERS drafts beyond
    # g — they are force-masked here, so one executable per static gamma
    # serves every mix of dials.
    g_lane = jnp.clip(gamma_lane, 1, gamma)               # [B]
    offered = jnp.arange(gamma, dtype=jnp.int32)[None, :] < g_lane[:, None]
    accept = accept & offered
    acc = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    n_acc = jnp.sum(acc, axis=1)                          # [B]

    # Extra token: target argmax at the frontier (greedy) / residual or
    # bonus sample (sampled rows) [Leviathan et al. 2023]. A lane whose
    # OFFERED drafts were all accepted takes the bonus (target) dist at
    # the frontier, never the residual — the masked positions past its
    # dial were never offered, so there is no rejection to correct for.
    bonus = n_acc >= g_lane
    dist = jnp.where(
        bonus[:, None],
        t_probs[rows, n_acc],
        residual_extra_dist(t_probs, d_dists, n_acc),
    )
    extra_sampled = _row_categorical(
        tagged(pos + 1 + n_acc, 103), jnp.log(jnp.maximum(dist, 1e-20))
    )
    extra = jnp.where(greedy_row, t_choice[rows, n_acc], extra_sampled)

    # --- Emit accepted prefix + extra; advance per-row state. -------------
    emit = jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
    emit = emit.at[rows, n_acc].set(extra)                # [B, gamma+1]
    n_out = (n_acc + 1) * active.astype(jnp.int32)

    cols = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
    is_eos = (emit == eos_id) & (cols < n_out[:, None])
    has_eos = jnp.any(is_eos, axis=1)
    first_eos = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
    n_out = jnp.where(has_eos, first_eos + 1, n_out)
    n_out = jnp.minimum(n_out, jnp.maximum(caps - seq_lens, 0))

    emit = jnp.where(active[:, None], emit, 0)
    new_seq_lens = seq_lens + n_out
    new_last = jnp.where(
        active & (n_out > 0), emit[rows, jnp.maximum(n_out - 1, 0)], last_tokens
    )
    new_active = active & ~has_eos & (new_seq_lens < caps)
    tokens_out = jnp.where(cols < n_out[:, None], emit, -1)  # [B, gamma+1]

    # Acceptance-dial stats, computed HERE because truncation happens here
    # (the host only sees truncated n_out): per ADVICE r1, a round cut
    # short by EOS/cap counts only the drafts that had a chance to be
    # emitted — sent/sent, so a perfect draft reads exactly 1.0 — while a
    # full round counts n_acc over the lane's OFFERED count (its dial,
    # not the static gamma). Inactive lanes contribute nothing.
    untrunc = (n_acc + 1) * active.astype(jnp.int32)
    cut = n_out < untrunc
    acc_rows = jnp.minimum(jnp.maximum(untrunc - 1, 0), n_out)
    prop_rows = jnp.where(cut, n_out, g_lane) * active.astype(jnp.int32)

    # Per-lane dial update, ON DEVICE: the old engine-global host ladder
    # (engine.py _process_spec) moves here, one EWMA + hysteresis band per
    # lane, carried in the donated slot state so it costs no crossings.
    rate = acc_rows.astype(jnp.float32) / jnp.maximum(
        prop_rows, 1
    ).astype(jnp.float32)
    new_ewma = jnp.where(
        prop_rows > 0,
        GAMMA_EWMA_BETA * accept_ewma + (1.0 - GAMMA_EWMA_BETA) * rate,
        accept_ewma,
    )
    # Hold band keeps the STORED dial (not the clipped g_lane): a round
    # dispatched at the low rung must not silently forget that a lane's
    # dial was at gamma_max.
    new_gamma_lane = jnp.where(
        new_ewma < GAMMA_ACCEPT_FLOOR,
        jnp.int32(gamma_low),
        jnp.where(
            new_ewma > GAMMA_ACCEPT_CEIL,
            jnp.int32(gamma_max),
            jnp.clip(gamma_lane, gamma_low, gamma_max),
        ),
    ).astype(jnp.int32)

    packed = jnp.concatenate([
        tokens_out,
        acc_rows[:, None],
        prop_rows[:, None],
        jnp.round(new_ewma * 1e6).astype(jnp.int32)[:, None],
        new_gamma_lane[:, None],
    ], axis=1)                                # [B, gamma+1+SPEC_STAT_COLS]
    return packed, new_last, new_seq_lens, new_active, new_ewma, new_gamma_lane


def spec_prefill_fn(
    t_params, d_params, t_cfg: ModelConfig, d_cfg: ModelConfig,
    t_paged, d_paged,
    tokens, start, last_rel, page_table, seeds, temperature, top_p, top_k,
    greedy: bool = False, candidates: int = 0, mesh=None,
):
    """Prefill BOTH caches for N windows; first tokens from the TARGET.

    Same contract as engine._prefill_fn (N windows at per-row start
    offsets + relative sampling indices → serves batched burst
    admissions, single admissions, and long-prompt chunks alike) plus
    the draft pool: the draft model must see the full prompt or its
    proposals start from a cold cache and acceptance collapses.
    """
    N, T = tokens.shape
    positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    hidden, t_paged = forward_paged(
        t_params, t_cfg, tokens, positions, t_paged, page_table, mesh=mesh
    )
    _, d_paged = forward_paged(
        d_params, d_cfg, tokens, positions, d_paged, page_table, mesh=mesh
    )
    last = hidden[jnp.arange(N), last_rel]                # [N, H]
    logits = unembed(t_params, t_cfg, last)               # [N, V]
    token = sample_tail(
        logits, seeds, start + last_rel + 1, temperature, top_p, top_k,
        greedy, candidates,
    )
    return token, t_paged, d_paged


def spec_decode_fn(
    t_params, d_params, t_cfg: ModelConfig, d_cfg: ModelConfig,
    t_paged, d_paged,
    last_tokens, seq_lens, page_tables, active, caps, seeds, temperature,
    top_p, top_k, accept_ewma, gamma_lane,
    gamma: int, eos_id: int, gamma_low: int | None = None,
    gamma_max: int | None = None, candidates: int = 0, mesh=None,
):
    """One draft/verify round for the whole slot batch.

    Returns (packed [B, gamma+1+SPEC_STAT_COLS] — emit token id within
    each row's emitted prefix, -1 beyond it, then the stat columns, so
    ONE D2H transfer carries tokens, counts, AND the gamma dial — plus
    new_last [B], new_seq_lens [B], new_active [B], new_ewma [B],
    new_gamma_lane [B], t_paged, d_paged). Row semantics: `last_tokens`
    is the already-emitted token at position seq_lens-1 whose KV is not
    yet written (the same invariant as the plain decode step); the round
    emits n_out = n_acc+1 tokens per active row. Greedy rows reproduce
    the target's exact greedy chain for any draft model.
    """
    if gamma_low is None:
        gamma_low = gamma
    if gamma_max is None:
        gamma_max = gamma
    B = last_tokens.shape[0]
    pos = jnp.maximum(seq_lens - 1, 0)
    greedy_row = temperature == 0.0                       # [B]
    temp = jnp.maximum(temperature, 1e-6)                 # [B]
    tagged = _lane_tagger(seeds)
    # Greedy rows must see untruncated dists (their acceptance is argmax
    # equality; truncation is irrelevant and top_p may be any value).
    eff_top_p = jnp.where(greedy_row, 1.0, top_p)         # [B]
    eff_top_k = jnp.where(greedy_row, 0, top_k)           # [B]

    # --- Draft gamma tokens autoregressively (bandwidth-light model). ---
    def draft_step(carry, _):
        d_paged, tok, p = carry
        hidden, d_paged = forward_paged(
            d_params, d_cfg, tok[:, None], p[:, None], d_paged, page_tables,
            mesh=mesh,
        )
        logits = unembed(d_params, d_cfg, hidden[:, 0])   # [B, V]
        dist = (
            truncated_dist(logits, temp, eff_top_p, eff_top_k, candidates)
            if candidates
            else jax.nn.softmax(logits / temp[:, None], axis=-1)
        )
        sampled = _row_categorical(
            tagged(p + 1, 101), jnp.log(jnp.maximum(dist, 1e-20))
        )
        nxt = jnp.where(
            greedy_row, jnp.argmax(logits, axis=-1).astype(jnp.int32), sampled
        )
        return (d_paged, nxt, p + 1), (nxt, dist)

    (d_paged, _, _), (drafts, d_dists) = jax.lax.scan(
        draft_step, (d_paged, last_tokens, pos), None, length=gamma
    )
    drafts = drafts.T                                     # [B, gamma]
    d_dists = jnp.swapaxes(d_dists, 0, 1)                 # [B, gamma, V]

    # --- Verify: ONE target forward over [prev, drafts] (gamma+1 wide —
    # prefill-shaped MXU work instead of gamma bandwidth-bound steps). -----
    window = jnp.concatenate([last_tokens[:, None], drafts], axis=1)
    w_pos = pos[:, None] + jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
    t_hidden, t_paged = forward_paged(
        t_params, t_cfg, window, w_pos, t_paged, page_tables, mesh=mesh
    )
    t_logits = unembed(t_params, t_cfg, t_hidden)         # [B, gamma+1, V]
    # Draft-cache sync over the same window: the scan wrote pos..pos+γ-1
    # only, so on full acceptance slot pos+γ would be a permanent hole
    # (models/speculative.py:164-169 rationale, ported to pages).
    _, d_paged = forward_paged(
        d_params, d_cfg, window, w_pos, d_paged, page_tables, mesh=mesh
    )

    packed, new_last, new_seq_lens, new_active, new_ewma, new_gamma_lane = (
        _accept_merge(
            t_logits, drafts, d_dists, last_tokens, seq_lens, active, caps,
            accept_ewma, gamma_lane, pos, greedy_row, temp, eff_top_p,
            eff_top_k, tagged, gamma=gamma, gamma_low=gamma_low,
            gamma_max=gamma_max, eos_id=eos_id, candidates=candidates,
        )
    )
    return (
        packed, new_last, new_seq_lens, new_active, new_ewma,
        new_gamma_lane, t_paged, d_paged,
    )
