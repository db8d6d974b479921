"""Token sampling: greedy, temperature, top-k, top-p.

jit-friendly by construction: the sampling configuration is static (baked at
trace time via SamplingParams), shapes never depend on data, and top-p is a
cumulative-sum mask over each row's sorted head (the whole row sorted only
when a nucleus runs past it: _trunc_thresholds) rather than dynamic
truncation.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    """Static sampling configuration (hashable → usable as a jit static arg)."""

    temperature: float = 0.0   # 0 → greedy
    top_k: int = 0             # 0 → disabled
    top_p: float = 1.0         # 1.0 → disabled
    max_new_tokens: int = 128

    @property
    def is_greedy(self) -> bool:
        return self.temperature == 0.0


def _apply_top_k(logits: jax.Array, k: int) -> jax.Array:
    vals, _ = jax.lax.top_k(logits, k)
    threshold = vals[..., -1:]
    return jnp.where(logits < threshold, -jnp.inf, logits)


def _top_p_keep_mask(sorted_logits: jax.Array, p: jax.Array) -> jax.Array:
    """Keep-mask over descending-sorted logits: smallest prefix with
    cumulative mass >= p, and always at least the top-1 entry (so p <= 0
    degrades to greedy support instead of masking everything)."""
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    return _prefix_keep_mask(probs, p)


def _prefix_keep_mask(desc_probs: jax.Array, p) -> jax.Array:
    """THE top-p keep rule, shared by every path (exact sort, top-k
    prefilter, and the speculative truncated distributions — they must
    agree token-for-token): over descending-ordered probabilities, keep
    each entry whose exclusive cumulative mass is < p, always keeping
    the first."""
    keep = jnp.cumsum(desc_probs, axis=-1) - desc_probs < p
    return keep.at[..., 0].set(True)


def _rank_keep_mask(width: int, top_k) -> jax.Array:
    """[..., width] keep mask for per-row top-k over DESCENDING-ordered
    entries (rank < k); top_k <= 0 disables. THE top-k rule for every
    candidates-prefiltered path (exact paths use the k-th-value threshold
    instead — ties there keep all equal values, consistently between the
    plain sampler and the speculative truncated dists)."""
    r = jnp.arange(width)
    k = jnp.where(top_k > 0, top_k, width)
    return r < k[..., None]


# The sorted head's width: the exact path reads its truncation thresholds
# out of each row's HEAD_WIDTH largest values and sorts the whole
# vocabulary only when a live sampled row cannot be answered from them
# (top_k wider than the head, or a nucleus that does not close inside
# it). Sized on the chip (scripts/tpu_kernel_check.py --sampler-head;
# PERF.md section 6, PR 61).
HEAD_WIDTH = 64


def _sorted_head(scaled: jax.Array, width: int) -> jax.Array:
    """The `width` largest values of each row of `scaled` [N, V], in
    descending order, duplicates included — exact. Two levels, so that
    nothing the size of the row is ever sorted: the row is cut into
    `groups` strided groups (element j belongs to group j mod groups),
    one pass takes each group's maximum, and the `width` groups with the
    largest maxima hold the row's `width` largest values (a value outside
    them is below `width` distinct maxima), so the head is the top of
    their members. A row too short to cut is taken whole."""
    N, V = scaled.shape
    per_group = 1
    while width * (2 * per_group) ** 2 <= V:
        per_group *= 2                    # ~sqrt(V / width): both top_k's
    if per_group == 1:                    # are then about equally wide
        return jax.lax.top_k(scaled, width)[0]
    groups = -(-V // per_group)
    padded = jnp.pad(
        scaled, ((0, 0), (0, groups * per_group - V)),
        constant_values=-jnp.inf,
    ).reshape(N, per_group, groups)
    _, chosen = jax.lax.top_k(jnp.max(padded, axis=1), width)   # [N, W]
    members = jnp.take_along_axis(padded, chosen[:, None, :], axis=2)
    return jax.lax.top_k(members.reshape(N, per_group * width), width)[0]


def _full_sort_thresholds(scaled: jax.Array, top_p, top_k):
    """(thr_p, thr_k) [N] from one descending sort of the whole row: the
    rule the head stands in for, at any top_k and any nucleus."""
    V = scaled.shape[-1]
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    keep = _top_p_keep_mask(sorted_desc, top_p[:, None])
    thr_p = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1)
    kidx = jnp.clip(top_k - 1, 0, V - 1)
    thr_k = jnp.take_along_axis(sorted_desc, kidx[:, None], axis=-1)[:, 0]
    return thr_p, thr_k


@jax.jit       # one executable for an eager caller too, branches and all
def _trunc_thresholds(scaled: jax.Array, top_p, top_k, sampled=None):
    """THE exact-path truncation thresholds: (thr_p, thr_k, full) such
    that keeping `scaled >= thr_p` realizes the shared top-p keep rule
    and `scaled >= thr_k` keeps the k largest (ties keep all equal
    values); -inf where the row's top_p >= 1 / top_k <= 0 disables the
    rule. One implementation for the plain sampler, the static top-p
    filter AND the speculative truncated dists — they must agree
    token-for-token, so the rule lives in exactly one place.

    scaled [..., V]; top_p, top_k [...]; `sampled` [...] bool marks the
    rows whose thresholds will be used (live, temperature > 0; None: every
    row). Both thresholds come from the row's sorted head (_sorted_head,
    HEAD_WIDTH values): thr_k is its k-th entry, thr_p the smallest entry
    the keep rule keeps, on the head's TRUE probabilities
    exp(head - logsumexp(row)) — the nucleus closes inside the head when
    the head's last entry is not kept. A sampled row with top_k beyond
    the head, or a nucleus still open at its end, needs the whole row
    sorted: `full` (a scalar) says some row did, and then — only then —
    the sort runs and answers those rows. A row answered from its head is
    answered from it whatever the rest of the batch needs, so a request's
    thresholds never depend on its neighbours."""
    lead, V = scaled.shape[:-1], scaled.shape[-1]
    W = min(V, HEAD_WIDTH)
    scaled = scaled.reshape(-1, V)
    top_p = jnp.broadcast_to(top_p, lead).reshape(-1)
    top_k = jnp.broadcast_to(top_k, lead).reshape(-1)
    head = _sorted_head(scaled, W)                         # [N, W] desc
    lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    keep = _prefix_keep_mask(jnp.exp(head - lse), top_p[:, None])
    thr_p = jnp.min(jnp.where(keep, head, jnp.inf), axis=-1)
    kidx = jnp.clip(top_k - 1, 0, W - 1)
    thr_k = jnp.take_along_axis(head, kidx[:, None], axis=-1)[:, 0]
    cut_p, cut_k = top_p < 1.0, top_k > 0
    needs = (cut_p & keep[:, -1] & (W < V)) | (top_k > W)
    if sampled is not None:
        needs &= jnp.broadcast_to(sampled, lead).reshape(-1)
    full = jnp.any(needs)

    def sort_rows(_):
        full_p, full_k = _full_sort_thresholds(scaled, top_p, top_k)
        return jnp.where(needs, full_p, thr_p), jnp.where(needs, full_k, thr_k)

    thr_p, thr_k = jax.lax.cond(
        full, sort_rows, lambda _: (thr_p, thr_k), None
    )
    thr_p = jnp.where(cut_p, thr_p, -jnp.inf)
    thr_k = jnp.where(cut_k, thr_k, -jnp.inf)
    return thr_p.reshape(*lead, 1), thr_k.reshape(*lead, 1), full


def truncated_dist(
    logits: jax.Array,        # [..., V]
    temp: jax.Array,          # [...] (>0; callers handle greedy rows)
    top_p: jax.Array,         # [...]
    top_k: jax.Array,         # [...] int32; <= 0 → disabled
    candidates: int,          # static top-k prefilter width; 0 → exact
) -> jax.Array:
    """Per-row top-p-truncated, renormalized sampling distribution
    [..., V] — exactly the distribution sample_dynamic draws from for the
    same (candidates, top_p): the top-k-prefiltered rule when
    0 < candidates < V (keep rule on FULL-vocab probabilities via
    logsumexp, no sort), the exact full-vocab sort otherwise. Rows with
    top_p >= 1 get the untruncated softmax. The speculative draft/verify
    pair (engine/spec_decode.py) samples and accepts against this."""
    V = logits.shape[-1]
    scaled = logits / temp[..., None]
    probs = jax.nn.softmax(scaled, axis=-1)
    if candidates and candidates < V:
        vals, idx = jax.lax.top_k(scaled, candidates)      # desc [..., C]
        lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
        p_c = jnp.exp(vals - lse)             # true full-vocab probabilities
        keep = _prefix_keep_mask(p_c, top_p[..., None])
        keep &= _rank_keep_mask(candidates, top_k)
        kept = jnp.where(keep, p_c, 0.0)
        trunc = jnp.put_along_axis(
            jnp.zeros_like(probs), idx, kept, axis=-1, inplace=False
        )
    else:
        # Exact full-vocab truncation (candidates disabled OR wider than
        # the vocabulary — never silently skip the requested nucleus).
        thr_p, thr_k, _ = _trunc_thresholds(scaled, top_p, top_k)
        trunc = jnp.where(
            (scaled >= thr_p) & (scaled >= thr_k), probs, 0.0
        )
    trunc = trunc / jnp.maximum(
        jnp.sum(trunc, axis=-1, keepdims=True), 1e-20
    )
    no_trunc = (top_p >= 1.0) & (top_k <= 0)
    return jnp.where(no_trunc[..., None], probs, trunc)


def _top_p_threshold(scaled: jax.Array, p) -> jax.Array:
    """Exact full-vocab top-p cut: the smallest kept logit
    (_trunc_thresholds with no top-k). ONE implementation — the exact
    sampler, the static top-p filter, and the speculative truncated dists
    all cut at this threshold, so tie handling cannot drift between
    paths."""
    return _trunc_thresholds(scaled, p, jnp.int32(0))[0]


def _apply_top_p(logits: jax.Array, p: float) -> jax.Array:
    threshold = _top_p_threshold(logits, jnp.float32(p))
    return jnp.where(logits < threshold, -jnp.inf, logits)


def _masked_rows(logits, temperature, top_p, top_k, candidates: int,
                 live=None):
    """Shared top-p/top-k masking for the dynamic samplers. Returns
    (greedy [B], masked [B, C or V], idx [B, C] | None, scaled_full,
    full): categorical over `masked` (mapped through idx when present)
    realizes the truncated distribution; `scaled_full` serves untruncated
    rows (top_p >= 1 and top_k disabled); `full` says the exact path
    sorted the whole vocabulary for some sampled row among `live` [B]
    (None: every row) — never, on the prefiltered path."""
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    if candidates and candidates < logits.shape[-1]:
        scaled_full = logits / temp                       # [B, V]
        lse = jax.scipy.special.logsumexp(
            scaled_full, axis=-1, keepdims=True
        )
        vals, idx = jax.lax.top_k(scaled_full, candidates)  # desc [B, C]
        greedy = idx[:, 0].astype(jnp.int32)
        probs = jnp.exp(vals - lse)       # true full-vocab probabilities
        keep = _prefix_keep_mask(probs, top_p[:, None])
        keep &= _rank_keep_mask(candidates, top_k)
        return (greedy, jnp.where(keep, vals, -jnp.inf), idx, scaled_full,
                jnp.bool_(False))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temp
    # Per-row top-p/top-k on the scaled logits (shared rules): a greedy
    # or idle row's thresholds are never read, whatever its stale top_p.
    sampled = temperature > 0.0
    if live is not None:
        sampled &= live
    thr_p, thr_k, full = _trunc_thresholds(scaled, top_p, top_k, sampled)
    masked = jnp.where(
        (scaled < thr_p) | (scaled < thr_k), -jnp.inf, scaled
    )
    return greedy, masked, None, scaled, full


def sample_dynamic(
    logits: jax.Array,            # [B, vocab] fp32
    key: jax.Array,
    temperature: jax.Array,       # [B] — 0 → greedy for that row
    top_p: jax.Array,             # [B] — 1.0 → disabled for that row
    top_k: jax.Array = None,      # [B] int32 — <= 0 → disabled
    candidates: int = 0,          # static: 0 → exact (full-vocab sort)
) -> jax.Array:
    """Per-row sampling with *data-dependent* temperature/top-p, one
    shared RNG key for the whole batch.

    The continuous-batching decode step serves many requests with different
    sampling settings in one jitted call, so the settings arrive as arrays
    rather than static config. Greedy rows are selected with jnp.where (no
    control flow → no recompilation as the batch mix changes).

    `candidates` > 0 prefilters each row to its top-`candidates` logits
    with lax.top_k (already descending — no separate [B, vocab] sort, the
    expensive op at 128k-256k vocab) and applies top-p within them:
    equivalent to composing top-k=candidates with top-p. Candidate
    probabilities are normalized by the FULL-vocab logsumexp (a sort-free
    reduction), so the keep rule matches the exact path token-for-token;
    the result is exact whenever the top-p support fits in the candidate
    set. Rows with top_p >= 1 asked for no truncation and bypass the
    prefilter entirely (untruncated categorical needs no sort either).
    Pass candidates=0 for the exact full-vocab path.
    """
    if top_k is None:
        top_k = jnp.zeros(logits.shape[0], jnp.int32)
    greedy, masked, idx, scaled_full, _ = _masked_rows(
        logits, temperature, top_p, top_k, candidates
    )
    if idx is not None:
        k_pre, k_full = jax.random.split(key)
        local = jax.random.categorical(k_pre, masked, axis=-1)
        truncated = jnp.take_along_axis(
            idx, local[:, None], axis=-1
        )[:, 0].astype(jnp.int32)
        # Untruncated rows: unrestricted sampling over the whole vocab.
        full = jax.random.categorical(
            k_full, scaled_full, axis=-1
        ).astype(jnp.int32)
        sampled = jnp.where((top_p >= 1.0) & (top_k <= 0), full, truncated)
    else:
        sampled = jax.random.categorical(
            key, masked, axis=-1
        ).astype(jnp.int32)
    return jnp.where(temperature == 0.0, greedy, sampled)


def _row_categorical(keys: jax.Array, logits: jax.Array) -> jax.Array:
    """Independent per-row draws: keys [B, 2] uint32, logits [B, V] → [B]."""
    return jax.vmap(
        lambda k, l: jax.random.categorical(k, l)
    )(keys, logits).astype(jnp.int32)


def lane_keys(seed_hi: jax.Array, seed_lo: jax.Array) -> jax.Array:
    """Per-lane base PRNG keys [B, 2] from two int32 seed halves — the
    engine's per-request RNG roots (engine.py: every sampled draw for a
    request is keyed by fold_in(base, token position), so a request's
    stream depends only on (seed, prompt), never on batch composition or
    scheduling)."""
    def one(hi, lo):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), hi), lo
        )

    return jax.vmap(one)(seed_hi, seed_lo)


def fold_positions(base_keys: jax.Array, positions: jax.Array) -> jax.Array:
    """fold_in each lane's base key with its token position → [B, 2]."""
    return jax.vmap(jax.random.fold_in)(base_keys, positions)


def _sample_rows(logits, keys, temperature, top_p, top_k, candidates: int,
                 live=None):
    """sample_dynamic_rows' draw and whether the exact path sorted the
    whole vocabulary for it (_masked_rows' `full`): (tokens [B], full)."""
    greedy, masked, idx, scaled_full, took_full = _masked_rows(
        logits, temperature, top_p, top_k, candidates, live
    )
    if idx is not None:
        keys2 = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
        local = _row_categorical(keys, masked)
        truncated = jnp.take_along_axis(
            idx, local[:, None], axis=-1
        )[:, 0].astype(jnp.int32)
        full = _row_categorical(keys2, scaled_full)
        sampled = jnp.where((top_p >= 1.0) & (top_k <= 0), full, truncated)
    else:
        sampled = _row_categorical(keys, masked)
    return jnp.where(temperature == 0.0, greedy, sampled), took_full


def sample_dynamic_rows(
    logits: jax.Array,            # [B, vocab] fp32
    keys: jax.Array,              # [B, 2] uint32 — per-row keys
    temperature: jax.Array,       # [B]
    top_p: jax.Array,             # [B]
    top_k: jax.Array = None,      # [B] int32 — <= 0 → disabled
    candidates: int = 0,
) -> jax.Array:
    """sample_dynamic with an independent RNG key per row — the engine's
    seeded path. Identical masking (shared _masked_rows); only the draw
    granularity differs."""
    if top_k is None:
        top_k = jnp.zeros(logits.shape[0], jnp.int32)
    return _sample_rows(
        logits, keys, temperature, top_p, top_k, candidates
    )[0]


def sample(
    logits: jax.Array,            # [..., vocab] fp32
    key: jax.Array,
    params: SamplingParams,
) -> jax.Array:
    """Sample token ids [...] from logits under the static params."""
    if params.is_greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / params.temperature
    if params.top_k > 0:
        logits = _apply_top_k(logits, params.top_k)
    if params.top_p < 1.0:
        logits = _apply_top_p(logits, params.top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_tail_counted(logits, seeds, positions, temperature, top_p, top_k,
                        greedy: bool, candidates: int = 0, live=None):
    """THE shared sampling tail for prefill and decode (plain and
    speculative paths — one implementation so key derivation cannot
    drift): greedy takes pure argmax (no RNG); sampled rows draw
    independently, each keyed by fold_in(lane seed key, positions[row]).
    Returns (tokens [B], full): `full` is a scalar bool, true when the
    exact sampler sorted the whole vocabulary for a sampled row among
    `live` [B] (None: every row; an idle decode lane's stale top_p asks
    for nothing) — None from a greedy tail, which holds no sampler."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), None
    base = lane_keys(seeds[:, 0], seeds[:, 1])
    keys = fold_positions(base, positions)
    return _sample_rows(
        logits, keys, temperature, top_p, top_k, candidates, live
    )


def sample_tail(logits, seeds, positions, temperature, top_p, top_k,
                greedy: bool, candidates: int = 0):
    """sample_tail_counted's tokens, for a caller that counts nothing."""
    return sample_tail_counted(
        logits, seeds, positions, temperature, top_p, top_k, greedy,
        candidates,
    )[0]
