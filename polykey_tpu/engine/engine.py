"""The inference engine: continuous batching over a paged KV cache.

This is the TPU-native replacement for the reference's mock backend — the
component the north star mounts at the Service seam (SURVEY.md §3.2: "the
handler keeps its signature; the implementation becomes enqueue-into-
scheduler, and the hot loop becomes the decode step loop on-device").

Design:

- One engine thread owns all device state (page pools, page tables, slot
  arrays). gRPC handler threads only enqueue GenRequests and read from
  per-request queues — no device access, no locks around jax calls.
- Static shapes everywhere: the decode batch is a fixed array of
  `max_decode_slots` slots; prompts prefill through a small set of padded
  length buckets. Slot occupancy is data (`active` mask), not shape.
- Latency-tolerant loop: decode runs in K-step blocks (one lax.scan
  dispatch each, device-side EOS/cap stopping), structured as a lookahead
  pipeline with two frontiers. The DISPATCH frontier runs ahead: block
  N+1 is dispatched before block N's results are read back, with up to
  `lookahead_blocks` slot-state generations device-resident (deepened
  proportionally when adaptive blocking shrinks K, so steps-in-flight —
  and therefore roundtrip hiding — stay constant). The PROCESSED frontier
  trails one (or more) blocks behind, reading each block's packed
  "done"/token buffer through the sanctioned `_host_crossing` path —
  landed copies drain in batches, and only a copy that has not landed
  yet blocks the host (measured as `host_stall_ms`). Depth 1 collapses
  the pipeline to synchronous dispatch-then-read, bit-identically.
  The per-step slot state (tokens / seq_lens / active) is DONATED through
  every decode dispatch, so the pipeline is double-buffered rather than
  allocating: at depth 2 exactly two generations exist on device — the
  in-flight block's inputs and the outputs the next dispatch consumes —
  and the donation chain guarantees they never alias. Admissions prefill
  in padded buckets (batched for bursts, chunked for long prompts) and
  activate their lanes via tiny on-device merge dispatches — no sync, no
  pipeline flush; retirements dispatch the mirror-image lane reset.
  Dispatch is asynchronous and effectively free; only first syncs of
  fresh results pay the host↔device roundtrip (PERF.md), so steady state
  pays ~one hidden sync per block regardless of latency.
- Inactive slots point their page tables at the reserved garbage page 0 and
  carry position 0; their lanes compute masked garbage that is never read.
- Page pools are donated through every jitted step (in-place update — the
  pool is by far the largest buffer); the donation chain also totally
  orders every dispatch on the device, which is what makes stale
  in-flight blocks' writes safe (see _retire_lane_fn / _merge_slot).
- RNG: no global chain — per-lane seed halves ride the device state and
  every sampled draw keys on fold_in(seed key, token position)
  (GenRequest.seed).
"""

from __future__ import annotations

import collections
import contextlib
import os
import queue
import threading
import time
import traceback
from dataclasses import dataclass, field
from functools import partial
from typing import TYPE_CHECKING, NamedTuple, Optional

if TYPE_CHECKING:
    from ..obs.trace import Span

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import schedwitness as _schedwitness
from ..faults import get_injector
from ..models.config import ModelConfig, get_config
from ..obs.timeline import STARTUP_PHASES, TimelineRecorder, phase
from ..models.hybrid import (
    FROM_PREVIOUS_ROW,
    FROM_SLOT,
    FROM_ZERO,
    PrefillRows,
)
from ..models.transformer import (
    forward_slots,
    forward_slots_counted,
    unembed,
)
from ..ops.moe import held_experts_grouped
from ..ops.paged_attention import prefill_bounded, prefill_keys_read
from ..parallel.mesh import MeshConfig, create_mesh
from ..parallel.sharding import (
    init_sharded_params,
    kv_scale_sharding,
    paged_kv_sharding,
    shard_params,
)
from .config import EngineConfig, compile_cache_dir
from .device import (
    collective_ops,
    compile_counts,
    compile_delta,
    device_identity,
    device_memory,
    install_compile_census,
    mosaic_calls,
    release_compile_heap,
)
from .executables import ExecutableStore, StoredStep
from .kv_cache import (
    AllocationError,
    BlockAllocator,
    KVHandoffState,
    KVWireError,
    PagedKV,
    SlotState,
    fold_pages,
    init_paged_kv,
    init_slot_state,
    unfold_pages,
)
from .metrics import EngineMetrics, RequestTimings
from .prefix_cache import TIER_DEVICE, TIER_HOST
from .sampling import sample_tail, sample_tail_counted
from .tokenizer import load_tokenizer


# Sanctioned-crossing census (ISSUE 19): every _host_crossing scope names
# its site; entries count here so graphlint GL004 can pin the SET of
# crossing sites a serving smoke actually exercises per engine mode — the
# device-resident spec round's 5→2 per-round drop is a committed gate
# (analysis/graph.py SANCTIONED_CROSSINGS), not a claim. Engine-thread
# writes only; GL004 snapshots deltas around its guarded drive.
CROSSING_CENSUS: dict = {}


def _host_crossing(site: str = "unlabeled"):
    """Deliberate host<->device crossing point: resolve-point reads
    (np.asarray of landed blocks/tokens) and the tiny numpy scalars the
    lane merge/retire dispatches upload. graphlint GL004 smokes the
    serving loop under ``jax.transfer_guard("disallow")``; these scopes
    mark the sanctioned crossings, so any NEW implicit transfer added to
    the loop path trips the guard there instead of shipping silently.
    (PL001 is the source-tier mirror of the same invariant.)

    `site` labels the crossing for the census above; call sites pass a
    stable name (GL004 asserts the fired set against the committed
    table).

    Fast path: with no guard configured (every run except the GL004
    smoke) this is a nullcontext — the real jax context manager costs
    ~30 us per entry, which the per-block process path should not pay.
    The three per-direction options are what actually gate transfers
    (the umbrella jax_transfer_guard propagates INTO them on update but
    doesn't reflect a per-direction update), so they are what we check."""
    CROSSING_CENSUS[site] = CROSSING_CENSUS.get(site, 0) + 1
    if all(
        getattr(jax.config, opt) in (None, "allow")
        for opt in ("jax_transfer_guard_host_to_device",
                    "jax_transfer_guard_device_to_host",
                    "jax_transfer_guard_device_to_device")
    ):
        return contextlib.nullcontext()
    return jax.transfer_guard("allow")


@dataclass
class GenRequest:
    """One generation request, enqueued by a gRPC handler thread.

    The engine pushes ("token", id), then ("done", RequestTimings) or
    ("error", message) into `out`.
    """

    prompt: str
    max_new_tokens: int = 64
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0             # <= 0 → disabled
    # Reproducibility root: on a plain (non-speculative) engine, identical
    # (prompt, seed, params, sampling) yields an identical stream
    # regardless of batch composition or scheduling — every sampled draw
    # is keyed by fold_in(seed key, token position). Speculative engines
    # guarantee greedy exactness and distributional reproducibility only:
    # the spec path draws differently from the plain path, and which path
    # a block takes can depend on batchmates (engine._dispatch_step).
    # Seeds are taken mod 2**64. None → a fresh root from the engine's
    # seed RNG.
    seed: Optional[int] = None
    # Absolute monotonic deadline stamped by the gateway from the RPC's
    # time_remaining() (None → no deadline). The engine drops expired
    # requests at dequeue (before prefill) and at decode-block
    # boundaries, failing them with a "deadline exceeded" error the
    # gateway maps to DEADLINE_EXCEEDED — expired work never reaches the
    # device.
    deadline: Optional[float] = None
    out: queue.Queue = field(default_factory=queue.Queue)
    cancelled: threading.Event = field(default_factory=threading.Event)
    timings: RequestTimings = field(default_factory=RequestTimings)
    # Root span attached by the gateway; None means the request is
    # untraced and the engine records no spans for it (bench and embedder
    # paths pay zero tracing cost). The engine appends queue_wait /
    # prefill / decode children; decode gets per-block children as blocks
    # are processed.
    trace: Optional["Span"] = None
    # Disaggregated tiers (ISSUE 13). `prefill_only`: run prefill, then
    # instead of decoding emit ("handoff", KVHandoffState) + ("done", …)
    # — the prefill-tier worker's mode. `resume_state`: a deserialized
    # KVHandoffState; the engine skips tokenize/prefill entirely, maps
    # the shipped pages into its own pool, and resumes decode at
    # seq_len = prompt_len + 1 — the decode-tier worker's mode. Both
    # default off; every non-disaggregated path never sets them.
    prefill_only: bool = False
    resume_state: Optional[object] = None


@dataclass
class _Slot:
    request: GenRequest
    pages: list[int]
    generated: int = 0
    position_cap: int = 0      # absolute position limit for this request
    # Chunked-prefill state: prompts longer than the largest bucket hold
    # their ids here and prefill one chunk per engine-loop iteration;
    # `pending is None` ⇔ the slot is decoding (or short-prompt prefilled).
    pending: Optional[np.ndarray] = None
    filled: int = 0            # prompt positions already prefilled
    # The slot's page table stays HERE until activation: the decode batch's
    # inactive lanes write garbage KV at position 0 through whatever table
    # the device holds, so a mid-prefill slot's real table must never reach
    # the device mirrors — only the reserved garbage page 0 (see
    # _upload_slot_state) — or decode blocks would corrupt the prompt's
    # position-0 KV between prefill chunks.
    table: Optional[np.ndarray] = None
    # Async prefill: the dispatched-but-unread sampled token (a device
    # array, slot's row at `token_row`) — the lane was already activated
    # on device by the merge dispatch; this handle exists only so the host
    # can emit the first token to the client once the async D2H copy
    # lands (_resolve_prefills). The host never blocks the loop on it.
    token_dev: Optional[jax.Array] = None
    token_row: int = 0
    merged: bool = False       # device lane activated (merge dispatched)
    seed_row: Optional[np.ndarray] = None   # [2] int32 RNG root halves
    prompt_len: int = 0
    prompt_ids: Optional[np.ndarray] = None  # for prefix-cache insertion
    # Host-KV page faults (ISSUE 15): [(key, host_page, chain_index)]
    # for prefix pages whose contents sit in the host tier. While set,
    # the slot is FAULTING — it joins no prefill dispatch — until
    # the engine loop's restore frontier issues its scatter
    # (_issue_restores), after which the donation chain orders the page
    # contents ahead of every dispatch that could read them. The slot
    # owns the listed host pages (detached from the cache at admission);
    # _finish re-adopts them if the slot dies before its restore.
    restore_pages: Optional[list] = None
    # Open "decode" span for traced requests (None otherwise): opened when
    # the first token resolves, closed by _finish; per-block children are
    # appended by _process_step/_process_spec.
    decode_span: Optional["Span"] = None
    # End of this slot's previous emit window (first-token resolve or the
    # last processed block) — the inter-token-latency clock.
    last_emit: float = 0.0


class _RRCursor:
    """Starved-first round-robin cursor over a modulo-N slot space —
    the ONE shared implementation of the `_chunk_rr`/`_restore_rr`
    discipline (schedlint SL002 checks this class instead of divergent
    open-coded copies). A frontier sweep iterates :meth:`scan`; a
    completed sweep calls :meth:`advance` so index order alone never
    privileges a slot; an early exit (budget spent, stream width full)
    calls :meth:`reanchor` ON the first skipped slot so it scans first
    next iteration instead of losing its turn to the advance."""

    __slots__ = ("pos",)

    def __init__(self) -> None:
        self.pos = 0

    def scan(self, n: int):
        """Slot indices anchored at the cursor: (pos+0)%n … (pos+n-1)%n.
        The anchor is captured at the call, so a reanchor() fired by an
        early exit mid-sweep cannot perturb the remaining order."""
        base = self.pos
        return ((base + off) % n for off in range(n))

    def reanchor(self, i: int) -> None:
        """Early exit: the starved slot goes first next sweep."""
        self.pos = i

    def advance(self, n: int) -> None:
        """Completed sweep: rotate the anchor past the slot that led."""
        self.pos = (self.pos + 1) % n


def _prefill_fn(
    params, cfg: ModelConfig, paged: PagedKV,
    tokens, start, last_rel, page_table, seeds, temperature, top_p, top_k,
    state: SlotState = SlotState(), state_rows=None,
    *, greedy: bool, candidates: int = 0, mesh=None,
):
    """Prefill N windows (tokens [N, T]) at absolute positions
    start[i]..start[i]+T-1 and sample from each hidden state at relative
    index last_rel[i]. One compiled shape serves every path: single
    admissions (N=1), burst admissions batched by bucket (N up to the
    group cap), a prompt covered by several windows (prefill_cover:
    consecutive rows on ONE page table at starts s, s+T, …), and long
    prompts chunk through it N=1 at a time; the engine discards the
    sampled token of every window but a prompt's last. Rows may share
    a table because forward_paged writes every row's K/V of a layer
    before any row's attention gathers it, masked by absolute position.
    Padded tail positions write KV that is either masked (position > any
    query), overwritten by later decode steps, or lands on the reserved
    garbage page — never read; padded GROUP rows point their whole table
    at the garbage page.

    `state` is the per-slot recurrent state of a stateful model, donated
    and returned like `paged` (an empty pytree otherwise), and
    `state_rows` [N, 3] int32 says per row whose state it reads, where it
    starts from and which slot keeps its end (hybrid.PrefillRows: slot,
    source, store); a row's real length is last_rel + 1.

    `greedy` is a static variant selector: an all-greedy group takes a
    pure-argmax tail (no full-vocab sort, no RNG use) — at 128k-256k vocab
    the top-p sort is a real per-step cost, and greedy is the north-star
    benchmark mode. Sampled rows draw with fold_in(seed key, sampled
    token's position) — per-request streams, batch-independent.
    """
    N, T = tokens.shape
    positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    rows = None if state_rows is None else PrefillRows(
        state_rows[:, 0], state_rows[:, 1], state_rows[:, 2], last_rel + 1
    )
    hidden, paged, state = forward_slots(
        params, cfg, tokens, positions, paged, page_table, state, rows=rows,
        mesh=mesh,
    )
    last = hidden[jnp.arange(N), last_rel]                 # [N, H]
    logits = unembed(params, cfg, last)                    # [N, V]
    token = sample_tail(
        logits, seeds, start + last_rel + 1, temperature, top_p, top_k,
        greedy, candidates,
    )
    return token, paged, state


def _decode_fn(
    params, cfg: ModelConfig, paged: PagedKV,
    last_tokens, seq_lens, page_tables, active, caps, seeds, temperature,
    top_p, top_k, state: SlotState = SlotState(),
    *, greedy: bool, steps: int, eos_id: int, candidates: int = 0, mesh=None,
):
    """`steps` decode steps for the whole slot batch in ONE dispatch.

    A lax.scan drives the block: each sub-step writes KV for the current
    tokens at position seq_lens-1, samples the next token for live slots,
    and advances device-resident state. Live-ness mirrors the host's
    _maybe_finish ON DEVICE — a slot stops at EOS or when seq_lens reaches
    its position cap — so a finished stream neither advances nor pollutes
    its own cache beyond its final position (its lane keeps computing
    masked garbage that the host discards via the returned emit masks).

    Blocking the decode this way amortizes per-dispatch host overhead
    (Python + host<->device transfer latency) over `steps` tokens. The
    host uploads nothing per block
    and downloads ONE packed [steps, B] int32 array (token id where the
    sub-step emitted for that lane, -1 where it did not) — a single D2H
    transfer per block instead of separate token/mask reads.

    `greedy` (static) selects the argmax-only tail when every active slot
    is greedy, skipping the sampler entirely.

    `state` (the per-slot recurrent state of a stateful model; an empty
    pytree otherwise) rides the scan's carry beside the pool: a sub-step
    advances it for the lanes live at that sub-step and for no other.

    A layer pattern with expert layers also counts the held experts its
    live lanes chose (forward_slots_counted): the block's sum rides home
    as ONE MORE ROW of `packed` ([steps + 1, B], the sum in every
    column). A model without an expert layer compiles to what it compiled
    to without the count, and downloads [steps, B].

    The sampled variant adds one row more, after that one: the sub-steps
    of the block on which the exact sampler sorted the whole vocabulary
    for a live sampled lane (sampling._trunc_thresholds; on every other
    sub-step the rows' sorted heads answered). The greedy variant holds
    no sampler and carries nothing for it.

    A looped stack (ModelConfig.loop_steps > 1) adds `loop_steps` rows
    right after the tokens', before those: row u the block's live
    lane-steps whose exit rule chose pass u (forward_slots_counted's
    `exits`, counted here on the device).
    """

    def one(carry, _):
        last, seq, act, paged, state = carry
        positions = jnp.maximum(seq - 1, 0)[:, None]       # [B, 1]
        hidden, paged, state, hit, exits = forward_slots_counted(
            params, cfg, last[:, None], positions, paged, page_tables,
            state, active=act, mesh=mesh,
        )
        left = None if exits is None else jnp.sum(
            act[None, :]
            & (exits[:, 0][None, :] == jnp.arange(cfg.loop_steps)[:, None]),
            axis=1, dtype=jnp.int32)                       # [loop_steps]
        logits = unembed(params, cfg, hidden[:, 0])        # [B, V]
        # The new token lands at index seq → that position keys its draw.
        tokens, full = sample_tail_counted(
            logits, seeds, seq, temperature, top_p, top_k, greedy, candidates,
            live=act,
        )
        tokens = jnp.where(act, tokens, 0)
        new_seq = seq + act.astype(jnp.int32)
        cont = act & (tokens != eos_id) & (new_seq < caps)
        packed = jnp.where(act, tokens, -1)
        return (tokens, new_seq, cont, paged, state), (packed, left, hit, full)

    carry = (last_tokens, seq_lens, active, paged, state)
    (last, seq, act, paged, state), (packed, lefts, hits, fulls) = jax.lax.scan(
        one, carry, None, length=steps
    )
    sums = [jnp.sum(n, dtype=jnp.int32) for n in (hits, fulls) if n is not None]
    if lefts is not None:
        sums.insert(0, jnp.sum(lefts, axis=0, dtype=jnp.int32)[:, None])
    if sums:
        packed = jnp.concatenate([packed] + [
            jnp.broadcast_to(n, (n.size, packed.shape[1]))
            for n in sums
        ])
    return packed, last, seq, act, paged, state


def _merge_lane_fn(
    last_tokens, seq_lens, page_tables, active, caps, temperature, top_p,
    top_k, seeds, tokens_vec, row, slot, seq_len, cap, temp, tp, tk,
    table_row, seed_row, accept_ewma=None, gamma_lane=None,
    gamma_reset=None,
    *, eos_id: int, spec: bool = False,
):
    """Activate ONE decode lane entirely on device: splice the prefill's
    sampled token (still a device array — no host sync) and the slot's
    geometry into the device-resident decode state. Dispatched right after
    the prefill that produced `tokens_vec`, so the lane joins the next
    decode block without the host ever waiting on the device — the
    mechanism that lets admissions ride the lookahead pipeline instead of
    flushing it.

    The lane is born live only if its first token isn't EOS and the
    position budget allows generation (the same conditions the host's
    _maybe_finish applies when it later emits the first token).

    Speculative engines (`spec=True`) also carry the per-lane gamma dial
    (ISSUE 19) in the donated slot state: a fresh lane starts with an
    optimistic acceptance EWMA of 1.0 and its dial at `gamma_reset`
    (= gamma_max), exactly like the old engine-global ladder's boot
    state — the dial is per-REQUEST evidence, so it must not inherit the
    previous occupant's history."""
    token = tokens_vec.reshape(-1)[row]   # [N] group/prefill token vector
    live = (token != eos_id) & (seq_len < cap)
    out = (
        last_tokens.at[slot].set(token),
        seq_lens.at[slot].set(seq_len),
        page_tables.at[slot].set(table_row),
        active.at[slot].set(live),
        caps.at[slot].set(cap),
        temperature.at[slot].set(temp),
        top_p.at[slot].set(tp),
        top_k.at[slot].set(tk),
        seeds.at[slot].set(seed_row),
    )
    if spec:
        out += (
            accept_ewma.at[slot].set(1.0),
            gamma_lane.at[slot].set(gamma_reset),
        )
    return out


def _retire_lane_fn(last_tokens, seq_lens, page_tables, active, caps, slot):
    """Deactivate ONE lane on device and point its page table at the
    reserved garbage page. Dispatched when the host retires a slot
    (EOS/cap/cancel): the lane's pages go back to the allocator, so later
    blocks must stop writing through the stale table — in-flight blocks
    dispatched before this merge still carry it, which is safe because
    their writes are ordered (pool chaining) before any reuse of the pages
    and masked by absolute position until overwritten."""
    return (
        last_tokens.at[slot].set(0),
        seq_lens.at[slot].set(0),
        page_tables.at[slot].set(jnp.zeros_like(page_tables[0])),
        active.at[slot].set(False),
        caps.at[slot].set(0),
    )


def _kv_restore_fn(paged: PagedKV, idx, pages: PagedKV):
    """Scatter handed-off page contents into the pool at the target's
    own page ids (ISSUE 13 decode-side restore). `pages` arrives in the
    stored layout, kv [L, P, 2, page_size, Hk·D] (and, for int8 KV, the
    two scale pools' pages, restored with it byte for byte): the host
    folds its K and V pages (kv_cache.fold_pages) before the upload,
    so nothing is relaid out on the device. `idx` and `pages` are
    padded to a FIXED width (pages_per_seq) so one compiled executable
    serves every handoff size — pad rows target the reserved garbage
    page 0, whose contents are never read (inactive lanes write it
    constantly anyway). The pool is donated: the restore is an in-place
    page write ordered after every in-flight dispatch through the
    donation chain, exactly like a prefill's KV writes."""
    return jax.tree.map(lambda pool, new: pool.at[:, idx].set(new), paged, pages)


def _kv_gather_fn(paged: PagedKV, idx) -> PagedKV:
    """Gather page contents out of the pool for host-tier eviction
    (ISSUE 15) — the read half of the fixed-width gather/scatter pair
    whose write half is `_kv_restore_fn`; pages leave in the stored
    layout (a PagedKV of page-wide arrays, the int8 scale pools' pages
    with it) and the host unfolds them (kv_cache.unfold_pages). `idx` is
    padded to pages_per_seq (pad rows read the reserved garbage page 0
    and are discarded host-side), so ONE compiled executable serves every spill
    batch — the GL001 discipline. Read-only: the pool is NOT donated
    (the gathered copy leaves, the pool stays), so in-flight decode
    blocks are unaffected and the copy observes the donation-chain
    ordering of every dispatch issued before it."""
    return jax.tree.map(lambda pool: jnp.take(pool, idx, axis=1), paged)


_MAX_PREFILL_GROUP = 8   # rows batched per prefill dispatch


def prefill_group_sizes(slots: int) -> tuple[int, ...]:
    """The row counts N a prefill dispatch is padded to — the [N, bucket]
    shapes the warm-up compiles — given the slot count: a group fills
    from free slots, so 3 or 4 slots stop at 4 rows, 5 and more at
    _MAX_PREFILL_GROUP."""
    return tuple(
        n for n, least in ((1, 1), (2, 2), (4, 3), (_MAX_PREFILL_GROUP, 5))
        if slots >= least
    )


def prefill_cover(
    n: int, start: int, widths: tuple[int, ...], groups: tuple[int, ...],
    page_size: int = 1,
) -> list[tuple[int, int]]:
    """The (width, start) windows that prefill `n` tokens from position
    `start` with the FEWEST rows the compiled shapes allow. `widths` are
    the window widths that exist (the prefill buckets; the chunk width
    beside them for a long prompt's tail), `groups` the row counts a
    dispatch is padded to (prefill_group_sizes).

    A span no wider than the widest window takes ceil(n / w) windows of
    ONE width w, consecutive rows of one [N, w] dispatch on the same
    page table: within a layer every row's K/V is written before any
    row's attention gathers (models/transformer.py), and the mask is by
    absolute position, so a later row reads the earlier rows' keys of
    the same layer. w is the width whose padded row count
    pad(ceil(n / w)) * w is least, a tie going to the fewer windows; a
    width is split over only where it is whole pages (every start stays
    page-aligned, ops/paged_attention.paged_write's page-granular
    scatter) and its windows fit one group. Buckets (128, 512): n <= 128
    -> [128]; 129..256 -> [128, 128]; 257..512 -> [512] (three or four
    128-windows pad to 512 rows — no gain, so one window).

    A longer span leads with windows of the widest width, one dispatch
    each (the long prompt's chunks), and covers its tail the same way."""
    widest = max(widths)
    windows = []
    while n > widest:
        windows.append((widest, start))
        start += widest
        n -= widest
    best = None
    for w in sorted(widths):
        k = -(-n // w)
        if k > 1 and (w % page_size or k > groups[-1]):
            continue
        rows = next(g for g in groups if g >= k) * w
        if best is None or (rows, k) < best[:2]:
            best = (rows, k, w)
    _, k, w = best
    return windows + [(w, start + i * w) for i in range(k)]

# Router weight of a HOST-resident cached prefix token relative to a
# device-resident one (prefix_warmth): warm — no recompute — but a
# restore scatter away from usable, so half credit keeps the router
# preferring truly resident replicas at equal warmth.
_HOST_WARMTH_WEIGHT = 0.5


class _InflightBlock(NamedTuple):
    """One dispatched-but-unprocessed decode block (or spec round) in the
    lookahead pipeline. A NamedTuple so legacy (kind, data, reqs) tuples
    still unpack (tests build minimal blocks by hand); `seq` is the
    block's dispatch sequence number — at process time,
    engine._dispatch_seq - seq is the OBSERVED lookahead (how many newer
    blocks were dispatched before this one's readback), the number the
    dispatch-order regression test pins. `gap_ms` (the host gap preceding
    this dispatch) and `live` (slot indices active at dispatch) carry
    the device-time attribution inputs to process time (ISSUE 10);
    `steps` is the block's device steps per lane, which the lane-step
    outcome counters need for a dead block that is never read; `sampled`
    says the block ran _decode_fn's sampled variant, whose `packed` ends
    in the sampler's row."""

    kind: str
    data: object
    reqs: list
    seq: int = 0
    gap_ms: float = 0.0
    live: tuple = ()
    steps: int = 0
    sampled: bool = False


@dataclass(eq=False)     # compared by identity: `toks_dev` is an array
class _FirstTokens:
    """One prefill dispatch whose sampled tokens hold first tokens the
    host has not read yet (the rows of a dispatch share one `toks_dev`
    and land together). Kept in dispatch order, which is the order the
    device finishes them in; the first read of a dispatch's tokens runs
    inside its `first_token` phase (_read_first_tokens)."""

    toks_dev: jax.Array
    members: list           # [(slot_idx, slot)] of the dispatch's `last` rows
    # When the engine last looked and found it unfinished; until then,
    # when its dispatch call returned. The tokens cannot have been ready
    # for longer than the time since (metrics.on_first_tokens_read).
    polled: float
    read: bool = False      # its `first_token` phase has been entered


class EngineDeadError(RuntimeError):
    """The engine (or pool) cannot take work. `retry_after_ms`, when the
    raiser can estimate it (a replica pool with a supervised restart in
    flight), is the recovery hint the gateway ships as the
    `retry-after-ms` trailer on the resulting UNAVAILABLE — without it,
    well-behaved clients hammer a recovering tier at their own backoff
    schedule instead of the server's."""

    def __init__(self, message: str, retry_after_ms: Optional[int] = None):
        super().__init__(message)
        self.retry_after_ms = retry_after_ms


class EngineOverloadedError(RuntimeError):
    """Admission shed this request (queue bound or estimated-delay
    check). `retry_after_ms` is the engine's best guess at when a retry
    could be admitted — the gateway ships it as trailing metadata."""

    def __init__(self, message: str, retry_after_ms: int = 100):
        super().__init__(message)
        self.retry_after_ms = retry_after_ms


# Error-message prefix contract with the gateway: engine failures that
# begin with this map to gRPC DEADLINE_EXCEEDED (tpu_service).
DEADLINE_MSG = "deadline exceeded"

# The engine's jitted steps by the attribute that holds each, and the
# static argument names each is jitted with: what jax.jit is told, and
# what the executable store (engine/executables.py) takes out of a call
# before it reaches a loaded executable.
_STEP_STATICS = {
    "_jit_prefill": ("cfg", "greedy", "candidates", "mesh"),
    "_jit_decode": ("cfg", "greedy", "steps", "eos_id", "candidates", "mesh"),
    "_jit_merge": ("eos_id", "spec"),
    "_jit_retire": (),
    "_jit_kv_restore": (),
    "_jit_kv_gather": (),
    "_jit_spec_prefill": ("t_cfg", "d_cfg", "greedy", "candidates", "mesh"),
    "_jit_spec_decode": (
        "t_cfg", "d_cfg", "gamma", "eos_id", "gamma_low", "gamma_max",
        "candidates", "mesh",
    ),
}


class InferenceEngine:
    def __init__(
        self,
        config: EngineConfig,
        params: Optional[dict] = None,
        health=None,
        logger=None,
        seed: int = 0,
        draft_params: Optional[dict] = None,
    ):
        t_begin = time.monotonic()
        config.validate()
        self.config = config
        self.logger = logger
        install_compile_census(logger)
        compiles_before = compile_counts()
        self.metrics = EngineMetrics()
        # Warm-up's evidence about what it built (stats()): one row a
        # warm-up dispatch (the `startup` record's `executables`), Mosaic
        # custom calls per served step, collectives per step on a mesh.
        self._warm_rows: list = []
        self._warm_compiles: dict = {}
        self._warm_kernels: dict = {}
        self._warm_collectives: dict = {}
        # Start-up is measured where it happens (ISSUE 62): the whole
        # constructor is the `init` phase, its stages phases inside it, on
        # this thread — the engine thread starts after the last of them.
        # No method of its own for the body: a Python frame between the
        # process entry and the warm-up dispatches costs 1.7 s (17 %) of
        # a warm 7B start on the chip — JAX's lowering pays for every
        # user frame above an operation (PERF.md section 6, PR 62).
        with self._phase("init"):
            # Platform, device_kind, device count, roofline row — fixed for
            # the process; an unknown TPU kind raises here, at engine start.
            self._identity = device_identity()
            # Constructor inputs AS PASSED (before checkpoint load / quantize /
            # shard mutate the local): the supervisor's default restart factory
            # replays them so a restarted engine is built from the same
            # weights/seed, not a fresh random init (None → the checkpoint or
            # random-init path reruns, which is already faithful). Pinning the
            # raw params tree costs its host memory for the engine's lifetime,
            # so it happens only when supervision can actually consume it.
            self._ctor_args = {
                "params": params if config.supervise else None,
                "seed": seed,
                "draft_params": draft_params if config.supervise else None,
            }
            # Whether weights came from the caller (vs checkpoint/seed
            # derivation) — one input to the durable-KV params fingerprint.
            self._params_explicit = params is not None
            self.model_cfg = get_config(config.model)
            self.tokenizer = load_tokenizer(config.tokenizer)
            self.health = health
            # Fault injection (polykey_tpu/faults.py): None unless
            # POLYKEY_FAULTS is set, so every injection point below is one
            # attribute load + `is None` — nothing on the hot path when off.
            self._faults = get_injector()
            # Identity within a replica pool (engine/replica_pool.py): fault
            # targeting (":replica=N") and per-replica metric labels key on
            # it. A standalone engine is replica 0.
            self.replica_id = config.replica
            # Tier identity within a disaggregated worker (engine/worker.py):
            # scopes ":tier=prefill|decode" fault targeting. None everywhere
            # else, so tier-targeted faults can never fire in-process.
            self._tier = config.disagg_tier or None
            self._dtype = jnp.dtype(config.dtype)

            # --- Serving mesh: tp shards heads/hidden (Megatron specs,
            # parallel/sharding.py), dp shards the decode-slot batch, ep shards
            # MoE expert weights (token dispatch rides all-to-all over ep —
            # measurement config 4). tp=dp=ep=1 degenerates to a single-device
            # mesh with identical code paths (specs over size-1 axes are
            # no-ops, so there is no unsharded special case to keep in sync).
            n_devices = (
                config.tp * config.dp * config.ep * config.sp * config.pp
            ) * config.num_slices
            all_devices = jax.devices()
            if n_devices > len(all_devices):
                raise ValueError(
                    f"tp={config.tp} x dp={config.dp} x ep={config.ep} x "
                    f"sp={config.sp} x pp={config.pp} x "
                    f"slices={config.num_slices} needs {n_devices} "
                    f"devices, have {len(all_devices)}"
                )
            # Replica placement: one of N pooled replicas (replicas > 1) owns
            # device slice i when the host has a slice for every replica. With
            # fewer devices the replicas share the first slice (several
            # engines on one chip divide its HBM) — stated in DEPLOY.md and
            # logged, never silent. An engine that is not in a pool takes the
            # first slice whatever its `replica` says: a disagg worker's
            # `replica` is its index within its tier (a fault-targeting and
            # label identity, engine/worker.py), and that worker is the only
            # engine in its process.
            first = 0
            if config.replicas > 1 and config.replica >= config.replicas:
                raise ValueError(
                    f"replica={config.replica} is not one of "
                    f"replicas={config.replicas}"
                )
            if config.replicas > 1 and (
                config.replicas * n_devices <= len(all_devices)
            ):
                first = config.replica * n_devices
            elif config.replicas > 1 and logger is not None:
                logger.warn(
                    "replicas share devices",
                    replica=config.replica, replicas=config.replicas,
                    devices_per_replica=n_devices, devices=len(all_devices),
                )
            devices = all_devices[first:first + n_devices]
            if self.model_cfg.num_kv_heads % config.tp != 0:
                raise ValueError(
                    f"tp={config.tp} must divide num_kv_heads="
                    f"{self.model_cfg.num_kv_heads} ({self.model_cfg.name})"
                )
            # dp is per-slice; the mesh's dp axis extent (what slots batch
            # over) is num_slices × dp.
            total_dp = config.dp * config.num_slices
            if config.max_decode_slots % total_dp != 0:
                raise ValueError(
                    f"dp={config.dp} x num_slices={config.num_slices} must "
                    f"divide max_decode_slots={config.max_decode_slots}"
                )
            if config.ep > 1:
                if not self.model_cfg.is_moe:
                    raise ValueError(
                        f"ep={config.ep} requires an MoE model "
                        f"({self.model_cfg.name} has no experts)"
                    )
                if self.model_cfg.num_experts % config.ep != 0:
                    raise ValueError(
                        f"ep={config.ep} must divide num_experts="
                        f"{self.model_cfg.num_experts}"
                    )
            if self.model_cfg.num_layers % config.pp != 0:
                raise ValueError(
                    f"pp={config.pp} must divide num_layers="
                    f"{self.model_cfg.num_layers}"
                )
            mesh_config = MeshConfig(
                dp=config.dp, pp=config.pp, sp=config.sp, ep=config.ep,
                tp=config.tp,
            )
            if config.num_slices > 1:
                # Hybrid DCN mesh: dp (the only axis whose collectives
                # amortize DCN latency) spans the slices; everything else
                # stays inside one ICI domain.
                from ..parallel.distributed import create_hybrid_mesh

                self.mesh = create_hybrid_mesh(
                    mesh_config, config.num_slices, devices
                )
            else:
                self.mesh = create_mesh(mesh_config, devices=devices)
            from jax.sharding import NamedSharding, PartitionSpec

            # int8 KV (config.kv_dtype): quantized pools + scale pools. The
            # pool sharding then becomes a PagedKV-shaped pytree (the scale
            # pools are 4-D — one broadcast NamedSharding can't serve both).
            self._kv_quantized = config.kv_dtype == "int8"
            if self._kv_quantized and self._identity["platform"] == "tpu":
                from ..ops.paged_attention_kernel import INT8_KV_MOSAIC_ERROR

                raise ValueError(
                    "kv_dtype=int8 (POLYKEY_KV_DTYPE) does not lower on TPU "
                    f"yet: {INT8_KV_MOSAIC_ERROR}"
                )
            scale_sh = kv_scale_sharding(self.mesh) if self._kv_quantized else None
            self._pool_sharding = PagedKV(
                kv=paged_kv_sharding(self.mesh), ks=scale_sh, vs=scale_sh
            )
            self._repl = NamedSharding(self.mesh, PartitionSpec())
            # Sequence-parallel prefill: the window's token axis shards over
            # sp, spreading prefill compute across chips; the page pools are
            # sp-replicated, so GSPMD exchanges the KV writes (sp=1 → a no-op
            # spec, same code path).
            self._prefill_tok = NamedSharding(self.mesh, PartitionSpec(None, "sp"))
            self._dp_vec = NamedSharding(self.mesh, PartitionSpec("dp"))
            self._dp_mat = NamedSharding(self.mesh, PartitionSpec("dp", None))
            # Pinned output shardings keep the donated pool's layout stable
            # across steps (donation requires matching input/output shardings).
            self._jit_prefill = jax.jit(
                _prefill_fn,
                static_argnames=_STEP_STATICS["_jit_prefill"],
                donate_argnames=("paged", "state"),
                out_shardings=(self._repl, self._pool_sharding, self._repl),
            )
            self._dp_steps = NamedSharding(self.mesh, PartitionSpec(None, "dp"))
            # Double-buffered slot state: the three per-step-advancing vectors
            # (last_tokens / seq_lens / active) are donated alongside the pool,
            # so the decode chain updates them in place instead of allocating a
            # fresh generation per block. With lookahead, the runtime keeps the
            # in-flight block's buffers alive until it completes while the next
            # dispatch writes the other generation — two device-resident copies
            # that never alias (GL002 audits the aliasing). Read-only geometry
            # (page_tables / caps / sampling params / seeds) is NOT donated:
            # it has no corresponding output to alias into.
            self._jit_decode = jax.jit(
                _decode_fn,
                static_argnames=_STEP_STATICS["_jit_decode"],
                donate_argnames=(
                    "paged", "last_tokens", "seq_lens", "active", "state",
                ),
                out_shardings=(
                    self._dp_steps, self._dp_vec, self._dp_vec,
                    self._dp_vec, self._pool_sharding, self._repl,
                ),
            )
            # Lane merges: tiny functional updates of the device-resident decode
            # state, chained between blocks so slot transitions never flush the
            # lookahead pipeline (out shardings must match the decode inputs so
            # the chain keeps stable layouts).
            lane_out = (
                self._dp_vec, self._dp_vec, self._dp_mat, self._dp_vec,
                self._dp_vec, self._dp_vec, self._dp_vec, self._dp_vec,
                self._dp_mat,
            )
            # Speculative engines carry two extra donated-state vectors (the
            # per-lane acceptance EWMA + gamma dial, ISSUE 19) that the merge
            # resets per admission.
            merge_out = lane_out + (
                (self._dp_vec, self._dp_vec)
                if config.draft_model is not None else ()
            )
            self._jit_merge = jax.jit(
                _merge_lane_fn,
                static_argnames=_STEP_STATICS["_jit_merge"],
                out_shardings=merge_out,
            )
            self._jit_retire = jax.jit(
                _retire_lane_fn, out_shardings=lane_out[:5],
            )
            # KV handoff restore (ISSUE 13): scatter shipped pages into this
            # pool at the receiving slot's page ids. Donates the pool like
            # every other pool-touching dispatch; the fixed padded width
            # (pages_per_seq) keeps it ONE executable per engine.
            self._jit_kv_restore = jax.jit(
                _kv_restore_fn,
                donate_argnames=("paged",),
                out_shardings=self._pool_sharding,
            )
            # Host-tier eviction gather (ISSUE 15): the read half of the
            # gather/scatter pair (restore above is the write half). Same
            # fixed width (pages_per_seq), one executable; outputs land
            # replicated so the host copy is a straight np.asarray.
            self._jit_kv_gather = jax.jit(
                _kv_gather_fn, out_shardings=self._repl,
            )
            # Per-request RNG roots for seedless requests (GenRequest.seed
            # None): drawn once per admission from the engine seed.
            self._seed_rng = np.random.default_rng(seed + 3)

            with self._phase("place_params"):
                self.params = self._place_params(
                    params, self.model_cfg, config.checkpoint_path, seed
                )

            with self._phase("pools"):
                B, P = config.max_decode_slots, config.pages_per_seq
                pool_fp_dtype = (
                    jnp.dtype(config.kv_dtype)
                    if config.kv_dtype in ("bfloat16", "float32") else self._dtype
                )
                kv_q = jnp.int8 if self._kv_quantized else None
                # Pools are born sharded: zeros built on the default device and
                # then moved would pass the whole pool through device 0, next to
                # whatever another replica already holds there.
                def new_pool(model_cfg: ModelConfig) -> PagedKV:
                    shape = jax.eval_shape(lambda: init_paged_kv(
                        model_cfg, config.num_pages, config.page_size,
                        pool_fp_dtype, kv_dtype=kv_q,
                    ))
                    return jax.tree.map(
                        lambda x, sh: jnp.zeros(x.shape, x.dtype, device=sh),
                        shape, self._pool_sharding,
                    )

                self.paged = new_pool(self.model_cfg)
                # Host-known facts of the pool for `stats` (the arrays are donated
                # dispatch by dispatch): its bytes, and one token's over all layers.
                self._kv_pool_bytes = sum(
                    x.nbytes for x in jax.tree.leaves(self.paged))
                self._kv_token_bytes = self._kv_pool_bytes // (
                    config.num_pages * config.page_size)
                # Expert layers of a layer pattern: a decode block of such a model
                # brings home the held experts its live lanes chose (_decode_fn).
                self._expert_layers = self.model_cfg.layer_pattern.count("E")
                # A looped stack's passes (1: none): its decode block brings
                # home the exits by pass, and every dispatch counts
                # `_layer_passes` layer applications a step.
                self._loops = self.model_cfg.loop_steps
                self._layer_passes = (
                    self._loops * self.model_cfg.num_layers
                    if self._loops > 1 else 0)
                self._loop_attrs = (
                    {"loops": self._loops} if self._loops > 1 else {})
                self._loop_stats = {"loop": {
                    "steps": self._loops,
                    "kv_layers": self.model_cfg.kv_layers,
                    "kv_bytes_per_token": self._kv_token_bytes,
                }} if self._loops > 1 else {}
                # What a slot holds beside its pages (kv_cache.SlotState): born
                # on the device like the pools; an empty pytree for a model with
                # no recurrent state.
                self.state = SlotState()
                if self.model_cfg.stateful:
                    self.state = jax.tree.map(
                        lambda x: jnp.zeros(x.shape, x.dtype, device=self._repl),
                        jax.eval_shape(
                            lambda: init_slot_state(self.model_cfg, B, self._dtype)
                        ),
                    )
                # Host-known like the pool's (the leaves are donated dispatch by
                # dispatch): the bytes the state takes on the device, its layout's
                # padding included.
                self._state_pool_bytes = self.state.resident_nbytes
                self.allocator = BlockAllocator(config.num_pages)
                # --- Host-memory KV tier (ISSUE 15): a second page pool in host
                # RAM for COLD pages (prefix-cache entries of finished sticky
                # sessions, long-context middles). 0 bytes → no pool, no store,
                # every existing path byte-identical.
                self._host_kv = None
                self._kv_state = None
                self._kv_reloaded_pages = 0
                if config.host_kv_bytes > 0:
                    from .kv_cache import HostKVPool, host_kv_page_bytes

                    page_b = host_kv_page_bytes(
                        self.model_cfg, config.page_size, pool_fp_dtype, kv_q
                    )
                    capacity = config.host_kv_bytes // max(1, page_b)
                    if capacity < 1:
                        raise ValueError(
                            f"POLYKEY_HOST_KV_BYTES={config.host_kv_bytes} is "
                            f"smaller than one KV page ({page_b} bytes for "
                            f"{self.model_cfg.name} at page_size "
                            f"{config.page_size})"
                        )
                    self._host_kv = HostKVPool(
                        self.model_cfg, capacity, config.page_size,
                        pool_fp_dtype, self._kv_quantized,
                    )
                # Resident working set: _finish spills cold pages whenever a
                # retirement leaves fewer free device pages than this floor.
                # Live attribute (not a frozen-config read): the autopilot's
                # set_resident_floor actuation must land mid-run.
                self._resident_low = (
                    config.host_kv_resident_pages or config.num_pages // 8
                )
                # Per-iteration restore budget. Mirrors the frozen config field
                # into a live attribute so _issue_restores reads THIS every
                # iteration — a mid-run set_kv_restore_slots actuation takes
                # effect on the next loop pass instead of being silently
                # ignored (the knob-application audit, ISSUE 18).
                # Clamped like set_kv_restore_slots: the restore frontier's
                # progress floor (schedlint SL001) assumes a budget of at least
                # one scatter per iteration.
                self._restore_slots = max(1, config.host_kv_restore_slots)
                # Restore-frontier round-robin cursor (the shared starved-first
                # discipline for page faults).
                self._restore_rr = _RRCursor()
                # Durable-store gc cadence: gc() lists and parses the whole
                # state dir — amortize it over batches instead of paying a
                # directory scan per spill on the engine thread.
                self._kv_gc_countdown = 0
                self._prefix = None
                if config.prefix_cache:
                    from .prefix_cache import PrefixCache

                    self._prefix = PrefixCache(
                        self.allocator, config.page_size,
                        config.prefix_cache_pages or config.num_pages // 2,
                        host_pool=self._host_kv,
                    )
                if self._host_kv is not None and config.kv_state_dir:
                    # Restart-durable prefix cache: reload spilled pages
                    # persisted by a previous incarnation (same weights — the
                    # params_key gate) into the host tier, so the first sticky
                    # turn after a supervisor restart faults its prefix back in
                    # instead of recomputing it cold.
                    from .prefix_cache import PrefixStateStore

                    self._kv_state = PrefixStateStore(
                        config.kv_state_dir, self.model_cfg.name, config.page_size,
                        params_key=self._params_fingerprint(seed),
                        quantized=self._kv_quantized, logger=logger,
                    )
                    self._kv_reloaded_pages = self._kv_state.load_into(
                        self._prefix, self._host_kv,
                        expect_shape=(
                            self.model_cfg.num_layers, 0, config.page_size,
                            self.model_cfg.num_kv_heads, self.model_cfg.head_dim,
                        ),
                    )

            self._chunk = config.prefill_chunk or max(config.prefill_buckets)
            # What prefill_cover chooses from: the [N, bucket] shapes of an
            # admission, and for a long prompt's tail the buckets narrower
            # than the chunk beside the chunk itself.
            self._group_sizes = prefill_group_sizes(config.max_decode_slots)
            self._chunk_widths = tuple(
                b for b in config.prefill_buckets if b < self._chunk
            ) + (self._chunk,)
            # Interleaved-prefill budget (config.prefill_budget; 0 → auto):
            # prefill tokens allowed per loop iteration while decode lanes
            # are live. Floored at one chunk so a budget below the dispatch
            # granularity still makes progress (the knob bounds stall length,
            # it must never deadlock a long prompt).
            self._prefill_budget = max(
                config.prefill_budget or 2 * self._chunk, self._chunk
            )
            # Round-robin cursor over slots with pending chunked prefill —
            # budgeted chunk advancement must not starve the highest-index
            # pending slot when the budget covers fewer chunks than slots.
            self._chunk_rr = _RRCursor()
            self._block_steps = config.decode_block_steps
            # Load-adaptive block size (config.adaptive_block): the solo block
            # is a distinct static `steps` value, so it gets its own compile —
            # warmup covers it alongside the full block.
            self._solo_steps = (
                max(1, config.decode_block_steps // 8)
                if config.adaptive_block else config.decode_block_steps
            )
            self._last_dispatch_steps = 0    # observability (bench step_costs)

            # --- Speculative decoding: draft model + its own page pool, same
            # page tables (position → (page, offset) is model-independent).
            self._spec = config.draft_model is not None
            # Adaptive gamma (VERDICT r2 #8, per-lane since ISSUE 19): each
            # LANE carries its own dial on a two-level ladder {max(1, γ/2), γ}
            # driven by a per-lane acceptance EWMA with hysteresis, updated
            # INSIDE the jitted round (spec_decode._accept_merge) — the dial
            # rides the donated slot state, so it costs no crossings. The
            # host-side `self._gamma` is now only the DISPATCH WIDTH: the
            # ladder rung covering the widest active lane dial (recomputed
            # from the packed round stats in _process_spec), clamped by the
            # autopilot's `_gamma_cap` (set_spec_gamma). Page/position SLACK
            # always reserves for _gamma_max, so a mid-stream dial increase
            # can never overflow a slot's pages. Each ladder rung is its own
            # compile; warmup covers both.
            self._gamma_max = config.spec_gamma if self._spec else 0
            self._gamma = self._gamma_max
            self._gamma_low = (
                max(1, config.spec_gamma // 2)
                if (self._spec and config.adaptive_gamma) else self._gamma_max
            )
            self._gamma_cap = self._gamma_max   # autopilot bound (rung-snapped)
            # Batch-aggregate acceptance EWMA, kept for observability/back-
            # compat (stats()["spec_accept_ewma"]); the per-lane EWMAs below
            # are what drive the dial.
            self._accept_ewma = 1.0          # optimistic start: full gamma
            if self._spec:
                from .spec_decode import spec_decode_fn, spec_prefill_fn

                self.draft_cfg = get_config(config.draft_model)
                if self.draft_cfg.vocab_size != self.model_cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {self.draft_cfg.vocab_size} != target "
                        f"vocab {self.model_cfg.vocab_size}"
                    )
                if self.draft_cfg.num_kv_heads % config.tp != 0:
                    raise ValueError(
                        f"tp={config.tp} must divide draft num_kv_heads="
                        f"{self.draft_cfg.num_kv_heads}"
                    )
                if self.draft_cfg.num_layers % config.pp != 0:
                    raise ValueError(
                        f"pp={config.pp} must divide draft num_layers="
                        f"{self.draft_cfg.num_layers} (the draft's params and "
                        f"page pool shard the same pp axis)"
                    )
                # Caller-provided draft weights win (benchmarks pass the
                # target tree itself to measure the acceptance-1.0 ceiling).
                # The engine-wide quantize knob covers the draft too — the
                # draft exists to save bandwidth, and an unquantized draft
                # could push the HBM budget the flag exists to protect.
                with self._phase("place_params"):
                    self.draft_params = self._place_params(
                        draft_params, self.draft_cfg,
                        config.draft_checkpoint_path, seed + 2,
                    )
                with self._phase("pools"):
                    self.d_paged = new_pool(self.draft_cfg)
                self._jit_spec_prefill = jax.jit(
                    spec_prefill_fn,
                    static_argnames=_STEP_STATICS["_jit_spec_prefill"],
                    donate_argnames=("t_paged", "d_paged"),
                    out_shardings=(
                        self._repl, self._pool_sharding, self._pool_sharding,
                    ),
                )
                self._jit_spec_decode = jax.jit(
                    spec_decode_fn,
                    static_argnames=_STEP_STATICS["_jit_spec_decode"],
                    # Same double-buffered slot-state donation as the plain
                    # decode block — spec rounds ride the identical pipeline.
                    # The per-lane gamma dial (accept_ewma / gamma_lane,
                    # ISSUE 19) donates alongside: it advances on device
                    # every round like the rest of the slot state.
                    donate_argnames=(
                        "t_paged", "d_paged",
                        "last_tokens", "seq_lens", "active",
                        "accept_ewma", "gamma_lane",
                    ),
                    out_shardings=(
                        self._dp_mat, self._dp_vec, self._dp_vec, self._dp_vec,
                        self._dp_vec, self._dp_vec,
                        self._pool_sharding, self._pool_sharding,
                    ),
                )

            # Host mirrors of per-slot device state (engine thread only). They
            # are the source of truth at slot transitions (admit/finish mark
            # `_dev_dirty` → re-upload); between transitions the decode state —
            # RNG key included — stays device-resident (`_dev`) and advances
            # on-device, so steady decode uploads nothing per block.
            self._page_tables = np.zeros((B, P), dtype=np.int32)
            self._seq_lens = np.zeros((B,), dtype=np.int32)
            self._last_tokens = np.zeros((B,), dtype=np.int32)
            self._active = np.zeros((B,), dtype=bool)
            self._caps = np.zeros((B,), dtype=np.int32)
            self._temperature = np.zeros((B,), dtype=np.float32)
            self._top_p = np.ones((B,), dtype=np.float32)
            self._top_k = np.zeros((B,), dtype=np.int32)
            self._seeds = np.zeros((B, 2), dtype=np.int32)
            # Per-lane gamma dial mirrors (spec engines, ISSUE 19): refreshed
            # from each processed round's packed stat columns — the DEVICE
            # copy is authoritative between slot transitions, exactly like
            # the other mirrors.
            self._lane_ewma = np.ones((B,), dtype=np.float32)
            self._lane_gamma = np.full(
                (B,), max(self._gamma_max, 1), dtype=np.int32
            )
            self._slots: list[Optional[_Slot]] = [None] * B
            self._dev: dict = {}
            self._dev_dirty = True

            self._submit: queue.Queue[GenRequest] = queue.Queue()
            # Lookahead pipeline: dispatched-but-unprocessed decode blocks,
            # oldest first (_InflightBlock records). While dispatching, up to
            # _depth_target - 1 blocks stay queued ACROSS iterations — depth
            # counts device-resident slot-state generations including the
            # block just dispatched, so depth 2 = double-buffered overlap
            # (dispatch N+1 before reading N) and depth 1 = synchronous
            # dispatch-then-read, exactly. POLYKEY_DISPATCH_LOOKAHEAD
            # overrides the config depth regardless of how the config was
            # built (serving env, bench, tests) — the operator knob for
            # host-bound decode (DEPLOY.md runbook).
            from collections import deque

            self._inflight_q: deque = deque()
            # Prefill dispatches whose first tokens wait to be read, oldest
            # first (_FirstTokens; engine thread only).
            self._first_tokens: list = []
            # (end time, reason) of the `_admit` visits that left requests
            # waiting, oldest first, and when the visit now running began:
            # what a request's queue time is cut by when it is admitted
            # (_stamp_admitted). Engine thread only. Requests leave the
            # queue oldest first, so an admission drops the visits that
            # ended before its request arrived; the bound only matters to a
            # queue that nothing leaves.
            self._admit_visits: deque = deque(maxlen=1024)
            self._admit_began = 0.0
            try:
                # polylint: disable=ML004(documented operator override: env beats any programmatic config, see comment above)
                self._depth = max(1, int(os.environ.get(
                    "POLYKEY_DISPATCH_LOOKAHEAD", config.lookahead_blocks
                )))
            except ValueError:
                self._depth = config.lookahead_blocks
            # Flight-deck timeline (ISSUE 10): the promoted pipeline ring —
            # typed, bounded, always-on events for both frontiers plus slot
            # lifecycle, exported as Perfetto JSON (/debug/timeline). The
            # dispatch-order regression test asserts dispatch-N+1-before-
            # process-N on it. timeline_capacity=0 disables it entirely:
            # no ring allocated, every emission site one `is None` branch —
            # obs-off engines pay nothing (the memory-discipline contract
            # tests/test_timeline.py pins).
            self.timeline: Optional[TimelineRecorder] = (
                TimelineRecorder(config.timeline_capacity)
                if config.timeline_capacity > 0 else None
            )
            # SLO signal plane (ISSUE 11): windowed rates/delta-quantiles
            # over a ring of metrics snapshots, plus burn-rate evaluation of
            # the declarative POLYKEY_SLO objectives. Attached to the
            # METRICS object so the supervisor's adoption path carries the
            # windows and budget state across restarts; the supervisor
            # rebinds `timeline` to the fresh ring. signals_interval_s=0
            # allocates nothing (`metrics.signals is None`) and the loop
            # emission site below is one `is None` branch.
            if config.signals_interval_s > 0 and self.metrics.signals is None:
                from ..obs.signals import (
                    ENV_POLICY,
                    ENV_WINDOWS,
                    SignalPlane,
                    SloPolicy,
                    windows_from_spec,
                )

                # Config-first, env-fallback: an EngineConfig.from_env
                # carries the boot-time specs (restart-stable); a
                # programmatic config controls them without touching
                # os.environ; the empty defaults read the env here.
                self.metrics.signals = SignalPlane(
                    self.metrics,
                    windows=windows_from_spec(
                        config.signals_windows
                        or os.environ.get(ENV_WINDOWS, "")
                    ),
                    interval_s=config.signals_interval_s,
                    policy=SloPolicy.from_spec(
                        config.slo_policy or os.environ.get(ENV_POLICY, "")
                    ),
                    timeline=self.timeline,
                )
            self._dispatch_seq = 0
            # In-flight target for the CURRENT block size: when the adaptive
            # dispatcher shrinks K, the LOOKAHEAD portion deepens by the
            # same factor (1 + (depth-1) x (K/steps) — constant queued-ahead
            # steps), because roundtrip hiding needs lookahead × block_time
            # ≥ the host's sync roundtrip — a K/8 block at the configured
            # depth would leave the host stalled on un-landed copies. Only the
            # lookahead portion scales, so depth 1 stays exactly
            # synchronous at every block size (the escape-hatch contract).
            # The 64-block cap binds only for large lookahead_blocks (the
            # scale factor itself tops out at block_steps // solo_steps).
            self._depth_target = self._depth
            # The executable store (engine/executables.py): where the
            # process has a compile cache directory, warm-up loads each
            # step's COMPILED executable from beside it — no trace, no
            # lowering — or builds and writes it, and every `_jit_*`
            # handle becomes the step served from that table. No
            # directory (POLYKEY_COMPILE_CACHE=0, or none placed), or no
            # warm-up to fill a table: the handles stay the jitted
            # functions themselves.
            self._executables: Optional[ExecutableStore] = None
            if config.compile_warmup:
                cache_dir = compile_cache_dir()
                if cache_dir is not None:
                    self._executables = ExecutableStore(
                        cache_dir, self.mesh.devices.flat, logger)
                    for handle, statics in _STEP_STATICS.items():
                        if hasattr(self, handle):
                            setattr(self, handle, StoredStep(
                                self._executables,
                                handle.removeprefix("_jit_"),
                                getattr(self, handle), statics,
                            ))
                with self._phase("warmup"):
                    self._compile_warmup()
                # What building or loading the executables left on the host
                # heap goes back to the OS before the first request, not 2 s
                # into serving (device.release_compile_heap).
                with self._phase("release_heap"):
                    release_compile_heap()
            self._wake = threading.Event()
            self._stop = threading.Event()
            self.dead: Optional[str] = None
            self.last_progress = time.monotonic()
        self._startup = self._startup_record(t_begin, compiles_before)
        if logger is not None:
            rows = self._startup["executables"]
            logger.info(
                "engine started", **self._startup,
                slowest_executable=max(
                    rows, key=lambda row: row["seconds"], default=None),
            )
        self._thread = threading.Thread(
            target=self._run, name="polykey-engine", daemon=True
        )
        self._thread.start()

    def _startup_record(self, t_begin: float, compiles_before: dict) -> dict:
        """What the construction just finished cost (stats()["startup"],
        the `engine started` line): `t_begin` / `t_end` on
        time.monotonic() — CLOCK_MONOTONIC, one clock for every process
        of the host, so a harness that started this process can place the
        constructor inside its own wait; `stages`, the seconds of each
        start-up phase entered (`init` is the whole, the others lie
        inside it one after another); `compile`, what the compile census
        gained over the constructor, and `warmup_compile`, over its
        warm-up alone; `executables`, one row a warm-up dispatch, whose
        `backend_s` add up to `warmup_compile`'s; `executable_store`,
        what the store did for those rows (_store_counts)."""
        seconds, entered = self.metrics.phase_seconds, self.metrics.phase_count
        return {
            "t_begin": t_begin,
            "t_end": time.monotonic(),
            "stages": {
                name: round(seconds[name], 6) for name in STARTUP_PHASES
                if name != "warm_call" and entered[name]
            },
            "compile": compile_delta(compiles_before, compile_counts()),
            "warmup_compile": self._warm_compiles,
            "executables": self._warm_rows,
            "executable_store": self._store_counts(),
        }

    def _store_counts(self) -> Optional[dict]:
        """What the executable store did for this engine so far
        (ExecutableStore.counts), None where the engine has none."""
        if self._executables is None:
            return None
        return self._executables.counts()

    def _place_params(
        self, params: Optional[dict], model_cfg: ModelConfig,
        checkpoint_path: Optional[str], seed: int,
    ) -> dict:
        """A model's params on this engine's mesh, quantized per config:
        the caller's tree, else the checkpoint, else random init (the
        dev/bench path) — which draws each leaf straight into its final
        dtype and sharding, so 8B-int8 on one chip and 8B-bf16 at tp=4
        start from EngineConfig alone."""
        config = self.config
        bits = config.quantize_bits if config.quantize else None
        if params is None and not checkpoint_path:
            return init_sharded_params(
                jax.random.PRNGKey(seed), model_cfg, self.mesh, self._dtype,
                quantize_bits=bits,
            )
        if params is None:
            from ..models.loader import load_checkpoint

            params = load_checkpoint(checkpoint_path, model_cfg, self._dtype)
        if bits:
            # Weight-only quantization halves (int8) or quarters (int4)
            # weight HBM — the single-chip 8B enabler (models/quant.py).
            from ..models.quant import quantize_params

            params = quantize_params(params, model_cfg, bits=bits)
        return shard_params(params, model_cfg, self.mesh)

    # -- public API (any thread) -------------------------------------------

    def submit(self, request: GenRequest) -> None:
        if self.dead is not None:
            raise EngineDeadError(self.dead)
        if self._stop.is_set():
            raise EngineDeadError("engine is shut down")
        # Bounded admission with load shedding: over-limit submissions
        # fail in O(1) with a retry-after hint instead of queueing into
        # unbounded latency — overload degrades to fast rejections.
        limit = self.config.max_queue_depth
        if limit > 0 and self._submit.qsize() >= limit:
            self.metrics.on_shed()
            raise EngineOverloadedError(
                f"submit queue full ({limit} waiting)",
                retry_after_ms=self._retry_after_ms(),
            )
        if request.deadline is not None:
            # Deadline-aware admission: if the estimated queue delay
            # already blows the request's budget, shedding now is
            # strictly better than burning a slot on work the client
            # will throw away. Estimate is qsize × EWMA(service time) /
            # slots — zero until the first completed request, so cold
            # engines never false-positive.
            est = self._estimated_queue_delay_s()
            if est > 0.0 and time.monotonic() + est >= request.deadline:
                self.metrics.on_shed()
                raise EngineOverloadedError(
                    f"estimated queue delay {est:.2f}s exceeds request "
                    "deadline",
                    retry_after_ms=self._retry_after_ms(),
                )
        self.metrics.on_admit()
        self._submit.put(request)
        self._wake.set()
        # Close the submit/shutdown race: if the engine died or stopped
        # between the check above and the put, nothing will ever drain the
        # queue — fail it from here (queue ops are thread-safe; a duplicate
        # terminal event is harmless, readers stop at the first one).
        if self.dead is not None or self._stop.is_set():
            self._fail_pending(self.dead or "engine is shut down")

    def _estimated_queue_delay_s(self) -> float:
        """Expected wait before a newly queued request is admitted: with
        S slots draining in parallel and an EWMA per-request service
        time, the queue drains at roughly S requests per EWMA."""
        ewma = self.metrics.service_time_ewma_s()
        if ewma <= 0.0:
            return 0.0
        slots = max(1, self.config.max_decode_slots)
        return self._submit.qsize() * ewma / slots

    def _retry_after_ms(self) -> int:
        """Shed hint: about one slot-drain interval, floored at 50 ms so
        clients never busy-spin, defaulting to 100 ms on a cold engine."""
        ewma = self.metrics.service_time_ewma_s()
        if ewma <= 0.0:
            return 100
        slots = max(1, self.config.max_decode_slots)
        return max(50, int(1000.0 * ewma / slots))

    # -- router signals (replica_pool; any thread) ---------------------------

    def queue_delay_estimate_s(self) -> float:
        """Public routing signal: the same estimated queue delay the
        deadline-aware admission check uses (qsize × service EWMA /
        slots) — the replica pool ranks candidates by it."""
        return self._estimated_queue_delay_s()

    def load_fraction(self) -> float:
        """Instantaneous load for routing: (busy slots + queued) over
        slots. The EWMA-based delay estimate is 0 until a first request
        completes, so a cold pool would tie every score and pile work on
        replica 0 — this term spreads concurrent cold traffic."""
        slots = max(1, self.config.max_decode_slots)
        busy = sum(s is not None for s in self._slots)
        return (busy + self._submit.qsize()) / slots

    def prefix_warmth(self, ids) -> float:
        """Fraction [0, 1] of `ids` (token id sequence) whose KV this
        engine could serve from its prefix cache — the NetKV-style
        warmth signal the replica/disagg routers score on. Read-only:
        no page retains, no LRU refresh, no hit accounting
        (prefix_cache.probe_tiered). TIER-AWARE (ISSUE 15): host-
        resident pages count as warm — a spilled-but-warm sticky
        session must not route as cold — but weighted below device-
        resident ones (a restore scatter stands between them and a
        dispatch). 0.0 with prefix caching off or an empty prompt."""
        if self._prefix is None or len(ids) == 0:
            return 0.0
        ids = np.asarray(ids, dtype=np.int32)
        dev, host = self._prefix.probe_tiered(ids)
        return (dev + _HOST_WARMTH_WEIGHT * host) / len(ids)

    # -- live-knob actuation (autopilot; any thread) -------------------------
    #
    # The scheduling knobs below were once read from the frozen config
    # (or captured at construction) exactly once — a mid-run change was
    # silently ignored. Each setter mutates the ONE attribute the engine
    # loop reads per iteration, so an actuation lands within one loop
    # pass. Plain int/float attribute swaps: GIL-atomic against the loop
    # thread, no lock needed (racelint: no blocking under any lock).
    # Every setter clamps to the engine's own hard bounds and returns
    # the value actually applied — the autopilot records old→new from
    # the return, never from its request.

    def set_lookahead(self, depth: int) -> int:
        """Dispatch pipeline depth (POLYKEY_DISPATCH_LOOKAHEAD). The
        adaptive _depth_target recomputes from _depth on every dispatch,
        so the new depth governs the very next block."""
        self._depth = max(1, min(64, int(depth)))
        return self._depth

    def set_prefill_budget(self, tokens: int) -> int:
        """Interleaved-prefill token budget per loop iteration. Floored
        at one chunk (the knob bounds stall length, it must never
        deadlock a long prompt)."""
        tokens = max(int(tokens), self._chunk)
        self._prefill_budget = tokens
        return tokens

    def set_kv_restore_slots(self, slots: int) -> int:
        """Per-iteration restore-frontier budget (POLYKEY_KV_RESTORE_
        SLOTS): host→device page-fault scatters issued ahead of each
        iteration's dispatches."""
        self._restore_slots = max(1, min(
            int(slots), self.config.max_decode_slots
        ))
        return self._restore_slots

    def set_resident_floor(self, pages: int) -> int:
        """Host-KV resident floor (POLYKEY_KV_RESIDENT_PAGES): _finish
        spills cold pages whenever a retirement leaves fewer free
        device pages than this."""
        self._resident_low = max(0, min(
            int(pages), self.config.num_pages
        ))
        return self._resident_low

    def set_spec_gamma(self, gamma: int) -> int:
        """Upper bound on the speculative dispatch width (autopilot's
        `decide_gamma`). Snapped to the nearest ladder rung — the per-
        lane dial (device-resident) only ever takes rung values, and
        each rung is its own compiled executable, so an off-rung cap
        would either mask the dial or force a fresh compile. The cap
        clamps the dispatch-width recompute in _process_spec; lane dials
        keep adapting underneath it, so lifting the cap restores full
        gamma within one round."""
        if not self._spec:
            return 0
        g = int(gamma)
        # Snap down to the low rung unless the cap clears the high one.
        self._gamma_cap = (
            self._gamma_max if g >= self._gamma_max else self._gamma_low
        )
        self._gamma = min(self._gamma, self._gamma_cap)
        return self._gamma_cap

    def knob_setpoints(self) -> dict:
        """The live values of every actuated knob — what the loop will
        read on its next iteration, not what any config said at boot."""
        out = {
            "lookahead": self._depth,
            "prefill_budget": self._prefill_budget,
        }
        if self._host_kv is not None:
            out["restore_slots"] = self._restore_slots
            out["resident_floor"] = self._resident_low
        if self._spec:
            out["spec_gamma"] = self._gamma_cap
        return out

    @staticmethod
    def _deadline_expired(request: GenRequest) -> bool:
        return (
            request.deadline is not None
            and time.monotonic() >= request.deadline
        )

    @staticmethod
    def _trace_id_of(request: Optional[GenRequest]) -> Optional[str]:
        if request is None or request.trace is None:
            return None
        return request.trace.trace_id

    def _expire(self, request: GenRequest, phase: str) -> None:
        """Fail an expired request that never held (or no longer holds)
        a slot. Slot-holding expiries go through _finish instead."""
        self.metrics.on_deadline_expired(phase)
        if self.timeline is not None:
            self.timeline.expire(phase, self._trace_id_of(request))
        request.out.put(("error", f"{DEADLINE_MSG} while {phase}"))
        self.metrics.on_finish(request.timings, failed=True,
                               trace_id=self._trace_id_of(request))

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap.update(
            {
                "model": self.model_cfg.name,
                "replica": self.replica_id,
                # What this engine runs on (engine/device.py): platform
                # as JAX reports it, the devices this engine's mesh owns
                # and their allocator readings, compiles so far and their
                # seconds, what this engine's construction cost
                # (_startup_record), and what warm-up found in the
                # executables it built.
                **self._identity,
                "devices": [int(d.id) for d in self.mesh.devices.flat],
                "device_memory": device_memory(self.mesh.devices.flat),
                "compiles": compile_counts(),
                # The constructor's record; the store's counts as they
                # stand NOW (`fallback_calls` moves while serving).
                "startup": {
                    **self._startup,
                    "executable_store": self._store_counts(),
                },
                "warmup_mosaic_calls": dict(self._warm_kernels),
                "warmup_collectives": dict(self._warm_collectives),
                "slots_busy": sum(s is not None for s in self._slots),
                "slots_total": self.config.max_decode_slots,
                "pages_free": self.allocator.num_free,
                "pages_total": self.config.num_pages,
                # Bytes of per-slot recurrent state beside the pool, as
                # they lie on the device (kv_cache.SlotState
                # `resident_nbytes`; 0 for a model that has none).
                "state_pool_bytes": self._state_pool_bytes,
                # The page pool itself, and what ONE token holds in it
                # over all layers: pages in use read in bytes.
                "kv_pool_bytes": self._kv_pool_bytes,
                "kv_token_bytes": self._kv_token_bytes,
                **self._loop_stats,
                "queued": self._submit.qsize(),
                "inflight_blocks": len(self._inflight_q),
                "prefill_budget": self._prefill_budget,
                # Lookahead pipeline (ISSUE 6): configured depth (env
                # override included); the host-stall/overlap numbers
                # ride the metrics snapshot (host_stall_ms_p50,
                # lookahead_observed_*).
                "lookahead_depth": self._depth,
            }
        )
        if snap.get("avg_lanes") is not None:
            # Measured occupancy fraction: step-weighted mean live lanes
            # over the slot count (the ≥0.8 target ISSUE 4 soaks against).
            snap["occupancy"] = round(
                snap["avg_lanes"] / max(1, self.config.max_decode_slots), 4
            )
        signals = self.metrics.signals
        if signals is not None:
            # Windowed quantiles alongside the lifetime ones (ISSUE 11
            # satellite): ttft_ms_p95_5m etc. reflect the last minutes,
            # not the whole uptime — the staleness fix operators read.
            snap.update(signals.stats_fields())
        if self._spec:
            # Dispatch width (the rung covering the widest active lane
            # dial, under the autopilot cap) plus the per-lane dial/EWMA
            # aggregates (ISSUE 19 satellite: the engine-global value is
            # meaningless per-lane — mean/min/max over occupied lanes is
            # what operators and the autopilot read).
            snap["spec_gamma"] = self._gamma
            snap["spec_gamma_cap"] = self._gamma_cap
            occ = [
                i for i, s in enumerate(self._slots) if s is not None
            ]
            if occ:
                dials = self._lane_gamma[occ]
                ewmas = self._lane_ewma[occ]
                snap["spec_gamma_mean"] = round(float(dials.mean()), 4)
                snap["spec_gamma_min"] = int(dials.min())
                snap["spec_gamma_max"] = int(dials.max())
                snap["spec_accept_ewma_mean"] = round(
                    float(ewmas.mean()), 4
                )
                snap["spec_accept_ewma_min"] = round(
                    float(ewmas.min()), 4
                )
                snap["spec_accept_ewma_max"] = round(
                    float(ewmas.max()), 4
                )
            else:
                snap["spec_gamma_mean"] = float(self._gamma)
                snap["spec_gamma_min"] = self._gamma
                snap["spec_gamma_max"] = self._gamma
                snap["spec_accept_ewma_mean"] = 1.0
                snap["spec_accept_ewma_min"] = 1.0
                snap["spec_accept_ewma_max"] = 1.0
        if self._prefix is not None:
            snap.update(self._prefix.stats())
        # Host-KV tier (ISSUE 15): always present — collectors index
        # these unconditionally, and 0s on a tier-less engine are the
        # honest reading (no host pool exists).
        snap["host_kv"] = self._host_kv is not None
        snap["kv_host_pages"] = (
            self._host_kv.used if self._host_kv is not None else 0
        )
        snap["kv_host_capacity"] = (
            self._host_kv.capacity if self._host_kv is not None else 0
        )
        # Device pages in use by slots/cache (reserved page 0 excluded).
        snap["kv_device_pages"] = (
            self.config.num_pages - 1 - self.allocator.num_free
        )
        snap["kv_reloaded_pages"] = self._kv_reloaded_pages
        return snap

    @property
    def busy(self) -> bool:
        return (
            bool(self._active.any())
            or not self._submit.empty()
            or any(s is not None for s in self._slots)
        )

    def shutdown(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=timeout)

    # -- engine thread ------------------------------------------------------

    def _phase(self, name: str, **attrs) -> phase:
        """One engine phase (obs.timeline.phase): a `polykey/<name>`
        annotation on the profiler's clock plus the always-on per-phase
        accumulators of whichever EngineMetrics this engine holds now
        (a supervised restart hands the old one to the new engine)."""
        return phase(self.metrics, name, **attrs)

    def _process_due(self, target: int, floor: int) -> bool:
        """The processed frontier's rule: the oldest in-flight block is
        due while more than `target` are queued, or more than `floor`
        and its packed copy has already landed."""
        queued = len(self._inflight_q)
        return queued > target or (
            queued > floor and self._block_ready(self._inflight_q[0])
        )

    def _run(self) -> None:
        # Loop phases (ISSUE 26): each stretch of this loop that does
        # work runs inside `self._phase(...)`. A phase is entered only
        # when there is something for it to do: the idle loop turns at
        # 20 Hz and must not fill a capture with empty spans.
        # Heap-witness heartbeat (memlint ML006): bound once outside the
        # loop; heartbeat() self-throttles to ~1 Hz and is a no-op
        # unless POLYKEY_HEAP_WITNESS armed the witness at import.
        from ..analysis.heapwitness import heartbeat as _heap_heartbeat

        try:
            while not self._stop.is_set():
                _heap_heartbeat()
                if self.dead is not None:  # watchdog tripped while we were out
                    self._fail_all(self.dead)
                    return
                # Admit every waiting request a free slot can take, every
                # iteration — under the interleaved-prefill TOKEN BUDGET
                # (config.prefill_budget) whenever decode lanes are live.
                # Burst admissions cost one batched prefill dispatch per
                # bucket group (_dispatch_prefill_group) and long prompts
                # advance in chunks, all scheduled BETWEEN decode-block
                # dispatches; the budget bounds how many prefill tokens
                # ride any one gap, so a prompt burst can no longer stall
                # in-flight decode beyond ~budget tokens of prefill work
                # (Sarathi-style chunked interleaving; ISSUE 4). With no
                # live lanes the budget is waived — there is no ITL to
                # protect and cold bursts should fill every slot at once.
                # (History: the old `limit=1 if active` admission policy
                # equilibrated occupancy at ~max_new/K lanes — measured
                # 5/32 live lanes and 230 tok/s where full slots give
                # ~2,000; r03, PERF.md.)
                decode_live = bool(self._active.any())
                budget = self._prefill_budget if decode_live else None
                worked, spent = False, 0
                if not self._submit.empty():
                    with self._phase("admit"):
                        worked, spent = self._admit(budget=budget)
                if self._host_kv is not None:
                    # Restore frontier (ISSUE 15): issue host→device
                    # page scatters for faulting slots BEFORE this
                    # iteration's prefill/decode dispatches — restores
                    # ride ahead of need on the donation chain, budgeted
                    # like interleaved prefill so they cannot stall live
                    # decode beyond host_kv_restore_slots uploads.
                    if self._issue_restores():
                        worked = True
                remaining = (
                    None if budget is None else max(0, budget - spent)
                )
                chunked = self._advance_chunked_prefills(remaining)
                if chunked:
                    worked = True
                self.metrics.on_prefill_interleave(
                    spent + chunked, decode_live
                )
                if self._dev_dirty and self._inflight_q:
                    # Rare full transition (init/recovery): a mirror upload
                    # may never rewind live device state, so the whole
                    # pipeline drains first.
                    self._drain_inflight()
                # Dispatch frontier: keep up to `_depth_target` slot-state
                # generations resident — the dispatch in hand plus
                # `_depth_target - 1` queued blocks (constant
                # steps-in-flight across block sizes).
                # Device-side stopping makes stale blocks safe (a stream the
                # host finished was stopped on device by the same EOS/cap
                # condition, so its lookahead emit lanes read -1);
                # cancellations are the one host-only transition, guarded
                # per-block by the request-identity snapshot in
                # _process_step. Spec rounds carry the same device-side
                # stop, so both block kinds pipeline alike.
                dispatched = False
                if self._active.any():
                    with self._phase("dispatch"):
                        self._inflight_q.append(self._dispatch_step())
                    dispatched = True
                    worked = True
                if _schedwitness.installed() and self._active.any():
                    # Decode boundary: a dispatched block serves every
                    # active lane (flat batch); active lanes with no
                    # block this iteration are waiting on the frontier.
                    lanes = np.flatnonzero(self._active).tolist()
                    _schedwitness.note(
                        "decode", lanes if dispatched else [], lanes
                    )
                if self._has_unresolved():
                    with self._phase("resolve"):
                        self._resolve_prefills()
                # Processed frontier: drain down to depth-1 queued blocks
                # (depth counts the dispatch in hand, so depth 1 reads the
                # block it just dispatched — synchronous — and depth 2
                # keeps one block in flight while dispatching the next).
                # Behind the forced drain, any OLDER block whose packed
                # copy already LANDED is processed too — a free batched
                # readback that never blocks the host. The freshest block
                # stays in flight across the iteration boundary (floor)
                # even when a fast device finishes it instantly: reading
                # it now would re-serialize dispatch-then-read, and the
                # whole point of the pipeline is that block N's readback
                # happens AFTER block N+1's dispatch (the happens-before
                # the dispatch-order test pins). Idle iterations (floor 0)
                # collapse the pipeline completely.
                target = max(0, self._depth_target - 1) if dispatched else 0
                floor = 1 if (dispatched and self._depth > 1) else 0
                while self._process_due(target, floor):
                    head = self._inflight_q[0]
                    with self._phase(
                        "process", seq=head.seq,
                        lookahead=self._dispatch_seq - head.seq,
                    ):
                        # Popped only here: between the pop and the
                        # block's on_process_block nothing else runs.
                        self._process_step(self._inflight_q.popleft())
                    worked = True
                # SLO signal plane (ISSUE 11): ring sample at block
                # boundaries — idle iterations reach here too at ~20 Hz
                # (the low-rate fallback timer). Time-gated inside to
                # signals_interval_s; one `is None` branch when off.
                signals = self.metrics.signals
                if signals is not None:
                    signals.maybe_sample()
                if worked:
                    self.last_progress = time.monotonic()
                else:
                    # Idle iteration ⇒ no live lanes and an empty
                    # pipeline: the idle wait must not be charged to the
                    # next request as device time (attribution reads the
                    # inter-dispatch gap as device-busy, which only
                    # holds while dispatches tile the device schedule).
                    self.metrics.on_dispatch_idle()
                    if self._has_unresolved():
                        with self._phase("resolve"):
                            self._resolve_prefills(block=True)
                    with self._phase("idle_wait"):
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
                    # Idle time is not a stall: only the engine thread itself
                    # may refresh the stall clock (a submit() reset would let
                    # steady client traffic suppress the watchdog during a
                    # genuine device hang mid-_step).
                    self.last_progress = time.monotonic()
            self._fail_all("engine is shut down")
        except Exception as e:  # engine thread must never die silently
            self.dead = f"engine loop crashed: {e}"
            if self.logger is not None:
                self.logger.error(
                    "engine loop crashed",
                    error=str(e),
                    traceback=traceback.format_exc(),
                )
            self._fail_all(self.dead)
            if self.health is not None:
                self.health.shutdown()

    def _admit(self, budget: Optional[int] = None) -> tuple[bool, int]:
        """Admit waiting requests into free slots. A short prompt (or a
        prefix-cache suffix) is covered by the fewest rows the compiled
        windows allow (prefill_cover): one window of the bucket that
        holds it, or several windows of a narrower bucket — 129..256
        tokens on buckets (128, 512) are two 128-row windows, not one of
        512. Windows gather into per-bucket groups and prefill in ONE
        batched dispatch per group (burst admissions — e.g. cold start —
        pay one device call instead of one per request; spec engines
        batch the same way, prefilling both pools per dispatch); a
        prompt's windows are consecutive rows of the SAME dispatch, so
        a group that cannot take them all goes out first. Long prompts
        register for chunked prefill.

        `budget` (tokens, None → unbounded) is the interleaved-prefill
        discipline: each short admission charges the rows of its cover
        (the prefill tokens its group dispatch will compute); once spent
        reaches the budget, the rest of the queue WAITS for the next
        loop iteration — i.e. for the next decode block to dispatch
        first. Long-prompt registrations charge nothing here; their
        chunks are budgeted as they dispatch
        (_advance_chunked_prefills). Returns (admitted_any, spent)."""
        admitted = False
        spent = 0
        self._admit_began = time.monotonic()
        # bucket → [(slot_idx, slot, window ids, window start, last window)]
        groups: dict[int, list] = {}
        cap = self._group_sizes[-1]
        try:
            while budget is None or spent < budget:
                free_slots = [
                    i for i, s in enumerate(self._slots) if s is None
                ]
                if not free_slots:
                    if not self._submit.empty():
                        self._defer_admission("no_slot")
                    return admitted, spent
                try:
                    request = self._submit.get_nowait()
                except queue.Empty:
                    return admitted, spent
                if request.cancelled.is_set():
                    continue
                if self._deadline_expired(request):
                    # Dropped at dequeue: the request never tokenizes,
                    # never allocates pages, never reaches the device.
                    self._expire(request, "queued")
                    continue
                try:
                    prep = self._prepare_request(free_slots[0], request)
                    admitted = True
                    if self.timeline is not None:
                        self.timeline.admit(
                            free_slots[0], self._trace_id_of(request),
                            request.timings.prompt_tokens,
                        )
                    if prep is not None:
                        bucket, rows = self._cover_rows(
                            *prep, self.config.prefill_buckets
                        )
                        # Budget charge = the cover's rows (known only
                        # after tokenize), so the LAST admission may
                        # overshoot by one bucket — the budget is a soft
                        # bound at dispatch granularity (config).
                        spent += bucket * len(rows)
                        group = groups.setdefault(bucket, [])
                        if len(group) + len(rows) > cap:
                            self._dispatch_prefill_group(
                                bucket, groups.pop(bucket)
                            )
                            group = groups.setdefault(bucket, [])
                        group += rows
                        if len(group) >= cap:
                            self._dispatch_prefill_group(
                                bucket, groups.pop(bucket)
                            )
                except AllocationError:
                    # Pool exhausted: put it back and let running requests
                    # finish. FIFO fairness over throughput.
                    self._defer_admission("no_pages")
                    self._requeue_front(request)
                    return admitted, spent
                except Exception as e:
                    request.out.put(("error", f"admission failed: {e}"))
                    self.metrics.on_finish(request.timings, failed=True,
                                           trace_id=self._trace_id_of(request))
            if not self._submit.empty():
                # This iteration's prefill budget is spent: whoever still
                # waits does so behind the next decode block.
                self._defer_admission("budget")
            return admitted, spent
        finally:
            for bucket, group in groups.items():
                self._dispatch_prefill_group(bucket, group)

    def _defer_admission(self, reason: str) -> None:
        """This `_admit` visit leaves requests waiting, for `reason`."""
        self.metrics.on_admit_deferred(reason)
        self._admit_visits.append((time.monotonic(), reason))

    def _stamp_admitted(self, timings: RequestTimings) -> None:
        """Admission has taken a request out of the queue: stamp
        `prefill_start`, and cut the time since `enqueued` at the
        `_admit` visits that ended inside it. From a visit that left the
        request waiting to the next visit (for the last one: to the
        start of this visit) is that visit's reason; the rest — before
        the first visit that saw the request, and inside this one — is
        nobody's decision and is left to cause `loop`
        (metrics.QUEUE_CAUSES, EngineMetrics.on_first_token)."""
        visits = self._admit_visits
        while visits and visits[0][0] <= timings.enqueued:
            visits.popleft()
        deferred: dict = {}
        ends = [end for end, _ in visits]
        for (end, reason), nxt in zip(visits, ends[1:] + [self._admit_began]):
            deferred[reason] = deferred.get(reason, 0.0) + (nxt - end)
        timings.queue_deferred = deferred
        timings.prefill_start = time.monotonic()

    def _cover_rows(self, slot_idx: int, slot: "_Slot", ids, start: int,
                    widths: tuple[int, ...]) -> tuple[int, list]:
        """The next prefill dispatch of `ids` from position `start`, as
        (width, rows): every window of the cover (prefill_cover) when
        the span fits the widest of `widths`, else its leading
        chunk-wide window alone. A row is (slot_idx, slot, window ids,
        window start, last); `last` marks the window that reaches the
        span's end — the one whose sampled token is the request's first
        token."""
        windows = prefill_cover(
            len(ids), start, widths, self._group_sizes,
            self.config.page_size,
        )
        if len(ids) > max(widths):
            windows = windows[:1]
        width = windows[0][0]
        end = start + len(ids)
        return width, [
            (slot_idx, slot, ids[at - start:at - start + width], at,
             at + width >= end)
            for _, at in windows
        ]

    def _requeue_front(self, request: GenRequest) -> None:
        # queue.Queue has no push-front; rebuild (small queues, rare path).
        items = [request]
        try:
            while True:
                items.append(self._submit.get_nowait())
        except queue.Empty:
            pass
        for item in items:
            self._submit.put(item)

    def _prepare_request(self, slot_idx: int, request: GenRequest):
        """Tokenize, budget, allocate pages, and register the slot.
        Returns (slot_idx, slot, ids, start) — the tokens to prefill
        and the position they start at — for short prompts (the caller
        covers them with windows and batches the dispatches, plain and
        spec engines alike) or None for long prompts (registered for
        chunked prefill)."""
        cfg = self.config
        if request.resume_state is not None:
            # Decode-tier resume (ISSUE 13): the prompt's KV arrives
            # with the request; nothing tokenizes or prefills here.
            return self._admit_resume(slot_idx, request)
        self._stamp_admitted(request.timings)

        if self._faults is not None:
            self._faults.maybe_raise("tokenizer-error", replica=self.replica_id, tier=self._tier)
        prompt_ids = self.tokenizer.encode(request.prompt)
        max_new = max(
            1,
            min(request.max_new_tokens, cfg.max_new_tokens_cap,
                cfg.max_seq_len - 1 - self._gamma_max),
        )
        # Leave room for generation within the per-request position cap
        # (max_new ≤ max_seq_len-1-gamma guarantees max_prompt ≥ 1, so the
        # tail-truncation slice below can never be [-0:]). The gamma slack
        # keeps the final speculative verify window's overdraft inside the
        # request's own pages (spec_decode.py module docstring). Prompts
        # beyond the largest bucket go through chunked prefill, so the cap
        # is the position budget, not the bucket table.
        max_prompt = cfg.max_seq_len - max_new - self._gamma_max
        if len(prompt_ids) > max_prompt:
            prompt_ids = prompt_ids[-max_prompt:]  # keep the prompt tail
        prompt_len = len(prompt_ids)
        request.timings.prompt_tokens = prompt_len

        total_len = prompt_len + max_new
        ids = np.asarray(prompt_ids, dtype=np.int32)

        # Prefix cache: reuse pages covering a cached page-aligned prefix
        # (lookup retains device pages for this slot); only the suffix
        # prefills. With the host tier on (ISSUE 15) the lookup walks
        # BOTH tiers: host-resident hits are PAGE FAULTS — each gets a
        # fresh device page here, the host contents scatter in via the
        # restore frontier (_issue_restores), and the slot joins no
        # dispatch until that restore has issued.
        matched: list[int] = []
        chain: list = []
        fault_idx: list[int] = []
        if self._prefix is not None:
            if self._host_kv is not None:
                chain, fault_idx = self._prefix.lookup_chain(ids)
                if not fault_idx:
                    # All-device chain: identical to the classic lookup.
                    matched = [page for _, _, page in chain]
                    chain = []
            else:
                matched = self._prefix.lookup(ids)
        restore_items: list = []
        if chain:
            # Detach the chain's host pages BEFORE allocating: the
            # pressure path below may spill into a full host tier,
            # whose LRU drop (`pop_lru_host`) must never free a page
            # this admission's pending restore depends on. Ownership
            # moves to this request now and returns (re-adopt) on the
            # allocation-failure path.
            for ci, (key, tier, _page) in enumerate(chain):
                if tier == TIER_HOST:
                    restore_items.append(
                        (key, self._prefix.detach_host(key), ci)
                    )
        n_dev_matched = (
            (len(chain) - len(fault_idx)) if chain else len(matched)
        )
        need = (
            -(-(total_len + self._gamma_max) // cfg.page_size)
            - n_dev_matched
        )
        try:
            if self._faults is not None:
                # Inside the try: the AllocationError path below must
                # still release the prefix-cache lookup's page refs.
                self._faults.maybe_raise(
                    "alloc-fail", AllocationError, replica=self.replica_id,
                    tier=self._tier,
                )
            try:
                fresh = self.allocator.alloc(need)
            except AllocationError:
                if self._prefix is None:
                    raise
                # Allocation pressure: offload cold cache pages to the
                # host tier when it exists (warmth preserved), drop them
                # when it doesn't (or it couldn't free enough), retry.
                if self._host_kv is not None:
                    self._spill_for(need)
                if self.allocator.num_free < need:
                    self._prefix.evict_for(need)
                fresh = self.allocator.alloc(need)
        except AllocationError:
            if chain:
                self._prefix.release_chain(chain)   # drop lookup's refs
                for key, host_page, _ci in restore_items:
                    # Hand the detached host pages back to the cache
                    # (warmth survives the requeue); a key re-cached
                    # meanwhile keeps its copy and ours frees.
                    if not self._prefix.adopt_host(key, host_page):
                        self._host_kv.release(host_page)
            else:
                self.allocator.release_all(matched)
            raise
        if chain:
            # Assemble the table in chain order: device hits keep their
            # shared pages; fault positions take fresh pages whose
            # contents arrive via the restore frontier (the host pages
            # detached to this slot above).
            pages = []
            fi = 0
            for _key, tier, page in chain:
                if tier == TIER_DEVICE:
                    pages.append(page)
                else:
                    pages.append(fresh[fi])
                    fi += 1
            pages += fresh[fi:]
        else:
            pages = matched + fresh
        if request.trace is not None:
            # Recorded only after allocation succeeds: an AllocationError
            # requeues the request and re-enters this method, and the
            # span tree must hold ONE queue_wait covering the whole wait
            # (enqueue through the attempt that actually admitted).
            request.trace.child(
                "queue_wait",
                start=request.timings.enqueued,
                end=request.timings.prefill_start,
            )

        page_table = np.zeros((1, cfg.pages_per_seq), dtype=np.int32)
        page_table[0, : len(pages)] = pages
        seed = request.seed
        if seed is None:
            seed = int(self._seed_rng.integers(0, 1 << 63))
        # Injective packing of the seed's low 64 bits into two int32
        # halves (uint32 wraparound, not masking to 31 bits — distinct
        # 64-bit seeds must never collide to the same stream; seeds are
        # taken mod 2**64).
        s = seed & 0xFFFFFFFFFFFFFFFF
        seed_row = np.array(
            [(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], np.uint32
        ).view(np.int32)
        slot = _Slot(request=request, pages=pages, position_cap=total_len)
        slot.seed_row = seed_row
        widest = max(cfg.prefill_buckets)

        slot.table = page_table
        slot.prompt_len = prompt_len
        slot.prompt_ids = ids

        if restore_items:
            # Faulting admission: the slot registers with its whole
            # prompt pending from the post-chain offset and WAITS for
            # the restore frontier — it joins no prefill dispatch until
            # its pages are in flight on the donation chain, so resident
            # lanes admitted this same iteration dispatch ahead of it
            # (the page-aware no-stall property).
            slot.restore_pages = restore_items
            kind = "ctx" if prompt_len > widest else "prefix"
            self.metrics.on_kv_fault(kind, len(restore_items))
            slot.pending = ids
            slot.filled = len(chain) * cfg.page_size
            self._slots[slot_idx] = slot
            return None

        if matched:
            # Prefill only the suffix. A bucket-sized suffix rides the
            # batched bucket path, covered at its own length from the
            # offset (a hit must not cost more than a miss); longer
            # suffixes chunk from the offset.
            # On spec engines the group dispatch prefills BOTH pools, and
            # cached pages already hold both models' prefix KV.
            filled = len(matched) * cfg.page_size
            suffix = ids[filled:]
            self._slots[slot_idx] = slot
            if len(suffix) > widest:
                slot.pending = ids
                slot.filled = filled
                return None
            return slot_idx, slot, suffix, filled

        if prompt_len > widest:
            # Long prompt: register the slot in prefilling state; the
            # engine loop runs one chunk per iteration (interleaved with
            # decode steps) until the prompt is in cache. Its page table
            # stays slot-local (NOT in the device mirrors) so concurrent
            # decode blocks keep writing this lane's garbage through the
            # reserved page 0 instead of over the chunks already prefilled.
            slot.pending = np.asarray(prompt_ids, dtype=np.int32)
            self._slots[slot_idx] = slot
            return None

        # Registered but inactive until _resolve_prefills reads the token —
        # after the next decode block is dispatched, so prefill overlaps it.
        self._slots[slot_idx] = slot
        return slot_idx, slot, ids, 0

    def _dispatch_prefill_group(self, bucket: int, group: list) -> bool:
        """One batched prefill dispatch for up to _MAX_PREFILL_GROUP
        same-bucket windows, padded to a power of two so the compiled
        shape set stays small ({1,2,4,8} × buckets). A row is (slot_idx,
        slot, window ids, window start, last): the windows of one prompt
        are consecutive rows on the same page table at starts s, s +
        bucket, …, and only a `last` row's sampled token activates its
        slot — the others' are discarded without a sync, as a chunked
        prompt's are. Padded rows point their page tables at the
        reserved garbage page and are never resolved. False when the
        dispatch failed (every member slot is then finished)."""
        n = len(group)
        n_pad = next(g for g in self._group_sizes if g >= n)
        cfg = self.config
        tokens = np.zeros((n_pad, bucket), dtype=np.int32)
        starts = np.zeros((n_pad,), dtype=np.int32)
        last_rel = np.zeros((n_pad,), dtype=np.int32)
        tables = np.zeros((n_pad, cfg.pages_per_seq), dtype=np.int32)
        temp = np.zeros((n_pad,), dtype=np.float32)
        top_p = np.ones((n_pad,), dtype=np.float32)
        top_k = np.zeros((n_pad,), dtype=np.int32)
        seeds = np.zeros((n_pad, 2), dtype=np.int32)
        for r, (slot_idx, slot, ids, start, _) in enumerate(group):
            tokens[r, : len(ids)] = ids
            starts[r] = start       # >0: a later window, a cached prefix
            last_rel[r] = len(ids) - 1
            tables[r] = slot.table[0]
            temp[r] = slot.request.temperature
            top_p[r] = slot.request.top_p
            top_k[r] = self._eff_top_k(slot.request)
            seeds[r] = slot.seed_row
        greedy = bool(np.all(temp == 0.0))

        put = partial(jax.device_put, device=self._repl)
        common = (
            jax.device_put(tokens, self._prefill_tok),
            put(starts), put(last_rel), put(tables), put(seeds),
            put(temp), put(top_p), put(top_k),
        )
        # A stateful model: what each row does with its slot's state.
        stateful = self.model_cfg.stateful
        state_rows = self._state_rows(group, n_pad) if stateful else None
        real = sum(len(row[2]) for row in group)
        try:
            if self._faults is not None:
                self._faults.maybe_raise("prefill-error", replica=self.replica_id, tier=self._tier)
            with self._phase("prefill", bucket=bucket, rows=n_pad * bucket,
                             tokens=real, **self._loop_attrs):
                # The last dispatch's stamp is the one that stays: the
                # one that completes the prompt.
                issued = time.monotonic()
                for _, slot, _, _, _ in group:
                    slot.request.timings.prefill_dispatched = issued
                if self._spec:
                    # Spec burst admissions batch exactly like plain ones
                    # (spec_prefill_fn is N-row); both pools prefill in
                    # the one dispatch.
                    toks_dev, self.paged, self.d_paged = self._jit_spec_prefill(
                        self.params, self.draft_params,
                        self.model_cfg, self.draft_cfg,
                        self.paged, self.d_paged,
                        *common,
                        greedy=greedy,
                        candidates=self.config.top_p_candidates,
                        mesh=self.mesh,
                    )
                else:
                    toks_dev, self.paged, self.state = self._jit_prefill(
                        self.params, self.model_cfg, self.paged,
                        *common, self.state,
                        None if state_rows is None else put(state_rows),
                        greedy=greedy,
                        candidates=self.config.top_p_candidates,
                        mesh=self.mesh,
                    )
        except Exception as e:
            # Contain the failure to this group: every member slot is
            # already registered, so each must be finished (pages released,
            # client errored) or they leak and their clients hang forever.
            for slot_idx, slot, _, _, _ in group:
                if self._slots[slot_idx] is slot:
                    self._finish(slot_idx, error=f"prefill failed: {e}")
            return False
        # Padding-waste accounting: the group computed n_pad × bucket
        # token rows for Σ len(ids) real prompt tokens, in n windows; a
        # slot with several rows here is a prompt split over windows.
        rows_of = collections.Counter(row[0] for row in group)
        rows = n_pad * bucket
        # An expert layer of a layer pattern ran these rows sorted by
        # expert, not every row against every held expert: ops/moe.py
        # decides by the same function.
        grouped = self._expert_layers and held_experts_grouped(rows)
        # The keys each table row's attention read a layer: the program
        # chooses by the same functions of its furthest position.
        table = cfg.pages_per_seq * cfg.page_size
        keys = table
        if prefill_bounded(
            bucket, table, cfg.page_size, self.model_cfg.kv_row_width
            if self.model_cfg.latent_kv else self.model_cfg.head_dim,
            self.model_cfg.latent_kv, self.mesh,
        ):
            keys = int(prefill_keys_read(
                int(starts.max()) + bucket, bucket, table, cfg.page_size))
        self.metrics.on_prefill_rows(
            rows, real, n, sum(c > 1 for c in rows_of.values()),
            grouped_experts=rows if grouped else 0,
            keys_read=n_pad * keys,
            keys_table=n_pad * table,
        )
        if stateful:
            sources = collections.Counter(state_rows[:n, 1].tolist())
            self.metrics.on_state_rows(
                sources[FROM_ZERO], sources[FROM_PREVIOUS_ROW],
                sources[FROM_SLOT],
            )
        completes = []
        for r, (slot_idx, slot, ids, _, last) in enumerate(group):
            if self.timeline is not None:
                self.timeline.prefill(slot_idx, len(ids), last)
            if last:
                self._merge_slot(slot_idx, slot, toks_dev, r)
                completes.append((slot_idx, slot))
        if completes:
            self._first_tokens.append(
                _FirstTokens(toks_dev, completes, time.monotonic())
            )
        return True

    def _state_rows(self, group: list, n_pad: int) -> np.ndarray:
        """[n_pad, 3] int32 (slot, source, store) of a prefill group for a
        stateful model (hybrid.PrefillRows). A window at position 0
        starts from zero state — admission resets the slot by never
        reading what its last occupant left; a later window starts where
        the row above ended when that row is the same prompt's (the
        cover's consecutive rows), else from what the slot stores (a
        long prompt's next chunk). Only a slot's LAST row of the dispatch
        stores: the others, and the padded rows, name a slot past the
        last, which the scatter drops."""
        rows = np.zeros((n_pad, 3), np.int32)
        rows[:, 2] = len(self._slots)
        for r, (slot_idx, _, _, start, _) in enumerate(group):
            chained = r > 0 and group[r - 1][0] == slot_idx
            source = (FROM_ZERO if start == 0 else
                      FROM_PREVIOUS_ROW if chained else FROM_SLOT)
            ends = r + 1 == len(group) or group[r + 1][0] != slot_idx
            rows[r] = (slot_idx, source,
                       slot_idx if ends else len(self._slots))
        return rows

    def _compile_warmup(self) -> None:
        """Pre-compile the greedy prefill group shapes and the greedy
        decode block (or spec round) against the reserved garbage page.
        Runs in __init__ before the engine thread starts, so there is no
        concurrent owner of the donated pools; first real requests then
        never pay compile time."""
        cfg = self.config
        B = cfg.max_decode_slots
        compiles_before = compile_counts()
        warm_sampled = cfg.warm_sampled_variants
        greedy_variants = (True, False) if warm_sampled else (True,)
        put = partial(jax.device_put, device=self._repl)
        # Padded group sizes given the slot count (prefill_group_sizes:
        # n=3 pads to 4, n=5 pads to 8). A full-rate admission burst of
        # 32 then costs 4 weight-read passes instead of 8 — prefill is
        # weight-bandwidth-bound exactly like decode, so group width
        # amortizes it. The same shapes serve a prompt covered by
        # several windows (prefill_cover): no shape is added for it.
        pads = self._group_sizes
        self._upload_slot_state()
        dev = self._dev
        zrow = np.zeros((cfg.pages_per_seq,), np.int32)
        for bucket in cfg.prefill_buckets:
            for n in pads:
                window = (
                    jax.device_put(
                        np.zeros((n, bucket), np.int32), self._prefill_tok
                    ),
                    put(np.zeros((n,), np.int32)),
                    put(np.zeros((n,), np.int32)),
                    put(np.zeros((n, cfg.pages_per_seq), np.int32)),
                    put(np.zeros((n, 2), np.int32)),
                    put(np.zeros((n,), np.float32)),
                    put(np.ones((n,), np.float32)),
                    put(np.zeros((n,), np.int32)),
                )
                # A stateful model's rows read slot 0 from zero and store
                # nowhere.
                state_rows = (
                    put(self._state_rows([], n))
                    if self.model_cfg.stateful else None
                )
                # greedy is a static argname keyed on the BATCH (all-greedy
                # vs any-sampled), so both variants occur at serving time —
                # warm both or the first sampled admission pays a compile.
                # (warm_sampled_variants=False: greedy-only runs skip the
                # sampled compiles entirely.)
                for greedy in greedy_variants:
                    named = {"bucket": bucket, "rows": n, "greedy": greedy}
                    if self._spec:
                        toks_dev, self.paged, self.d_paged = self._warm_call(
                            "prefill", named, self._jit_spec_prefill,
                            self.params, self.draft_params,
                            self.model_cfg, self.draft_cfg,
                            self.paged, self.d_paged,
                            *window,
                            greedy=greedy,
                            candidates=self.config.top_p_candidates,
                            mesh=self.mesh,
                        )
                    else:
                        toks_dev, self.paged, self.state = self._warm_call(
                            "prefill", named, self._jit_prefill,
                            self.params, self.model_cfg, self.paged,
                            *window, self.state, state_rows,
                            greedy=greedy,
                            candidates=self.config.top_p_candidates,
                            mesh=self.mesh,
                        )
                if bucket == cfg.prefill_buckets[0]:
                    # Warm the lane merge with the prefill's OWN device
                    # output — a numpy stand-in would compile a different
                    # cache entry (committedness is part of the key) and
                    # the real first admission would still pay the compile.
                    merge_args = (
                        dev["last_tokens"], dev["seq_lens"],
                        dev["page_tables"], dev["active"], dev["caps"],
                        dev["temperature"], dev["top_p"], dev["top_k"],
                        dev["seeds"],
                        toks_dev, np.int32(0), np.int32(0),
                        np.int32(1), np.int32(2), np.float32(0.0),
                        np.float32(1.0), np.int32(0), zrow,
                        np.zeros((2,), np.int32),
                    )
                    if self._spec:
                        self._warm_call(
                            "merge", {"rows": n}, self._jit_merge,
                            *merge_args, dev["accept_ewma"],
                            dev["gamma_lane"], np.int32(self._gamma_max),
                            eos_id=self.tokenizer.eos_id, spec=True,
                        )
                    else:
                        self._warm_call(
                            "merge", {"rows": n}, self._jit_merge,
                            *merge_args, eos_id=self.tokenizer.eos_id,
                        )
        if self._spec:
            # The spec round is the steady-state step; its compile is the
            # heavy one (draft scan + verify + draft-sync forwards).
            # _dispatch_spec alternates between candidates=0 (all rows
            # greedy/untruncated) and candidates=top_p_candidates, and
            # each value is a distinct compile — warm both so the first
            # truncated-top-p batch at serving time doesn't stall.
            warm_candidates = [0]
            if warm_sampled and self.config.top_p_candidates > 0:
                warm_candidates.append(self.config.top_p_candidates)
            # The adaptive gamma dial alternates between both ladder
            # levels at dispatch time; each is a distinct compile.
            for cand in warm_candidates:
                for gamma in sorted({self._gamma_low, self._gamma_max}):
                    outs = self._warm_call(
                        "spec", {"steps": gamma + 1, "candidates": cand},
                        self._jit_spec_decode,
                        self.params, self.draft_params,
                        self.model_cfg, self.draft_cfg,
                        self.paged, self.d_paged,
                        dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                        dev["active"], dev["caps"], dev["seeds"],
                        dev["temperature"], dev["top_p"], dev["top_k"],
                        dev["accept_ewma"], dev["gamma_lane"],
                        gamma=gamma,
                        eos_id=self.tokenizer.eos_id,
                        gamma_low=self._gamma_low,
                        gamma_max=self._gamma_max,
                        candidates=cand, mesh=self.mesh,
                    )
                    # Donated slot state: rebind the warmed dev entries
                    # from the outputs or the next warmup call would feed
                    # deleted buffers.
                    (_, dev["last_tokens"], dev["seq_lens"], dev["active"],
                     dev["accept_ewma"], dev["gamma_lane"],
                     self.paged, self.d_paged) = outs
            if warm_sampled and self.config.top_p_candidates == 0:
                # Without the top-k prefilter, a batch containing any
                # sampled top_p<1 row leaves the spec path entirely and
                # takes the PLAIN decode block (see _dispatch_step's
                # all_untruncated gate) — warm that fallback too. Only
                # greedy=False is reachable there: all_untruncated can
                # only be False via a temp>0 row, which makes the batch
                # non-greedy.
                for steps in sorted({self._solo_steps, self._block_steps}):
                    outs = self._warm_call(
                        "decode", {"steps": steps, "greedy": False},
                        self._jit_decode,
                        self.params, self.model_cfg, self.paged,
                        dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                        dev["active"], dev["caps"], dev["seeds"],
                        dev["temperature"], dev["top_p"], dev["top_k"],
                        self.state,
                        greedy=False, steps=steps,
                        eos_id=self.tokenizer.eos_id,
                        candidates=0, mesh=self.mesh,
                    )
                    (_, dev["last_tokens"], dev["seq_lens"], dev["active"],
                     self.paged, self.state) = outs
        else:
            # greedy is batch-keyed at dispatch (all-greedy vs any-sampled)
            # and the adaptive dispatcher alternates between the solo and
            # full block sizes — warm every reachable (greedy, steps) pair.
            for greedy in greedy_variants:
                for steps in sorted({self._solo_steps, self._block_steps}):
                    outs = self._warm_call(
                        "decode", {"steps": steps, "greedy": greedy},
                        self._jit_decode,
                        self.params, self.model_cfg, self.paged,
                        dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                        dev["active"], dev["caps"], dev["seeds"],
                        dev["temperature"], dev["top_p"], dev["top_k"],
                        self.state,
                        greedy=greedy, steps=steps,
                        eos_id=self.tokenizer.eos_id,
                        candidates=self.config.top_p_candidates, mesh=self.mesh,
                    )
                    # Donated slot state: rebind or the next warmup call
                    # would feed deleted buffers.
                    (_, dev["last_tokens"], dev["seq_lens"], dev["active"],
                     self.paged, self.state) = outs
        self._warm_call(
            "retire", {}, self._jit_retire,
            dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
            dev["active"], dev["caps"], np.int32(0),
        )
        if self._host_kv is not None:
            # Host-tier gather/scatter pair (ISSUE 15): pre-compile both
            # fixed-width executables against the reserved garbage page
            # so the first spill or page fault at serving time never
            # pays XLA compile time (the GL001 discipline: one resident
            # executable each way, warmed here, never again).
            P = cfg.pages_per_seq
            idx0 = np.zeros((P,), np.int32)
            jax.block_until_ready(self._warm_call(
                "kv_gather", {}, self._jit_kv_gather, self.paged, put(idx0)))
            zeros = jax.tree.map(
                lambda pool: put(np.zeros(
                    (pool.shape[0], P, *pool.shape[2:]), pool.dtype)),
                self.paged,
            )
            self.paged = self._warm_call(
                "kv_restore", {}, self._jit_kv_restore,
                self.paged, put(idx0), zeros)
        jax.block_until_ready(self.paged)
        self._warm_compiles = compile_delta(compiles_before, compile_counts())
        # The dirty flag forces a fresh upload once real slots exist.
        self._dev_dirty = True

    def _warm_call(self, step: str, named: dict, fn, *args, **kwargs):
        """One warm-up dispatch, as a `warm_call` phase and a row of the
        start-up record: `step` ("prefill" / "decode" / "spec" / "merge" /
        "retire" / "kv_gather" / "kv_restore") and `named` (bucket, rows,
        steps, greedy: what tells this executable from the step's others)
        are the phase's attributes and the row's; beside them the row has
        the host's `seconds` in the call (tracing, lowering, the cache
        read or XLA's compile, the dispatch; nothing waits for the
        device), the census's `backend_s` over it, `cache_hit` (it loaded
        what it built from the persistent cache) and `loaded` (it built
        nothing: the executable store held the compiled step).

        The first prefill and the first decode or spec dispatch are also
        inspected, so stats() can say from the executable itself — not
        from the gate functions — which Mosaic kernels it carries and, on
        a mesh, how many collectives. An entry of the store carries the
        inspection of the step it was built from, so a loaded start says
        the same.

        This builds nothing twice: `fn.lower(...)` and `.compile()` go
        through the jit's own lowering cache, so a dispatch through the
        jitted function finds the executable already built and serves
        from the very object that was inspected (tests/test_device.py
        pins that on this JAX: one backend compile for lower + compile +
        call); with a store the dispatch goes through that `Compiled`
        itself. `fn.lower` is called from THIS frame either way: a frame
        more above a lowering costs seconds of a cold start (PERF.md
        section 6, PR 62 (1))."""
        served = "decode" if step == "spec" else step
        inspected = (served in ("prefill", "decode")
                     and served not in self._warm_kernels)
        on_mesh, stored = self.mesh.size > 1, self._executables is not None
        spent = self.metrics.phase_seconds
        seconds_before, compiles_before = spent["warm_call"], compile_counts()
        with self._phase("warm_call", step=step, **named):
            key, found = (
                fn.load(args, kwargs, inspected) if stored else (None, None))
            loaded = found is not None
            if not loaded and (inspected or stored):
                lowered = fn.lower(*args, **kwargs)
                found = {
                    "kernels": mosaic_calls(lowered) if inspected else None,
                    "collectives": None,
                }
                if stored or (inspected and on_mesh):
                    compiled = lowered.compile()
                    if inspected and on_mesh:
                        found["collectives"] = collective_ops(compiled)
                    if stored:
                        spent_here = compile_delta(
                            compiles_before, compile_counts())
                        fn.keep(
                            key, compiled, **found,
                            first_hand=spent_here["fresh_compiles"] > 0
                            and not spent_here["cache_hits"],
                        )
            if inspected:
                # polylint: disable=ML002(keyed by step kind: "prefill" / "decode", written at warm-up only)
                self._warm_kernels[served] = found["kernels"]
                if on_mesh:
                    # polylint: disable=ML002(keyed by step kind: "prefill" / "decode", written at warm-up only)
                    self._warm_collectives[served] = found["collectives"]
            out = fn(*args, **kwargs)
        built = compile_delta(compiles_before, compile_counts())
        # polylint: disable=ML002(one row a warm-up dispatch, written in the constructor only)
        self._warm_rows.append({
            "step": step, **named,
            "seconds": round(spent["warm_call"] - seconds_before, 6),
            "backend_s": built["backend_s"],
            "cache_hit": built["cache_hits"] > 0
            and not built["fresh_compiles"],
            "loaded": loaded,
        })
        return out

    def _merge_slot(
        self, slot_idx: int, slot: _Slot, toks_dev: jax.Array, row: int
    ) -> None:
        """Activate a prefilled slot's decode lane ON DEVICE: the merge
        dispatch splices the sampled token (still a device array) and the
        slot's geometry into the device-resident state, so the lane joins
        the next decode block with zero host↔device syncs and no pipeline
        flush. The host keeps a handle to the token purely for client
        delivery (_resolve_prefills)."""
        request = slot.request
        if request.prefill_only:
            # Prefill-tier mode (ISSUE 13): the lane never activates —
            # the sampled first token and the written KV pages ARE this
            # request's product; decode happens on the decode tier after
            # the handoff. The token handle still resolves through
            # _resolve_prefills, which routes to the handoff export.
            slot.merged = False
            slot.pending = None
            slot.token_dev = toks_dev
            slot.token_row = row
            try:
                toks_dev.copy_to_host_async()
            except Exception:
                pass  # harmless: np.asarray at resolve time starts the copy
            return
        if self._dev_dirty:
            # Cold start / post-recovery: fold mirrors in before merging.
            self._drain_inflight()
            self._upload_slot_state()
        dev = self._dev
        try:
            # _host_crossing: the merge's geometry rides as tiny numpy
            # scalars (an implicit upload that piggybacks the dispatch).
            with _host_crossing("merge-upload"):
                args = (
                    dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                    dev["active"], dev["caps"], dev["temperature"], dev["top_p"],
                    dev["top_k"], dev["seeds"],
                    toks_dev, np.int32(row), np.int32(slot_idx),
                    np.int32(slot.prompt_len + 1), np.int32(slot.position_cap),
                    np.float32(request.temperature), np.float32(request.top_p),
                    np.int32(self._eff_top_k(request)),
                    slot.table[0], slot.seed_row,
                )
                if self._spec:
                    # The per-lane gamma dial resets with its occupant
                    # (fresh EWMA, dial at gamma_max) — see _merge_lane_fn.
                    outs = self._jit_merge(
                        *args, dev["accept_ewma"], dev["gamma_lane"],
                        np.int32(self._gamma_max),
                        eos_id=self.tokenizer.eos_id, spec=True,
                    )
                    dev["accept_ewma"], dev["gamma_lane"] = outs[9:]
                else:
                    outs = self._jit_merge(
                        *args, eos_id=self.tokenizer.eos_id,
                    )
                (
                    dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                    dev["active"], dev["caps"], dev["temperature"], dev["top_p"],
                    dev["top_k"], dev["seeds"],
                ) = outs[:9]
        except Exception as e:
            self._finish(slot_idx, error=f"activation failed: {e}")
            return
        try:
            toks_dev.copy_to_host_async()
        except Exception:
            pass  # harmless: np.asarray at resolve time starts the copy
        slot.merged = True
        slot.pending = None
        slot.token_dev = toks_dev
        slot.token_row = row
        # Host mirrors (flush-upload source of truth; _last_tokens follows
        # at resolve time, and any flush first drains + resolves).
        self._page_tables[slot_idx] = slot.table[0]
        slot.table = None
        self._seq_lens[slot_idx] = slot.prompt_len + 1
        self._active[slot_idx] = True
        self._caps[slot_idx] = slot.position_cap
        self._temperature[slot_idx] = request.temperature
        self._top_p[slot_idx] = request.top_p
        self._top_k[slot_idx] = self._eff_top_k(request)
        self._seeds[slot_idx] = slot.seed_row
        self._lane_ewma[slot_idx] = 1.0
        self._lane_gamma[slot_idx] = max(self._gamma_max, 1)

    def _has_unresolved(self) -> bool:
        """A prefill dispatch's first tokens still wait to be read."""
        return bool(self._first_tokens)

    def _unread(self, record: _FirstTokens) -> list:
        """The members of a dispatch whose first token is still to read
        (a member that finished meanwhile — cancelled, failed — is not)."""
        return [
            (i, slot) for i, slot in record.members
            if self._slots[i] is slot and slot.token_dev is not None
        ]

    def _resolve_prefills(self, block: bool = False) -> None:
        """Deliver the first tokens of the prefill dispatches whose async
        D2H copies have landed (all of them when `block=True`), oldest
        dispatch first. Activation already happened at merge time; this
        is purely client-facing delivery + host bookkeeping."""
        for record in list(self._first_tokens):
            unread = self._unread(record)
            if not unread:
                self._first_tokens.remove(record)
            elif block or record.toks_dev.is_ready():
                self._read_first_tokens(record, unread)
            else:
                record.polled = time.monotonic()

    def _read_first_tokens(self, record: _FirstTokens, members: list) -> None:
        """Hand `members` of one prefill dispatch their first tokens. The
        dispatch's first read — all its members from _resolve_prefills,
        the first of them to come up in a block's emit loop from
        _process_step — runs inside the dispatch's `first_token` phase:
        that span's start is when the host took up tokens the device had
        finished earlier."""
        span = contextlib.nullcontext()
        if not record.read:
            record.read = True
            self.metrics.on_first_tokens_read(
                time.monotonic() - record.polled
            )
            span = self._phase("first_token")
        with span:
            for i, slot in members:
                self._resolve_slot(i, slot)
        if not self._unread(record):
            self._first_tokens.remove(record)

    def _resolve_in_block(self, slot_idx: int, slot: _Slot) -> None:
        """A lane of the block in hand still owes its client a first
        token, which precedes the block's tokens in the stream: its
        prefill ran before the block, so the read is local."""
        record = next(
            r for r in self._first_tokens
            if any(s is slot for _, s in r.members)
        )
        self._read_first_tokens(record, [(slot_idx, slot)])

    def _resolve_slot(self, slot_idx: int, slot: _Slot) -> None:
        try:
            # Deliberate resolve point: the copy was started async at merge
            # time (copy_to_host_async), so this sync is local by now.
            with _host_crossing("first-token-resolve"):
                # polylint: disable=PL001(first-token resolve point; async copy landed), PL008(reached from dispatch only on the dev-dirty cold path, behind a full pipeline drain)
                token = int(np.asarray(slot.token_dev).reshape(-1)[slot.token_row])
        except Exception as e:
            slot.token_dev = None
            self._finish(slot_idx, error=f"prefill failed: {e}")
            return
        slot.token_dev = None
        slot.generated = 1
        request = slot.request
        if self._prefix is not None and slot.prompt_ids is not None:
            # Publish the prompt's page-aligned pages only now: the token
            # read above proves the prefill computation succeeded, so the
            # cached pages hold real KV (an async prefill failure above
            # would otherwise poison the cache with unwritten pages). Any
            # consumer's own prefill dispatches after this point, so
            # device-order still guarantees the pages are written first.
            self._prefix.insert(slot.prompt_ids, slot.pages)
        if request.prefill_only:
            # Prefill-tier product (ISSUE 13): instead of activating
            # decode, gather the prompt's KV pages and hand the state to
            # the worker harness (which serializes + retains it until
            # the coordinator acks — the two-phase hand-over).
            self._export_handoff(slot_idx, slot, token)
            return
        self._last_tokens[slot_idx] = token
        self._note_first_token(slot_idx, slot, prompt_tokens=slot.prompt_len)
        request.out.put(("token", token))
        self._maybe_finish(slot_idx, token)

    def _note_first_token(self, slot_idx: int, slot: _Slot,
                          **prefill_attrs) -> None:
        """A request's first token is in hand (bucketed, batched and
        chunked prefill all funnel through _resolve_slot; a handoff
        resume comes from _admit_resume): stamp it, file the three TTFT
        phases and the queue's causes, and give a traced request its
        `prefill_wait` (admitted, tokenized, held on the host), `prefill`
        (from the dispatch call to here) and open `decode` spans. What
        the time after the dispatch call is made of — the device's
        queue, the prefill, the finished token waiting to be read — is
        not a request's to know: a capture times the first two, the
        `first_token` phase and the poll gap the third."""
        request = slot.request
        timings = request.timings
        timings.first_token = time.monotonic()
        slot.last_emit = timings.first_token
        self.metrics.on_first_token(timings)
        if self.timeline is not None:
            self.timeline.slot_start(slot_idx, self._trace_id_of(request))
        if request.trace is not None:
            dispatched = timings.prefill_dispatched or timings.prefill_start
            request.trace.child(
                "prefill_wait", start=timings.prefill_start, end=dispatched,
            )
            request.trace.child(
                "prefill", start=dispatched, end=timings.first_token,
                **prefill_attrs,
            )
            slot.decode_span = request.trace.child(
                "decode", start=timings.first_token
            )

    def _export_handoff(self, slot_idx: int, slot: _Slot,
                        token: int) -> None:
        """Prefill-tier export (ISSUE 13): gather the slot's prompt KV
        pages to host, emit ("handoff", KVHandoffState) then the usual
        ("done", timings), and release the slot. The gathered host copy
        is the retained artifact of the two-phase hand-over (the worker
        harness keeps its serialized form until the coordinator acks);
        the device pages themselves release with the slot — block-table
        order is preserved by the gather, so the target re-maps pages to
        its own ids without any index translation."""
        request = slot.request
        cfg = self.config
        n_kv = -(-slot.prompt_len // cfg.page_size)
        try:
            # polylint: disable=PL008(tiny page-index upload, not a readback; prefill_only cold path)
            idx = jnp.asarray(np.asarray(slot.pages[:n_kv], np.int32))
            with _host_crossing("handoff-export"):
                # polylint: disable=PL008(handoff export: deliberate one-shot gather; prefill_only cold path never taken by in-process serving)
                kv = np.asarray(jnp.take(self.paged.kv, idx, axis=1))
                # The wire format keeps K and V, and the heads, apart
                # (kv_cache.py).
                k, v = unfold_pages(kv, self.model_cfg.head_dim)
                ks = vs = None
                if self.paged.quantized:
                    # polylint: disable=PL008(handoff export gather; prefill_only cold path)
                    ks = np.asarray(jnp.take(self.paged.ks, idx, axis=1))
                    # polylint: disable=PL008(handoff export gather; prefill_only cold path)
                    vs = np.asarray(jnp.take(self.paged.vs, idx, axis=1))
        except Exception as e:
            self._finish(slot_idx, error=f"handoff export failed: {e}")
            return
        halves = slot.seed_row.view(np.uint32).astype(np.uint64)
        seed = int((halves[0] << np.uint64(32)) | halves[1])
        state = KVHandoffState(
            model=self.model_cfg.name, page_size=cfg.page_size,
            prompt_len=slot.prompt_len, first_token=int(token), seed=seed,
            prompt_ids=slot.prompt_ids, k=k, v=v, ks=ks, vs=vs,
        )
        request.timings.first_token = time.monotonic()
        if self.timeline is not None:
            self.timeline.note(
                "handoff_export", slot=slot_idx,
                prompt_tokens=slot.prompt_len, pages=n_kv,
            )
        request.out.put(("handoff", state))
        self._finish(slot_idx)

    def _admit_resume(self, slot_idx: int, request: GenRequest) -> None:
        """Decode-tier admission (ISSUE 13): map a handed-off KV state
        into this pool and splice the slot state a single-process run
        would hold at seq_len = prompt_len + 1 — no tokenize, no
        prefill dispatch. Greedy continuation is then bit-identical to
        an uninterrupted run (same params, same seed, same position
        keys). Geometry/dtype mismatches reject as typed 'kv-handoff
        rejected' failures BEFORE any pool write; AllocationError takes
        the usual requeue backpressure path (the resume_state rides the
        request, so a retry re-admits cleanly)."""
        cfg = self.config
        state: KVHandoffState = request.resume_state
        self._stamp_admitted(request.timings)
        try:
            state.validate_for(
                self.model_cfg, cfg.page_size, self._kv_quantized
            )
            if jnp.dtype(state.k.dtype) != self.paged.kv.dtype:
                raise KVWireError(
                    f"kv-handoff pool dtype mismatch: blob "
                    f"{state.k.dtype}, target {self.paged.kv.dtype}"
                )
        except KVWireError as e:
            # _admit wraps as "admission failed: kv-handoff ..." — the
            # coordinator matches the marker and re-routes cleanly.
            raise RuntimeError(f"kv-handoff rejected: {e}") from e
        prompt_len = state.prompt_len
        request.timings.prompt_tokens = prompt_len
        max_new = max(
            1,
            min(request.max_new_tokens, cfg.max_new_tokens_cap,
                cfg.max_seq_len - 1 - self._gamma_max),
        )
        total_len = prompt_len + max_new
        if total_len + self._gamma_max > cfg.max_seq_len:
            raise RuntimeError(
                f"kv-handoff rejected: prompt_len {prompt_len} + max_new "
                f"{max_new} exceeds this worker's position budget "
                f"({cfg.max_seq_len})"
            )
        need = -(-(total_len + self._gamma_max) // cfg.page_size)
        if self._faults is not None:
            self._faults.maybe_raise(
                "alloc-fail", AllocationError, replica=self.replica_id,
                tier=self._tier,
            )
        pages = self.allocator.alloc(need)
        P = cfg.pages_per_seq
        n_kv = state.num_pages
        idx = np.zeros((P,), np.int32)     # pad rows → garbage page 0
        idx[:n_kv] = pages[:n_kv]

        def _pad(arr: np.ndarray) -> np.ndarray:
            out = np.zeros((arr.shape[0], P) + arr.shape[2:], arr.dtype)
            out[:, :n_kv] = arr
            return out

        try:
            # _host_crossing: the padded page payload rides up as one
            # deliberate upload (the handoff's whole point).
            with _host_crossing("handoff-restore"):
                request.timings.prefill_dispatched = time.monotonic()
                self.paged = self._jit_kv_restore(
                    self.paged, jax.device_put(idx, self._repl),
                    self._page_upload(
                        _pad(state.k), _pad(state.v),
                        _pad(state.ks) if self._kv_quantized else None,
                        _pad(state.vs) if self._kv_quantized else None,
                    ),
                )
        except Exception as e:
            self.allocator.release_all(pages)
            raise RuntimeError(f"kv-handoff restore failed: {e}") from e
        if request.trace is not None:
            request.trace.child(
                "queue_wait",
                start=request.timings.enqueued,
                end=request.timings.prefill_start,
            )
        seed = state.seed & 0xFFFFFFFFFFFFFFFF
        seed_row = np.array(
            [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32
        ).view(np.int32)
        slot = _Slot(request=request, pages=pages, position_cap=total_len)
        slot.generated = 1
        slot.seed_row = seed_row
        slot.prompt_len = prompt_len
        slot.prompt_ids = np.asarray(state.prompt_ids, np.int32)
        self._slots[slot_idx] = slot
        token = int(state.first_token)
        seq_len = prompt_len + 1
        live = token != self.tokenizer.eos_id and seq_len < total_len
        # Host mirrors become the source of truth; the dirty flag folds
        # them (and the restored pool) in before the next dispatch —
        # the same full-transition discipline as recovery.
        table = np.zeros((P,), np.int32)
        table[:len(pages)] = pages
        self._page_tables[slot_idx] = table
        self._seq_lens[slot_idx] = seq_len
        self._last_tokens[slot_idx] = token
        self._caps[slot_idx] = total_len
        self._temperature[slot_idx] = request.temperature
        self._top_p[slot_idx] = request.top_p
        self._top_k[slot_idx] = self._eff_top_k(request)
        self._seeds[slot_idx] = seed_row
        self._active[slot_idx] = live
        slot.merged = live
        self._dev_dirty = True
        if self.timeline is not None:
            self.timeline.admit(
                slot_idx, self._trace_id_of(request), prompt_len
            )
            self.timeline.note(
                "handoff_restore", slot=slot_idx, pages=n_kv,
                seq_len=seq_len,
            )
        self._note_first_token(slot_idx, slot, prompt_tokens=prompt_len,
                               handoff=True)
        request.out.put(("token", token))
        self._maybe_finish(slot_idx, token)
        return None

    def _drain_inflight(self) -> None:
        """Process every in-flight block and deliver every pending first
        token — the full pipeline flush that must precede any mirror
        upload (rare: cold start and failure recovery)."""
        while self._inflight_q:
            self._process_step(self._inflight_q.popleft())
        self._resolve_prefills(block=True)

    # -- host-memory KV tier (ISSUE 15) --------------------------------------

    def _params_fingerprint(self, seed: int) -> str:
        """Fingerprint of everything that determines KV content, gating
        durable prefix reloads: a state dir written under one set of
        weights must never warm an engine serving another. Explicit
        caller-provided params hash as a flag only — the supervisor's
        restart factory replays the same object, which is the contract
        that makes the flag sufficient there; callers mixing state dirs
        across different explicit weights are on their own (DEPLOY.md)."""
        import hashlib as _hashlib

        basis = (
            self.config.model, self.config.dtype, self.config.kv_dtype,
            self.config.quantize, self.config.quantize_bits,
            self.config.checkpoint_path or "",
            -1 if (self._params_explicit or self.config.checkpoint_path)
            else seed,
            self._params_explicit,
            self.config.page_size,
        )
        return _hashlib.blake2b(
            repr(basis).encode(), digest_size=8
        ).hexdigest()

    def _note_sched_frontier(self, frontier: str, served: list) -> None:
        """Starvation-witness hook (schedlint SL006): record one
        dispatch boundary — the slots this frontier served and the
        slots that were ELIGIBLE for it but got nothing (faulting slots
        at the restore frontier, pending-prefill resident slots at the
        prefill frontier). One predicate call when the witness is not
        armed (POLYKEY_SCHED_WITNESS=1)."""
        if not _schedwitness.installed():
            return
        if frontier == "restore":
            waiting = [
                i for i, s in enumerate(self._slots)
                if s is not None and s.restore_pages is not None
            ]
        else:
            waiting = [
                i for i, s in enumerate(self._slots)
                if s is not None and s.pending is not None
                and s.restore_pages is None
            ]
        _schedwitness.note(frontier, served, waiting)

    def _issue_restores(self) -> int:
        """The restore frontier: issue host→device page scatters for up
        to `host_kv_restore_slots` FAULTING slots, round-robin ahead of
        this iteration's prefill/decode dispatches. A faulting lane
        joins no dispatch until its restore has issued; once it has, the
        pool donation chain orders the restored contents ahead of every
        dispatch that could read them — so a resident lane never waits
        on a faulting one, and a faulting lane never needs a host sync
        to know its pages landed (page-aware scheduling, PersistentKV
        shape). Returns the number of slots restored."""
        if self._host_kv is None:
            return 0
        issued = 0
        served: list = []
        B = len(self._slots)
        # Round-robin from the cursor (the shared _RRCursor
        # discipline): admissions always fill the lowest free index, so
        # a 0-based scan would let fresh low-index faults starve a
        # high-index faulting slot of the per-iteration budget.
        for i in self._restore_rr.scan(B):
            slot = self._slots[i]
            if slot is None or slot.restore_pages is None:
                continue
            if issued >= self._restore_slots and issued > 0:
                # Progress floor (schedlint SL001): the `issued > 0`
                # conjunct proves at least one scatter rode this
                # iteration before the budget can wedge the frontier —
                # previously implicit in the >=1 clamp on the knob,
                # which a mis-tuned live actuation could have violated.
                self._restore_rr.reanchor(i)    # starved goes first next
                self._note_sched_frontier("restore", served)
                return issued
            if slot.request.cancelled.is_set():
                self._finish(i, error="cancelled")
                continue
            with self._phase("restore"):
                self._restore_slot_pages(i, slot)
            issued += 1
            served.append(i)
        self._restore_rr.advance(B)
        self._note_sched_frontier("restore", served)
        return issued

    def _page_upload(self, k, v, ks, vs) -> PagedKV:
        """`_jit_kv_restore`'s page operand from host arrays padded to
        pages_per_seq: K and V (heads apart, as the host tier and the
        wire keep them) folded into the stored layout, the int8 scale
        pages beside them as they are."""
        put = partial(jax.device_put, device=self._repl)
        pages = PagedKV(kv=put(fold_pages(k, v)))
        if self._kv_quantized:
            pages = pages.replace(ks=put(ks), vs=put(vs))
        return pages

    def _restore_slot_pages(self, slot_idx: int, slot: _Slot) -> None:
        """One faulting slot's restore: copy its host pages into the
        fixed-width upload buffers, scatter them into the slot's own
        device pages (`_jit_kv_restore`, pool donated — ONE executable,
        shared with the ISSUE 13 handoff restore), then promote the
        prefix-cache entries so later lookups hit device tier."""
        items = slot.restore_pages
        assert items
        cfg = self.config
        t0 = time.monotonic()
        P = cfg.pages_per_seq
        pool = self._host_kv
        idx = np.zeros((P,), np.int32)        # pad rows → garbage page 0
        k = np.zeros((self.model_cfg.num_layers, P, cfg.page_size,
                      self.model_cfg.num_kv_heads,
                      self.model_cfg.head_dim), self.paged.kv.dtype)
        v = np.zeros_like(k)
        ks = vs = None
        if self._kv_quantized:
            ks = np.zeros(k.shape[:-1], jnp.dtype(jnp.bfloat16))
            vs = np.zeros_like(ks)
        for r, (key, host_page, chain_idx) in enumerate(items):
            idx[r] = slot.pages[chain_idx]
            hk, hv, hks, hvs = pool.read(host_page)
            k[:, r] = hk
            v[:, r] = hv
            if self._kv_quantized:
                ks[:, r] = hks
                vs[:, r] = hvs
        try:
            # _host_crossing: the page payload rides up as one
            # deliberate upload — the page fault's whole point.
            with _host_crossing("kv-fault-restore"):
                self.paged = self._jit_kv_restore(
                    self.paged, jax.device_put(idx, self._repl),
                    self._page_upload(k, v, ks, vs),
                )
        except Exception as e:
            # Host copies are untouched on failure; _finish re-adopts
            # them into the cache so the warmth survives this slot.
            self._finish(slot_idx, error=f"kv restore failed: {e}")
            return
        for key, host_page, chain_idx in items:
            pool.release(host_page)
            # Re-register under the slot's device page (detached at
            # admission); a racing re-insert of the same prefix wins
            # harmlessly — our copy still serves this slot.
            self._prefix.reinsert_device(key, slot.pages[chain_idx])
        slot.restore_pages = None
        ms = (time.monotonic() - t0) * 1e3
        trace_id = self._trace_id_of(slot.request)
        self.metrics.on_kv_restore(len(items), ms, trace_id=trace_id)
        if self.timeline is not None:
            self.timeline.note(
                "kv_restore", slot=slot_idx, pages=len(items),
                ms=round(ms, 3), trace=trace_id,
            )

    def _spill_for(self, target_free: int) -> int:
        """Cold-page offload: spill LRU device-tier prefix entries into
        the host pool until the allocator has `target_free` free pages
        or no spillable entries remain. A spilled page whose content is
        also shared by a live slot frees only when that slot retires —
        the loop re-reads num_free rather than counting. Returns pages
        spilled."""
        if self._host_kv is None or self._prefix is None:
            return 0
        spilled = 0
        P = self.config.pages_per_seq
        while self.allocator.num_free < target_free:
            cands = self._prefix.spill_candidates(P)
            if not cands:
                break
            spilled += self._spill_batch(cands)
        return spilled

    def _spill_batch(self, cands: list) -> int:
        """Gather one batch of cold pages (≤ pages_per_seq — the fixed
        gather width) to host in ONE dispatch + one packed D2H read,
        move each into the host pool (LRU-dropping host entries under
        cap pressure), and write the batch through to the durable state
        dir when configured."""
        cfg = self.config
        P = cfg.pages_per_seq
        idx = np.zeros((P,), np.int32)
        idx[:len(cands)] = [page for _, page in cands]
        outs = self._jit_kv_gather(self.paged, jax.device_put(idx, self._repl))
        with _host_crossing("kv-evict-gather"):
            # The host tier keeps K and V, and the heads, apart
            # (kv_cache.HostKVPool).
            # polylint: disable=PL008(eviction gather resolve: one packed D2H read per spill batch; cold path, reached from dispatch only via _finish under the resident-floor check)
            k, v = unfold_pages(np.asarray(outs.kv), self.model_cfg.head_dim)
            ks = vs = None
            if self._kv_quantized:
                # polylint: disable=PL008(spill gather read, same cold path)
                ks = np.asarray(outs.ks)
                # polylint: disable=PL008(spill gather read, same cold path)
                vs = np.asarray(outs.vs)
        moved: list[tuple[bytes, int]] = []   # (key, gather row)
        for r, (key, _page) in enumerate(cands):
            try:
                host_page = self._host_kv.alloc()
            except AllocationError:
                # Host tier full: LRU pressure — drop the coldest host
                # entry to make room; an empty host LRU means the tier
                # is smaller than this batch, so the entry is dropped
                # outright (forgotten, recomputed on next use).
                if self._prefix.pop_lru_host() is None:
                    self._prefix.drop(key)
                    continue
                host_page = self._host_kv.alloc()
            self._host_kv.write(
                host_page, k[:, r], v[:, r],
                ks[:, r] if ks is not None else None,
                vs[:, r] if vs is not None else None,
            )
            self._prefix.mark_host(key, host_page)
            moved.append((key, r))
        if moved:
            self.metrics.on_kv_evict(len(moved))
            if self.timeline is not None:
                self.timeline.note("kv_evict", pages=len(moved))
            if self._kv_state is not None:
                rows = [r for _, r in moved]
                self._kv_state.save_batch(
                    [key for key, _ in moved],
                    k[:, rows], v[:, rows],
                    ks[:, rows] if ks is not None else None,
                    vs[:, rows] if vs is not None else None,
                )
                # Amortized gc: the cap is approximate anyway (oldest
                # batches beyond ~capacity), so a dir scan every 16
                # batches bounds the overshoot without paying listdir +
                # sidecar parses on every retire-pressure spill.
                self._kv_gc_countdown -= 1
                if self._kv_gc_countdown <= 0:
                    self._kv_state.gc(self._host_kv.capacity)
                    self._kv_gc_countdown = 16
        return len(moved)

    def _advance_chunked_prefills(self, budget: Optional[int]) -> int:
        """Advance slots mid-chunked-prefill, round-robin from the
        `_chunk_rr` cursor, one chunk per slot per call, until the token
        budget is spent (None → every pending slot advances one chunk —
        the no-live-decode fast path). The FIRST chunk always dispatches
        regardless of budget (progress floor: the budget bounds decode
        stalls, it must never wedge a long prompt). Returns prefill
        tokens dispatched."""
        spent = 0
        served: list = []
        B = len(self._slots)
        for i in self._chunk_rr.scan(B):
            s = self._slots[i]
            if s is None or s.pending is None:
                continue
            if s.restore_pages is not None:
                # Faulting slot: its prefix pages are not in flight yet
                # — it joins no dispatch until the restore frontier
                # issues its scatter (_issue_restores).
                continue
            if budget is not None and spent > 0 and spent >= budget:
                # Leave the cursor ON the starved slot so it goes first
                # next iteration.
                self._chunk_rr.reanchor(i)
                self._note_sched_frontier("prefill", served)
                return spent
            with self._phase("chunk"):
                charged = self._prefill_one_chunk(i)
            if charged:
                served.append(i)
            spent += charged
        self._chunk_rr.advance(B)
        self._note_sched_frontier("prefill", served)
        return spent

    def _prefill_one_chunk(self, slot_idx: int) -> int:
        """Advance a long-prompt slot by one dispatch: a chunk-wide
        window, or — once no more than a chunk is left — the cover of
        the tail (prefill_cover: a 600-token prompt's last 88 take a
        128-row window, not a second 512), whose last window samples
        the first token and activates the slot. Returns the rows
        dispatched (what the budget is charged), 0 when the slot exited
        without dispatching (cancelled / deadline-expired / prefill
        failure), so quota accounting (schedlint SL005) never bills
        tokens that never rode a dispatch."""
        slot = self._slots[slot_idx]
        assert slot is not None and slot.pending is not None
        request = slot.request
        if request.cancelled.is_set():
            self._finish(slot_idx, error="cancelled")
            return 0
        if self._deadline_expired(request):
            # Expired mid-prefill: remaining chunks never dispatch.
            self.metrics.on_deadline_expired("prefill")
            self._finish(slot_idx, error=f"{DEADLINE_MSG} during prefill")
            return 0
        width, rows = self._cover_rows(
            slot_idx, slot, slot.pending[slot.filled:], slot.filled,
            self._chunk_widths,
        )
        # No row of a dispatch that leaves tokens behind is `last`:
        # nothing is merged and its device token is never read.
        if not self._dispatch_prefill_group(width, rows):
            return 0
        slot.filled += width * len(rows)
        return width * len(rows)

    def _upload_slot_state(self) -> None:
        # A COPY of each mirror goes up, never the mirror: on the CPU
        # backend device_put of a 64-byte-aligned numpy array is
        # zero-copy, and a dispatch issued on this state runs later — it
        # would read the mirror writes of a merge made in between (a
        # lane active beside a sequence length still 0 emits one token
        # of garbage; tests/test_engine_streams.py).
        def put(mirror, sharding):
            return jax.device_put(mirror.copy(), sharding)

        self._dev = {
            "last_tokens": put(self._last_tokens, self._dp_vec),
            "seq_lens": put(self._seq_lens, self._dp_vec),
            "page_tables": put(self._page_tables, self._dp_mat),
            "active": put(self._active, self._dp_vec),
            "caps": put(self._caps, self._dp_vec),
            "temperature": put(self._temperature, self._dp_vec),
            "top_p": put(self._top_p, self._dp_vec),
            "top_k": put(self._top_k, self._dp_vec),
            "seeds": put(self._seeds, self._dp_mat),
        }
        if self._spec:
            # Per-lane gamma dial (ISSUE 19): device-resident like the
            # rest of the slot state; the mirrors were refreshed from the
            # last processed round's packed stat columns.
            self._dev["accept_ewma"] = put(self._lane_ewma, self._dp_vec)
            self._dev["gamma_lane"] = put(self._lane_gamma, self._dp_vec)
        self._dev_dirty = False

    def _dispatch_step(self):
        """Dispatch one decode block (or spec round) without waiting for it;
        returns an opaque record for _process_step. Between dispatch and
        process the engine resolves pending prefills, overlapping their
        device time with the block's."""
        if self._faults is not None:
            # Stand-ins for a wedged (step-stall) or degraded (slow-step)
            # device call: they block the engine thread exactly where the
            # real dispatch would, so the watchdog's no-progress clock
            # sees the genuine failure shape.
            self._faults.maybe_sleep("step-stall", replica=self.replica_id, tier=self._tier)
            self._faults.maybe_sleep("slow-step", replica=self.replica_id, tier=self._tier)
        if self._dev_dirty:
            # Rare (init / retire-failure recovery): mirrors must be
            # complete before they become the device state — deliver any
            # pending first tokens so _last_tokens is exact (the loop has
            # already drained in-flight blocks).
            self._resolve_prefills(block=True)
            self._upload_slot_state()
        # top_p composes with speculation via truncated rejection sampling
        # (sampling.truncated_dist), which needs the top-k prefilter
        # (top_p_candidates > 0) to avoid full-vocab sorts. Without the
        # prefilter, a batch containing any top_p<1 row takes the plain
        # step; note that blast radius is batch-wide — speculation is off
        # for every slot while such a row is active, and the plain steps
        # leave draft-cache holes, so acceptance stays collapsed for
        # surviving streams afterwards. Correctness never degrades.
        # Greedy rows neutralize top_p inside the round (eff_top_p), so
        # only SAMPLED rows with top_p<1 require the truncated variant.
        act = self._active
        all_untruncated = bool(np.all(
            ((self._top_p[act] >= 1.0) & (self._top_k[act] <= 0))
            | (self._temperature[act] == 0.0)
        ))
        spec_on = self._spec and (
            self.config.top_p_candidates > 0 or all_untruncated
        )
        dev = self._dev
        if spec_on:
            spec_candidates = (
                0 if all_untruncated else self.config.top_p_candidates
            )
            # Spec rounds: full-size blocks; >= 1 token lands per round,
            # so `remaining` rounds always suffice (same tail-work cap
            # as the plain path).
            self._depth_target = min(
                self._depth, max(1, self._remaining_budget(act))
            )
            # Occupancy tracker: a spec round's scan length is gamma
            # draft steps + one verify — the step weight that makes its
            # lane-seconds comparable to a plain K-step block's.
            lanes = int(act.sum())
            gap_ms = self.metrics.on_dispatch(
                lanes, self._gamma + 1, slots=len(self._slots),
                depth=self._depth_target,
            )
            live = tuple(int(i) for i in np.flatnonzero(act))
            data = self._dispatch_spec(dev, spec_candidates, lanes)
            self._dispatch_seq += 1
            if self.timeline is not None:
                self.timeline.dispatch(
                    self._dispatch_seq, "spec", lanes, self._gamma + 1,
                    gap_ms,
                )
            return _InflightBlock(
                "spec", data, self._snapshot_requests(), self._dispatch_seq,
                gap_ms, live, self._gamma + 1,
            )
        # Static variant: an all-greedy batch (the benchmark mode) skips
        # the sampler's sorted head and all RNG work. At most two
        # compiled variants exist; the mix flips only at slot transitions.
        greedy = bool(np.all(self._temperature[self._active] == 0.0))
        # Load-adaptive K: one active stream → small blocks (per-token
        # delivery at the device's step rate); more → the full block.
        steps = (
            self._solo_steps if int(act.sum()) == 1 else self._block_steps
        )
        self._last_dispatch_steps = steps
        # Constant steps-in-flight across block sizes — but never more
        # than the active streams still NEED: every in-flight step costs
        # a full weight-read on device even when its lanes have stopped,
        # so lookahead past the longest remaining budget burns device
        # time at stream tails and queues real latency in front of the
        # next arrival's prefill (a solo stream at K=2 used to keep 64
        # steps ≈ 0.9 s of dead work in flight).
        remaining = self._remaining_budget(act)
        blocks_needed = max(1, -(-remaining // max(1, steps)))
        # Scale only the LOOKAHEAD portion (depth - 1 queued blocks);
        # the +1 is the dispatch in hand. Deepening the whole depth
        # would let depth 1 — the documented synchronous escape hatch —
        # run ahead whenever adaptive blocking shrinks K (target 8 on a
        # solo stream), breaking the bit-identical-rollback contract on
        # any backend where readback isn't instant.
        self._depth_target = min(
            64,
            1 + (self._depth - 1) * (self._block_steps // max(1, steps)),
            blocks_needed,
        )
        lanes = int(act.sum())
        gap_ms = self.metrics.on_dispatch(
            lanes, steps, slots=len(self._slots), depth=self._depth_target,
            layer_passes=self._layer_passes,
        )
        live = tuple(int(i) for i in np.flatnonzero(act))
        with self._phase("decode", seq=self._dispatch_seq + 1, lanes=lanes,
                         steps=steps, **self._loop_attrs):
            (packed_dev, last_dev, seq_dev, act_dev,
             self.paged, self.state) = self._jit_decode(
                self.params,
                self.model_cfg,
                self.paged,
                dev["last_tokens"],
                dev["seq_lens"],
                dev["page_tables"],
                dev["active"],
                dev["caps"],
                dev["seeds"],
                dev["temperature"],
                dev["top_p"],
                dev["top_k"],
                self.state,
                greedy=greedy,
                steps=steps,
                eos_id=self.tokenizer.eos_id,
                candidates=self.config.top_p_candidates,
                mesh=self.mesh,
            )
            # Feed final state straight back as the next block's inputs;
            # host mirrors update in _process_step for bookkeeping.
            dev["last_tokens"] = last_dev
            dev["seq_lens"] = seq_dev
            dev["active"] = act_dev
        try:
            # Ship the block's packed tokens host-ward as soon as the
            # device finishes them; by processing time (lookahead_blocks
            # later) the read is then local.
            packed_dev.copy_to_host_async()
        except Exception:
            # Best-effort copy hint only: np.asarray at process time syncs
            # regardless, so a backend without async copies loses overlap,
            # not correctness.
            pass
        self._dispatch_seq += 1
        if self.timeline is not None:
            self.timeline.dispatch(
                self._dispatch_seq, "plain", lanes, steps, gap_ms
            )
        return _InflightBlock(
            "plain", packed_dev, self._snapshot_requests(), self._dispatch_seq,
            gap_ms, live, steps, not greedy,
        )

    def _eff_top_k(self, request: GenRequest) -> int:
        """Effective per-request top_k: with the top-k prefilter enabled
        (top_p_candidates = C > 0) every sampled path sees only the top-C
        logits, so a wider top_k clamps to C — applied at admission so
        the narrowing is a visible, documented contract
        (engine/config.py top_p_candidates) rather than a silent property
        of the sampler."""
        k = request.top_k
        C = self.config.top_p_candidates
        return min(k, C) if (C > 0 and k > 0) else k

    def _remaining_budget(self, act) -> int:
        """Longest remaining token budget over active lanes (host
        mirrors) — the tail-work cap both dispatch paths share."""
        return int(np.max(np.where(act, self._caps - self._seq_lens, 0)))

    def _note_block_token(self, slot: _Slot, block_span, before: int,
                          t_sync: float, **attrs):
        """Per-token block-span upkeep shared by the plain and spec
        process paths: lazily open the slot's decode_block child (only
        traced slots get one) and keep its token count and end time
        current. Called BEFORE the token (and any terminal event
        _maybe_finish enqueues) reaches the client — the gateway may
        snapshot the tree the moment the stream ends, and a child added
        after that snapshot would be lost."""
        if slot.decode_span is None:
            return None
        if block_span is None:
            # Clamp to the parent's start: when the slot's first token
            # resolved within THIS sync, t_sync predates the decode span
            # opened at first_token, and a child must not begin before
            # its parent in the rendered tree.
            block_span = slot.decode_span.child(
                "decode_block",
                start=max(t_sync, slot.decode_span.start),
                **attrs,
            )
        block_span.set(tokens=slot.generated - before)
        block_span.end = time.monotonic()
        return block_span

    def _note_block_done(self, slot: _Slot, before: int) -> None:
        """Post-block ITL accounting shared by both process paths: the
        window since the slot's previous emit, amortized per token."""
        n = slot.generated - before
        if n > 0:
            now = time.monotonic()
            if slot.last_emit > 0:
                self.metrics.on_itl(
                    (now - slot.last_emit) * 1e3 / n, n,
                    trace_id=self._trace_id_of(slot.request),
                )
            slot.last_emit = now

    def _snapshot_requests(self):
        """Per-slot request identities at dispatch time: with cross-block
        lookahead a slot can be finished (cancel) and re-admitted while its
        block is in flight, and the stale lane's tokens must never reach
        the new occupant."""
        return [s.request if s is not None else None for s in self._slots]

    def _block_ready(self, block) -> bool:
        """True when a dispatched block's result buffers have landed —
        its readback will not block the host. Conservative: a backend
        without is_ready() reports landed (the read then syncs, which is
        the pre-pipeline behavior — correctness over overlap)."""
        data = block[1]
        try:
            return data.is_ready()
        except Exception:
            # Justified: is_ready() is an optional backend capability —
            # "landed" is the safe answer (process path syncs regardless),
            # and an error here must never take the engine loop down.
            return True

    def _process_step(self, block) -> None:
        """Sync a dispatched block's results and emit/finish on the host.
        Slots activated between dispatch and process were not in the block:
        their device lanes were inactive, so their columns read -1.

        `block` is an _InflightBlock (legacy bare (kind, data, reqs)
        tuples still unpack — seq then defaults to the current dispatch
        frontier, i.e. observed lookahead 0)."""
        kind, data, reqs = block[0], block[1], block[2]
        seq = block[3] if len(block) > 3 else self._dispatch_seq
        gap_ms = block[4] if len(block) > 4 else 0.0
        live = block[5] if len(block) > 5 else ()
        # Lane-step outcomes are counted for blocks that say how many
        # steps they ran (every _InflightBlock the engine builds; a bare
        # legacy tuple's 0 steps count as nothing).
        steps = block[6] if len(block) > 6 else 0
        sampled = block[7] if len(block) > 7 else False
        slots = len(self._slots)
        # Observed lookahead: blocks dispatched after this one, before its
        # readback — ≥1 is the overlap the pipeline exists for; 0 is the
        # synchronous depth-1 shape. Recorded for every processed block
        # (the dispatch-order test and engine_stats read it).
        lookahead = self._dispatch_seq - seq
        queued_after = len(self._inflight_q)
        if kind == "spec":
            # Spec rounds always sync: their device-computed acceptance
            # stats feed the gamma-tuning dial even when every occupant is
            # gone by processing time.
            emitted = self._process_spec(data, reqs, lookahead, seq=seq,
                                         gap_ms=gap_ms, live=live,
                                         queued_after=queued_after)
            # gamma+1 lane-steps a lane (the weight on_dispatch gave the
            # round): rejected draft positions delivered nothing and land
            # in overshoot with the steps past a stream's end.
            self.metrics.on_lane_steps(emitted, len(live), slots, steps)
            return
        if not any(
            s is not None and s.request is reqs[i]
            for i, s in enumerate(self._slots)
        ):
            # Dead block: every dispatch-time occupant is gone (batch
            # drained / all cancelled). Nothing to emit — skip the sync
            # entirely so the drain costs no host↔device roundtrip (no
            # stall is recorded: nothing was read; no device time is
            # attributed: every lane's request already finished). Its
            # live lanes' steps all delivered nothing: overshoot.
            self.metrics.on_process_block(lookahead, None)
            self.metrics.on_lane_steps(0, len(live), slots, steps)
            if self.timeline is not None:
                now = time.monotonic()
                self.timeline.process(seq, now, now, None, lookahead,
                                      queued_after, 0.0)
            return
        t_sync = time.monotonic()
        with self._phase("readback_wait"), _host_crossing("block-packed"):
            # polylint: disable=PL001(block resolve point; one packed D2H read per block)
            packed = np.asarray(data)     # [K, B]; blocks until block done
        # Host stall: how long the processed frontier blocked waiting for
        # this block's copy to land — ~0 when lookahead hid the roundtrip,
        # ~roundtrip_ms when the host is on the critical path (the r03
        # signature this pipeline exists to erase).
        stall_ms = (time.monotonic() - t_sync) * 1e3
        if sampled:
            # The sampled variant's last row (_decode_fn): the sub-steps
            # on which the sampler sorted the whole vocabulary.
            packed, full_sorts = packed[:-1], int(packed[-1, 0])
        if self._expert_layers:
            # One more row (_decode_fn): the held experts the block's
            # expert layers hit, over its steps that had a live lane.
            packed, hit = packed[:-1], int(packed[-1, 0])
        if self._loops > 1:
            # A looped stack's rows (_decode_fn): the block's live
            # lane-steps by the pass their exit rule chose.
            packed, exits = packed[:-self._loops], packed[-self._loops:, 0]
            self.metrics.on_loop_exits(exits)
        live_steps = int((packed >= 0).any(axis=1).sum())
        if sampled:
            self.metrics.on_sampler_steps(live_steps, full_sorts)
        if self._expert_layers:
            self.metrics.on_held_experts(self._expert_layers * live_steps, hit)
        self.metrics.on_process_block(
            lookahead, stall_ms, trace_id=self._block_trace_id(reqs, live)
        )
        busy_ms = self._attribute_device_time(gap_ms, stall_ms, live, reqs)

        emitted = 0
        for i, slot in enumerate(self._slots):
            if slot is None or not self._active[i] or slot.request is not reqs[i]:
                continue
            if slot.request.cancelled.is_set():
                self._finish(i, error="cancelled")
                continue
            if self._deadline_expired(slot.request):
                # Block-boundary deadline drop: the lane retires now, so
                # no further block computes for a client that is gone.
                self.metrics.on_deadline_expired("decode")
                self._finish(i, error=f"{DEADLINE_MSG} mid-decode")
                continue
            if slot.token_dev is not None:
                self._resolve_in_block(i, slot)
                if self._slots[i] is not slot:
                    continue
            # The block's own [K, B] shape, not the configured K — the
            # adaptive dispatcher varies K per block.
            before = slot.generated
            block_span = None
            for k in range(packed.shape[0]):
                token = int(packed[k, i])
                if token < 0:
                    break
                slot.generated += 1
                self._seq_lens[i] += 1
                self._last_tokens[i] = token
                block_span = self._note_block_token(
                    slot, block_span, before, t_sync,
                    steps=int(packed.shape[0]),
                )
                slot.request.out.put(("token", token))
                emitted += 1
                self._maybe_finish(i, token)
                if self._slots[i] is None:  # finished mid-block
                    break
            self._note_block_done(slot, before)
        self.metrics.on_step(emitted)
        # Lane-step outcomes, known only now.
        self.metrics.on_lane_steps(emitted, len(live), slots, steps)
        if self.timeline is not None:
            self.timeline.process(seq, t_sync, time.monotonic(), stall_ms,
                                  lookahead, queued_after, busy_ms)

    def _block_trace_id(self, reqs, live) -> Optional[str]:
        """A trace id to exemplar block-level observations with: the
        first traced request live in the block (any live request is an
        honest witness for a shared stall)."""
        for i in live:
            trace_id = self._trace_id_of(reqs[i])
            if trace_id is not None:
                return trace_id
        return None

    def _attribute_device_time(self, gap_ms: float, stall_ms: float,
                               live, reqs) -> float:
        """Per-request device-time attribution (ISSUE 10): charge this
        block's device-busy window — the host gap that preceded its
        dispatch minus the host stall its readback cost — equally to the
        lanes live at dispatch, into each request's timings.device_ms.

        The dispatch gap approximates the block's device residency
        (dispatches serialize on the device through the pool donation
        chain, so at steady state consecutive dispatches tile the
        device's schedule); subtracting the measured stall removes the
        host's share. Conservation: Σ busy ≤ Σ counted gaps ≤ wall, so
        Σ per-request device_ms can never exceed wall × slots — and on
        a single-lane run the one request receives exactly
        device_busy_ms_total (both pinned by tests/test_timeline.py).
        Returns the busy ms charged (0.0 when nothing was)."""
        if not live or gap_ms <= 0.0:
            return 0.0
        busy = gap_ms - max(0.0, stall_ms)
        if busy <= 0.0:
            return 0.0
        self.metrics.on_device_busy(busy)
        share = busy / len(live)
        for i in live:
            request = reqs[i]
            if request is not None:
                request.timings.device_ms += share
        return busy

    def _dispatch_spec(self, dev: dict, candidates: int = 0,
                       lanes: int = 0):
        """Dispatch one draft/verify round (spec_decode.py). `candidates`
        is 0 when every active row has top_p >= 1 — the round then skips
        all truncation work (plain softmax dists). The round is fully
        device-resident (ISSUE 19): acceptance stats and the per-lane
        gamma dial ride the packed matrix's stat columns, so the block
        boundary costs ONE D2H read, same as a plain block."""
        with self._phase("spec_decode", seq=self._dispatch_seq + 1,
                         lanes=lanes, steps=self._gamma + 1):
            (packed_dev, new_last, new_seq, new_active, new_ewma,
             new_gamma, self.paged, self.d_paged) = self._jit_spec_decode(
                self.params, self.draft_params,
                self.model_cfg, self.draft_cfg,
                self.paged, self.d_paged,
                dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                dev["active"], dev["caps"], dev["seeds"],
                dev["temperature"], dev["top_p"], dev["top_k"],
                dev["accept_ewma"], dev["gamma_lane"],
                gamma=self._gamma,
                eos_id=self.tokenizer.eos_id,
                gamma_low=self._gamma_low, gamma_max=self._gamma_max,
                candidates=candidates, mesh=self.mesh,
            )
            dev["last_tokens"] = new_last
            dev["seq_lens"] = new_seq
            dev["active"] = new_active
            dev["accept_ewma"] = new_ewma
            dev["gamma_lane"] = new_gamma
        try:
            packed_dev.copy_to_host_async()
        except Exception:
            # Best-effort copy hint only: _process_spec's np.asarray syncs
            # regardless; backends without async copies lose overlap only.
            pass
        return packed_dev

    def _process_spec(self, data, reqs, lookahead: int = 0, seq: int = 0,
                      gap_ms: float = 0.0, live: tuple = (),
                      queued_after: int = 0) -> int:
        """Sync a spec round; emits each row's packed prefix (-1 padded —
        device-truncated) and returns how many tokens it emitted. Acceptance stats AND the per-lane gamma dial
        come FROM the device inside the same packed matrix (ISSUE 19:
        spec_decode._accept_merge owns truncation, the untruncated n_acc,
        and the dial update) — ONE D2H read per round, exactly like a
        plain block's packed readback."""
        packed_dev = data
        t_sync = time.monotonic()
        with self._phase("readback_wait"), _host_crossing("spec-packed"):
            # polylint: disable=PL001(spec-round resolve point; the ONE packed D2H read carries tokens, counts, and the gamma dial)
            packed = np.asarray(packed_dev)  # [B, gamma+1+SPEC_STAT_COLS]
        stall_ms = (time.monotonic() - t_sync) * 1e3
        # Stat columns (spec_decode.SPEC_STAT_COLS): per-lane accepted /
        # proposed counts, the acceptance EWMA in 1e-6 fixed point, and
        # the lane's next gamma dial.
        g1 = packed.shape[1] - 4
        acc_col, prop_col = packed[:, g1], packed[:, g1 + 1]
        ewma_col, dial_col = packed[:, g1 + 2], packed[:, g1 + 3]
        accepted, proposed = int(acc_col.sum()), int(prop_col.sum())
        for i, slot in enumerate(self._slots):
            # Mirror refresh gated on request identity: a stale lookahead
            # round must not overwrite a re-admitted lane's fresh dial
            # (the DEVICE copy is already correct — the merge reset
            # chained after this round's outputs).
            if slot is not None and slot.request is reqs[i]:
                self._lane_ewma[i] = ewma_col[i] / 1e6
                self._lane_gamma[i] = dial_col[i]
        self.metrics.on_process_block(
            lookahead, stall_ms, trace_id=self._block_trace_id(reqs, live)
        )
        busy_ms = self._attribute_device_time(gap_ms, stall_ms, live, reqs)

        emitted = 0
        for i, slot in enumerate(self._slots):
            if slot is None or not self._active[i] or slot.request is not reqs[i]:
                continue
            if slot.request.cancelled.is_set():
                self._finish(i, error="cancelled")
                continue
            if self._deadline_expired(slot.request):
                self.metrics.on_deadline_expired("decode")
                self._finish(i, error=f"{DEADLINE_MSG} mid-decode")
                continue
            if slot.token_dev is not None:
                self._resolve_in_block(i, slot)
                if self._slots[i] is not slot:
                    continue
            before = slot.generated
            block_span = None
            for j in range(g1):
                token = int(packed[i, j])
                if token < 0:
                    break
                slot.generated += 1
                self._seq_lens[i] += 1
                self._last_tokens[i] = token
                block_span = self._note_block_token(
                    slot, block_span, before, t_sync, spec_round=True,
                )
                slot.request.out.put(("token", token))
                emitted += 1
                self._maybe_finish(i, token)
                if self._slots[i] is None:   # finished mid-window
                    break
            self._note_block_done(slot, before)
        self.metrics.on_step(emitted)
        if self.timeline is not None:
            self.timeline.process(seq, t_sync, time.monotonic(), stall_ms,
                                  lookahead, queued_after, busy_ms)
        self.metrics.on_spec(accepted, proposed)
        if proposed > 0:
            # Batch-aggregate EWMA, observability only (the per-lane dial
            # updated on DEVICE; see spec_decode._accept_merge). Same
            # blend as the per-lane one so operators can sanity-check the
            # lane spread against a familiar aggregate.
            from .spec_decode import GAMMA_EWMA_BETA

            rate = accepted / proposed
            self._accept_ewma = (
                GAMMA_EWMA_BETA * self._accept_ewma
                + (1.0 - GAMMA_EWMA_BETA) * rate
            )
        # Dispatch width: the ladder rung covering the widest ACTIVE lane
        # dial (a lane at gamma_low costs nothing extra when batchmates
        # need gamma_max — its surplus drafts are force-masked on
        # device), clamped by the autopilot's cap. Both rungs are
        # warmup-compiled; no new executables.
        if self._spec:
            act = [
                i for i, s in enumerate(self._slots)
                if s is not None and self._active[i]
            ]
            want = (
                int(self._lane_gamma[act].max()) if act else self._gamma_max
            )
            rung = (
                self._gamma_max if want > self._gamma_low
                else self._gamma_low
            )
            self._gamma = min(rung, self._gamma_cap)
        return emitted

    def _maybe_finish(self, slot_idx: int, token: int) -> None:
        slot = self._slots[slot_idx]
        assert slot is not None
        request = slot.request
        hit_eos = token == self.tokenizer.eos_id
        hit_cap = (
            slot.generated >= request.max_new_tokens
            or slot.generated >= self.config.max_new_tokens_cap
            or int(self._seq_lens[slot_idx]) >= slot.position_cap
        )
        if hit_eos or hit_cap:
            self._finish(slot_idx)

    def _finish(self, slot_idx: int, error: Optional[str] = None) -> None:
        slot = self._slots[slot_idx]
        if slot is None:
            return
        request = slot.request
        request.timings.finished = time.monotonic()
        request.timings.completion_tokens = slot.generated
        if self.timeline is not None:
            self.timeline.slot_end(
                slot_idx,
                "cancelled" if error == "cancelled"
                else ("error" if error is not None else "done"),
                slot.generated,
            )
        if slot.decode_span is not None:
            slot.decode_span.set(tokens=slot.generated)
            slot.decode_span.finish(end=request.timings.finished)
        if request.trace is not None and request.timings.device_ms > 0:
            # Attribution rides the span tree too: the root span carries
            # the request's accumulated device time so a flight-recorder
            # tree answers "device or host?" without cross-referencing.
            request.trace.set(device_ms=round(request.timings.device_ms, 3))
        if request.trace is not None and error is not None:
            # Cancellation is not a failure label: the gateway cancels on
            # stop-sequence matches and client disconnects, both of which
            # end the RPC cleanly (tpu_service._text_events calls the
            # engine's "cancelled" the EXPECTED outcome). A postmortem
            # reader must not chase phantom errors on stop-terminated
            # requests.
            if error == "cancelled":
                request.trace.set(cancelled=True)
            else:
                request.trace.set(error=error)
        if slot.restore_pages:
            # Died faulting (cancel/deadline/failure before its restore
            # issued): the slot owns these host pages — re-adopt them
            # into the cache so the warmth survives the slot; a key
            # re-cached meanwhile keeps its copy and ours frees.
            for key, host_page, _ci in slot.restore_pages:
                if self._prefix is None or \
                        not self._prefix.adopt_host(key, host_page):
                    self._host_kv.release(host_page)
            slot.restore_pages = None
        self.allocator.release_all(slot.pages)
        self._slots[slot_idx] = None
        self._active[slot_idx] = False
        self._caps[slot_idx] = 0
        self._seq_lens[slot_idx] = 0
        self._last_tokens[slot_idx] = 0
        self._page_tables[slot_idx] = 0
        self._seeds[slot_idx] = 0
        if slot.merged and self.dead is None and not self._stop.is_set():
            # Retire the device lane (stop stale-table writes) without
            # flushing the pipeline — a tiny chained dispatch, the mirror
            # of _merge_slot. EOS/cap retirements already stopped on
            # device; this also covers cancellations and failures.
            dev = self._dev
            try:
                # _host_crossing: the slot index rides as a numpy scalar.
                with _host_crossing("retire-upload"):
                    (
                        dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                        dev["active"], dev["caps"],
                    ) = self._jit_retire(
                        dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
                        dev["active"], dev["caps"], np.int32(slot_idx),
                    )
            except Exception as e:
                # Retire is an optimization; the dirty flag's full mirror
                # re-upload is the correct fallback — but a recurring
                # failure here means every finish flushes the pipeline,
                # so leave a trace for the postmortem reader.
                if self.logger is not None:
                    self.logger.warn(
                        "lane retire failed; falling back to full "
                        "mirror re-upload", slot=slot_idx, error=str(e),
                    )
                self._dev_dirty = True
        if self._host_kv is not None and self.dead is None \
                and not self._stop.is_set() \
                and self.allocator.num_free < self._resident_low:
            # Eviction at retire (ISSUE 15): the request just released
            # its pages; if the free list is still below the resident
            # working-set floor, the pool is crowded with COLD pages —
            # spill LRU prefix entries to host now, off any admission's
            # critical path, so the next burst allocates without paying
            # the gather synchronously.
            self._spill_for(self._resident_low)
        if error is not None:
            request.out.put(("error", error))
            self.metrics.on_finish(request.timings, failed=True,
                                   trace_id=self._trace_id_of(request))
        else:
            request.out.put(("done", request.timings))
            self.metrics.on_finish(request.timings,
                                   trace_id=self._trace_id_of(request))

    def _fail_pending(self, message: str) -> None:
        try:
            while True:
                request = self._submit.get_nowait()
                request.out.put(("error", message))
        except queue.Empty:
            pass

    def _fail_all(self, message: str) -> None:
        self._inflight_q.clear()  # drop unprocessed lookahead results
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._finish(i, error=message)
        self._fail_pending(message)
