"""Serving metrics: the north-star counters (tok/s, TTFT) plus engine gauges.

The reference's observability is per-RPC duration logging only (SURVEY.md §5
"metrics"); the engine adds what serving needs: request phase timestamps
(enqueue → prefill → first token → finish), throughput counters, and pool
gauges. Snapshots surface through the `engine_stats` tool and per-request
Usage on the streaming RPC; the same state exports in Prometheus text form
via obs.exposition.engine_collector (ISSUE 1).

TTFT and inter-token latency are histogram-backed (obs.histogram): fixed
log-spaced buckets give O(1)-memory p50/p90/p95/p99 over the FULL history
(the old 512-entry ring only saw recent requests and sorted on every
snapshot) and render directly as Prometheus ``_bucket`` families.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from ..obs.histogram import Histogram
from ..obs.timeline import PHASES, STARTUP_PHASES

# Bucket bounds for the live-lanes-per-block histogram: lane counts are
# small integers bounded by max_decode_slots, so a fixed power-of-two-ish
# ladder up to 512 covers every plausible slot configuration with ~16
# buckets (O(1) memory, same Prometheus rendering as the latency
# histograms). Exact occupancy ratios come from the counters, not the
# histogram — this exists for the distribution's SHAPE (is the engine
# bimodal between empty and full, or genuinely holding N lanes?).
LANE_BUCKETS = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
                384, 512)

# What a request's time in the submit queue is charged to (ISSUE 45):
# `loop` — the engine thread was in another phase and had not looked at
# it (or was inside the visit that admitted it); the other three are the
# reasons an `_admit` visit left it waiting (EngineMetrics.admit_deferred
# counts the same visits), each charged from that visit to the next.
QUEUE_CAUSES = ("loop", "budget", "no_slot", "no_pages")


@dataclass
class RequestTimings:
    enqueued: float = field(default_factory=time.monotonic)
    prefill_start: float = 0.0
    # Seconds of enqueued -> prefill_start spent behind `_admit` visits
    # that left the request waiting, by the visit's reason (budget,
    # no_slot, no_pages); stamped with prefill_start. What they do not
    # cover is cause `loop` (QUEUE_CAUSES).
    queue_deferred: dict = field(default_factory=dict)
    # Host time at which the prefill dispatch that completes the prompt
    # was issued (the group dispatch of a bucketed prompt, the last chunk
    # of a chunked one): splits the time after admission into "held on
    # the host" and what follows the dispatch call (ISSUE 26) — the
    # device's queue and the prefill (a capture times both), then the
    # finished token waiting for the host to read it (the `first_token`
    # engine phase, EngineMetrics.first_token_poll_gap_seconds).
    prefill_dispatched: float = 0.0
    first_token: float = 0.0
    finished: float = 0.0
    prompt_tokens: int = 0
    completion_tokens: int = 0
    # Device-time attribution (ISSUE 10): the request's share of every
    # decode block's device-busy window (dispatch gap minus host stall,
    # split equally across the lanes live at dispatch). Accumulated by
    # the engine thread; surfaced as a span attribute, the `device-ms`
    # trailer, and the polykey_request_device_ms histogram.
    device_ms: float = 0.0

    @property
    def ttft_ms(self) -> float:
        if self.first_token and self.enqueued:
            return (self.first_token - self.enqueued) * 1e3
        return 0.0

    def ttft_phases_s(self) -> tuple[float, float, float]:
        """(queue, prefill_wait, first_token) seconds: three phases that
        partition enqueued -> first_token exactly. A path that stamped
        no admission or no dispatch gives that phase's time to the next
        one, so the sum stays `ttft_ms`."""
        admitted = self.prefill_start or self.enqueued
        dispatched = self.prefill_dispatched or admitted
        return (admitted - self.enqueued, dispatched - admitted,
                self.first_token - dispatched)

    @property
    def tokens_per_sec(self) -> float:
        if self.finished and self.first_token and self.completion_tokens > 1:
            elapsed = self.finished - self.first_token
            if elapsed > 0:
                return (self.completion_tokens - 1) / elapsed
        return 0.0

class EngineMetrics:
    """Thread-safe counters; cheap enough to update from the step loop."""

    def __init__(self):
        self._lock = threading.Lock()
        self.requests_admitted = 0
        self.requests_completed = 0
        self.requests_failed = 0
        self.tokens_generated = 0
        self.decode_steps = 0
        # Latency histograms (observe() is internally locked; kept outside
        # self._lock so a scrape rendering them never contends the step
        # loop's counter lock).
        self.ttft_hist = Histogram()
        self.itl_hist = Histogram()
        self.drafts_accepted = 0
        self.drafts_proposed = 0
        # Overload accounting (ISSUE 3): sheds at admission, deadline
        # expiries by phase. Exported as polykey_requests_shed_total and
        # polykey_deadline_expired_total{phase=...}.
        self.requests_shed = 0
        self.deadline_expired = {"queued": 0, "prefill": 0, "decode": 0}
        # EWMA of per-request service time (admission → finish), the
        # input to the estimated-queue-delay admission check: with S
        # slots draining in parallel, one queued request waits roughly
        # qsize × ewma / S before admission. 0.0 until the first finish.
        self._service_ewma_s = 0.0
        self._window_start = time.monotonic()
        self._window_tokens = 0
        self.tokens_per_sec = 0.0
        # Occupancy tracker (ISSUE 4): always-on per-dispatch live-lane
        # accounting, the source of truth for avg_lanes. One locked add
        # per dispatched block (the loop runs a handful a second
        # at steady state — negligible next to the device call it rides):
        #   blocks_dispatched — decode blocks / spec rounds dispatched
        #   lanes_dispatched  — Σ live lanes at dispatch (block-weighted)
        #   lane_steps        — Σ lanes × steps   (step-weighted; what the
        #                       roofline's bytes/token actually amortizes
        #                       over, since a K-step block reads weights K
        #                       times at that occupancy)
        #   steps_dispatched  — Σ steps
        # avg_lanes in snapshots is the STEP-weighted mean; an EWMA of
        # lanes-per-block gives the "now" gauge for dashboards.
        self.blocks_dispatched = 0
        self.lanes_dispatched = 0
        self.lane_steps = 0
        self.steps_dispatched = 0
        self._lanes_ewma = 0.0
        self.lanes_hist = Histogram(bounds=LANE_BUCKETS)
        # Interleaved-prefill accounting: total prefill tokens dispatched
        # and the worst single-iteration injection observed WHILE decode
        # lanes were live — the bound the stall test pins (engine loop
        # charges per iteration; see EngineConfig.prefill_budget for the
        # overshoot semantics).
        self.prefill_tokens_total = 0
        self.interleave_max_tokens = 0
        # Padding-waste accounting (ISSUE 12): per dispatch, how many
        # token rows the device COMPUTED vs how many were useful work.
        # Decode blocks charge slots×steps dispatched / lanes×steps
        # useful (dead-lane padding); bucketed prefill charges the
        # padded group width (n_pad × bucket, or the chunk width C) vs
        # the real token count. tokens_useful / tokens_dispatched is
        # the occupancy-soak's padding-waste ratio.
        self.tokens_dispatched_total = 0
        self.tokens_useful_total = 0
        # Engine phases, request phases and lane-step outcomes
        # (ISSUE 26), all written by the ENGINE THREAD ONLY. The phase
        # accumulators and deferral counts take plain adds with no lock
        # (several a loop iteration); the per-request and per-block
        # counters take the one lock the per-block counters above
        # already take. snapshot() copies.
        #
        # phase_seconds / phase_count: time.monotonic() seconds and
        # entries per engine phase (obs.timeline.PHASES; one `with
        # phase(...)` site each). Loop phases never nest in one another,
        # so their sum is the engine thread's time. The "startup" ones
        # are written by the thread that constructs the engine, before
        # the engine thread exists (ISSUE 62).
        self.phase_seconds = dict.fromkeys(PHASES, 0.0)
        self.phase_count = dict.fromkeys(PHASES, 0)
        # Requests whose first token resolved: seconds in the three
        # phases of RequestTimings.ttft_phases_s (they partition
        # Usage.ttft_ms), and how many requests.
        self.ttft_phase_seconds = {
            "queue": 0.0, "prefill_wait": 0.0, "first_token": 0.0,
        }
        self.ttft_phase_count = 0
        # The same requests' `queue` seconds by cause (QUEUE_CAUSES): the
        # four add up to ttft_phase_seconds["queue"], request by request.
        self.ttft_queue_seconds = dict.fromkeys(QUEUE_CAUSES, 0.0)
        # Each time _admit leaves a waiting request where it is, by
        # reason: no free slot, no pages (AllocationError, requeued), or
        # the interleaved-prefill budget of this iteration spent.
        self.admit_deferred = {"no_slot": 0, "no_pages": 0, "budget": 0}
        # Decode lane-steps by OUTCOME, counted when a block is
        # processed (on_lane_steps): delivered + overshoot + dead =
        # sum of slots x steps over processed blocks. Plain blocks
        # and spec rounds (steps = gamma + 1; a rejected draft position
        # is a lane-step that delivered nothing and lands in overshoot)
        # both keep the identity.
        self.decode_lane_steps_delivered = 0
        self.decode_lane_steps_overshoot = 0
        self.decode_lane_steps_dead = 0
        # Prefill rows computed vs real prompt tokens, bucketed groups
        # and chunks (on_prefill_rows).
        self.prefill_rows_dispatched = 0
        self.prefill_rows_useful = 0
        # Of the rows dispatched, those whose expert layers ran the
        # grouped product (ops/moe.py held_experts_grouped).
        self.prefill_rows_grouped_experts = 0
        # Keys a prefill dispatch's attention gathered and streamed a
        # layer, summed over its table rows, beside what whole tables
        # would have been (ops/paged_attention.py prefill_keys_read).
        self.prefill_keys_read_total = 0
        self.prefill_keys_table_total = 0
        # The decode program's expert layers, over the blocks whose
        # readback has landed: expert layers × steps with a live lane,
        # and the held experts a live lane chose there — the part of the
        # held experts' read that was asked for (on_held_experts; in the
        # snapshot once a block of a model with expert layers is in).
        self.held_expert_calls = 0
        self.held_experts_hit = 0
        # The sampled decode program's steps with a live lane, over the
        # blocks whose readback has landed, and those of them on which
        # the exact sampler sorted the whole vocabulary — a live row's
        # top_k or nucleus went past its sorted head (on_sampler_steps;
        # in the snapshot once a sampled block is in).
        self.sampler_steps_total = 0
        self.sampler_full_sort_steps_total = 0
        # A looped stack's decode program (ModelConfig.loop_steps > 1; in
        # the snapshot once such a block is dispatched): layer
        # applications, loop_steps × num_layers a dispatched step
        # (on_dispatch, host-known), and the live lane-steps by the pass
        # the exit rule chose, over the blocks whose readback has landed
        # (on_loop_exits; counted on the device, engine._decode_fn).
        self.loop_layer_passes = 0
        self.loop_exits_by_step: list = []
        # Prefill dispatches whose first tokens were read, and for each
        # the seconds since the engine last looked at it and found it
        # unfinished (or since its dispatch call returned): an upper
        # bound, with no capture, on how long finished first tokens lay
        # unread (on_first_tokens_read).
        self.first_token_poll_gap_seconds = 0.0
        self.first_token_poll_gap_count = 0
        # Windows (real rows) among those dispatches, and prompts whose
        # cover put more than one window into a dispatch
        # (engine.prefill_cover).
        self.prefill_windows_dispatched = 0
        self.prefill_prompts_split = 0
        # Per-slot recurrent state of a stateful model (prefill rows by
        # where their state started: kv_cache.SlotState's rules).
        self.state_slots_reset = 0
        self.state_windows_chained = 0
        self.state_chunks_resumed = 0
        # Deepest in-flight target any dispatch ran with (on_dispatch).
        self.depth_target_max = 0
        # Lookahead pipeline accounting (ISSUE 6): per processed block,
        # the OBSERVED lookahead (blocks dispatched after it, before its
        # readback — ≥1 means the dispatch frontier ran ahead of the
        # processed frontier; 0 is the synchronous depth-1 shape) and the
        # host stall (ms the processed frontier blocked waiting for the
        # block's D2H copy — ~0 when the pipeline hid the roundtrip).
        # The stall histogram renders as polykey_host_stall_ms_bucket.
        self.blocks_processed = 0
        # Blocks that actually performed a readback — dead blocks (every
        # occupant gone, sync skipped) count in blocks_processed but not
        # here, so stall means divide by the reads that happened.
        self.blocks_synced = 0
        self.lookahead_sum = 0
        self.lookahead_max = 0
        self.host_stall_ms_total = 0.0
        self.host_stall_hist = Histogram()
        # Dispatch cadence: host-side gap between consecutive block
        # dispatches. At depth 1 the gap is bounded below by the block's
        # device time plus the readback (the host sits between blocks);
        # with lookahead it shrinks toward pure host scheduling work —
        # bench's `dispatch_gap_ms` is the windowed mean of this.
        self.dispatch_gap_ms_total = 0.0
        self.dispatch_gaps = 0
        self._last_dispatch_t = 0.0
        # Device-time attribution (ISSUE 10): total device-busy ms
        # charged across blocks (gap − stall, clamped ≥ 0) and the
        # per-request distribution of that charge. busy/gap is the
        # polykey_device_busy_fraction gauge — the "how device-bound is
        # steady state" dial, from the recorded schedule.
        self.device_busy_ms_total = 0.0
        self.device_ms_hist = Histogram()
        # Host-memory KV tier (ISSUE 15): page-fault counters by kind
        # ("prefix" = a sticky short-prompt session resuming off spilled
        # pages, "ctx" = a long-context prompt's middle pages paging
        # back for chunked prefill), spill/restore page counters, and
        # the restore-latency histogram (gather of host contents +
        # upload + scatter dispatch — the cost a faulting lane pays that
        # a resident lane must never share).
        self.kv_page_faults = {"prefix": 0, "ctx": 0}
        self.kv_pages_evicted = 0
        self.kv_pages_restored = 0
        self.kv_restore_hist = Histogram()
        # SLO signal plane (ISSUE 11): attached by the engine when
        # signals are enabled (obs.signals.SignalPlane), None otherwise.
        # It lives HERE — not on the engine — because the supervisor's
        # metrics-adoption path already carries this object to the fresh
        # engine on restart, which is exactly the continuity the
        # windowed ring and the SLO budget accounting need.
        self.signals = None

    def on_process_block(self, lookahead: int,
                         stall_ms: Optional[float],
                         trace_id: Optional[str] = None) -> None:
        """One in-flight block processed with `lookahead` newer blocks
        already dispatched; `stall_ms` is the blocking-readback wall time
        (None for dead blocks whose sync was skipped entirely).
        `trace_id` exemplars the stall bucket with a request that was
        live in the block."""
        with self._lock:
            self.blocks_processed += 1
            self.lookahead_sum += lookahead
            if lookahead > self.lookahead_max:
                self.lookahead_max = lookahead
            if stall_ms is not None:
                self.blocks_synced += 1
                self.host_stall_ms_total += stall_ms
        if stall_ms is not None:
            self.host_stall_hist.observe(stall_ms, trace_id=trace_id)

    def on_device_busy(self, busy_ms: float) -> None:
        """Device-busy ms attributed to one processed block."""
        with self._lock:
            self.device_busy_ms_total += busy_ms

    def on_dispatch_idle(self) -> None:
        """The engine went idle (no live lanes, nothing in flight): reset
        the dispatch-gap clock so the FIRST block of the next request is
        not charged the idle wait as device-busy time. Without this, a
        low-QPS engine (one request every few seconds) reports seconds
        of device_ms for sub-second requests — the gap-tiles-the-device
        assumption only holds while dispatches are back to back."""
        with self._lock:
            self._last_dispatch_t = 0.0

    def on_prefill_interleave(self, tokens: int, decode_live: bool) -> None:
        """Prefill tokens dispatched in one engine-loop iteration;
        `decode_live` marks iterations where decode lanes were active at
        admission time (only those can stall a running stream)."""
        if tokens <= 0:
            return
        with self._lock:
            self.prefill_tokens_total += tokens
            if decode_live and tokens > self.interleave_max_tokens:
                self.interleave_max_tokens = tokens

    def on_padding_tokens(self, dispatched: int, useful: int) -> None:
        """Token rows computed vs useful for one prefill dispatch
        (bucketed group / chunk) — the padding-waste
        counters the occupancy soak diffs."""
        with self._lock:
            self.tokens_dispatched_total += dispatched
            self.tokens_useful_total += useful

    def on_prefill_rows(self, dispatched: int, useful: int,
                        windows: int, split: int,
                        grouped_experts: int = 0,
                        keys_read: int = 0, keys_table: int = 0) -> None:
        """One bucketed-group or chunk prefill dispatch: `dispatched`
        rows computed (n_pad x bucket, or the chunk width) for `useful`
        real prompt tokens in `windows` real rows, `split` of its
        prompts covered by more than one of them; `grouped_experts`:
        `dispatched` again where the model's expert layers ran them as
        the grouped product, else 0; `keys_read` of the `keys_table`
        positions its rows' page tables span were gathered for the
        attention. Feeds the prefill-only counters and, as before, the
        mixed padding-waste pair."""
        with self._lock:
            self.prefill_rows_dispatched += dispatched
            self.prefill_rows_useful += useful
            self.prefill_rows_grouped_experts += grouped_experts
            self.prefill_keys_read_total += keys_read
            self.prefill_keys_table_total += keys_table
            self.prefill_windows_dispatched += windows
            self.prefill_prompts_split += split
            self.tokens_dispatched_total += dispatched
            self.tokens_useful_total += useful

    def on_held_experts(self, calls: int, hit: int) -> None:
        """One decode block of a layer pattern with expert layers has
        landed: `calls` = its expert layers × its steps with a live lane,
        `hit` = the held experts a live lane chose, summed over those
        calls (counted on the device, engine._decode_fn)."""
        with self._lock:
            self.held_expert_calls += calls
            self.held_experts_hit += hit

    def on_loop_exits(self, exits) -> None:
        """One decode block of a looped stack has landed: `exits[u]` =
        its live lane-steps whose exit rule chose pass u (counted on the
        device, engine._decode_fn)."""
        with self._lock:
            if not self.loop_exits_by_step:
                self.loop_exits_by_step = [0] * len(exits)
            for u, n in enumerate(exits):
                self.loop_exits_by_step[u] += int(n)

    def on_sampler_steps(self, steps: int, full_sorts: int) -> None:
        """One decode block of the sampled variant has landed: `steps` =
        its steps with a live lane, `full_sorts` = those on which the
        sampler sorted the whole vocabulary (counted on the device,
        engine._decode_fn; sampling._trunc_thresholds says when)."""
        with self._lock:
            self.sampler_steps_total += steps
            self.sampler_full_sort_steps_total += full_sorts

    def on_state_rows(self, reset: int, chained: int, resumed: int) -> None:
        """One prefill dispatch of a stateful model: real rows that
        started from zero state (an admission: the slot is reset), from
        the row above (a prompt's next window in the dispatch), from the
        slot's stored state (a long prompt's next chunk)."""
        with self._lock:
            self.state_slots_reset += reset
            self.state_windows_chained += chained
            self.state_chunks_resumed += resumed

    def on_phase(self, name: str, seconds: float) -> None:
        """One engine phase ended (obs.timeline.phase). A name outside
        obs.timeline.PHASES is a KeyError: the table is the contract."""
        self.phase_seconds[name] += seconds
        self.phase_count[name] += 1

    def adopt_startup(self, built: "EngineMetrics") -> None:
        """A supervised restart hands this object to the engine it just
        built: the start-up phases of that construction, which `built`
        counted, come along, so `phase_seconds{warmup}` grows by every
        restart's warm-up and not the first start's alone."""
        for name in STARTUP_PHASES:
            self.phase_seconds[name] += built.phase_seconds[name]
            self.phase_count[name] += built.phase_count[name]

    def on_first_token(self, timings: RequestTimings) -> None:
        """A request's first token resolved: file its three TTFT phases,
        and its queue time by cause — `loop` is what the deferring
        visits do not cover, so the causes add up to `queue` exactly."""
        queue, wait, first = timings.ttft_phases_s()
        deferred = timings.queue_deferred
        acc = self.ttft_phase_seconds
        by_cause = self.ttft_queue_seconds
        with self._lock:
            acc["queue"] += queue
            acc["prefill_wait"] += wait
            acc["first_token"] += first
            self.ttft_phase_count += 1
            for reason, seconds in deferred.items():
                by_cause[reason] += seconds
            by_cause["loop"] += queue - sum(deferred.values())

    def on_first_tokens_read(self, poll_gap_s: float) -> None:
        """One prefill dispatch's first tokens are about to be read,
        `poll_gap_s` after the engine last found them unfinished."""
        with self._lock:
            self.first_token_poll_gap_seconds += poll_gap_s
            self.first_token_poll_gap_count += 1

    def on_admit_deferred(self, reason: str) -> None:
        self.admit_deferred[reason] += 1

    def on_lane_steps(self, delivered: int, live: int, slots: int,
                      steps: int) -> None:
        """One processed block of `slots` x `steps` lane-steps, `live`
        lanes live at dispatch, `delivered` tokens put on request
        queues from it. What the live lanes did not deliver is
        overshoot: steps after a stream's end (the first -1 of its
        column, a finish mid-block, a whole lookahead block of a stream
        that had already ended, a dead block skipped unread) or of a
        cancelled or expired lane. A block that does not say its steps
        (0) is not counted at all."""
        if steps <= 0:
            return
        with self._lock:
            self.decode_lane_steps_delivered += delivered
            self.decode_lane_steps_overshoot += live * steps - delivered
            self.decode_lane_steps_dead += (slots - live) * steps

    def on_dispatch(self, lanes: int, steps: int,
                    slots: int = 0, depth: int = 0,
                    layer_passes: int = 0) -> float:
        """One decode block (or spec round) dispatched with `lanes` live
        decode lanes for `steps` device steps. Returns the counted
        dispatch gap in ms (0.0 for the first dispatch or an idle-capped
        gap) — the attribution window the engine charges to the block.
        `slots` (the static batch width) feeds the padding-waste
        counters: the device computes slots×steps token rows of which
        lanes×steps are useful. `depth` is the in-flight target the
        dispatch ran with (its running maximum is kept). `layer_passes`:
        a looped stack's layer applications a step (loop_steps ×
        num_layers; 0 for a stack of one pass)."""
        now = time.monotonic()
        counted_gap = 0.0
        with self._lock:
            self.loop_layer_passes += layer_passes * steps
            if depth > self.depth_target_max:
                self.depth_target_max = depth
            if slots > 0:
                self.tokens_dispatched_total += slots * steps
                self.tokens_useful_total += lanes * steps
            if self._last_dispatch_t:
                gap_ms = (now - self._last_dispatch_t) * 1e3
                # Idle gaps (no active lanes → no dispatch) are load
                # shape, not scheduling cost; cap what one gap can
                # contribute so the windowed mean reads cadence.
                if gap_ms < 10_000.0:
                    self.dispatch_gap_ms_total += gap_ms
                    self.dispatch_gaps += 1
                    counted_gap = gap_ms
            self._last_dispatch_t = now
            self.blocks_dispatched += 1
            self.lanes_dispatched += lanes
            self.lane_steps += lanes * steps
            self.steps_dispatched += steps
            self._lanes_ewma = (
                float(lanes) if self.blocks_dispatched == 1
                else 0.9 * self._lanes_ewma + 0.1 * lanes
            )
        self.lanes_hist.observe(float(lanes))
        return counted_gap

    def counter_sample(self) -> dict:
        """Every monotone counter in ONE locked read — the signal
        plane's ring entry (obs.signals). Raw values only: rates,
        availability, and delta-quantiles are computed read-side from
        two samples, so this stays cheap enough for a 5 s cadence (and
        a 50 ms test cadence) on the engine thread."""
        with self._lock:
            return {
                "requests_admitted": self.requests_admitted,
                "requests_completed": self.requests_completed,
                "requests_failed": self.requests_failed,
                "requests_shed": self.requests_shed,
                "deadline_expired_queued": self.deadline_expired["queued"],
                "deadline_expired_prefill": self.deadline_expired["prefill"],
                "deadline_expired_decode": self.deadline_expired["decode"],
                "tokens_generated": self.tokens_generated,
                "decode_steps": self.decode_steps,
                "blocks_dispatched": self.blocks_dispatched,
                "lanes_dispatched": self.lanes_dispatched,
                "lane_steps": self.lane_steps,
                "steps_dispatched": self.steps_dispatched,
                "prefill_tokens_total": self.prefill_tokens_total,
                "tokens_dispatched_total": self.tokens_dispatched_total,
                "tokens_useful_total": self.tokens_useful_total,
                "blocks_processed": self.blocks_processed,
                "blocks_synced": self.blocks_synced,
                "lookahead_sum": self.lookahead_sum,
                "host_stall_ms_total": self.host_stall_ms_total,
                "dispatch_gap_ms_total": self.dispatch_gap_ms_total,
                "dispatch_gaps": self.dispatch_gaps,
                "device_busy_ms_total": self.device_busy_ms_total,
                "drafts_accepted": self.drafts_accepted,
                "drafts_proposed": self.drafts_proposed,
                "kv_page_faults_prefix": self.kv_page_faults["prefix"],
                "kv_page_faults_ctx": self.kv_page_faults["ctx"],
                "kv_pages_evicted": self.kv_pages_evicted,
                "kv_pages_restored": self.kv_pages_restored,
            }

    def lanes_snapshot(self) -> dict:
        """Occupancy counters alone — cheap enough for harnesses to poll
        around a measurement window and diff (occupancy_soak, bench)."""
        with self._lock:
            return {
                "blocks_dispatched": self.blocks_dispatched,
                "lanes_dispatched": self.lanes_dispatched,
                "lane_steps": self.lane_steps,
                "steps_dispatched": self.steps_dispatched,
                "avg_lanes": (
                    round(self.lane_steps / self.steps_dispatched, 2)
                    if self.steps_dispatched else None
                ),
                "lanes_ewma": round(self._lanes_ewma, 2),
                # Pipeline counters for windowed diffs (bench step_costs,
                # occupancy soak): host stall + dispatch cadence.
                "blocks_processed": self.blocks_processed,
                "blocks_synced": self.blocks_synced,
                "lookahead_sum": self.lookahead_sum,
                "host_stall_ms_total": self.host_stall_ms_total,
                "dispatch_gap_ms_total": self.dispatch_gap_ms_total,
                "dispatch_gaps": self.dispatch_gaps,
                "device_busy_ms_total": self.device_busy_ms_total,
                # Padding-waste counters (ISSUE 12): harnesses diff these
                # over a window; useful/dispatched is the waste ratio.
                "tokens_dispatched_total": self.tokens_dispatched_total,
                "tokens_useful_total": self.tokens_useful_total,
            }

    def on_kv_fault(self, kind: str, pages: int) -> None:
        """`pages` host-resident pages faulted for one admission
        (restored before its suffix may prefill)."""
        with self._lock:
            self.kv_page_faults[kind] += pages

    def on_kv_evict(self, pages: int) -> None:
        with self._lock:
            self.kv_pages_evicted += pages

    def on_kv_restore(self, pages: int, ms: float,
                      trace_id: Optional[str] = None) -> None:
        with self._lock:
            self.kv_pages_restored += pages
        self.kv_restore_hist.observe(ms, trace_id=trace_id)

    def on_admit(self) -> None:
        with self._lock:
            self.requests_admitted += 1

    def on_shed(self) -> None:
        with self._lock:
            self.requests_shed += 1

    def on_deadline_expired(self, phase: str) -> None:
        with self._lock:
            self.deadline_expired[phase] += 1

    def service_time_ewma_s(self) -> float:
        with self._lock:
            return self._service_ewma_s

    def on_step(self, num_tokens: int) -> None:
        with self._lock:
            self.decode_steps += 1
            self.tokens_generated += num_tokens
            self._window_tokens += num_tokens
            now = time.monotonic()
            elapsed = now - self._window_start
            if elapsed >= 1.0:
                self.tokens_per_sec = self._window_tokens / elapsed
                self._window_start = now
                self._window_tokens = 0

    def on_itl(self, gap_ms: float, count: int = 1,
               trace_id: Optional[str] = None) -> None:
        """Record `count` tokens delivered with a per-token gap of
        `gap_ms` (one decode block's inter-emit window amortized over its
        tokens). Per-BLOCK measurement, not per-request mean: a 2 s stall
        between blocks lands in the histogram as 2 s-scale gaps for that
        block's tokens instead of vanishing into a request average."""
        if gap_ms > 0:
            self.itl_hist.observe(gap_ms, count, trace_id=trace_id)

    def on_spec(self, accepted: int, proposed: int) -> None:
        """Per-round speculative counters; acceptance rate is the speedup
        dial (engine._spec_step counts emitted tokens only — ADVICE r1)."""
        with self._lock:
            self.drafts_accepted += accepted
            self.drafts_proposed += proposed

    def on_finish(self, timings: RequestTimings, failed: bool = False,
                  trace_id: Optional[str] = None) -> None:
        ttft = timings.ttft_ms
        with self._lock:
            if failed:
                self.requests_failed += 1
            else:
                self.requests_completed += 1
                if timings.finished and timings.prefill_start:
                    dur = timings.finished - timings.prefill_start
                    if dur > 0:
                        self._service_ewma_s = (
                            dur if self._service_ewma_s == 0.0
                            else 0.8 * self._service_ewma_s + 0.2 * dur
                        )
        if ttft > 0:
            self.ttft_hist.observe(ttft, trace_id=trace_id)
        if timings.device_ms > 0:
            self.device_ms_hist.observe(timings.device_ms,
                                        trace_id=trace_id)

    def snapshot(self) -> dict:
        with self._lock:
            # The throughput window only advances inside on_step, so on an
            # idle engine the last busy window's rate would be reported
            # forever (now also scraped as polykey_tokens_per_sec —
            # phantom throughput on dashboards). Under traffic on_step
            # flushes the window at ~1s intervals; a window start more
            # than 5s old means the step loop has gone idle — decay the
            # gauge (any unflushed remainder tokens are equally stale).
            if (
                self.tokens_per_sec > 0.0
                and time.monotonic() - self._window_start > 5.0
            ):
                self.tokens_per_sec = 0.0
                # Restart the window clean or the first flush after idle
                # would average the new burst over the whole idle gap and
                # report ~0 while decoding at full speed.
                self._window_start = time.monotonic()
                self._window_tokens = 0
            snap = {
                # Host-KV tier (ISSUE 15): always present (0 with the
                # tier off) so collectors index them unconditionally.
                "kv_page_faults_prefix": self.kv_page_faults["prefix"],
                "kv_page_faults_ctx": self.kv_page_faults["ctx"],
                "kv_pages_evicted": self.kv_pages_evicted,
                "kv_pages_restored": self.kv_pages_restored,
                "requests_admitted": self.requests_admitted,
                "requests_completed": self.requests_completed,
                "requests_failed": self.requests_failed,
                "requests_shed": self.requests_shed,
                "deadline_expired_queued": self.deadline_expired["queued"],
                "deadline_expired_prefill": self.deadline_expired["prefill"],
                "deadline_expired_decode": self.deadline_expired["decode"],
                "tokens_generated": self.tokens_generated,
                "decode_steps": self.decode_steps,
                "tokens_per_sec": round(self.tokens_per_sec, 2),
                "blocks_dispatched": self.blocks_dispatched,
                "lane_steps": self.lane_steps,
                "steps_dispatched": self.steps_dispatched,
                "lanes_ewma": round(self._lanes_ewma, 2),
                "prefill_tokens_total": self.prefill_tokens_total,
                "interleave_max_tokens": self.interleave_max_tokens,
                "tokens_dispatched": self.tokens_dispatched_total,
                "tokens_useful": self.tokens_useful_total,
                # Fraction of computed token rows that were useful work
                # (1 − padding waste).
                "tokens_useful_fraction": (
                    round(self.tokens_useful_total
                          / self.tokens_dispatched_total, 4)
                    if self.tokens_dispatched_total else None
                ),
                # ISSUE 26: engine phases, request phases, admission
                # deferrals, lane-step outcomes (see __init__).
                "phase_seconds": {
                    k: round(v, 6) for k, v in self.phase_seconds.items()
                },
                "phase_count": dict(self.phase_count),
                "ttft_phase_seconds": {
                    k: round(v, 6)
                    for k, v in self.ttft_phase_seconds.items()
                },
                "ttft_phase_count": self.ttft_phase_count,
                "ttft_queue_seconds": {
                    k: round(v, 6)
                    for k, v in self.ttft_queue_seconds.items()
                },
                "admit_deferred": dict(self.admit_deferred),
                "decode_lane_steps_delivered":
                    self.decode_lane_steps_delivered,
                "decode_lane_steps_overshoot":
                    self.decode_lane_steps_overshoot,
                "decode_lane_steps_dead": self.decode_lane_steps_dead,
                "prefill_rows_dispatched": self.prefill_rows_dispatched,
                "prefill_rows_useful": self.prefill_rows_useful,
                "prefill_rows_grouped_experts":
                    self.prefill_rows_grouped_experts,
                "prefill_keys_read_total": self.prefill_keys_read_total,
                "prefill_keys_table_total": self.prefill_keys_table_total,
                "first_token_poll_gap_seconds":
                    round(self.first_token_poll_gap_seconds, 6),
                "first_token_poll_gap_count":
                    self.first_token_poll_gap_count,
                "prefill_windows_dispatched":
                    self.prefill_windows_dispatched,
                "prefill_prompts_split": self.prefill_prompts_split,
                "state_slots_reset": self.state_slots_reset,
                "state_windows_chained": self.state_windows_chained,
                "state_chunks_resumed": self.state_chunks_resumed,
                "blocks_processed": self.blocks_processed,
                "lookahead_observed_max": self.lookahead_max,
                "lookahead_observed_mean": (
                    round(self.lookahead_sum / self.blocks_processed, 2)
                    if self.blocks_processed else 0.0
                ),
                "host_stall_ms_total": round(self.host_stall_ms_total, 2),
                "device_busy_ms_total": round(self.device_busy_ms_total, 2),
                # Cumulative device-busy fraction of inter-dispatch wall
                # time — the attribution-side mirror of bench's windowed
                # overlap_ratio, always in [0, 1] (busy = gap − stall).
                "device_busy_fraction": (
                    round(self.device_busy_ms_total
                          / self.dispatch_gap_ms_total, 4)
                    if self.dispatch_gap_ms_total else 0.0
                ),
            }
            if self.steps_dispatched:
                # Step-weighted measured occupancy — the number roofline
                # grading consumes (avg_lanes_source: "measured").
                snap["avg_lanes"] = round(
                    self.lane_steps / self.steps_dispatched, 2
                )
            drafts_proposed = self.drafts_proposed
            drafts_accepted = self.drafts_accepted
            if self.held_expert_calls:
                snap["held_expert_calls"] = self.held_expert_calls
                snap["held_experts_hit"] = self.held_experts_hit
            if self.loop_layer_passes:
                snap["loop_layer_passes"] = self.loop_layer_passes
                snap["loop_exits_by_step"] = list(self.loop_exits_by_step)
            if self.sampler_steps_total:
                snap["sampler_steps_total"] = self.sampler_steps_total
                snap["sampler_full_sort_steps_total"] = (
                    self.sampler_full_sort_steps_total
                )
        if self.ttft_hist.count:
            # TTFT tail percentiles — TTFT is half the north-star metric
            # and its tail, not its mean, is what operators chase. These
            # are SINCE-START percentiles (the old p50_ttft_ms/p95_ttft_ms
            # keys over a recent-512 ring are gone — recency belongs to
            # the scraper via rate() over the exported buckets, not to a
            # second windowing scheme in-process).
            p50, p95, p99 = self.ttft_hist.percentiles(50, 95, 99)
            snap["ttft_ms_p50"] = round(p50, 2)
            snap["ttft_ms_p95"] = round(p95, 2)
            snap["ttft_ms_p99"] = round(p99, 2)
        if self.itl_hist.count:
            p50, p95, p99 = self.itl_hist.percentiles(50, 95, 99)
            snap["itl_ms_p50"] = round(p50, 2)
            snap["itl_ms_p95"] = round(p95, 2)
            snap["itl_ms_p99"] = round(p99, 2)
        if self.host_stall_hist.count:
            # Host-stall tail: the "is decode host-bound?" dial — a p50
            # near roundtrip_ms means the lookahead pipeline is not
            # hiding the host (see DEPLOY.md runbook).
            p50, p95 = self.host_stall_hist.percentiles(50, 95)
            snap["host_stall_ms_p50"] = round(p50, 2)
            snap["host_stall_ms_p95"] = round(p95, 2)
        if self.kv_restore_hist.count:
            p50, p95 = self.kv_restore_hist.percentiles(50, 95)
            snap["kv_restore_ms_p50"] = round(p50, 2)
            snap["kv_restore_ms_p95"] = round(p95, 2)
        if drafts_proposed:
            snap["drafts_accepted"] = drafts_accepted
            snap["drafts_proposed"] = drafts_proposed
            snap["spec_acceptance"] = round(
                drafts_accepted / drafts_proposed, 3
            )
        return snap
