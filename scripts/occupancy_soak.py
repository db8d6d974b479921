"""Sustained-occupancy soak: Poisson arrivals against the 48-slot config.

BASELINE.md's lane arithmetic makes occupancy a PRECONDITION of the
2,000 tok/s target (≥ ~20 live lanes at int8; the 8B bench requests 48
slots), yet until ISSUE 4 nothing demonstrated the scheduler *sustaining*
high occupancy — the best evidence was 7.13/8 lanes at 8 slots from a
closed-loop burst (a CPU repro script since removed). This harness is the
missing proof, shaped like production load instead of a burst:

- OPEN-loop Poisson arrivals (exponential inter-arrival gaps) at a rate
  calibrated to oversubscribe the engine (Little's law: lambda =
  oversub × slots / measured service time, from a calibration burst),
  so admissions never starve;
- mixed prompt lengths — short bucket, full bucket, and beyond-bucket
  prompts that exercise chunked prefill INTERLEAVED with decode under
  the token budget (`POLYKEY_PREFILL_BUDGET`);
- measurement from the engine's always-on occupancy tracker
  (metrics.lanes_snapshot() deltas over the soak window — the same
  counters roofline grading consumes as avg_lanes_source: "measured"),
  never from harness-side guesses. Client-side draining is deliberately
  absent: request timings live engine-side (EngineMetrics), and token
  queues buffer, so the harness cannot perturb the schedule it measures.

Writes a JSON artifact (default perf/occupancy_soak_<UTC date>.json) and
exits nonzero when measured occupancy misses --min-occupancy — which is
what `make occupancy-smoke` gates CI on at a smaller scale.

Run (the ISSUE 4 acceptance config):
  JAX_PLATFORMS=cpu python scripts/occupancy_soak.py \
      --slots 48 --duration 60 --min-occupancy 0.8
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def sched_witness_verdict():
    """Merged starvation-witness verdict for the artifact (schedlint
    SL006): when POLYKEY_SCHED_WITNESS armed the witness, dump this
    process's per-slot wait-age/skip summary now and merge every dump
    in the out directory. None when the witness is off — artifacts only
    carry evidence that was actually recorded."""
    from polykey_tpu.analysis import sched, schedwitness

    if not schedwitness.installed():
        return None
    path = schedwitness.dump()
    if path is None:
        return None
    return sched.witness_verdict(
        schedwitness.load_witness(os.path.dirname(path)))


def build_engine(args):
    from polykey_tpu.engine.config import EngineConfig
    from polykey_tpu.engine.engine import InferenceEngine

    cfg = EngineConfig(
        model=args.model,
        dtype="float32",
        kv_dtype=args.kv_dtype,
        max_decode_slots=args.slots,
        page_size=16,
        # Room for every slot at max_seq plus prefill slack — allocation
        # pressure would confound the occupancy measurement.
        num_pages=args.slots * (args.max_seq // 16) + 64,
        max_seq_len=args.max_seq,
        prefill_buckets=(32, 64),
        prefill_chunk=64,
        prefill_budget=args.prefill_budget,
        max_new_tokens_cap=args.max_new,
        decode_block_steps=args.block,
        lookahead_blocks=2,
        compile_warmup=False,
        # Open-loop load deliberately keeps a backlog; the soak must not
        # shed it (shedding would deflate the very queue that keeps
        # slots full). Deadline-less requests are never delay-shed.
        max_queue_depth=0,
        supervise=False,
    )
    return InferenceEngine(cfg)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slots", type=int, default=48)
    ap.add_argument("--duration", type=float, default=60.0,
                    help="measurement window seconds (after ramp)")
    ap.add_argument("--ramp", type=float, default=None,
                    help="seconds of Poisson load before the measurement "
                         "window opens (default: 2 x service time)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="arrivals/s; 0 -> auto-calibrate via a burst")
    ap.add_argument("--oversub", type=float, default=1.3,
                    help="auto-rate multiplier over slots/service_time")
    # Stream length sets the occupancy ceiling: a retiring lane idles
    # ~lookahead_blocks before the host even learns it finished, so a
    # lane's duty cycle is roughly lifetime/(lifetime + lookahead). 48
    # tokens ≈ 12 blocks at K=4 keeps turnover cost <10%; max_new 16
    # measures ~0.69 occupancy from turnover alone.
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--model", default="tiny-llama")
    ap.add_argument("--kv-dtype", default="")
    ap.add_argument("--prefill-budget", type=int, default=0)
    ap.add_argument("--long-frac", type=float, default=0.15,
                    help="fraction of prompts beyond the largest bucket "
                         "(chunked prefill path)")
    ap.add_argument("--min-occupancy", type=float, default=0.0,
                    help="exit 1 when measured avg_lanes/slots is below")
    ap.add_argument("--seed", type=int, default=29)
    ap.add_argument("--out", default="")
    ap.add_argument("--timeline", default="",
                    help="also export the engine's flight-deck timeline "
                         "as Perfetto JSON to this path (ISSUE 10: the "
                         "committed perf/timeline_*.json artifacts — "
                         "open at https://ui.perfetto.dev)")
    ap.add_argument("--host-kv", action="store_true",
                    help="host-memory KV tier soak (ISSUE 15): sticky "
                         "multi-turn sessions whose aggregate KV exceeds "
                         "the device pool, greedy streams gated "
                         "bit-identical to an all-device run, and a "
                         "supervised restart mid-soak that must recover "
                         "warm TTFT from the persisted prefix cache")
    ap.add_argument("--hk-sessions", type=int, default=12,
                    help="sticky sessions in --host-kv mode")
    ap.add_argument("--hk-turns", type=int, default=4,
                    help="turns per sticky session in --host-kv mode")
    ap.add_argument("--hk-base", type=int, default=96,
                    help="base history tokens per session (--host-kv)")
    ap.add_argument("--hk-turn-tokens", type=int, default=48,
                    help="history growth per turn (--host-kv)")
    ap.add_argument("--min-footprint", type=float, default=1.5,
                    help="gate: aggregate session KV / device pool must "
                         "reach this ratio in --host-kv mode")
    args = ap.parse_args()
    return run_main(args)


def run_main(args) -> int:
    if getattr(args, "host_kv", False):
        return run_hostkv_main(args)
    result = run_soak(args)
    failures = result["failed_in_window"]

    verdict = sched_witness_verdict()
    if verdict is not None:
        # The soak's fairness evidence rides the same artifact as its
        # occupancy numbers: per-frontier worst wait age / consecutive
        # skips vs the SL006 gates, merged across every process that
        # dumped into the witness dir.
        result["sched_witness"] = verdict

    out_path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perf",
        f"occupancy_soak_{time.strftime('%Y-%m-%d', time.gmtime())}.json",
    )
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    log(f"wrote {out_path}")
    print(json.dumps(result))

    if failures:
        log(f"FAIL: {failures} requests errored inside the window")
        return 1
    if args.min_occupancy and result["occupancy"] < args.min_occupancy:
        log(f"FAIL: occupancy {result['occupancy']:.3f} < "
            f"{args.min_occupancy}")
        return 1
    log(f"OK: {result['avg_lanes']:.2f}/{args.slots} lanes "
        f"(occupancy {result['occupancy']:.3f}, padding waste "
        f"{result['padding_waste']:.3f}) over {result['window_s']:.0f}s")
    return 0


def run_soak(args) -> dict:
    rng = np.random.default_rng(args.seed)

    def prompt() -> str:
        # Mixed lengths (in BYTE tokens ≈ chars): short bucket, full
        # bucket, and beyond-bucket prompts that chunk-prefill. Base-26
        # letters keep the byte tokenizer in its dense range.
        r = rng.random()
        if r < args.long_frac:
            n = int(rng.integers(96, 160))     # > 64-bucket -> chunked
        elif r < 0.55:
            n = int(rng.integers(8, 30))       # 32-bucket
        else:
            n = int(rng.integers(33, 62))      # 64-bucket
        return "".join(chr(c) for c in rng.integers(97, 123, n))

    from polykey_tpu.engine.engine import GenRequest

    engine = build_engine(args)
    try:
        def completed() -> int:
            return (engine.metrics.requests_completed
                    + engine.metrics.requests_failed)

        # --- calibration: two concurrent bursts. The first pays the XLA
        # compiles (bucket groups, both block sizes, merges) so it only
        # warms; the SECOND is timed — n_cal concurrent requests finish
        # in about one service time, giving capacity ≈ slots / svc
        # requests/s without compile contamination.
        def burst(n: int) -> float:
            base = completed()
            for _ in range(n):
                engine.submit(GenRequest(
                    prompt=prompt(), max_new_tokens=args.max_new))
            t0 = time.monotonic()
            while completed() < base + n:
                time.sleep(0.05)
                if time.monotonic() - t0 > 600:
                    raise RuntimeError("calibration burst never completed")
            return time.monotonic() - t0

        n_cal = max(4, args.slots // 2)
        burst(n_cal)                      # cold: compiles
        svc = max(0.05, burst(n_cal))     # warm: timed
        rate = args.rate or args.oversub * args.slots / svc
        feedback = not args.rate
        log(f"calibration: warm burst of {n_cal} in {svc:.2f}s -> "
            f"Poisson rate {rate:.1f}/s"
            f" ({'given' if args.rate else 'auto'}"
            f"{'+backlog-tracked' if feedback else ''})")

        ramp = args.ramp if args.ramp is not None else max(8.0, 2 * svc)
        window_open = time.monotonic() + ramp
        stop_at = window_open + args.duration
        snap0 = stats0 = None
        t_open = None
        arrivals = 0
        queued_min = None
        rate0 = rate
        # --- Poisson arrivals until the window closes. The rate tracks
        # a bounded backlog (2-4x slots) on a 0.5 s wall-clock tick:
        # arrivals stay an (inhomogeneous) Poisson process — each gap is
        # an exponential draw at the current rate, never a reaction to
        # any individual completion — while coarse load feedback keeps
        # the queue from either running dry (an underfed engine idles
        # lanes for lack of offered load, which would test the load
        # generator, not the scheduler) or growing without bound. The
        # artifact records initial/final rate and the minimum in-window
        # backlog so saturation is auditable.
        next_tick = time.monotonic()

        def tick(now: float) -> None:
            """Feedback tick, shared by the arrival loop and the
            inter-arrival sleep loop: sample the backlog for the
            in-window audit and nudge the rate toward the 2-4x-slots
            backlog band."""
            nonlocal next_tick, queued_min, rate
            if now < next_tick:
                return
            next_tick = now + 0.5
            q = engine.stats()["queued"]
            if snap0 is not None:
                queued_min = q if queued_min is None else min(queued_min, q)
            if feedback:
                if q < 2 * args.slots:
                    rate *= 1.15
                elif q > 4 * args.slots:
                    rate *= 0.9

        while True:
            now = time.monotonic()
            if now >= stop_at:
                break
            if snap0 is None and now >= window_open:
                snap0 = engine.metrics.lanes_snapshot()
                stats0 = engine.stats()
                t_open = now
            tick(now)
            # Exponential inter-arrival gap at the current rate, slept
            # in <=0.2 s slices so feedback ticks stay on schedule.
            deadline = now + float(rng.exponential(1.0 / rate))
            while True:
                now = time.monotonic()
                if now >= deadline or now >= stop_at:
                    break
                tick(now)
                time.sleep(min(0.2, max(0.0, deadline - now)))
            if time.monotonic() >= stop_at:
                break
            engine.submit(GenRequest(
                prompt=prompt(), max_new_tokens=args.max_new))
            arrivals += 1
        if snap0 is None:       # degenerate: duration shorter than ramp
            snap0 = engine.metrics.lanes_snapshot()
            stats0 = engine.stats()
            t_open = time.monotonic()
        snap1 = engine.metrics.lanes_snapshot()
        stats1 = engine.stats()
        window_s = time.monotonic() - t_open

        blocks = snap1["blocks_dispatched"] - snap0["blocks_dispatched"]
        steps = snap1["steps_dispatched"] - snap0["steps_dispatched"]
        lane_steps = snap1["lane_steps"] - snap0["lane_steps"]
        avg_lanes = lane_steps / steps if steps else 0.0
        occupancy = avg_lanes / args.slots
        tokens = stats1["tokens_generated"] - stats0["tokens_generated"]

        tokens_dispatched = (snap1["tokens_dispatched_total"]
                             - snap0["tokens_dispatched_total"])
        tokens_useful = (snap1["tokens_useful_total"]
                         - snap0["tokens_useful_total"])

        result = {
            "config": {
                "slots": args.slots, "model": args.model,
                "kv_dtype": args.kv_dtype or "fp",
                "max_new": args.max_new, "block_steps": args.block,
                "prefill_budget": stats1["prefill_budget"],
                "long_prompt_frac": args.long_frac,
                "rate_initial_per_s": round(rate0, 2),
                "rate_final_per_s": round(rate, 2),
                "rate_source": (
                    "given"
                    if args.rate else "auto-calibrated+backlog-tracked"),
                "warm_burst_s": round(svc, 3),
                "ramp_s": round(ramp, 1),
                "seed": args.seed,
            },
            "window_s": round(window_s, 1),
            "arrivals": arrivals,
            "completed_in_window": (stats1["requests_completed"]
                                    - stats0["requests_completed"]),
            "failed_in_window": (stats1["requests_failed"]
                                 - stats0["requests_failed"]),
            "queued_at_close": stats1["queued"],
            "queued_min_in_window": queued_min,
            "requests_shed": stats1["requests_shed"],
            "blocks_dispatched": blocks,
            "steps_dispatched": steps,
            "lane_steps": lane_steps,
            "avg_lanes": round(avg_lanes, 2),
            "occupancy": round(occupancy, 4),
            "avg_lanes_source": "measured",
            # Lookahead-pipeline host accounting over the same window
            # (ISSUE 6): mean time the processed frontier blocked per
            # readback, and mean observed lookahead (blocks dispatched
            # ahead of each readback) — host-stall alongside lanes, so
            # a soak that holds occupancy but pays the host tax is
            # visible from the artifact alone.
            "host_stall_ms_mean": round(
                (snap1["host_stall_ms_total"] - snap0["host_stall_ms_total"])
                / max(1, snap1["blocks_synced"]
                      - snap0["blocks_synced"]), 3),
            "lookahead_observed_mean": round(
                (snap1["lookahead_sum"] - snap0["lookahead_sum"])
                / max(1, snap1["blocks_processed"]
                      - snap0["blocks_processed"]), 2),
            "host_stall_ms_p50": stats1.get("host_stall_ms_p50"),
            "lookahead_depth": stats1["lookahead_depth"],
            # Device-time attribution over the same window (ISSUE 10):
            # the device-busy share of inter-dispatch wall time — the
            # soak-side twin of bench's overlap_ratio, from the recorded
            # schedule rather than a separate probe.
            "device_busy_fraction": round(
                (snap1["device_busy_ms_total"]
                 - snap0["device_busy_ms_total"])
                / max(1e-9, snap1["dispatch_gap_ms_total"]
                      - snap0["dispatch_gap_ms_total"]), 4),
            # Mean host-side gap between consecutive dispatches over the
            # window.
            "dispatch_gap_ms_mean": round(
                (snap1["dispatch_gap_ms_total"]
                 - snap0["dispatch_gap_ms_total"])
                / max(1, snap1["dispatch_gaps"]
                      - snap0["dispatch_gaps"]), 3),
            "tok_s": round(tokens / window_s, 1) if window_s else None,
            # Padding-waste accounting (ISSUE 12), first-class: token
            # rows the device computed vs rows that were useful work
            # over the window (decode dead lanes + bucket/pad-group
            # prefill padding). waste = 1 − useful/dispatched.
            "tokens_dispatched": tokens_dispatched,
            "tokens_useful": tokens_useful,
            "tokens_useful_fraction": round(
                tokens_useful / max(1, tokens_dispatched), 4),
            "padding_waste": round(
                1.0 - tokens_useful / max(1, tokens_dispatched), 4),
            "interleave_max_tokens": stats1["interleave_max_tokens"],
            # Lifetime TTFT percentiles (incl. ramp — queue wait under
            # deliberate oversubscription is the honest shape here).
            "ttft_ms_p50": stats1.get("ttft_ms_p50"),
            "ttft_ms_p95": stats1.get("ttft_ms_p95"),
            "platform": jax.devices()[0].platform,
            "measured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        if args.timeline and engine.timeline is not None:
            from polykey_tpu.obs.timeline import engine_timelines, to_perfetto

            trace = to_perfetto(
                engine_timelines(engine),
                meta={
                    "source": "occupancy_soak",
                    "slots": args.slots,
                    "lookahead_depth": stats1["lookahead_depth"],
                    "occupancy": result["occupancy"],
                    "device_busy_fraction": result["device_busy_fraction"],
                    "measured_at": result["measured_at"],
                },
            )
            with open(args.timeline, "w") as f:
                json.dump(trace, f, indent=1)
                f.write("\n")
            log(f"wrote timeline {args.timeline} "
                f"({len(trace['traceEvents'])} events)")

        return result
    finally:
        engine.shutdown()


# -- host-memory KV tier soak (ISSUE 15) --------------------------------------
#
# Shape: S sticky multi-turn sessions whose histories grow every turn,
# sized so the aggregate KV footprint exceeds the device pool by
# >= --min-footprint (1.5x by default). Cold histories spill to the
# host tier between turns (resident-floor eviction at retire) and fault
# back in on the next turn — the soak gates that EVERY greedy stream is
# bit-identical to an all-device reference run (huge pool, host tier
# off), that zero requests fail, and that a real EngineSupervisor
# restart mid-soak recovers warm TTFT from the durable prefix store
# (measured warm-vs-cold delta in the artifact).


def _hk_collect(request) -> tuple[list, object]:
    tokens = []
    while True:
        kind, value = request.out.get(timeout=300)
        if kind == "token":
            tokens.append(value)
        elif kind == "done":
            return tokens, value
        else:
            raise RuntimeError(f"request failed: {value}")


def _hk_prompt(session: int, turn: int, args) -> str:
    """Deterministic sticky-session history: a session-specific base
    plus one filler block per completed turn — turn t's prompt extends
    turn t-1's, which is exactly what keeps the prefix cache (and the
    host tier behind it) warm across turns."""
    rng = np.random.default_rng(1000 + session)
    base = "".join(chr(c) for c in rng.integers(97, 123, args.hk_base))
    blocks = []
    for t in range(turn):
        rng_t = np.random.default_rng(7000 + session * 131 + t)
        blocks.append("".join(
            chr(c) for c in rng_t.integers(97, 123, args.hk_turn_tokens)
        ))
    return base + "".join(blocks)


def _hk_run_turns(engine, jobs, max_new, concurrency=3):
    """Run (session, turn) jobs in bounded-concurrency waves; returns
    {job: tokens}. Greedy streams are batch-independent, so the wave
    shape cannot change any stream's content — only the schedule."""
    from polykey_tpu.engine.engine import GenRequest

    out = {}
    jobs = list(jobs)
    for lo in range(0, len(jobs), concurrency):
        wave = jobs[lo:lo + concurrency]
        requests = []
        for (s, t, prompt) in wave:
            r = GenRequest(prompt=prompt, max_new_tokens=max_new)
            engine.submit(r)
            requests.append(((s, t), r))
        for key, r in requests:
            tokens, _ = _hk_collect(r)
            out[key] = tokens
    return out


def run_hostkv_main(args) -> int:
    import dataclasses
    import shutil
    import tempfile

    from polykey_tpu.engine.config import EngineConfig
    from polykey_tpu.engine.engine import GenRequest, InferenceEngine
    from polykey_tpu.analysis import heapwitness
    from polykey_tpu.engine.roofline import (
        CHIP_SPECS,
        grade,
        kv_pool_bytes_spec,
    )
    from polykey_tpu.models.config import get_config as _model_config

    def _heap_checkpoint(label: str, engine) -> None:
        # Observed pool occupancy vs declared capacity rides every
        # heap sample, so `mem --witness` can catch the allocator
        # drifting past the ledger (ML006) — no-op unless
        # POLYKEY_HEAP_WITNESS armed the witness.
        if not heapwitness.installed():
            return
        st = engine.stats()
        heapwitness.checkpoint(label, pools={
            "device_kv_pages": {
                "used": st["kv_device_pages"],
                "capacity": engine.config.num_pages - 1,
            },
            "host_kv_pages": {
                "used": st["kv_host_pages"],
                "capacity": st["kv_host_capacity"],
            },
        })
    from polykey_tpu.engine.supervisor import EngineSupervisor

    page_size = 16
    max_new = 16
    S, T = args.hk_sessions, args.hk_turns
    final_len = args.hk_base + T * args.hk_turn_tokens
    pages_per_session = -(-(final_len + max_new) // page_size)
    aggregate_pages = S * pages_per_session
    # Device pool sized so the sticky working set OVERSUBSCRIBES it by
    # ~1.6x while a 3-wide turn wave still fits with slack.
    num_pages = max(
        int(aggregate_pages / 1.6) + 1, 3 * pages_per_session + 12,
    )
    footprint_ratio = aggregate_pages / (num_pages - 1)
    max_seq = -(-(final_len + max_new + page_size) // page_size) * page_size

    state_dir = tempfile.mkdtemp(prefix="polykey-hostkv-soak-")
    cfg = EngineConfig(
        model=args.model, dtype="float32", kv_dtype=args.kv_dtype,
        max_decode_slots=args.slots, page_size=page_size,
        num_pages=num_pages, max_seq_len=max_seq,
        prefill_buckets=(32, 64), prefill_chunk=64,
        max_new_tokens_cap=max_new, decode_block_steps=args.block,
        lookahead_blocks=2, compile_warmup=False, max_queue_depth=0,
        supervise=False,
        prefix_cache=True, prefix_cache_pages=8192,
        host_kv_bytes=256 << 20,
        host_kv_resident_pages=num_pages // 2,
        kv_state_dir=state_dir,
    )
    log(f"host-kv soak: {S} sessions x {T} turns, final history "
        f"{final_len} tok, aggregate {aggregate_pages} pages vs device "
        f"pool {num_pages - 1} (ratio {footprint_ratio:.2f}), state dir "
        f"{state_dir}")

    jobs_by_round = [
        [(s, t, _hk_prompt(s, t, args)) for s in range(S)]
        for t in range(1, T + 1)
    ]
    # Restart after this round; needs a round before AND after it —
    # with a single turn there is no "next turn" to measure warm TTFT
    # on, so the restart leg (and its gates) is skipped, loudly.
    restart_round = T // 2 if T >= 2 else None
    if restart_round is None:
        log("WARNING: --hk-turns < 2 — restart/warm-TTFT leg skipped "
            "(no post-restart turn exists to measure)")

    failures = 0
    t_start = time.monotonic()
    factory = lambda: InferenceEngine(cfg, seed=args.seed)  # noqa: E731
    engine = factory()
    sup = EngineSupervisor(
        engine, factory, max_restarts=3, check_interval_s=0.1,
    ).start()
    streams = {}
    warm_ttfts, cold_ttfts = [], []
    restart_recovery_s = None
    kv_reloaded = 0
    try:
        measured_round = None
        for round_idx, jobs in enumerate(jobs_by_round, start=1):
            if round_idx == measured_round:
                continue   # consumed by the post-restart measurement
            streams.update(_hk_run_turns(sup.engine, jobs, max_new))
            _heap_checkpoint(f"hostkv-round-{round_idx}", sup.engine)
            if round_idx == restart_round:
                # --- supervised restart mid-soak: quiesced crash (the
                # bare supervisor's recovery unit is the engine; the
                # PR 7 pool owns mid-stream resume) → fresh engine via
                # the factory → durable prefix reload → warm turns.
                log(f"injecting engine crash after round {round_idx} ...")
                old = sup.engine
                t_kill = time.monotonic()
                old.dead = "host-kv soak: injected crash"
                deadline = time.monotonic() + 120
                while sup.engine is old:
                    if time.monotonic() > deadline:
                        raise RuntimeError("supervisor never restarted")
                    time.sleep(0.05)
                restart_recovery_s = time.monotonic() - t_kill
                engine = sup.engine
                kv_reloaded = engine._kv_reloaded_pages
                log(f"restarted in {restart_recovery_s:.1f}s, reloaded "
                    f"{kv_reloaded} durable pages")
                # Throwaway pair absorbs post-restart compiles so the
                # measured warm/cold medians compare page-fault restore
                # vs cold recompute, not XLA compile time.
                for prompt in (_hk_prompt(S + 7, restart_round, args),
                               _hk_prompt(0, restart_round, args)):
                    r = GenRequest(prompt=prompt, max_new_tokens=max_new)
                    engine.submit(r)
                    _hk_collect(r)
                # Warm TTFT: the NEXT turn of each sticky session —
                # history pages fault back from the reloaded host tier
                # instead of recomputing. Sequential, so ttft ≈ prefill.
                measured_round = restart_round + 1
                next_jobs = jobs_by_round[restart_round]
                for (s, t, prompt) in next_jobs:
                    r = GenRequest(prompt=prompt, max_new_tokens=max_new)
                    engine.submit(r)
                    tokens, timings = _hk_collect(r)
                    streams[(s, t)] = tokens
                    warm_ttfts.append(timings.ttft_ms)
                # Cold TTFT: brand-new sessions of the same length.
                for c in range(len(next_jobs)):
                    r = GenRequest(
                        prompt=_hk_prompt(S + 100 + c, restart_round + 1,
                                          args),
                        max_new_tokens=max_new,
                    )
                    engine.submit(r)
                    _, timings = _hk_collect(r)
                    cold_ttfts.append(timings.ttft_ms)
                _heap_checkpoint("hostkv-post-restart", sup.engine)
        _heap_checkpoint("hostkv-final", sup.engine)
        stats = sup.engine.stats()
        hist = sup.engine.metrics.kv_restore_hist
        counts, hist_sum = hist.counts_snapshot()
    except RuntimeError as e:
        log(f"FAIL: {e}")
        failures += 1
        stats = sup.engine.stats()
        counts, hist_sum = [], 0.0
        hist = None
    finally:
        sup.stop()
        sup.engine.shutdown()

    # --- all-device reference: huge pool, host tier off, same prompts.
    log("=== all-device reference run ===")
    ref_cfg = dataclasses.replace(
        cfg, num_pages=aggregate_pages * 2 + 64, host_kv_bytes=0,
        host_kv_resident_pages=0, kv_state_dir="",
    )
    ref_engine = InferenceEngine(ref_cfg, seed=args.seed)
    try:
        ref_streams = {}
        for jobs in jobs_by_round:
            ref_streams.update(_hk_run_turns(ref_engine, jobs, max_new))
    finally:
        ref_engine.shutdown()
    shutil.rmtree(state_dir, ignore_errors=True)

    # The restart round's streams were re-measured on the fresh engine;
    # every (session, turn) key must match the uninterrupted reference.
    mismatched = sorted(
        key for key in ref_streams if streams.get(key) != ref_streams[key]
    )
    bit_identical = not mismatched and len(streams) >= len(ref_streams)

    warm_p50 = float(np.median(warm_ttfts)) if warm_ttfts else None
    cold_p50 = float(np.median(cold_ttfts)) if cold_ttfts else None
    faults = (stats["kv_page_faults_prefix"], stats["kv_page_faults_ctx"])
    # Projected capacity grade: hbm_weight_fraction against the v5e
    # spec sheet — what fraction of a real chip's HBM the weights would
    # pin, i.e. the budget this tier's host pages no longer compete for.
    roof = grade(
        model=args.model, dtype="float32", quantize=False, quantize_bits=8,
        kv_dtype=args.kv_dtype, tok_s=0.0, avg_lanes=None,
        avg_ctx=final_len, chip=CHIP_SPECS["tpu-v5e"],
        kv_pool_bytes=kv_pool_bytes_spec(
            _model_config(args.model), num_pages, page_size,
            args.kv_dtype or "float32",
        ),
    )
    # The north-star capacity statement: at llama-3-8b int8 on a 16 GiB
    # v5e, weights pin this fraction of HBM — the complement is the
    # device KV budget the host tier stops being the hard ceiling for.
    # kv_pool_bytes at the EngineConfig default geometry (2048 pages x
    # 16 tokens): the resident fraction the ML001 ledger re-derives.
    roof_8b = grade(
        model="llama-3-8b", dtype="bfloat16", quantize=True,
        quantize_bits=8, kv_dtype="int8", tok_s=0.0, avg_lanes=None,
        avg_ctx=4096, chip=CHIP_SPECS["tpu-v5e"],
        kv_pool_bytes=kv_pool_bytes_spec(
            _model_config("llama-3-8b"), 2048, 16, "int8",
        ),
    )
    result = {
        "mode": "host_kv",
        "config": {
            "model": args.model, "kv_dtype": args.kv_dtype or "fp",
            "slots": args.slots, "page_size": page_size,
            "num_pages": num_pages, "max_seq_len": max_seq,
            "sessions": S, "turns": T, "final_history_tokens": final_len,
            "host_kv_bytes": cfg.host_kv_bytes,
            "resident_floor_pages": cfg.host_kv_resident_pages,
            "seed": args.seed,
        },
        "window_s": round(time.monotonic() - t_start, 1),
        "aggregate_kv_pages": aggregate_pages,
        "device_pool_pages": num_pages - 1,
        "kv_footprint_ratio": round(footprint_ratio, 3),
        "requests": len(streams),
        "failed_rpcs": failures,
        "bit_identical_to_all_device": bit_identical,
        "mismatched_streams": mismatched[:8],
        "kv_page_faults": {"prefix": faults[0], "ctx": faults[1]},
        "kv_pages_evicted": stats["kv_pages_evicted"],
        "kv_pages_restored": stats["kv_pages_restored"],
        "kv_restore_ms_p50": stats.get("kv_restore_ms_p50"),
        "kv_restore_ms_p95": stats.get("kv_restore_ms_p95"),
        "cold_page_fault_hist": {
            "bounds": list(hist.bounds) if hist is not None else [],
            "counts": list(counts),
            "sum_ms": round(float(hist_sum), 3),
        },
        "restart": {
            "after_round": restart_round,
            "recovery_s": (round(restart_recovery_s, 2)
                           if restart_recovery_s else None),
            "kv_reloaded_pages": kv_reloaded,
            "warm_ttft_ms_p50": (round(warm_p50, 2)
                                 if warm_p50 is not None else None),
            "cold_ttft_ms_p50": (round(cold_p50, 2)
                                 if cold_p50 is not None else None),
            "warm_vs_cold_delta_ms": (
                round(cold_p50 - warm_p50, 2)
                if warm_p50 is not None and cold_p50 is not None else None
            ),
        },
        "roofline": {
            "chip": "tpu-v5e (projected; CPU run)",
            "hbm_weight_fraction": roof.get("hbm_weight_fraction"),
            "hbm_weight_fraction_8b_int8": roof_8b.get(
                "hbm_weight_fraction"),
        },
        "platform": jax.devices()[0].platform,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    verdict = sched_witness_verdict()
    if verdict is not None:
        result["sched_witness"] = verdict

    out_path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perf",
        f"hostkv_soak_{time.strftime('%Y-%m-%d', time.gmtime())}.json",
    )
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    log(f"wrote {out_path}")
    print(json.dumps(result))

    ok = True
    if failures:
        log(f"FAIL: {failures} requests errored")
        ok = False
    if not bit_identical:
        log(f"FAIL: {len(mismatched)} streams differ from the "
            f"all-device reference (first: {mismatched[:3]})")
        ok = False
    if footprint_ratio < args.min_footprint:
        log(f"FAIL: footprint ratio {footprint_ratio:.2f} < "
            f"{args.min_footprint}")
        ok = False
    if sum(faults) == 0 or stats["kv_pages_restored"] == 0:
        log("FAIL: the soak never faulted/restored a host page — the "
            "tier was not exercised")
        ok = False
    if restart_round is not None:
        if kv_reloaded == 0:
            log("FAIL: the restart reloaded nothing from the durable "
                "store")
            ok = False
        if warm_p50 is None or cold_p50 is None or warm_p50 >= cold_p50:
            log(f"FAIL: post-restart warm TTFT {warm_p50} ms did not "
                f"beat cold {cold_p50} ms")
            ok = False
    if ok:
        tail = (
            f"restart recovered warm TTFT {warm_p50:.0f} ms vs cold "
            f"{cold_p50:.0f} ms ({kv_reloaded} pages reloaded)"
            if restart_round is not None else "(restart leg skipped)"
        )
        log(f"OK: {len(streams)} sticky turns bit-identical at "
            f"{footprint_ratio:.2f}x device pool; {tail}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
