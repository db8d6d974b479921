"""Failover drill: kill one replica under open-loop load, lose nothing.

ISSUE 9's acceptance criterion in script form: with ≥2 replicas serving
Poisson traffic, injecting a fault that kills ONE replica mid-run
(`step-stall` targeted via ``:replica=K``, long enough to trip the
watchdog) must cost added latency only:

- **zero failed requests** — the pool re-routes the dead replica's
  queued work losslessly and resumes its in-flight streams on healthy
  replicas (greedy streams bit-identically; test_replica_pool pins the
  bit-identity itself, this drill pins it at load);
- **every stream is token-complete** — exactly max_new tokens arrive
  per request (greedy, no EOS on the hermetic byte tokenizer);
- **bounded p95 TTFT inflation** — post-kill p95 TTFT may exceed the
  pre-kill p95 by at most --max-p95-added-ms (the detection + reroute
  latency bound), not collapse into timeouts;
- **recovery to full capacity** — the killed replica's supervisor
  restarts it and the pool returns to all-replicas-SERVING.

Writes a JSON artifact and exits nonzero on any violated bound. CI runs
`make failover-smoke` (2 replicas / short window); the committed
acceptance artifact comes from `make failover-soak` (3 replicas).
"""

import argparse
import itertools
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def percentile(values: list, q: float) -> float:
    if not values:
        return 0.0
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def build_pool(args):
    from polykey_tpu.engine.config import EngineConfig
    from polykey_tpu.engine.replica_pool import ReplicaPool

    config = EngineConfig(
        model=args.model,
        dtype="float32",
        max_decode_slots=args.slots,
        page_size=8,
        num_pages=args.slots * (args.max_seq // 8) + 32,
        max_seq_len=args.max_seq,
        prefill_buckets=(16, 32),
        max_new_tokens_cap=args.max_new,
        default_max_new_tokens=args.max_new,
        decode_block_steps=2,
        adaptive_block=False,
        lookahead_blocks=2,
        # Pre-compile BEFORE the watchdogs arm: a cold first-dispatch
        # compile can exceed the test-scaled watchdog window and read as
        # a spurious stall (the pool would recover, but the drill must
        # attribute every reroute to ITS injected kill).
        compile_warmup=True,
        warm_sampled_variants=False,
        # Open-loop load keeps a backlog; shedding it would turn
        # deliberate oversubscription into "failed RPCs".
        max_queue_depth=0,
        watchdog_timeout_s=args.watchdog_timeout,
        supervise=True,
        max_engine_restarts=5,
        restart_window_s=600.0,
        replicas=args.replicas,
    )
    return ReplicaPool.create(
        config,
        watchdog_interval_s=0.1,
        supervisor_interval_s=0.1,
    )


def _disagg_config(args):
    from polykey_tpu.engine.config import EngineConfig

    return EngineConfig(
        model=args.model,
        dtype="float32",
        max_decode_slots=args.slots,
        page_size=8,
        num_pages=args.slots * (args.max_seq // 8) + 32,
        max_seq_len=args.max_seq,
        prefill_buckets=(16, 32),
        max_new_tokens_cap=args.max_new,
        default_max_new_tokens=args.max_new,
        decode_block_steps=2,
        adaptive_block=False,
        lookahead_blocks=2,
        compile_warmup=True,
        max_queue_depth=0,
        watchdog_timeout_s=300.0,
        supervise=True,
        max_engine_restarts=5,
        restart_window_s=600.0,
        disagg=f"{args.prefill}x{args.decode}",
        disagg_heartbeat_s=0.25,
        disagg_recovery_wait_s=60.0,
        max_reroutes=6,
    )


def _arm_worker(pool, tier: str, index: int, spec: str) -> bool:
    """Mid-run kill: install a POLYKEY_FAULTS spec inside ONE worker
    process over its control plane (the cross-process mirror of the
    replica drill's injector handoff)."""
    from polykey_tpu.engine.worker import WorkerConn

    for worker in pool.workers:
        if worker.tier == tier and worker.index == index:
            try:
                with WorkerConn(worker.addr, timeout=5.0) as conn:
                    reply, _ = conn.request(
                        {"op": "arm_faults", "spec": spec}, timeout=5.0
                    )
                return bool(reply.get("ok"))
            except (OSError, ConnectionError, ValueError):
                return False
    return False


def _protocol_gate() -> bool:
    """ISSUE 14: the CL005 protocol-conformance check runs BEFORE the
    drill spawns anything, so a coordinator/worker protocol drift fails
    in seconds on the chaos path instead of surfacing as a mysterious
    re-route storm twenty seconds in. Stdlib-only, so it costs nothing
    even inside the hermetic tester image."""
    from polykey_tpu.analysis import concurrency

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc = concurrency.main(["--root", repo_root, "--only", "CL005"])
    if rc != 0:
        log("protocol-conformance check (racelint CL005) FAILED — "
            "coordinator and worker disagree; fix the drift before "
            "drilling the protocol")
    return rc == 0


def _handoff_causal_gate(merged: dict) -> dict:
    """ISSUE 16 acceptance read over the merged Perfetto trace: the
    drill's killed-mid-handoff traffic must come out as ordinary,
    causally-ordered rows — every matched handoff flow arc runs forward
    in (coordinator-aligned) time, and at least one request's handoff
    appears on THREE distinct process rows: a coordinator lifecycle
    note, a prefill worker's serialize instant, and a decode worker's
    scatter instant with serialize end <= scatter start."""
    events = merged.get("traceEvents", [])
    instants = [e for e in events if e.get("ph") == "i"]

    def notes(name: str) -> list:
        return [e for e in instants if e.get("name") == name]

    def trace_of(event: dict):
        return (event.get("args") or {}).get("trace")

    arc_s = {str(e.get("id")): e for e in events
             if e.get("ph") == "s" and e.get("name") == "handoff"}
    arc_f = {str(e.get("id")): e for e in events
             if e.get("ph") == "f" and e.get("name") == "handoff"}
    matched = sorted(set(arc_s) & set(arc_f))
    backwards = [i for i in matched if arc_s[i]["ts"] > arc_f[i]["ts"]]

    # Prefer the kill's own evidence: traces the coordinator aborted
    # mid-handoff. Fallback to any trace (a drill where the kill raced
    # the handoff window still has to prove the three-row merge).
    aborted = sorted({t for t in map(trace_of, notes("handoff_abort"))
                      if t})
    started = sorted({t for t in map(trace_of, notes("handoff_start"))
                      if t})
    three_row = None
    for trace in (aborted or started):
        coords = [e for e in notes("handoff_start")
                  if trace_of(e) == trace]
        serials = [e for e in notes("handoff_serialize")
                   if trace_of(e) == trace]
        scatters = [e for e in notes("handoff_scatter")
                    if trace_of(e) == trace]
        for serialize in serials:
            for scatter in scatters:
                rows = {coords[0]["pid"], serialize["pid"],
                        scatter["pid"]} if coords else set()
                if len(rows) == 3 and serialize["ts"] <= scatter["ts"]:
                    three_row = {
                        "trace": trace,
                        "pids": sorted(rows),
                        "serialize_to_scatter_us":
                            scatter["ts"] - serialize["ts"],
                        "aborted_then_rerouted": trace in aborted,
                    }
                    break
            if three_row:
                break
        if three_row:
            break
    return {
        "process_rows": len({e.get("pid") for e in events}),
        "arcs_matched": len(matched),
        "arcs_backwards": len(backwards),
        "three_row_handoff": three_row,
    }


def _dump_lock_witness() -> None:
    """Write this process's observed lock-order graph (no-op unless
    POLYKEY_LOCK_WITNESS=1 armed the witness at import). Workers dump
    their own files on clean exit; killed workers lose theirs — the
    coordinator side still covers every cross-worker ordering it
    drove."""
    from polykey_tpu.analysis import witness as lock_witness

    if lock_witness.installed():
        path = lock_witness.dump()
        if path is not None:
            log(f"lock witness -> {path}")


def _sched_witness_verdict():
    """Dump this process's starvation-witness summary (no-op unless
    POLYKEY_SCHED_WITNESS=1 armed it at import) and return the merged
    SL006 verdict over every dump in the witness dir — workers dump
    their own files on clean exit; a SIGKILLed worker loses its file
    and the surviving processes still cover the frontiers they ran."""
    from polykey_tpu.analysis import sched, schedwitness

    if not schedwitness.installed():
        return None
    path = schedwitness.dump()
    if path is None:
        return None
    log(f"sched witness -> {path}")
    return sched.witness_verdict(
        schedwitness.load_witness(os.path.dirname(path)))


def run_disagg(args) -> int:
    """ISSUE 13 acceptance drill: prefill/decode worker PROCESSES over
    localhost under open-loop Poisson load, a prefill worker killed
    mid-handoff (worker-exit=1) and a decode worker killed mid-stream
    (worker-exit>=2) — zero failed RPCs, all streams token-complete,
    greedy streams bit-identical to a single-process reference, bounded
    p95-TTFT inflation, recovery of every worker to SERVING. Emits the
    failover-soak artifact schema plus the disagg extras."""
    import dataclasses
    import tempfile

    if not _protocol_gate():
        return 2

    from polykey_tpu.engine.disagg_pool import DisaggPool
    from polykey_tpu.engine.engine import GenRequest, InferenceEngine
    from polykey_tpu.engine.replica_pool import SERVING

    rng = np.random.default_rng(args.seed)
    config = _disagg_config(args)

    # Bit-identity reference: a single-process engine at the SAME
    # config/seed. Its greedy streams are the acceptance baseline.
    log("building single-process reference engine ...")
    ref_cfg = dataclasses.replace(config, disagg="", supervise=False)
    reference = InferenceEngine(ref_cfg, seed=args.seed)
    ref_prompts = [f"bit identity probe {i}" for i in range(4)]
    ref_streams = {}
    for prompt in ref_prompts:
        request = GenRequest(prompt=prompt, max_new_tokens=args.max_new)
        reference.submit(request)
        tokens = []
        while True:
            kind, value = request.out.get(timeout=120)
            if kind == "token":
                tokens.append(value)
            elif kind == "done":
                break
            else:
                log(f"reference stream failed: {value}")
                return 2
        ref_streams[prompt] = tokens
    reference.shutdown()

    state_dir = tempfile.mkdtemp(prefix="polykey-disagg-")
    log(f"spawning {args.prefill} prefill + {args.decode} decode worker "
        f"processes (compile warmup; logs in {state_dir}) ...")
    pool = DisaggPool.create(config, seed=args.seed, state_dir=state_dir)

    results_lock = threading.Lock()
    results: list[dict] = []

    def drain(request: GenRequest, enqueued_at: float) -> None:
        tokens = []
        error = None
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            try:
                kind, value = request.out.get(
                    timeout=deadline - time.monotonic())
            except Exception:
                # Justified: queue.Empty (or a negative timeout at the
                # deadline edge) both mean the stream starved — recorded
                # as a drill failure below, never silently dropped.
                error = "drain timeout"
                break
            if kind == "token":
                tokens.append(value)
            elif kind == "done":
                break
            else:
                error = value
                break
        else:
            error = error or "drain timeout"
        with results_lock:
            results.append({
                "enqueued_at": enqueued_at,
                "prompt": request.prompt,
                "tokens": len(tokens),
                "stream": tokens,
                "error": error,
                "ttft_ms": request.timings.ttft_ms,
                "restarted": bool(getattr(request, "restarted", False)),
            })

    fired = itertools.count()

    def fire(prompt: str, enqueued_at: float) -> threading.Thread:
        from polykey_tpu.obs import Span

        request = GenRequest(prompt=prompt, max_new_tokens=args.max_new)
        # Every drill request is traced like a gateway RPC would be —
        # the causal gate keys its three-process-row evidence on the
        # trace id riding the handoff notes and worker-side instants.
        request.trace = Span("gateway", trace_id=f"soak-{next(fired)}")
        pool.submit(request)
        thread = threading.Thread(
            target=drain, args=(request, enqueued_at), daemon=True
        )
        thread.start()
        return thread

    # Bit-identity probes through the DISAGGREGATED path.
    log("running bit-identity probes through the pool ...")
    probe_threads = [fire(p, 0.0) for p in ref_prompts]
    for thread in probe_threads:
        thread.join(timeout=180)
    with results_lock:
        probes = list(results)
        results.clear()
    bit_identical = all(
        r["error"] is None and r["stream"] == ref_streams[r["prompt"]]
        for r in probes
    ) and len(probes) == len(ref_prompts)
    if not bit_identical:
        log("bit-identity probes FAILED; continuing to collect evidence")

    # Rate calibration from the probes' wall time.
    service_s = max(0.05, max(
        (r["ttft_ms"] for r in probes if r["ttft_ms"] > 0), default=200.0
    ) / 1000.0 * 4)
    rate = args.rate or (
        args.oversub * args.decode * args.slots / service_s
    )
    kill_prefill_at = args.kill_at * args.duration
    kill_decode_at = min(0.95, args.kill_at + 0.25) * args.duration
    log(f"rate {rate:.1f}/s; kill prefill/{args.kill_replica} "
        f"(mid-handoff) at {kill_prefill_at:.1f}s, decode/0 (mid-stream) "
        f"at {kill_decode_at:.1f}s")

    start = time.monotonic()
    kills_done = {"prefill": None, "decode": None}
    threads = []
    index = 0
    next_arrival = start
    while True:
        now = time.monotonic()
        if kills_done["prefill"] is None and now - start >= kill_prefill_at:
            ok = _arm_worker(
                pool, "prefill", args.kill_replica,
                f"worker-exit=1@1:tier=prefill:replica={args.kill_replica}",
            )
            kills_done["prefill"] = now - start
            log(f"t+{now - start:.1f}s: armed mid-handoff kill on "
                f"prefill/{args.kill_replica} (ok={ok})")
        if kills_done["decode"] is None and now - start >= kill_decode_at:
            ok = _arm_worker(
                pool, "decode", 0,
                f"worker-exit={max(2, args.max_new // 3)}@1"
                f":tier=decode:replica=0",
            )
            kills_done["decode"] = now - start
            log(f"t+{now - start:.1f}s: armed mid-stream kill on "
                f"decode/0 (ok={ok})")
        if now - start >= args.duration:
            break
        if now >= next_arrival:
            threads.append(fire(f"soak request {index}", now - start))
            index += 1
            next_arrival += rng.exponential(1.0 / rate)
        else:
            time.sleep(min(0.005, next_arrival - now))

    log(f"arrivals done ({index}); draining ...")
    for thread in threads:
        thread.join(timeout=240)
    alive = sum(t.is_alive() for t in threads)

    recovered_s = None
    recovery_deadline = time.monotonic() + args.recovery_timeout
    while time.monotonic() < recovery_deadline:
        states = {w.name: w.state for w in pool.workers}
        if all(state == SERVING for state in states.values()):
            recovered_s = (time.monotonic() - start) - (
                kills_done["decode"] or kills_done["prefill"] or 0.0
            )
            break
        time.sleep(0.2)

    stats = pool.stats()

    # ISSUE 16: ONE merged cross-process Perfetto trace — a process row
    # per worker plus the coordinator, worker events mapped onto the
    # coordinator clock via the heartbeat's ping-offset estimates (a
    # dead worker's row falls back to its black-box checkpoint). The
    # causal gate below is the drill's "read the arc" acceptance.
    merged = pool.merged_perfetto()
    causal = _handoff_causal_gate(merged)
    pool.shutdown()
    _dump_lock_witness()

    with results_lock:
        done = list(results)
    failed = [r for r in done if r["error"] is not None]
    short = [r for r in done if r["error"] is None
             and r["tokens"] != args.max_new]
    kill_rel = kills_done["prefill"]
    pre = [r["ttft_ms"] for r in done
           if r["error"] is None and kill_rel is not None
           and r["enqueued_at"] < kill_rel and r["ttft_ms"] > 0]
    post = [r["ttft_ms"] for r in done
            if r["error"] is None and kill_rel is not None
            and r["enqueued_at"] >= kill_rel and r["ttft_ms"] > 0]
    p95_pre = percentile(pre, 95)
    p95_post = percentile(post, 95)
    added_ms = p95_post - p95_pre

    artifact = {
        "schema": "polykey_failover_soak_v1",
        "mode": "disagg",
        "replicas": args.prefill + args.decode,
        "prefill_workers": args.prefill,
        "decode_workers": args.decode,
        "slots_per_replica": args.slots,
        "duration_s": args.duration,
        "rate_per_s": round(rate, 2),
        "arrivals": index,
        "completed": len(done) - len(failed),
        "failed": len(failed),
        "failed_errors": sorted(
            {str(r["error"]) for r in failed})[:5],
        "short_streams": len(short),
        "undrained": alive,
        "kill_replica": args.kill_replica,
        "kill_at_s": round(kill_rel, 2) if kill_rel is not None else None,
        "kill_decode_at_s": (
            round(kills_done["decode"], 2)
            if kills_done["decode"] is not None else None
        ),
        "bit_identical": bit_identical,
        "bit_identity_probes": len(ref_prompts),
        "requests_rerouted": stats["requests_rerouted"],
        "streams_resumed": stats["streams_resumed"],
        "restarted_streams": sum(r["restarted"] for r in done),
        "handoffs": stats["handoffs"],
        "handoff_bytes": stats["handoff_bytes"],
        "handoff_ms_p50": stats["handoff_ms_p50"],
        "handoff_ms_p95": stats["handoff_ms_p95"],
        "ttft_ms_p50_pre_kill": round(percentile(pre, 50), 1),
        "ttft_ms_p95_pre_kill": round(p95_pre, 1),
        "ttft_ms_p50_post_kill": round(percentile(post, 50), 1),
        "ttft_ms_p95_post_kill": round(p95_post, 1),
        "p95_added_ms": round(added_ms, 1),
        "max_p95_added_ms": args.max_p95_added_ms,
        "recovered_to_full_capacity_s": (
            round(recovered_s, 2) if recovered_s is not None else None
        ),
        "replica_states_final": stats["tier_states"],
        "per_worker_completed": {
            f"{s.get('tier')}/{s.get('replica')}":
                s.get("requests_completed")
            for s in stats["per_worker"]
        },
        "clock_offsets": stats.get("clock_offsets", {}),
        "handoff_causal_gate": causal,
    }
    verdict = _sched_witness_verdict()
    if verdict is not None:
        artifact["sched_witness"] = verdict
    out = args.out or os.path.join(
        "perf", f"disagg_soak_{time.strftime('%Y-%m-%d')}.json"
    )
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    perfetto_out = os.path.splitext(out)[0] + ".perfetto.json"
    artifact["perfetto"] = perfetto_out
    with open(perfetto_out, "w") as f:
        json.dump(merged, f)
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    log(json.dumps(artifact, indent=2, sort_keys=True))
    log(f"artifact -> {out}")
    log(f"merged perfetto -> {perfetto_out}")

    ok = True
    if failed or alive:
        log(f"FAIL: {len(failed)} failed requests, {alive} undrained "
            "(the drill requires ZERO failed RPCs)")
        ok = False
    if short:
        log(f"FAIL: {len(short)} streams finished short of "
            f"{args.max_new} tokens")
        ok = False
    if not bit_identical:
        log("FAIL: disaggregated greedy streams diverged from the "
            "single-process reference")
        ok = False
    if kills_done["prefill"] is None or kills_done["decode"] is None:
        log("FAIL: a kill never fired (duration too short)")
        ok = False
    if stats["requests_rerouted"] < 1:
        log("FAIL: kills caused no re-routes — the faults missed")
        ok = False
    if added_ms > args.max_p95_added_ms:
        log(f"FAIL: p95 TTFT inflation {added_ms:.0f}ms exceeds bound "
            f"{args.max_p95_added_ms:.0f}ms")
        ok = False
    if recovered_s is None:
        log("FAIL: a killed worker never rejoined SERVING")
        ok = False
    if causal["arcs_matched"] < 1:
        log("FAIL: merged perfetto has no matched handoff arc")
        ok = False
    if causal["arcs_backwards"] > 0:
        log(f"FAIL: {causal['arcs_backwards']} handoff arc(s) run "
            "backwards after clock alignment")
        ok = False
    if causal["three_row_handoff"] is None:
        log("FAIL: no request's handoff spans three process rows "
            "(coordinator + prefill serialize + decode scatter) in "
            "causal order")
        ok = False
    log("disagg drill " + ("PASSED" if ok else "FAILED"))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots PER replica")
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="arrivals/s; 0 -> auto-calibrate via a warm burst")
    ap.add_argument("--oversub", type=float, default=0.8,
                    help="auto-rate multiplier over pool slots/service_time "
                         "(< 1: the drill measures failover, not saturation)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--model", default="tiny-llama")
    ap.add_argument("--kill-replica", type=int, default=0)
    ap.add_argument("--kill-at", type=float, default=0.35,
                    help="kill time as a fraction of --duration")
    ap.add_argument("--stall", type=float, default=2.0,
                    help="injected stall seconds (> watchdog window)")
    ap.add_argument("--watchdog-timeout", type=float, default=0.6)
    ap.add_argument("--max-p95-added-ms", type=float, default=None,
                    help="post-kill p95 TTFT may exceed pre-kill p95 by "
                         "at most this (detection + reroute bound). "
                         "Default 8000 (in-process replica restart); "
                         "30000 with --disagg (a worker PROCESS respawn "
                         "pays jax import + engine build + warmup)")
    ap.add_argument("--recovery-timeout", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--out", default="")
    # Disaggregated-tier mode (ISSUE 13): kill a prefill worker
    # mid-handoff AND a decode worker mid-stream across real worker
    # processes; gate zero failed RPCs + bit-identical greedy streams.
    ap.add_argument("--disagg", action="store_true",
                    help="drill the cross-process prefill/decode tiers")
    ap.add_argument("--prefill", type=int, default=2,
                    help="prefill-tier worker processes (--disagg)")
    ap.add_argument("--decode", type=int, default=2,
                    help="decode-tier worker processes (--disagg)")
    args = ap.parse_args()

    if args.max_p95_added_ms is None:
        args.max_p95_added_ms = 30000.0 if args.disagg else 8000.0

    if args.disagg:
        if args.prefill < 1 or args.decode < 1:
            log("disagg drill needs >= 1 worker per tier")
            return 2
        if args.kill_replica >= args.prefill:
            log("--kill-replica must name a prefill worker index")
            return 2
        return run_disagg(args)

    if args.replicas < 2:
        log("failover drill needs >= 2 replicas")
        return 2

    from polykey_tpu import faults
    from polykey_tpu.engine.engine import GenRequest
    from polykey_tpu.engine.replica_pool import SERVING

    rng = np.random.default_rng(args.seed)
    log(f"building {args.replicas}-replica pool "
        f"({args.slots} slots each, compile warmup) ...")
    pool = build_pool(args)

    results_lock = threading.Lock()
    results: list[dict] = []

    def drain(request: GenRequest, enqueued_at: float) -> None:
        tokens = 0
        error = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                kind, value = request.out.get(
                    timeout=deadline - time.monotonic())
            except Exception:
                # queue.Empty (or a negative timeout at the deadline
                # edge): both mean the stream starved — recorded as a
                # drill failure below, never silently dropped.
                error = "drain timeout"
                break
            if kind == "token":
                tokens += 1
            elif kind == "done":
                break
            else:
                error = value
                break
        else:
            error = error or "drain timeout"
        with results_lock:
            results.append({
                "enqueued_at": enqueued_at,
                "tokens": tokens,
                "error": error,
                "ttft_ms": request.timings.ttft_ms,
                "replica": getattr(request, "replica", None),
                "restarted": bool(getattr(request, "restarted", False)),
            })

    def fire(prompt: str, enqueued_at: float) -> threading.Thread:
        request = GenRequest(prompt=prompt, max_new_tokens=args.max_new)
        pool.submit(request)
        thread = threading.Thread(
            target=drain, args=(request, enqueued_at), daemon=True
        )
        thread.start()
        return thread

    # Warm every replica (spreads via the router's load term) and
    # calibrate the arrival rate from the measured service time.
    warm_start = time.monotonic()
    warm_threads = [
        fire(f"warm replica {i}", 0.0) for i in range(args.replicas)
    ]
    for thread in warm_threads:
        thread.join(timeout=120)
    service_s = max(0.05, (time.monotonic() - warm_start))
    with results_lock:
        results.clear()       # warmers don't count
    rate = args.rate or (
        args.oversub * args.replicas * args.slots / service_s
    )
    log(f"warm service ~{service_s:.2f}s -> rate {rate:.1f}/s; "
        f"kill replica {args.kill_replica} at "
        f"{args.kill_at * args.duration:.1f}s")

    start = time.monotonic()
    kill_at = start + args.kill_at * args.duration
    killed_at = None
    threads = []
    index = 0
    next_arrival = start
    while True:
        now = time.monotonic()
        if killed_at is None and now >= kill_at:
            # The targeted stall wedges ONE replica's decode dispatch
            # long enough to trip its watchdog; every other replica
            # keeps serving (":replica=K" scoping, faults.py). Engines
            # cache the shared injector at construction (the env-var
            # path arms it before the server boots), so a MID-RUN kill
            # must hand the fresh injector to the live engine; the
            # supervisor's replacement engine re-reads the shared one,
            # whose @1 budget is then already spent — restart runs clean.
            injector = faults.install(
                f"step-stall={args.stall}@1:replica={args.kill_replica}"
            )
            pool.replicas[args.kill_replica].engine._faults = injector
            killed_at = now
            log(f"t+{now - start:.1f}s: injected kill on replica "
                f"{args.kill_replica}")
        if now - start >= args.duration:
            break
        if now >= next_arrival:
            threads.append(fire(f"soak request {index}", now - start))
            index += 1
            next_arrival += rng.exponential(1.0 / rate)
        else:
            time.sleep(min(0.005, next_arrival - now))

    log(f"arrivals done ({index}); draining ...")
    for thread in threads:
        thread.join(timeout=180)
    alive = sum(t.is_alive() for t in threads)

    # Recovery: the supervisor restarts the killed replica and the pool
    # returns to full SERVING capacity.
    recovered_s = None
    recovery_deadline = time.monotonic() + args.recovery_timeout
    while time.monotonic() < recovery_deadline:
        states = pool.stats()["replica_states"]
        if all(state == SERVING for state in states.values()):
            recovered_s = time.monotonic() - (killed_at or start)
            break
        time.sleep(0.1)

    stats = pool.stats()
    faults.clear()
    pool.shutdown()
    _dump_lock_witness()

    with results_lock:
        done = list(results)
    kill_rel = (killed_at - start) if killed_at is not None else None
    failed = [r for r in done if r["error"] is not None]
    short = [r for r in done if r["error"] is None
             and r["tokens"] != args.max_new]
    pre = [r["ttft_ms"] for r in done
           if r["error"] is None and kill_rel is not None
           and r["enqueued_at"] < kill_rel and r["ttft_ms"] > 0]
    post = [r["ttft_ms"] for r in done
            if r["error"] is None and kill_rel is not None
            and r["enqueued_at"] >= kill_rel and r["ttft_ms"] > 0]
    p95_pre = percentile(pre, 95)
    p95_post = percentile(post, 95)
    added_ms = p95_post - p95_pre

    artifact = {
        "schema": "polykey_failover_soak_v1",
        "replicas": args.replicas,
        "slots_per_replica": args.slots,
        "duration_s": args.duration,
        "rate_per_s": round(rate, 2),
        "arrivals": index,
        "completed": len(done) - len(failed),
        "failed": len(failed),
        "failed_errors": sorted({r["error"] for r in failed})[:5],
        "short_streams": len(short),
        "undrained": alive,
        "kill_replica": args.kill_replica,
        "kill_at_s": round(kill_rel, 2) if kill_rel is not None else None,
        "requests_rerouted": stats["requests_rerouted"],
        "streams_resumed": stats["streams_resumed"],
        "router_decisions": stats["router_decisions"],
        "restarted_streams": sum(r["restarted"] for r in done),
        "ttft_ms_p50_pre_kill": round(percentile(pre, 50), 1),
        "ttft_ms_p95_pre_kill": round(p95_pre, 1),
        "ttft_ms_p50_post_kill": round(percentile(post, 50), 1),
        "ttft_ms_p95_post_kill": round(p95_post, 1),
        "p95_added_ms": round(added_ms, 1),
        "max_p95_added_ms": args.max_p95_added_ms,
        "recovered_to_full_capacity_s": (
            round(recovered_s, 2) if recovered_s is not None else None
        ),
        "replica_states_final": stats["replica_states"],
        "per_replica_completed": {
            str(s.get("replica")): s.get("requests_completed")
            for s in stats["per_replica"]
        },
    }
    out = args.out or os.path.join(
        "perf", f"failover_soak_{time.strftime('%Y-%m-%d')}.json"
    )
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    log(json.dumps(artifact, indent=2, sort_keys=True))
    log(f"artifact -> {out}")

    ok = True
    if failed or alive:
        log(f"FAIL: {len(failed)} failed requests, {alive} undrained "
            "(the drill requires ZERO failed RPCs)")
        ok = False
    if short:
        log(f"FAIL: {len(short)} streams finished short of "
            f"{args.max_new} tokens")
        ok = False
    if killed_at is None:
        log("FAIL: kill never fired (duration too short for --kill-at)")
        ok = False
    if stats["requests_rerouted"] < 1:
        log("FAIL: kill caused no reroutes — the fault missed "
            "(no request was on the killed replica?)")
        ok = False
    if added_ms > args.max_p95_added_ms:
        log(f"FAIL: p95 TTFT inflation {added_ms:.0f}ms exceeds bound "
            f"{args.max_p95_added_ms:.0f}ms")
        ok = False
    if recovered_s is None:
        log("FAIL: pool never recovered to full SERVING capacity")
        ok = False
    log("failover drill " + ("PASSED" if ok else "FAILED"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
