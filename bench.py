"""Benchmark harness: serving-engine throughput + TTFT on one chip.

Prints exactly ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N|null, ...}

What it measures (VERDICT r1 #1: bench what the north star names):
- Phase A — the continuous-batching engine (InferenceEngine: paged KV,
  slot-batched decode) on llama-1b-bench bf16: tok/s and p50 TTFT under a
  closed-loop load with in-flight capped at the slot count.
- Phase B — the 8B-class single-chip config BASELINE.md's target is defined
  for: llama-3-8b with int8 weights (the engine's own seeded random init),
  same engine path. Its tok/s is the headline `value`, and `vs_baseline` =
  value / 2000 (the BASELINE.md north-star tok/s/chip). Per ADVICE r1,
  vs_baseline is null when the 8B phase didn't run — a 1B number is not
  comparable to the 8B target.

It measures on a TPU or not at all: no chip => non-zero exit and no result
line; a failed phase => its error in `details` and a non-zero exit. Nothing
is replayed from old artifacts, nothing falls back to the CPU, and no kernel
is switched off to get a phase through. With POLYKEY_BENCH_ISOLATE=1 (the
default) the parent never imports JAX and each phase child holds the chip
alone. ROADMAP Queue 1 item 1 rebuilds this file as cells; until then the
phases below are the r03-era ones.

Phases beyond A/B: 0 gateway echo roundtrip over real gRPC against the
mock service (BASELINE config 1 — the dev_client request via
build_test_request; `gateway_echo` key), A-tok TTFT including real-BPE host
encode (the locally-trained 32k tokenizer asset under
assets/bench_tokenizer, or POLYKEY_BENCH_TOKENIZER; a recorded exclusion
when absent), A2 prefix-cache TTFT (cold vs warm suffix prefill), G gRPC
end to end, D long-context (2k prompts / 4k positions, chunked prefill),
D2 long-context XL (8k prompts / 16k positions), E MoE, C speculative
serving with draft == target (the acceptance-1.0 ceiling), C2 Gemma-2 9B
with a 2B draft.

Run order is 0, A, B, B2, A-tok, A2, G, D, D2, E, C, C2.
POLYKEY_BENCH_SKIP_8B_INT4=1 skips B2.

Knobs (env): POLYKEY_BENCH_MODEL, POLYKEY_BENCH_REQUESTS,
POLYKEY_BENCH_PROMPT, POLYKEY_BENCH_NEW_TOKENS, POLYKEY_BENCH_BLOCK,
POLYKEY_BENCH_LOOKAHEAD, POLYKEY_BENCH_8B_SLOTS, POLYKEY_BENCH_SKIP_8B=1,
POLYKEY_BENCH_SKIP_SPEC=1, POLYKEY_BENCH_SKIP_LONGCTX=1,
POLYKEY_BENCH_SKIP_MOE=1, POLYKEY_BENCH_MOE_SLOTS,
POLYKEY_BENCH_SKIP_GEMMA_SPEC=1, POLYKEY_BENCH_GEMMA_SLOTS,
POLYKEY_BENCH_SKIP_8B_INT4=1, POLYKEY_BENCH_8B_INT4_SLOTS,
POLYKEY_BENCH_KV_DTYPE (int8 → quantized KV pools for phases B/B2/D —
the slot-count lever), POLYKEY_BENCH_TOKENIZER, POLYKEY_BENCH_PHASES
(comma-separated subset), POLYKEY_BENCH_ISOLATE, POLYKEY_BENCH_PHASE_TIMEOUT.

All progress chatter goes to stderr; stdout carries only the JSON line.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _PhaseSkipped(Exception):
    """Control-flow sentinel: a phase opted out before doing any work."""


def probe_backend() -> dict:
    """Ask a CHILD process what JAX runs on (platform, device_kind,
    count): this process then stays off JAX, so when phases run isolated
    every phase child gets the chip to itself. No TPU is an error — a
    benchmark number from another platform is not a benchmark number."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax; d = jax.devices(); "
         "print(json.dumps({'platform': d[0].platform, "
         "'device_kind': d[0].device_kind, 'device_count': len(d)}))"],
        capture_output=True, text=True, timeout=300,
    )
    if out.returncode != 0 or not out.stdout.strip():
        log(out.stderr.strip())
        raise SystemExit(f"backend probe failed (rc={out.returncode})")
    device = json.loads(out.stdout.strip().splitlines()[-1])
    log(f"backend probe: {device}")
    return _require_tpu(device)


def _require_tpu(device: dict) -> dict:
    if device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX reports {device} — refusing "
            "to write a CPU number under a device metric's name")
    return device


def _probe_step_costs(engine, max_new: int) -> dict:
    """Diagnostic on the already-warm engine: a host↔device roundtrip floor
    and one SOLO stream decoded start-to-finish (engine otherwise idle, so
    the window is contiguous decode blocks — no admissions, no refill
    gaps). Goes into the JSON `details` so a slow bench is attributable
    (compute vs host latency) from the artifact alone."""
    import jax
    import numpy as np

    from polykey_tpu.engine.engine import GenRequest

    out: dict = {}
    # Host→device→host roundtrip floor (tiny transfer + sync).
    t0 = time.monotonic()
    for _ in range(5):
        np.asarray(jax.device_put(np.zeros((1,), np.int32)))
    out["roundtrip_ms"] = round((time.monotonic() - t0) / 5 * 1000, 2)

    probe = GenRequest(prompt="step cost probe", max_new_tokens=max_new)
    engine.submit(probe)
    kind, _ = probe.out.get(timeout=600.0)        # first token → decoding
    if kind != "token":
        return out
    snap0 = engine.metrics.snapshot()
    lanes0 = engine.metrics.lanes_snapshot()
    t0 = time.monotonic()
    kind, value = probe.out.get(timeout=600.0)
    while kind == "token":
        kind, value = probe.out.get(timeout=600.0)
    dt = time.monotonic() - t0
    snap1 = engine.metrics.snapshot()
    lanes1 = engine.metrics.lanes_snapshot()
    steps = snap1["decode_steps"] - snap0["decode_steps"]
    if kind == "done" and steps > 0 and dt > 0:
        out["block_ms"] = round(dt / steps * 1000, 2)
        # The adaptive dispatcher shrinks K for a solo stream; report the
        # K this probe actually ran with, not the configured full block.
        out["block_steps"] = getattr(
            engine, "_last_dispatch_steps", 0
        ) or engine.config.decode_block_steps
        out["solo_tok_s"] = round((value.completion_tokens - 1) / dt, 1)
    # Lookahead-pipeline cadence over the same contiguous-decode window
    # (ISSUE 6): dispatch_gap_ms is the host's realized block cadence
    # (mean dispatch-to-dispatch gap), host_stall_ms the mean time the
    # processed frontier blocked per readback, and overlap_ratio the
    # device-busy fraction of each block's wall — (gap - stall) / gap,
    # i.e. everything the host did NOT spend blocked on readback counts
    # as device-overlapped work. A synchronous host-bound loop (r03:
    # roundtrip 587 ms vs block 62 ms) reads ~0.1; the pipeline's target
    # is ~1.0. All three come from the engine's always-on counters, so
    # the hardware re-measurement lands in this same artifact format.
    gaps = lanes1["dispatch_gaps"] - lanes0["dispatch_gaps"]
    # Dead blocks (sync skipped) count in blocks_processed but did no
    # readback — the stall mean divides by the reads that happened.
    blocks = lanes1["blocks_synced"] - lanes0["blocks_synced"]
    gap_ms = None
    if gaps > 0:
        gap_ms = (lanes1["dispatch_gap_ms_total"]
                  - lanes0["dispatch_gap_ms_total"]) / gaps
        out["dispatch_gap_ms"] = round(gap_ms, 2)
    if blocks > 0:
        stall_ms = (lanes1["host_stall_ms_total"]
                    - lanes0["host_stall_ms_total"]) / blocks
        out["host_stall_ms"] = round(stall_ms, 2)
        if gap_ms:
            out["overlap_ratio"] = round(
                min(1.0, max(0.0, (gap_ms - stall_ms) / gap_ms)), 3)
    # Attribution-side cross-check (ISSUE 10): the windowed device-busy
    # fraction from the per-block attribution the engine charges to
    # requests — should track overlap_ratio (same gap − stall model,
    # accumulated per block instead of averaged over means).
    gap_total = (lanes1["dispatch_gap_ms_total"]
                 - lanes0["dispatch_gap_ms_total"])
    if gap_total > 0:
        out["device_busy_fraction"] = round(
            (lanes1["device_busy_ms_total"]
             - lanes0["device_busy_ms_total"]) / gap_total, 3)
    out["lookahead_depth"] = getattr(engine, "_depth", 1)
    return out


def bench_engine(
    engine_cfg, params, n_requests: int, prompt_len: int, max_new: int,
    draft_params=None, prompt_fn=None,
) -> dict:
    """Closed-loop engine bench + a light-load TTFT probe.

    The closed loop keeps in-flight at 2x the slot count: done-delivery
    lags the dispatch pipeline by `lookahead_blocks`, so a queue capped AT
    the slot count leaves every retiring slot empty for several blocks
    (measured 5/32 live lanes in r03) — a load-generator artifact, not an
    engine property. The deeper queue keeps a waiting request ready the
    iteration a slot frees, which is what a saturated server looks like.

    TTFT under that saturation measures queue wait, not serving latency,
    so `p50_ttft_ms` additionally comes from a separate light-load probe
    (a few requests, in-flight 2) on the same warm engine; the saturated
    number is kept as `saturated_ttft_ms`. `prompt_fn` overrides the
    default random-chars prompts (the real-tokenizer phase passes text
    sized in TOKENS)."""
    import threading

    import numpy as np

    from polykey_tpu.engine.engine import GenRequest, InferenceEngine

    rng = np.random.default_rng(7)

    def prompt() -> str:
        if prompt_fn is not None:
            return prompt_fn()
        return "".join(chr(c) for c in rng.integers(97, 123, prompt_len))

    engine = InferenceEngine(engine_cfg, params=params, draft_params=draft_params)
    try:
        # Shape compiles happen in __init__ (compile_warmup=True); this
        # end-to-end warmup covers the host paths (tokenizer, queues).
        log("warmup (e2e; shapes pre-compiled at engine init)...")
        t0 = time.monotonic()
        warm = [GenRequest(prompt=prompt(), max_new_tokens=max_new)
                for _ in range(2)]
        for r in warm:
            engine.submit(r)
        for r in warm:
            while r.out.get(timeout=600.0)[0] == "token":
                pass
        log(f"warmup done in {time.monotonic() - t0:.1f}s")

        slots = engine_cfg.max_decode_slots
        lock = threading.Lock()

        def run_closed_loop(n: int, depth: int, new_tokens: int,
                            sink: list, errs: list) -> float:
            """Submit n requests with in-flight capped at `depth`; drain
            each on its own thread into `sink` (done timings) / `errs`.
            One implementation serves both the saturated measurement and
            the light-load TTFT probe."""
            sem = threading.Semaphore(depth)

            def drain(r: GenRequest) -> None:
                try:
                    while True:
                        kind, value = r.out.get(timeout=600.0)
                        if kind == "done":
                            with lock:
                                sink.append(value)
                            return
                        if kind == "error":
                            with lock:
                                errs.append(value)
                            return
                except Exception as e:  # incl. queue.Empty: a hung request
                    with lock:          # must surface, not deflate tok/s
                        errs.append(f"drain: {type(e).__name__}: {e}")
                finally:
                    sem.release()

            t0 = time.monotonic()
            threads = []
            for _ in range(n):
                sem.acquire()
                r = GenRequest(prompt=prompt(), max_new_tokens=new_tokens)
                engine.submit(r)
                th = threading.Thread(target=drain, args=(r,), daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=600.0)
            return time.monotonic() - t0

        # Saturated closed loop: in-flight at 2x slots (done-delivery lags
        # the lookahead pipeline; a queue capped AT the slot count leaves
        # retiring slots empty for several blocks — measured 5/32 lanes).
        # Snapshot the always-on occupancy tracker around JUST this loop
        # so avg_lanes reflects the saturated run, not warmup/probe
        # blocks (ISSUE 4: measured lanes from the always-on tracker).
        acc0 = engine.metrics.lanes_snapshot()
        timings, errors = [], []
        elapsed = run_closed_loop(
            n_requests, slots * 2, max_new, timings, errors)
        acc1 = engine.metrics.lanes_snapshot()
        sat_blocks = acc1["blocks_dispatched"] - acc0["blocks_dispatched"]
        sat_steps = acc1["steps_dispatched"] - acc0["steps_dispatched"]
        sat_lane_steps = acc1["lane_steps"] - acc0["lane_steps"]
        sat_dispatched = (acc1["tokens_dispatched_total"]
                          - acc0["tokens_dispatched_total"])
        sat_useful = (acc1["tokens_useful_total"]
                      - acc0["tokens_useful_total"])

        if errors:
            raise RuntimeError(f"{len(errors)} requests failed: {errors[0]}")
        total_tokens = sum(t.completion_tokens for t in timings)
        tok_s = total_tokens / elapsed
        sat_ttft = statistics.median(t.ttft_ms for t in timings)
        log(f"{len(timings)} requests, {total_tokens} tokens in "
            f"{elapsed:.2f}s -> {tok_s:.1f} tok/s, saturated p50 TTFT "
            f"{sat_ttft:.1f} ms")

        # Light-load TTFT probe: 6 requests, in-flight 2, short replies —
        # prefill + first-token latency without saturation queue wait.
        # Probe failures only cost the probe (fall back to the saturated
        # number); they must not fail the whole phase.
        probe_timings, probe_errors = [], []
        run_closed_loop(6, 2, min(8, max_new), probe_timings, probe_errors)
        p50_ttft = (
            statistics.median(t.ttft_ms for t in probe_timings)
            if probe_timings else sat_ttft
        )
        log(f"light-load p50 TTFT {p50_ttft:.1f} ms "
            f"({len(probe_timings)} probe requests)")

        costs = _probe_step_costs(engine, max_new)
        avg_lanes = None
        if sat_steps > 0:
            # Step-weighted mean over the saturated window — the same
            # statistic the engine's own stats() reports lifetime-wide.
            avg_lanes = round(sat_lane_steps / sat_steps, 2)
            costs["avg_lanes"] = avg_lanes
            costs["blocks"] = sat_blocks
        log(f"step costs: {costs}")
        out = {
            "tok_s": round(tok_s, 1),
            "p50_ttft_ms": round(p50_ttft, 1),
            "saturated_ttft_ms": round(sat_ttft, 1),
            # Measured occupancy of the saturated window, first-class in
            # every engine phase (ISSUE 4) — next to slots so any artifact
            # reader can grade occupancy without digging in step_costs.
            "avg_lanes": avg_lanes,
            "slots": engine_cfg.max_decode_slots,
            "requests": len(timings),
            "total_tokens": total_tokens,
            "elapsed_s": round(elapsed, 2),
            # Padding-waste accounting over the saturated window (ISSUE
            # 12), first-class: token rows computed vs useful
            # (bucket/pad-group padding, dead decode lanes).
            "tokens_dispatched": sat_dispatched,
            "tokens_useful": sat_useful,
            "tokens_useful_fraction": (
                round(sat_useful / sat_dispatched, 4)
                if sat_dispatched else None
            ),
            "step_costs": costs,
        }
        # Physics scorecard (VERDICT r4 #4): grade tok/s against the
        # weight+KV HBM-read roofline and TTFT against the MXU prefill
        # roofline. On CPU mbu/mfu stay null but the per-token geometry
        # still lands.
        from polykey_tpu.engine.roofline import (
            detect_chip, grade, kv_pool_bytes_spec)
        from polykey_tpu.models.config import get_config

        kwargs = dict(
            model=engine_cfg.model,
            dtype=engine_cfg.dtype,
            quantize=engine_cfg.quantize,
            quantize_bits=engine_cfg.quantize_bits,
            kv_dtype=engine_cfg.kv_dtype,
            tok_s=tok_s,
            # None when the tracker saw no dispatches (grade then
            # says avg_lanes_source=assumed_full instead of passing
            # an unmeasured occupancy off as data).
            avg_lanes=avg_lanes,
            assumed_lanes=float(engine_cfg.max_decode_slots),
            avg_ctx=prompt_len + max_new / 2.0,
            p50_ttft_ms=p50_ttft,
            prompt_len=prompt_len,
            chip=detect_chip(),
            draft_model=engine_cfg.draft_model,
            # Device KV pool + int8 scale planes: grade() folds these
            # into hbm_resident_fraction (weights-only
            # hbm_weight_fraction is unchanged).
            kv_pool_bytes=kv_pool_bytes_spec(
                get_config(engine_cfg.model), engine_cfg.num_pages,
                engine_cfg.page_size,
                engine_cfg.kv_dtype or engine_cfg.dtype),
        )
        out["roofline"] = grade(**kwargs)
        snap = engine.stats()
        if "spec_acceptance" in snap:
            out["spec_acceptance"] = snap["spec_acceptance"]
        return out
    finally:
        engine.shutdown()


def _compose_line(result: dict) -> dict:
    """Compose the single JSON line. Headline = the target-comparable
    number when it exists (8B-class engine tok/s — the best valid of
    int8/int4: both are "Llama-3-8B greedy decode on one chip";
    quantization width is an implementation choice the target doesn't
    constrain), else the phase-A number with vs_baseline null (ADVICE r1:
    no apples-to-oranges ratio). Every line carries platform, device_kind
    and device_count in `details`; main() refuses to start off-TPU."""
    baseline = 2000.0  # BASELINE.md: tok/s/chip, 8B-class greedy on v5e

    def valid(key):
        d = result.get(key)
        return d if isinstance(d, dict) and "tok_s" in d else None

    candidates_8b = [
        ("int8", valid("engine_8b_int8")), ("int4", valid("engine_8b_int4"))
    ]
    best = max(
        (c for c in candidates_8b if c[1] is not None),
        key=lambda c: c[1]["tok_s"], default=None,
    )
    if best is not None:
        qname, phase_best = best
        line = {
            "metric": f"llama3_8b_{qname}_engine_tok_s_per_chip",
            "value": phase_best["tok_s"],
            "unit": "tok/s",
            "vs_baseline": round(phase_best["tok_s"] / baseline, 3),
            "p50_ttft_ms": phase_best["p50_ttft_ms"],
            "details": result,
        }
    elif "tok_s" in result.get("engine_1b", {}):
        a = result["engine_1b"]
        line = {
            "metric": "{}_engine_tok_s_per_chip".format(a["model"]),
            "value": a["tok_s"],
            "unit": "tok/s",
            "vs_baseline": None,
            "p50_ttft_ms": a["p50_ttft_ms"],
            "details": result,
        }
    else:
        return {
            "metric": "bench_failed",
            "value": 0.0,
            "unit": "tok/s",
            "vs_baseline": None,
            "details": result,
        }
    return line


_PHASE_KEYS = (
    ("0", "gateway_echo"),
    ("A", "engine_1b"),
    ("B", "engine_8b_int8"),
    ("B2", "engine_8b_int4"),
    ("A-tok", "engine_ttft_tokenized"),
    ("A2", "prefix_cache"),
    ("G", "grpc_e2e"),
    ("D", "engine_longctx"),
    ("D2", "engine_longctx_xl"),
    ("E", "engine_moe"),
    ("C", "engine_spec"),
    ("C2", "engine_gemma_spec"),
)


def _failed_phases(result: dict) -> list:
    return sorted(
        key for key, entry in result.items()
        if isinstance(entry, dict) and "error" in entry
    )


def _run_isolated(result: dict, phases: list | None = None) -> None:
    """Run each phase in its own subprocess (POLYKEY_BENCH_PHASES=<name>)
    and merge their details into one artifact. A wedged backend client
    (the r03 failure: one UNIMPLEMENTED dispatch poisoned the in-process
    runtime and every later phase died with it), a crash, or a hang then
    costs only its own phase. This parent never imports JAX, so each
    child in turn is the one process that holds the chip; children share
    the persistent XLA compile cache, and their stderr streams through
    live. Any failed phase makes the exit code non-zero."""
    order = [p for p, _ in _PHASE_KEYS]
    phases = [p for p in order if phases is None or p in phases]
    keys = dict(_PHASE_KEYS)
    # Operator skips: record the skip here and don't pay the child launch.
    skip_envs = {"B": "POLYKEY_BENCH_SKIP_8B",
                 "B2": "POLYKEY_BENCH_SKIP_8B_INT4",
                 "D": "POLYKEY_BENCH_SKIP_LONGCTX",
                 "E": "POLYKEY_BENCH_SKIP_MOE",
                 "C": "POLYKEY_BENCH_SKIP_SPEC",
                 "C2": "POLYKEY_BENCH_SKIP_GEMMA_SPEC"}
    timeout = float(os.environ.get("POLYKEY_BENCH_PHASE_TIMEOUT", "2400"))
    for ph in phases:
        key = keys[ph]
        if os.environ.get(skip_envs.get(ph, ""), "") == "1":
            result[key] = {"skipped": f"{skip_envs[ph]}=1"}
            continue
        env = dict(os.environ)
        env["POLYKEY_BENCH_PHASES"] = ph
        env["POLYKEY_BENCH_ISOLATE"] = "0"
        t0 = time.monotonic()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, stdout=subprocess.PIPE, timeout=timeout,
            )
            lines = proc.stdout.decode(errors="replace").strip().splitlines()
            det = (json.loads(lines[-1]) if lines else {}).get("details", {})
            if key in det:
                result[key] = det[key]
            elif proc.returncode != 0:
                result[key] = {
                    "error": f"phase subprocess rc={proc.returncode}"}
            else:
                result[key] = {"error": "phase produced no entry"}
        except subprocess.TimeoutExpired:
            result[key] = {
                "error": f"phase subprocess timed out after {timeout:.0f}s"}
        except (OSError, ValueError) as e:     # spawn / JSON decode
            result[key] = {"error": f"phase subprocess failed: {e}"}
        log(f"[isolate] phase {ph} finished in {time.monotonic() - t0:.0f}s")
    print(json.dumps(_compose_line(result)), flush=True)
    failed = _failed_phases(result)
    if failed:
        raise SystemExit(f"failed phases: {', '.join(failed)}")


def main() -> None:
    # Phase selection (POLYKEY_BENCH_PHASES="B,B2") + subprocess isolation
    # (POLYKEY_BENCH_ISOLATE, default on): the r03 run lost every phase
    # after B2 to one wedged backend client (an UNIMPLEMENTED error
    # poisoned the in-process runtime) — isolation caps the blast radius
    # of a wedge, crash, or hang at its own phase.
    sel_env = os.environ.get("POLYKEY_BENCH_PHASES", "").strip()
    selected = (
        {p.strip() for p in sel_env.split(",") if p.strip()}
        if sel_env else None
    )

    def phase_on(name: str) -> bool:
        return selected is None or name in selected

    isolate = os.environ.get("POLYKEY_BENCH_ISOLATE", "1") == "1"
    if isolate and (selected is None or len(selected) > 1):
        # Parent of isolated phases: stays off JAX (a chip belongs to one
        # process) and learns the platform from a probe child.
        _run_isolated(
            dict(probe_backend()),
            phases=sorted(selected) if selected else None,
        )
        return

    # From here this process owns the chip: place the compile cache
    # before the first jit, then refuse anything that is not a TPU.
    from polykey_tpu.engine.config import (
        EngineConfig,
        enable_persistent_compile_cache,
    )
    from polykey_tpu.engine.device import device_identity

    log(f"compile cache: {enable_persistent_compile_cache()}")
    result: dict = _require_tpu(device_identity())

    # 128 requests ≈ 16k tokens: enough steady-state that ramp/tail don't
    # dominate a 32-slot run (64 was ~16 full-occupancy blocks total).
    n_req = int(os.environ.get("POLYKEY_BENCH_REQUESTS", "128"))
    prompt_len = int(os.environ.get("POLYKEY_BENCH_PROMPT", "128"))
    max_new = int(os.environ.get("POLYKEY_BENCH_NEW_TOKENS", "128"))

    block = int(os.environ.get("POLYKEY_BENCH_BLOCK", "16"))
    # KV-cache dtype for the engine phases ("" = follow dtype; "int8"
    # halves pool HBM — the slot-count lever; engine/config.py kv_dtype).
    kv_dtype = os.environ.get("POLYKEY_BENCH_KV_DTYPE", "")
    # Pipeline depth: the device stays busy only if in-flight blocks cover
    # the host's sync roundtrip. 4 is a guess that predates any measurement
    # on a directly attached chip (ROADMAP Queue 1 item 8 sweeps 2/3/4).
    lookahead = int(os.environ.get("POLYKEY_BENCH_LOOKAHEAD", "4"))

    # --- Phase 0: gateway echo roundtrip (BASELINE config 1 — dev_client
    # example_tool over real gRPC against the mock service; pure CPU, so
    # it lands even when the TPU is unreachable). ---
    try:
        if not phase_on("0"):
            raise _PhaseSkipped()
        import io

        import grpc

        from polykey_tpu.gateway import server as gateway_server
        from polykey_tpu.gateway.client import build_test_request
        from polykey_tpu.gateway.jsonlog import Logger
        from polykey_tpu.gateway.mock_service import MockService
        from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

        srv, _, port = gateway_server.build_server(
            MockService(), Logger(stream=io.StringIO()),
            address="127.0.0.1:0",
        )
        srv.start()
        try:
            with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
                stub = PolykeyServiceStub(channel)
                # The canonical dev_client payload (secret_id + metadata),
                # not a hand-rolled lookalike — config 1 measures THAT
                # request's serialization path.
                req = build_test_request()
                lat = []
                for _ in range(100):
                    t0 = time.monotonic()
                    stub.ExecuteTool(req, timeout=5)
                    lat.append((time.monotonic() - t0) * 1000)
                lat.sort()
                result["gateway_echo"] = {
                    "p50_ms": round(lat[len(lat) // 2], 3),
                    "p95_ms": round(lat[int(len(lat) * 0.95)], 3),
                    "calls": len(lat),
                }
                log(f"phase 0 gateway echo: {result['gateway_echo']}")
        finally:
            srv.stop(0)
    except _PhaseSkipped:
        pass
    except Exception as e:
        log(f"phase 0 failed: {e}")
        result["gateway_echo"] = {"error": str(e)}

    # --- Phase A: engine bench, 1B-class bf16. ---
    model_a = os.environ.get("POLYKEY_BENCH_MODEL", "llama-1b-bench")
    cfg_a = EngineConfig(
        model=model_a,
        dtype="bfloat16",
        max_decode_slots=32,
        page_size=16,
        num_pages=2048,
        max_seq_len=512,
        prefill_buckets=(prompt_len,),
        max_new_tokens_cap=max_new,
        decode_block_steps=block,
        lookahead_blocks=lookahead,
        compile_warmup=True,
        # Greedy-only workload: skip the sampled-variant warmup compiles.
        warm_sampled_variants=False,
    )
    try:
        if not phase_on("A"):
            raise _PhaseSkipped()
        log(f"--- phase A: engine bench, {model_a} (block={block}) ---")
        phase_a = bench_engine(cfg_a, None, n_req, prompt_len, max_new)
        result["engine_1b"] = {"model": model_a, **phase_a}
    except _PhaseSkipped:
        log("phase A skipped")
    except Exception as e:
        log(f"phase A failed: {e}")
        result["engine_1b"] = {"model": model_a, "error": str(e)}

    # --- Phase B: 8B-int8 — the config the 2,000 tok/s target names. ---
    if (phase_on("B")
            and os.environ.get("POLYKEY_BENCH_SKIP_8B", "") != "1"):
        try:
            log("--- phase B: engine bench, llama-3-8b int8 ---")
            # 48 slots x 512 positions = 1536 pages at full occupancy
            # (~3.2 GiB of KV next to ~8.5 GiB of int8 weights on a
            # 16 GiB chip — a safe margin). Batch width is the
            # single-chip throughput lever while decode stays
            # weight-bandwidth-bound: tok/s scales ~linearly in slots
            # until compute-per-step grows past the weight read.
            slots8 = int(os.environ.get("POLYKEY_BENCH_8B_SLOTS", "48"))
            cfg_b = EngineConfig(
                kv_dtype=kv_dtype,
                model="llama-3-8b",
                dtype="bfloat16",
                quantize=True,
                max_decode_slots=slots8,
                page_size=16,
                num_pages=slots8 * 32 + 64,
                max_seq_len=512,
                prefill_buckets=(prompt_len,),
                max_new_tokens_cap=max_new,
                decode_block_steps=block,
                lookahead_blocks=lookahead,
                compile_warmup=True,
                warm_sampled_variants=False,
            )
            result["engine_8b_int8"] = bench_engine(
                cfg_b, None, max(2 * slots8, 32), prompt_len, max_new)
        except Exception as e:
            log(f"phase B failed: {e}")
            result["engine_8b_int8"] = {"error": str(e)}

    # --- Phase B2: 8B int4 — the beat-the-target lever. Group-wise int4
    # halves weight HBM traffic vs int8; decode is weight-bandwidth-bound
    # at these batch sizes, so the ceiling roughly doubles. Same model,
    # same greedy workload — a valid 8B target number; the headline takes
    # the better of B/B2. ---
    if (phase_on("B2")
            and os.environ.get("POLYKEY_BENCH_SKIP_8B", "") != "1"
            and os.environ.get("POLYKEY_BENCH_SKIP_8B_INT4", "") != "1"):
        try:
            log("--- phase B2: engine bench, llama-3-8b int4 ---")
            # int4 frees ~4 GiB of HBM vs int8 — spend it on batch width
            # (48 slots ≈ 3.2 GiB KV at 512 ctx next to ~4.4 GiB weights):
            # more tokens per weight pass while decode stays bandwidth-
            # bound. An explicit POLYKEY_BENCH_8B_SLOTS cap (operator HBM
            # budget) carries over unless the int4 knob overrides it.
            slots8 = int(os.environ.get(
                "POLYKEY_BENCH_8B_INT4_SLOTS",
                os.environ.get("POLYKEY_BENCH_8B_SLOTS", "48"),
            ))
            cfg_b2 = EngineConfig(
                kv_dtype=kv_dtype,
                model="llama-3-8b",
                dtype="bfloat16",
                quantize=True,
                quantize_bits=4,
                max_decode_slots=slots8,
                page_size=16,
                num_pages=slots8 * 32 + 64,
                max_seq_len=512,
                prefill_buckets=(prompt_len,),
                max_new_tokens_cap=max_new,
                decode_block_steps=block,
                lookahead_blocks=lookahead,
                compile_warmup=True,
                warm_sampled_variants=False,
            )
            result["engine_8b_int4"] = bench_engine(
                cfg_b2, None, max(2 * slots8, 32), prompt_len, max_new)
        except Exception as e:
            log(f"phase B2 failed: {e}")
            result["engine_8b_int4"] = {"error": str(e)}

    # --- Phase A-tok: TTFT with a REAL BPE tokenizer (VERDICT r2 #4:
    # every previous TTFT excluded host-side encode — the ByteTokenizer
    # is a table lookup; a 32k+ BPE pays real merge work per request).
    # Uses the locally-trained tokenizer asset
    # (scripts/build_bench_tokenizer.py); skipped with a recorded
    # exclusion when the asset is absent. ---
    # Prefer the Llama-3-sized 128k asset (VERDICT r3 #6: host-encode
    # cost scales with merge-table depth; 32k under-charges TTFT) and
    # fall back to the original 32k one.
    _assets = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "assets")
    tok_dir = os.environ.get("POLYKEY_BENCH_TOKENIZER") or next(
        (d for d in (os.path.join(_assets, "bench_tokenizer_128k"),
                     os.path.join(_assets, "bench_tokenizer"))
         if os.path.exists(os.path.join(d, "tokenizer.json"))),
        os.path.join(_assets, "bench_tokenizer"),
    )
    if not phase_on("A-tok"):
        pass
    elif not os.path.exists(os.path.join(tok_dir, "tokenizer.json")):
        result["engine_ttft_tokenized"] = {
            "excluded": "no tokenizer asset; TTFT numbers exclude host "
                        "encode (build with scripts/build_bench_tokenizer.py)"
        }
    else:
        try:
            log("--- phase A-tok: TTFT incl. real-BPE host encode ---")
            import dataclasses
            import random as _random

            from polykey_tpu.engine.tokenizer import HFTokenizer

            ht = HFTokenizer(tok_dir)
            rng_t = _random.Random(11)
            vocab_words = ["the", "of", "and", "model", "token", "server",
                           "stream", "request", "engine", "attention",
                           "decode", "cache", "batch", "layer", "with"]
            target_tokens = max(8, int(prompt_len * 0.9))

            def text_prompt() -> str:
                words: list[str] = []
                while len(ht.encode(" ".join(words))) < target_tokens:
                    words.append(rng_t.choice(vocab_words))
                return " ".join(words)

            prompts = [text_prompt() for _ in range(16)]
            t0 = time.monotonic()
            for p in prompts:
                ht.encode(p)
            encode_ms = (time.monotonic() - t0) / len(prompts) * 1000
            pi = iter(range(1 << 30))
            phase_tok = bench_engine(
                dataclasses.replace(cfg_a, tokenizer=tok_dir),
                None, min(n_req, 16), prompt_len, max_new,
                prompt_fn=lambda: prompts[next(pi) % len(prompts)],
            )
            result["engine_ttft_tokenized"] = {
                "tokenizer_vocab": ht.vocab_size,
                "host_encode_ms": round(encode_ms, 2),
                "prompt_tokens": target_tokens,
                **phase_tok,
            }
        except Exception as e:
            log(f"phase A-tok failed: {e}")
            result["engine_ttft_tokenized"] = {"error": str(e)}

    # --- Phase A2: prefix-cache TTFT — requests sharing a long prefix
    # prefill only their suffix; p50 TTFT of the cached requests is the
    # feature's measurable win. ---
    try:
        if not phase_on("A2"):
            raise _PhaseSkipped()
        log("--- phase A2: prefix-cache TTFT ---")
        import dataclasses as _dc

        from polykey_tpu.engine.engine import GenRequest, InferenceEngine

        import numpy as _np

        # A small bucket matters: warm requests prefill only their short
        # suffix, and bucketing it to the full prompt width would erase
        # the very win this phase measures.
        cfg_a2 = _dc.replace(
            cfg_a, prefix_cache=True,
            prefill_buckets=tuple(sorted({32, *cfg_a.prefill_buckets})),
        )
        _r = _np.random.default_rng(13)
        header = "".join(chr(c) for c in _r.integers(97, 123, prompt_len - 8))
        engine2 = InferenceEngine(cfg_a2)
        try:
            ttfts = []
            for i in range(9):
                r = GenRequest(
                    prompt=header + f" tail{i}", max_new_tokens=16
                )
                engine2.submit(r)
                kind, value = r.out.get(timeout=600.0)
                while kind == "token":
                    kind, value = r.out.get(timeout=600.0)
                if kind != "done":
                    raise RuntimeError(f"request failed: {value}")
                ttfts.append(r.timings.ttft_ms)
            result["prefix_cache"] = {
                "cold_ttft_ms": round(ttfts[0], 1),
                "p50_warm_ttft_ms": round(statistics.median(ttfts[1:]), 1),
                **{k: v for k, v in engine2.stats().items()
                   if k.startswith("prefix_")},
            }
            log(f"prefix cache: {result['prefix_cache']}")
        finally:
            engine2.shutdown()
    except _PhaseSkipped:
        log("phase A2 skipped")
    except Exception as e:
        log(f"phase A2 failed: {e}")
        result["prefix_cache"] = {"error": str(e)}

    # --- Phase G: composed gRPC e2e — ExecuteToolStream against the real
    # gateway with the engine mounted (VERDICT r3 weak #7: the north-star
    # TTFT is gRPC end-to-end, yet gRPC-level and engine-level numbers had
    # never met in one run). The client clock gives e2e TTFT (proto
    # serialize → interceptor → tokenize → queue → prefill → first delta
    # over the wire); the final chunk's Usage carries the ENGINE TTFT for
    # the SAME request, so gateway_overhead_ms is a per-request
    # subtraction, not a cross-run comparison.
    try:
        if not phase_on("G"):
            raise _PhaseSkipped()
        log("--- phase G: gRPC e2e (ExecuteToolStream -> engine) ---")
        import io
        import threading as _threading

        import grpc
        import numpy as _np

        from polykey_tpu.engine.engine import InferenceEngine
        from polykey_tpu.gateway import server as gateway_server
        from polykey_tpu.gateway.jsonlog import Logger
        from polykey_tpu.gateway.tpu_service import TpuService
        from polykey_tpu.proto import polykey_v2_pb2 as pk
        from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

        slots_g2 = cfg_a.max_decode_slots
        conc_g = 2 * slots_g2           # same saturation depth as phase A
        n_req_g = min(n_req, 4 * slots_g2)
        rng_g = _np.random.default_rng(23)

        def _g_prompt() -> str:
            return "".join(
                chr(c) for c in rng_g.integers(97, 123, prompt_len))

        engine_g = InferenceEngine(cfg_a)
        service_g = TpuService(engine_g)
        srv_g, _, port_g = gateway_server.build_server(
            service_g, Logger(stream=io.StringIO()),
            address="127.0.0.1:0", max_workers=conc_g + 8,
        )
        srv_g.start()
        try:
            with grpc.insecure_channel(f"127.0.0.1:{port_g}") as chan:
                stub = PolykeyServiceStub(chan)
                g_lock = _threading.Lock()

                def stream_one(prompt: str, new_tokens: int,
                               sink: list, errs: list):
                    req = pk.ExecuteToolRequest(tool_name="llm_generate")
                    req.parameters.update({
                        "prompt": prompt, "max_tokens": new_tokens,
                    })
                    t0 = time.monotonic()
                    first_ms = None
                    usage = None
                    try:
                        for chunk in stub.ExecuteToolStream(
                                req, timeout=600.0):
                            if chunk.delta and first_ms is None:
                                first_ms = (time.monotonic() - t0) * 1000
                            if chunk.final:
                                usage = chunk.usage
                        with g_lock:
                            sink.append((first_ms, usage))
                    except Exception as e:
                        with g_lock:
                            errs.append(f"{type(e).__name__}: {e}")

                def closed_loop(n: int, depth: int, new_tokens: int):
                    sink: list = []
                    errs: list = []
                    sem = _threading.Semaphore(depth)
                    threads = []

                    def worker(prompt: str):
                        try:
                            stream_one(prompt, new_tokens, sink, errs)
                        finally:
                            sem.release()

                    t0 = time.monotonic()
                    for _ in range(n):
                        sem.acquire()
                        # Prompt generated on the launcher thread: the
                        # numpy Generator is not thread-safe.
                        th = _threading.Thread(
                            target=worker, args=(_g_prompt(),), daemon=True)
                        th.start()
                        threads.append(th)
                    for th in threads:
                        th.join(timeout=600.0)
                    return time.monotonic() - t0, sink, errs

                closed_loop(2, 2, max_new)          # host-path warmup
                elapsed_g, sat_g, errs_g = closed_loop(
                    n_req_g, conc_g, max_new)
                if errs_g:
                    raise RuntimeError(
                        f"{len(errs_g)} streams failed: {errs_g[0]}")
                total_tok_g = sum(
                    u.completion_tokens for _, u in sat_g if u is not None)
                # Light load (in-flight 2, short replies): e2e TTFT
                # without saturation queue wait — the north-star shape.
                _, light_g, light_errs = closed_loop(
                    6, 2, min(8, max_new))
                probe = [
                    (f, u) for f, u in light_g
                    if f is not None and u is not None
                ]
                entry_g: dict = {
                    "model": cfg_a.model,
                    "tok_s": round(total_tok_g / elapsed_g, 1),
                    "requests": n_req_g,
                    # The depth actually reached, not the cap: small runs
                    # never fill conc_g in-flight.
                    "concurrency": min(conc_g, n_req_g),
                    "saturated_e2e_ttft_ms": round(statistics.median(
                        f for f, _ in sat_g if f is not None), 1),
                }
                if probe:
                    entry_g.update({
                        "p50_e2e_ttft_ms": round(statistics.median(
                            f for f, _ in probe), 1),
                        "p50_engine_ttft_ms": round(statistics.median(
                            u.ttft_ms for _, u in probe), 1),
                        # Median of PER-REQUEST differences — a median-of-
                        # medians can pair different requests and go
                        # negative when latency swings between them.
                        "gateway_overhead_ms": round(statistics.median(
                            f - u.ttft_ms for f, u in probe), 1),
                    })
                elif light_errs:
                    entry_g["probe_error"] = light_errs[0]
                result["grpc_e2e"] = entry_g
                log(f"phase G: {entry_g}")
        finally:
            srv_g.stop(0)
            service_g.close()
    except _PhaseSkipped:
        log("phase G skipped")
    except Exception as e:
        log(f"phase G failed: {e}")
        result["grpc_e2e"] = {"error": str(e)}

    # --- Phase D: long-context serving — 2k-token prompts decoding at 4k
    # positions through chunked prefill + the paged kernel's grouped page
    # streaming (SURVEY §5 long-context; engine defaults are 4k). ---
    if (phase_on("D")
            and os.environ.get("POLYKEY_BENCH_SKIP_LONGCTX", "") != "1"):
        try:
            log("--- phase D: long-context engine bench (2k prompt / 4k positions) ---")
            cfg_d = EngineConfig(
                kv_dtype=kv_dtype,
                model=model_a,
                dtype="bfloat16",
                max_decode_slots=8,
                page_size=16,
                num_pages=(8 * 256 + 64),
                max_seq_len=4096,
                # Forced tiny scale keeps the SHAPE (bucket == chunk,
                # prompt >> bucket → chunked prefill) at CPU cost.
                prefill_buckets=(512,),
                prefill_chunk=512,
                max_new_tokens_cap=max_new,
                decode_block_steps=block,
                lookahead_blocks=lookahead,
                compile_warmup=True,
                warm_sampled_variants=False,
            )
            result["engine_longctx"] = {
                "model": model_a,
                **bench_engine(cfg_d, None, 16,
                               2048, max_new),
            }
        except Exception as e:
            log(f"phase D failed: {e}")
            result["engine_longctx"] = {"error": str(e)}

    # --- Phase D2: the 16k tier (VERDICT r4 #5 — 8k-prompt/16k-position
    # serving; SURVEY §5 "sequences beyond one chip's HBM" is covered by
    # sp/CP in the dryrun, this phase prices the single-chip envelope:
    # 8 slots x 16k x 32 KiB KV = 4 GiB next to the 1B bf16 weights). ---
    if (phase_on("D2")
            and os.environ.get("POLYKEY_BENCH_SKIP_LONGCTX", "") != "1"):
        try:
            log("--- phase D2: long-context XL (8k prompt / 16k positions) ---")
            cfg_d2 = EngineConfig(
                kv_dtype=kv_dtype,
                model=model_a,
                dtype="bfloat16",
                max_decode_slots=8,
                page_size=16,
                num_pages=(8 * 1024 + 64),
                max_seq_len=16384,
                prefill_buckets=(512,),
                prefill_chunk=512,
                max_new_tokens_cap=max_new,
                decode_block_steps=block,
                lookahead_blocks=lookahead,
                compile_warmup=True,
                warm_sampled_variants=False,
            )
            result["engine_longctx_xl"] = {
                "model": model_a,
                **bench_engine(cfg_d2, None, 8,
                               8192, max_new),
            }
        except Exception as e:
            log(f"phase D2 failed: {e}")
            result["engine_longctx_xl"] = {"error": str(e)}

    # --- Phase E: MoE serving — measurement config 4's mechanism on one
    # chip. mixtral-bench keeps the 8x7B architecture (8 experts, top-2,
    # dispatch routing) at ~4.7 B params so the int8 tree fits next to KV
    # in 16 GiB; at batch width every expert is hit each step, so decode
    # pays the full expert-weight HBM read like the real model does.
    # ep>1 (the all-to-all) is covered by the virtual-mesh dryrun; one
    # chip exercises routing + grouped expert matmuls under Mosaic. ---
    if (phase_on("E")
            and os.environ.get("POLYKEY_BENCH_SKIP_MOE", "") != "1"):
        try:
            moe_model = "mixtral-bench"
            log(f"--- phase E: {moe_model} int8 MoE engine bench ---")
            slots_m = int(os.environ.get("POLYKEY_BENCH_MOE_SLOTS", "16"))
            cfg_e = EngineConfig(
                model=moe_model,
                dtype="bfloat16",
                quantize=True,
                max_decode_slots=slots_m,
                page_size=16,
                num_pages=slots_m * 32 + 64,
                max_seq_len=512,
                prefill_buckets=(prompt_len,),
                max_new_tokens_cap=max_new,
                decode_block_steps=block,
                lookahead_blocks=lookahead,
                compile_warmup=True,
                warm_sampled_variants=False,
            )
            phase_e = bench_engine(
                cfg_e, None, 2 * slots_m, prompt_len, max_new)
            result["engine_moe"] = {"model": moe_model, **phase_e}
        except Exception as e:
            log(f"phase E failed: {e}")
            result["engine_moe"] = {"error": str(e)}

    # --- Phase C: speculative serving (config 5's mechanism on hardware).
    # Draft ≡ target (same tree), so greedy acceptance is exactly 1.0 and
    # the number is the spec machinery's ceiling: rounds of gamma draft
    # steps + one wide verify, pipelined like plain blocks. A real draft's
    # gain interpolates between this and the plain-engine number by its
    # acceptance rate. ---
    if (phase_on("C")
            and os.environ.get("POLYKEY_BENCH_SKIP_SPEC", "") != "1"):
        try:
            log("--- phase C: spec-decode engine bench (draft == target) ---")
            import dataclasses as _dc

            import jax
            import jax.numpy as jnp

            from polykey_tpu.models.config import get_config
            from polykey_tpu.models.transformer import init_params

            # One tree for both roles (the engine's own init would seed
            # the draft differently from the target).
            params1 = init_params(
                jax.random.PRNGKey(0), get_config(model_a), jnp.bfloat16)
            # compile_warmup inherits from cfg_a: spec engines warm the
            # spec prefill groups and the spec round since round 3.
            # adaptive_gamma off: draft == target accepts every draft, the
            # dial can never leave the full gamma, and the ladder's second
            # (heaviest) warmup compile would be pure waste.
            cfg_c = _dc.replace(
                cfg_a, draft_model=model_a, spec_gamma=4,
                adaptive_gamma=False, compile_warmup=True,
            )
            phase_c = bench_engine(
                cfg_c, params1, max(2, n_req // 2),
                prompt_len, max_new,
                draft_params=params1,
            )
            result["engine_spec"] = phase_c
            del params1
        except Exception as e:
            log(f"phase C failed: {e}")
            result["engine_spec"] = {"error": str(e)}

    # --- Phase C2: BASELINE config 5's actual SHAPE — a Gemma-2 target
    # server-streamed with a real smaller-family draft (2B drafting for
    # 9B, both int8; 27B exceeds one v5e's HBM — tp≥2 territory). Random
    # weights mean acceptance is noise, so the adaptive-gamma dial is
    # left ON and its collapse to the low rung is itself the evidence;
    # throughput here is a floor, not the spec win. ---
    if (phase_on("C2")
            and os.environ.get("POLYKEY_BENCH_SKIP_GEMMA_SPEC", "") != "1"):
        try:
            g_target = "gemma-2-9b"
            g_draft = "gemma-2-2b"
            log(f"--- phase C2: {g_target} int8 + {g_draft} draft ---")
            slots_g = int(os.environ.get("POLYKEY_BENCH_GEMMA_SLOTS", "8"))
            cfg_c2 = EngineConfig(
                model=g_target,
                draft_model=g_draft,
                spec_gamma=4,
                dtype="bfloat16",
                quantize=True,
                max_decode_slots=slots_g,
                page_size=16,
                num_pages=slots_g * 32 + 64,
                max_seq_len=512,
                prefill_buckets=(prompt_len,),
                max_new_tokens_cap=max_new,
                decode_block_steps=block,
                lookahead_blocks=lookahead,
                compile_warmup=True,
                warm_sampled_variants=False,
            )
            result["engine_gemma_spec"] = bench_engine(
                cfg_c2, None, 2 * slots_g, prompt_len, max_new)
        except Exception as e:
            log(f"phase C2 failed: {e}")
            result["engine_gemma_spec"] = {"error": str(e)}

    print(json.dumps(_compose_line(result)), flush=True)
    failed = _failed_phases(result)
    if failed:
        raise SystemExit(f"failed phases: {', '.join(failed)}")


if __name__ == "__main__":
    main()
