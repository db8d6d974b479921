#!/usr/bin/env python3
"""Observability smoke test (``make obs-smoke`` — grown from the PR 1
``metrics-smoke`` probe).

Boots the full serving stack on CPU with a tiny model — gRPC gateway,
TPU-service backend, observability bundle, Prometheus HTTP endpoint with
the flight-deck debug surface — runs streaming generations, and asserts:

- the required metric families (PR 1/3/4/6/9 + the ISSUE 10 attribution
  families) on /metrics and the gRPC metrics_text view;
- OpenMetrics content negotiation with parsable trace_id exemplars on
  the latency histograms;
- the /debug endpoints serve ONLY under POLYKEY_DEBUG_ENDPOINTS=1 —
  engine stats, a structurally valid Perfetto timeline, the flight
  recorder, trace-by-id round-trip — including against a 2-replica pool
  (one Perfetto process per replica);
- a profiler capture round-trip on CPU: non-empty artifact dir, and the
  single-flight guarantee (a second concurrent capture is 409).

Exit 0 means an operator gets the full flight deck, not just a page.
"""

import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Short signal-plane windows so the fault→breach→recovery cycle (ISSUE
# 11) completes in smoke time: the shortest window is the breach
# detector and must age the faulted requests out within seconds.
os.environ.setdefault("POLYKEY_SIGNALS_WINDOWS", "2,5,15")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import grpc  # noqa: E402

from polykey_tpu.engine.config import EngineConfig  # noqa: E402
from polykey_tpu.engine.engine import InferenceEngine  # noqa: E402
from polykey_tpu.gateway import server as gateway_server  # noqa: E402
from polykey_tpu.gateway.jsonlog import Logger  # noqa: E402
from polykey_tpu.gateway.tpu_service import TpuService  # noqa: E402
from polykey_tpu.obs import MetricsHTTPServer, Observability  # noqa: E402
from polykey_tpu.proto import polykey_v2_pb2 as pk  # noqa: E402
from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub  # noqa: E402

REQUIRED_FAMILIES = (
    "polykey_ttft_ms_bucket",
    "polykey_itl_ms_bucket",
    "polykey_decode_tokens_total",
    "polykey_active_requests",
    "polykey_requests_completed_total",
    "polykey_rpcs_total",
    "polykey_engine_up",
    "polykey_watchdog_stalls_total",
    "polykey_pages_free",
    # Overload-safety families (ISSUE 3): present (at 0) even on a
    # healthy stack, so dashboards/alerts can be written before the
    # first incident.
    "polykey_requests_shed_total",
    'polykey_deadline_expired_total{phase="queued"}',
    "polykey_engine_restarts_total",
    # Occupancy tracker (ISSUE 4): measured live-lane families the
    # roofline/occupancy dashboards are built on.
    "polykey_live_lanes",
    "polykey_lane_steps_total",
    "polykey_dispatched_steps_total",
    "polykey_live_lanes_per_block_bucket",
    "polykey_prefill_tokens_total",
    # Lookahead dispatch pipeline (ISSUE 6): in-flight depth gauge and
    # the host-stall histogram the "host-bound decode" runbook reads.
    "polykey_dispatch_inflight",
    "polykey_dispatch_lookahead_depth",
    "polykey_host_stall_ms_bucket",
    # Device-time attribution (ISSUE 10): the per-request device-ms
    # histogram and the device-busy fraction gauge.
    "polykey_request_device_ms_bucket",
    "polykey_device_busy_fraction",
    # SLO signal plane (ISSUE 11): family headers render whenever the
    # plane exists; objective-labeled samples are asserted by
    # slo_checks once a policy is installed.
    "polykey_slo_budget_remaining_ratio",
    "polykey_slo_burn_rate",
    "polykey_slo_breaches_total",
    # Host-memory KV tier (ISSUE 15): families render (at 0) with the
    # tier off too, so offload dashboards can exist before turn-on.
    'polykey_kv_page_faults_total{kind="prefix"}',
    'polykey_kv_page_faults_total{kind="ctx"}',
    "polykey_kv_pages_evicted_total",
    "polykey_kv_host_pages",
    "polykey_kv_device_pages",
    "polykey_kv_restore_ms_bucket",
)

# One exemplar line on the TTFT histogram, OpenMetrics syntax:
#   name_bucket{le="..."} N # {trace_id="..."} value timestamp
EXEMPLAR_RE = re.compile(
    r'polykey_ttft_ms_bucket\{le="[^"]+"\} \d+ '
    r'# \{trace_id="[A-Za-z0-9_-]{1,64}"\} \d+(\.\d+)? \d+\.\d{3}'
)

# ISSUE 16 satellites: the coordinator's handoff histogram and the
# engine's kv-restore histogram carry per-bucket trace-id exemplars too
# — the wire between "this bucket is slow" and "open THIS trace".
HANDOFF_EXEMPLAR_RE = re.compile(
    r'polykey_handoff_ms_bucket\{le="[^"]+"\} \d+ '
    r'# \{trace_id="disagg-smoke-trace-\d"\} \d+(\.\d+)?(e-?\d+)? '
    r'\d+\.\d{3}'
)
KV_EXEMPLAR_RE = re.compile(
    r'polykey_kv_restore_ms_bucket\{le="[^"]+"\} \d+ '
    r'# \{trace_id="kv-exemplar-\d+"\} \d+(\.\d+)?(e-?\d+)? \d+\.\d{3}'
)

CONFIG = EngineConfig(
    model="tiny-llama", tokenizer="byte", dtype="float32",
    max_decode_slots=4, page_size=8, num_pages=64, max_seq_len=64,
    prefill_buckets=(16, 32), max_new_tokens_cap=48,
    default_max_new_tokens=16,
    signals_interval_s=0.1,       # smoke-speed signal-plane sampling
)

# Replica-tier families (ISSUE 9): present on a pool-backed stack, with
# engine families carrying a replica label per member.
POOL_FAMILIES = (
    'polykey_requests_completed_total{replica="0"}',
    'polykey_requests_completed_total{replica="1"}',
    'polykey_ttft_ms_bucket{le="+Inf",replica="0"}',
    'polykey_replica_state{replica="0",state="SERVING"} 1',
    'polykey_replica_state{replica="1",state="SERVING"} 1',
    "polykey_replicas_serving 2",
    "polykey_requests_rerouted_total",
    "polykey_streams_resumed_total",
    'polykey_router_decisions_total{reason="least-delay"}',
    'polykey_deadline_expired_total{phase="queued",replica="1"}',
)

# Disaggregated-tier families (ISSUE 13): engine families carry
# {tier, replica} labels per worker, the handoff counters/histogram are
# coordinator-owned, and the worker state machine renders per tier.
# (The section boots 1 prefill + 2 decode workers: the second decode
# worker is the re-route target for the ISSUE 16 trace-continuity kill.)
DISAGG_FAMILIES = (
    'polykey_requests_completed_total{replica="0",tier="prefill"}',
    'polykey_requests_completed_total{replica="0",tier="decode"}',
    'polykey_ttft_ms_bucket{le="+Inf",replica="0",tier="decode"}',
    'polykey_replica_state{replica="0",state="SERVING",tier="prefill"} 1',
    'polykey_replica_state{replica="0",state="SERVING",tier="decode"} 1',
    'polykey_replicas_serving{tier="prefill"} 1',
    'polykey_replicas_serving{tier="decode"} 2',
    'polykey_handoffs_total{outcome="ok"} 1',
    "polykey_handoff_bytes_total",
    'polykey_handoff_ms_bucket{le="+Inf"} 1',
)


def scrape(port: int) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as resp:
        assert resp.status == 200, resp.status
        ctype = resp.headers["Content-Type"]
        assert "text/plain" in ctype, ctype
        return resp.read().decode()


def fetch(port: int, path: str, accept: str = "") -> tuple:
    """GET on the metrics server; returns (status, content_type, body)
    without raising on 4xx (the gating checks EXPECT 404/409)."""
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        headers={"Accept": accept} if accept else {},
    )
    try:
        with urllib.request.urlopen(request, timeout=90) as resp:
            return (resp.status, resp.headers.get("Content-Type", ""),
                    resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), e.read().decode()


def _debug_surface(service, obs):
    from polykey_tpu.obs import DebugSurface

    return DebugSurface(
        engine_provider=lambda: service.engine, obs=obs,
        profiler=service.profiler,
    )


def exemplar_checks(port: int) -> list:
    """OpenMetrics negotiation + exemplar syntax on the TTFT family."""
    failures = []
    status, ctype, body = fetch(
        port, "/metrics", accept="application/openmetrics-text"
    )
    if status != 200 or "application/openmetrics-text" not in ctype:
        failures.append(f"openmetrics scrape: {status} {ctype}")
        return failures
    if not body.rstrip().endswith("# EOF"):
        failures.append("openmetrics page missing # EOF terminator")
    if not EXEMPLAR_RE.search(body):
        failures.append("no parsable trace_id exemplar on polykey_ttft_ms")
    if "trace_id" in scrape(port):
        failures.append("classic text page leaked exemplars")
    return failures


def debug_checks(port: int, trace_id: str, expect_pids: int = 1) -> list:
    """The /debug surface: gating, engine stats, a structurally valid
    Perfetto timeline, flight recorder, trace-by-id."""
    failures = []
    os.environ.pop("POLYKEY_DEBUG_ENDPOINTS", None)
    status, _, _ = fetch(port, "/debug/engine")
    if status != 404:
        failures.append(f"/debug/engine served while gated off: {status}")
    os.environ["POLYKEY_DEBUG_ENDPOINTS"] = "1"

    status, ctype, body = fetch(port, "/debug/engine")
    if status != 200 or "json" not in ctype:
        failures.append(f"/debug/engine: {status} {ctype}")
    elif "slots_total" not in json.loads(body):
        failures.append("/debug/engine missing slots_total")

    status, _, body = fetch(port, "/debug/timeline")
    if status != 200:
        failures.append(f"/debug/timeline: {status}")
    else:
        trace = json.loads(body)
        events = trace.get("traceEvents", [])
        pids = {e.get("pid") for e in events}
        tracks = {e["args"]["name"] for e in events
                  if e.get("ph") == "M" and e.get("name") == "thread_name"}
        if len(pids) < expect_pids:
            failures.append(
                f"/debug/timeline has {len(pids)} processes, "
                f"expected >= {expect_pids}"
            )
        for track in ("dispatch frontier", "processed frontier"):
            if track not in tracks:
                failures.append(f"/debug/timeline missing track: {track}")
        if not any(e.get("ph") == "X" for e in events):
            failures.append("/debug/timeline has no slices")

    status, _, body = fetch(port, "/debug/flight")
    if status != 200 or not json.loads(body).get("traces"):
        failures.append(f"/debug/flight empty or failing: {status}")

    status, _, body = fetch(port, f"/debug/trace/{trace_id}")
    if status != 200 or json.loads(body).get("trace_id") != trace_id:
        failures.append(f"/debug/trace/{trace_id}: {status}")
    status, _, _ = fetch(port, "/debug/trace/no-such-trace")
    if status != 404:
        failures.append(f"unknown trace id returned {status}, wanted 404")
    return failures


def profiler_checks(port: int, stub, pk_mod) -> list:
    """Profiler round-trip on CPU + the single-flight guarantee across
    the two trigger surfaces (gRPC tool and HTTP endpoint)."""
    failures = []
    start = pk_mod.ExecuteToolRequest(tool_name="engine_profile")
    start.parameters.update({"action": "start"})
    stub.ExecuteTool(start, timeout=30)
    status, _, body = fetch(port, "/debug/profile?seconds=1")
    if status != 409:
        failures.append(
            f"concurrent capture got {status}, wanted 409 (single-flight)"
        )
    stop = pk_mod.ExecuteToolRequest(tool_name="engine_profile")
    stop.parameters.update({"action": "stop"})
    stub.ExecuteTool(stop, timeout=30)

    status, _, body = fetch(port, "/debug/profile?seconds=1")
    if status != 200:
        failures.append(f"/debug/profile capture failed: {status} {body}")
    else:
        result = json.loads(body)
        if result.get("files", 0) < 1:
            failures.append(f"profiler capture artifact dir empty: {result}")
    return failures


_BREACH_RE = re.compile(
    r'polykey_slo_breaches_total\{objective="ttft_fault"\} (\d+)'
)
_BURN_RE = re.compile(
    r'polykey_slo_burn_rate\{objective="ttft_fault",window="2s"\} '
    r'([0-9.]+)'
)


def slo_checks(port: int, stub, service) -> list:
    """The ISSUE 11 closed-loop cycle against the live stack: a
    mid-run injected slow-step fault drives TTFT burn rate > 1,
    increments polykey_slo_breaches_total, lands the breach on the
    timeline, flight recorder, and /debug/slo — and the budget burn
    STOPS once the fault clears (recovery event + burn back under 1)."""
    from polykey_tpu import faults
    from polykey_tpu.obs.signals import SloObjective, SloPolicy

    failures: list[str] = []
    engine = service.engine
    plane = engine.metrics.signals
    if plane is None:
        return ["signal plane missing on the smoke engine"]
    plane.set_policy(SloPolicy(objectives=(
        SloObjective(name="ttft_fault", kind="latency", signal="ttft_ms",
                     threshold_ms=900.0, target=0.7),
    )))

    def gen(prompt: str) -> None:
        request = pk.ExecuteToolRequest(tool_name="llm_generate")
        request.parameters.update({"prompt": prompt, "max_tokens": 16})
        chunks = list(stub.ExecuteToolStream(request, timeout=120))
        assert chunks[-1].final

    def breaches() -> int:
        match = _BREACH_RE.search(scrape(port))
        return int(match.group(1)) if match else 0

    # Clean traffic: the short window holds good evidence before the
    # fault lands (and pins that clean serving does not breach).
    for i in range(3):
        gen(f"slo clean {i}")
    time.sleep(0.3)
    plane.sample_now()
    breaches_before = breaches()

    # Mid-run fault: hand a fresh injector to the LIVE engine (engines
    # cache it at construction); every decode dispatch now sleeps 1.1 s
    # so TTFT blows the 900 ms threshold. Budget-bounded so it cannot
    # outlive this check.
    engine._faults = faults.install("slow-step=1.1@10")
    try:
        for i in range(2):
            gen(f"slo fault {i}")
        plane.sample_now()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if breaches() > breaches_before:
                break
            time.sleep(0.3)
            plane.sample_now()
        else:
            failures.append(
                "fault never incremented polykey_slo_breaches_total"
            )
        match = _BURN_RE.search(scrape(port))
        if match is None or float(match.group(1)) <= 1.0:
            failures.append(
                f"TTFT burn rate not > 1 under fault (got "
                f"{match.group(1) if match else 'no sample'})"
            )
    finally:
        faults.clear()
        engine._faults = None

    os.environ["POLYKEY_DEBUG_ENDPOINTS"] = "1"
    status, ctype, body = fetch(port, "/debug/slo")
    if status != 200 or "json" not in ctype:
        failures.append(f"/debug/slo: {status} {ctype}")
    else:
        snap = json.loads(body)
        slo = snap.get("replicas", {}).get("0", {}).get("slo", {})
        if "ttft_fault" not in slo:
            failures.append("/debug/slo missing the ttft_fault objective")
        if snap.get("gateway", {}).get("rpcs_ok", 0) < 1:
            failures.append("/debug/slo missing gateway availability")
    os.environ.pop("POLYKEY_DEBUG_ENDPOINTS", None)
    status, _, _ = fetch(port, "/debug/slo")
    if status != 404:
        failures.append(f"/debug/slo served while gated off: {status}")
    os.environ["POLYKEY_DEBUG_ENDPOINTS"] = "1"

    # Recovery: clean traffic ages the faulted TTFTs out of the short
    # window; burn must drop back under 1 (breached flag clears) and
    # the breach counter must stop moving.
    breaches_peak = breaches()
    recovered = False
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        gen("slo recovery probe")
        time.sleep(0.3)
        plane.sample_now()
        state = plane.slo_state().get("ttft_fault", {})
        if state and not state.get("breached"):
            recovered = True
            break
    if not recovered:
        failures.append("burn never recovered after the fault cleared")
    if breaches() != breaches_peak:
        failures.append("breach counter kept burning after recovery")

    # The cycle is visible on the flight deck: timeline notes + flight
    # recorder events for both transitions.
    status, _, body = fetch(port, "/debug/timeline")
    names = {e.get("name") for e in json.loads(body).get("traceEvents", [])} \
        if status == 200 else set()
    for note in ("slo_breach", "slo_recovered"):
        if note not in names:
            failures.append(f"timeline missing {note} note")
    status, _, body = fetch(port, "/debug/flight")
    kinds = {e.get("kind") for e in json.loads(body).get("events", [])} \
        if status == 200 else set()
    if "slo_breach" not in kinds:
        failures.append("flight recorder missing slo_breach event")

    plane.set_policy(None)
    os.environ.pop("POLYKEY_DEBUG_ENDPOINTS", None)
    return failures


def pool_smoke() -> list:
    """Replica-tier exposition (ISSUE 9): boot a 2-replica pool behind
    the same gateway wiring, drive both replicas (two concurrent
    generations — the router load-balances the second away from the
    first), and assert the replica-labeled engine families, the
    pool-tier families, and that engine_stats aggregates across
    replicas."""
    import dataclasses

    from polykey_tpu.engine.replica_pool import ReplicaPool

    print("booting 2-replica pool on CPU ...", flush=True)
    logger = Logger(stream=open(os.devnull, "w"))
    obs = Observability()
    config = dataclasses.replace(CONFIG, replicas=2)
    pool = ReplicaPool.create(config, logger=logger, obs=obs)
    service = TpuService.create(pool, logger=logger, obs=obs)
    server, _, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0", obs=obs
    )
    server.start()
    metrics = MetricsHTTPServer(obs.registry, host="127.0.0.1", port=0,
                                debug=_debug_surface(service, obs))
    metrics.start()

    failures: list[str] = []
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        stub = PolykeyServiceStub(channel)

        def generate(prompt):
            request = pk.ExecuteToolRequest(tool_name="llm_generate")
            request.parameters.update({"prompt": prompt, "max_tokens": 24})
            chunks = list(stub.ExecuteToolStream(request, timeout=120))
            assert chunks[-1].final

        # Two concurrent streams: the second routes to the other replica
        # (least-delay), so BOTH replicas record completions.
        threads = [
            threading.Thread(target=generate, args=(f"pool smoke {i}",))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "pool generation did not finish"

        page = scrape(metrics.port)
        for family in POOL_FAMILIES:
            if family not in page:
                failures.append(f"pool page missing: {family}")

        # engine_stats must aggregate across replicas: the top-level
        # completed count is the sum of the per-replica ones.
        stats = dict(
            stub.ExecuteTool(
                pk.ExecuteToolRequest(tool_name="engine_stats"), timeout=30
            ).struct_output
        )
        per = [dict(s) for s in stats.get("per_replica", [])]
        if stats.get("replicas_total") != 2 or len(per) != 2:
            failures.append("engine_stats missing per_replica for 2 replicas")
        else:
            total = sum(s.get("requests_completed", 0) for s in per)
            if stats.get("requests_completed") != total or total < 4:
                failures.append(
                    "engine_stats requests_completed does not aggregate: "
                    f"top={stats.get('requests_completed')} sum={total}"
                )
            if min(s.get("requests_completed", 0) for s in per) < 1:
                failures.append(
                    "router never load-balanced: a replica served nothing"
                )

        # Debug surface against the pool: the Perfetto export must carry
        # one process per replica, each with its own frontier tracks.
        os.environ["POLYKEY_DEBUG_ENDPOINTS"] = "1"
        status, _, body = fetch(metrics.port, "/debug/timeline")
        if status != 200:
            failures.append(f"pool /debug/timeline: {status}")
        else:
            events = json.loads(body).get("traceEvents", [])
            pids = {e.get("pid") for e in events}
            if len(pids) < 2:
                failures.append(
                    f"pool timeline has {len(pids)} processes, wanted 2"
                )
        status, _, body = fetch(metrics.port, "/debug/engine")
        if status != 200 or json.loads(body).get("replicas_total") != 2:
            failures.append(f"pool /debug/engine: {status}")
        channel.close()
    finally:
        metrics.stop()
        server.stop(grace=None)
        service.close()
        os.environ.pop("POLYKEY_DEBUG_ENDPOINTS", None)
    return failures


def spec_family_checks() -> list:
    """Speculative-decode exposition (ISSUE 19 satellite): boot a spec
    engine (seed+2 draft — quality is irrelevant, the families are the
    subject), serve one greedy generation, and assert the per-lane dial
    gauges (stat-labeled mean/min/max — the engine-global gamma died
    with the per-lane redesign) plus the draft counters render. Guards
    the `snap["spec_gamma"]` shape the exposition indexes: stats() once
    exported a bare int here and the collector silently skipped the
    family."""
    import dataclasses

    print("booting spec engine on CPU ...", flush=True)
    logger = Logger(stream=open(os.devnull, "w"))
    obs = Observability()
    config = dataclasses.replace(
        CONFIG, draft_model="tiny-llama", spec_gamma=2
    )
    engine = InferenceEngine(config, logger=logger)
    service = TpuService.create(engine, logger=logger, obs=obs)
    server, _, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0", obs=obs
    )
    server.start()
    metrics = MetricsHTTPServer(obs.registry, host="127.0.0.1", port=0)
    metrics.start()

    failures: list[str] = []
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        stub = PolykeyServiceStub(channel)
        request = pk.ExecuteToolRequest(tool_name="llm_generate")
        request.parameters.update({"prompt": "spec smoke", "max_tokens": 24})
        chunks = list(stub.ExecuteToolStream(request, timeout=120))
        assert chunks[-1].final
        channel.close()

        page = scrape(metrics.port)
        for family in (
            'polykey_spec_gamma{stat="mean"}',
            'polykey_spec_gamma{stat="min"}',
            'polykey_spec_gamma{stat="max"}',
            'polykey_spec_accept_rate{stat="mean"}',
            'polykey_spec_accept_rate{stat="min"}',
            'polykey_spec_accept_rate{stat="max"}',
            "polykey_spec_drafts_proposed_total",
            "polykey_spec_drafts_accepted_total",
        ):
            if family not in page:
                failures.append(f"spec page missing: {family}")
        snap = engine.stats()
        for key in ("spec_gamma_mean", "spec_gamma_min", "spec_gamma_max",
                    "spec_accept_ewma_mean"):
            if key not in snap:
                failures.append(f"engine stats missing {key}")
        if not snap.get("drafts_proposed"):
            failures.append("spec engine proposed no drafts")
    finally:
        metrics.stop()
        server.stop(grace=None)
        service.close()
    return failures


def disagg_smoke() -> list:
    """Disaggregated-tier exposition (ISSUE 13 + 16): one prefill + two
    decode workers (in-process servers over real localhost sockets)
    behind the coordinator. A clean generation asserts the tier-labeled
    engine families, the handoff families, and the pool timeline's
    handoff lifecycle notes; then a decode worker is killed mid-stream
    and the gateway trace id must survive the re-route — the same id on
    the coordinator's handoff_start/abort/ack notes, on both workers'
    grafted span subtrees, and as a per-bucket exemplar on the handoff
    histogram's OpenMetrics page."""
    from polykey_tpu import faults
    from polykey_tpu.engine.disagg_pool import DisaggPool
    from polykey_tpu.engine.worker import WorkerServer
    from polykey_tpu.obs import Span
    from polykey_tpu.obs.timeline import engine_timelines, to_perfetto
    from polykey_tpu.obs.trace import set_current_span

    print("booting 1-prefill/2-decode disagg pool on CPU ...", flush=True)
    logger = Logger(stream=open(os.devnull, "w"))
    obs = Observability()
    workers = [
        WorkerServer(CONFIG, tier=tier, replica=replica, seed=5,
                     exit_mode="simulate").start()
        for tier, replica in (("prefill", 0), ("decode", 0), ("decode", 1))
    ]
    pool = DisaggPool.create(
        CONFIG,
        workers=[(w.tier, ("127.0.0.1", w.port)) for w in workers],
        logger=logger, obs=obs,
    )
    service = TpuService.create(pool, logger=logger, obs=obs)
    failures: list[str] = []

    def generate(trace_id: str, prompt: str) -> bool:
        """One generation with a gateway span installed — the same
        x-trace-id channel the interceptor uses, minus the socket."""
        from google.protobuf import struct_pb2

        span = Span("gateway", trace_id=trace_id)
        set_current_span(span)
        try:
            params = struct_pb2.Struct()
            params.update({"prompt": prompt, "max_tokens": 8})
            response = service.execute_tool("llm_generate", params,
                                            None, None)
            return response.status.code == 200
        finally:
            set_current_span(None)

    def coord_notes(note_kind: str) -> list:
        return [e for e in pool.timeline.events()
                if e["kind"] == "note" and e["note_kind"] == note_kind]

    try:
        if not generate("disagg-smoke-trace-0", "disagg obs smoke"):
            failures.append("disagg llm_generate failed")
        page = obs.registry.render()
        for family in DISAGG_FAMILIES:
            if family not in page:
                failures.append(f"disagg page missing: {family}")
        # Handoff lifecycle on the pool timeline → Perfetto export.
        notes = [e.get("note_kind") for e in pool.timeline.events()
                 if e["kind"] == "note"]
        for kind in ("handoff_start", "handoff_ack"):
            if kind not in notes:
                failures.append(f"pool timeline missing {kind} note")
        names = {e.get("name")
                 for e in to_perfetto(
                     engine_timelines(pool))["traceEvents"]}
        if "handoff_ack" not in names:
            failures.append("perfetto export missing handoff_ack")

        # ISSUE 16: kill WHICHEVER decode worker takes the request after
        # 3 streamed tokens (tier-scoped, shared @1 budget — the NetKV
        # router's pick is load-dependent, the kill must not miss); the
        # re-routed request must keep its trace id end to end.
        faults.install("worker-exit=3@1:tier=decode")
        try:
            if not generate("disagg-smoke-trace-1", "disagg reroute smoke"):
                failures.append("disagg re-routed llm_generate failed")
        finally:
            faults.clear()
        for kind in ("handoff_start", "handoff_abort", "handoff_ack"):
            if not any(e["attrs"].get("trace") == "disagg-smoke-trace-1"
                       for e in coord_notes(kind)):
                failures.append(
                    f"coordinator {kind} notes lost the trace id "
                    "across the re-route"
                )
        aborts = [e for e in coord_notes("handoff_abort")
                  if e["attrs"].get("trace") == "disagg-smoke-trace-1"]
        start_ids = {e["attrs"].get("handoff_id")
                     for e in coord_notes("handoff_start")}
        if aborts and aborts[0]["attrs"].get("handoff_id") not in start_ids:
            failures.append("handoff_abort does not join a handoff_start")

        # Per-bucket trace-id exemplar on the coordinator's handoff
        # histogram — OpenMetrics page only, classic page stays clean.
        om_page = obs.registry.render(openmetrics=True)
        if not HANDOFF_EXEMPLAR_RE.search(om_page):
            failures.append(
                "no trace_id exemplar on polykey_handoff_ms buckets"
            )
        if "trace_id" in obs.registry.render():
            failures.append("classic disagg page leaked exemplars")

        # Clock-aligned merged timeline: one process row per live worker
        # plus the coordinator, handoff arcs causally ordered. The
        # killed decode worker's row is allowed to be absent: this
        # in-process smoke runs without a state dir, so a severed worker
        # has no black-box fallback (postmortem-smoke covers that path).
        merged = pool.merged_perfetto()
        events = merged.get("traceEvents", [])
        pids = {e.get("pid") for e in events}
        if len(pids) < 3:
            failures.append(
                f"merged perfetto has {len(pids)} process rows, wanted 3"
            )
        arc_s = {e["id"]: e for e in events if e.get("ph") == "s"}
        arc_f = {e["id"]: e for e in events if e.get("ph") == "f"}
        matched = set(arc_s) & set(arc_f)
        if not matched:
            failures.append("merged perfetto has no matched handoff arc")
        if any(arc_s[i]["ts"] > arc_f[i]["ts"] for i in matched):
            failures.append("a handoff arc runs backwards in time")
    finally:
        service.close()
        for worker in workers:
            worker.stop()
    return failures


def kv_exemplar_checks() -> list:
    """ISSUE 16 satellite: the host-KV tier's restore histogram carries
    per-bucket trace-id exemplars. A deliberately tiny device pool
    (test_host_kv geometry) forces sticky-session prefixes to spill to
    host and fault back in on revisit; each revisit rides a gateway
    span, so the restore that slowed a request names that request."""
    import dataclasses

    from polykey_tpu.obs import Span
    from polykey_tpu.obs.trace import set_current_span

    print("booting host-KV engine for restore exemplars ...", flush=True)
    config = dataclasses.replace(
        CONFIG, num_pages=24, max_decode_slots=4, prefill_chunk=16,
        prefix_cache=True, host_kv_bytes=64 << 20,
        host_kv_resident_pages=12, default_max_new_tokens=8,
    )
    logger = Logger(stream=open(os.devnull, "w"))
    obs = Observability()
    engine = InferenceEngine(config, logger=logger)
    service = TpuService.create(engine, logger=logger, obs=obs)
    failures: list[str] = []
    try:
        from google.protobuf import struct_pb2

        sessions = [
            f"session {s} header padded out to be long enough xx"
            for s in range(4)
        ]
        # First pass seeds + spills the prefixes; the revisit pass
        # faults them back in from host (the restores we exemplar).
        for index, prompt in enumerate(sessions + sessions):
            span = Span("gateway", trace_id=f"kv-exemplar-{index}")
            set_current_span(span)
            try:
                params = struct_pb2.Struct()
                params.update({"prompt": prompt, "max_tokens": 8})
                response = service.execute_tool("llm_generate", params,
                                                None, None)
                if response.status.code != 200:
                    failures.append(f"host-KV generation {index} failed")
            finally:
                set_current_span(None)
        stats = engine.stats()
        restored = (stats.get("kv_page_faults_prefix", 0)
                    + stats.get("kv_page_faults_ctx", 0))
        if restored < 1:
            failures.append(
                "host-KV drill caused no page faults — the pool is not "
                "tight enough to exercise restores"
            )
        if not KV_EXEMPLAR_RE.search(obs.registry.render(openmetrics=True)):
            failures.append(
                "no trace_id exemplar on polykey_kv_restore_ms buckets"
            )
    finally:
        service.close()
    return failures


def main() -> int:
    logger = Logger(stream=open(os.devnull, "w"))
    obs = Observability()
    print("booting tiny engine on CPU ...", flush=True)
    engine = InferenceEngine(CONFIG, logger=logger)
    # Same factory from_env uses — the smoke probe exercises exactly the
    # production service/watchdog/obs wiring.
    service = TpuService.create(engine, logger=logger, obs=obs)
    server, _, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0", obs=obs
    )
    server.start()
    metrics = MetricsHTTPServer(obs.registry, host="127.0.0.1", port=0,
                                debug=_debug_surface(service, obs))
    metrics.start()
    print(f"gateway :{port}  metrics :{metrics.port}/metrics", flush=True)

    trace_id = "obs-smoke-trace-1"
    failures: list[str] = []
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        stub = PolykeyServiceStub(channel)
        request = pk.ExecuteToolRequest(tool_name="llm_generate")
        request.parameters.update(
            {"prompt": "metrics smoke", "max_tokens": 32}
        )

        mid_stream_page = {}

        def generate():
            chunks = list(stub.ExecuteToolStream(
                request, timeout=120,
                metadata=(("x-trace-id", trace_id),),
            ))
            assert chunks[-1].final

        gen = threading.Thread(target=generate)
        gen.start()
        # Scrape while the stream is (likely) in flight — the endpoint
        # must serve concurrently with the engine loop.
        mid_stream_page["text"] = scrape(metrics.port)
        gen.join(timeout=120)
        assert not gen.is_alive(), "generation did not finish"

        page = scrape(metrics.port)
        for family in REQUIRED_FAMILIES:
            if family not in page:
                failures.append(f"missing family: {family}")
        if 'polykey_ttft_ms_bucket{le="+Inf"} 0' in page:
            failures.append("ttft histogram recorded no observations")
        if "polykey_engine_up 1" not in page:
            failures.append("engine_up gauge not 1")
        # The mid-stream scrape's real assertion is that it SUCCEEDED
        # (scrape() raises otherwise): the endpoint serves a valid page
        # concurrently with the engine loop. Check the page parsed.
        if not mid_stream_page["text"].startswith("# HELP"):
            failures.append("mid-stream scrape returned malformed page")

        # The gRPC metrics_text view must match the HTTP page's families.
        req = pk.ExecuteToolRequest(tool_name="engine_stats")
        req.parameters.update({"view": "metrics_text"})
        grpc_page = stub.ExecuteTool(req, timeout=30).string_output
        for family in REQUIRED_FAMILIES:
            if family not in grpc_page:
                failures.append(f"gRPC metrics_text missing: {family}")

        # And the span tree for the request must be retrievable.
        stats = dict(
            stub.ExecuteTool(
                pk.ExecuteToolRequest(tool_name="engine_stats"), timeout=30
            ).struct_output
        )
        if "last_trace" not in stats:
            failures.append("engine_stats has no last_trace")
        else:
            names = {c["name"] for c in dict(stats["last_trace"])["children"]}
            for phase in ("queue_wait", "prefill_wait", "prefill", "decode",
                          "detokenize"):
                if phase not in names:
                    failures.append(f"last_trace missing {phase} span")

        # ISSUE 10 surfaces: exemplars, debug endpoints, profiler.
        failures += exemplar_checks(metrics.port)
        failures += debug_checks(metrics.port, trace_id)
        failures += profiler_checks(metrics.port, stub, pk)
        # ISSUE 11: the SLO fault→breach→recovery cycle.
        failures += slo_checks(metrics.port, stub, service)
        channel.close()
    finally:
        metrics.stop()
        server.stop(grace=None)
        service.close()
        os.environ.pop("POLYKEY_DEBUG_ENDPOINTS", None)

    failures += spec_family_checks()
    failures += pool_smoke()
    failures += disagg_smoke()
    failures += kv_exemplar_checks()

    if failures:
        print("obs-smoke FAILED:")
        for failure in failures:
            print(f"  - {failure}")
        return 1
    print(f"obs-smoke OK: {len(REQUIRED_FAMILIES)} families present, "
          "span tree complete, exemplars parse, debug surface gated + "
          "serving, profiler single-flight round-trip, "
          "SLO fault→breach→recovery cycle closed, "
          f"{len(POOL_FAMILIES)} replica-pool families present, "
          "engine_stats aggregates across replicas, "
          f"{len(DISAGG_FAMILIES)} disagg-tier families present with "
          "handoff lifecycle on the pool timeline, trace-id continuity "
          "across a disagg re-route, handoff + kv-restore exemplars on "
          "the OpenMetrics page, merged perfetto arcs causally ordered")
    return 0


if __name__ == "__main__":
    sys.exit(main())
