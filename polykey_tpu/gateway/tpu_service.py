"""TPU backend for the Service seam: tool calls → inference engine.

What the north star describes as the `tpu` provider: the gRPC contract stays
exactly the reference's (tool_name + Struct parameters in, oneof output out —
internal/service/service.go:13-15), but `llm_generate` runs on the co-located
serving engine instead of proxying to an external API. Zero external calls.

Tools:
- ``llm_generate`` (alias ``generate``) — params: prompt (string, required),
  max_tokens, temperature, top_p, top_k, seed, stop (string or list of strings:
  generation cuts BEFORE the earliest match, which is never emitted; the
  engine request is cancelled so no further compute is spent). Unary
  returns the full completion as string_output; the streaming RPC emits
  incremental UTF-8-safe deltas and a terminal chunk with Usage (TTFT,
  tok/s).
- ``engine_stats`` — struct_output snapshot of engine metrics and pool state,
  including TTFT/ITL percentiles and the most recent traced request's span
  tree. ``view: "metrics_text"`` returns the Prometheus text page as
  string_output (same bytes as the HTTP /metrics endpoint — scrapeable over
  gRPC when no sidecar port is exposed); ``view: "trace"`` returns the
  recent span trees + flight-recorder events for postmortems.
- the reference's mock tools (example_tool / struct_tool / file_tool) keep
  their exact semantics via delegation to MockService, so a client of the
  reference sees no behavior change for non-LLM tools (including the
  unknown-tool-is-success contract, mock.go:60-63).
"""

from __future__ import annotations

import math
import os
import queue
import time
from typing import Iterator, Optional

from ..engine.config import EngineConfig, enable_persistent_compile_cache
from ..engine.device import require_accelerator
from ..engine.engine import (
    DEADLINE_MSG,
    EngineDeadError,
    EngineOverloadedError,
    GenRequest,
    InferenceEngine,
)
from ..engine.replica_pool import ReplicaPool
from ..engine.supervisor import EngineSupervisor
from ..engine.tokenizer import ByteTokenizer, IncrementalDetokenizer
from ..engine.watchdog import Watchdog
from ..obs import Observability, current_span, engine_collector
from ..obs.profiler import ProfilerCapture
from ..proto import common_v2_pb2 as cmn
from ..proto import polykey_v2_pb2 as pk
from . import errors
from .mock_service import MockService
from .service import Service
from google.protobuf import struct_pb2

_LLM_TOOLS = frozenset({"llm_generate", "generate"})


class TpuService(Service):
    def __init__(
        self,
        engine: InferenceEngine,
        watchdog: Optional[Watchdog] = None,
        secrets=None,
        logger=None,
        obs: Optional[Observability] = None,
    ):
        self.engine = engine
        self.watchdog = watchdog
        # Set by create() when supervision is on; the supervisor swaps
        # `self.engine` to the fresh instance after every restart.
        self.supervisor: Optional[EngineSupervisor] = None
        # Set by from_env() when POLYKEY_AUTOPILOT=1: the closed-loop
        # controller thread (engine/autopilot.py); close() stops it
        # before the engine so no actuation races the teardown.
        self.autopilot = None
        self.secrets = secrets      # gateway.security.SecretStore or None
        self.logger = logger
        self.obs = obs
        self.stall_counter = None
        self.restart_counter = None
        self._mock = MockService()
        # Single-flight profiler shared by the engine_profile tool AND
        # the /debug/profile HTTP trigger (obs/profiler.py): whichever
        # surface starts a capture, the other sees "busy" — jax's
        # profiler is process-global and two overlapping captures
        # corrupt each other's artifacts.
        self.profiler = ProfilerCapture(
            recorder=obs.recorder if obs is not None else None
        )
        if obs is not None:
            # SLO breach events reach the flight recorder (ISSUE 11):
            # every replica's signal plane gets the shared recorder so
            # breaches sit next to watchdog trips in /debug/flight.
            from ..obs.signals import bind_recorder

            bind_recorder(engine, obs.recorder)
            # Bind the engine into the scrape registry. A registry holds
            # ONE engine's families (the names carry no engine label):
            # first service to register wins, later services sharing the
            # Observability (in-process tests) reuse its families. The
            # stall counter is get-or-created independently so watchdog
            # accounting never depends on who registered the gauge.
            from ..obs import Counter, Gauge

            # Scrape through `self.engine`, not the constructor arg: a
            # supervised restart swaps the attribute, and the collector
            # must follow to the live engine.
            up_gauge, created = obs.registry.get_or_create(
                Gauge,
                "polykey_engine_up",
                "1 while the engine thread is alive.",
                fn=lambda: 0.0 if self.engine.dead else 1.0,
            )
            if created:
                obs.registry.register_collector(
                    engine_collector(lambda: self.engine)
                )
            self.stall_counter, _ = obs.registry.get_or_create(
                Counter,
                "polykey_watchdog_stalls_total",
                "Watchdog trips on a wedged engine step loop.",
            )
            self.restart_counter, _ = obs.registry.get_or_create(
                Counter,
                "polykey_engine_restarts_total",
                "Supervised in-process engine restarts.",
            )

    @classmethod
    def create(
        cls, engine: InferenceEngine, health=None, logger=None,
        secrets=None, obs: Optional[Observability] = None,
        engine_factory=None,
    ) -> "TpuService":
        """Build a service with its watchdog — and, when
        `engine.config.supervise` (the default), its supervisor — fully
        wired. Everything is built after the service so the
        observability hooks (flight-recorder events, stall + restart
        counters) come from the shared bundle — the ONE place this
        wiring lives (from_env and the metrics-smoke probe both call it,
        so they can't drift apart). `engine_factory` overrides how a
        replacement engine is built on supervised restart (default:
        reconstruct from the same config).

        A `ReplicaPool` passes through as-is: the pool already owns a
        watchdog and supervisor PER REPLICA (plus the aggregate-health
        wiring), so the single-engine supervision built here would be
        redundant and wrong (one watchdog cannot watch N engines). A
        `DisaggPool` (ISSUE 13) passes through for the same reason —
        its supervision lives inside each worker process, its liveness
        in the coordinator's heartbeat."""
        from ..engine.disagg_pool import DisaggPool

        service = cls(engine, None, secrets=secrets, logger=logger, obs=obs)
        if isinstance(engine, (ReplicaPool, DisaggPool)):
            return service
        recorder = obs.recorder if obs is not None else None
        watchdog = Watchdog(
            engine, health=health, logger=logger,
            recorder=recorder,
            stall_counter=service.stall_counter,
        )
        watchdog.start()
        service.watchdog = watchdog
        if engine.config.supervise:
            config = engine.config
            # The default factory replays the original constructor inputs
            # (raw params/seed/draft_params captured at engine init): a
            # restart must rebuild the SAME model — silently swapping in
            # a fresh random init would serve garbage with 200s.
            ctor = engine._ctor_args
            factory = engine_factory or (
                lambda: InferenceEngine(
                    config, params=ctor["params"], health=health,
                    logger=logger, seed=ctor["seed"],
                    draft_params=ctor["draft_params"],
                )
            )
            supervisor = EngineSupervisor(
                engine, factory,
                watchdog=watchdog, health=health, logger=logger,
                recorder=recorder,
                restart_counter=service.restart_counter,
                max_restarts=config.max_engine_restarts,
                restart_window_s=config.restart_window_s,
            )
            supervisor.add_restart_listener(
                lambda fresh: setattr(service, "engine", fresh)
            )
            supervisor.start()
            service.supervisor = supervisor
        return service

    @classmethod
    def from_env(
        cls, health=None, logger=None,
        obs: Optional[Observability] = None,
    ) -> "TpuService":
        from .security import SecretStore

        config = EngineConfig.from_env()
        # Persistent XLA compile cache at the SERVER entrypoint (not in
        # the engine constructor: embedders and tests shouldn't have
        # global jax config mutated under them), before the first jit.
        enable_persistent_compile_cache()
        # Where the backend is chosen is where a missing chip is refused:
        # POLYKEY_BACKEND=tpu never serves from JAX's silent CPU fallback.
        identity = require_accelerator()
        if config.disagg:
            # Disaggregated tiers (ISSUE 13): POLYKEY_DISAGG="PxD"
            # spawns prefill/decode worker PROCESSES behind the
            # coordinator. Unset (default) never takes this branch — no
            # processes, no pool, single-process paths byte-identical.
            from ..engine.disagg_pool import DisaggPool

            engine = DisaggPool.create(
                config, health=health, logger=logger, obs=obs,
                state_dir=os.environ.get("POLYKEY_DISAGG_STATE_DIR")
                or None,
            )
        elif config.replicas > 1:
            # Replica tier (ISSUE 9): POLYKEY_REPLICAS engines behind
            # the routing pool. POLYKEY_REPLICAS=1 (default) never takes
            # this branch — the single-engine wiring below is unchanged.
            engine = ReplicaPool.create(
                config, health=health, logger=logger, obs=obs,
            )
        else:
            engine = InferenceEngine(config, health=health, logger=logger)
        service = cls.create(
            engine, health=health, logger=logger,
            secrets=SecretStore.from_env(logger), obs=obs,
        )
        # Close the control loop (ISSUE 18): POLYKEY_AUTOPILOT=1 arms
        # the supervised controller thread over whatever target this
        # process serves (bare engine, replica pool, or disagg
        # coordinator). Default off — unset, nothing constructs and
        # every existing path is byte-identical. A start-time refusal
        # (signal plane disabled) propagates: that misconfiguration
        # must fail the boot, not silently serve an inert controller.
        from ..engine.autopilot import maybe_start

        service.autopilot = maybe_start(
            service.engine, supervisor=service.supervisor,
            obs=obs, logger=logger,
        )
        if logger is not None:
            logger.info(
                "engine initialized",
                **identity,
                model=config.model,
                replicas=config.replicas,
                slots=config.max_decode_slots,
                pages=config.num_pages,
                page_size=config.page_size,
            )
        return service

    def _resolve_secret(self, secret_id) -> None:
        """Resolve `secret_id` through the encrypted store (the consumption
        the reference's dead cipher adapter was scaffolding for). Unknown
        ids are NOT errors — the reference ignores secret_id entirely, so
        resolution only adds observability, never failure."""
        if not secret_id or self.secrets is None:
            return
        resolved = self.secrets.resolve(secret_id) is not None
        if self.logger is not None:
            self.logger.info(
                "secret resolved" if resolved else "secret unknown",
                secret_id=secret_id,
            )

    def close(self) -> None:
        if self.autopilot is not None:
            self.autopilot.stop()
        if self.supervisor is not None:
            self.supervisor.stop()
        if self.watchdog is not None:
            self.watchdog.stop()
        self.engine.shutdown()

    # -- request plumbing ---------------------------------------------------

    def _build_request(self, parameters: Optional[struct_pb2.Struct]) -> GenRequest:
        params = dict(parameters) if parameters is not None else {}
        prompt = params.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            raise ValueError("llm_generate requires a non-empty string 'prompt'")
        cfg = self.engine.config
        return GenRequest(
            prompt=prompt,
            # The RPC's remaining budget, published thread-locally by the
            # handler (gateway.errors): the engine drops the request the
            # moment it can no longer finish in time.
            deadline=errors.rpc_deadline(),
            max_new_tokens=int(params.get("max_tokens", cfg.default_max_new_tokens)),
            # Clamp client-supplied knobs into sane ranges rather than letting
            # degenerate values (negative temp, top_p=0) reach the sampler.
            temperature=max(0.0, float(params.get("temperature", 0.0))),
            top_p=min(1.0, max(0.0, float(params.get("top_p", 1.0)))),
            # top_k <= 0 disables; fractional values are client bugs.
            top_k=self._parse_top_k(params),
            # Reproducible sampling: same (prompt, seed, sampling) → same
            # stream regardless of batch composition (engine.GenRequest).
            seed=self._parse_seed(params),
        )

    @staticmethod
    def _parse_top_k(params: dict) -> int:
        kv = params.get("top_k", 0)
        if isinstance(kv, float) and (not math.isfinite(kv) or kv != int(kv)):
            raise ValueError("'top_k' must be a non-negative integer")
        k = int(kv)
        if k < 0:
            raise ValueError("'top_k' must be a non-negative integer")
        return k

    @staticmethod
    def _parse_seed(params: dict):
        if "seed" not in params:
            return None
        sv = params["seed"]
        # Struct numbers are IEEE doubles: beyond 2^53 distinct integers
        # collapse to the same float, silently breaking the documented
        # distinct-seeds-never-collide contract — reject instead.
        if isinstance(sv, float) and (
            not math.isfinite(sv) or sv != int(sv) or abs(sv) > 2 ** 53
        ):
            raise ValueError(
                "'seed' must be an integer with |seed| <= 2**53 (JSON "
                "numbers are doubles; larger seeds would silently collide)"
            )
        return int(sv)

    def _submit(self, request: GenRequest) -> None:
        """Submit with the overload contract mapped to typed RPC errors:
        sheds → RESOURCE_EXHAUSTED (+ retry-after-ms trailer), dead /
        restarting engine → UNAVAILABLE (retryable — the supervisor is
        probably already bringing a fresh engine up)."""
        try:
            self.engine.submit(request)
        except EngineOverloadedError as e:
            raise errors.ResourceExhaustedError(
                str(e), retry_after_ms=e.retry_after_ms
            ) from e
        except EngineDeadError as e:
            # The no-healthy-replica path (replica/disagg pools) carries
            # an estimated-recovery hint: without the trailer, every
            # shed-free client hammers a recovering tier at its own
            # backoff schedule instead of the server's (ISSUE 13 fix).
            trailers: tuple = ()
            retry_after = getattr(e, "retry_after_ms", None)
            if retry_after is not None:
                trailers = (
                    (errors.RETRY_AFTER_MS_KEY, str(int(retry_after))),
                )
            raise errors.UnavailableError(str(e), trailers=trailers) from e

    @staticmethod
    def _engine_error(message: str, delivered: Optional[int] = None) -> Exception:
        """Map an engine failure event to the RPC status contract:
        deadline expiries → DEADLINE_EXCEEDED (never retryable); engine
        lifecycle failures (dead / shut down / restarting — all begin
        "engine") → UNAVAILABLE (retryable); anything else keeps the
        reference's Unknown mapping.

        `delivered` (streaming only) is the count of tokens the client
        has already received: UNAVAILABLE then carries the mid-stream
        resume contract in trailing metadata — `resume-supported` plus
        `resume-tokens` — so a resuming client re-issues the request
        with `received_tokens` and gets only the missing suffix."""
        if message.startswith(DEADLINE_MSG):
            return errors.DeadlineExceededError(message)
        if message.startswith("engine"):
            trailers: tuple = ()
            if delivered is not None:
                trailers = (
                    (errors.RESUME_SUPPORTED_KEY, "1"),
                    (errors.RESUME_TOKENS_KEY, str(int(delivered))),
                )
            return errors.UnavailableError(message, trailers=trailers)
        return RuntimeError(message)

    @staticmethod
    def _parse_received(params: dict) -> int:
        """`received_tokens`: how many tokens this client already holds
        from an interrupted stream (the resume-tokens trailer value).
        The server replays the generation and suppresses that many
        leading tokens — exact for greedy and for seeded sampling on a
        plain engine (position-keyed draws)."""
        rv = params.get("received_tokens", 0)
        if isinstance(rv, float) and (not math.isfinite(rv) or rv != int(rv)):
            raise ValueError("'received_tokens' must be a non-negative integer")
        received = int(rv)
        if received < 0:
            raise ValueError("'received_tokens' must be a non-negative integer")
        return received

    def _stamp_serving_trailers(self, request: GenRequest) -> None:
        """Success-path trailers: the request's attributed device time
        (`device-ms`, any engine) plus the replica-tier pair — which
        replica served, and whether the stream was resumed on another
        replica (`restarted` — the signal that a SAMPLED stream's
        suffix may not extend the delivered prefix bit-exactly on a
        spec engine; replica keys are absent on a bare engine)."""
        trailers = []
        device_ms = request.timings.device_ms
        if device_ms > 0:
            trailers.append((errors.DEVICE_MS_KEY, f"{device_ms:.2f}"))
        replica = getattr(request, "replica", None)
        if replica is not None:
            trailers.append((errors.REPLICA_KEY, str(replica)))
            if getattr(request, "restarted", False):
                trailers.append((errors.RESTARTED_KEY, "1"))
        tier = getattr(request, "tier", None)
        if tier is not None:
            # Disagg tier breadcrumb (ISSUE 13): which prefill/decode
            # worker pair served this request.
            trailers.append((errors.TIER_KEY, str(tier)))
        if trailers:
            errors.add_rpc_trailers(*trailers)

    def _drain(self, request: GenRequest, timeout: float):
        """Yield engine events until done/error; raises on timeout."""
        while True:
            try:
                kind, value = request.out.get(timeout=timeout)
            except queue.Empty:
                request.cancelled.set()
                raise errors.DeadlineExceededError(
                    "generation timed out"
                ) from None
            yield kind, value
            if kind in ("done", "error"):
                return

    @staticmethod
    def _parse_stops(params: dict) -> list[str]:
        stop = params.get("stop")
        if stop is None:
            return []
        if isinstance(stop, str):
            return [stop] if stop else []
        import collections.abc

        if isinstance(stop, (dict, collections.abc.Mapping, struct_pb2.Struct)):
            # A mapping would silently iterate its KEYS as stop strings.
            raise ValueError("'stop' must be a string or a list of strings")
        try:
            stops = [s for s in stop]
        except TypeError:
            raise ValueError(
                "'stop' must be a string or a list of strings"
            ) from None
        if not all(isinstance(s, str) and s for s in stops):
            raise ValueError("'stop' entries must be non-empty strings")
        return stops

    def _text_events(self, request: GenRequest, stops: list[str],
                     skip: int = 0):
        """Decode engine tokens into text deltas, applying stop sequences:
        yields ("delta", str) then ("done", timings | None).

        Stop handling holds back up to max(len(stop))-1 trailing chars so
        a stop string arriving split across deltas is still caught and
        never emitted; on a match the engine request is cancelled (no
        further device work) and the stream ends cleanly at the text
        BEFORE the earliest match. The engine's own "cancelled" error is
        the expected outcome of that cancellation, not a failure.

        `skip` (client resume, `received_tokens`): the first `skip`
        tokens still pass through the detokenizer — incremental decode
        is context-dependent — but their text is discarded, so the
        stream carries only the suffix the client is missing. An engine-
        lifecycle failure raises UNAVAILABLE carrying the resume
        trailers with the total delivered count (skip + this stream's).
        """
        tokenizer = self.engine.tokenizer
        incremental = isinstance(tokenizer, ByteTokenizer)
        utf8_tail = b""
        detok = None if incremental else IncrementalDetokenizer(tokenizer)
        hold = max((len(s) for s in stops), default=1) - 1
        buf = ""
        stopped = False
        skipped = 0
        delivered = 0
        timings = None
        detok_s = 0.0     # cumulative detokenize wall time (trace span)
        for kind, value in self._drain(
            request, self.engine.config.request_timeout_s
        ):
            if kind == "token":
                t0 = time.monotonic()
                if incremental:
                    delta, utf8_tail = tokenizer.decode_incremental(
                        [value], utf8_tail
                    )
                else:
                    # Context-dependent detokenization (BPE/sentencepiece):
                    # bounded-window incremental decode, O(n) total.
                    delta = detok.push(value)
                detok_s += time.monotonic() - t0
                if skipped < skip:
                    skipped += 1
                    continue
                delivered += 1
                if not delta:
                    continue
                if not stops:
                    yield "delta", delta
                    continue
                buf += delta
                cut = min(
                    (i for i in (buf.find(s) for s in stops) if i >= 0),
                    default=-1,
                )
                if cut >= 0:
                    if buf[:cut]:
                        yield "delta", buf[:cut]
                    buf = ""
                    stopped = True
                    request.cancelled.set()
                    break
                if hold and len(buf) > hold:
                    yield "delta", buf[:-hold]
                    buf = buf[-hold:]
                elif not hold:
                    yield "delta", buf
                    buf = ""
            elif kind == "error":
                if buf:
                    # Flush the stop-scanner's held-back tail first: the
                    # resume-tokens trailer counts CONSUMED tokens, so
                    # text still held here would be advertised as
                    # delivered and silently lost across a client
                    # resume. The stream is ending either way; a stop
                    # that would only complete across the resume
                    # boundary is the one remaining (documented) gap.
                    yield "delta", buf
                    buf = ""
                raise self._engine_error(value, delivered=skip + delivered)
            else:
                timings = value
        if stopped:
            # Drain the terminal event the cancellation produces so the
            # engine's queue is not abandoned mid-handshake; the output is
            # already complete, so even a drain timeout must not destroy
            # it. Timings live on the request object (engine._finish fills
            # them for cancelled requests too), so Usage survives the
            # cancellation path.
            try:
                for kind, value in self._drain(
                    request, self.engine.config.request_timeout_s
                ):
                    if kind in ("done", "error"):
                        break
            except errors.DeadlineExceededError:
                pass
            timings = request.timings
        else:
            # End of stream: release held-back text (the incremental
            # detokenizer's window and/or the stop scanner's tail), still
            # honoring a stop that only completes in the final text.
            t0 = time.monotonic()
            tail = detok.flush() if detok is not None else ""
            detok_s += time.monotonic() - t0
            buf += tail
            if buf:
                cut = min(
                    (i for i in (buf.find(s) for s in stops) if i >= 0),
                    default=-1,
                )
                if cut >= 0:
                    buf = buf[:cut]
                if buf:
                    yield "delta", buf
        if request.trace is not None and detok_s > 0:
            # Detokenize work interleaves with decode; record it as one
            # span of its cumulative duration anchored at stream end (the
            # attr marks it as an accumulation, not a contiguous window).
            end = time.monotonic()
            request.trace.child(
                "detokenize", start=end - detok_s, end=end, cumulative=True
            )
        yield "done", timings

    @staticmethod
    def _queued_as_one(request: GenRequest, events):
        """`_text_events` with the deltas of tokens the engine has ALREADY
        queued joined into one: a decode block hands a stream its K
        tokens at one instant, and a message a token is K messages (K
        wake-ups of this thread, K of the client's) where one carries the
        same text at the same instant — 3,600 messages a second on 64
        lanes, most of a core on either side (PERF.md §6, PR 55). Text
        waits for nothing: a delta goes out as soon as the request's
        queue is empty, so a first token, or a stream decoded a token a
        step, is a message a token as before. On an engine error the
        text held here goes out first: the resume trailer counts it as
        delivered."""
        held: list[str] = []
        try:
            for kind, value in events:
                if kind == "delta":
                    held.append(value)
                    if not request.out.empty():
                        continue
                if held:
                    yield "delta", "".join(held)
                    held.clear()
                if kind != "delta":
                    yield kind, value
        except Exception:
            if held:
                yield "delta", "".join(held)
            raise

    # -- Service interface --------------------------------------------------

    def _engine_profile(self, parameters) -> pk.ExecuteToolResponse:
        """jax.profiler trace capture (SURVEY §5 tracing obligation).

        params: action = start | stop | status; log_dir (start only).
        Captured traces carry the polykey/prefill, polykey/decode and
        polykey/spec_decode annotations around the engine's device steps
        (engine.py) and open in TensorBoard / xprof. Delegates to the
        shared single-flight ProfilerCapture, so a capture started here
        blocks /debug/profile (and vice versa) — ProfilerBusyError is a
        ValueError, preserving the tool's original double-start contract.
        """
        params = dict(parameters) if parameters is not None else {}
        action = params.get("action", "status")
        if action == "start":
            log_dir = params.get("log_dir")
            self.profiler.start(str(log_dir) if log_dir else None)
        elif action == "stop":
            self.profiler.stop()
            if self.logger is not None:
                self.logger.info("profiler trace captured")
        elif action != "status":
            raise ValueError(
                f"unknown profiler action {action!r}; use start/stop/status"
            )
        response = pk.ExecuteToolResponse(
            status=cmn.Status(code=200, message="Tool executed successfully")
        )
        status = self.profiler.status()
        response.struct_output.update({
            "profiling": status["profiling"],
            "log_dir": status["log_dir"],
        })
        return response

    def _engine_stats(self, parameters) -> pk.ExecuteToolResponse:
        """engine_stats views: default counters+percentiles (+ the most
        recent traced request's span tree), `metrics_text` (Prometheus
        page over gRPC), `trace` (flight-recorder dump)."""
        params = dict(parameters) if parameters is not None else {}
        view = params.get("view", "stats")
        response = pk.ExecuteToolResponse(
            status=cmn.Status(code=200, message="Tool executed successfully")
        )
        if view in ("metrics_text", "prometheus"):
            if self.obs is None:
                raise ValueError(
                    "metrics_text needs observability wiring (serve via "
                    "gateway.server or pass obs= to TpuService)"
                )
            response.string_output = self.obs.registry.render()
            return response
        if view == "trace":
            if self.obs is None:
                raise ValueError(
                    "trace view needs observability wiring (serve via "
                    "gateway.server or pass obs= to TpuService)"
                )
            response.struct_output.update({
                "traces": self.obs.recorder.traces(),
                "events": self.obs.recorder.events(),
            })
            return response
        if view != "stats":
            raise ValueError(
                f"unknown engine_stats view {view!r}; "
                "use stats, metrics_text, or trace"
            )
        stats = self.engine.stats()
        if self.supervisor is not None:
            stats["engine_restarts"] = self.supervisor.restarts
            stats["supervisor_gave_up"] = self.supervisor.gave_up
        if self.obs is not None:
            last = self.obs.recorder.last(self._is_llm_trace)
            if last is not None:
                stats["last_trace"] = last
        response.struct_output.update(stats)
        return response

    @staticmethod
    def _is_llm_trace(trace: dict) -> bool:
        return trace.get("attrs", {}).get("tool") in _LLM_TOOLS

    def execute_tool(self, tool_name, parameters, secret_id, metadata):
        self._resolve_secret(secret_id)
        span = current_span()
        if span is not None:
            span.set(tool=tool_name)
        if tool_name == "engine_profile":
            return self._engine_profile(parameters)
        if tool_name == "engine_stats":
            return self._engine_stats(parameters)
        if tool_name not in _LLM_TOOLS:
            return self._mock.execute_tool(tool_name, parameters, secret_id, metadata)

        params = dict(parameters) if parameters is not None else {}
        request = self._build_request(parameters)
        request.trace = span
        stops = self._parse_stops(params)
        skip = self._parse_received(params)
        self._submit(request)

        if not stops:
            # No stop scanning → no per-token decode: collect ids and
            # detokenize once (one decode call beats _text_events'
            # per-token window decodes when no one needs deltas).
            token_ids: list[int] = []
            for kind, value in self._drain(
                request, self.engine.config.request_timeout_s
            ):
                if kind == "token":
                    token_ids.append(value)
                elif kind == "error":
                    raise self._engine_error(value)
            t0 = time.monotonic()
            text = self.engine.tokenizer.decode(token_ids[skip:])
            if request.trace is not None:
                request.trace.child(
                    "detokenize", start=t0, end=time.monotonic(),
                    tokens=len(token_ids),
                )
        else:
            pieces: list[str] = []
            for kind, value in self._text_events(request, stops, skip):
                if kind == "delta":
                    pieces.append(value)
            text = "".join(pieces)

        self._stamp_serving_trailers(request)
        response = pk.ExecuteToolResponse(
            status=cmn.Status(code=200, message="Tool executed successfully"),
            string_output=text,
        )
        return response

    def execute_tool_stream(
        self, tool_name, parameters, secret_id, metadata
    ) -> Iterator[pk.ExecuteToolStreamChunk]:
        self._resolve_secret(secret_id)
        span = current_span()
        if span is not None:
            span.set(tool=tool_name)
        if tool_name not in _LLM_TOOLS:
            yield from self._mock.execute_tool_stream(
                tool_name, parameters, secret_id, metadata
            )
            return

        params = dict(parameters) if parameters is not None else {}
        request = self._build_request(parameters)
        request.trace = span
        stops = self._parse_stops(params)
        skip = self._parse_received(params)
        self._submit(request)

        timings = None
        try:
            for kind, value in self._queued_as_one(
                request, self._text_events(request, stops, skip)
            ):
                if kind == "delta":
                    yield pk.ExecuteToolStreamChunk(delta=value)
                else:
                    timings = value
        except GeneratorExit:
            request.cancelled.set()  # client went away mid-stream
            if span is not None:
                # Stamp the abort reason NOW: the interceptor freezes the
                # tree into the flight recorder the moment this exception
                # unwinds, before the engine thread reaches its own
                # _finish bookkeeping for the cancelled slot.
                span.set(client_disconnected=True)
            raise

        self._stamp_serving_trailers(request)
        final = pk.ExecuteToolStreamChunk(
            final=True,
            status=cmn.Status(code=200, message="Tool executed successfully"),
        )
        if timings is not None:
            final.usage.prompt_tokens = timings.prompt_tokens
            final.usage.completion_tokens = timings.completion_tokens
            final.usage.ttft_ms = timings.ttft_ms
            final.usage.tokens_per_sec = timings.tokens_per_sec
        yield final
