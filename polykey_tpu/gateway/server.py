"""gRPC server wiring — parity with the reference server binary.

Reproduces /root/reference/cmd/polykey/main.go end to end:

- listen address from ``LISTEN_ADDR``, default ``:50051`` (main.go:57-59);
- keepalive: MaxConnectionIdle 5m, Time 2h, Timeout 20s (main.go:68-72);
- unary logging interceptor that skips health checks (main.go:25-52);
- health service with SERVING for ``polykey.v2.PolykeyService`` and ``""``
  (main.go:82-94), plus server reflection (main.go:80);
- startup log of the registered service/method table (main.go:97-103);
- graceful drain on SIGINT/SIGTERM: health shutdown first, then server stop
  (main.go:113-120).

The RPC handler mirrors internal/server/server.go: log the request shape, then
delegate to the Service seam, passing errors through unchanged (a plain
service error surfaces as code Unknown, as a bare Go error does).
"""

from __future__ import annotations

import os
import signal
import threading
from concurrent import futures
from typing import Optional

import grpc

from ..proto import health_v1_pb2 as health_pb
from ..proto import polykey_v2_pb2 as pk
from ..proto.health_v1_grpc import add_HealthServicer_to_server
from ..proto.polykey_v2_grpc import (
    SERVICE_NAME,
    PolykeyServiceServicer,
    add_PolykeyServiceServicer_to_server,
)
from ..obs import DebugSurface, MetricsHTTPServer, Observability
from . import errors
from .health import HealthService
from .interceptor import LoggingInterceptor
from .jsonlog import Logger
from .reflection import SERVICE_NAME as REFLECTION_SERVICE_NAME
from .reflection import SERVICE_NAME_V1 as REFLECTION_SERVICE_NAME_V1
from .reflection import ReflectionService, add_reflection_to_server
from .service import Service
from ..proto.health_v1_grpc import SERVICE_NAME as HEALTH_SERVICE_NAME

_KEEPALIVE_OPTIONS = [
    ("grpc.max_connection_idle_ms", 5 * 60 * 1000),   # MaxConnectionIdle 5m
    ("grpc.keepalive_time_ms", 2 * 60 * 60 * 1000),   # Time 2h
    ("grpc.keepalive_timeout_ms", 20 * 1000),         # Timeout 20s
    # Fail loudly when the port is taken (Go's net.Listen behavior) instead
    # of silently sharing it via Linux SO_REUSEPORT.
    ("grpc.so_reuseport", 0),
]

class PolykeyServer(PolykeyServiceServicer):
    """RPC handler layer (reference: internal/server/server.go:12-43)."""

    def __init__(self, service: Service, logger: Optional[Logger] = None):
        self.service = service
        self.logger = logger or Logger()

    def _log_call(self, rpc: str, request: pk.ExecuteToolRequest) -> None:
        self.logger.info(
            f"{rpc} called",
            tool_name=request.tool_name,
            has_parameters=request.HasField("parameters"),
            has_secret_id=request.HasField("secret_id"),
            has_metadata=request.HasField("metadata"),
        )

    @staticmethod
    def _unpack(request: pk.ExecuteToolRequest):
        return (
            request.tool_name,
            request.parameters if request.HasField("parameters") else None,
            request.secret_id if request.HasField("secret_id") else None,
            request.metadata if request.HasField("metadata") else None,
        )

    def _abort_status(self, rpc: str, context, e: errors.RpcStatusError):
        """Abort with the typed error's code + trailing metadata (the
        retry-after-ms contract rides the ResourceExhaustedError
        trailer; the interceptor's recording context merges it with the
        x-trace-id echo). Sheds and deadline expiries are EXPECTED
        flow-control outcomes that spike exactly when the server is
        overloaded — they log at warn so the O(1) fast-reject path can't
        drown real errors in ERROR-level log volume."""
        expected = e.code in (
            grpc.StatusCode.RESOURCE_EXHAUSTED,
            grpc.StatusCode.DEADLINE_EXCEEDED,
        )
        log = self.logger.warn if expected else self.logger.error
        log(f"Service {rpc} failed", error=str(e), code=e.code.name)
        metadata = e.trailing_metadata()
        if metadata:
            try:
                context.set_trailing_metadata(metadata)
            except Exception:
                pass  # in-process doubles without trailer support
        context.abort(e.code, str(e))

    @staticmethod
    def _flush_trailers(context) -> None:
        """Success-path trailing metadata the backend stashed through
        errors.add_rpc_trailers (replica id, restarted flag): set it on
        the context, where the interceptor's recording proxy merges it
        with the x-trace-id echo. Error paths carry their trailers on
        the typed error instead (_abort_status)."""
        trailers = errors.pop_rpc_trailers()
        if trailers:
            try:
                context.set_trailing_metadata(trailers)
            except Exception:
                pass  # in-process doubles without trailer support

    def ExecuteTool(self, request, context):
        self._log_call("ExecuteTool", request)
        # Deadline propagation (ISSUE 3): the Service seam is
        # context-free (reference parity), so the RPC's remaining budget
        # rides a thread-local the backend stamps onto GenRequest.
        errors.set_rpc_deadline(errors.deadline_from_context(context))
        try:
            response = self.service.execute_tool(*self._unpack(request))
            self._flush_trailers(context)
            return response
        except errors.RpcStatusError as e:
            self._abort_status("ExecuteTool", context, e)
        except Exception as e:
            self.logger.error("Service ExecuteTool failed", error=str(e))
            context.abort(grpc.StatusCode.UNKNOWN, str(e))
        finally:
            errors.set_rpc_deadline(None)  # handler threads are pooled
            errors.pop_rpc_trailers()      # drop any stash an abort left

    def ExecuteToolStream(self, request, context):
        self._log_call("ExecuteToolStream", request)
        errors.set_rpc_deadline(errors.deadline_from_context(context))
        try:
            yield from self.service.execute_tool_stream(*self._unpack(request))
            self._flush_trailers(context)
        except errors.RpcStatusError as e:
            self._abort_status("ExecuteToolStream", context, e)
        except Exception as e:
            self.logger.error("Service ExecuteToolStream failed", error=str(e))
            context.abort(grpc.StatusCode.UNKNOWN, str(e))
        finally:
            errors.set_rpc_deadline(None)
            errors.pop_rpc_trailers()


def normalize_address(addr: str) -> str:
    """Accept Go-style ':50051' (bind all interfaces) as well as host:port."""
    if addr.startswith(":"):
        return "[::]" + addr
    return addr


def rpc_workers(service: Service) -> int:
    """Threads of the RPC pool. A streaming RPC holds one for its whole
    life, so the pool bounds the streams in flight: with 32 workers an
    engine of 64 decode slots never saw more than 31 of them busy, its
    queue empty, while the other clients waited for a thread (PERF.md §6,
    PR 43). Twice the backend's slots — a queued request holds a thread
    too — and never under the 32 every smaller engine has had."""
    engine = getattr(service, "engine", None)
    slots = getattr(getattr(engine, "config", None), "max_decode_slots", 0)
    return max(32, 2 * int(slots or 0))


def build_server(
    service: Service,
    logger: Optional[Logger] = None,
    address: str = ":50051",
    max_workers: Optional[int] = None,
    health: Optional[HealthService] = None,
    obs: Optional[Observability] = None,
):
    """Assemble the fully-wired gRPC server; returns (server, health, port).

    `max_workers` None sizes the RPC pool from the backend (`rpc_workers`).

    An existing HealthService may be passed in so backends created before the
    server (the engine + its watchdog) can flip serving status. Passing an
    `Observability` bundle turns on request tracing (root spans in the
    interceptor, children from the backend) and RPC counters; the same
    bundle should be shared with the backend (TpuService) and the /metrics
    exposition server so all three see one registry and one recorder.
    """
    logger = logger or Logger()
    server = grpc.server(
        futures.ThreadPoolExecutor(
            max_workers=max_workers or rpc_workers(service),
            thread_name_prefix="polykey-rpc",
        ),
        interceptors=[LoggingInterceptor(logger, obs=obs)],
        options=_KEEPALIVE_OPTIONS,
    )

    add_PolykeyServiceServicer_to_server(PolykeyServer(service, logger), server)

    if health is None:
        health = HealthService()
    add_HealthServicer_to_server(health, server)
    health.set_serving_status(SERVICE_NAME, health_pb.HealthCheckResponse.SERVING)
    health.set_serving_status("", health_pb.HealthCheckResponse.SERVING)

    add_reflection_to_server(ReflectionService(), server)

    try:
        port = server.add_insecure_port(normalize_address(address))
    except RuntimeError as e:  # grpc raises on bind failure
        raise OSError(f"failed to listen on {address}: {e}") from e
    if port == 0:
        raise OSError(f"failed to listen on {address}")

    return server, health, port


_SERVICE_TABLE = {
    SERVICE_NAME: ["ExecuteTool", "ExecuteToolStream"],
    HEALTH_SERVICE_NAME: ["Check", "Watch"],
    REFLECTION_SERVICE_NAME_V1: ["ServerReflectionInfo"],
    REFLECTION_SERVICE_NAME: ["ServerReflectionInfo"],
}


def _log_service_table(logger: Logger) -> None:
    # Parity with the startup service/method table (main.go:97-103).
    logger.info("Registered services:")
    for name, methods in _SERVICE_TABLE.items():
        logger.info("Service registered", name=name, methods=len(methods))
        for method in methods:
            logger.info("Method available", service=name, method=method)


def serve(service: Optional[Service] = None, address: Optional[str] = None) -> None:
    """Process entry point (reference: cmd/polykey/main.go:54-121)."""
    logger = Logger(level=os.environ.get("POLYKEY_LOG_LEVEL", "info"))

    if address is None:
        address = os.environ.get("LISTEN_ADDR") or ":50051"

    obs = Observability()
    health = HealthService()
    if service is None:
        try:
            service = _default_service(logger, health, obs)
        except Exception as e:
            logger.error("failed to initialize backend", error=str(e))
            raise SystemExit(1)

    try:
        server, health, _ = build_server(
            service, logger, address, health=health, obs=obs
        )
    except OSError as e:
        logger.error("failed to listen", error=str(e))
        raise SystemExit(1)

    metrics_server = _start_metrics_server(obs, logger, service=service)

    _log_service_table(logger)

    quit_event = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: quit_event.set())

    server.start()
    logger.info("server starting", address=address)

    quit_event.wait()
    logger.info("server shutting down")
    health.shutdown()
    server.stop(grace=10).wait()
    service.close()
    if metrics_server is not None:
        metrics_server.stop()
    logger.info("server stopped")


def _start_metrics_server(
    obs: Observability, logger: Logger, service=None
) -> Optional[MetricsHTTPServer]:
    """Prometheus exposition sidecar thread. POLYKEY_METRICS_PORT picks
    the port (default 9464, the conventional exporter port); 0 disables.
    A bind failure degrades to no endpoint rather than killing the
    gateway — the gRPC metrics_text view still works.

    When the backend is engine-shaped (TpuService) the flight-deck
    debug surface mounts alongside /metrics — still a 404 unless
    POLYKEY_DEBUG_ENDPOINTS=1 (obs.exposition.DebugSurface). The
    engine provider follows `service.engine` so supervised restarts
    and replica pools stay visible without rewiring."""
    port_raw = os.environ.get("POLYKEY_METRICS_PORT", "9464")
    try:
        port = int(port_raw)
    except ValueError:
        logger.warn("invalid POLYKEY_METRICS_PORT; metrics disabled",
                    value=port_raw)
        return None
    if port <= 0:
        return None
    debug = None
    if service is not None and hasattr(service, "engine"):
        debug = DebugSurface(
            engine_provider=lambda: service.engine,
            obs=obs,
            profiler=getattr(service, "profiler", None),
        )
    try:
        metrics_server = MetricsHTTPServer(
            obs.registry, port=port, debug=debug
        ).start()
    except OSError as e:
        logger.warn("metrics endpoint failed to bind; continuing without",
                    port=port, error=str(e))
        return None
    logger.info("metrics endpoint listening", port=metrics_server.port,
                path="/metrics")
    return metrics_server


def _default_service(
    logger: Logger,
    health: Optional[HealthService] = None,
    obs: Optional[Observability] = None,
) -> Service:
    """Select the backend: TPU engine when requested, mock otherwise.

    The reference hard-wires its mock (main.go:85). Here POLYKEY_BACKEND=tpu
    mounts the serving engine; the default remains the dependency-free mock so
    the gateway runs anywhere.
    """
    backend = os.environ.get("POLYKEY_BACKEND", "mock").lower()
    if backend in ("tpu", "engine"):
        # Multi-host bootstrap BEFORE the engine initializes the backend:
        # under POLYKEY_COORDINATOR/NUM_PROCESSES/PROCESS_ID (or a TPU
        # pod runtime) every host's chips join one global device list, so
        # the engine's mesh can span hosts. Single-host no-op.
        from ..parallel.distributed import initialize_from_env

        initialize_from_env(logger)

        from .tpu_service import TpuService

        return TpuService.from_env(health=health, logger=logger, obs=obs)
    from .mock_service import MockService

    return MockService()


if __name__ == "__main__":
    serve()
