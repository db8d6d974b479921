"""On-demand jax.profiler capture with a single-flight guarantee.

TPU hardware windows are scarce (ROUND6.md: the chip has been gone for
days at a stretch), so the first minutes of the next window must harvest
maximal evidence — which means profiling has to be ONE call away on a
live server, not a redeploy. This wraps ``jax.profiler`` start/stop
behind a lock so the two triggers (the ``engine_profile`` gRPC tool and
the ``/debug/profile`` HTTP endpoint) can never start two overlapping
captures: jax's profiler is process-global, and a second start_trace
either raises or silently corrupts the first capture's artifact.

CPU-safe by construction (jax traces host + CPU-backend events too), so
the whole path is testable now and pays off unchanged on hardware.
Every start/stop lands in the flight recorder, so a postmortem reader
can see that a capture was running when a stall happened — profiling
overhead is itself a serving event worth recording.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

# Bounds for the HTTP trigger's blocking capture: long enough for a few
# decode blocks even on a cold CPU engine, short enough that a stray
# request can't pin the profiler (and a handler thread) for minutes.
MIN_CAPTURE_S = 0.1
MAX_CAPTURE_S = 60.0

DEFAULT_DIR = "/tmp/polykey_profile"


class ProfilerBusyError(ValueError):
    """A capture is already running (single-flight contract)."""


class ProfilerCapture:
    """Process-wide profiler guard shared by every trigger surface."""

    def __init__(self, base_dir: Optional[str] = None, recorder=None):
        self._base_dir = base_dir
        self.recorder = recorder
        self._lock = threading.Lock()
        self._dir: Optional[str] = None
        self._captures = 0

    @property
    def base_dir(self) -> str:
        # POLYKEY_PROFILE_DIR is read per capture, not cached: an
        # operator pointing it at a fresh PD mid-incident must win.
        return (self._base_dir
                or os.environ.get("POLYKEY_PROFILE_DIR")
                or DEFAULT_DIR)

    @property
    def active_dir(self) -> Optional[str]:
        return self._dir

    def status(self) -> dict:
        return {
            "profiling": self._dir is not None,
            "log_dir": self._dir or "",
            "captures": self._captures,
        }

    def start(self, log_dir: Optional[str] = None) -> str:
        """Begin a capture. Raises ProfilerBusyError when one is already
        running — the caller decides whether that is a 409 or a tool
        error; nobody ever gets a second concurrent trace."""
        import jax

        # Path assembly stays outside the critical section (PL004); the
        # lock covers only the busy check, the jax start, and the state
        # flip, so two racing starters serialize on exactly that.
        fallback = os.path.join(
            self.base_dir,
            time.strftime("%Y%m%d-%H%M%S", time.gmtime()),
        )
        with self._lock:
            if self._dir is not None:
                raise ProfilerBusyError(
                    f"profiler already tracing to {self._dir}"
                )
            target = log_dir or f"{fallback}-{self._captures}"
            os.makedirs(target, exist_ok=True)
            # No Python tracer: its hook on every call of every thread
            # freezes the gateway's stream handlers for the length of a
            # capture of a full slot batch, so what was captured was not
            # what is served. The host tracer stays at its default, so
            # the polykey/ spans and their attributes are recorded.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(target, profiler_options=options)
            self._dir = target
            self._captures += 1
        if self.recorder is not None:
            self.recorder.event("profiler_start", log_dir=target)
        return target

    def stop(self) -> str:
        import jax

        with self._lock:
            if self._dir is None:
                raise ValueError("profiler is not tracing")
            # Free the single-flight slot BEFORE stop_trace can raise
            # (disk full while flushing the artifact): a failed stop
            # must not wedge profiling until process restart — the next
            # start() gets a fresh chance instead of 409 forever.
            target, self._dir = self._dir, None
            jax.profiler.stop_trace()
        if self.recorder is not None:
            self.recorder.event(
                "profiler_stop", log_dir=target,
                files=_artifact_count(target),
            )
        return target

    def capture(self, seconds: float,
                log_dir: Optional[str] = None) -> dict:
        """Blocking start→sleep→stop round trip (the HTTP trigger).
        Returns the artifact summary; raises ProfilerBusyError when a
        capture is already in flight."""
        seconds = min(MAX_CAPTURE_S, max(MIN_CAPTURE_S, float(seconds)))
        target = self.start(log_dir)
        try:
            time.sleep(seconds)
        finally:
            # Even an interrupted sleep must release the single-flight
            # slot, or one bad request wedges profiling until restart.
            self.stop()
        return {
            "log_dir": target,
            "seconds": seconds,
            "files": _artifact_count(target),
        }


def _artifact_count(log_dir: str) -> int:
    total = 0
    for _root, _dirs, files in os.walk(log_dir):
        total += len(files)
    return total
