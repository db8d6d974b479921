"""Engine supervision: in-process restart instead of process recycling.

Before ISSUE 3 the failure story ended at the watchdog: one trip flipped
health to NOT_SERVING forever and the platform had to restart the whole
process — paying model load + warmup compiles and dropping every queued
request on the floor. The supervisor closes the loop in-process:

    watchdog trip / loop crash  →  engine.dead set
    supervisor notices          →  stop + drain the dead engine
                                   (in-flight requests failed cleanly)
                                →  build a fresh engine via the factory
                                →  re-arm the watchdog on it
                                →  health back to SERVING
                                →  flight-recorder "engine_restart" event
                                   + polykey_engine_restarts_total

Restarts are bounded: more than `max_restarts` inside `restart_window_s`
means the failure is not transient (bad checkpoint, broken device) — the
supervisor gives up, leaves health NOT_SERVING, and lets the platform
recycle the process per policy. That boundary is deliberate: in-process
restart handles transient faults cheaply; persistent faults still get
the full process restart the reference's compose healthcheck provides.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable


class EngineSupervisor:
    """Owns the live engine reference. `engine` is swapped atomically on
    restart; listeners (the TpuService) are told so their own reference
    follows."""

    def __init__(
        self,
        engine,
        factory: Callable[[], object],
        watchdog=None,
        health=None,
        logger=None,
        recorder=None,
        restart_counter=None,
        max_restarts: int = 3,
        restart_window_s: float = 600.0,
        check_interval_s: float = 0.5,
        join_timeout_s: float = 5.0,
    ):
        self.engine = engine
        self._factory = factory
        self.watchdog = watchdog
        self.health = health
        self.logger = logger
        self.recorder = recorder
        self.restart_counter = restart_counter
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        self.check_interval_s = check_interval_s
        self.join_timeout_s = join_timeout_s
        self.restarts = 0
        self.gave_up = False
        self._restart_times: deque[float] = deque()
        self._listeners: list[Callable[[object], None]] = []
        self._giveup_listeners: list[Callable[[str], None]] = []
        self._trip_listeners: list[Callable[[object, str], None]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="polykey-supervisor", daemon=True
        )

    def add_restart_listener(self, callback: Callable[[object], None]) -> None:
        """Called with the fresh engine after every successful restart
        (from the supervisor thread)."""
        self._listeners.append(callback)

    def add_giveup_listener(self, callback: Callable[[str], None]) -> None:
        """Called with the failure reason when the restart budget is
        exhausted and this supervisor stops trying (from the supervisor
        thread). The replica pool uses it to mark the replica DEAD while
        the rest of the pool keeps health SERVING — per-replica give-up
        instead of the single-engine whole-process NOT_SERVING."""
        self._giveup_listeners.append(callback)

    def add_trip_listener(
        self, callback: Callable[[object, str], None]
    ) -> None:
        """Called with (dead engine, reason) the moment the supervisor
        notices a trip — BEFORE the drain/restart/give-up path runs.
        Black boxes (ISSUE 16) hang a forced checkpoint here: the dying
        engine's timeline ring still exists at this point, and the
        moments before a trip are exactly what a postmortem needs."""
        self._trip_listeners.append(callback)

    def start(self) -> "EngineSupervisor":
        self._thread.start()
        return self

    def stop(self, join_timeout_s: float = 5.0) -> None:
        """Signal and (bounded) join: close() must not race a completing
        restart into swapping/reviving an engine on a terminating
        server. If the thread is mid-factory past the timeout, the
        in-restart `_stop` check shuts the fresh engine down itself."""
        self._stop.set()
        if self._thread.is_alive() and \
                threading.current_thread() is not self._thread:
            self._thread.join(timeout=join_timeout_s)

    # -- supervisor thread ---------------------------------------------------

    def _run(self) -> None:
        while not self._stop.wait(self.check_interval_s):
            engine = self.engine
            if engine.dead is None:
                continue
            for callback in self._trip_listeners:
                try:
                    callback(engine, engine.dead or "engine dead")
                except Exception:
                    pass  # a black-box flush must never break supervision
            if not self._budget_ok():
                self._give_up(engine.dead)
                return
            self._restart(engine)

    def _budget_ok(self) -> bool:
        now = time.monotonic()
        while self._restart_times and \
                now - self._restart_times[0] > self.restart_window_s:
            self._restart_times.popleft()
        return len(self._restart_times) < self.max_restarts

    def _give_up(self, reason: str) -> None:
        self.gave_up = True
        if self.logger is not None:
            self.logger.error(
                "supervisor giving up: restart budget exhausted",
                error=reason, restarts=self.restarts,
                window_s=self.restart_window_s,
            )
        if self.recorder is not None:
            self.recorder.event(
                "engine_restart_abandoned", reason=reason,
                restarts=self.restarts,
            )
        # Health stays NOT_SERVING (the watchdog/crash path already
        # flipped it); the platform's restart policy takes over.
        for callback in self._giveup_listeners:
            callback(reason)

    def _restart(self, old) -> None:
        reason = old.dead or "engine dead"
        if self.logger is not None:
            self.logger.warn(
                "supervisor restarting engine", error=reason,
                attempt=self.restarts + 1,
            )
        # Drain the corpse: reject racing submits, then give the engine
        # thread a grace window to unwind (a stall that clears — e.g. a
        # slow collective — lets the thread see `dead`, fail its own
        # in-flight work, and exit cleanly).
        old._stop.set()
        old._wake.set()
        old._thread.join(timeout=self.join_timeout_s)
        wedged = old._thread.is_alive()
        if wedged:
            # Genuinely wedged in a device call: the engine thread will
            # never fail its in-flight work, so do it from here. The old
            # engine object is discarded, so the slot/allocator races
            # this would normally risk are moot — only the requests'
            # thread-safe out-queues matter, and clients must not hang
            # to their timeouts.
            old._fail_all(f"engine restarting: {reason}")
        self._restart_times.append(time.monotonic())
        try:
            fresh = self._factory()
        except Exception as e:
            if self.logger is not None:
                self.logger.error(
                    "engine restart failed; will retry", error=str(e),
                )
            if self.recorder is not None:
                self.recorder.event(
                    "engine_restart_failed", reason=reason, error=str(e),
                )
            return  # budget was charged; next tick retries if any remains
        if self._stop.is_set():
            # Shutdown raced the restart (factory builds can take
            # minutes): a terminating server must not resurrect —
            # re-advertising SERVING and leaking a live engine thread.
            fresh.shutdown()
            return
        if not wedged:
            # Metric continuity: the fresh engine adopts the dead one's
            # EngineMetrics so shed/expired/latency counters survive the
            # swap (Prometheus counters must not reset on a supervised
            # restart — only on process restart). Skipped when the old
            # thread is still wedged: if its device call ever returns it
            # will run its own _fail_all concurrently with ours above,
            # and the double-counted failures must not pollute the live
            # engine's counters — a counter reset is the lesser evil.
            old.metrics.adopt_startup(fresh.metrics)
            fresh.metrics = old.metrics
        # Signal-plane continuity (ISSUE 11): the plane rides the
        # adopted metrics object, so its window ring and SLO budget
        # state survive the swap — but its timeline binding points at
        # the DEAD engine's ring. Rebind to the fresh engine's so
        # breach/recovery notes land where to_perfetto exports from.
        # (On the wedged path fresh.metrics is a new object whose plane
        # was freshly built against the fresh timeline — nothing to do.)
        signals = getattr(fresh.metrics, "signals", None)
        if signals is not None:
            signals.timeline = getattr(fresh, "timeline", None)
            if signals.recorder is None:
                signals.recorder = self.recorder
        self.restarts += 1
        self.engine = fresh
        for callback in self._listeners:
            callback(fresh)
        if self.watchdog is not None:
            self.watchdog.rearm(fresh)   # also resumes health SERVING
        elif self.health is not None:
            self.health.resume_serving()
        if self.restart_counter is not None:
            self.restart_counter.inc()
        if self.recorder is not None:
            self.recorder.event(
                "engine_restart", reason=reason, restarts=self.restarts,
            )
        # The fresh engine's flight-deck timeline opens with the restart
        # marker, so a Perfetto export of the post-restart schedule shows
        # WHY the frontier counters reset (ISSUE 10).
        timeline = getattr(fresh, "timeline", None)
        if timeline is not None:
            # kv_reloaded: pages the fresh engine pulled back from the
            # durable prefix store (ISSUE 15) — the restart-handoff
            # evidence that warm TTFT survived the swap.
            timeline.note("engine_restart", reason=reason,
                          restarts=self.restarts,
                          kv_reloaded=getattr(
                              fresh, "_kv_reloaded_pages", 0))
        if self.logger is not None:
            self.logger.info(
                "engine restarted", restarts=self.restarts,
            )
