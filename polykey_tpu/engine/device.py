"""What the process actually runs on: device identity, memory, compiles.

A serving process that silently fell back to the CPU, or to a jnp path
where a kernel was expected, produces numbers that look like device
numbers and are not. Everything here exists so the engine can SAY what
it runs on (engine_stats, the `engine initialized` log line) and so the
entry points can refuse a platform nobody asked for:

- `require_accelerator` — POLYKEY_BACKEND=tpu serves from a TPU or not
  at all (JAX falls back to the CPU with a warning when libtpu finds no
  chip).
- `device_identity` / `device_memory` — platform, device_kind, device
  count and the chip's roofline row; per-device bytes in use and peak.
- `compile_counts` — executables built in this process, how many of
  them came out of the persistent compilation cache, and the seconds JAX
  spent tracing, lowering and loading or building them, from JAX's own
  monitoring events (a benchmark window expects zero new ones; one that
  happens names the engine phase that paid for it).
- `mosaic_calls` / `collective_ops` — Mosaic custom calls, by kernel
  name, in a lowered step and collectives in its compiled HLO: the
  evidence that a served executable contains the Pallas kernels (not the
  gate functions' opinion that it should) and, on a mesh, really talks
  over ICI.
"""

from __future__ import annotations

import collections
import os
import re
import threading

import jax

from ..obs.timeline import STARTUP_PHASES, open_phase
from .roofline import detect_chip

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# JAX's three timed stages of making an executable, and the census's sum
# each feeds. Tracing and lowering are paid on every start (the persistent
# cache's key is computed from the lowered module); the backend stage is
# the cache read, or XLA's compile where the cache has no entry.
_STAGE_SUMS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _BACKEND_COMPILE_EVENT: "backend_s",
}
_COUNTS = ("executables", "cache_hits")
_SUMS = (*_STAGE_SUMS.values(), "cache_retrieval_s")

# One per Mosaic custom call in StableHLO text:
#   stablehlo.custom_call @tpu_custom_call(...) {..., kernel_name = "flash_attention"}
_KERNEL_NAME = re.compile(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"')

_census_lock = threading.Lock()
_census = {
    "installed": False, "logger": None, "by_phase": collections.Counter(),
    **dict.fromkeys(_COUNTS, 0), **dict.fromkeys(_SUMS, 0.0),
}
# How many of JAX's timed stages the thread is inside: tracing a jitted
# function traces the jitted functions it calls (most of jax.numpy), each
# with an event of its own, so only a stage that no other encloses adds
# its seconds to a sum — the sums are then disjoint stretches of the
# compiling thread's time.
_stages_open = threading.local()


def install_compile_census(logger=None) -> None:
    """Start counting and timing XLA compiles (idempotent; listeners are
    process-wide and stay for the process's life). `logger`, where given,
    is where the census says `compile while serving` from then on: one
    line an executable built on a thread that has a serving phase open
    (the engine thread's; what a thread with no phase compiles — a
    caller's own jits before or after the engine — is counted under
    `by_phase` "none" and not announced)."""
    with _census_lock:
        if logger is not None:
            _census["logger"] = logger
        if _census["installed"]:
            return
        _census["installed"] = True

    def on_stage_start(event: str, value: float, **kwargs) -> None:
        if event in _STAGE_SUMS:
            _stages_open.n = getattr(_stages_open, "n", 0) + 1

    def on_duration(event: str, duration_secs: float, **kwargs) -> None:
        if event == _CACHE_RETRIEVAL_EVENT:
            with _census_lock:
                _census["cache_retrieval_s"] += duration_secs
            return
        key = _STAGE_SUMS.get(event)
        if key is None:
            return
        _stages_open.n = enclosing = max(0, getattr(_stages_open, "n", 1) - 1)
        if not enclosing:
            with _census_lock:
                _census[key] += duration_secs
        if event != _BACKEND_COMPILE_EVENT:
            return
        where, attrs = _compiling_phase()
        with _census_lock:
            _census["executables"] += 1
            _census["by_phase"][where] += 1
            logger = _census["logger"]
        if where not in ("startup", "none") and logger is not None:
            logger.warn("compile while serving", **{
                **attrs, "phase": where,
                "thread": threading.current_thread().name,
                "executable": kwargs.get("fun_name"),
                "seconds": round(duration_secs, 6),
            })

    def on_event(event: str, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            with _census_lock:
                _census["cache_hits"] += 1

    jax.monitoring.register_scalar_listener(on_stage_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _compiling_phase() -> tuple[str, dict]:
    """Which engine phase the calling thread is in, for `by_phase` and the
    `compile while serving` line: ("startup", {}) inside the constructor,
    (name, attrs) of a serving phase, ("none", {}) on a thread that has
    no phase open (the gateway's, a caller's before or after the
    constructor)."""
    span = open_phase()
    if span is None:
        return "none", {}
    name, attrs = span
    return ("startup", {}) if name in STARTUP_PHASES else (name, attrs)


def compile_counts() -> dict:
    """Executables built since install_compile_census, and the seconds
    they took, all monotone: `executables` counts every one, `cache_hits`
    those loaded from the persistent cache, `fresh_compiles` the rest
    (the ones XLA actually compiled); `trace_s`, `lower_s` and
    `backend_s` (the cache read OR XLA's compile, as JAX reports it) are
    the seconds of JAX's three stages, `cache_retrieval_s` the part of
    `backend_s` that read the cache and found the entry; `by_phase` is
    `executables` by the engine phase the compiling thread was in
    ("startup" for all of a constructor's, "none" outside any)."""
    with _census_lock:
        out = {k: _census[k] for k in _COUNTS + _SUMS}
        out["by_phase"] = dict(_census["by_phase"])
    out["fresh_compiles"] = out["executables"] - out["cache_hits"]
    return out


def compile_delta(before: dict, after: dict) -> dict:
    """What two readings of `compile_counts` differ by: the counts and the
    four sums (seconds rounded to the microsecond)."""
    return {k: round(after[k] - before[k], 6)
            for k in (*_COUNTS, "fresh_compiles", *_SUMS)}


def release_compile_heap() -> None:
    """Hand the host heap that compiling left behind back to the OS NOW,
    before serving. A process that compiled its executables (a cold
    start) otherwise pays that release some seconds into serving: every
    thread stops for 1-2 s, the device idle under the engine's `process`
    phase (PERF.md section 7; a warm start, which loads them, has no such
    hole). glibc only; a no-op where malloc_trim is not to be had."""
    import ctypes
    import gc

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def device_identity() -> dict:
    """Platform as JAX reports it, plus the roofline row it maps to
    (None off-TPU; an unknown TPU kind raises — roofline.detect_chip)."""
    devices = jax.devices()
    chip = detect_chip()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "chip": chip.name if chip is not None else None,
    }


def require_accelerator() -> dict:
    """The serving entry point's platform gate; returns device_identity().

    JAX falls back to the CPU with a warning when no TPU is visible, and
    an engine would then serve — slowly, under a TPU backend's name. A
    non-TPU platform is a start-up error unless JAX_PLATFORMS=cpu was
    set explicitly (tests, compose, the smoke's tiny rehearsal)."""
    identity = device_identity()
    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if identity["platform"] != "tpu" and not explicit_cpu:
        raise RuntimeError(
            "POLYKEY_BACKEND=tpu but JAX initialized platform "
            f"{identity['platform']!r} ({identity['device_kind']}, "
            f"{identity['device_count']} device(s)): no TPU is visible to "
            "this process. Set JAX_PLATFORMS=cpu to serve from the CPU on "
            "purpose."
        )
    return identity


def device_memory(devices) -> list:
    """Per-device allocator readings where the backend reports them
    (TPU does; the CPU backend returns None → empty list)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if stats:
            out.append({
                "id": d.id,
                "bytes_in_use": stats.get("bytes_in_use", 0),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
                "bytes_limit": stats.get("bytes_limit", 0),
            })
    return out


def mosaic_calls(lowered) -> dict:
    """Mosaic (Pallas TPU) custom calls in a `jax.stages.Lowered` step, by
    kernel name (the `name=` of the pallas_call; each custom call carries
    it as its `kernel_name` attribute) -> number of call sites. A layer
    scan's body is lowered once, so a kernel every layer runs counts 1."""
    return dict(collections.Counter(_KERNEL_NAME.findall(lowered.as_text())))


def collective_ops(compiled) -> int:
    """Cross-device collectives in a compiled executable's HLO."""
    text = compiled.as_text()
    return sum(
        text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")
    )
