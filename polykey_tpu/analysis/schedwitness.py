"""Runtime starvation witness: the dynamic half of schedlint.

Static liveness analysis (analysis/sched.py, SL001–SL004) proves the
*shape* of the scheduler's fairness machinery — every budgeted loop has
a progress floor, every round-robin cursor advances, the frontiers
issue in order. It cannot prove that under a real mixed load no lane
actually aged out: a structurally fair scheduler can still starve a
slot when the workload keeps re-triggering the path that skips it
(faulting slots waiting on restores, pending prefills behind a
saturated budget). This module records what actually happened: with
``POLYKEY_SCHED_WITNESS=1`` in the environment, the engine loop calls
:func:`note` at every dispatch boundary — one call per frontier
(``restore``, ``prefill``, ``decode``) naming which slots were served
this boundary and which were eligible but skipped. The recorder keeps,
per frontier and slot, the wall-clock age of the oldest unserved wait
and the consecutive-skip count, plus the running worst case ever
observed. The summary dumps as JSON at process exit (and on demand),
one file per process under ``POLYKEY_SCHED_WITNESS_OUT`` (a directory —
the disagg drill spans several worker processes).

``python -m polykey_tpu.analysis sched --witness <file-or-dir>`` merges
these summaries into the static verdict: a slot whose wait age exceeded
the max-starvation-age gate (or whose consecutive-skip count exceeded
the skip gate) becomes an SL006 finding carrying the frontier, slot,
age, and skip count — real evidence from a real run.

Approximations (documented, same contract as the lock/heap witnesses):

- Wait ages are per-process monotonic-clock differences; no cross-
  process clock alignment is needed (unlike the trace-merge tier) and
  none is attempted — each process's worst case stands on its own.
- A process killed with ``os._exit`` (the worker-exit fault's real
  mode) never dumps — the drill's witness comes from the coordinator
  and the surviving workers.
- The witness sees dispatch *boundaries*, not device completion: a
  served slot whose dispatch later fails still counts as served. That
  is the right accounting for starvation (the scheduler offered it the
  frontier); failure handling is the watchdog's job.
"""

from __future__ import annotations

import json
import os
import sys
import time

SCHED_WITNESS_VERSION = 1
ENV_FLAG = "POLYKEY_SCHED_WITNESS"
ENV_OUT = "POLYKEY_SCHED_WITNESS_OUT"
DEFAULT_OUT = "/tmp/polykey-sched-witness"

# The witness obeys the discipline it audits: per-frontier state is one
# dict keyed by slot index (bounded by the engine's max_decode_slots),
# and the dump carries only aggregates plus a truncated worst-offender
# list — never an unbounded event log.
_TOP_WAITERS = 8

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def _relpath(filename: str) -> str:
    absolute = os.path.abspath(filename)
    if absolute.startswith(_REPO_ROOT + os.sep):
        return absolute[len(_REPO_ROOT) + 1:].replace(os.sep, "/")
    return absolute.replace(os.sep, "/")


class _FrontierState:
    __slots__ = ("notes", "serves", "waiting", "max_wait_age_s",
                 "max_wait_slot", "max_skips", "max_skip_slot")

    def __init__(self) -> None:
        self.notes = 0
        self.serves = 0
        # slot -> [first_wait_monotonic, consecutive_skips]
        self.waiting: dict[int, list] = {}
        self.max_wait_age_s = 0.0
        self.max_wait_slot = -1
        self.max_skips = 0
        self.max_skip_slot = -1


class _Recorder:
    def __init__(self) -> None:
        self.t0 = time.monotonic()
        self.frontiers: dict[str, _FrontierState] = {}

    def note(self, frontier: str, served, waiting) -> None:
        st = self.frontiers.get(frontier)
        if st is None:
            st = self.frontiers[frontier] = _FrontierState()
        now = time.monotonic()
        st.notes += 1
        served = set(served)
        st.serves += len(served)
        # A served slot's wait (if any) ends here; serving wins over
        # waiting when a slot appears in both (chunked prefill mid-
        # flight: it got a range this boundary, it is not starved).
        for i in served:
            st.waiting.pop(i, None)
        for i in waiting:
            if i in served:
                continue
            ent = st.waiting.get(i)
            if ent is None:
                st.waiting[i] = [now, 1]
                continue
            ent[1] += 1
            age = now - ent[0]
            if age > st.max_wait_age_s:
                st.max_wait_age_s = age
                st.max_wait_slot = i
            if ent[1] > st.max_skips:
                st.max_skips = ent[1]
                st.max_skip_slot = i
        # Slots no longer eligible (finished, cancelled, shed) stop
        # waiting — their recorded worst case already counted.
        gone = [i for i in st.waiting if i not in waiting]
        for i in gone:
            del st.waiting[i]

    def snapshot(self) -> dict:
        now = time.monotonic()
        frontiers: dict[str, dict] = {}
        for name, st in sorted(self.frontiers.items()):
            outstanding = sorted(
                ({"slot": i, "wait_age_s": round(now - t, 3), "skips": n}
                 for i, (t, n) in st.waiting.items()),
                key=lambda e: -e["wait_age_s"],
            )[:_TOP_WAITERS]
            # The gate reads the worst EVER observed, not just what is
            # still outstanding at dump time.
            max_age, max_slot = st.max_wait_age_s, st.max_wait_slot
            for e in outstanding:
                if e["wait_age_s"] > max_age:
                    max_age, max_slot = e["wait_age_s"], e["slot"]
            max_skips, skip_slot = st.max_skips, st.max_skip_slot
            for i, (_t, n) in st.waiting.items():
                if n > max_skips:
                    max_skips, skip_slot = n, i
            frontiers[name] = {
                "notes": st.notes,
                "serves": st.serves,
                "max_wait_age_s": round(max_age, 3),
                "max_wait_slot": max_slot,
                "max_consecutive_skips": max_skips,
                "max_skip_slot": skip_slot,
                "outstanding": outstanding,
            }
        return {
            "version": SCHED_WITNESS_VERSION,
            "pid": os.getpid(),
            "argv0": _relpath(sys.argv[0]) if sys.argv else "",
            "elapsed_s": round(now - self.t0, 3),
            "frontiers": frontiers,
        }


_recorder: _Recorder | None = None


def install() -> None:
    """Create the recorder and register the exit-time dump. Idempotent."""
    global _recorder
    if _recorder is not None:
        return
    _recorder = _Recorder()
    import atexit

    atexit.register(dump)


def maybe_install() -> bool:
    """install() iff POLYKEY_SCHED_WITNESS=1; returns whether installed."""
    if os.environ.get(ENV_FLAG, "") == "1":
        install()
        return True
    return False


def installed() -> bool:
    return _recorder is not None


def note(frontier: str, served, waiting) -> None:
    """Record one dispatch boundary (no-op unless installed). `served`
    is the slot indices this frontier dispatched work for; `waiting` is
    the indices that were ELIGIBLE for this frontier but got nothing —
    faulting slots at the restore frontier, pending-prefill slots at
    the prefill frontier. A slot in both counts as served."""
    if _recorder is not None:
        _recorder.note(frontier, served, waiting)


def snapshot() -> dict:
    if _recorder is None:
        return {"version": SCHED_WITNESS_VERSION, "pid": os.getpid(),
                "argv0": "", "elapsed_s": 0.0, "frontiers": {}}
    return _recorder.snapshot()


def dump(out: str | None = None) -> str | None:
    """Write this process's witness JSON. `out` (or
    $POLYKEY_SCHED_WITNESS_OUT, default /tmp/polykey-sched-witness) is a
    DIRECTORY; the file is sched_witness_<pid>.json so concurrent worker
    processes never clobber each other. Returns the written path (None
    when not installed)."""
    if _recorder is None:
        return None
    directory = out or os.environ.get(ENV_OUT, DEFAULT_OUT)
    try:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"sched_witness_{os.getpid()}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(snapshot(), f, indent=2, sort_keys=True)
            f.write("\n")
        return path
    except OSError:
        return None  # a failed witness dump must never fail the run


def load_witness(path: str) -> list[dict]:
    """Load one witness file, or every sched_witness_*.json in a
    directory (the multi-process drill). Returns a list of per-process
    snapshots; raises ValueError on an unreadable/mismatched file."""
    if os.path.isdir(path):
        files = sorted(
            os.path.join(path, name) for name in os.listdir(path)
            if name.startswith("sched_witness_") and name.endswith(".json")
        )
        if not files:
            raise ValueError(f"no sched_witness_*.json files under {path}")
    else:
        files = [path]
    out: list[dict] = []
    for name in files:
        with open(name, encoding="utf-8") as f:
            data = json.load(f)
        if data.get("version") != SCHED_WITNESS_VERSION:
            raise ValueError(
                f"sched witness file {name} has version "
                f"{data.get('version')!r}, expected {SCHED_WITNESS_VERSION}"
            )
        out.append(data)
    return out
