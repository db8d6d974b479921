"""polylint — project-invariant static analysis for the TPU serving stack.

The engine's hot path survives on rules no general-purpose linter knows:
host↔device syncs are only legal at annotated resolve points, latency
math must use monotonic clocks, ``except Exception`` must never wedge a
request silently, nothing may block under the engine's locks, threads
must be daemons or owned by a ``stop()``, jit boundaries must stay pure,
and metric families must follow the ``obs/`` naming contract. PR 1 made
regressions in these invariants *observable*; this package makes a whole
class of them impossible to merge.

Usage::

    python -m polykey_tpu.analysis                    # lint the repo
    python -m polykey_tpu.analysis --json             # machine-readable
    python -m polykey_tpu.analysis --list-rules       # rule table
    python -m polykey_tpu.analysis --write-baseline   # grandfather
    python -m polykey_tpu.analysis --prune            # drop stale baseline
    python -m polykey_tpu.analysis graph              # graphlint (2nd tier)
    python -m polykey_tpu.analysis race               # racelint (3rd tier)
    python -m polykey_tpu.analysis mem                # memlint (4th tier)
    python -m polykey_tpu.analysis sched              # schedlint (5th tier)
    python -m polykey_tpu.analysis all                # every tier, one exit

Five tiers, one discipline (per-tier baselines that trend toward
empty, mandatory-reason suppressions, content-hashed fingerprints):

- **polylint** (``rules.py``, PL***) — what the *source* promises:
  per-file AST invariants on syncs, clocks, excepts, locks, threads,
  jit purity, metric naming. Stdlib-only.
- **graphlint** (``graph.py``, GL***) — what the *compiled graphs*
  actually do: recompile stability, donation aliasing, dtype policy,
  host-transfer discipline, kernel/sharding layout, by tracing the real
  engine on a CPU backend. Needs jax; imported lazily by the ``graph``
  subcommand only.
- **racelint** (``concurrency.py``, CL***) — what the *threads and
  processes* do to each other: the interprocedural lock-acquisition
  graph (cycles = deadlocks), unguarded shared state, lock-scope
  escapes, blocking-under-lock across call boundaries, and the disagg
  coordinator/worker + KV-wire protocol conformance. Stdlib-only, with
  an opt-in runtime witness (``witness.py``, POLYKEY_LOCK_WITNESS=1)
  that merges *observed* acquisition-order edges — with stacks — into
  the static graph (``race --witness``).
- **memlint** (``memory.py``, ML***) — what the *bytes* do: an
  analytic capacity ledger (weights + device KV pool + int8 scale
  planes + largest jit transient, with donation aliasing credits) that
  must fit ``ChipSpec.hbm_bytes`` for every served-matrix entry,
  unbounded-growth rules over long-lived containers, and the
  ``POLYKEY_*`` knob contracts (documented in DEPLOY.md, single parse
  site, shipped to disagg workers via ``_config_env``). Stdlib-only,
  with an opt-in runtime heap witness (``heapwitness.py``,
  POLYKEY_HEAP_WITNESS=1) that merges *observed* tracemalloc growth
  and pool occupancies into the findings (``mem --witness``).
- **schedlint** (``sched.py``, SL***) — what the *scheduler* promises:
  liveness and fairness contracts over the engine loop — every
  budget-bounded dispatch loop has a statically provable progress
  floor, every round-robin cursor advances or re-anchors
  (starved-first) on every consumption path, the restore→prefill→
  decode frontier order holds per iteration, and consumed queues pair
  with an admission bound or shed path. Stdlib-only, with an opt-in
  runtime starvation witness (``schedwitness.py``,
  POLYKEY_SCHED_WITNESS=1) that records per-slot wait ages and
  consecutive-skip counts at dispatch boundaries and merges them into
  the verdict under a max-starvation-age gate (``sched --witness``).

Per-line suppression (reason required; reasonless or unused suppressions
are themselves findings; the rule id's prefix names the tier that
validates it, so PL/CL/ML/SL entries never cross-fire)::

    packed = np.asarray(data)  # polylint: disable=PL001(resolve point)
    self._closing = True  # polylint: disable=CL002(one-way latch)
    self._sticky[k] = v  # polylint: disable=ML002(EWMA per replica id)
    drain()  # polylint: disable=SL004(shutdown path, loop already dead)

The package is stdlib-only by design: the CI lint job installs ruff and
nothing else, and ``python -m polykey_tpu.analysis`` must run there.
"""

from .baseline import (
    apply_baseline,
    load_baseline,
    prune_baseline,
    write_baseline,
)
from .core import (
    FileContext,
    Finding,
    Rule,
    all_rules,
    check_file,
    register,
    run_paths,
)

# Importing the rules module populates the registry as a side effect
# (it must follow the core import that defines the registry).
from . import rules

__all__ = [
    "FileContext",
    "Finding",
    "Rule",
    "all_rules",
    "apply_baseline",
    "check_file",
    "load_baseline",
    "prune_baseline",
    "register",
    "rules",
    "run_paths",
    "write_baseline",
]
