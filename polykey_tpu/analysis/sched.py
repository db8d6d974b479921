"""schedlint — the fifth analysis tier: scheduler liveness & fairness.

The engine loop's scheduling invariants — the interleaved-prefill
progress floor, the starved-first round-robin cursors, the
restore→prefill→decode frontier order, deadline-disciplined queues —
were enforced only by scattered regression tests and comments. ROADMAP item 1 (SLO-class-weighted scheduling) is
about to multiply every one of them by a traffic-class dimension, so
this tier turns them into contracts in the ``SL`` namespace alongside
PL/GL/CL/ML, with the same committed-empty baseline
(``schedlint-baseline.json``) and the same line-suppression syntax
(``# polylint: disable=SL002(reason)``). Stdlib-only AST.

``SL001`` progress floor
    A budget- or quota-bounded dispatch loop (an accumulator compared
    against a name containing ``budget``/``quota`` or ending in
    ``_slots``, guarding a break/return) must carry a statically
    provable at-least-one-dispatch conjunct: ``and spent > 0`` or a
    non-empty work-list truthiness test (``and ranges``). The "budget
    waived with no live lanes" and "one chunk regardless of budget"
    disciplines stop being comments and become checked shape.

``SL002`` cursor discipline
    Every modulo-N round-robin cursor (the ``_rr`` naming convention,
    or an ``_RRCursor`` instance) must be advanced or re-anchored on
    EVERY exit path of every consuming method — a cursor read whose
    path can return without a write means the same slot scans first
    forever. The cursor must stay bounded (no un-modded increment),
    and a sweep with an early exit (budget/skip path) must re-anchor
    starved-first somewhere in the method.

``SL003`` frontier ordering
    Inside one engine-loop iteration (the ``while not
    self._stop.is_set()`` loop), restores issue before chunked
    prefills, which issue before the decode dispatch — verified from
    first-call order in the loop body. The chunk advancer must skip
    faulting slots (``restore_pages is not None`` → continue): a
    faulting lane joins no dispatch until the restore frontier owns it.

``SL004`` bounded wait
    Every queue/deque a long-lived (lock-holding / serve-loop) class
    consumes must pair with an admission bound (bounded constructor or
    a ``len()``/``qsize()`` comparison) or a shed/deadline-drop path in
    a consuming method — no unboundedly deferrable work class.

``SL006`` observed starvation (``--witness``)
    Merges runtime starvation-witness summaries
    (analysis/schedwitness.py, ``POLYKEY_SCHED_WITNESS=1``) into the
    static verdict: a slot whose dispatch-boundary wait age exceeded
    the max-starvation-age gate, or whose consecutive-skip count
    exceeded the skip gate, is a finding carrying the frontier, slot,
    and observed numbers. The occupancy/disagg/autopilot smokes run
    under the witness and gate on zero.

``SL000`` is the meta rule (suppression hygiene, unparseable inputs,
stale contract anchors); like the other tiers' ``*000`` it refuses
--prune and --write-baseline while present.
"""

from __future__ import annotations

import argparse
import ast
import json
import sys
from pathlib import Path
from typing import Iterable, Iterator, Optional

from .baseline import (
    apply_baseline,
    load_baseline,
    prune_baseline,
    write_baseline,
)
from .core import (
    DEFAULT_TARGETS,
    FileContext,
    Finding,
    Rule,
    UsageError,
    iter_py_files,
    load_witness_arg,
    parse_only,
    require_full_run,
)

SCHED_BASELINE = "schedlint-baseline.json"

# Repo root of the PACKAGE (contract anchors name this repo's engine;
# the scanned --root may be elsewhere, but the frontier contract is
# about the code that actually runs).
_PKG_ROOT = Path(__file__).resolve().parents[2]

ENGINE_REL = "polykey_tpu/engine/engine.py"

# The engine-loop methods whose first-call order IS the frontier
# contract: restores ride ahead of chunked prefills, which ride ahead
# of the decode dispatch. If the engine renames one the contract is STALE
# (SL000), not silently green.
ORDERED_FRONTIERS = (
    "_issue_restores", "_advance_chunked_prefills", "_dispatch_step",
)


# SL006 gates. Engine-loop iterations are milliseconds; the progress
# floor + round-robin bound any eligible slot's wait to ~B iterations,
# so multi-second wait ages mean a lane genuinely aged out. The skip
# gate is the fast-spin backstop: a hot idle loop can rack thousands of
# boundaries per second, so it only fires far beyond fair-share skips.
WITNESS_MAX_WAIT_AGE_S = 5.0
WITNESS_MAX_SKIPS = 100_000


def _anchor(rel: str, needle: str) -> tuple[str, int]:
    """(rel, line) of the first source line containing `needle` in a
    package file — witness findings anchor at the frontier whose
    dispatch boundary observed the starvation."""
    try:
        text = (_PKG_ROOT / rel).read_text(encoding="utf-8")
        for i, line in enumerate(text.splitlines(), 1):
            if needle in line:
                return rel, i
    except OSError:
        pass
    return rel, 1


# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------


def _terminal(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _is_self_attr(node: ast.AST, attr: Optional[str] = None) -> bool:
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
            and (attr is None or node.attr == attr))


def _functions(tree: ast.AST) -> Iterator[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


# ---------------------------------------------------------------------------
# SL001: progress floor on budget-bounded dispatch loops
# ---------------------------------------------------------------------------


def _budget_like(name: str) -> bool:
    low = name.lower()
    return "budget" in low or "quota" in low or low.endswith("_slots")


def _budget_exit_compare(test: ast.AST, accs: set,
                         ) -> Optional[tuple[str, str]]:
    """(accumulator, budget name) when `test` contains `acc >= budget`
    (either operand order) against a budget-like name; else None."""
    nodes = test.values if isinstance(test, ast.BoolOp) else [test]
    for node in nodes:
        if not (isinstance(node, ast.Compare) and len(node.ops) == 1):
            continue
        left, op, right = node.left, node.ops[0], node.comparators[0]
        if (isinstance(op, (ast.Gt, ast.GtE))
                and isinstance(left, ast.Name) and left.id in accs
                and _budget_like(_terminal(right))):
            return left.id, _terminal(right)
        if (isinstance(op, (ast.Lt, ast.LtE))
                and isinstance(right, ast.Name) and right.id in accs
                and _budget_like(_terminal(left))):
            return right.id, _terminal(left)
    return None


def _has_progress_conjunct(test: ast.AST, accs: set, grown: set) -> bool:
    """True when the budget exit's own test proves at least one unit
    already dispatched: `and acc > 0`-shaped, or a truthiness test of a
    collection the loop appends dispatched work to (`and ranges`)."""
    if not (isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And)):
        return False
    for v in test.values:
        if (isinstance(v, ast.Compare) and len(v.ops) == 1
                and isinstance(v.left, ast.Name) and v.left.id in accs
                and isinstance(v.ops[0], (ast.Gt, ast.GtE))
                and isinstance(v.comparators[0], ast.Constant)
                and isinstance(v.comparators[0].value, (int, float))
                and (v.comparators[0].value > 0
                     or isinstance(v.ops[0], ast.Gt))):
            return True
        if isinstance(v, (ast.Name, ast.Attribute)) \
                and _terminal(v) in grown:
            return True
    return False


def _body_exits(stmts: list) -> bool:
    """A break/return reachable in this statement list WITHOUT entering
    a nested loop (whose break would not exit the budgeted loop)."""
    for s in stmts:
        if isinstance(s, (ast.Break, ast.Return)):
            return True
        if isinstance(s, ast.If):
            if _body_exits(s.body) or _body_exits(s.orelse):
                return True
        if isinstance(s, ast.With):
            if _body_exits(s.body):
                return True
    return False


class ProgressFloorRule(Rule):
    id = "SL001"
    name = "progress-floor"
    description = ("budget-bounded dispatch loop must prove at least "
                   "one dispatch before the budget exit can fire")

    def applies(self, rel: str) -> bool:
        return rel.startswith("polykey_tpu/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in _functions(ctx.tree):
            for loop in ast.walk(fn):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                accs = {
                    n.target.id for n in ast.walk(loop)
                    if isinstance(n, ast.AugAssign)
                    and isinstance(n.op, ast.Add)
                    and isinstance(n.target, ast.Name)
                }
                if not accs:
                    continue
                grown = {
                    _terminal(n.func.value) for n in ast.walk(loop)
                    if isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in ("append", "add")
                }
                for sub in ast.walk(loop):
                    if not isinstance(sub, ast.If):
                        continue
                    hit = _budget_exit_compare(sub.test, accs)
                    if hit is None or not _body_exits(sub.body):
                        continue
                    acc, budget = hit
                    if _has_progress_conjunct(sub.test, accs, grown):
                        continue
                    yield ctx.finding(
                        "SL001", sub,
                        f"budget exit `{acc} >= {budget}` has no progress "
                        f"floor — it can fire before the first dispatch, "
                        f"wedging the frontier when the budget is 0 or "
                        f"mis-tuned; add `and {acc} > 0` (or a non-empty "
                        "work-list conjunct) so one unit always proceeds, "
                        "or annotate SL001(reason)")


# ---------------------------------------------------------------------------
# SL002: round-robin cursor discipline
# ---------------------------------------------------------------------------


def _cursor_attrs(cls: ast.ClassDef) -> dict:
    """Map of cursor attribute name -> idiom ('int' | 'helper'),
    recognized by the `_rr` naming convention (the convention is part
    of the contract) or construction from an *RRCursor* factory."""
    attrs: dict[str, str] = {}
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and _is_self_attr(node.targets[0]):
            name = node.targets[0].attr
            v = node.value
            if isinstance(v, ast.Call) \
                    and "rrcursor" in _terminal(v.func).lower().replace("_", ""):
                attrs[name] = "helper"
            elif name.endswith("_rr"):
                attrs.setdefault(name, "int")
    return attrs


def _expr_cursor_read(node: ast.AST, attr: str) -> bool:
    """A read form: `(self.X + e) % n` or `self.X.scan(...)`."""
    for n in ast.walk(node):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod) \
                and any(_is_self_attr(s, attr) for s in ast.walk(n.left)):
            return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "scan" \
                and _is_self_attr(n.func.value, attr):
            return True
    return False


def _stmt_cursor_write(node: ast.AST, attr: str,
                       ) -> tuple[bool, Optional[int], bool]:
    """(writes, unbounded_line, reanchors) for one statement: any
    assignment to self.X or .advance()/.reanchor() call counts as a
    write; `self.X = self.X + c` with no modulo is the unbounded form;
    an assignment from a bare Name (the scan loop variable) or a
    .reanchor() call is the starved-first re-anchor form."""
    writes, unbounded, reanchors = False, None, False
    for n in ast.walk(node):
        if isinstance(n, ast.Assign) \
                and any(_is_self_attr(t, attr) for t in n.targets):
            writes = True
            if isinstance(n.value, ast.BinOp) \
                    and isinstance(n.value.op, ast.Add):
                unbounded = n.lineno
            if isinstance(n.value, ast.Name):
                reanchors = True
        if isinstance(n, ast.AugAssign) and _is_self_attr(n.target, attr):
            writes = True
            if isinstance(n.op, ast.Add):
                unbounded = n.lineno
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and _is_self_attr(n.func.value, attr):
            if n.func.attr in ("advance", "reanchor"):
                writes = True
            if n.func.attr == "reanchor":
                reanchors = True
    return writes, unbounded, reanchors


def _check_cursor_exits(fn: ast.FunctionDef, attr: str) -> list[int]:
    """Line numbers of exits reachable after a cursor read but before
    any cursor write — the "same slot scans first forever" paths. A
    conservative path-sensitive walk: branch joins keep `read` if any
    side read and keep `written` only if every surviving side wrote;
    loop bodies are analyzed as one symbolic iteration and never
    guarantee a write (they may run zero times)."""
    violations: list[int] = []

    def visit(stmts: list, read: bool, written: bool,
              ) -> tuple[bool, bool, bool]:
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, ast.Return):
                if read and not written:
                    violations.append(s.lineno)
                return read, written, True
            if isinstance(s, ast.If):
                if _expr_cursor_read(s.test, attr):
                    read = True
                r1, w1, e1 = visit(s.body, read, written)
                r2, w2, e2 = visit(s.orelse, read, written)
                if e1 and e2:
                    return read, written, True
                if e1:
                    read, written = r2, w2
                elif e2:
                    read, written = r1, w1
                else:
                    read, written = (r1 or r2), (w1 and w2)
                continue
            if isinstance(s, (ast.For, ast.While)):
                header = s.iter if isinstance(s, ast.For) else s.test
                if _expr_cursor_read(header, attr):
                    read = True
                r1, _w1, _e1 = visit(s.body, read, written)
                read = read or r1
                continue
            if isinstance(s, ast.Try):
                r1, w1, _e1 = visit(s.body, read, written)
                read = read or r1
                for h in s.handlers:
                    rh, _wh, _eh = visit(h.body, read, written)
                    read = read or rh
                if s.finalbody:
                    read, written, _ = visit(s.finalbody, read,
                                             written and w1)
                continue
            if isinstance(s, ast.With):
                read, written, exited = visit(s.body, read, written)
                if exited:
                    return read, written, True
                continue
            w, _ub, _re = _stmt_cursor_write(s, attr)
            if w:
                written = True
            if _expr_cursor_read(s, attr):
                read = True
        return read, written, False

    read, written, exited = visit(fn.body, False, False)
    if not exited and read and not written and fn.body:
        violations.append(fn.body[-1].lineno)
    return violations


class CursorRule(Rule):
    id = "SL002"
    name = "cursor-discipline"
    description = ("modulo-N round-robin cursor must advance or "
                   "re-anchor (starved-first) on every consumption path "
                   "and stay bounded")

    def applies(self, rel: str) -> bool:
        return rel.startswith("polykey_tpu/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            cursors = _cursor_attrs(cls)
            for attr in sorted(cursors):
                for fn in (n for n in cls.body
                           if isinstance(n, ast.FunctionDef)):
                    reads = _expr_cursor_read(fn, attr)
                    _w, unbounded, has_reanchor = _stmt_cursor_write(
                        fn, attr)
                    if unbounded is not None and fn.name != "__init__":
                        yield ctx.finding(
                            "SL002", unbounded,
                            f"cursor `{attr}` is advanced without a "
                            "modulo bound — it grows forever and the "
                            "`% n` consumers drift; write "
                            "`(cursor + 1) % n` or use the shared "
                            "_RRCursor helper")
                    if not reads:
                        continue
                    for line in _check_cursor_exits(fn, attr):
                        yield ctx.finding(
                            "SL002", line,
                            f"round-robin cursor `{attr}` is consumed in "
                            f"{fn.name}() but this exit path neither "
                            "advances nor re-anchors it — the same slot "
                            "scans first forever (starvation); advance "
                            "past the anchor on a completed sweep or "
                            "re-anchor on the starved slot")
                    # A sweep with an early exit (budget/skip path) must
                    # re-anchor starved-first SOMEWHERE in the method —
                    # always advancing past the anchor would be fair in
                    # shape but starve the skipped slot of its turn.
                    for loop in ast.walk(fn):
                        if not isinstance(loop, (ast.For, ast.While)):
                            continue
                        header = (loop.iter if isinstance(loop, ast.For)
                                  else loop.test)
                        in_loop = _expr_cursor_read(header, attr) or any(
                            _expr_cursor_read(s, attr) for s in loop.body)
                        if not in_loop:
                            continue
                        early = any(
                            isinstance(n, (ast.Break, ast.Return))
                            for n in ast.walk(loop))
                        if early and not has_reanchor:
                            yield ctx.finding(
                                "SL002", loop,
                                f"cursor `{attr}` sweep in {fn.name}() "
                                "has an early exit but the method never "
                                "re-anchors — the starved slot loses its "
                                "turn to the advance; re-anchor the "
                                "cursor ON the first slot the exit "
                                "skipped")


# ---------------------------------------------------------------------------
# SL003: frontier ordering inside the engine loop
# ---------------------------------------------------------------------------


def _is_engine_loop(node: ast.While) -> bool:
    """`while not self._stop.is_set()` (any attribute spelling that
    calls is_set on a *stop*-named event)."""
    for n in ast.walk(node.test):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "is_set" \
                and "stop" in _terminal(n.func.value).lower():
            return True
    return False


class FrontierOrderRule(Rule):
    id = "SL003"
    name = "frontier-ordering"
    description = ("restore -> prefill -> decode issue order per "
                   "engine-loop iteration; the chunk advancer skips "
                   "faulting slots")

    def applies(self, rel: str) -> bool:
        return rel.startswith("polykey_tpu/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, ast.While) or not _is_engine_loop(loop):
                continue
            first_call: dict[str, int] = {}
            for n in ast.walk(loop):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr in ORDERED_FRONTIERS:
                    first_call.setdefault(n.func.attr, n.lineno)
            present = [f for f in ORDERED_FRONTIERS if f in first_call]
            for a, b in zip(present, present[1:]):
                if first_call[a] >= first_call[b]:
                    yield ctx.finding(
                        "SL003", first_call[a],
                        f"frontier order violated in the engine loop: "
                        f"{a}() first issues at line {first_call[a]}, "
                        f"after {b}() at line {first_call[b]} — restores "
                        "must ride ahead of prefills ahead of the decode "
                        "dispatch so a faulting lane's pages land before "
                        "anything can read them")
        # The faulting-slot skip guard: only meaningful in modules that
        # have the host-KV restore tier at all (mention restore_pages).
        mentions_restore = any(
            isinstance(n, ast.Attribute) and n.attr == "restore_pages"
            for n in ast.walk(ctx.tree))
        if not mentions_restore:
            return
        for fn in _functions(ctx.tree):
            if fn.name != "_advance_chunked_prefills":
                continue
            guarded = False
            for n in ast.walk(fn):
                if isinstance(n, ast.If) and any(
                        isinstance(c, ast.Attribute)
                        and c.attr == "restore_pages"
                        for c in ast.walk(n.test)) \
                        and any(isinstance(b, ast.Continue)
                                for b in n.body):
                    guarded = True
            if not guarded:
                yield ctx.finding(
                    "SL003", fn,
                    f"{fn.name}() does not skip faulting slots "
                    "(`restore_pages is not None` -> continue) — a slot "
                    "whose pages are still on host must not join any "
                    "dispatch until the restore frontier issues its "
                    "scatter")


# ---------------------------------------------------------------------------
# SL004: bounded wait on consumed work queues
# ---------------------------------------------------------------------------

_QUEUE_CTORS = {"Queue", "SimpleQueue", "LifoQueue", "PriorityQueue",
                "deque"}
_CONSUME_ATTRS = {"get", "get_nowait", "popleft", "pop"}
_SHED_TOKENS = ("deadline", "expire", "shed", "drop")


def _ctor_bounded(call: ast.Call) -> bool:
    name = _terminal(call.func)
    if name == "deque":
        return len(call.args) >= 2 or any(
            k.arg == "maxlen" and not (isinstance(k.value, ast.Constant)
                                       and k.value.value is None)
            for k in call.keywords)
    return bool(call.args) or any(
        k.arg == "maxsize" for k in call.keywords)


def _class_long_lived(cls: ast.ClassDef) -> bool:
    for base in cls.bases:
        if _terminal(base) == "Thread":
            return True
    for n in ast.walk(cls):
        if isinstance(n, ast.While):
            if isinstance(n.test, ast.Constant) and n.test.value is True:
                return True
            if any(isinstance(c, ast.Call)
                   and isinstance(c.func, ast.Attribute)
                   and c.func.attr == "is_set"
                   for c in ast.walk(n.test)):
                return True
        if isinstance(n, ast.Call) \
                and _terminal(n.func) in ("Lock", "RLock", "Condition"):
            return True
    return False


class BoundedWaitRule(Rule):
    id = "SL004"
    name = "bounded-wait"
    description = ("queue/deque consumed by a long-lived loop needs an "
                   "admission bound, shed path, or deadline drop")

    def applies(self, rel: str) -> bool:
        return rel.startswith("polykey_tpu/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef) \
                    or not _class_long_lived(cls):
                continue
            queues: dict[str, tuple[int, bool]] = {}
            for n in ast.walk(cls):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and _is_self_attr(n.targets[0]) \
                        and isinstance(n.value, ast.Call) \
                        and _terminal(n.value.func) in _QUEUE_CTORS:
                    queues.setdefault(
                        n.targets[0].attr,
                        (n.lineno, _ctor_bounded(n.value)))
            if not queues:
                continue
            consumed: dict[str, set[str]] = {}
            sized: set[str] = set()
            for fn in (n for n in cls.body
                       if isinstance(n, ast.FunctionDef)):
                shed_here = any(
                    isinstance(n, ast.Call)
                    and any(t in _terminal(n.func).lower()
                            for t in _SHED_TOKENS)
                    for n in ast.walk(fn))
                for n in ast.walk(fn):
                    if isinstance(n, ast.Call) \
                            and isinstance(n.func, ast.Attribute) \
                            and n.func.attr in _CONSUME_ATTRS \
                            and _is_self_attr(n.func.value) \
                            and n.func.value.attr in queues:
                        consumed.setdefault(n.func.value.attr, set())
                        if shed_here:
                            consumed[n.func.value.attr].add("shed")
                    if isinstance(n, ast.Compare):
                        for side in [n.left] + list(n.comparators):
                            if isinstance(side, ast.Call):
                                f = side.func
                                if isinstance(f, ast.Name) \
                                        and f.id == "len" and side.args \
                                        and _is_self_attr(side.args[0]) \
                                        and side.args[0].attr in queues:
                                    sized.add(side.args[0].attr)
                                if isinstance(f, ast.Attribute) \
                                        and f.attr == "qsize" \
                                        and _is_self_attr(f.value) \
                                        and f.value.attr in queues:
                                    sized.add(f.value.attr)
            for attr, discipline in sorted(consumed.items()):
                line, bounded = queues[attr]
                if bounded or "shed" in discipline or attr in sized:
                    continue
                yield ctx.finding(
                    "SL004", line,
                    f"{cls.name}.{attr} is consumed by a long-lived loop "
                    "with no admission bound, shed path, or deadline "
                    "drop — work queued here can defer unboundedly; "
                    "bound the constructor, compare its length against "
                    "a cap, or drop expired entries at dequeue")


# ---------------------------------------------------------------------------
# SL006: observed starvation (runtime witness merge)
# ---------------------------------------------------------------------------

_FRONTIER_ANCHORS = {
    "restore": "def _issue_restores",
    "prefill": "def _advance_chunked_prefills",
    "decode": "def _dispatch_step",
}


def witness_findings(processes: list[dict],
                     max_wait_age_s: Optional[float] = None,
                     max_skips: Optional[int] = None) -> list[Finding]:
    """SL006: per-process, per-frontier starvation gate over merged
    sched-witness summaries. The wait-age gate is primary (wall-clock
    starvation is what an SLO sees); the consecutive-skip gate is the
    fast-spin backstop."""
    age_gate = WITNESS_MAX_WAIT_AGE_S if max_wait_age_s is None \
        else max_wait_age_s
    skip_gate = WITNESS_MAX_SKIPS if max_skips is None else max_skips
    findings: list[Finding] = []
    for proc in processes:
        pid = proc.get("pid", "?")
        for frontier, st in sorted(proc.get("frontiers", {}).items()):
            rel, line = _anchor(
                ENGINE_REL,
                _FRONTIER_ANCHORS.get(frontier, "def _dispatch_step"))
            age = float(st.get("max_wait_age_s", 0.0))
            if age > age_gate:
                findings.append(Finding(
                    rule="SL006", path=rel, line=line,
                    message=f"observed starvation at the {frontier} "
                            f"frontier (pid {pid}): slot "
                            f"{st.get('max_wait_slot')} waited "
                            f"{age:.3f}s across "
                            f"{st.get('max_consecutive_skips', 0)} "
                            f"skipped dispatch boundaries (gate "
                            f"{age_gate:g}s) — a lane aged out under "
                            "real load",
                    snippet=frontier))
            skips = int(st.get("max_consecutive_skips", 0))
            if skips > skip_gate:
                findings.append(Finding(
                    rule="SL006", path=rel, line=line,
                    message=f"observed starvation at the {frontier} "
                            f"frontier (pid {pid}): slot "
                            f"{st.get('max_skip_slot')} was skipped "
                            f"{skips} consecutive dispatch boundaries "
                            f"(gate {skip_gate}) while eligible",
                    snippet=frontier))
    return findings


def witness_verdict(processes: list[dict],
                    max_wait_age_s: Optional[float] = None,
                    max_skips: Optional[int] = None) -> dict:
    """The merged starvation verdict soak artifacts embed: worst wait
    age and skip count per frontier across every process, the gates,
    and whether the run was starvation-free."""
    frontiers: dict[str, dict] = {}
    for proc in processes:
        for name, st in proc.get("frontiers", {}).items():
            agg = frontiers.setdefault(name, {
                "notes": 0, "serves": 0, "max_wait_age_s": 0.0,
                "max_wait_slot": -1, "max_consecutive_skips": 0,
                "max_skip_slot": -1,
            })
            agg["notes"] += int(st.get("notes", 0))
            agg["serves"] += int(st.get("serves", 0))
            age = float(st.get("max_wait_age_s", 0.0))
            if age > agg["max_wait_age_s"]:
                agg["max_wait_age_s"] = age
                agg["max_wait_slot"] = st.get("max_wait_slot", -1)
            skips = int(st.get("max_consecutive_skips", 0))
            if skips > agg["max_consecutive_skips"]:
                agg["max_consecutive_skips"] = skips
                agg["max_skip_slot"] = st.get("max_skip_slot", -1)
    findings = witness_findings(processes, max_wait_age_s, max_skips)
    worst_age = max(
        (f["max_wait_age_s"] for f in frontiers.values()), default=0.0)
    return {
        "processes": len(processes),
        "gate_max_wait_age_s": (WITNESS_MAX_WAIT_AGE_S
                                if max_wait_age_s is None
                                else max_wait_age_s),
        "gate_max_consecutive_skips": (WITNESS_MAX_SKIPS
                                       if max_skips is None
                                       else max_skips),
        "frontiers": {k: dict(v) for k, v in sorted(frontiers.items())},
        "max_wait_age_s": round(worst_age, 3),
        "findings": [f.message for f in findings],
        "starvation_free": not findings,
    }


# ---------------------------------------------------------------------------
# Rule registry (for --list-rules and namespace validation)
# ---------------------------------------------------------------------------


class _ProjectRule(Rule):
    """Project-scope rule: implemented as a cross-file/witness check,
    present here so the SL namespace validates suppressions and --only
    ids."""

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        return iter(())


class WitnessStarvationRule(_ProjectRule):
    id = "SL006"
    name = "observed-starvation"
    description = ("sched witness observed a slot's wait age or "
                   "consecutive skips above the gate (--witness)")


SCHED_RULES: list[Rule] = [
    ProgressFloorRule(), CursorRule(), FrontierOrderRule(),
    BoundedWaitRule(), WitnessStarvationRule(),
]


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def _stale_contract_findings(ctx: FileContext) -> list[Finding]:
    """SL000 when the engine no longer carries the anchors SL003
    verify against — a renamed frontier method must fail loud, not let
    the contract silently stop checking anything."""
    have = {n.name for n in _functions(ctx.tree)}
    findings: list[Finding] = []
    for name in ORDERED_FRONTIERS:
        if name not in have:
            findings.append(Finding(
                rule="SL000", path=ctx.rel, line=1,
                message=f"frontier contract anchor {name}() is gone "
                        "from the engine — the scheduler contract is "
                        "stale; update ORDERED_FRONTIERS in "
                        "analysis/sched.py"))
    if not any(isinstance(n, ast.While) and _is_engine_loop(n)
               for n in ast.walk(ctx.tree)):
        findings.append(Finding(
            rule="SL000", path=ctx.rel, line=1,
            message="no `while not self._stop.is_set()` engine loop "
                    "found — SL003 has nothing to order; the scheduler "
                    "contract is stale"))
    return findings


def run_sched(root: Path, targets: Optional[Iterable[str]] = None,
              only: Optional[set[str]] = None,
              witness: Optional[list[dict]] = None,
              max_wait_age_s: Optional[float] = None,
              max_skips: Optional[int] = None) -> list[Finding]:
    """Run the sched tier. `only` restricts to the named SL rules
    (already validated); `witness` is the loaded per-process snapshot
    list (SL006). Findings come back sorted with per-file suppressions
    applied (a partial run refuses --prune, so skipping can't drop
    debt)."""
    if targets is None:
        targets = [t for t in DEFAULT_TARGETS if (root / t).exists()]
        if not targets:
            raise FileNotFoundError(
                f"none of the default lint targets "
                f"({', '.join(DEFAULT_TARGETS)}) exist under {root}")
    want = (lambda rid: only is None or rid in only)

    contexts: dict[str, FileContext] = {}
    findings: list[Finding] = []
    for path in iter_py_files(root, targets):
        rel = path.resolve().relative_to(root.resolve()).as_posix()
        if rel.startswith("polykey_tpu/proto/"):
            continue
        source = path.read_text(encoding="utf-8")
        try:
            contexts[rel] = FileContext(path, rel, source)
        except SyntaxError as e:
            findings.append(Finding(
                rule="SL000", path=rel, line=e.lineno or 1,
                message=f"syntax error: {e.msg}"))

    by_path: dict[str, list[Finding]] = {rel: [] for rel in contexts}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)

    for rule in SCHED_RULES:
        if not want(rule.id):
            continue
        for rel, ctx in contexts.items():
            if rule.applies(rel):
                by_path[rel].extend(rule.check(ctx))

    if ENGINE_REL in contexts:
        by_path[ENGINE_REL].extend(
            _stale_contract_findings(contexts[ENGINE_REL]))

    if want("SL006") and witness is not None:
        for f in witness_findings(witness, max_wait_age_s, max_skips):
            by_path.setdefault(f.path, []).append(f)

    out: list[Finding] = []
    for rel in sorted(by_path):
        ctx = contexts.get(rel)
        fs = by_path[rel]
        if ctx is not None:
            fs = ctx.apply_suppressions(fs, rules=SCHED_RULES)
        out.extend(fs)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m polykey_tpu.analysis sched",
        description="schedlint: scheduler liveness & fairness contract "
                    "analysis (progress floors, cursor discipline, "
                    "frontier order, quota conservation, starvation "
                    "witness)",
    )
    parser.add_argument(
        "targets", nargs="*", default=None,
        help=f"files/directories to scan (default: "
             f"{' '.join(DEFAULT_TARGETS)})")
    parser.add_argument("--root", default=".",
                        help="repo root (default: cwd)")
    parser.add_argument("--baseline", default=SCHED_BASELINE,
                        metavar="FILE",
                        help="grandfathering baseline file")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline file entirely")
    parser.add_argument("--write-baseline", action="store_true",
                        help="grandfather current blocking findings")
    parser.add_argument("--prune", action="store_true",
                        help="drop stale baseline entries, then exit")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit findings + summary as JSON")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule table and exit")
    parser.add_argument("--only", metavar="IDS",
                        help="comma-separated rule ids to run "
                             "(e.g. SL002,SL006)")
    parser.add_argument("--witness", metavar="PATH",
                        help="sched-witness JSON file or directory to "
                             "merge (SL006)")
    parser.add_argument("--max-wait-age", type=float, default=None,
                        metavar="SECONDS",
                        help=f"SL006 wait-age gate (default "
                             f"{WITNESS_MAX_WAIT_AGE_S:g}s)")
    return parser


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.list_rules:
        print("SL000  meta                       suppression hygiene, "
              "unparseable inputs, stale contract anchors")
        for rule in SCHED_RULES:
            print(f"{rule.id}  {rule.name:<26} {rule.description}")
        return 0

    root = Path(args.root).resolve()
    if not root.is_dir():
        print(f"schedlint: --root {args.root} is not a directory",
              file=sys.stderr)
        return 2

    targets = args.targets or None
    try:
        only = parse_only(args.only, {r.id for r in SCHED_RULES})
        require_full_run(partial=bool(targets) or only is not None,
                         prune=args.prune,
                         write_baseline=args.write_baseline)
        from . import schedwitness

        witness = load_witness_arg(args.witness,
                                   schedwitness.load_witness)
    except UsageError as e:
        print(f"schedlint: {e}", file=sys.stderr)
        return 2

    try:
        findings = run_sched(root, targets, only, witness,
                             args.max_wait_age)
    except FileNotFoundError as e:
        print(f"schedlint: {e}", file=sys.stderr)
        return 2

    partial = bool(targets) or only is not None
    if partial:
        # Unused-suppression and stale-baseline signals need the full
        # sweep; a partial run must neither report nor act on them.
        findings = [f for f in findings
                    if not (f.rule == "SL000"
                            and "unused suppression" in f.message)]

    meta = [f for f in findings if f.rule == "SL000" and f.blocking]
    baseline_path = root / args.baseline
    if args.prune:
        if meta:
            print("schedlint: refusing --prune while SL000 findings "
                  "exist (a broken check is a partial run in disguise):",
                  file=sys.stderr)
            for f in meta:
                print(f"  {f.render()}", file=sys.stderr)
            return 2
        kept, dropped = prune_baseline(baseline_path, findings)
        print(f"schedlint: pruned {dropped} stale baseline entr"
              f"{'y' if dropped == 1 else 'ies'} from {baseline_path} "
              f"({kept} kept)")
        return 0
    if args.write_baseline:
        if meta:
            print("schedlint: refusing --write-baseline while SL000 "
                  "findings exist — fix the infrastructure first:",
                  file=sys.stderr)
            for f in meta:
                print(f"  {f.render()}", file=sys.stderr)
            return 2
        count = write_baseline(baseline_path, findings)
        print(f"schedlint: wrote {count} baseline entr"
              f"{'y' if count == 1 else 'ies'} to {baseline_path}")
        return 0

    stale: list[str] = []
    if not args.no_baseline:
        findings, stale = apply_baseline(
            findings, load_baseline(baseline_path))
        if partial:
            stale = []      # partial runs can't call entries stale

    blocking = [f for f in findings if f.blocking]
    suppressed = sum(1 for f in findings if f.suppressed)
    baselined = sum(1 for f in findings if f.baselined)

    if args.as_json:
        payload = {
            "findings": [f.to_json() for f in findings],
            "summary": {
                "blocking": len(blocking),
                "suppressed": suppressed,
                "baselined": baselined,
                "stale_baseline_entries": stale,
                "witness_processes": len(witness) if witness else 0,
                "sched_clean": not blocking,
            },
        }
        if witness:
            payload["witness_verdict"] = witness_verdict(
                witness, args.max_wait_age)
        print(json.dumps(payload, indent=2))
    else:
        for f in findings:
            if f.blocking:
                print(f.render())
        parts = [f"{len(blocking)} blocking"]
        if suppressed:
            parts.append(f"{suppressed} suppressed")
        if baselined:
            parts.append(f"{baselined} baselined")
        if witness:
            verdict = witness_verdict(witness, args.max_wait_age)
            parts.append(
                f"{len(witness)} witness process"
                f"{'' if len(witness) == 1 else 'es'} merged "
                f"(max wait age {verdict['max_wait_age_s']:g}s)")
        print(f"schedlint: {', '.join(parts)}")
        if stale and not partial:
            print(f"schedlint: {len(stale)} stale baseline entr"
                  f"{'y' if len(stale) == 1 else 'ies'} (fixed findings) "
                  "— re-run with --prune")
    return 1 if blocking else 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
