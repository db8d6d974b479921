"""polylint CLI: ``python -m polykey_tpu.analysis``.

Exit codes: 0 clean (suppressed/baselined findings allowed), 1 blocking
findings, 2 usage error. ``--json`` emits one machine-readable object
(findings + summary) for CI annotation tooling.

``python -m polykey_tpu.analysis graph`` dispatches to the second
analysis tier (graphlint, analysis/graph.py): compiled-graph contract
checks that need jax, traced on a CPU backend. The AST tier here stays
stdlib-only — the dispatch imports graph lazily so the dependency-free
CI lint job is unaffected.

``python -m polykey_tpu.analysis race`` dispatches to the third tier
(racelint, analysis/concurrency.py): concurrency and cross-process
protocol contracts — lock-order cycles, unguarded shared state,
lock-scope escapes, interprocedural blocking-under-lock, and
coordinator/worker protocol conformance. Stdlib-only like this tier.

``python -m polykey_tpu.analysis mem`` dispatches to the fourth tier
(memlint, analysis/memory.py): memory & capacity contracts — the
analytic byte ledger vs chip HBM, unbounded-growth AST rules, knob
documentation/ship contracts, and the runtime heap-witness merge.
Stdlib-only like this tier.

``python -m polykey_tpu.analysis sched`` dispatches to the fifth tier
(schedlint, analysis/sched.py): scheduler liveness & fairness contracts
— progress floors on budget-bounded dispatch loops, round-robin cursor
discipline, frontier ordering, bounded-wait queues, and the runtime
starvation-witness merge. Stdlib-only like this tier.

``python -m polykey_tpu.analysis all`` runs all five tiers with one
aggregate exit code (and one merged JSON object under ``--json``).

Shared CLI plumbing (``--only`` typo rejection, ``--prune``/
``--write-baseline`` partial-run refusal, ``--witness`` loading) lives
in core.py (parse_only / require_full_run / load_witness_arg raising
UsageError) so the five tiers cannot drift on the refusal semantics.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from .baseline import (
    DEFAULT_BASELINE,
    apply_baseline,
    load_baseline,
    prune_baseline,
    write_baseline,
)
from .core import (
    DEFAULT_TARGETS,
    UsageError,
    all_rules,
    require_full_run,
    run_paths,
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m polykey_tpu.analysis",
        description="polylint: project-invariant static analysis for the "
                    "TPU serving stack",
    )
    parser.add_argument(
        "targets", nargs="*", default=None,
        help=f"files/directories to lint (default: {' '.join(DEFAULT_TARGETS)})",
    )
    parser.add_argument(
        "--root", default=".",
        help="repo root paths are reported relative to (default: cwd)",
    )
    parser.add_argument(
        "--baseline", default=DEFAULT_BASELINE, metavar="FILE",
        help="grandfathering baseline file (missing file = empty baseline)",
    )
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="ignore the baseline file entirely",
    )
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="grandfather every current blocking finding into --baseline",
    )
    parser.add_argument(
        "--prune", action="store_true",
        help="drop baseline entries whose finding no longer exists "
             "(deleted file / fixed line / changed content), then exit",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit findings + summary as one JSON object",
    )
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the rule table and exit",
    )
    return parser


def run_all(argv: list[str]) -> int:
    """``python -m polykey_tpu.analysis all [--json]``: polylint +
    racelint + graphlint + memlint + schedlint as one gate. Each tier runs its full
    default sweep against its own committed baseline; the exit code is
    clean only when every tier is. Tier-specific flags (--only, --prune,
    --write-baseline, targets) are refused — partial aggregate runs
    would report 'all clean' while skipping debt (the graphlint --only
    precedent, applied across tiers)."""
    parser = argparse.ArgumentParser(
        prog="python -m polykey_tpu.analysis all",
        description="run every analysis tier (polylint + racelint + "
                    "graphlint + memlint + schedlint) with one "
                    "aggregate exit code",
    )
    parser.add_argument("--root", default=".",
                        help="repo root for every tier (default: cwd)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="one merged JSON object over all tiers")
    args = parser.parse_args(argv)

    import contextlib
    import io

    from . import concurrency, graph, memory, sched

    tiers = (
        ("polylint", main),
        ("racelint", concurrency.main),
        ("graphlint", graph.main),
        ("memlint", memory.main),
        ("schedlint", sched.main),
    )
    results: dict[str, dict] = {}
    codes: dict[str, int] = {}
    for name, tier_main in tiers:
        tier_argv = ["--root", args.root]
        if args.as_json:
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                codes[name] = tier_main(tier_argv + ["--json"])
            try:
                results[name] = json.loads(buf.getvalue())
            except ValueError:
                results[name] = {"error": buf.getvalue()[-2000:]}
        else:
            print(f"== {name} ==")
            codes[name] = tier_main(tier_argv)
    aggregate = max(codes.values(), default=0)
    if args.as_json:
        print(json.dumps({
            "tiers": results,
            "summary": {
                "exit_codes": codes,
                "blocking": sum(
                    r.get("summary", {}).get("blocking", 0)
                    for r in results.values()
                ),
                "all_clean": aggregate == 0,
            },
        }, indent=2))
    else:
        status = ", ".join(f"{name}={code}"
                           for name, code in codes.items())
        print(f"analysis all: {status} -> "
              f"{'CLEAN' if aggregate == 0 else 'FAILING'}")
    return aggregate


def main(argv: list[str] | None = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "graph":
        # The graph tier needs jax; import only on explicit request so
        # the AST tier keeps running in dependency-free environments.
        from . import graph

        return graph.main(argv[1:])
    if argv and argv[0] == "race":
        from . import concurrency

        return concurrency.main(argv[1:])
    if argv and argv[0] == "mem":
        # memlint is stdlib-only but imports engine.config/roofline for
        # the byte ledger; keep it off the base tier's import path.
        from . import memory

        return memory.main(argv[1:])
    if argv and argv[0] == "sched":
        from . import sched

        return sched.main(argv[1:])
    if argv and argv[0] == "all":
        return run_all(argv[1:])
    args = build_parser().parse_args(argv)

    if args.list_rules:
        for rule in all_rules():
            print(f"{rule.id}  {rule.name:<26} {rule.description}")
        return 0

    root = Path(args.root).resolve()
    if not root.is_dir():
        print(f"polylint: --root {args.root} is not a directory",
              file=sys.stderr)
        return 2
    targets = args.targets or None
    try:
        # A partial run can't tell "fixed" from "not scanned"; pruning
        # against it would drop live baseline entries for every file
        # outside the target list (shared refusal semantics, core.py).
        require_full_run(partial=bool(targets), prune=args.prune,
                         write_baseline=False)
    except UsageError as e:
        print(f"polylint: {e}", file=sys.stderr)
        return 2
    try:
        findings = run_paths(root, targets)
    except FileNotFoundError as e:
        print(f"polylint: {e}", file=sys.stderr)
        return 2

    baseline_path = root / args.baseline
    if args.prune:
        kept, dropped = prune_baseline(baseline_path, findings)
        print(f"polylint: pruned {dropped} stale baseline entr"
              f"{'y' if dropped == 1 else 'ies'} from {baseline_path} "
              f"({kept} kept)")
        return 0
    if args.write_baseline:
        count = write_baseline(baseline_path, findings)
        print(f"polylint: wrote {count} baseline entr"
              f"{'y' if count == 1 else 'ies'} to {baseline_path}")
        return 0

    stale: list[str] = []
    if not args.no_baseline:
        findings, stale = apply_baseline(findings, load_baseline(baseline_path))

    blocking = [f for f in findings if f.blocking]
    suppressed = sum(1 for f in findings if f.suppressed)
    baselined = sum(1 for f in findings if f.baselined)

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_json() for f in findings],
            "summary": {
                "blocking": len(blocking),
                "suppressed": suppressed,
                "baselined": baselined,
                "stale_baseline_entries": stale,
                "files_clean": not blocking,
            },
        }, indent=2))
    else:
        for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
            if f.blocking:
                print(f.render())
        parts = [f"{len(blocking)} blocking"]
        if suppressed:
            parts.append(f"{suppressed} suppressed")
        if baselined:
            parts.append(f"{baselined} baselined")
        print(f"polylint: {', '.join(parts)}")
        if stale:
            print(
                f"polylint: {len(stale)} stale baseline entr"
                f"{'y' if len(stale) == 1 else 'ies'} (fixed findings) — "
                "re-run with --write-baseline to prune",
            )
    return 1 if blocking else 0
