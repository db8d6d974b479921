"""polylint core: file model, rule registry, suppressions, runner.

Rules operate on a ``FileContext`` — parsed AST plus a tokenize-derived
comment map (comments matter here: a justification comment is part of
the ``except`` contract, and suppressions live in comments). Everything
is stdlib-only so the CLI runs in the dependency-free CI lint job.

Suppression syntax (shown here in the docstring because a literal
example in a comment would parse as a live suppression)::

    x = np.asarray(d)  # polylint: disable=PL001(deliberate resolve point)

A suppression on a comment-only line applies to the next code line (for
statements too long to carry a trailing comment). Reasons are mandatory;
multiple rules separate with commas::

    # polylint: disable=PL001(sync ok), PL003(error surfaces via queue)
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Iterable, Iterator, Optional

SUPPRESS_RE = re.compile(r"polylint:\s*disable=(?P<entries>.+)$")
# The reason may itself contain one level of balanced parentheses
# ("async copy (D2H) landed"); deeper nesting is not supported.
# The rule id's two-letter prefix names the tier that owns it: PL = the
# AST tier here, CL = racelint (analysis/concurrency.py), ML = memlint
# (analysis/memory.py), SL = schedlint (analysis/sched.py). One comment
# syntax serves every line-anchored tier; each tier validates only the
# suppressions in its own namespace, so a CL004 annotation in engine
# code is invisible to a plain polylint run instead of an "unknown
# rule" finding.
ENTRY_RE = re.compile(
    r"(?P<rule>[A-Z]{2}\d{3})\s*"
    r"(?:\((?P<reason>[^()]*(?:\([^()]*\)[^()]*)*)\))?"
)
# Every namespace a line-comment suppression can legally target. An
# entry outside this set (a typo'd prefix, or GL — the graph tier
# suppresses via class-level SUPPRESSIONS, not comments) suppresses
# nothing; the base PL tier reports it so it can't sit dead forever.
LINE_TIER_PREFIXES = frozenset({"PL", "CL", "ML", "SL"})


@dataclass
class Suppression:
    rule: str
    reason: str
    target_line: int      # code line this suppression covers
    comment_line: int     # where the comment physically sits
    used: bool = False


@dataclass(frozen=True)
class Finding:
    rule: str             # "PL003"
    path: str             # repo-relative posix path
    line: int             # 1-based
    message: str
    snippet: str = ""     # stripped source line (feeds the baseline hash)
    suppressed: bool = False
    reason: str = ""      # suppression reason when suppressed
    baselined: bool = False

    @property
    def blocking(self) -> bool:
        return not (self.suppressed or self.baselined)

    def render(self) -> str:
        tag = ""
        if self.suppressed:
            tag = f"  [suppressed: {self.reason}]"
        elif self.baselined:
            tag = "  [baselined]"
        return f"{self.path}:{self.line}: {self.rule} {self.message}{tag}"

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "suppressed": self.suppressed,
            "baselined": self.baselined,
        }


class FileContext:
    """One parsed source file: AST, raw lines, comment map, suppressions."""

    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        # line -> comment text (without '#'), via tokenize so '#' inside
        # string literals can't masquerade as comments.
        self.comments: dict[int, str] = {}
        # lines carrying at least one non-comment, non-NL token — used to
        # distinguish trailing comments from comment-only lines.
        self.code_lines: set[int] = set()
        try:
            for tok in tokenize.generate_tokens(io.StringIO(source).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string.lstrip("#").strip()
                elif tok.type not in (
                    tokenize.NL, tokenize.NEWLINE, tokenize.INDENT,
                    tokenize.DEDENT, tokenize.ENDMARKER,
                ):
                    self.code_lines.add(tok.start[0])
        except tokenize.TokenError:
            pass  # partial comment map; the AST parse already succeeded
        self.suppressions: list[Suppression] = []
        # (comment line, rule id or None, detail) — rendered into
        # meta-findings by apply_suppressions, which knows the running
        # tier's namespace (a reasonless CL entry is racelint's problem,
        # not polylint's).
        self.bad_suppressions: list[tuple[int, Optional[str], str]] = []
        self._parse_suppressions()

    # -- helpers rules use ---------------------------------------------------

    def finding(self, rule: str, node, message: str) -> Finding:
        line = node if isinstance(node, int) else node.lineno
        snippet = self.lines[line - 1].strip() if 0 < line <= len(self.lines) else ""
        return Finding(rule=rule, path=self.rel, line=line,
                       message=message, snippet=snippet)

    def has_justification(self, start: int, end: int) -> bool:
        """A non-suppression comment anywhere on lines [start, end]."""
        for line in range(start, end + 1):
            text = self.comments.get(line)
            if text is not None and not SUPPRESS_RE.search(text):
                return True
        return False

    # -- suppressions --------------------------------------------------------

    def _parse_suppressions(self) -> None:
        for line, text in sorted(self.comments.items()):
            m = SUPPRESS_RE.search(text)
            if m is None:
                continue
            target = line
            if line not in self.code_lines:
                # Comment-only line: covers the next code line.
                nxt = line + 1
                while nxt <= len(self.lines) and nxt not in self.code_lines:
                    nxt += 1
                target = nxt
            entries = m.group("entries")
            matched_spans: list[tuple[int, int]] = []
            for em in ENTRY_RE.finditer(entries):
                matched_spans.append(em.span())
                rule, reason = em.group("rule"), (em.group("reason") or "").strip()
                if not reason:
                    self.bad_suppressions.append((
                        line, rule,
                        f"suppression for {rule} is missing its "
                        f"(reason) — write disable={rule}(why this is safe)",
                    ))
                    continue
                self.suppressions.append(Suppression(
                    rule=rule, reason=reason,
                    target_line=target, comment_line=line,
                ))
            leftover = "".join(
                entries[i] for i in range(len(entries))
                if not any(a <= i < b for a, b in matched_spans)
            ).strip(" ,")
            if leftover:
                self.bad_suppressions.append((
                    line, None,
                    f"malformed suppression entry {leftover!r} "
                    "(expected PLxxx(reason))",
                ))

    def apply_suppressions(self, findings: list[Finding],
                           rules: Optional[list["Rule"]] = None,
                           ) -> list[Finding]:
        """Mark suppressed findings and surface suppression hygiene
        problems — for ONE tier's namespace. `rules` is the rule set the
        run used (polylint's full registry when None); only suppressions
        whose id shares a prefix with those rules are validated here, so
        each tier polices its own comments. Rule-less malformed entries
        are attributed to the base PL tier (the one that always runs)."""
        tier_rules = rules if rules is not None else all_rules()
        known = {r.id for r in tier_rules}
        prefixes = {rule_id[:2] for rule_id in known} or {"PL"}
        meta = min(prefixes) + "000"
        out: list[Finding] = []
        for f in findings:
            hit: Optional[Suppression] = None
            for s in self.suppressions:
                if s.rule == f.rule and s.target_line == f.line:
                    hit = s
                    break
            if hit is not None:
                hit.used = True
                out.append(replace(f, suppressed=True, reason=hit.reason))
            else:
                out.append(f)
        for s in self.suppressions:
            if s.rule[:2] not in prefixes:
                # Another LINE tier's namespace validates its own
                # entries; a prefix no line tier owns would otherwise
                # be invisible to every run — the always-running base
                # tier claims it.
                if "PL" in prefixes and s.rule[:2] not in LINE_TIER_PREFIXES:
                    out.append(self.finding(
                        meta, s.comment_line,
                        f"suppression names rule {s.rule} in a "
                        "namespace no line tier owns (valid prefixes: "
                        f"{', '.join(sorted(LINE_TIER_PREFIXES))}) — "
                        "it suppresses nothing",
                    ))
                continue
            if s.rule not in known:
                out.append(self.finding(
                    meta, s.comment_line,
                    f"suppression names unknown rule {s.rule}",
                ))
            elif not s.used:
                out.append(self.finding(
                    meta, s.comment_line,
                    f"unused suppression for {s.rule} — the rule no longer "
                    "fires here; delete the comment",
                ))
        for line, rule, message in self.bad_suppressions:
            if rule is None:
                if "PL" in prefixes:
                    out.append(self.finding(meta, line, message))
            elif rule[:2] in prefixes:
                out.append(self.finding(meta, line, message))
        return out


# -- rule registry ------------------------------------------------------------


class Rule:
    """Base rule. Subclasses set id/name/description and implement check();
    applies() scopes by repo-relative path."""

    id: str = "PL000"
    name: str = "unnamed"
    description: str = ""

    def applies(self, rel: str) -> bool:
        return True

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        raise NotImplementedError


_REGISTRY: dict[str, Rule] = {}


def register(cls: type[Rule]) -> type[Rule]:
    inst = cls()
    if inst.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {inst.id}")
    _REGISTRY[inst.id] = inst
    return cls


def all_rules() -> list[Rule]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


# -- shared CLI plumbing ------------------------------------------------------
#
# Every line-anchored tier's main() repeats the same three safety
# behaviors: --only typo rejection (a typo'd id silently running zero
# rules reads as a clean repo), --prune/--write-baseline refusal on
# partial runs (a partial run can't tell "fixed" from "not scanned"),
# and --witness load-error handling. One implementation here; each tier
# catches UsageError, prints it under its own prog name, and exits 2.


class UsageError(Exception):
    """CLI usage error (exit code 2). The tier main prints str(e) to
    stderr prefixed with its own tier name."""


def parse_only(raw: Optional[str], known: set,
               noun: str = "rule") -> Optional[set]:
    """Parse a --only value against the tier's known ids. Returns the
    selected id set (None = full run); raises UsageError on a typo'd
    id — it must not silently run zero rules."""
    if not raw:
        return None
    only = {t.strip().upper() for t in raw.split(",") if t.strip()}
    unknown = only - set(known)
    if unknown:
        raise UsageError(
            f"unknown {noun} id(s) for --only: {', '.join(sorted(unknown))} "
            f"(known: {', '.join(sorted(known))})"
        )
    return only


def require_full_run(*, partial: bool, prune: bool,
                     write_baseline: bool) -> None:
    """Refuse baseline mutation on a partial run: pruning against it
    drops live entries for everything outside the selection, and
    write-baseline is worse — it rewrites the file from only the run
    rules' findings, silently discarding every other rule's debt."""
    if (prune or write_baseline) and partial:
        flag = "--prune" if prune else "--write-baseline"
        raise UsageError(
            f"{flag} requires a full run (drop --only and explicit targets)"
        )


def load_witness_arg(path: Optional[str], loader):
    """Load a --witness file-or-directory via the tier's loader
    (witness/heapwitness/schedwitness .load_witness). Returns the
    per-process snapshot list, or None when no path was given; raises
    UsageError on unreadable or version-mismatched dumps."""
    if not path:
        return None
    try:
        return loader(path)
    except (OSError, ValueError) as e:
        raise UsageError(f"cannot load witness {path}: {e}") from e


# -- runner -------------------------------------------------------------------

DEFAULT_TARGETS = ("polykey_tpu", "scripts")
_EXCLUDE_DIRS = {"__pycache__"}
# Generated protobuf stubs and this package's test fixtures are not ours
# to lint.
_EXCLUDE_PREFIXES = ("polykey_tpu/proto/",)


def iter_py_files(root: Path, targets: Iterable[str]) -> Iterator[Path]:
    for target in targets:
        p = root / target
        if p.is_file() and p.suffix == ".py":
            yield p
        elif p.is_dir():
            for sub in sorted(p.rglob("*.py")):
                if _EXCLUDE_DIRS.isdisjoint(sub.parts):
                    yield sub
        else:
            # A typo'd target must not let the gate pass with 0 files
            # linted ("0 blocking" on nothing looks like success).
            raise FileNotFoundError(
                f"lint target {target!r} is neither a .py file nor a "
                f"directory under {root}"
            )


def check_file(path: Path, root: Path,
               rules: Optional[list[Rule]] = None) -> list[Finding]:
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    if rel.startswith(_EXCLUDE_PREFIXES):
        return []
    source = path.read_text(encoding="utf-8")
    try:
        ctx = FileContext(path, rel, source)
    except SyntaxError as e:
        return [Finding(rule="PL000", path=rel, line=e.lineno or 1,
                        message=f"syntax error: {e.msg}")]
    findings: list[Finding] = []
    for rule in (rules if rules is not None else all_rules()):
        if rule.applies(rel):
            findings.extend(rule.check(ctx))
    findings = ctx.apply_suppressions(findings, rules=rules)
    return sorted(findings, key=lambda f: (f.line, f.rule))


def run_paths(root: Path, targets: Optional[Iterable[str]] = None,
              rules: Optional[list[Rule]] = None) -> list[Finding]:
    """Lint every .py file under `targets` (repo defaults when None).
    Explicit targets must exist (FileNotFoundError otherwise — a typo'd
    path must not pass as '0 findings'); defaults tolerate absentees so
    partial trees (tests, subprojects) still lint."""
    if targets is None:
        targets = [t for t in DEFAULT_TARGETS if (root / t).exists()]
        if not targets:
            raise FileNotFoundError(
                f"none of the default lint targets "
                f"({', '.join(DEFAULT_TARGETS)}) exist under {root}"
            )
    findings: list[Finding] = []
    for path in iter_py_files(root, targets):
        findings.extend(check_file(path, root, rules))
    return findings
