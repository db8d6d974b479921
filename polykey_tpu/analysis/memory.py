"""memlint — the fourth analysis tier: memory & capacity contracts.

Every next roadmap direction is a bytes problem (adapter slabs paged
like KV, page-level compression targets, resident-floor autopilot), yet
the repo's capacity statements were prose computed ad hoc. This tier
turns them into contracts a CI gate re-derives, in the ``ML`` namespace
alongside PL (polylint), GL (graphlint) and CL (racelint), with the
same committed-empty baseline (``memlint-baseline.json``) and the same
line-suppression syntax (``# polylint: disable=ML002(reason)``).

Three rule families, stdlib-only (the ledger is analytic — it mirrors
the allocator arithmetic in ``kv_cache.init_paged_kv`` via the pure
helpers in ``engine/roofline.py``, and tests pin the mirror byte-for-
byte against the jax-backed allocator):

``ML001`` capacity contracts
    An analytic byte ledger per served engine config: resident weights
    (``roofline.weight_resident_bytes``), the preallocated device KV
    pool and its int8 scale planes (``roofline.kv_pool_bytes_split``),
    the draft model's pool under speculation, plus first-order peak
    transients for every warmed jit executable (prefill at the largest
    bucket, decode at full slots, spec at gamma+1 positions,
    gather/restore staging at one full sequence of pages). Donation
    credits come from the same alias map GL002 audits: executables that
    donate ``paged`` reuse the pool in place, so the ledger counts it
    once (and records the credit — if donation breaks, GL002 fails
    before this ledger lies). The contract: per-chip resident + largest
    transient must fit ``ChipSpec.hbm_bytes`` for every entry of the
    served matrix, and every matrix entry must pass
    ``EngineConfig.validate()`` — a validate()-accepted config that
    cannot fit is a finding, not a surprise OOM at warmup.

``ML002`` unbounded growth
    Module/class containers that long-lived objects grow without a cap,
    ring, LRU, or amortized-gc discipline. A class counts as long-lived
    when it holds a threading primitive or runs a ``while True`` loop
    (serve-path objects); module-level containers are process-lived by
    definition. Discipline is any shrink path on the same container
    (pop/popitem/clear/del/discard/popleft, reassignment outside
    __init__, a ``len(...)`` cap check, or ``deque(maxlen=...)`` at
    construction). Deliberate survivors (the flight-deck rings, sticky
    maps, EWMA state, witness edge sets) carry ML002 annotations with
    reasons.

``ML003``/``ML004``/``ML005`` knob contracts
    Every ``POLYKEY_*`` env read must appear as a row in DEPLOY.md's
    knob tables or be declared internal-only here (ML003); a knob that
    ``EngineConfig.from_env`` owns must not be re-parsed ad hoc
    elsewhere in the package (ML004 — default drift); and every knob
    ``from_env`` reads must ship to disagg workers via ``_config_env``
    or carry a coordinator-only exemption with a reason (ML005 — the
    PR 15 "knob not shipped to workers" bug class, made structural).

``ML006`` observed growth (``--witness``)
    Merges runtime heap-witness series (analysis/heapwitness.py,
    ``POLYKEY_HEAP_WITNESS=1``) into the static findings: sustained
    tracemalloc growth after warmup, or a pool observed above its
    declared capacity, is a finding carrying real evidence. The hostkv
    and disagg smokes run under the witness and gate on zero.

``ML000`` is the meta rule (suppression hygiene, unparseable inputs,
stale matrix entries); like PL000/GL000/CL000 it refuses --prune and
--write-baseline while present.
"""

from __future__ import annotations

import argparse
import ast
import json
import math
import sys
from dataclasses import replace as dc_replace
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

MEM_BASELINE = "memlint-baseline.json"

# Repo root of the PACKAGE (ledger anchors name this repo's files; the
# scanned --root may be elsewhere, but the capacity contract is about
# the code that is actually imported).
_PKG_ROOT = Path(__file__).resolve().parents[2]

# ---------------------------------------------------------------------------
# ML001: the served-model capacity matrix.
#
# One entry per BASELINE.md measurement config that reaches a TPU:
# single-chip 8B in both quantization widths (config 2), the TP=4 bf16
# variant (config 3), expert-parallel Mixtral (config 4), and Gemma-2
# with its speculative draft (config 5). Geometry not listed here is
# the EngineConfig default (2048 pages x 16 tokens, 16 decode slots).
# ---------------------------------------------------------------------------

SERVED_MATRIX: tuple[dict, ...] = (
    {"name": "llama3-8b-int8", "model": "llama-3-8b", "dtype": "bfloat16",
     "quantize": True, "quantize_bits": 8, "kv_dtype": "int8",
     "chip": "tpu-v5e", "n_chips": 1},
    {"name": "llama3-8b-int4", "model": "llama-3-8b", "dtype": "bfloat16",
     "quantize": True, "quantize_bits": 4, "kv_dtype": "int8",
     "chip": "tpu-v5e", "n_chips": 1},
    {"name": "llama3-8b-bf16-tp4", "model": "llama-3-8b",
     "dtype": "bfloat16", "quantize": False, "quantize_bits": 8,
     "kv_dtype": "", "chip": "tpu-v5e", "n_chips": 4, "mesh": {"tp": 4}},
    {"name": "mixtral-8x7b-int8-ep4", "model": "mixtral-8x7b",
     "dtype": "bfloat16", "quantize": True, "quantize_bits": 8,
     "kv_dtype": "int8", "chip": "tpu-v5e", "n_chips": 4,
     "mesh": {"ep": 4}},
    {"name": "gemma2-27b-int8-spec-tp4", "model": "gemma-2-27b",
     "dtype": "bfloat16", "quantize": True, "quantize_bits": 8,
     "kv_dtype": "int8", "chip": "tpu-v5e", "n_chips": 4,
     "mesh": {"tp": 4}, "draft_model": "gemma-2-2b"},
)

# Executables that donate their KV pool operand (mirrors engine.py's
# donate_argnames, which GL002 audits against the compiled alias map).
# The ledger counts a donated pool once: in+out alias in place.
DONATED_EXECUTABLES = {
    "prefill": ("paged",),
    "decode": ("paged", "last_tokens", "seq_lens", "active"),
    "spec_prefill": ("t_paged", "d_paged"),
    "spec_decode": ("t_paged", "d_paged"),
    "kv_restore": ("paged",),
}

# ---------------------------------------------------------------------------
# ML003: knobs that are deliberately NOT operator surface. Each entry is
# an explicit internal-only annotation — the documented alternative to a
# DEPLOY.md row. A knob must appear in exactly one place.
# ---------------------------------------------------------------------------

INTERNAL_KNOBS: dict[str, str] = {
    # dev/test escape hatches and harness-local switches; each is
    # documented at its read site.
    "POLYKEY_FAULTS":
        "chaos fault-injection spec (faults.py); test/soak harness "
        "surface, never an operator knob",
}

# ---------------------------------------------------------------------------
# ML005: from_env knobs that legitimately never ship to disagg workers.
# Reasons are part of the contract — an exemption without a mechanism
# ("validate() rejects it" / "coordinator consumes it") would just be
# the PR 15 bug with paperwork.
# ---------------------------------------------------------------------------

WORKER_ENV_EXEMPT: dict[str, str] = {
    "POLYKEY_DRAFT_MODEL":
        "validate() rejects draft models under disagg (spec decode is "
        "single-engine); a worker can never need it",
    "POLYKEY_DRAFT_CHECKPOINT": "rides POLYKEY_DRAFT_MODEL (see above)",
    "POLYKEY_SPEC_GAMMA": "rides POLYKEY_DRAFT_MODEL (see above)",
    "POLYKEY_ADAPTIVE_GAMMA": "rides POLYKEY_DRAFT_MODEL (see above)",
    "POLYKEY_ROUTE_W_PREFIX":
        "replica-pool routing weight; the coordinator routes, workers "
        "only serve what arrives",
    "POLYKEY_ROUTE_W_DELAY": "coordinator routing weight (see above)",
    "POLYKEY_MAX_REROUTES": "coordinator routing policy (see above)",
    "POLYKEY_DISAGG":
        "the spawn pins POLYKEY_DISAGG=\"\" on workers (no recursive "
        "pools); shipping the parent's value would fork-bomb",
    "POLYKEY_REPLICAS":
        "the spawn pins POLYKEY_REPLICAS=1 on workers (see above)",
    "POLYKEY_DISAGG_HEARTBEAT":
        "coordinator liveness policy; workers answer heartbeats, they "
        "do not time them",
    "POLYKEY_DISAGG_MISS": "coordinator liveness policy (see above)",
    "POLYKEY_DISAGG_RECOVERY_WAIT":
        "coordinator liveness policy (see above)",
}

# ML006 thresholds: growth below the floor OR below the fraction of the
# post-warmup base is noise (allocator jitter, late caches); both must
# be exceeded AND the growth must be sustained (still rising in the
# final half) to flag.
WITNESS_GROWTH_FLOOR_BYTES = 16 << 20
WITNESS_GROWTH_FRACTION = 0.20
WITNESS_MIN_CHECKPOINTS = 6


# ---------------------------------------------------------------------------
# The analytic byte ledger
# ---------------------------------------------------------------------------


def _engine_config(entry: dict):
    """Materialize a SERVED_MATRIX entry as an EngineConfig (defaults +
    the entry's model/precision/mesh overrides)."""
    from ..engine.config import EngineConfig

    mesh = entry.get("mesh", {})
    return dc_replace(
        EngineConfig(),
        model=entry["model"],
        dtype=entry["dtype"],
        quantize=entry["quantize"],
        quantize_bits=entry["quantize_bits"],
        kv_dtype=entry["kv_dtype"],
        draft_model=entry.get("draft_model"),
        tp=mesh.get("tp", 1),
        dp=mesh.get("dp", 1),
        ep=mesh.get("ep", 1),
        sp=mesh.get("sp", 1),
        pp=mesh.get("pp", 1),
    )


def build_ledger(cfg, chip_name: str, n_chips: int,
                 chip_specs: Optional[dict] = None) -> dict:
    """Analytic resident + peak-transient bytes for one engine config.

    All arithmetic is stdlib: weights via roofline's geometry model,
    pools via the pure mirror of kv_cache.init_paged_kv (a test pins
    the mirror against the allocator), transients first-order — the
    activation stream (4H + 2I per token), fp32 logits rows, and the
    paged staging of one full sequence for gather/restore. That is the
    same fidelity stance roofline.py documents: good enough to tell "it
    fits with 40% headroom" from "warmup OOMs", which is the contract.
    """
    from ..engine import roofline
    from ..models.config import get_config

    specs = chip_specs if chip_specs is not None else roofline.CHIP_SPECS
    chip = specs[chip_name]
    mcfg = get_config(cfg.model)
    kv_dt = cfg.kv_dtype or cfg.dtype
    act = 2.0 if cfg.dtype == "bfloat16" else 4.0

    weights = roofline.weight_resident_bytes(
        mcfg, cfg.dtype, cfg.quantize, cfg.quantize_bits)
    kv_values, kv_scales = roofline.kv_pool_bytes_split(
        mcfg, cfg.num_pages, cfg.page_size, kv_dt)

    draft_weights = draft_kv = 0.0
    dcfg = None
    if cfg.draft_model:
        dcfg = get_config(cfg.draft_model)
        weights_d = roofline.weight_resident_bytes(
            dcfg, cfg.dtype, cfg.quantize, cfg.quantize_bits)
        draft_weights = weights_d
        draft_kv = roofline.kv_pool_bytes_spec(
            dcfg, cfg.num_pages, cfg.page_size, kv_dt)

    def stream(tokens: float, m) -> float:
        # Residual stream + attention projections (~4H) and the gated
        # MLP pair (~2I) per token — the dominant live activations.
        return tokens * (4.0 * m.hidden_size
                         + 2.0 * m.intermediate_size) * act

    max_bucket = float(max(cfg.prefill_buckets))
    slots = float(cfg.max_decode_slots)
    vocab = float(mcfg.vocab_size)
    # fp32 logits: one row for prefill's final position, one per lane
    # for decode.
    transients = {
        "prefill": stream(max_bucket, mcfg) + vocab * 4.0,
        "decode": stream(slots, mcfg) + slots * vocab * 4.0,
    }
    if dcfg is not None:
        spec_tokens = slots * (cfg.spec_gamma + 1.0)
        transients["spec_decode"] = (
            stream(spec_tokens, mcfg) + stream(spec_tokens, dcfg)
            + spec_tokens * vocab * 4.0)
    # Gather/restore staging: the KV pages of one full sequence cross as
    # a dense operand (handoff upload, host-tier restore scatter).
    seq_pages = math.ceil(cfg.max_seq_len / cfg.page_size)
    page_bytes = roofline.kv_pool_bytes_spec(mcfg, 1, cfg.page_size, kv_dt)
    transients["kv_gather"] = float(seq_pages) * page_bytes
    if cfg.host_kv_bytes > 0:
        transients["kv_restore"] = float(seq_pages) * page_bytes

    resident = weights + kv_values + kv_scales + draft_weights + draft_kv
    peak_transient = max(transients.values())
    per_chip = resident / n_chips + peak_transient
    # Donation credit: every pool-touching executable donates its pool
    # (DONATED_EXECUTABLES, audited by GL002), so no executable ever
    # holds an undonated output copy of the pool. The credit is what
    # the peak would grow by if that contract broke.
    donation_credit = kv_values + kv_scales + draft_kv

    host = {}
    if cfg.host_kv_bytes > 0:
        host_page = roofline.kv_pool_bytes_spec(
            mcfg, 1, cfg.page_size, kv_dt)
        host = {
            "host_kv_bytes": float(cfg.host_kv_bytes),
            "host_kv_page_bytes": host_page,
            "host_capacity_pages": int(cfg.host_kv_bytes // host_page),
        }

    return {
        "model": cfg.model,
        "chip": chip_name,
        "n_chips": n_chips,
        "weights_bytes": weights,
        "draft_weights_bytes": draft_weights,
        "kv_pool_bytes": kv_values,
        "kv_scale_pool_bytes": kv_scales,
        "draft_kv_pool_bytes": draft_kv,
        "transient_bytes": transients,
        "peak_transient_bytes": peak_transient,
        "donation_credit_bytes": donation_credit,
        "resident_bytes": resident,
        "per_chip_bytes": per_chip,
        "hbm_bytes_per_chip": float(chip.hbm_bytes),
        "hbm_fraction": per_chip / chip.hbm_bytes,
        "fits": per_chip <= chip.hbm_bytes,
        **host,
    }


def _anchor(rel: str, needle: str) -> tuple[str, int]:
    """(rel, line) of the first source line containing `needle` in a
    package file — capacity findings anchor where the violated number
    is declared, so the baseline fingerprint tracks the declaration."""
    try:
        text = (_PKG_ROOT / rel).read_text(encoding="utf-8")
        for i, line in enumerate(text.splitlines(), 1):
            if needle in line:
                return rel, i
    except OSError:
        pass
    return rel, 1


def check_capacity(matrix: Optional[Iterable[dict]] = None,
                   chip_specs: Optional[dict] = None,
                   ) -> tuple[list[Finding], list[dict]]:
    """ML001: every served matrix entry must validate() AND fit the
    ledger into its chip's HBM. Returns (findings, ledger entries)."""
    findings: list[Finding] = []
    ledgers: list[dict] = []
    roofline_rel = "polykey_tpu/engine/roofline.py"
    config_rel = "polykey_tpu/engine/config.py"
    for entry in (matrix if matrix is not None else SERVED_MATRIX):
        try:
            cfg = _engine_config(entry)
            cfg.validate()
        except Exception as e:
            rel, line = _anchor(config_rel, "def validate")
            findings.append(Finding(
                rule="ML000", path=rel, line=line,
                message=f"served-matrix entry {entry['name']!r} no longer "
                        f"passes EngineConfig.validate(): {e} — the "
                        "capacity matrix is stale",
                snippet=entry["name"]))
            continue
        ledger = build_ledger(cfg, entry["chip"], entry["n_chips"],
                              chip_specs=chip_specs)
        ledger["name"] = entry["name"]
        ledgers.append(ledger)
        if not ledger["fits"]:
            rel, line = _anchor(roofline_rel, f'"{entry["chip"]}"')
            gib = 1 << 30
            findings.append(Finding(
                rule="ML001", path=rel, line=line,
                message=f"capacity contract violated for "
                        f"{entry['name']}: weights "
                        f"{ledger['weights_bytes'] / gib:.2f} GiB + KV "
                        f"pool {(ledger['kv_pool_bytes'] + ledger['kv_scale_pool_bytes']) / gib:.2f} GiB "
                        f"+ peak transient "
                        f"{ledger['peak_transient_bytes'] / gib:.2f} GiB = "
                        f"{ledger['per_chip_bytes'] / gib:.2f} GiB/chip > "
                        f"{ledger['hbm_bytes_per_chip'] / gib:.0f} GiB "
                        f"{entry['chip']} HBM (x{entry['n_chips']} chips) "
                        "— a validate()-accepted config that OOMs at "
                        "warmup",
                snippet=entry["name"]))
    return findings, ledgers


# ---------------------------------------------------------------------------
# ML002: unbounded-growth AST rule
# ---------------------------------------------------------------------------

_GROW_METHODS = {"append", "appendleft", "add", "insert", "extend",
                 "setdefault", "update"}
_SHRINK_METHODS = {"pop", "popitem", "popleft", "clear", "remove",
                   "discard"}
_CONTAINER_FACTORIES = {"dict", "list", "set", "OrderedDict",
                        "defaultdict", "Counter"}
_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore", "allocate_lock"}


def _call_name(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _empty_container_kind(node: ast.AST) -> Optional[str]:
    """Container-typed initializer with no bound: {} / [] / set() /
    dict() / list() / OrderedDict() / defaultdict(...) / Counter() /
    deque(...) WITHOUT maxlen. Returns the kind name or None."""
    if isinstance(node, ast.Dict) and not node.keys:
        return "dict"
    if isinstance(node, ast.List) and not node.elts:
        return "list"
    if isinstance(node, ast.Call):
        name = _call_name(node.func)
        if name == "deque":
            if any(kw.arg == "maxlen" for kw in node.keywords):
                return None
            return "deque"
        if name in _CONTAINER_FACTORIES and not node.args:
            return name
        if name == "defaultdict":
            return name
    return None


def _self_attr(node: ast.AST) -> Optional[str]:
    """'x' for `self.x`, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


class _ClassScan:
    def __init__(self) -> None:
        self.containers: dict[str, tuple[str, int]] = {}  # attr -> kind, line
        self.growth: dict[str, tuple[int, str]] = {}      # attr -> line, method
        self.disciplined: set[str] = set()
        self.long_lived = False


def _scan_class(cls: ast.ClassDef) -> _ClassScan:
    scan = _ClassScan()
    if any(_call_name(b) == "Thread" for b in cls.bases):
        scan.long_lived = True
    for method in cls.body:
        if not isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        is_init = method.name == "__init__"
        for node in ast.walk(method):
            if isinstance(node, ast.While):
                test = node.test
                if isinstance(test, ast.Constant) and test.value is True:
                    scan.long_lived = True
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                value = node.value
                for tgt in targets:
                    attr = _self_attr(tgt)
                    if attr is None:
                        continue
                    if value is not None and isinstance(value, ast.Call) \
                            and _call_name(value.func) in _LOCK_FACTORIES:
                        scan.long_lived = True
                    if is_init:
                        if value is not None:
                            kind = _empty_container_kind(value)
                            if kind is not None:
                                scan.containers.setdefault(
                                    attr, (kind, node.lineno))
                    else:
                        # Reassignment outside __init__ is a reset /
                        # truncation path: discipline.
                        scan.disciplined.add(attr)
            if isinstance(node, ast.Call):
                func = node.func
                if isinstance(func, ast.Attribute):
                    attr = _self_attr(func.value)
                    if attr is not None:
                        if func.attr in _GROW_METHODS and not is_init:
                            scan.growth.setdefault(
                                attr, (node.lineno, method.name))
                        elif func.attr in _SHRINK_METHODS:
                            scan.disciplined.add(attr)
                if isinstance(func, ast.Name) and func.id == "len" \
                        and node.args:
                    attr = _self_attr(node.args[0])
                    if attr is not None:
                        # A len() check anywhere in the class is a cap /
                        # amortized-gc signal.
                        scan.disciplined.add(attr)
            if isinstance(node, ast.Delete):
                for tgt in node.targets:
                    base = tgt.value if isinstance(tgt, ast.Subscript) \
                        else tgt
                    attr = _self_attr(base)
                    if attr is not None:
                        scan.disciplined.add(attr)
            if isinstance(node, ast.Assign) and not is_init:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Subscript):
                        attr = _self_attr(tgt.value)
                        if attr is not None and attr in scan.containers:
                            scan.growth.setdefault(
                                attr, (node.lineno, method.name))
    return scan


class GrowthRule(Rule):
    id = "ML002"
    name = "unbounded-growth"
    description = ("long-lived container grows without a cap, ring, LRU, "
                   "or amortized-gc discipline")

    def applies(self, rel: str) -> bool:
        # Serve-path packages only: harness scripts accumulate results
        # for the lifetime of one bounded run.
        return rel.startswith("polykey_tpu/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # Module-level containers are process-lived by definition.
        module_containers: dict[str, tuple[str, int]] = {}
        module_disciplined: set[str] = set()
        module_growth: dict[str, tuple[int, str]] = {}
        for node in ctx.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                kind = _empty_container_kind(node.value)
                if kind is not None:
                    module_containers.setdefault(
                        node.targets[0].id, (kind, node.lineno))
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                func = node.func
                if isinstance(func, ast.Attribute) \
                        and isinstance(func.value, ast.Name) \
                        and func.value.id in module_containers:
                    if func.attr in _GROW_METHODS:
                        module_growth.setdefault(
                            func.value.id, (node.lineno, func.attr))
                    elif func.attr in _SHRINK_METHODS:
                        module_disciplined.add(func.value.id)
                if isinstance(func, ast.Name) and func.id == "len" \
                        and node.args \
                        and isinstance(node.args[0], ast.Name) \
                        and node.args[0].id in module_containers:
                    module_disciplined.add(node.args[0].id)
            if isinstance(node, ast.Delete):
                for tgt in node.targets:
                    base = tgt.value if isinstance(tgt, ast.Subscript) \
                        else tgt
                    if isinstance(base, ast.Name) \
                            and base.id in module_containers:
                        module_disciplined.add(base.id)
            if isinstance(node, ast.Assign) \
                    and not isinstance(node, ast.AnnAssign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Subscript) \
                            and isinstance(tgt.value, ast.Name) \
                            and tgt.value.id in module_containers:
                        module_growth.setdefault(
                            tgt.value.id, (node.lineno, "[]="))
        for name, (line, how) in sorted(module_growth.items()):
            kind, decl = module_containers[name]
            if name in module_disciplined:
                continue
            if decl == line:
                continue
            yield ctx.finding(
                "ML002", line,
                f"module-level {kind} `{name}` (declared line {decl}) "
                f"grows via {how} with no shrink path — module state "
                "lives for the process; bound it or annotate "
                "ML002(reason)")

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            scan = _scan_class(node)
            if not scan.long_lived:
                continue
            for attr, (line, method) in sorted(scan.growth.items()):
                if attr not in scan.containers:
                    continue
                if attr in scan.disciplined:
                    continue
                kind, decl = scan.containers[attr]
                yield ctx.finding(
                    "ML002", line,
                    f"{node.name}.{attr} ({kind}, created line {decl}) "
                    f"grows in {method}() with no cap, ring, LRU, or "
                    "amortized-gc discipline — this class is long-lived "
                    "(lock/serve loop); bound it or annotate "
                    "ML002(reason)")


# ---------------------------------------------------------------------------
# Knob contracts (ML003/ML004/ML005)
# ---------------------------------------------------------------------------

_ENV_GET_ATTRS = {"get", "getenv", "pop"}
_ENV_HELPERS = {"_env_int", "_env_float", "_env_bool", "getenv"}


def _attr_chain(node: ast.AST) -> list[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts


def _const_str(node: ast.AST, consts: dict[str, str]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def module_env_reads(tree: ast.AST) -> list[tuple[str, int, str]]:
    """Every POLYKEY_* env READ in a module: (knob, line, enclosing
    function name or '<module>'). Reads are .get/.getenv/.pop calls on
    an environ-like object, the config helpers (_env_int/_env_float/
    _env_bool), and environ[...] subscripts in Load context — dict
    literal keys and env[...] = assignments (the ship side) don't
    count. Module-level string constants resolve one level deep."""
    consts: dict[str, str] = {}
    for node in getattr(tree, "body", []):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            consts[node.targets[0].id] = node.value.value
    reads: list[tuple[str, int, str]] = []

    def visit(node: ast.AST, func: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            func = node.name
        if isinstance(node, ast.Call):
            knob = None
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _ENV_GET_ATTRS:
                chain = _attr_chain(f.value)
                if "environ" in chain or (
                        chain == ["os"] and f.attr == "getenv"):
                    knob = _const_str(node.args[0], consts) \
                        if node.args else None
            elif isinstance(f, ast.Name) and f.id in _ENV_HELPERS:
                knob = _const_str(node.args[0], consts) \
                    if node.args else None
            if knob and knob.startswith("POLYKEY_") \
                    and len(knob) > len("POLYKEY_"):
                reads.append((knob, node.lineno, func))
        if isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and "environ" in _attr_chain(node.value):
            knob = _const_str(node.slice, consts)
            if knob and knob.startswith("POLYKEY_") \
                    and len(knob) > len("POLYKEY_"):
                reads.append((knob, node.lineno, "<subscript>"))
        for child in ast.iter_child_nodes(node):
            visit(child, func)

    visit(tree, "<module>")
    return reads


def deploy_documented_knobs(deploy_text: str) -> set[str]:
    """Knob names with a row in a DEPLOY.md knob table: every
    backticked POLYKEY_* in the FIRST cell of a table line (one row may
    document a family, e.g. the mesh axes TP/DP/EP/SP/PP). Mentions in
    later cells (runbook prose) don't count as documentation."""
    import re

    documented: set[str] = set()
    for m in re.finditer(r"(?m)^\|\s*(`[^|]*)\|", deploy_text):
        documented.update(
            re.findall(r"`(POLYKEY_[A-Z0-9_]+)`", m.group(1)))
    return documented


def check_knob_docs(env_reads: dict[str, list[tuple[str, int, str]]],
                    deploy_text: Optional[str],
                    ) -> list[Finding]:
    """ML003: every knob read anywhere must have a DEPLOY.md table row
    or an internal-only annotation (INTERNAL_KNOBS). One finding per
    knob, at its first read site."""
    findings: list[Finding] = []
    if deploy_text is None:
        rel, line = _anchor("polykey_tpu/analysis/memory.py",
                            "def check_knob_docs")
        return [Finding(
            rule="ML000", path=rel, line=line,
            message="DEPLOY.md is missing or unreadable — the knob-"
                    "documentation contract (ML003) cannot run")]
    documented = deploy_documented_knobs(deploy_text)
    first_site: dict[str, tuple[str, int]] = {}
    for rel in sorted(env_reads):
        for knob, line, _fn in env_reads[rel]:
            first_site.setdefault(knob, (rel, line))
    for knob in sorted(first_site):
        if knob in documented or knob in INTERNAL_KNOBS:
            continue
        rel, line = first_site[knob]
        findings.append(Finding(
            rule="ML003", path=rel, line=line,
            message=f"{knob} is read here but has no DEPLOY.md knob-"
                    "table row and no internal-only annotation "
                    "(analysis/memory.py INTERNAL_KNOBS) — an operator "
                    "cannot discover it",
            snippet=knob))
    return findings


CONFIG_REL = "polykey_tpu/engine/config.py"
DISAGG_REL = "polykey_tpu/engine/disagg_pool.py"


def check_knob_single_parse(
        env_reads: dict[str, list[tuple[str, int, str]]]) -> list[Finding]:
    """ML004: a knob EngineConfig.from_env owns must not be re-read ad
    hoc elsewhere in the package — two parse sites mean two defaults
    that drift apart. Harness scripts/bench are exempt (they *set* the
    env for the engine to read)."""
    owned = {knob for knob, _l, fn in env_reads.get(CONFIG_REL, ())}
    findings: list[Finding] = []
    for rel in sorted(env_reads):
        if rel == CONFIG_REL or not rel.startswith("polykey_tpu/"):
            continue
        seen: set[str] = set()
        for knob, line, _fn in env_reads[rel]:
            if knob in owned and knob not in seen:
                seen.add(knob)
                findings.append(Finding(
                    rule="ML004", path=rel, line=line,
                    message=f"{knob} already parses in "
                            "EngineConfig.from_env — a second ad-hoc "
                            "read risks default drift; route through "
                            "the config object (or annotate "
                            "ML004(reason))",
                    snippet=knob))
    return findings


def from_env_knobs(config_tree: ast.AST) -> set[str]:
    """Knobs EngineConfig.from_env reads (the engine-relevant set)."""
    for node in ast.walk(config_tree):
        if isinstance(node, ast.FunctionDef) and node.name == "from_env":
            return {knob for knob, _l, _f in module_env_reads(
                ast.Module(body=[node], type_ignores=[]))}
    return set()


def shipped_knobs(disagg_tree: ast.AST) -> set[str]:
    """Knobs _config_env renders (dict-literal keys) plus any
    env["POLYKEY_X"] = ... pins elsewhere in the module (the spawn's
    DISAGG/REPLICAS/METRICS_PORT overrides)."""
    shipped: set[str] = set()
    for node in ast.walk(disagg_tree):
        if isinstance(node, ast.FunctionDef) \
                and node.name == "_config_env":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Dict):
                    for key in sub.keys:
                        if isinstance(key, ast.Constant) \
                                and isinstance(key.value, str) \
                                and key.value.startswith("POLYKEY_"):
                            shipped.add(key.value)
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript) \
                        and isinstance(tgt.slice, ast.Constant) \
                        and isinstance(tgt.slice.value, str) \
                        and tgt.slice.value.startswith("POLYKEY_"):
                    shipped.add(tgt.slice.value)
    return shipped


def check_ship_contract(config_tree: ast.AST, disagg_tree: ast.AST,
                        disagg_rel: str = DISAGG_REL,
                        exempt: Optional[dict[str, str]] = None,
                        ) -> list[Finding]:
    """ML005: from_env ∖ (_config_env ∪ spawn pins ∪ exemptions) must be
    empty — a knob the engine parses but the disagg spawn doesn't ship
    silently reverts to its default inside every worker (the PR 15
    _config_env bug class)."""
    exempt_map = WORKER_ENV_EXEMPT if exempt is None else exempt
    env = from_env_knobs(config_tree)
    shipped = shipped_knobs(disagg_tree)
    def_line = 1
    for node in ast.walk(disagg_tree):
        if isinstance(node, ast.FunctionDef) \
                and node.name == "_config_env":
            def_line = node.lineno
    findings: list[Finding] = []
    for knob in sorted(env - shipped):
        if knob in exempt_map:
            continue
        findings.append(Finding(
            rule="ML005", path=disagg_rel, line=def_line,
            message=f"{knob} parses in EngineConfig.from_env but "
                    "_config_env never ships it — disagg workers "
                    "silently run the default (the PR 15 bug class); "
                    "add it to _config_env or exempt it with a reason "
                    "in analysis/memory.py WORKER_ENV_EXEMPT",
            snippet=knob))
    for knob in sorted(set(exempt_map) - env):
        findings.append(Finding(
            rule="ML000", path=disagg_rel, line=def_line,
            message=f"WORKER_ENV_EXEMPT names {knob}, which from_env "
                    "no longer reads — stale exemption, delete it",
            snippet=knob))
    return findings


# ---------------------------------------------------------------------------
# ML006: heap-witness merge
# ---------------------------------------------------------------------------


def _witness_growth(series: list[int]) -> tuple[int, bool]:
    """(growth bytes, sustained?) after discarding the warmup prefix."""
    if len(series) < WITNESS_MIN_CHECKPOINTS:
        return 0, False
    warm = max(2, len(series) // 3)
    base = series[warm]
    mid = series[(warm + len(series) - 1) // 2]
    last = series[-1]
    growth = last - base
    sustained = last > base and last >= mid
    return growth, sustained


def witness_findings(processes: list[dict]) -> list[Finding]:
    findings: list[Finding] = []
    for proc in processes:
        cps = proc.get("checkpoints", [])
        path = proc.get("argv0") or "<heap-witness>"
        series = [int(cp.get("traced_current", 0)) for cp in cps]
        growth, sustained = _witness_growth(series)
        if sustained and growth > max(
                WITNESS_GROWTH_FLOOR_BYTES,
                WITNESS_GROWTH_FRACTION * series[max(2, len(series) // 3)]):
            warm = max(2, len(series) // 3)
            base_top = {t["file"]: t["bytes"]
                        for t in cps[warm].get("top", [])}
            deltas = sorted(
                ((t["bytes"] - base_top.get(t["file"], 0), t["file"])
                 for t in cps[-1].get("top", [])),
                reverse=True)[:3]
            sites = ", ".join(f"{f} (+{d >> 10} KiB)"
                              for d, f in deltas if d > 0) or "unknown"
            findings.append(Finding(
                rule="ML006", path=path, line=1,
                message=f"observed unbounded heap growth: traced heap "
                        f"grew {growth >> 20} MiB after warmup over "
                        f"{len(cps)} checkpoints (pid "
                        f"{proc.get('pid')}); top growing sites: "
                        f"{sites}",
                snippet=f"pid={proc.get('pid')}"))
        overflowed: set[str] = set()
        for cp in cps:
            for name, pool in (cp.get("pools") or {}).items():
                used = pool.get("used")
                cap = pool.get("capacity")
                if used is None or cap is None or name in overflowed:
                    continue
                if used > cap:
                    # First offending checkpoint per pool — one finding,
                    # not one per sample of the same breach.
                    overflowed.add(name)
                    findings.append(Finding(
                        rule="ML006", path=path, line=1,
                        message=f"pool {name!r} observed above its "
                                f"declared capacity at checkpoint "
                                f"{cp.get('label')!r}: used {used} > "
                                f"capacity {cap} — the static ledger "
                                "no longer matches the allocator",
                        snippet=name))
    return findings


# ---------------------------------------------------------------------------
# Rule registry (for --list-rules and namespace validation)
# ---------------------------------------------------------------------------


class _ProjectRule(Rule):
    """Project-scope rule: implemented as a cross-file check, present
    here so the ML namespace validates suppressions and --only ids."""

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        return iter(())


class CapacityRule(_ProjectRule):
    id = "ML001"
    name = "capacity-contract"
    description = ("served config's weights + KV pool + scale pools + "
                   "largest transient must fit ChipSpec.hbm_bytes")


class KnobDocRule(_ProjectRule):
    id = "ML003"
    name = "knob-documented"
    description = ("every POLYKEY_* read needs a DEPLOY.md row or an "
                   "internal-only annotation")


class KnobSingleParseRule(_ProjectRule):
    id = "ML004"
    name = "knob-single-parse"
    description = ("a from_env-owned knob must not be re-read ad hoc "
                   "elsewhere in the package")


class KnobShipRule(_ProjectRule):
    id = "ML005"
    name = "knob-ships-to-workers"
    description = ("every from_env knob ships via disagg _config_env "
                   "or carries a coordinator-only exemption")


class WitnessGrowthRule(_ProjectRule):
    id = "ML006"
    name = "observed-growth"
    description = ("heap witness observed sustained growth or a pool "
                   "above its declared capacity (--witness)")


MEM_RULES: list[Rule] = [
    CapacityRule(), GrowthRule(), KnobDocRule(), KnobSingleParseRule(),
    KnobShipRule(), WitnessGrowthRule(),
]


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_memlint(root: Path, targets: Optional[Iterable[str]] = None,
                only: Optional[set[str]] = None,
                witness: Optional[list[dict]] = None,
                ) -> tuple[list[Finding], list[dict]]:
    """Run the tier. Returns (findings, capacity ledgers). `only`
    filters rule ids; project checks whose inputs fall outside the
    scanned targets are skipped on partial runs (mirroring racelint:
    a partial run refuses --prune, so skipping can't drop debt)."""
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
                rule="ML000", path=rel, line=e.lineno or 1,
                message=f"syntax error: {e.msg}"))

    by_path: dict[str, list[Finding]] = {rel: [] for rel in contexts}

    if want("ML002"):
        rule = next(r for r in MEM_RULES if r.id == "ML002")
        for rel, ctx in contexts.items():
            if rule.applies(rel):
                by_path[rel].extend(rule.check(ctx))

    env_reads = {rel: module_env_reads(ctx.tree)
                 for rel, ctx in contexts.items()}
    env_reads = {rel: reads for rel, reads in env_reads.items() if reads}

    def _sink(fs: list[Finding]) -> None:
        for f in fs:
            by_path.setdefault(f.path, []).append(f)

    if want("ML003"):
        deploy = root / "DEPLOY.md"
        deploy_text = None
        try:
            deploy_text = deploy.read_text(encoding="utf-8")
        except OSError:
            pass
        _sink(check_knob_docs(env_reads, deploy_text))
    if want("ML004"):
        _sink(check_knob_single_parse(env_reads))
    if want("ML005") and CONFIG_REL in contexts and DISAGG_REL in contexts:
        _sink(check_ship_contract(contexts[CONFIG_REL].tree,
                                  contexts[DISAGG_REL].tree))

    ledgers: list[dict] = []
    if want("ML001"):
        cap_findings, ledgers = check_capacity()
        _sink(cap_findings)

    if want("ML006") and witness is not None:
        _sink(witness_findings(witness))

    out: list[Finding] = []
    for rel in sorted(by_path):
        ctx = contexts.get(rel)
        fs = by_path[rel]
        if ctx is not None:
            fs = ctx.apply_suppressions(fs, rules=MEM_RULES)
        out.extend(fs)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule)), ledgers


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m polykey_tpu.analysis mem",
        description="memlint: memory & capacity contract analysis "
                    "(byte ledger, unbounded growth, knob contracts)",
    )
    parser.add_argument(
        "targets", nargs="*", default=None,
        help=f"files/directories to scan (default: "
             f"{' '.join(DEFAULT_TARGETS)})")
    parser.add_argument("--root", default=".",
                        help="repo root (default: cwd)")
    parser.add_argument("--baseline", default=MEM_BASELINE, metavar="FILE",
                        help="grandfathering baseline file")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline file entirely")
    parser.add_argument("--write-baseline", action="store_true",
                        help="grandfather current blocking findings")
    parser.add_argument("--prune", action="store_true",
                        help="drop stale baseline entries, then exit")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit findings + ledger + summary as JSON")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule table and exit")
    parser.add_argument("--only", metavar="IDS",
                        help="comma-separated rule ids to run "
                             "(e.g. ML002,ML005)")
    parser.add_argument("--witness", metavar="PATH",
                        help="heap-witness JSON file or directory to "
                             "merge (ML006)")
    return parser


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.list_rules:
        print("ML000  meta                       suppression hygiene, "
              "unparseable inputs, stale matrix")
        for rule in MEM_RULES:
            print(f"{rule.id}  {rule.name:<26} {rule.description}")
        return 0

    root = Path(args.root).resolve()
    if not root.is_dir():
        print(f"memlint: --root {args.root} is not a directory",
              file=sys.stderr)
        return 2

    targets = args.targets or None
    try:
        only = parse_only(args.only, {r.id for r in MEM_RULES})
        # A partial run can't tell "fixed" from "not scanned" (shared
        # refusal semantics, core.py).
        require_full_run(partial=bool(targets) or only is not None,
                         prune=args.prune,
                         write_baseline=args.write_baseline)
        from . import heapwitness

        witness = load_witness_arg(args.witness, heapwitness.load_witness)
    except UsageError as e:
        print(f"memlint: {e}", file=sys.stderr)
        return 2
    partial = bool(targets) or only is not None

    try:
        findings, ledgers = run_memlint(root, targets, only, witness)
    except FileNotFoundError as e:
        print(f"memlint: {e}", file=sys.stderr)
        return 2

    if partial:
        # Unused-suppression and stale-baseline signals need the full
        # sweep; a partial run must neither report nor act on them.
        findings = [f for f in findings
                    if not (f.rule == "ML000"
                            and "unused suppression" in f.message)]

    meta = [f for f in findings if f.rule == "ML000" and f.blocking]
    baseline_path = root / args.baseline
    if args.prune:
        if meta:
            print("memlint: refusing --prune while ML000 findings exist "
                  "(a broken check is a partial run in disguise):",
                  file=sys.stderr)
            for f in meta:
                print(f"  {f.render()}", file=sys.stderr)
            return 2
        kept, dropped = prune_baseline(baseline_path, findings)
        print(f"memlint: pruned {dropped} stale baseline entr"
              f"{'y' if dropped == 1 else 'ies'} from {baseline_path} "
              f"({kept} kept)")
        return 0
    if args.write_baseline:
        if meta:
            print("memlint: refusing --write-baseline while ML000 "
                  "findings exist — fix the infrastructure first:",
                  file=sys.stderr)
            for f in meta:
                print(f"  {f.render()}", file=sys.stderr)
            return 2
        count = write_baseline(baseline_path, findings)
        print(f"memlint: wrote {count} baseline entr"
              f"{'y' if count == 1 else 'ies'} to {baseline_path}")
        return 0

    stale: list[str] = []
    if not args.no_baseline:
        findings, stale = apply_baseline(
            findings, load_baseline(baseline_path))

    blocking = [f for f in findings if f.blocking]
    suppressed = sum(1 for f in findings if f.suppressed)
    baselined = sum(1 for f in findings if f.baselined)

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_json() for f in findings],
            "ledger": [
                {k: (round(v, 4) if isinstance(v, float) else v)
                 for k, v in entry.items()
                 if k != "transient_bytes"}
                | {"transient_bytes": {
                    k: round(v, 1)
                    for k, v in entry["transient_bytes"].items()}}
                for entry in ledgers
            ],
            "summary": {
                "blocking": len(blocking),
                "suppressed": suppressed,
                "baselined": baselined,
                "stale_baseline_entries": stale,
                "witness_processes": len(witness) if witness else 0,
                "mem_clean": not blocking,
            },
        }, indent=2))
    else:
        for f in findings:
            if f.blocking:
                print(f.render())
        parts = [f"{len(blocking)} blocking"]
        if suppressed:
            parts.append(f"{suppressed} suppressed")
        if baselined:
            parts.append(f"{baselined} baselined")
        if ledgers:
            fits = sum(1 for e in ledgers if e["fits"])
            parts.append(f"{fits}/{len(ledgers)} capacity entries fit")
        if witness:
            parts.append(f"{len(witness)} witness process"
                         f"{'' if len(witness) == 1 else 'es'} merged")
        print(f"memlint: {', '.join(parts)}")
        if stale and not partial:
            print(f"memlint: {len(stale)} stale baseline entr"
                  f"{'y' if len(stale) == 1 else 'ies'} (fixed findings) "
                  "— re-run with --prune")
    return 1 if blocking else 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
