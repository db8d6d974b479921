"""graphlint — compiled-graph contract analysis (the second analysis tier).

polylint (rules.py) checks what the *source* promises; this module checks
what the *compiled graph* actually does. It traces the real engine/model
step functions under abstract inputs (``jax.eval_shape`` /
``jax.make_jaxpr`` / ``.lower()``) and drives a real CPU-backed engine,
verifying the invariants that gate paged-KV continuous batching at
ICI-limited speed — the production killers that are silent on TPU until
the latency graph melts:

| Check | Contract                                                         |
|-------|------------------------------------------------------------------|
| GL001 | recompile stability: each jitted step compiles once at warm-up   |
| GL002 | donation audit: every donate_argnames site aliases its buffers   |
| GL003 | dtype policy: no f64 anywhere; no weight upcasts in bf16 paths   |
| GL004 | host-transfer guard: no callbacks/unannotated transfers in steps |
| GL005 | shape/layout: kernel block contracts + sharding divisibility     |

Like polylint, graphlint trades recall for precision: every check
documents its approximation, deliberate violations are suppressed with
an explicit reason (class-level ``SUPPRESSIONS``), and pre-existing debt
grandfathers through a content-hashed baseline
(``graphlint-baseline.json``, reusing the PR 2 machinery). Analyzer
infrastructure failures surface as blocking GL000 findings — a broken
probe must never read as a clean graph.

Run::

    make graphlint                                  # repo gate (CI parity)
    python -m polykey_tpu.analysis graph            # same, direct
    python -m polykey_tpu.analysis graph --json     # machine-readable
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import re
import sys
import time
import warnings
from functools import partial
from pathlib import Path
from typing import Callable, Iterator, Optional

from .baseline import (
    apply_baseline,
    load_baseline,
    prune_baseline,
    write_baseline,
)
from .core import Finding, UsageError, parse_only, require_full_run

GRAPH_BASELINE = "graphlint-baseline.json"

# Raised for each collected stream before the engine is declared wedged.
_COLLECT_TIMEOUT_S = 180.0


def _ensure_cpu_backend() -> None:
    """Pin jax to a simulated multi-device CPU platform.

    GL001's recompile sweep and GL004's guard smoke need a real engine but
    no hardware; GL005's sharding walk wants >= 8 devices. The tier is a
    CPU check whatever the host holds, so the environment is set here —
    before jax initializes its backend — rather than left to the caller.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


# -- check registry -----------------------------------------------------------


class GraphCheck:
    """One compiled-graph contract. Subclasses set id/name/description and
    implement run(env) -> list[Finding].

    SUPPRESSIONS maps a finding's snippet key to the reason it is a
    deliberate, reviewed exception — the graph-tier analogue of polylint's
    ``# polylint: disable=`` comments (jaxpr findings have no source line
    to hang a comment on)."""

    id: str = "GL000"
    name: str = "unnamed"
    description: str = ""
    SUPPRESSIONS: dict[str, str] = {}

    def run(self, env: "GraphEnv") -> list[Finding]:
        raise NotImplementedError


_GRAPH_REGISTRY: dict[str, GraphCheck] = {}


def register_graph(cls: type[GraphCheck]) -> type[GraphCheck]:
    inst = cls()
    if inst.id in _GRAPH_REGISTRY:
        raise ValueError(f"duplicate graph check id {inst.id}")
    _GRAPH_REGISTRY[inst.id] = inst
    return cls


def all_graph_checks() -> list[GraphCheck]:
    return [_GRAPH_REGISTRY[k] for k in sorted(_GRAPH_REGISTRY)]


def graph_finding(rule: str, path: str, key: str, message: str) -> Finding:
    """A graph-tier finding. `key` is the stable identity string — it
    feeds both the baseline fingerprint (via Finding.snippet) and the
    per-check SUPPRESSIONS lookup, so it must not embed counters,
    addresses, or timings."""
    return Finding(rule=rule, path=path, line=0, message=message, snippet=key)


# -- engine driving (shared by GL001 / GL004) ---------------------------------


def _collect_stream(request, timeout: float = _COLLECT_TIMEOUT_S):
    """Drain one GenRequest's out queue; returns (tokens, error)."""
    tokens: list[int] = []
    deadline = time.monotonic() + timeout
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return tokens, "timed out waiting for engine output"
        try:
            kind, value = request.out.get(timeout=remaining)
        except queue.Empty:
            return tokens, "timed out waiting for engine output"
        if kind == "token":
            tokens.append(value)
        elif kind == "done":
            return tokens, None
        else:
            return tokens, str(value)


def drive_engine(engine, waves: list[list]) -> list[str]:
    """Submit requests wave-by-wave (later waves land while earlier ones
    are still decoding — the occupancy variation GL001 needs) and drain
    every stream. Returns the error strings (empty = clean run)."""
    errors: list[str] = []
    all_requests = []
    for wave in waves:
        for request in wave:
            engine.submit(request)
        all_requests.extend(wave)
        # A short beat between waves so admission interleaves with live
        # decode lanes rather than batching everything into one burst.
        time.sleep(0.05)
    for request in all_requests:
        _, error = _collect_stream(request)
        if error is not None:
            errors.append(error)
    return errors


def measure_recompiles(
    handles: dict[str, object], drive: Callable[[], list[str]]
) -> tuple[dict[str, tuple[int, int]], list[str], list[str]]:
    """Core of GL001: snapshot each jit handle's executable-cache size,
    run `drive`, snapshot again. Returns (sizes {name: (before, after)},
    drive errors, compile log lines captured during the drive)."""
    import logging

    before = {name: h._cache_size() for name, h in handles.items()}

    compile_lines: list[str] = []

    class _Capture(logging.Handler):
        def emit(self, record: logging.LogRecord) -> None:
            msg = record.getMessage()
            if "ompil" in msg:  # "Compiling"/"Finished XLA compilation of"
                compile_lines.append(msg.splitlines()[0][:200])

    import jax

    handler = _Capture(level=logging.DEBUG)
    jax_logger = logging.getLogger("jax")
    jax_logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            errors = drive()
    finally:
        jax_logger.removeHandler(handler)

    sizes = {
        name: (before[name], h._cache_size()) for name, h in handles.items()
    }
    return sizes, errors, compile_lines


def recompile_findings(
    label: str, handles: dict[str, object], drive: Callable[[], list[str]]
) -> tuple[list[Finding], dict[str, tuple[int, int]]]:
    """Core of GL001 for one engine: any handle whose executable cache
    grows during `drive` recompiled at serving time; any handle whose
    cache is empty beforehand was missed by warmup."""
    findings: list[Finding] = []
    for name, handle in handles.items():
        if not hasattr(handle, "_cache_size"):
            findings.append(graph_finding(
                "GL000", f"graph:{label}", f"{label}:{name}:no-probe",
                f"jit handle {name} has no _cache_size probe on this "
                "jax — GL001 cannot verify recompile stability",
            ))
            return findings, {}
    sizes, errors, compile_lines = measure_recompiles(handles, drive)
    for error in errors:
        findings.append(graph_finding(
            "GL000", f"graph:{label}", f"{label}:drive-error",
            f"GL001 sweep on {label} hit a request error: {error}",
        ))
    for name, (before, after) in sizes.items():
        if before == 0:
            findings.append(graph_finding(
                "GL001", f"graph:{label}", f"{label}:{name}:cold",
                f"{name} had an empty executable cache after warmup — "
                "compile warmup no longer covers this step, so the first "
                "real request pays its compile",
            ))
        if after > before:
            detail = "; ".join(compile_lines[:3])
            findings.append(graph_finding(
                "GL001", f"graph:{label}", f"{label}:{name}:grew",
                f"{name} compiled {after - before} new executable(s) "
                f"during the serving sweep ({before} -> {after}) — a "
                "shape/static-arg variant reached serving that warmup "
                f"never compiled{': ' + detail if detail else ''}",
            ))
    return findings, sizes


# -- jaxpr walking (shared by GL003 / GL004) ----------------------------------


def iter_jaxprs(jaxpr) -> Iterator:
    """Yield a jaxpr and every nested jaxpr (pjit bodies, scan/while
    bodies, cond branches, custom_* calls), depth-first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            yield from _nested_jaxprs(value)


def _nested_jaxprs(value) -> Iterator:
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):  # ClosedJaxpr
        yield from iter_jaxprs(value.jaxpr)
    elif hasattr(value, "eqns") and hasattr(value, "invars"):  # Jaxpr
        yield from iter_jaxprs(value)
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _nested_jaxprs(item)


def _eqn_avals(jaxpr) -> Iterator:
    for var in list(jaxpr.invars) + list(jaxpr.constvars) + list(jaxpr.outvars):
        aval = getattr(var, "aval", None)
        if aval is not None:
            yield aval
    for eqn in jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None:
                yield aval


def dtype_findings(
    label: str, closed_jaxpr, weight_shapes: set[tuple[int, ...]],
    bf16_path: bool,
) -> list[Finding]:
    """Core of GL003. Walks a traced step's jaxpr (nested bodies
    included) for:

    - any float64 value anywhere (inputs, intermediates, outputs) — with
      a bf16/f32 serving stack an f64 is always an accident (a Python
      float promotion under x64) and doubles bandwidth where it lands;
    - in bf16 paths, ``convert_element_type`` to f32 applied to a
      weight-shaped bf16 operand — the classic silent upcast that doubles
      weight HBM traffic. Activation-precision f32 (norms, softmax,
      logits) is deliberate mixed precision and does NOT fire: only
      operands whose shape matches a params leaf (ndim >= 2) are flagged.
    """
    import numpy as np

    findings: list[Finding] = []
    seen_f64: set[str] = set()
    seen_upcast: set[str] = set()
    for sub in iter_jaxprs(closed_jaxpr.jaxpr):
        for aval in _eqn_avals(sub):
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and dtype in (np.float64, np.complex128):
                key = f"{label}:f64:{getattr(aval, 'shape', ())}"
                if key not in seen_f64:
                    seen_f64.add(key)
                    findings.append(graph_finding(
                        "GL003", f"graph:{label}", key,
                        f"float64 value {aval} in the compiled graph of "
                        f"{label} — the serving stack is bf16/f32; an f64 "
                        "is an accidental Python-float promotion",
                    ))
        if not bf16_path:
            continue
        for eqn in sub.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            new_dtype = eqn.params.get("new_dtype")
            if new_dtype is None or np.dtype(new_dtype) != np.float32:
                continue
            operand = eqn.invars[0]
            aval = getattr(operand, "aval", None)
            if aval is None:
                continue
            import jax.numpy as jnp

            if getattr(aval, "dtype", None) != jnp.bfloat16:
                continue
            shape = tuple(getattr(aval, "shape", ()))
            if shape in weight_shapes:
                key = f"{label}:upcast:{shape}"
                if key not in seen_upcast:
                    seen_upcast.add(key)
                    findings.append(graph_finding(
                        "GL003", f"graph:{label}", key,
                        f"bf16 weight tensor {shape} upcast to f32 inside "
                        f"{label} — doubles its HBM read on every step; "
                        "keep weights bf16 into the matmul "
                        "(preferred_element_type handles accumulation)",
                    ))
    return findings


# Host round-trip primitives by their names on the installed JAX (0.9.0):
# jax.debug.print -> debug_print, jax.debug.callback -> debug_callback,
# io_callback -> io_callback, pure_callback -> pure_callback.
_CALLBACK_PRIMITIVES = ("infeed", "outfeed", "debug_print")


def callback_findings(label: str, closed_jaxpr) -> list[Finding]:
    """Core of GL004's static half: any callback/infeed/outfeed primitive
    inside a jitted step is a host round-trip per dispatch — fatal for a
    loop whose whole design is 'one hidden sync per block'."""
    findings: list[Finding] = []
    seen: set[str] = set()
    for sub in iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in sub.eqns:
            name = eqn.primitive.name
            if "callback" in name or name in _CALLBACK_PRIMITIVES:
                key = f"{label}:{name}"
                if key not in seen:
                    seen.add(key)
                    findings.append(graph_finding(
                        "GL004", f"graph:{label}", key,
                        f"host callback primitive '{name}' inside the "
                        f"compiled graph of {label} — every dispatch pays "
                        "a device->host round-trip (debug prints and "
                        "io_callback must not ship in step functions)",
                    ))
    return findings


# -- donation auditing (GL002) ------------------------------------------------

_ALIAS_RE = re.compile(r"(?:may|must)-alias")


def audit_donation_site(
    label: str, lower: Callable[[], object], donated_big_leaves: int
) -> list[Finding]:
    """Core of GL002: lower + compile one donate_argnames site, fail on
    dropped-donation warnings and on an input_output_alias map smaller
    than the donated buffer count.

    `donated_big_leaves` counts donated array leaves >= 1 KiB — XLA may
    legitimately decline to alias a scalar, but a non-aliased page pool
    or parameter tree is exactly the regression this check exists for
    (donation silently dropped = double HBM residency + a copy per step).
    """
    findings: list[Finding] = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            compiled = lower().compile()
        except Exception as e:  # infra failure must be visible, not a pass
            findings.append(graph_finding(
                "GL000", f"graph:{label}", f"{label}:lower-failed",
                f"GL002 could not lower/compile {label}: "
                f"{type(e).__name__}: {e}",
            ))
            return findings
    for w in caught:
        message = str(w.message)
        if "donated" in message.lower():
            findings.append(graph_finding(
                "GL002", f"graph:{label}", f"{label}:dropped-donation",
                f"XLA dropped a donation while compiling {label}: "
                f"{message.splitlines()[0]}",
            ))
    aliased = len(_ALIAS_RE.findall(compiled.as_text()))
    if aliased < donated_big_leaves:
        findings.append(graph_finding(
            "GL002", f"graph:{label}", f"{label}:alias-deficit",
            f"{label} donates {donated_big_leaves} buffer(s) >= 1 KiB but "
            f"the compiled executable aliases only {aliased} — a donated "
            "buffer that does not alias its output still exists twice in "
            "HBM and costs a copy every step",
        ))
    return findings


def count_big_leaves(tree, min_bytes: int = 1024) -> int:
    import jax

    return sum(
        1 for leaf in jax.tree_util.tree_leaves(tree)
        if getattr(leaf, "nbytes", 0) >= min_bytes
    )


# -- shape/layout contracts (GL005) -------------------------------------------


def _axis_extent(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    extent = 1
    for axis in axes:
        extent *= mesh.shape[axis]
    return extent


def sharding_divisibility(
    label: str, shape: tuple[int, ...], sharding
) -> list[Finding]:
    """Core of GL005's sharding half: every dim a PartitionSpec annotates
    must be divisible by its mesh-axis extent — GSPMD silently pads the
    remainder (wasted HBM + ragged collectives), and for the KV pool a
    padded page axis corrupts the page-index arithmetic."""
    findings: list[Finding] = []
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return findings
    for dim, (size, axes) in enumerate(zip(shape, tuple(spec))):
        extent = _axis_extent(mesh, axes)
        if extent > 1 and size % extent != 0:
            findings.append(graph_finding(
                "GL005", f"graph:{label}",
                f"{label}:dim{dim}:{size}%{extent}",
                f"{label}: dim {dim} (size {size}) is sharded over mesh "
                f"axes {axes!r} (extent {extent}) but {size} % {extent} "
                "!= 0 — GSPMD pads the remainder",
            ))
    return findings


def gate_consistency_findings(configs) -> list[Finding]:
    """Core of GL005's gate half: kernel-eligibility gates must agree
    with the alignment rules their kernels assume — a config that passes
    the gate but breaks alignment would compile-fail (or silently
    mis-tile) on first hardware contact."""
    from ..ops.flash_attention import _FLASH_HEAD_DIMS

    findings: list[Finding] = []
    for cfg in configs:
        folded = cfg.num_kv_heads * cfg.head_dim
        eligible = folded % 128 == 0
        if eligible and cfg.head_dim % 8 != 0:
            findings.append(graph_finding(
                "GL005", "graph:ops.gates",
                f"paged-gate:{cfg.name}",
                f"{cfg.name}: paged kernel eligible (folded lanes "
                f"{folded}) but head_dim {cfg.head_dim} is not "
                "sublane-aligned — the DMA slice would mis-tile",
            ))
        if cfg.head_dim in _FLASH_HEAD_DIMS and cfg.head_dim % 64 != 0:
            findings.append(graph_finding(
                "GL005", "graph:ops.gates",
                f"flash-gate:{cfg.name}",
                f"{cfg.name}: head_dim {cfg.head_dim} is in "
                "_FLASH_HEAD_DIMS but not 64-aligned — the proven set "
                "must only contain Mosaic-tileable dims",
            ))
    return findings


def abstract_contract(
    label: str, fn: Callable, args: tuple,
    expected: list[tuple[tuple[int, ...], str]],
) -> list[Finding]:
    """Core of GL005's kernel half: abstract-eval `fn(*args)` (traces the
    pallas_call block machinery without lowering — runs on CPU) and
    compare the flattened outputs against (shape, dtype) expectations. A
    trace-time exception means the block/grid arithmetic itself is
    inconsistent for this geometry."""
    import jax

    try:
        out = jax.eval_shape(fn, *args)
    except Exception as e:
        return [graph_finding(
            "GL005", f"graph:{label}", f"{label}:abstract-eval",
            f"abstract eval of {label} failed — block/grid contract is "
            f"inconsistent for this geometry: {type(e).__name__}: "
            f"{str(e).splitlines()[0][:160]}",
        )]
    leaves = jax.tree_util.tree_leaves(out)
    got = [(tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves]
    want = [(tuple(shape), dtype) for shape, dtype in expected]
    if got != want:
        return [graph_finding(
            "GL005", f"graph:{label}", f"{label}:out-contract",
            f"{label}: abstract outputs {got} != contract {want}",
        )]
    return []


# -- shared fixture environment -----------------------------------------------


class GraphEnv:
    """Lazily-built fixtures shared across checks: a warmed plain CPU
    engine, a warmed speculative engine, an unwarmed bf16 engine for
    tracing, and a tiny train step. Engines are built once — GL001 drives
    them, GL002 lowers their handles, GL004 smokes them under the
    transfer guard.

    profile="full" is the repo gate; profile="smoke" shrinks warmup for
    the test suite (1 bucket, 2 slots, greedy-only)."""

    def __init__(self, profile: str = "full"):
        self.profile = profile
        self.logs: list[str] = []
        self._plain = None
        self._spec = None
        self._hostkv = None
        self._bf16 = None
        self._train = None
        self._jaxprs = None

    # -- configs -------------------------------------------------------------

    def _base_config(self):
        from ..engine.config import EngineConfig

        if self.profile == "smoke":
            return EngineConfig(
                model="tiny-llama", tokenizer="byte", dtype="float32",
                max_decode_slots=2, page_size=8, num_pages=64,
                max_seq_len=64, prefill_buckets=(16,),
                max_new_tokens_cap=16, default_max_new_tokens=6,
                compile_warmup=True, warm_sampled_variants=False,
            )
        return EngineConfig(
            model="tiny-llama", tokenizer="byte", dtype="float32",
            max_decode_slots=4, page_size=8, num_pages=64,
            max_seq_len=64, prefill_buckets=(16, 32),
            max_new_tokens_cap=32, default_max_new_tokens=8,
            compile_warmup=True, warm_sampled_variants=True,
        )

    # -- engines -------------------------------------------------------------

    def plain_engine(self):
        if self._plain is None:
            from ..engine.engine import InferenceEngine

            self.logs.append("building plain CPU engine (compile warmup)")
            self._plain = InferenceEngine(self._base_config())
        return self._plain

    def spec_engine(self):
        if self._spec is None:
            import dataclasses

            from ..engine.engine import InferenceEngine

            self.logs.append("building speculative CPU engine (warmup)")
            config = dataclasses.replace(
                self._base_config(), draft_model="tiny-llama", spec_gamma=2,
            )
            self._spec = InferenceEngine(config)
        return self._spec

    def bf16_engine(self):
        """Unwarmed bf16 engine: GL003/GL004 only trace its step
        functions (make_jaxpr), never execute them — construction cost is
        params init + device_put."""
        if self._bf16 is None:
            import dataclasses

            from ..engine.engine import InferenceEngine

            config = dataclasses.replace(
                self._base_config(), dtype="bfloat16", compile_warmup=False,
            )
            self._bf16 = InferenceEngine(config)
        return self._bf16

    def hostkv_engine(self):
        """Warmed CPU engine with the host KV tier active (ISSUE 15):
        a deliberately TIGHT device pool + an aggressive resident
        floor, so the standard request sweep spills cold prefix pages
        to host at retire and — because GL001/GL004 drive the same mix
        twice (depths 1 and 2) — faults them back on the revisit. Both
        new crossing paths (the eviction gather's packed D2H read, the
        restore's page-payload upload) then run under the transfer
        guard, and the gather/scatter pair's recompile stability is
        probed like any other handle."""
        if self._hostkv is None:
            import dataclasses

            from ..engine.engine import InferenceEngine

            self.logs.append("building host-KV CPU engine (warmup)")
            config = dataclasses.replace(
                self._base_config(), prefix_cache=True,
                num_pages=28, host_kv_bytes=64 << 20,
                host_kv_resident_pages=24,
            )
            self._hostkv = InferenceEngine(config)
        return self._hostkv

    def engines(self):
        yield "engine.plain", self.plain_engine()
        if self.profile != "smoke":
            yield "engine.spec", self.spec_engine()
            yield "engine.hostkv", self.hostkv_engine()

    def jit_handles(self, engine) -> dict[str, object]:
        handles = {
            "_jit_prefill": engine._jit_prefill,
            "_jit_decode": engine._jit_decode,
            "_jit_merge": engine._jit_merge,
            "_jit_retire": engine._jit_retire,
        }
        if engine._spec:
            handles["_jit_spec_prefill"] = engine._jit_spec_prefill
            handles["_jit_spec_decode"] = engine._jit_spec_decode
        if engine._host_kv is not None:
            # The host tier's fixed-width gather/scatter pair (ISSUE
            # 15): warmed at construction, and a spill or page fault
            # mid-sweep must never mint another executable.
            handles["_jit_kv_gather"] = engine._jit_kv_gather
            handles["_jit_kv_restore"] = engine._jit_kv_restore
        return handles

    def request_mix(self, sampled: bool) -> list[list]:
        """The representative sweep: a slot-filling greedy burst (padded
        group widths 1/2/4), a mid-flight sampled wave (greedy=False
        variants + top-k/top-p paths), then a chunked long prompt plus a
        short chaser (occupancy 1..slots, chunk interleaving)."""
        from ..engine.engine import GenRequest

        def req(prompt_len: int, temperature: float = 0.0,
                top_p: float = 1.0, top_k: int = 0, max_new: int = 6,
                seed: int = 7) -> GenRequest:
            prompt = ("abcdefgh" * 12)[:prompt_len]
            return GenRequest(
                prompt=prompt, max_new_tokens=max_new,
                temperature=temperature, top_p=top_p, top_k=top_k,
                seed=seed,
            )

        if self.profile == "smoke":
            return [
                [req(3), req(12)],
                [req(40)],            # > largest bucket: chunked prefill
                [req(7)],
            ]
        waves = [
            [req(3), req(10), req(20), req(28)],
        ]
        if sampled:
            waves.append([
                req(5, temperature=0.7, top_p=0.9, top_k=5),
                req(18, temperature=1.0),
            ])
        waves.append([req(40), req(6)])  # chunked long prompt + chaser
        return waves

    # -- train fixture (GL002's train.py:110 site) ---------------------------

    def train_fixture(self):
        """(train_step, state, batch) for the donated train step, tiny
        config on a single-device mesh."""
        if self._train is None:
            import jax
            import jax.numpy as jnp
            import numpy as np

            from ..models.config import get_config
            from ..models.transformer import init_params
            from ..parallel.mesh import MeshConfig, create_mesh
            from ..train.train import make_train_step

            cfg = get_config("tiny-llama")
            mesh = create_mesh(MeshConfig(), jax.devices()[:1])
            init_state, train_step, shard_batch = make_train_step(cfg, mesh)
            params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
            state = init_state(params)
            B, T = 2, 8
            tokens = np.zeros((B, T), np.int32)
            targets = np.zeros((B, T), np.int32)
            positions = np.broadcast_to(np.arange(T), (B, T)).astype(np.int32)
            batch = shard_batch(tokens, targets, positions)
            self._train = (train_step, state, batch)
        return self._train

    # -- donation sites (GL002) ----------------------------------------------

    def donation_sites(self):
        """Yield (label, lower_thunk, donated_big_leaf_count) for every
        donate_argnames site: engine.py plain prefill/decode, spec
        prefill/decode, train.py train_step (state). The decode sites
        donate the double-buffered slot state (last_tokens / seq_lens /
        active) alongside the pools (ISSUE 6) — those leaves join the
        big-leaf count (tiny at smoke scale, real at 48 slots) and any
        dropped-donation warning on them fails the audit either way."""
        import jax
        import numpy as np

        for engine_label, engine in self.engines():
            cfg = engine.config
            dev = engine._dev
            put = partial(jax.device_put, device=engine._repl)
            bucket = cfg.prefill_buckets[0]
            window = (
                jax.device_put(
                    np.zeros((1, bucket), np.int32), engine._prefill_tok),
                put(np.zeros((1,), np.int32)),
                put(np.zeros((1,), np.int32)),
                put(np.zeros((1, cfg.pages_per_seq), np.int32)),
                put(np.zeros((1, 2), np.int32)),
                put(np.zeros((1,), np.float32)),
                put(np.ones((1,), np.float32)),
                put(np.zeros((1,), np.int32)),
            )
            # Donated double-buffered slot state rides the decode sites
            # alongside the pools (ISSUE 6): count its leaves too, so an
            # alias dropped on a 48-slot deployment's vectors is a
            # deficit, not a rounding error.
            slot_state = (dev["last_tokens"], dev["seq_lens"], dev["active"])
            if engine._spec:
                pools = (engine.paged, engine.d_paged)
                # The per-lane gamma dial donates alongside the slot
                # state (ISSUE 19): it advances on device every round.
                dial = (dev["accept_ewma"], dev["gamma_lane"])
                yield (
                    f"{engine_label}._jit_spec_prefill",
                    partial(
                        engine._jit_spec_prefill.lower,
                        engine.params, engine.draft_params,
                        engine.model_cfg, engine.draft_cfg,
                        engine.paged, engine.d_paged, *window,
                        greedy=True, candidates=cfg.top_p_candidates,
                        mesh=engine.mesh,
                    ),
                    count_big_leaves(pools),
                )
                yield (
                    f"{engine_label}._jit_spec_decode",
                    partial(
                        engine._jit_spec_decode.lower,
                        engine.params, engine.draft_params,
                        engine.model_cfg, engine.draft_cfg,
                        engine.paged, engine.d_paged,
                        dev["last_tokens"], dev["seq_lens"],
                        dev["page_tables"], dev["active"], dev["caps"],
                        dev["seeds"], dev["temperature"], dev["top_p"],
                        dev["top_k"], *dial,
                        gamma=engine._gamma_max,
                        eos_id=engine.tokenizer.eos_id,
                        gamma_low=engine._gamma_low,
                        gamma_max=engine._gamma_max,
                        candidates=0, mesh=engine.mesh,
                    ),
                    count_big_leaves((pools, slot_state, dial)),
                )
            else:
                yield (
                    f"{engine_label}._jit_prefill",
                    partial(
                        engine._jit_prefill.lower,
                        engine.params, engine.model_cfg, engine.paged,
                        *window,
                        greedy=True, candidates=cfg.top_p_candidates,
                        mesh=engine.mesh,
                    ),
                    count_big_leaves(engine.paged),
                )
                yield (
                    f"{engine_label}._jit_decode",
                    partial(
                        engine._jit_decode.lower,
                        engine.params, engine.model_cfg, engine.paged,
                        dev["last_tokens"], dev["seq_lens"],
                        dev["page_tables"], dev["active"], dev["caps"],
                        dev["seeds"], dev["temperature"], dev["top_p"],
                        dev["top_k"],
                        greedy=True, steps=engine._block_steps,
                        eos_id=engine.tokenizer.eos_id,
                        candidates=cfg.top_p_candidates, mesh=engine.mesh,
                    ),
                    count_big_leaves((engine.paged, slot_state)),
                )
            # KV restore scatter — shared by the ISSUE 13 handoff
            # resume and the ISSUE 15 host-tier page fault: donates the
            # pool like every pool-touching dispatch, so its alias map
            # is audited like one. (The gather half of the pair donates
            # nothing — it is a read, the pool stays.)
            P = cfg.pages_per_seq
            pages = jax.tree.map(
                lambda pool: np.zeros(
                    (pool.shape[0], P, *pool.shape[2:]), pool.dtype),
                engine.paged,
            )
            yield (
                f"{engine_label}._jit_kv_restore",
                partial(
                    engine._jit_kv_restore.lower,
                    engine.paged, np.zeros((P,), np.int32), pages,
                ),
                count_big_leaves(engine.paged),
            )
        train_step, state, batch = self.train_fixture()
        yield (
            "train.train_step",
            partial(train_step.lower, state, *batch),
            count_big_leaves(state),
        )

    # -- traced step jaxprs (GL003 / GL004) ----------------------------------

    def step_jaxprs(self):
        """(label, closed_jaxpr, weight_shapes, bf16_path) tuples for the
        serving step functions, traced abstractly (never executed).
        Cached: GL003 and GL004 both walk these, and each trace runs the
        full model (including the decode scan) through make_jaxpr."""
        if self._jaxprs is None:
            self._jaxprs = list(self._trace_step_jaxprs())
        return self._jaxprs

    def _trace_step_jaxprs(self):
        import jax
        import numpy as np

        from ..engine import engine as engine_mod

        for bf16, eng in ((True, self.bf16_engine()),
                          (False, self.plain_engine())):
            cfg = eng.config
            weight_shapes = {
                tuple(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(eng.params)
                if getattr(leaf, "ndim", 0) >= 2 and leaf.size >= 1024
            }
            if eng._dev_dirty or not eng._dev:
                eng._upload_slot_state()
            dev = eng._dev
            bucket = cfg.prefill_buckets[0]
            window = (
                np.zeros((1, bucket), np.int32),
                np.zeros((1,), np.int32), np.zeros((1,), np.int32),
                np.zeros((1, cfg.pages_per_seq), np.int32),
                np.zeros((1, 2), np.int32),
                np.zeros((1,), np.float32), np.ones((1,), np.float32),
                np.zeros((1,), np.int32),
            )
            label = "bf16" if bf16 else "f32"
            model_cfg, mesh = eng.model_cfg, eng.mesh
            prefill = jax.make_jaxpr(
                lambda params, paged, *rest: engine_mod._prefill_fn(
                    params, model_cfg, paged, *rest,
                    greedy=False, candidates=cfg.top_p_candidates, mesh=mesh,
                )
            )(eng.params, eng.paged, *window)
            yield (f"engine.{label}._prefill_fn", prefill,
                   weight_shapes, bf16)
            decode = jax.make_jaxpr(
                lambda params, paged, *rest: engine_mod._decode_fn(
                    params, model_cfg, paged, *rest,
                    greedy=False, steps=2, eos_id=eng.tokenizer.eos_id,
                    candidates=cfg.top_p_candidates, mesh=mesh,
                )
            )(eng.params, eng.paged, dev["last_tokens"], dev["seq_lens"],
              dev["page_tables"], dev["active"], dev["caps"], dev["seeds"],
              dev["temperature"], dev["top_p"], dev["top_k"])
            yield (f"engine.{label}._decode_fn", decode, weight_shapes, bf16)

    def close(self) -> None:
        for engine in (self._plain, self._spec, self._hostkv, self._bf16):
            if engine is not None:
                engine.shutdown()
        self._plain = self._spec = self._hostkv = self._bf16 = None
        self._jaxprs = None


# -- GL001: recompile stability ----------------------------------------------


@register_graph
class RecompileStability(GraphCheck):
    """After compile warmup, a mixed-occupancy request sweep (bucketed and
    chunked prefill, greedy and sampled decode, admissions mid-decode,
    retires, spec rounds with the gamma dial) must not grow ANY jitted
    step's executable cache: one recompile per decode step is the
    canonical silent TPU production killer. Cache sizes are probed via
    the jit handles' _cache_size(), cross-checked with jax.log_compiles
    capture so a firing check names the compiled computation."""

    id = "GL001"
    name = "recompile-stability"
    description = ("each jitted engine step compiles exactly once "
                   "(at warm-up) across a mixed request sweep, at "
                   "lookahead depths 1 and 2")

    def run(self, env: GraphEnv) -> list[Finding]:
        findings: list[Finding] = []
        for label, engine in env.engines():
            handles = env.jit_handles(engine)
            mix = env.request_mix(sampled=engine.config.warm_sampled_variants)
            # The sweep runs at both pipeline depths: depth 1 is the
            # synchronous dispatch-then-read shape, depth 2 the
            # double-buffered overlap (ISSUE 6). Double buffering is a
            # host-side scheduling change over DONATED device buffers —
            # it must not mint a single new executable (the donation
            # chain keeps shapes/dtypes identical across generations).
            # `_depth` is the knob POLYKEY_DISPATCH_LOOKAHEAD sets; the
            # sweep restores the engine's configured depth afterwards.
            def sweep(e=engine, m=mix):
                configured = e._depth
                try:
                    errors: list[str] = []
                    for depth in (1, 2):
                        e._depth = depth
                        errors.extend(drive_engine(e, m))
                    return errors
                finally:
                    e._depth = configured

            found, sizes = recompile_findings(label, handles, sweep)
            findings.extend(found)
            env.logs.append(
                f"GL001 {label} (depths 1+2): " + ", ".join(
                    f"{n}={b}->{a}" for n, (b, a) in sorted(sizes.items())
                )
            )
        return findings

# -- GL002: donation audit ----------------------------------------------------


@register_graph
class DonationAudit(GraphCheck):
    """Every donate_argnames site in the repo (engine.py plain/spec
    prefill+decode, train.py train_step) lowers and compiles with its
    donations intact: no dropped-donation warnings, and the compiled
    executable's input_output_alias map covers every donated buffer
    >= 1 KiB. The donation chain is also what totally orders dispatches
    on device (engine.py module docstring) — a dropped donation is a
    correctness smell, not just 2x pool HBM."""

    id = "GL002"
    name = "donation-audit"
    description = ("every donate_argnames site compiles to aliased "
                   "in-place buffer updates")

    def run(self, env: GraphEnv) -> list[Finding]:
        findings: list[Finding] = []
        for label, lower, big_leaves in env.donation_sites():
            site = audit_donation_site(label, lower, big_leaves)
            findings.extend(site)
            env.logs.append(
                f"GL002 {label}: {big_leaves} donated buffers, "
                f"{'CLEAN' if not site else f'{len(site)} finding(s)'}"
            )
        return findings


# -- GL003: dtype policy ------------------------------------------------------


@register_graph
class DtypePolicy(GraphCheck):
    """The serving steps' jaxprs obey the dtype policy: no float64
    anywhere (any path), and no f32 upcast of weight-shaped tensors in
    bf16 paths. Mixed-precision activations (norms/softmax/logits in f32)
    are the documented design and do not fire."""

    id = "GL003"
    name = "dtype-policy"
    description = ("no f64 anywhere; bf16 paths never upcast weight "
                   "tensors to f32")

    def run(self, env: GraphEnv) -> list[Finding]:
        findings: list[Finding] = []
        for label, jaxpr, weight_shapes, bf16 in env.step_jaxprs():
            found = dtype_findings(label, jaxpr, weight_shapes, bf16)
            findings.extend(found)
            env.logs.append(
                f"GL003 {label}: "
                f"{'CLEAN' if not found else f'{len(found)} finding(s)'}"
            )
        return findings


# -- GL004: host-transfer guard -----------------------------------------------


# Sanctioned-crossing census (ISSUE 19 satellite): the exact set of
# engine._host_crossing() sites each engine MODE is allowed to fire
# during the guarded serving smoke. This pins the tentpole's crossing
# drop as a GATE: a speculative engine's steady state crosses at the
# block boundary only ("spec-packed" — the once-per-round packed D2H
# that carries tokens, counts, AND the gamma dial), plus the cold-path
# admission/retire scalar sites every mode shares. A new fired site =
# a new per-dispatch host tax someone added without sanctioning it
# here; an expected site that never fires = the fixture stopped
# exercising a crossing this check claims to cover.
_BASE_CROSSINGS = frozenset({
    "merge-upload",         # lane merge scalar upload (admission)
    "first-token-resolve",  # cold-path first-token readback
    "retire-upload",        # retire scalar upload
})
SANCTIONED_CROSSINGS: dict[str, frozenset] = {
    "engine.plain": _BASE_CROSSINGS | {"block-packed"},
    "engine.spec": _BASE_CROSSINGS | {"spec-packed"},
    "engine.hostkv": _BASE_CROSSINGS | {
        "block-packed", "kv-evict-gather", "kv-fault-restore",
    },
}


@register_graph
class HostTransferGuard(GraphCheck):
    """Two halves. Static: the step jaxprs contain no callback/infeed/
    outfeed primitives (a host round-trip per dispatch). Dynamic: a live
    engine smoke runs with jax.transfer_guard('disallow') — the engine's
    deliberate crossings (resolve-point reads, lane merge/retire scalar
    uploads) are annotated with engine._host_crossing(), so any
    UNANNOTATED implicit host<->device transfer added to the serving loop
    raises and surfaces here. On CPU the guard catches implicit
    host-to-device transfers (device-to-host is zero-copy there); on TPU
    the same smoke catches both directions."""

    id = "GL004"
    name = "host-transfer-guard"
    description = ("no callbacks in compiled steps; serving loop passes "
                   "under jax.transfer_guard('disallow')")

    def run(self, env: GraphEnv) -> list[Finding]:
        findings: list[Finding] = []
        for label, jaxpr, _, _ in env.step_jaxprs():
            findings.extend(callback_findings(label, jaxpr))
        findings.extend(self._guarded_smoke(env))
        return findings

    def _guarded_smoke(self, env: GraphEnv) -> list[Finding]:
        # Both serving variants run under the guard: the spec dispatch
        # path has its own annotated crossings (packed + stats reads),
        # and an unannotated transfer added there must trip here too.
        # Both pipeline depths run (ISSUE 6): depth 2 exercises the
        # batched-readback path (_process_step draining LANDED copies
        # behind the dispatch frontier) — its reads must ride the same
        # sanctioned _host_crossing scope as the synchronous depth-1
        # read, or the guard trips here.
        import jax

        from ..engine.engine import CROSSING_CENSUS

        findings: list[Finding] = []
        for label, engine in env.engines():
            waves = env.request_mix(sampled=False)
            # Save/restore the three per-direction options, not the
            # umbrella: updating the umbrella propagates into them, so
            # restoring only it would wipe any pre-set per-direction
            # guard (verified against jax 0.4.37).
            direction_opts = (
                "jax_transfer_guard_host_to_device",
                "jax_transfer_guard_device_to_host",
                "jax_transfer_guard_device_to_device",
            )
            previous = {o: getattr(jax.config, o) for o in direction_opts}
            previous_umbrella = jax.config.jax_transfer_guard
            configured_depth = engine._depth
            census_before = dict(CROSSING_CENSUS)
            jax.config.update("jax_transfer_guard", "disallow")
            try:
                errors = []
                for depth in (1, 2):
                    engine._depth = depth
                    errors.extend(drive_engine(engine, waves))
            finally:
                engine._depth = configured_depth
                # Umbrella first (it propagates into the directions),
                # then each saved per-direction value on top.
                jax.config.update("jax_transfer_guard", previous_umbrella)
                for opt, value in previous.items():
                    jax.config.update(opt, value)
            findings.extend(self._census_findings(
                label, census_before, dict(CROSSING_CENSUS), env,
            ))
            for error in errors:
                key = f"{label}:guarded-smoke"
                if "transfer" in error.lower():
                    findings.append(graph_finding(
                        "GL004", f"graph:{label}", key,
                        "unannotated host<->device transfer in the serving "
                        f"loop (engine smoke under transfer_guard=disallow): "
                        f"{error.splitlines()[0][:200]} — wrap deliberate "
                        "crossings in engine._host_crossing()",
                    ))
                else:
                    findings.append(graph_finding(
                        "GL000", f"graph:{label}", key + ":error",
                        f"GL004 guarded smoke hit a request error: {error}",
                    ))
            if engine.dead is not None:
                findings.append(graph_finding(
                    "GL004", f"graph:{label}",
                    f"{label}:guard-killed-engine",
                    "the engine loop died under transfer_guard=disallow "
                    f"({engine.dead.splitlines()[0][:200]}) — an unannotated "
                    "transfer sits on the loop path itself",
                ))
            if engine._host_kv is not None:
                # Fixture-rot guard (ISSUE 15): the host-KV engine
                # exists to run the eviction gather AND the fault
                # restore under the guard — a sweep that exercised
                # neither proved nothing about the new crossings.
                evicted = engine.metrics.kv_pages_evicted
                restored = engine.metrics.kv_pages_restored
                if evicted == 0 or restored == 0:
                    findings.append(graph_finding(
                        "GL000", f"graph:{label}",
                        f"{label}:hostkv-not-exercised",
                        "GL004's host-KV smoke recorded "
                        f"{evicted} evictions / {restored} restores — "
                        "the sweep no longer drives both host-tier "
                        "crossings (tighten the fixture pool or the "
                        "resident floor)",
                    ))
        env.logs.append(
            "GL004 guarded smoke: "
            + ("CLEAN" if not findings else f"{len(findings)} finding(s)")
        )
        return findings

    @staticmethod
    def _census_findings(label: str, before: dict, after: dict,
                         env) -> list[Finding]:
        """Sanctioned-crossing census for one engine's guarded sweep:
        the set of _host_crossing sites that FIRED (count delta > 0)
        must equal the mode's pinned SANCTIONED_CROSSINGS entry. The
        per-site deltas are logged, so a census regression names the
        site and its per-sweep crossing count."""
        expected = SANCTIONED_CROSSINGS.get(label)
        if expected is None:
            return []
        deltas = {
            site: after.get(site, 0) - before.get(site, 0)
            for site in set(after) | set(before)
        }
        fired = {site for site, n in deltas.items() if n > 0}
        env.logs.append(
            f"GL004 {label} crossing census: " + (", ".join(
                f"{site}={deltas[site]}" for site in sorted(fired)
            ) or "none")
        )
        findings: list[Finding] = []
        for site in sorted(fired - expected):
            findings.append(graph_finding(
                "GL004", f"graph:{label}",
                f"{label}:census:{site}",
                f"unsanctioned host-crossing site '{site}' fired "
                f"{deltas[site]}x during {label}'s guarded sweep — the "
                "serving loop grew a host tax outside the pinned census "
                "(add a per-block/cold-path justification to "
                "SANCTIONED_CROSSINGS or remove the crossing)",
            ))
        for site in sorted(expected - fired):
            findings.append(graph_finding(
                "GL000", f"graph:{label}",
                f"{label}:census-not-exercised:{site}",
                f"sanctioned crossing site '{site}' never fired during "
                f"{label}'s guarded sweep — the fixture no longer "
                "exercises a crossing the census claims to cover",
            ))
        return findings


# -- GL005: shape/layout contracts -------------------------------------------


@register_graph
class ShapeLayoutContracts(GraphCheck):
    """Pallas block-shape and sharding-annotation consistency, verified
    abstractly (no TPU needed):

    - the flash prefill and paged decode kernels trace under eval_shape
      for representative eligible geometries (128-aligned folded lanes,
      int8 KV variant included) and honor their output contracts;
    - kernel eligibility gates agree with the alignment rules they
      encode (use_paged_kernel's 128-lane fold, use_flash's proven head
      dims);
    - every sharding annotation the engine/train path would apply
      (params, KV pool, scale pools) divides its tensor dims exactly, for
      the serving meshes (tp/dp/sp/ep) and the north-star model set."""

    id = "GL005"
    name = "shape-layout-contracts"
    description = ("Pallas block contracts abstract-eval clean; sharding "
                   "annotations divide their dims")

    # Served model set: the tiny CPU-testable configs plus the north-star
    # serving targets (abstract shapes only — an 8B tree is free here).
    MODELS = ("tiny-llama", "tiny-mixtral", "llama-3-8b", "mixtral-8x7b")

    def run(self, env: GraphEnv) -> list[Finding]:
        findings: list[Finding] = []
        findings.extend(self._kernel_contracts())
        findings.extend(self._gate_consistency())
        findings.extend(self._sharding_contracts(env))
        return findings

    def _kernel_contracts(self) -> list[Finding]:
        import jax.numpy as jnp

        from ..ops import flash_attention as flash_mod
        from ..ops import paged_attention_kernel as paged_mod

        findings: list[Finding] = []
        # Flash prefill kernel: eligible geometry (D=64), ragged T/S that
        # the wrapper must pad to block multiples.
        B, T, S, Hq, Hk, D = 1, 130, 257, 4, 2, 64
        findings.extend(abstract_contract(
            "ops.flash_attention",
            lambda q, k, v, pos: flash_mod.flash_attention(
                q, k, v, pos, scale=D ** -0.5, force_kernel=True,
                block_q=64, block_k=128,
            ),
            (
                jnp.zeros((B, T, Hq, D), jnp.bfloat16),
                jnp.zeros((B, S, Hk, D), jnp.bfloat16),
                jnp.zeros((B, S, Hk, D), jnp.bfloat16),
                jnp.zeros((B, T), jnp.int32),
            ),
            [((B, T, Hq, D), "bfloat16")],
        ))
        # Paged decode DMA kernel: folded lane dim Hk*D = 128.
        N, ps, P = 8, 16, 4
        q = jnp.zeros((2, Hq, D), jnp.float32)
        kvp = jnp.zeros((2 * N, ps, Hk * D), jnp.float32)     # page halves
        tables = jnp.zeros((2, P), jnp.int32)
        positions = jnp.zeros((2,), jnp.int32)
        window = jnp.zeros((1,), jnp.int32)
        page_range = jnp.asarray([0, P], jnp.int32)
        findings.extend(abstract_contract(
            "ops.paged_attention_kernel._decode_call",
            lambda *args: paged_mod._decode_call(
                *args, scale=D ** -0.5, logit_softcap=None, interpret=False,
                state=False,
            ),
            (q, kvp, tables, positions, window, page_range),
            [((2, Hq, D), "float32")],
        ))
        # int8-KV variant: the (values, k scales, v scales) triple,
        # scales [N, ps, Hk]; here as a context-parallel shard calls it,
        # for the unnormalised state.
        kq = jnp.zeros((2 * N, ps, Hk * D), jnp.int8)
        scales = jnp.zeros((N, ps, Hk), jnp.bfloat16)
        findings.extend(abstract_contract(
            "ops.paged_attention_kernel._decode_call[int8]",
            lambda q2, kv, sc, t, p, w, r: paged_mod._decode_call(
                q2, (kv, sc, sc), t, p, w, r,
                scale=D ** -0.5, logit_softcap=None, interpret=False,
                state=True,
            ),
            (q.astype(jnp.bfloat16), kq, scales, tables, positions, window,
             page_range),
            [((2, Hq, D), "float32"),
             ((2, Hq, 1), "float32"), ((2, Hq, 1), "float32")],
        ))
        return findings

    def _gate_consistency(self) -> list[Finding]:
        from ..models.config import get_config

        return gate_consistency_findings(
            get_config(name) for name in self.MODELS
        )

    def _sharding_contracts(self, env: GraphEnv) -> list[Finding]:
        import jax
        import jax.numpy as jnp

        from ..engine.kv_cache import init_paged_kv
        from ..models.config import get_config
        from ..models.transformer import init_params
        from ..parallel.mesh import MeshConfig, create_mesh
        from ..parallel.sharding import (
            kv_scale_sharding,
            paged_kv_sharding,
            param_shardings,
        )

        findings: list[Finding] = []
        n_devices = len(jax.devices())
        mesh_cfgs = [
            ("tp2", MeshConfig(tp=2), 2),
            ("dp2", MeshConfig(dp=2), 2),
            ("sp2", MeshConfig(sp=2), 2),
            ("tp2dp2", MeshConfig(tp=2, dp=2), 4),
            ("ep2", MeshConfig(ep=2), 2),
        ]
        for model in self.MODELS:
            cfg = get_config(model)
            abstract_params = jax.eval_shape(
                lambda key, c=cfg: init_params(key, c, jnp.bfloat16),
                jax.random.PRNGKey(0),
            )
            pool = jax.eval_shape(
                lambda c=cfg: init_paged_kv(c, 64, 16, jnp.bfloat16)
            )
            scale_pool = jax.eval_shape(
                lambda c=cfg: init_paged_kv(
                    c, 64, 16, jnp.bfloat16, kv_dtype=jnp.int8)
            )
            for mesh_name, mesh_cfg, needed in mesh_cfgs:
                if needed > n_devices:
                    env.logs.append(
                        f"GL005 sharding {model}/{mesh_name}: skipped "
                        f"(needs {needed} devices, have {n_devices})"
                    )
                    continue
                if mesh_cfg.ep > 1 and not cfg.is_moe:
                    continue
                if cfg.num_kv_heads % mesh_cfg.tp != 0:
                    continue  # the engine refuses this combo up front
                mesh = create_mesh(
                    mesh_cfg,
                    jax.devices()[: needed],
                )
                shardings = param_shardings(
                    cfg, mesh, params_tree=abstract_params)
                flat_params, _ = jax.tree_util.tree_flatten(abstract_params)
                flat_shardings, _ = jax.tree_util.tree_flatten(shardings)
                for leaf, sharding in zip(flat_params, flat_shardings):
                    findings.extend(sharding_divisibility(
                        f"params[{model}/{mesh_name}]",
                        tuple(leaf.shape), sharding,
                    ))
                kv_sh = paged_kv_sharding(mesh)
                for leaf in jax.tree_util.tree_leaves(pool):
                    findings.extend(sharding_divisibility(
                        f"kv_pool[{model}/{mesh_name}]",
                        tuple(leaf.shape), kv_sh,
                    ))
                scale_sh = kv_scale_sharding(mesh)
                for leaf in jax.tree_util.tree_leaves(scale_pool):
                    findings.extend(sharding_divisibility(
                        f"kv_scale_pool[{model}/{mesh_name}]",
                        tuple(leaf.shape),
                        kv_sh if leaf.ndim == 5 else scale_sh,
                    ))
        return findings


# -- runner + CLI -------------------------------------------------------------


def apply_check_suppressions(findings: list[Finding]) -> list[Finding]:
    """Mark findings whose snippet key carries a class-level suppression
    (the graph tier's disable= analogue; reasons are mandatory by
    construction — the dict value IS the reason)."""
    from dataclasses import replace

    by_id = {check.id: check for check in all_graph_checks()}
    out: list[Finding] = []
    for f in findings:
        reason = by_id.get(f.rule, GraphCheck).SUPPRESSIONS.get(f.snippet)
        if reason is not None:
            out.append(replace(f, suppressed=True, reason=reason))
        else:
            out.append(f)
    return out


def run_graph_checks(
    env: Optional[GraphEnv] = None,
    only: Optional[set[str]] = None,
) -> tuple[list[Finding], GraphEnv]:
    _ensure_cpu_backend()
    if env is None:
        env = GraphEnv()
    findings: list[Finding] = []
    for check in all_graph_checks():
        if only is not None and check.id not in only:
            continue
        try:
            findings.extend(check.run(env))
        except Exception as e:  # a crashed check must not read as clean
            findings.append(graph_finding(
                "GL000", f"graph:{check.id}", f"{check.id}:crashed",
                f"check {check.id} ({check.name}) crashed: "
                f"{type(e).__name__}: {e}",
            ))
    return apply_check_suppressions(findings), env


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m polykey_tpu.analysis graph",
        description="graphlint: compiled-graph contract analysis for the "
                    "TPU serving stack (CPU-backed; no hardware needed)",
    )
    parser.add_argument(
        "--root", default=".",
        help="repo root the baseline file lives under (default: cwd)",
    )
    parser.add_argument(
        "--baseline", default=GRAPH_BASELINE, metavar="FILE",
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
        help="drop baseline entries whose finding no longer fires, keep "
             "the rest, and exit (never adds entries)",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit findings + summary as one JSON object",
    )
    parser.add_argument(
        "--list-checks", action="store_true",
        help="print the check table and exit",
    )
    parser.add_argument(
        "--only", default=None, metavar="GL001[,GL002...]",
        help="run only the named checks",
    )
    return parser


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.list_checks:
        for check in all_graph_checks():
            print(f"{check.id}  {check.name:<26} {check.description}")
        return 0

    root = Path(args.root).resolve()
    if not root.is_dir():
        print(f"graphlint: --root {args.root} is not a directory",
              file=sys.stderr)
        return 2
    try:
        # A typo'd id silently running zero checks would read as a
        # clean graph (the exact failure mode GL000 exists to prevent),
        # and a partial run can't tell "fixed" from "not checked"
        # (shared refusal semantics, core.py).
        only = parse_only(args.only, set(_GRAPH_REGISTRY), noun="check")
        require_full_run(partial=only is not None, prune=args.prune,
                         write_baseline=args.write_baseline)
    except UsageError as e:
        print(f"graphlint: {e}", file=sys.stderr)
        return 2

    t0 = time.monotonic()
    env = GraphEnv()
    try:
        findings, env = run_graph_checks(env, only=only)
    finally:
        env.close()
    elapsed = time.monotonic() - t0
    for line in env.logs:
        print(f"graphlint: {line}", file=sys.stderr)

    baseline_path = root / args.baseline
    if args.prune:
        # A crashed check is a partial run in disguise: its real findings
        # were replaced by GL000, so every entry it grandfathers would
        # read "fixed" and get dropped while the debt is still live.
        infra = [f for f in findings if f.rule == "GL000"]
        if infra:
            print(
                f"graphlint: refusing to prune with {len(infra)} GL000 "
                "analyzer-infrastructure finding(s) present — fix the "
                "probe first", file=sys.stderr)
            return 1
        kept, dropped = prune_baseline(baseline_path, findings)
        print(f"graphlint: pruned {dropped} stale baseline entr"
              f"{'y' if dropped == 1 else 'ies'} from {baseline_path} "
              f"({kept} kept)")
        return 0
    if args.write_baseline:
        # GL000 = the analyzer itself is broken; grandfathering it would
        # make graphlint exit 0 forever while verifying nothing — and a
        # crashed check is a partial run in disguise, so rewriting the
        # file now would drop its still-live grandfathered entries.
        # Refuse BEFORE touching the file.
        infra = [f for f in findings if f.rule == "GL000"]
        if infra:
            print(
                f"graphlint: refusing to write the baseline with "
                f"{len(infra)} GL000 analyzer-infrastructure finding(s) "
                "present — fix the probe first", file=sys.stderr)
            return 1
        count = write_baseline(baseline_path, findings)
        print(f"graphlint: wrote {count} baseline entr"
              f"{'y' if count == 1 else 'ies'} to {baseline_path}")
        return 0

    stale: list[str] = []
    if not args.no_baseline:
        findings, stale = apply_baseline(findings, load_baseline(baseline_path))
        if only is not None:
            # A partial run can't distinguish "fixed" from "not checked";
            # reporting entries of unrun checks as stale would be a false
            # debt-paid signal (and bad --prune advice).
            stale = []

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
                "elapsed_s": round(elapsed, 1),
                "graph_clean": not blocking,
            },
        }, indent=2))
    else:
        for f in sorted(findings, key=lambda f: (f.path, f.rule, f.snippet)):
            if f.blocking:
                print(f.render())
        parts = [f"{len(blocking)} blocking"]
        if suppressed:
            parts.append(f"{suppressed} suppressed")
        if baselined:
            parts.append(f"{baselined} baselined")
        print(f"graphlint: {', '.join(parts)} ({elapsed:.1f}s)")
        if stale:
            print(
                f"graphlint: {len(stale)} stale baseline entr"
                f"{'y' if len(stale) == 1 else 'ies'} (fixed findings) — "
                "re-run with --prune to drop them",
            )
    return 1 if blocking else 0
