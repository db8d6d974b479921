"""Engine configuration, layered on the gateway's precedence discipline.

Extends the reference's config model (internal/config/config.go: defaults <
flags < env) with the serving-engine settings the north star needs: model
selection, decode-batch geometry, KV page pool, prefill buckets, parallelism
axes. Env vars use the same POLYKEY_* prefix.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_bool(name: str, extra: tuple[str, ...] = ()) -> bool:
    return os.environ.get(name, "").lower() in ("1", "true", *extra)


# The checkout root, computed from the package's location — never from the
# current directory. <checkout>/.jax_cache is the default compile cache: the
# directory is part of JAX's cache key, so a path that moves between runs
# never hits.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
_CHECKOUT_CACHE = os.path.join(CHECKOUT, ".jax_cache")


def enable_persistent_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns the directory in
    use (None when POLYKEY_COMPILE_CACHE=0 opts out).

    Where JAX_COMPILATION_CACHE_DIR is set JAX already reads it, and this
    function writes no cache directory of its own — whoever runs the
    process places the cache from outside. Otherwise the cache lives in
    <checkout>/.jax_cache (git-ignored). JAX binds the directory at the
    first compile, so every process entry that compiles (gateway server,
    disagg worker, chip_smoke.py, perfbench, the kernel check) calls this
    before its first jit.

    Every executable is persisted, however quick its compile: a serving
    warm-up builds a handful of sub-second ones (slot-state merges, the
    host-tier gather) that JAX's default 1 s threshold would recompile at
    every start. JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS from outside
    wins; JAX's other variables tune the rest.
    """
    if os.environ.get("POLYKEY_COMPILE_CACHE", "1") == "0":
        return None
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


def compile_cache_dir() -> Optional[str]:
    """The directory this process's persistent compile cache is placed in
    (what enable_persistent_compile_cache returned, or whoever set JAX's
    own variable or option), None where there is none: the opt-out, or a
    process that placed no cache. The engine keeps its executable store
    (engine/executables.py) there, so whoever places the cache places
    the store and POLYKEY_COMPILE_CACHE=0 turns both off."""
    if os.environ.get("POLYKEY_COMPILE_CACHE", "1") == "0":
        return None
    import jax

    return jax.config.jax_compilation_cache_dir or None


@dataclass(frozen=True)
class EngineConfig:
    model: str = "tiny-llama"
    tokenizer: str = "byte"              # 'byte' or a local HF tokenizer path
    dtype: str = "bfloat16"
    checkpoint_path: Optional[str] = None  # None → random init (dev/bench)
    quantize: bool = False               # weight-only quant (models/quant.py)
    # Quantization width: 8 (per-channel int8) or 4 (group-wise int4 —
    # halves weight HBM traffic again; embed/lm_head stay int8).
    # POLYKEY_QUANTIZE=int4 selects 4.
    quantize_bits: int = 8
    # KV-cache dtype: "bfloat16"/"float32" full precision, or "int8" —
    # per-(token, head) symmetric quantization at write time
    # (ops/paged_attention.quantize_kv_rows). Halves pool HBM, which is
    # the decode-slot budget on a 16 GiB chip; decode attention streams
    # the int8 pages through the DMA read kernel's in-kernel dequant
    # stage (half the bf16 bytes). POLYKEY_KV_DTYPE=int8 selects it.
    kv_dtype: str = ""                   # "" → follow `dtype`

    # Decode-batch geometry (static shapes; compile-time constants).
    # Defaults target real serving lengths (VERDICT r1 #5): 4k positions
    # per request, 32k pooled KV token-slots. Prompts longer than the
    # largest bucket prefill in `prefill_chunk`-sized chunks interleaved
    # with decode steps, so a long prompt never stalls running streams for
    # more than one chunk.
    max_decode_slots: int = 16
    page_size: int = 16
    num_pages: int = 2048                # includes reserved garbage page 0
    max_seq_len: int = 4096              # per-request position cap
    # The prefill window widths that are compiled, each for 1/2/4/8 rows.
    # A prompt (or a prefix-cache suffix, or a long prompt's tail) is
    # covered by the FEWEST rows they allow (engine.prefill_cover): one
    # window of the bucket that holds it, or several windows of a
    # narrower bucket as consecutive rows of one dispatch — 129..256
    # tokens here are two 128-row windows, not one of 512; 257..512 stay
    # one 512 window (three or four 128-windows pad to as many rows).
    # Buckets should be whole pages: a bucket that is not is never
    # split over.
    prefill_buckets: tuple[int, ...] = (128, 512)
    prefill_chunk: int = 0               # 0 → max(prefill_buckets)
    max_new_tokens_cap: int = 1024
    default_max_new_tokens: int = 64

    # Interleaved-prefill token budget (ISSUE 4, Sarathi-style): while any
    # decode lane is live, at most ~this many prefill tokens (burst
    # admission groups + long-prompt chunks) dispatch per engine-loop
    # iteration, i.e. between two decode blocks — so a prefill burst or a
    # long-prompt admission can no longer starve the decode lookahead
    # pipeline and blow ITL. 0 → auto (2 × the prefill chunk). The budget
    # is a soft bound at dispatch granularity: one admission group or one
    # chunk always proceeds per iteration (progress floor), and the last
    # unit may overshoot — worst case per iteration is
    # budget + largest_bucket + chunk. With NO live decode lanes the
    # budget is waived entirely (there is no ITL to protect; cold bursts
    # should fill all slots at once). POLYKEY_PREFILL_BUDGET.
    prefill_budget: int = 0

    # Automatic prefix caching (engine/prefix_cache.py): requests sharing a
    # page-aligned prompt prefix reuse its KV pages and prefill only the
    # suffix. prefix_cache_pages caps the cache's own page references
    # (LRU); 0 → num_pages // 2. Composes with speculative decoding: the
    # draft pool shares page indices and spec prefill writes BOTH pools
    # for every window, so a cached page carries both models' prefix KV.
    prefix_cache: bool = False
    prefix_cache_pages: int = 0

    # -- Host-memory KV tier (ISSUE 15, ROADMAP item 3) ----------------------
    # POLYKEY_HOST_KV_BYTES > 0 adds a second KV tier in host RAM: cold
    # pages — prefix-cache entries whose sessions finished (sticky
    # multi-turn histories, long-context middles) — are evicted from the
    # device pool into pinned host buffers through a fixed-width jit'd
    # gather, and paged back on demand through the (equally fixed-width,
    # pool-donating) `_jit_kv_restore` scatter when a later request's
    # prefix-cache lookup hits them. Capacity then bounds on host RAM
    # instead of HBM. 0 (the default) allocates NO host pool and leaves
    # every existing path byte-identical. Requires prefix_cache (the
    # spill source); from_env auto-enables it.
    host_kv_bytes: int = 0
    # Resident working set: when a retiring request leaves fewer than
    # this many device pages free, LRU prefix-cache pages spill to the
    # host tier until the floor is restored (eviction at retire — the
    # proactive path that keeps admissions from ever paying the spill
    # synchronously). 0 → num_pages // 8. POLYKEY_KV_RESIDENT_PAGES.
    host_kv_resident_pages: int = 0
    # Page-aware restore scheduling: how many faulting slots may issue
    # their host→device restore dispatch per engine-loop iteration. A
    # lane whose pages are in flight never joins a prefill/decode
    # dispatch until its restore has issued, and this budget bounds how
    # much restore upload work rides any one gap between decode blocks
    # — the interleaved-prefill discipline applied to page faults.
    # POLYKEY_KV_RESTORE_SLOTS.
    host_kv_restore_slots: int = 2
    # Restart-durable prefix cache: a directory where spilled prefix
    # pages are ALSO serialized in the PR 13 KV wire format (CRC-framed
    # `serialize_kv_state` blobs + a JSON sidecar of page keys). A fresh
    # engine — in particular the supervisor's post-crash restart — scans
    # the dir at construction and reloads matching pages into the host
    # tier, so sticky sessions keep their warm TTFT across restarts.
    # Corrupt/CRC-failing files are skipped (warmth lost, never
    # liveness). "" disables persistence. POLYKEY_KV_STATE_DIR.
    kv_state_dir: str = ""

    # Pre-compile the prefill group shapes ({1,2,4,8} × buckets) and the
    # decode block (or spec round) at engine construction, before the loop
    # starts — first requests (and benchmark windows) then never pay XLA
    # compile time. Costs startup latency.
    compile_warmup: bool = False

    # With compile_warmup, also pre-compile the sampled-path variants
    # (greedy=False prefill/decode, truncated-top-p spec round, spec→plain
    # fallback). On for serving — the first sampled request must not stall
    # on a compile; off for greedy-only runs (the benchmark), where those
    # variants are never dispatched and roughly double warmup wall-clock.
    warm_sampled_variants: bool = True

    # Decode steps per dispatch: the jitted decode runs `decode_block_steps`
    # steps in one lax.scan call, with device-side EOS/budget stopping, so
    # per-dispatch host overhead (Python + host<->device transfer latency)
    # amortizes K-fold.
    # Tokens stream out in blocks of ≤K per request; prefills interleave at
    # block boundaries. 1 → token-at-a-time (lowest streaming latency).
    decode_block_steps: int = 8

    # Load-adaptive blocking: when only ONE stream is active, dispatch
    # small blocks (max(1, K // 8)) instead of the full K — a lone
    # stream's tokens then stream out one-at-a-time at the device's step
    # rate rather than arriving K at a time (the solo-latency cliff,
    # VERDICT r2 weak #6), while the lookahead pipeline keeps the device
    # busy. Under load the full K amortizes per-dispatch host overhead.
    # Output is unchanged either way (blocked decode is a pure batching
    # of the step loop); only dispatch granularity adapts.
    adaptive_block: bool = True

    # In-flight decode blocks (pipeline depth): the engine keeps up to
    # `lookahead_blocks` dispatched-but-unprocessed FULL-K blocks on the
    # device queue, so host-side processing and D2H latency hide behind
    # device compute. When adaptive blocking shrinks K the LOOKAHEAD
    # portion scales up by the same factor — 1 + (depth-1) x (K/steps),
    # capped at 64 blocks — keeping queued-ahead steps constant while
    # depth 1 stays exactly synchronous.
    # Device-side stopping + per-block request snapshots make
    # stale blocks safe (engine.py _run); the cost is up to
    # lookahead_blocks x decode_block_steps wasted device steps when a
    # stream finishes. 1 → classic dispatch-then-process.
    lookahead_blocks: int = 2

    # Flight-deck timeline (ISSUE 10): bounded ring of typed engine
    # events — dispatch/process frontiers, admissions, prefill chunks,
    # retirements, expiries, restarts, re-routes — exported as
    # Perfetto JSON (/debug/timeline, occupancy_soak --timeline).
    # Capacity bounds memory (events are small tuples; 4096 ≈ a few
    # hundred KB worst case). 0 DISABLES it: the engine allocates no
    # ring and every emission site is one `is None` branch, so an
    # obs-less deployment pays nothing. POLYKEY_TIMELINE_CAPACITY.
    timeline_capacity: int = 4096

    # Black-box checkpoint cadence (ISSUE 16, obs/postmortem.py): a
    # disagg member with a state dir flushes its timeline +
    # flight-recorder rings to `blackbox-<role>.json` every this many
    # timeline appends (plus forced flushes at control-plane op intake
    # and on the supervisor trip path). 0 DISABLES black boxes even
    # when a state dir exists. POLYKEY_BLACKBOX_EVERY.
    blackbox_every: int = 64

    # SLO signal plane (ISSUE 11, obs/signals.py): seconds between ring
    # samples of the metrics registry — monotone counters become
    # windowed rates, cumulative histograms become delta-quantiles over
    # 1m/5m/1h windows (POLYKEY_SIGNALS_WINDOWS), fixing the "p95 since
    # boot" staleness and feeding burn-rate SLO evaluation
    # (POLYKEY_SLO). Sampling rides engine-loop block boundaries with
    # the idle tick as the low-rate fallback; the read side also
    # samples, so windows advance even when the loop is wedged. 0
    # DISABLES the plane entirely: no ring allocated,
    # `metrics.signals is None`, one `is None` branch in the loop — the
    # timeline_capacity=0 discipline. POLYKEY_SIGNALS_INTERVAL.
    signals_interval_s: float = 5.0
    # Window widths (comma-separated seconds, "" → the env /
    # 60,300,3600 defaults) and the SLO policy spec (inline JSON,
    # "@/path.json", or "default"; "" → POLYKEY_SLO). Carried on the
    # config so programmatic constructions (tests, embedded
    # engines) control them without mutating os.environ, and so a
    # supervised restart rebuilds the plane from the SAME spec the
    # original engine ran — engines built with the empty defaults fall
    # back to the env at construction time.
    signals_windows: str = ""
    slo_policy: str = ""

    # Parallelism axes (parallel/mesh.py); 1 → axis unused. ep shards MoE
    # expert weights and rides token dispatch over the ep axis (Mixtral —
    # BASELINE.md measurement config 4); it requires an MoE model. sp
    # shards the PREFILL token axis (sequence-parallel prefill): long
    # prompts spread their attention/MLP compute over sp chips, with the
    # KV writes exchanged into the sp-replicated page pools by GSPMD —
    # the serving-path long-context story (SURVEY §5). Decode is
    # unaffected (T=1). Buckets and prefill_chunk must divide by sp.
    # pp shards the stacked-layer axis (memory distribution: a model
    # larger than one chip's HBM serves across pp stages; decode
    # activations hop stages via compiler-inserted transfers — capacity,
    # not throughput; the GPipe schedule in parallel/pipeline.py is the
    # training-side formulation).
    tp: int = 1
    dp: int = 1
    ep: int = 1
    sp: int = 1
    pp: int = 1

    # Multi-slice serving: >1 spans the mesh across `num_slices` ICI
    # domains connected by DCN (parallel/distributed.py:create_hybrid_mesh).
    # dp above is PER-SLICE — the mesh's dp axis extent becomes
    # num_slices × dp, with the slice dimension outermost so data-parallel
    # is the ONLY axis whose collectives cross DCN; tp/ep/sp/pp stay
    # inside a slice (the layout rule from parallel/distributed.py).
    num_slices: int = 1

    # Sampled-path top-p prefilter width: >0 restricts each row to its
    # top-K logits via lax.top_k (no full [B, vocab] sort — the expensive
    # op at 128k-256k vocab) and applies top-p within them; equivalent to
    # composing top-k=K with top-p, exact whenever the top-p support fits
    # in K. 0 → exact full-vocab sort. Greedy batches never sort either
    # way. Also enables top_p<1 requests on the SPECULATIVE path
    # (truncated rejection sampling — sampling.truncated_dist); with
    # 0, spec engines route top_p<1 batches through the plain step.
    # With the prefilter on, a request's top_k clamps to this width
    # (the sampled paths only ever see the top-C logits).
    top_p_candidates: int = 0

    # Speculative decoding (engine/spec_decode.py): a draft model name turns
    # it on; gamma = drafts per verify round. Draft must share the target's
    # vocab. top_p<1 requests ride the spec path when top_p_candidates > 0
    # (truncated rejection sampling); otherwise they fall back to the
    # plain decode step.
    draft_model: Optional[str] = None
    draft_checkpoint_path: Optional[str] = None  # None → random init
    spec_gamma: int = 4

    # Wire gamma to MEASURED acceptance: dispatch gamma moves on a
    # two-level ladder {max(1, spec_gamma//2), spec_gamma} driven by an
    # acceptance EWMA with hysteresis (engine._process_spec) — a draft
    # that keeps getting rejected stops wasting spec_gamma draft
    # forwards per round. Page/position slack always reserves for the
    # full spec_gamma, so adaptation never overflows a slot.
    adaptive_gamma: bool = True

    # Liveness. The watchdog window must comfortably exceed worst-case XLA
    # compile time (each new prefill bucket compiles on first use).
    watchdog_timeout_s: float = 300.0
    request_timeout_s: float = 300.0

    # -- Overload safety (ISSUE 3) -------------------------------------------
    # Bound on the submit queue: requests beyond it are shed immediately
    # with RESOURCE_EXHAUSTED + a retry-after-ms hint (engine.submit)
    # instead of queueing into unbounded latency. 0 → unbounded (bench /
    # soak harnesses that deliberately flood the queue).
    max_queue_depth: int = 256
    # Supervised restarts (engine/supervisor.py): a watchdog trip or
    # engine-loop crash triggers an in-process restart — fresh engine,
    # re-armed watchdog, health back to SERVING — up to
    # `max_engine_restarts` times within `restart_window_s` before the
    # supervisor gives up and leaves the process NOT_SERVING for the
    # platform to recycle (compose healthcheck / k8s restart policy).
    supervise: bool = True
    max_engine_restarts: int = 3
    restart_window_s: float = 600.0

    # -- Replica tier (ISSUE 9) ----------------------------------------------
    # POLYKEY_REPLICAS > 1 serves through an in-process pool of
    # independently supervised engine replicas (engine/replica_pool.py)
    # behind a health/load-aware router. 1 (the default) keeps the
    # single-engine wiring byte-for-byte: no pool object, no routing, no
    # behavior change.
    replicas: int = 1
    # This engine's identity within a pool (fault targeting, metric
    # labels, stats). Set by the pool via dataclasses.replace — not an
    # env knob; a standalone engine is replica 0.
    replica: int = 0
    # Router score = prefix_weight × (cached-prefix fraction)
    #              − delay_weight × (estimated queue delay, s);
    # candidates whose estimated delay would blow the request deadline
    # are filtered first (headroom check). Ties break on the lowest
    # replica index, so routing is deterministic given equal state.
    route_prefix_weight: float = 1.0
    route_delay_weight: float = 1.0
    # How many times one request may be re-routed onto another replica
    # after an engine-lifecycle failure (queued requests move losslessly;
    # in-flight streams resume with already-emitted tokens suppressed).
    # 0 disables failover re-routing (failures surface as UNAVAILABLE,
    # exactly the single-engine contract).
    max_reroutes: int = 3

    # -- Disaggregated prefill/decode tiers (ISSUE 13) -----------------------
    # POLYKEY_DISAGG="PxD" (e.g. "2x2") or "prefill=P,decode=D" serves
    # through CROSS-PROCESS worker tiers (engine/disagg_pool.py): P
    # prefill-tier + D decode-tier worker processes on localhost, each a
    # supervised engine behind a socket control plane
    # (engine/worker.py), with finished prefill KV shipped to a
    # NetKV-scored decode worker in the versioned kv_cache wire format.
    # "" (the default) builds NO worker processes and NO pool — every
    # single-process path is byte-identical. Mutually exclusive with
    # POLYKEY_REPLICAS > 1 (the in-process stage-(a) pool).
    disagg: str = ""
    # This engine's tier identity inside a disaggregated worker
    # ("prefill" / "decode"; set by engine/worker.py via
    # dataclasses.replace, not an env knob). Scopes ":tier=" fault
    # targeting; "" for every non-disaggregated engine.
    disagg_tier: str = ""
    # Worker liveness: the coordinator heartbeats every worker's control
    # plane at this interval and declares death after `disagg_miss`
    # consecutive misses (process exit via poll() is detected
    # immediately either way). POLYKEY_DISAGG_HEARTBEAT /
    # POLYKEY_DISAGG_MISS.
    disagg_heartbeat_s: float = 0.5
    disagg_miss: int = 3
    # How long a re-route waits for a tier to regain a SERVING worker
    # (a supervised worker restart takes seconds on CPU; giving up
    # sooner would turn every restart window into failed RPCs).
    # POLYKEY_DISAGG_RECOVERY_WAIT.
    disagg_recovery_wait_s: float = 30.0

    @property
    def pages_per_seq(self) -> int:
        return self.max_seq_len // self.page_size

    @classmethod
    def from_env(cls) -> "EngineConfig":
        buckets = os.environ.get("POLYKEY_PREFILL_BUCKETS")
        return cls(
            model=os.environ.get("POLYKEY_MODEL", cls.model),
            tokenizer=os.environ.get("POLYKEY_TOKENIZER", cls.tokenizer),
            dtype=os.environ.get("POLYKEY_DTYPE", cls.dtype),
            checkpoint_path=os.environ.get("POLYKEY_CHECKPOINT") or None,
            quantize=_env_bool("POLYKEY_QUANTIZE", extra=("int8", "int4")),
            kv_dtype=os.environ.get("POLYKEY_KV_DTYPE", cls.kv_dtype),
            quantize_bits=(
                4 if os.environ.get("POLYKEY_QUANTIZE", "").lower() == "int4"
                else cls.quantize_bits
            ),
            max_decode_slots=_env_int("POLYKEY_MAX_DECODE_SLOTS", cls.max_decode_slots),
            page_size=_env_int("POLYKEY_PAGE_SIZE", cls.page_size),
            num_pages=_env_int("POLYKEY_NUM_PAGES", cls.num_pages),
            max_seq_len=_env_int("POLYKEY_MAX_SEQ_LEN", cls.max_seq_len),
            prefill_buckets=tuple(
                int(x) for x in buckets.split(",")
            ) if buckets else cls.prefill_buckets,
            prefill_chunk=_env_int("POLYKEY_PREFILL_CHUNK", cls.prefill_chunk),
            prefill_budget=_env_int(
                "POLYKEY_PREFILL_BUDGET", cls.prefill_budget
            ),
            max_new_tokens_cap=_env_int(
                "POLYKEY_MAX_NEW_TOKENS_CAP", cls.max_new_tokens_cap
            ),
            default_max_new_tokens=_env_int(
                "POLYKEY_DEFAULT_MAX_NEW_TOKENS", cls.default_max_new_tokens
            ),
            # The host tier's spill source is the prefix cache, so
            # enabling the tier enables the cache (validate() enforces
            # the pairing for programmatic configs).
            prefix_cache=(
                _env_bool("POLYKEY_PREFIX_CACHE")
                or _env_int("POLYKEY_HOST_KV_BYTES", 0) > 0
            ),
            prefix_cache_pages=_env_int(
                "POLYKEY_PREFIX_CACHE_PAGES", cls.prefix_cache_pages
            ),
            host_kv_bytes=_env_int("POLYKEY_HOST_KV_BYTES", cls.host_kv_bytes),
            host_kv_resident_pages=_env_int(
                "POLYKEY_KV_RESIDENT_PAGES", cls.host_kv_resident_pages
            ),
            host_kv_restore_slots=_env_int(
                "POLYKEY_KV_RESTORE_SLOTS", cls.host_kv_restore_slots
            ),
            kv_state_dir=os.environ.get(
                "POLYKEY_KV_STATE_DIR", cls.kv_state_dir
            ),
            compile_warmup=_env_bool("POLYKEY_COMPILE_WARMUP"),
            decode_block_steps=_env_int(
                "POLYKEY_DECODE_BLOCK", cls.decode_block_steps
            ),
            # Default ON; POLYKEY_ADAPTIVE_BLOCK=0 pins the static block.
            adaptive_block=os.environ.get(
                "POLYKEY_ADAPTIVE_BLOCK", "1"
            ).lower() in ("1", "true"),
            # POLYKEY_DISPATCH_LOOKAHEAD is the documented knob (DEPLOY.md;
            # the engine also honors it as a construction-time override so
            # it works however the config was built).
            lookahead_blocks=_env_int(
                "POLYKEY_DISPATCH_LOOKAHEAD", cls.lookahead_blocks
            ),
            timeline_capacity=_env_int(
                "POLYKEY_TIMELINE_CAPACITY", cls.timeline_capacity
            ),
            blackbox_every=_env_int(
                "POLYKEY_BLACKBOX_EVERY", cls.blackbox_every
            ),
            signals_interval_s=_env_float(
                "POLYKEY_SIGNALS_INTERVAL", cls.signals_interval_s
            ),
            # Captured as raw strings at from_env time so the config —
            # and therefore every supervised-restart factory replay —
            # pins the windows/policy the server booted with even if
            # the process env mutates later.
            signals_windows=os.environ.get(
                "POLYKEY_SIGNALS_WINDOWS", cls.signals_windows
            ),
            slo_policy=os.environ.get("POLYKEY_SLO", cls.slo_policy),
            tp=_env_int("POLYKEY_TP", cls.tp),
            dp=_env_int("POLYKEY_DP", cls.dp),
            ep=_env_int("POLYKEY_EP", cls.ep),
            sp=_env_int("POLYKEY_SP", cls.sp),
            pp=_env_int("POLYKEY_PP", cls.pp),
            num_slices=_env_int("POLYKEY_NUM_SLICES", cls.num_slices),
            top_p_candidates=_env_int(
                "POLYKEY_TOP_P_CANDIDATES", cls.top_p_candidates
            ),
            draft_model=os.environ.get("POLYKEY_DRAFT_MODEL") or None,
            draft_checkpoint_path=os.environ.get("POLYKEY_DRAFT_CHECKPOINT")
            or None,
            spec_gamma=_env_int("POLYKEY_SPEC_GAMMA", cls.spec_gamma),
            adaptive_gamma=os.environ.get(
                "POLYKEY_ADAPTIVE_GAMMA", "1"
            ).lower() in ("1", "true"),
            watchdog_timeout_s=_env_float(
                "POLYKEY_WATCHDOG_TIMEOUT", cls.watchdog_timeout_s
            ),
            request_timeout_s=_env_float(
                "POLYKEY_REQUEST_TIMEOUT", cls.request_timeout_s
            ),
            max_queue_depth=_env_int(
                "POLYKEY_MAX_QUEUE", cls.max_queue_depth
            ),
            # Default ON; POLYKEY_SUPERVISE=0 pins the one-shot behavior
            # (process restart is then the only recovery path).
            supervise=os.environ.get(
                "POLYKEY_SUPERVISE", "1"
            ).lower() in ("1", "true"),
            max_engine_restarts=_env_int(
                "POLYKEY_MAX_RESTARTS", cls.max_engine_restarts
            ),
            restart_window_s=_env_float(
                "POLYKEY_RESTART_WINDOW", cls.restart_window_s
            ),
            replicas=_env_int("POLYKEY_REPLICAS", cls.replicas),
            route_prefix_weight=_env_float(
                "POLYKEY_ROUTE_W_PREFIX", cls.route_prefix_weight
            ),
            route_delay_weight=_env_float(
                "POLYKEY_ROUTE_W_DELAY", cls.route_delay_weight
            ),
            max_reroutes=_env_int("POLYKEY_MAX_REROUTES", cls.max_reroutes),
            disagg=os.environ.get("POLYKEY_DISAGG", cls.disagg),
            disagg_heartbeat_s=_env_float(
                "POLYKEY_DISAGG_HEARTBEAT", cls.disagg_heartbeat_s
            ),
            disagg_miss=_env_int("POLYKEY_DISAGG_MISS", cls.disagg_miss),
            disagg_recovery_wait_s=_env_float(
                "POLYKEY_DISAGG_RECOVERY_WAIT", cls.disagg_recovery_wait_s
            ),
        )

    def disagg_tiers(self) -> Optional[tuple[int, int]]:
        """Parse the `disagg` spec into (prefill_workers, decode_workers),
        or None when unset. Accepts "PxD" ("2x2") and
        "prefill=P,decode=D" (any order). Raises ValueError on malformed
        specs — a typo must not silently serve single-process."""
        spec = self.disagg.strip().lower()
        if not spec:
            return None
        try:
            if "x" in spec and "=" not in spec:
                p_s, d_s = spec.split("x", 1)
                tiers = {"prefill": int(p_s), "decode": int(d_s)}
            else:
                tiers = {}
                for part in spec.split(","):
                    key, _, value = part.strip().partition("=")
                    tiers[key.strip()] = int(value)
                if set(tiers) != {"prefill", "decode"}:
                    raise ValueError(f"tiers {sorted(tiers)}")
        except (ValueError, TypeError) as e:
            raise ValueError(
                f"malformed POLYKEY_DISAGG spec {self.disagg!r}: expected "
                f"'PxD' or 'prefill=P,decode=D' ({e})"
            ) from None
        if tiers["prefill"] < 1 or tiers["decode"] < 1:
            raise ValueError(
                "POLYKEY_DISAGG needs >= 1 worker per tier, got "
                f"{self.disagg!r}"
            )
        return tiers["prefill"], tiers["decode"]

    def validate(self) -> None:
        if self.max_seq_len % self.page_size != 0:
            raise ValueError("max_seq_len must be a multiple of page_size")
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        for b in self.prefill_buckets:
            if b > self.max_seq_len:
                raise ValueError(
                    f"prefill bucket {b} exceeds max_seq_len {self.max_seq_len}"
                )
        if not self.prefill_buckets:
            raise ValueError("need at least one prefill bucket")
        if self.draft_model is not None and self.spec_gamma < 1:
            raise ValueError("spec_gamma must be >= 1")
        if self.prefix_cache_pages < 0:
            raise ValueError(
                "prefix_cache_pages must be >= 0 (0 → num_pages // 2); "
                "negative would silently disable the LRU cap"
            )
        if self.host_kv_bytes < 0:
            raise ValueError(
                "host_kv_bytes must be >= 0 (0 disables the host KV tier)"
            )
        if self.host_kv_bytes > 0 and not self.prefix_cache:
            raise ValueError(
                "host_kv_bytes > 0 requires prefix_cache: the host tier's "
                "only spill source is the prefix cache (from_env pairs "
                "them automatically)"
            )
        if self.host_kv_resident_pages < 0:
            raise ValueError(
                "host_kv_resident_pages must be >= 0 (0 → num_pages // 8)"
            )
        if self.host_kv_bytes > 0 and \
                self.host_kv_resident_pages >= self.num_pages - 1:
            raise ValueError(
                f"host_kv_resident_pages={self.host_kv_resident_pages} "
                f"must stay below the usable device pool "
                f"({self.num_pages - 1} pages): a floor the pool can "
                "never satisfy turns every retire into a full cache "
                "spill and every turn into wall-to-wall page faults"
            )
        if self.host_kv_restore_slots < 1:
            raise ValueError(
                "host_kv_restore_slots must be >= 1 (a restore budget of "
                "0 would wedge every faulting lane forever)"
            )
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 → max bucket)")
        if self.prefill_budget < 0:
            raise ValueError(
                "prefill_budget must be >= 0 (0 → 2 x prefill chunk)"
            )
        if self.decode_block_steps < 1:
            raise ValueError("decode_block_steps must be >= 1")
        if self.lookahead_blocks < 1:
            raise ValueError("lookahead_blocks must be >= 1")
        if self.timeline_capacity < 0:
            raise ValueError(
                "timeline_capacity must be >= 0 (0 disables the ring)"
            )
        if self.blackbox_every < 0:
            raise ValueError(
                "blackbox_every must be >= 0 (0 disables black boxes)"
            )
        if self.signals_interval_s < 0:
            raise ValueError(
                "signals_interval_s must be >= 0 (0 disables the plane)"
            )
        if self.quantize_bits not in (4, 8):
            raise ValueError("quantize_bits must be 4 or 8")
        if self.kv_dtype not in ("", "bfloat16", "float32", "int8"):
            raise ValueError(
                "kv_dtype must be '', bfloat16, float32, or int8; "
                f"got {self.kv_dtype!r}"
            )
        if self.top_p_candidates < 0:
            raise ValueError("top_p_candidates must be >= 0 (0 → exact)")
        if self.max_queue_depth < 0:
            raise ValueError("max_queue_depth must be >= 0 (0 → unbounded)")
        if self.max_engine_restarts < 0:
            raise ValueError("max_engine_restarts must be >= 0")
        if self.restart_window_s <= 0:
            raise ValueError("restart_window_s must be > 0")
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if self.replica < 0:
            raise ValueError("replica index must be >= 0")
        if self.max_reroutes < 0:
            raise ValueError("max_reroutes must be >= 0 (0 → no failover)")
        self.disagg_tiers()      # raises on a malformed spec
        if self.disagg and self.replicas > 1:
            raise ValueError(
                "POLYKEY_DISAGG and POLYKEY_REPLICAS>1 are mutually "
                "exclusive: the disaggregated tier replaces the "
                "in-process replica pool (each tier already scales by "
                "worker count)"
            )
        if self.disagg and self.draft_model is not None:
            raise ValueError(
                "disaggregated tiers have no speculative formulation yet "
                "(the KV handoff ships one pool; the draft pool would "
                "need its own) — unset POLYKEY_DISAGG or the draft model"
            )
        if self.disagg_tier not in ("", "prefill", "decode"):
            raise ValueError(
                f"disagg_tier must be '', 'prefill', or 'decode'; got "
                f"{self.disagg_tier!r}"
            )
        if self.disagg_heartbeat_s <= 0:
            raise ValueError("disagg_heartbeat_s must be > 0")
        if self.disagg_miss < 1:
            raise ValueError("disagg_miss must be >= 1")
        if self.disagg_recovery_wait_s < 0:
            raise ValueError("disagg_recovery_wait_s must be >= 0")
        if self.route_prefix_weight < 0 or self.route_delay_weight < 0:
            raise ValueError("routing weights must be >= 0")
        for name in ("tp", "dp", "ep", "sp", "pp", "num_slices"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")
        if self.sp > 1:
            chunk = self.prefill_chunk or max(self.prefill_buckets)
            for b in (*self.prefill_buckets, chunk):
                if b % self.sp != 0:
                    raise ValueError(
                        f"sp={self.sp} must divide every prefill bucket "
                        f"and the prefill chunk (got {b})"
                    )
        self._refuse_for_recurrent_state()
        self._refuse_for_latent_pool()
        self._refuse_for_looped_stack()

    def _served_model(self):
        """The ModelConfig this engine would serve, looked up when the
        config is validated (a name the registry does not know cannot be
        cleared by either refusal below and is refused itself)."""
        from ..models.config import get_config

        try:
            return get_config(self.model)
        except KeyError as e:
            raise ValueError(e.args[0]) from None

    def _refuse_for_looped_stack(self) -> None:
        """A looped stack (ModelConfig.loop_steps > 1) keeps one cache
        layer a layer AND pass: its pool is `kv_layers` = num_layers ×
        loop_steps deep. The plain prefill / decode pair, the prefix
        cache, int8 K/V, quantized weights and tp follow `kv_layers` or
        never count layers (tests/test_ouro.py holds each against the
        reference). What counts `num_layers` cache layers, or runs the
        stack once by construction, is refused here, in one place, rather
        than half-ported."""
        model = self._served_model()
        if model.loop_steps <= 1:
            return
        refused = {
            "pp > 1 (a pipeline stage runs its layers once and hands on: "
            "parallel/pipeline.py has no loop over the stages)": self.pp > 1,
            "host_kv_bytes (the host tier's pages are num_layers deep: "
            "kv_cache.HostKVPool)": self.host_kv_bytes > 0,
            "disagg / disagg_tier (the KV handoff ships and checks "
            "num_layers cache layers: kv_cache.KVHandoffState)":
                bool(self.disagg or self.disagg_tier),
            "draft_model (the speculative pair verifies one pass over "
            "num_layers-deep pools: engine/spec_decode.py)":
                self.draft_model is not None,
        }
        for what, on in refused.items():
            if on:
                raise ValueError(
                    f"{self.model} runs its {model.num_layers} layers "
                    f"{model.loop_steps} times a token over "
                    f"{model.kv_layers} cache layers "
                    "(ModelConfig.loop_steps); not supported with it: "
                    f"{what}"
                )

    def _refuse_for_latent_pool(self) -> None:
        """A latent-attention model (ModelConfig.latent_kv: "A" layers)
        keeps ONE row a token and layer in a one-part page pool
        (engine/kv_cache.py) and is served by the plain prefill / decode
        pair on one device, with or without the prefix cache (cached
        pages are pages, whatever their parts). Everything that copies a
        page out of the device pool, reads it in another form or splits
        it over devices knows two-part K/V pages only and is refused
        here, in one place, rather than half-ported."""
        model = self._served_model()
        if not model.latent_kv:
            return
        refused = {
            "host_kv_bytes (the host tier holds K and V pages with the "
            "heads apart)": self.host_kv_bytes > 0,
            "disagg / disagg_tier (the KV handoff's wire format ships K "
            "and V)": bool(self.disagg or self.disagg_tier),
            "draft_model (the speculative pair verifies over K/V pools)":
                self.draft_model is not None,
            "kv_dtype=int8 (no quantized form of a latent row)":
                self.kv_dtype == "int8",
            "quantize (no quantized weights for a layer pattern yet)":
                self.quantize,
            "tp/dp/ep/sp/pp/num_slices > 1 (a latent row cannot be split "
            "by heads, and its kernels run on one device)":
                max(self.tp, self.dp, self.ep, self.sp, self.pp,
                    self.num_slices) > 1,
        }
        for what, on in refused.items():
            if on:
                raise ValueError(
                    f"{self.model} keeps a latent pool (one "
                    f"{model.latent_width}-wide row a token and layer, "
                    "engine/kv_cache.py: a one-part page); not supported "
                    f"with it: {what}"
                )

    def _refuse_for_recurrent_state(self) -> None:
        """A model with per-slot recurrent state (ModelConfig.stateful:
        kv_cache.SlotState beside the pages — a mixer's h, a delta rule's
        S, a conv's last columns) is served by the plain prefill / decode
        pair on one device and by nothing else yet. Every
        feature that moves, shares or rebuilds a slot's K/V pages would
        have to move, share or rebuild that state with them, and none
        does: each is refused here, in one place, rather than half-ported.
        The model is looked up when the engine validates its config
        (InferenceEngine.__init__), so a ModelConfig registered after this
        EngineConfig was built is seen; a name the registry does not know
        cannot be cleared and is refused too."""
        model = self._served_model()
        if not model.stateful:
            return
        refused = {
            "prefix_cache (cached pages carry no recurrent state to resume "
            "from)": self.prefix_cache,
            "host_kv_bytes (the host tier spills pages, not state)":
                self.host_kv_bytes > 0,
            "disagg / disagg_tier (the KV handoff ships pages, not state)":
                bool(self.disagg or self.disagg_tier),
            "draft_model (a rejected draft token cannot be taken back out "
            "of the state)": self.draft_model is not None,
            "kv_dtype=int8 (no quantized pool path for a layer pattern)":
                self.kv_dtype == "int8",
            "quantize (no quantized weights for a layer pattern yet)":
                self.quantize,
            "tp/dp/ep/sp/pp/num_slices > 1 (the state is not sharded)":
                max(self.tp, self.dp, self.ep, self.sp, self.pp,
                    self.num_slices) > 1,
        }
        for what, on in refused.items():
            if on:
                raise ValueError(
                    f"{self.model} keeps per-slot recurrent state "
                    f"({model.state_held}, engine/kv_cache.py SlotState) "
                    f"beside its KV pages; not supported with it: {what}"
                )
