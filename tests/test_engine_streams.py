"""Stream identity: the engine's one prefill path (bucketed group prefill
+ chunk dispatch) and its one decode path (the K-step block, or the spec
round) held to a reference that shares none of their machinery.

The reference is models/generate.py: the contiguous cache, one request at
a time, no pages, no buckets, no batching, no lookahead. Greedy streams
are compared with `generate()` itself. A sampled stream is compared with
generate.py's `prefill` / `decode_step` logits drawn under the engine's
documented sampling contract — every draw keyed by fold_in(seed key, the
position the token lands at) — because `generate()` splits one key per
step and cannot reproduce a per-request keyed stream.

The traffic mixes are the ones tests/test_ragged.py compared ragged with
bucketed streams over (ISSUE 35 deleted that path): greedy at lookahead
depth 1 and 2, sampled, a prefill-only cold burst, a prompt whose chunks
the prefill budget clips, decode-only iterations between admissions, a
replica-pool resume, a supervisor restart mid-stream, the host-KV tier
under a sticky mix, a speculative engine at depth 1 and 2 — each over
prompt lengths on both sides of every bucket and chunk edge, in two
geometries: buckets 16/32 and the registry default 128/512.

The cover (ISSUE 41) — a prompt prefilled as several windows of a
narrower bucket, consecutive rows of one group dispatch — has cases of
its own at its edges, in a third geometry (buckets 16/64) where a small
prompt splits, and in 16/32 where none does (two 16-windows are the 32
bucket's rows).

Every case runs its traffic TWICE on fresh engines in one process and
holds both passes to the reference: the second pass finds every
executable already compiled, which is the condition under which the
bucketed stream was once seen to gain a token (CHANGES.md PR 21; the
suite's conftest drops compiled executables between modules, so a case
cannot count on another file having warmed its shapes).
"""

import dataclasses
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu import faults
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.sampling import SamplingParams, sample_tail
from polykey_tpu.models.generate import decode_step, generate, prefill
from polykey_tpu.models.transformer import init_cache


@pytest.fixture(autouse=True)
def _clean_injector(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.clear()
    yield
    faults.clear()


# -- geometries ---------------------------------------------------------------

_COMMON = dict(
    model="tiny-llama", tokenizer="byte", dtype="float32",
    max_decode_slots=4, decode_block_steps=4, lookahead_blocks=2,
    compile_warmup=False, supervise=False, signals_interval_s=0,
)


@dataclasses.dataclass(frozen=True)
class Geometry:
    config: EngineConfig
    # Prompt lengths in TOKENS (BOS + bytes), both sides of every bucket
    # edge and one beyond the chunk (= the largest bucket).
    edges: tuple
    max_new: int
    # The clipped-chunk scenario: budget = chunk = the small bucket, and
    # a prompt of several such chunks.
    clip: int
    clipped_prompt: int

    @property
    def pad(self) -> int:
        return self.config.max_seq_len


GEOMETRIES = {
    "b16-32": Geometry(
        EngineConfig(
            **_COMMON, page_size=8, num_pages=64, max_seq_len=64,
            prefill_buckets=(16, 32), max_new_tokens_cap=16,
        ),
        edges=(1, 15, 16, 17, 31, 32, 33, 41), max_new=8,
        clip=16, clipped_prompt=57,
    ),
    "b128-512": Geometry(
        EngineConfig(
            **_COMMON, page_size=16, num_pages=208, max_seq_len=768,
            prefill_buckets=(128, 512), max_new_tokens_cap=16,
        ),
        edges=(1, 127, 128, 129, 511, 512, 513, 700), max_new=8,
        clip=128, clipped_prompt=700,
    ),
}
GEOMETRY_IDS = tuple(GEOMETRIES)

# The cover's cases run in these: 16/64 splits a 17..32-token prompt over
# two 16-windows (as 128/512 does 129..256), 16/32 never splits.
COVER_GEOMETRIES = {
    **GEOMETRIES,
    "b16-64": Geometry(
        EngineConfig(
            **_COMMON, page_size=8, num_pages=96, max_seq_len=128,
            prefill_buckets=(16, 64), max_new_tokens_cap=16,
        ),
        edges=(1, 15, 16, 17, 63, 64, 65, 90), max_new=8,
        clip=16, clipped_prompt=90,
    ),
}
COVER_GEOMETRY_IDS = tuple(COVER_GEOMETRIES)

_REF_NEW = 16       # one compiled generate() per geometry; streams are prefixes


def _prompt(n_tokens: int, salt: int = 0) -> str:
    """A prompt of exactly `n_tokens` tokens (the byte tokenizer adds BOS)."""
    return "".join(
        chr(97 + (i * 7 + n_tokens + salt) % 26) for i in range(n_tokens - 1)
    )


# -- the reference ------------------------------------------------------------


def _seed_row(seed: int) -> np.ndarray:
    s = seed & 0xFFFFFFFFFFFFFFFF
    return np.array(
        [(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], np.uint32
    ).view(np.int32)


def reference_stream(params, model_cfg, tokenizer, geometry, spec) -> list:
    """What models/generate.py emits for one request, alone."""
    ids = tokenizer.encode(spec["prompt"])
    n, max_new = len(ids), spec["max_new_tokens"]
    assert max_new <= _REF_NEW
    tokens = np.zeros((1, geometry.pad), np.int32)
    tokens[0, :n] = ids
    seq_lens = jnp.asarray([n], jnp.int32)
    max_len = geometry.pad + _REF_NEW
    temperature = spec.get("temperature", 0.0)
    if temperature == 0.0:
        generated, _ = generate(
            params, model_cfg, jnp.asarray(tokens), seq_lens,
            jax.random.PRNGKey(0), SamplingParams(max_new_tokens=_REF_NEW),
            max_len, tokenizer.eos_id,
        )
        return [int(t) for t in np.asarray(generated)[0, :max_new]]
    seeds = jnp.asarray(_seed_row(spec["seed"])[None])
    temp = jnp.asarray([temperature], jnp.float32)
    top_p = jnp.asarray([spec.get("top_p", 1.0)], jnp.float32)
    top_k = jnp.asarray([spec.get("top_k", 0)], jnp.int32)
    cache = init_cache(model_cfg, 1, max_len, jnp.float32)
    logits, cache = prefill(
        params, model_cfg, jnp.asarray(tokens), seq_lens, cache
    )
    out = []
    for step in range(max_new):
        position = jnp.asarray([n + step], jnp.int32)
        token = sample_tail(
            logits, seeds, position, temp, top_p, top_k, greedy=False
        )
        out.append(int(token[0]))
        if step + 1 < max_new:
            logits, cache = decode_step(
                params, model_cfg, token, position, cache
            )
    return out


def assert_streams_match(engine, geometry, specs, streams, label=""):
    params = jax.device_get(engine.params)
    for i, (spec, got) in enumerate(zip(specs, streams)):
        want = reference_stream(
            params, engine.model_cfg, engine.tokenizer, geometry, spec
        )
        n = len(engine.tokenizer.encode(spec["prompt"]))
        assert got == want, (
            f"{label} request {i} ({n} prompt tokens, "
            f"temperature {spec.get('temperature', 0.0)}): "
            f"engine {got} != generate {want}"
        )


# -- driving an engine --------------------------------------------------------


class _Reader:
    """Drains requests' queues incrementally, so a plan can wait for one
    stream to have made progress before it sends the next request."""

    def __init__(self, timeout: float = 120.0):
        self.deadline = time.monotonic() + timeout
        self.tokens: dict = {}
        self.ended: dict = {}

    def track(self, request) -> None:
        self.tokens[id(request)] = []

    def pump(self, request, until_tokens=None) -> None:
        got = self.tokens[id(request)]
        while id(request) not in self.ended:
            if until_tokens is not None and len(got) >= until_tokens:
                return
            try:
                kind, value = request.out.get(
                    timeout=max(0.01, self.deadline - time.monotonic())
                )
            except queue.Empty:
                raise AssertionError(
                    f"stream stalled after {len(got)} tokens"
                ) from None
            if kind == "token":
                got.append(value)
            else:
                self.ended[id(request)] = (kind, value)

    def finish(self, request) -> list:
        self.pump(request)
        kind, value = self.ended[id(request)]
        assert kind == "done", value
        return self.tokens[id(request)]


def _request(spec: dict) -> GenRequest:
    return GenRequest(**{k: v for k, v in spec.items() if k != "after"})


def drive(engine, specs) -> list:
    """Submit `specs` in order; a spec with `after=(i, n)` is sent only
    once request i has streamed n tokens (decode-only iterations lie
    between the two admissions). Returns every request's token list."""
    reader = _Reader()
    requests = []
    for spec in specs:
        if "after" in spec:
            i, n = spec["after"]
            reader.pump(requests[i], until_tokens=n)
        request = _request(spec)
        reader.track(request)
        requests.append(request)
        engine.submit(request)
    return [reader.finish(r) for r in requests]


def serve_twice(config, geometry, specs, check=None):
    """The traffic on a fresh engine, then again on another one that
    finds every executable compiled; both held to the reference."""
    for label in ("first pass", "warmed pass"):
        engine = InferenceEngine(config)
        try:
            streams = drive(engine, specs)
            stats = engine.stats()
            assert_streams_match(engine, geometry, specs, streams, label)
        finally:
            engine.shutdown()
        if check is not None:
            check(stats)


# -- one prompt length at a time, beside a decoding neighbour ------------------


@pytest.mark.parametrize("geometry_id,index", [
    (g, i) for g in GEOMETRY_IDS for i in range(8)
])
def test_prompt_length_edge_matches_generate(geometry_id, index):
    """One prompt on each side of every bucket and chunk edge, greedy and
    sampled, admitted while a neighbour is already decoding."""
    geometry = GEOMETRIES[geometry_id]
    n = geometry.edges[index]
    specs = [
        dict(prompt="neighbour", max_new_tokens=16),
        dict(prompt=_prompt(n), max_new_tokens=geometry.max_new,
             after=(0, 2)),
        dict(prompt=_prompt(n, salt=3), max_new_tokens=geometry.max_new,
             temperature=0.9, top_p=0.8, top_k=5, seed=42 + n),
    ]
    serve_twice(geometry.config, geometry, specs)


# -- the traffic mixes --------------------------------------------------------


def _edge_specs(geometry, picks, **kw):
    return [
        dict(prompt=_prompt(geometry.edges[i], salt=i),
             max_new_tokens=geometry.max_new, **kw)
        for i in picks
    ]


def _mix_greedy(geometry):
    # Short admissions, both sides of the large bucket, a chunked prompt.
    return geometry.config, _edge_specs(geometry, (0, 2, 7, 3, 5, 6), seed=11)


def _mix_sampled(geometry):
    specs = _edge_specs(geometry, (1, 4, 6), temperature=0.9, top_p=0.8,
                        top_k=5, seed=42)
    specs += _edge_specs(geometry, (7, 2), temperature=1.0, seed=7)
    return geometry.config, specs


def _mix_cold_burst(geometry):
    # Every slot filled from idle: prefill-only iterations, one group of
    # four same-bucket prompts (lengths differ inside the bucket).
    n = geometry.edges[4]
    specs = [
        dict(prompt=_prompt(n - 2 * i, salt=i), max_new_tokens=4, seed=3)
        for i in range(4)
    ]
    return geometry.config, specs


def _mix_clipped_chunk(geometry):
    # Budget = chunk = the small bucket while another lane decodes: the
    # long prompt advances one clipped chunk per iteration, its last
    # chunk partial.
    config = dataclasses.replace(
        geometry.config, prefill_budget=geometry.clip,
        prefill_chunk=geometry.clip,
    )
    specs = [
        dict(prompt="warm", max_new_tokens=12, seed=9),
        dict(prompt=_prompt(geometry.clipped_prompt), max_new_tokens=6,
             seed=9),
    ]
    return config, specs


def _mix_staggered(geometry):
    # Decode-only iterations between admissions: each request is sent
    # after the one before it has streamed a few tokens.
    specs = _edge_specs(geometry, (3, 5, 0, 7))
    for i in range(1, len(specs)):
        specs[i]["after"] = (i - 1, 3)
    specs[0]["max_new_tokens"] = 16
    return geometry.config, specs


MIXES = {
    "greedy": _mix_greedy, "sampled": _mix_sampled,
    "cold-burst": _mix_cold_burst, "clipped-chunk": _mix_clipped_chunk,
    "staggered": _mix_staggered,
}


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("geometry_id", GEOMETRY_IDS)
@pytest.mark.parametrize("mix", ["greedy", "sampled"])
def test_mix_at_lookahead_depth_matches_generate(mix, geometry_id, depth,
                                                 monkeypatch):
    monkeypatch.setenv("POLYKEY_DISPATCH_LOOKAHEAD", str(depth))
    geometry = GEOMETRIES[geometry_id]
    config, specs = MIXES[mix](geometry)
    serve_twice(config, geometry, specs)


@pytest.mark.parametrize("geometry_id", GEOMETRY_IDS)
@pytest.mark.parametrize("mix", ["cold-burst", "clipped-chunk", "staggered"])
def test_mix_matches_generate(mix, geometry_id):
    geometry = GEOMETRIES[geometry_id]
    config, specs = MIXES[mix](geometry)

    def check(stats):
        if mix == "clipped-chunk":
            assert stats["prefill_tokens_total"] >= geometry.clipped_prompt
        if mix == "staggered":
            # K-step blocks served the decode-only iterations.
            assert stats["steps_dispatched"] > stats["blocks_dispatched"]

    serve_twice(config, geometry, specs, check)


# -- the cover: a prompt over several windows of one dispatch ------------------


def _splits(geometry, n: int) -> bool:
    """Whether `n` tokens to prefill take two windows of the small bucket:
    more than one, no more than two, and two of them fewer rows than the
    wide bucket (stated here from the geometry, not from the engine)."""
    small, wide = geometry.config.prefill_buckets
    return small < n <= 2 * small < wide


def _cover_edge(geometry, n):
    # One prompt length beside a decoding neighbour, greedy and sampled.
    specs = [
        dict(prompt="neighbour", max_new_tokens=16),
        dict(prompt=_prompt(n), max_new_tokens=geometry.max_new,
             after=(0, 2)),
        dict(prompt=_prompt(n, salt=3), max_new_tokens=geometry.max_new,
             temperature=0.9, top_p=0.8, top_k=5, seed=42 + n),
    ]
    return geometry.config, specs, dict(split=2 * _splits(geometry, n))


def _cover_edges():
    def at(pick):
        def build(geometry):
            small = geometry.config.prefill_buckets[0]
            return _cover_edge(geometry, pick(small))
        return build

    return {
        "b": at(lambda b: b), "b+1": at(lambda b: b + 1),
        "mid": at(lambda b: b + b // 2), "2b": at(lambda b: 2 * b),
        "2b+1": at(lambda b: 2 * b + 1),
    }


def _cover_two_split(geometry):
    # Two prompts that both split, sent together from idle: their four
    # windows are one group's rows when one admission takes both.
    small = geometry.config.prefill_buckets[0]
    lengths = (small + 5, 2 * small - 3)
    specs = [
        dict(prompt=_prompt(n, salt=i), max_new_tokens=geometry.max_new)
        for i, n in enumerate(lengths)
    ]
    split = sum(_splits(geometry, n) for n in lengths)
    return geometry.config, specs, dict(
        split=split, windows=2 + split if split else None,
    )


def _cover_split_beside_unsplit(geometry):
    # One group holds a split prompt's two rows and an unsplit one's row;
    # the wide bucket's group goes out beside it.
    small = geometry.config.prefill_buckets[0]
    lengths = (small + 3, small - 2, 2 * small + 1)
    specs = [
        dict(prompt=_prompt(n, salt=i), max_new_tokens=geometry.max_new,
             **(dict(temperature=0.9, top_k=5, seed=5) if i == 1 else {}))
        for i, n in enumerate(lengths)
    ]
    return geometry.config, specs, dict(
        split=sum(_splits(geometry, n) for n in lengths),
    )


def _letters(n: int, first: int) -> str:
    return "".join(chr(first + (i * 7) % 26) for i in range(n))


def _cover_prefix_suffix(geometry):
    # The second prompt shares a cached page-aligned prefix with the
    # first: only its suffix prefills, from the offset, and that suffix
    # is itself covered by two small windows where the geometry splits.
    base = geometry.config
    small = base.prefill_buckets[0]
    prefix = small + 2 * base.page_size             # tokens, whole pages
    first = _letters(prefix + 5, 97)                # prefix + 6 tokens
    second = first[:prefix] + _letters(small + 3, 65)
    suffix = small + 4                              # = tokens - prefix
    config = dataclasses.replace(base, prefix_cache=True)
    specs = [
        dict(prompt=first, max_new_tokens=geometry.max_new),
        dict(prompt=second, max_new_tokens=geometry.max_new, after=(0, 1)),
    ]
    return config, specs, dict(
        split=_splits(geometry, prefix + 6) + _splits(geometry, suffix),
        prefix_hit_tokens=prefix,
    )


def _cover_spec(geometry):
    # A speculative engine's group dispatch prefills both pools per row.
    small = geometry.config.prefill_buckets[0]
    lengths = (small + 1, small + small // 2, 2 * small)
    config = dataclasses.replace(
        geometry.config, draft_model="tiny-llama", spec_gamma=3,
    )
    specs = [
        dict(prompt=_prompt(n, salt=i), max_new_tokens=geometry.max_new,
             seed=11)
        for i, n in enumerate(lengths)
    ]
    return config, specs, dict(
        split=sum(_splits(geometry, n) for n in lengths), drafts=True,
    )


def _cover_long_tail(geometry):
    # Long prompts, one at a time: the chunk-wide window, then the tail
    # in the small window — and a tail that is itself split.
    small, wide = geometry.config.prefill_buckets
    tails = (small - 3, small + 5)
    specs = [
        dict(prompt=_prompt(wide + tail, salt=i),
             max_new_tokens=geometry.max_new)
        for i, tail in enumerate(tails)
    ]
    specs[1]["after"] = (0, geometry.max_new)
    rows = sum(
        wide + (2 * small if _splits(geometry, tail)
                else small if tail <= small else wide)
        for tail in tails
    )
    return geometry.config, specs, dict(
        split=sum(_splits(geometry, tail) for tail in tails), rows=rows,
    )


COVER_CASES = {
    **_cover_edges(),
    "two-split": _cover_two_split,
    "split-beside-unsplit": _cover_split_beside_unsplit,
    "prefix-suffix": _cover_prefix_suffix,
    "spec": _cover_spec,
    "long-tail": _cover_long_tail,
}


@pytest.mark.parametrize("geometry_id", COVER_GEOMETRY_IDS)
@pytest.mark.parametrize("case", tuple(COVER_CASES))
def test_cover_matches_generate(case, geometry_id):
    """A prompt covered by several windows of one group dispatch streams
    what generate() streams, and the counters say how it was covered."""
    geometry = COVER_GEOMETRIES[geometry_id]
    config, specs, want = COVER_CASES[case](geometry)

    def check(stats):
        assert stats["prefill_prompts_split"] == want["split"]
        assert stats["prefill_windows_dispatched"] >= \
            len(specs) + want["split"]
        if want.get("windows") is not None:
            assert stats["prefill_windows_dispatched"] == want["windows"]
        if want.get("rows") is not None:
            assert stats["prefill_rows_dispatched"] == want["rows"]
        if "prefix_hit_tokens" in want:
            assert stats["prefix_hit_tokens"] == want["prefix_hit_tokens"]
        if want.get("drafts"):
            assert stats["drafts_proposed"] > 0

    serve_twice(config, geometry, specs, check)


# -- speculative engine -------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("geometry_id", GEOMETRY_IDS)
def test_spec_engine_greedy_matches_generate(geometry_id, depth, monkeypatch):
    """A speculative engine's greedy stream is the TARGET's greedy chain
    for any draft (the draft here is another seed's random model, so
    nearly every proposal is rejected and corrected)."""
    monkeypatch.setenv("POLYKEY_DISPATCH_LOOKAHEAD", str(depth))
    geometry = GEOMETRIES[geometry_id]
    config = dataclasses.replace(
        geometry.config, draft_model="tiny-llama", spec_gamma=3,
    )
    specs = _edge_specs(geometry, (0, 2, 7, 3, 5), seed=11)

    def check(stats):
        assert stats["drafts_proposed"] > 0

    serve_twice(config, geometry, specs, check)


# -- host-KV tier -------------------------------------------------------------


@pytest.mark.parametrize("geometry_id", GEOMETRY_IDS)
def test_host_kv_sticky_mix_matches_generate(geometry_id):
    """Sticky sessions over a device pool too small to keep them: later
    turns fault spilled prefix pages back in and prefill only a suffix."""
    geometry = GEOMETRIES[geometry_id]
    base = geometry.config
    n = geometry.edges[5] + base.page_size + 3      # past the large bucket
    pages_per_session = -(-(n + geometry.max_new) // base.page_size)
    config = dataclasses.replace(
        base, prefix_cache=True, host_kv_bytes=64 << 20,
        # Room for every session's chain across both tiers: the revisit
        # must find its pages spilled to the host, not dropped.
        prefix_cache_pages=4 * pages_per_session,
        prefill_chunk=geometry.clip,
        num_pages=3 * pages_per_session + 3,
        host_kv_resident_pages=(3 * pages_per_session + 3) // 2,
    )
    sessions = [_prompt(n, salt=s) for s in range(4)]
    order = (0, 1, 2, 3, 0, 2, 1, 3)
    specs = [
        dict(prompt=sessions[s], max_new_tokens=geometry.max_new)
        for s in order
    ]
    for i in range(1, len(specs)):              # one turn at a time
        specs[i]["after"] = (i - 1, geometry.max_new)

    def check(stats):
        assert stats["kv_pages_restored"] > 0

    serve_twice(config, geometry, specs, check)


# -- chaos: a killed replica, a restarted engine -------------------------------


def _chaos_config(geometry, **kw):
    return dataclasses.replace(
        geometry.config, max_decode_slots=2, decode_block_steps=1,
        adaptive_block=False, lookahead_blocks=1, compile_warmup=True,
        warm_sampled_variants=False, watchdog_timeout_s=0.3,
        max_queue_depth=0, supervise=True, **kw,
    )


@pytest.mark.parametrize("geometry_id", GEOMETRY_IDS)
def test_pool_resume_matches_generate(geometry_id):
    """A replica wedged mid-stream: the stream resumes on the survivor and
    head + tail is still what generate() emits for the prompt."""
    from polykey_tpu.engine.replica_pool import ReplicaPool

    geometry = GEOMETRIES[geometry_id]
    config = _chaos_config(geometry, replicas=2)
    spec = dict(prompt=_prompt(geometry.edges[3], salt=5), max_new_tokens=12)
    pool = ReplicaPool.create(
        config, watchdog_interval_s=0.05, supervisor_interval_s=0.05,
    )
    try:
        engine = pool.replicas[0].engine
        want = reference_stream(
            jax.device_get(engine.params), engine.model_cfg,
            engine.tokenizer, geometry, spec,
        )
        # Pace replica 0, let a few tokens flow, then wedge it.
        engine._faults = faults.install("slow-step=0.1:replica=0")
        victim = GenRequest(**spec)
        pool.submit(victim)
        assert victim.replica == 0
        reader = _Reader()
        reader.track(victim)
        reader.pump(victim, until_tokens=3)
        engine._faults = faults.install(
            "slow-step=0.1:replica=0,step-stall=1.0@1:replica=0"
        )
        assert reader.finish(victim) == want
        assert pool.stats()["streams_resumed"] >= 1
    finally:
        pool.shutdown()


@pytest.mark.parametrize("spec_engine", [False, True],
                         ids=["plain", "spec"])
@pytest.mark.parametrize("geometry_id", GEOMETRY_IDS)
def test_supervisor_restart_mid_stream_matches_generate(geometry_id,
                                                        spec_engine):
    """An engine wedged mid-stream fails its request cleanly — what it
    had streamed is a prefix of generate()'s stream — and the engine the
    supervisor swaps in serves the reference's streams."""
    from polykey_tpu.engine.supervisor import EngineSupervisor
    from polykey_tpu.engine.watchdog import Watchdog
    from polykey_tpu.gateway.health import SERVING, HealthService

    geometry = GEOMETRIES[geometry_id]
    extra = (
        dict(draft_model="tiny-llama", spec_gamma=2) if spec_engine else {}
    )
    config = _chaos_config(geometry, **extra)
    victim_spec = dict(
        prompt=_prompt(geometry.edges[7], salt=1), max_new_tokens=12, seed=11,
    )
    engine = InferenceEngine(config)
    health = HealthService()
    health.set_serving_status("", SERVING)
    watchdog = Watchdog(engine, health=health, check_interval_s=0.05)
    watchdog.start()
    supervisor = EngineSupervisor(
        engine, lambda: InferenceEngine(config),
        watchdog=watchdog, health=health,
        max_restarts=2, restart_window_s=60.0,
        check_interval_s=0.05, join_timeout_s=5.0,
    )
    # The rebuild compiles a warmed engine while the suite's other
    # workers compile theirs: the test waits for the supervisor to say
    # it swapped (or gave up), not for a number of seconds.
    settled = threading.Event()
    supervisor.add_restart_listener(lambda fresh: settled.set())
    supervisor.add_giveup_listener(lambda reason: settled.set())
    supervisor.start()
    try:
        want = reference_stream(
            jax.device_get(engine.params), engine.model_cfg,
            engine.tokenizer, geometry, victim_spec,
        )
        engine._faults = faults.install("slow-step=0.1")
        victim = GenRequest(**victim_spec)
        engine.submit(victim)
        reader = _Reader()
        reader.track(victim)
        reader.pump(victim, until_tokens=2)
        engine._faults = faults.install("slow-step=0.1,step-stall=1.0@1")
        reader.pump(victim)
        kind, _ = reader.ended[id(victim)]
        head = reader.tokens[id(victim)]
        assert kind == "error"
        assert 2 <= len(head) < 12 and head == want[:len(head)]
        assert settled.wait(timeout=120.0)      # a hang's guard, as _Reader's
        assert supervisor.restarts == 1 and not supervisor.gave_up
        faults.clear()
        fresh = supervisor.engine
        assert fresh is not engine
        fresh._faults = None
        specs = [victim_spec] + _edge_specs(geometry, (0, 4), seed=11)
        streams = drive(fresh, specs)
        assert_streams_match(fresh, geometry, specs, streams, "restarted")
        if spec_engine:
            assert fresh.metrics.snapshot()["drafts_proposed"] > 0
    finally:
        supervisor.stop()
        watchdog.stop()
        supervisor.engine.shutdown()


# -- what the witness found: uploaded slot state aliased the host mirrors ------


def _aligned_like(array: np.ndarray) -> np.ndarray:
    """A copy of `array` whose buffer starts on a 64-byte boundary — where
    numpy's allocator puts a small array by chance, process by process."""
    raw = np.zeros(array.nbytes + 64, np.uint8)
    offset = -raw.ctypes.data % 64
    out = raw[offset:offset + array.nbytes].view(array.dtype)
    out = out.reshape(array.shape)
    out[...] = array
    return out


@pytest.mark.parametrize("model,draft", [
    ("tiny-llama", None), ("tiny-llama", "tiny-llama"), ("tiny-pangu", None),
], ids=["plain", "spec", "latent"])
def test_uploaded_slot_state_does_not_alias_host_mirrors(model, draft):
    """On the CPU backend `jax.device_put` of a 64-byte-aligned numpy
    array is zero-copy. The slot state the engine uploads must therefore
    not BE its host mirrors: a dispatch already issued would read a later
    merge's mirror writes (`_active[i] = True` beside a sequence length
    still 0) and its lane would emit one token of garbage — the stream
    that gained a token in a warmed process (CHANGES.md PR 21, PR 35).
    A latent-attention model's slots are the same slots: its pool is one
    part a page, its tables, lengths and lanes what every model's are."""
    engine = InferenceEngine(dataclasses.replace(
        GEOMETRIES["b16-32"].config, model=model, draft_model=draft,
    ))
    engine.shutdown()                   # the loop has ended: driven by hand
    mirrors = {
        "last_tokens": "_last_tokens", "seq_lens": "_seq_lens",
        "page_tables": "_page_tables", "active": "_active", "caps": "_caps",
        "temperature": "_temperature", "top_p": "_top_p", "top_k": "_top_k",
        "seeds": "_seeds",
    }
    if draft is not None:
        mirrors.update(accept_ewma="_lane_ewma", gamma_lane="_lane_gamma")
    for attr in mirrors.values():
        setattr(engine, attr, _aligned_like(getattr(engine, attr)))
    engine._upload_slot_state()
    assert set(engine._dev) == set(mirrors)
    before = {k: np.array(v) for k, v in engine._dev.items()}
    for attr in mirrors.values():       # what a merge does, after the upload
        mirror = getattr(engine, attr)
        mirror[...] = np.ones_like(mirror) if mirror.dtype == bool \
            else mirror + 3
    for key, value in engine._dev.items():
        assert np.array_equal(np.asarray(value), before[key]), key


# -- structure: one prefill path, one decode path ------------------------------


def test_no_ragged_dispatch_option_switch_or_phase():
    """The flat-stream dispatch fork (ISSUE 35) left no option, no
    environment switch and no phase name behind."""
    import re
    from pathlib import Path

    from polykey_tpu.obs.timeline import PHASES

    root = Path(__file__).resolve().parents[1]
    assert "ragged_dispatch" not in {
        f.name for f in dataclasses.fields(EngineConfig)
    }
    switch = re.compile(r"POLYKEY_(DISABLE_)?RAGGED")
    sources = list((root / "polykey_tpu").rglob("*.py")) + [root / "DEPLOY.md"]
    named = [
        str(path.relative_to(root)) for path in sources
        if switch.search(path.read_text(encoding="utf-8"))
    ]
    assert named == []
    assert [name for name in PHASES if name.startswith("ragged")] == []


@pytest.mark.parametrize("draft", [None, "tiny-llama"], ids=["plain", "spec"])
def test_warmed_engine_holds_exactly_these_jit_handles(draft):
    want = {
        "_jit_decode", "_jit_prefill", "_jit_merge", "_jit_retire",
        "_jit_kv_gather", "_jit_kv_restore",
    }
    if draft is not None:
        want |= {"_jit_spec_prefill", "_jit_spec_decode"}
    config = dataclasses.replace(
        GEOMETRIES["b16-32"].config, compile_warmup=True,
        warm_sampled_variants=False, draft_model=draft,
    )
    engine = InferenceEngine(config)
    try:
        handles = {
            name for name, value in vars(engine).items()
            if name.startswith("_jit_") and value is not None
        }
        assert handles == want
    finally:
        engine.shutdown()
