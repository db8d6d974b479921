"""The stored KV layout (engine/kv_cache.py): pools [L, N, page_size, Hk·D],
kept whole in the layer scan's carry and addressed by (layer, page) in place.

Three things are held here.

- STRUCTURE, a count from the compiled decode step: no instruction other than
  the pool parameters, the loops' carries, bitcasts and the in-place writes
  (the kernels' aliased outputs on TPU, the scatters elsewhere) has a result
  of one layer's pool bytes or more, and the pools' outputs alias their
  donated inputs. Before ISSUE 34 the step sliced a layer's pool out, folded
  it, and wrote it back: 8 such instructions in the step compiled for a v5e
  (74 % of the Mistral-7B step on the chip), 4 in the CPU's. This is the
  guard the next architecture's PR runs into first.
- PARITY: paged prefill + decode equal the non-paged forward through every
  path that addresses the layout (XLA gather/scatter, the Pallas kernels in
  interpret mode, int8 KV, tp = 2).
- BOUNDARY: the host tier and the handoff wire format keep [..., Hk, D];
  pages cross that boundary byte for byte, and a blob written by the tree
  BEFORE the fold (tests/data/kv_handoff_parent_pr30.pkkv) restores here.
"""

import json
import os
import re
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine import engine as engine_mod
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.kv_cache import (
    HostKVPool,
    KVHandoffState,
    deserialize_kv_state,
    fold_heads,
    init_paged_kv,
    serialize_kv_state,
    unfold_heads,
)
from polykey_tpu.models.config import TINY_LLAMA, get_config
from polykey_tpu.models.transformer import (
    forward,
    forward_paged,
    init_params,
)
from polykey_tpu.parallel.mesh import MeshConfig, create_mesh
from polykey_tpu.parallel.sharding import paged_kv_sharding, param_shardings

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# -- structure ----------------------------------------------------------------

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_ITEMSIZE) + r")\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
# What may hold a pool: where it comes in, how a loop or a branch carries it,
# a view of it, and the write that updates it in place.
_HOLDERS = {"parameter", "get-tuple-element", "tuple", "while", "conditional",
            "call", "bitcast", "scatter", "custom-call", "optimization-barrier"}


def _largest(result_type: str) -> int:
    sizes = [
        _ITEMSIZE[dt] * int(np.prod([int(d) for d in dims.split(",") if d]))
        for dt, dims in _ARRAY.findall(result_type)
    ]
    return max(sizes, default=0)


def pool_sized_instructions(hlo: str, layer_bytes: int) -> list[str]:
    """Instructions of a compiled module that MATERIALISE an array of one
    layer's pool bytes or more and are not one of `_HOLDERS`. A fusion is
    judged by what its computation returns (an in-place scatter of the pool
    is the XLA paths' write); instructions inside a fused computation
    materialise nothing themselves."""
    computations: dict[str, list[tuple[str, str, str, str]]] = {}
    current = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = computations.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            current.append((m.group(1), m.group(2), m.group(3), line))
    fused = set(re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", hlo))

    def root_op(computation: str) -> str:
        for name, _, op, line in computations.get(computation, []):
            if line.lstrip().startswith("ROOT"):
                return op
        return ""

    found = []
    for comp, instructions in computations.items():
        if comp in fused:
            continue
        for name, result_type, op, line in instructions:
            if _largest(result_type) < layer_bytes or op in _HOLDERS:
                continue
            if op == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                if root_op(called) == "scatter":
                    continue
            found.append(f"{op} {result_type} %{name}")
    return found


def aliased_pool_parameters(hlo: str, pool_shape: str) -> int:
    """How many entry parameters of the pool's per-device shape the module's
    input_output_alias map hands to an output."""
    header = hlo.split("\n", 1)[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), ", header)}
    entry = hlo[hlo.index("ENTRY"):]
    count = 0
    for m in re.finditer(
        r"= (\S+?)(?:\{[^}]*\})? parameter\((\d+)\)", entry
    ):
        if m.group(1) == pool_shape and int(m.group(2)) in aliased:
            count += 1
    return count


def _engine_decode_hlo(model: str, tp: int) -> tuple[str, int, str]:
    """The engine's own decode step, compiled for the CPU at toy geometry
    with a pool larger than any weight."""
    cfg = EngineConfig(
        model=model, dtype="float32", max_decode_slots=4, page_size=8,
        num_pages=512, max_seq_len=64, prefill_buckets=(16,),
        decode_block_steps=2, adaptive_block=False, supervise=False, tp=tp,
    )
    engine = InferenceEngine(cfg, seed=0)
    try:
        if engine._dev_dirty or not engine._dev:
            engine._upload_slot_state()
        dev = engine._dev
        compiled = engine._jit_decode.lower(
            engine.params, engine.model_cfg, engine.paged,
            dev["last_tokens"], dev["seq_lens"], dev["page_tables"],
            dev["active"], dev["caps"], dev["seeds"], dev["temperature"],
            dev["top_p"], dev["top_k"],
            greedy=True, steps=engine._block_steps,
            eos_id=engine.tokenizer.eos_id,
            candidates=cfg.top_p_candidates, mesh=engine.mesh,
        ).compile()
        L, N, ps, folded = engine.paged.k.shape
        shape = f"f32[{L},{N},{ps},{folded // tp}]"
        return compiled.as_text(), N * ps * (folded // tp) * 4, shape
    finally:
        engine.shutdown()


@pytest.mark.parametrize("model,tp", [
    ("tiny-llama", 1), ("tiny-mixtral", 1), ("tiny-llama", 2),
])
def test_decode_step_moves_no_pool(model, tp):
    hlo, layer_bytes, pool_shape = _engine_decode_hlo(model, tp)
    assert pool_sized_instructions(hlo, layer_bytes) == []
    assert aliased_pool_parameters(hlo, pool_shape) == 2      # K and V


def test_pool_sized_instructions_sees_a_layer_slice():
    """The census has teeth: the per-layer slice / write-back this layout
    replaced (one layer out of the stack, updated, written back) is seen."""
    def step(pool, layer, rows):
        one = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
        one = one.at[3].set(rows) * 2.0
        return jax.lax.dynamic_update_index_in_dim(pool, one, layer, 0)

    pool = jnp.zeros((2, 512, 8, 32), jnp.float32)
    hlo = jax.jit(step, donate_argnums=0).lower(
        pool, jnp.int32(1), jnp.ones((8, 32), jnp.float32)
    ).compile().as_text()
    assert len(pool_sized_instructions(hlo, 512 * 8 * 32 * 4)) >= 1


@pytest.fixture(scope="module")
def v5e():
    """A described (not attached) v5e host: the chip's own compiler, here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_decode_step_compiled_for_v5e_moves_no_pool(v5e, tp, monkeypatch):
    """The same count in the step the chip runs — both Pallas kernels on the
    stacked pool, `paged_kv_write` aliasing it — compiled for a v5e by the
    compiler installed here. Toy depth, real page geometry; the folded
    dimension a tp shard sees is 128 lanes, and at tp = 4 the 256 lanes
    (2 KV heads of 128) of a mixtral-8x7b shard, where the decode kernel
    takes a wider block than on 1024 lanes."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polykey_tpu.ops import paged_attention_kernel

    # A described chip cannot read a cached executable back (it warns).
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # The gates ask jax.default_backend(), which is the CPU here.
    monkeypatch.setattr(
        paged_attention_kernel, "use_paged_kernel", lambda Hk, D: True
    )
    cfg = replace(TINY_LLAMA, name="layout-probe", num_heads=2 * tp,
                  num_kv_heads=2 * tp, head_dim=128 if tp == 4 else 64)
    mesh = create_mesh(MeshConfig(tp=tp), devices=list(v5e.devices)[:tp])
    repl = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, param_shardings(cfg, mesh, shapes),
    )
    B, pages, ps, tables = 8, 1024, 16, 8
    pool_sh = paged_kv_sharding(mesh)
    pool = jax.eval_shape(lambda: init_paged_kv(cfg, pages, ps, jnp.bfloat16))
    paged = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=pool_sh),
        pool,
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    try:
        compiled = jax.jit(
            engine_mod._decode_fn,
            static_argnames=("cfg", "greedy", "steps", "eos_id", "candidates",
                             "mesh"),
            donate_argnames=("paged",),
            out_shardings=(repl, repl, repl, repl,
                           jax.tree.map(lambda s: pool_sh, pool), repl),
        ).lower(
            params, cfg, paged, arg((B,), jnp.int32), arg((B,), jnp.int32),
            arg((B, tables), jnp.int32), arg((B,), jnp.bool_),
            arg((B,), jnp.int32), arg((B, 2), jnp.int32),
            arg((B,), jnp.float32), arg((B,), jnp.float32),
            arg((B,), jnp.int32),
            greedy=True, steps=2, eos_id=2, candidates=0, mesh=mesh,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    hlo = compiled.as_text()
    folded = cfg.num_kv_heads * cfg.head_dim // tp
    assert pool_sized_instructions(hlo, pages * ps * folded * 2) == []
    assert aliased_pool_parameters(
        hlo, f"bf16[{cfg.num_layers},{pages},{ps},{folded}]"
    ) == 2
    # One write and one read kernel per layer and step, under the names the
    # benchmark's readers hold fixed, on the whole stack.
    stack = f"bf16[{cfg.num_layers * pages},{ps},{folded}]"
    calls = [line.split(" custom-call(")[0] for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    writes = [c for c in calls if c.lstrip().startswith("%paged_kv_write")]
    reads = [c for c in calls
             if c.lstrip().startswith("%paged_attention_decode")]
    assert len(writes) == len(reads) == 1, calls
    assert writes[0].count(stack) == 2


def test_hybrid_decode_step_compiled_for_v5e_aliases_its_state(v5e, monkeypatch):
    """The decode step of a hybrid stack (models/hybrid.py) compiled for a
    v5e at the published widths of its two new kernels — a state of
    [slots, 128, 64, 128] float32 a mixer layer, experts of 1024 x 2688 —
    at toy depth and with 8 experts held: the per-slot state and the pool
    are aliased input to output (donated, updated in place), no
    instruction copies a state-sized buffer, and both kernels are there
    under the names the benchmark's readers hold fixed."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from polykey_tpu.engine.kv_cache import init_slot_state
    from polykey_tpu.models.config import get_config
    from polykey_tpu.ops import hybrid_kernels, paged_attention_kernel

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(
        paged_attention_kernel, "use_paged_kernel", lambda Hk, D: True
    )
    monkeypatch.setattr(hybrid_kernels, "use_kernels", lambda: True)
    cfg = replace(
        get_config("tiny-hybrid"), name="hybrid-probe", hidden_size=512,
        layer_pattern="M*E", num_layers=3, num_heads=4, num_kv_heads=2,
        head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
        ssm_state_size=128, ssm_groups=8, ssm_chunk=128,
        intermediate_size=2688, moe_latent_size=1024,
        moe_shared_intermediate=256, n_routed_experts=32, experts_held=8,
        num_experts_per_tok=6,
    )
    one = SingleDeviceSharding(v5e.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree,
        )

    B, pages, ps, tables = 64, 1024, 16, 8
    params = shaped(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    paged = shaped(jax.eval_shape(
        lambda: init_paged_kv(cfg, pages, ps, jnp.bfloat16)))
    state = shaped(jax.eval_shape(
        lambda: init_slot_state(cfg, B, jnp.bfloat16)))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    try:
        compiled = jax.jit(
            engine_mod._decode_fn,
            static_argnames=("cfg", "greedy", "steps", "eos_id", "candidates",
                             "mesh"),
            donate_argnames=("paged", "last_tokens", "seq_lens", "active",
                             "state"),
        ).lower(
            params, cfg, paged, arg((B,), jnp.int32), arg((B,), jnp.int32),
            arg((B, tables), jnp.int32), arg((B,), jnp.bool_),
            arg((B,), jnp.int32), arg((B, 2), jnp.int32),
            arg((B,), jnp.float32), arg((B,), jnp.float32),
            arg((B,), jnp.int32), state,
            greedy=True, steps=2, eos_id=-1, candidates=0, mesh=None,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    hlo = compiled.as_text()
    ssm = "f32[64,128,64,128]"
    assert aliased_pool_parameters(hlo, ssm) == 1
    assert [line for line in hlo.splitlines()
            if ssm in line and " copy(" in line] == []
    stats = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((paged, state)))
    assert stats.alias_size_in_bytes >= held
    calls = [line.split(" custom-call(")[0].lstrip() for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("%ssm_state_update", "%moe_held_experts", "%paged_kv_write",
                 "%paged_attention_decode"):
        assert sum(c.startswith(name) for c in calls) == 1, (name, calls)


# -- parity ---------------------------------------------------------------------


def _interpret_kernels(monkeypatch):
    """Both Pallas kernels in interpret mode, through the dispatch the chip
    takes (the gates ask for a TPU backend)."""
    from polykey_tpu.ops import paged_attention_kernel as pak
    from polykey_tpu.ops import paged_write_kernel as pwk

    monkeypatch.setattr(pak, "use_paged_kernel", lambda Hk, D: True)
    read, write = pak._decode_call, pwk.paged_write_rows_kernel
    monkeypatch.setattr(
        pak, "_decode_call",
        lambda *a, **kw: read(*a, **{**kw, "interpret": True}),
    )
    monkeypatch.setattr(
        pwk, "paged_write_rows_kernel", partial(write, interpret=True)
    )


@pytest.mark.parametrize("path,kv_dtype,tp", [
    ("xla", None, 1), ("xla", jnp.int8, 1), ("xla", None, 2),
    ("pallas-interpret", None, 1), ("pallas-interpret", jnp.int8, 1),
    ("pallas-interpret", None, 2),
])
def test_paged_prefill_and_decode_match_forward(path, kv_dtype, tp,
                                                monkeypatch):
    """Prefill 5 tokens, decode 4 more one at a time: every hidden state
    equals the one-shot non-paged forward. Two layers and non-contiguous
    pages, so a page of one layer landing in another's range would show."""
    if path == "pallas-interpret":
        _interpret_kernels(monkeypatch)
    cfg = TINY_LLAMA
    mesh = (create_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
            if tp > 1 else None)
    params = init_params(jax.random.PRNGKey(4), cfg, jnp.float32)
    B, T, ps = 2, 9, 4
    tokens = jax.random.randint(
        jax.random.PRNGKey(5), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    want, _ = forward(params, cfg, tokens, positions, None)
    paged = init_paged_kv(cfg, 16, ps, jnp.float32, kv_dtype=kv_dtype)
    tables = jnp.array([[9, 2, 14], [7, 4, 1]], jnp.int32)
    if mesh is not None:
        params = jax.device_put(params, param_shardings(cfg, mesh, params))
        paged = jax.device_put(paged, paged_kv_sharding(mesh))

    got = []
    hidden, paged = forward_paged(
        params, cfg, tokens[:, :5], positions[:, :5], paged, tables,
        mesh=mesh,
    )
    got.append(hidden)
    for t in range(5, T):
        hidden, paged = forward_paged(
            params, cfg, tokens[:, t:t + 1], positions[:, t:t + 1],
            paged, tables, mesh=mesh,
        )
        got.append(hidden)
    have = jnp.concatenate(got, axis=1)
    err = float(jnp.max(jnp.abs(have - want)))
    if kv_dtype is None:
        assert err < 5e-4, err
    else:                               # int8 KV: quantization tolerance
        assert err / (float(jnp.max(jnp.abs(want))) + 1e-6) < 0.05, err


# -- boundary -------------------------------------------------------------------

_ENGINE = dict(
    model="tiny-llama", dtype="float32", max_decode_slots=2, page_size=8,
    num_pages=32, max_seq_len=64, prefill_buckets=(16, 32),
    decode_block_steps=2, adaptive_block=False, max_new_tokens_cap=12,
    default_max_new_tokens=12, supervise=False,
)


def _drain(engine, **kw):
    request = GenRequest(**kw)
    engine.submit(request)
    tokens, state = [], None
    while True:
        kind, value = request.out.get(timeout=120)
        if kind == "token":
            tokens.append(int(value))
        elif kind == "handoff":
            state = value
        elif kind == "done":
            return tokens, state
        else:
            raise AssertionError(value)


def _gather(engine, pages):
    idx = np.zeros((engine.config.pages_per_seq,), np.int32)
    idx[:len(pages)] = pages
    outs = engine._jit_kv_gather(engine.paged, jnp.asarray(idx))
    head_dim = engine.model_cfg.head_dim
    return [
        unfold_heads(np.asarray(o), head_dim)[:, :len(pages)]
        if i < 2 else np.asarray(o)[:, :len(pages)]
        for i, o in enumerate(outs)
    ]


def _restore(engine, pages, arrays):
    P = engine.config.pages_per_seq
    idx = np.zeros((P,), np.int32)
    idx[:len(pages)] = pages
    operands = [jnp.asarray(idx)]
    for i, a in enumerate(arrays):
        padded = np.zeros((a.shape[0], P) + a.shape[2:], a.dtype)
        padded[:, :len(pages)] = a
        operands.append(jnp.asarray(fold_heads(padded) if i < 2 else padded))
    engine.paged = engine._jit_kv_restore(engine.paged, *operands)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_pages_cross_the_host_boundary_byte_for_byte(kv_dtype):
    """Device pool → gather → host tier (HostKVPool, [..., Hk, D]) → wire
    blob → restore into OTHER pages → gather: the same bytes, fp and int8."""
    engine = InferenceEngine(
        EngineConfig(**{**_ENGINE, "kv_dtype": kv_dtype}), seed=7)
    try:
        _, state = _drain(engine, prompt="host boundary round trip",
                          max_new_tokens=4, seed=1, prefill_only=True)
        cfg = engine.model_cfg
        assert state.k.shape == (cfg.num_layers, state.num_pages, 8,
                                 cfg.num_kv_heads, cfg.head_dim)
        assert np.any(state.k != 0) and np.any(state.v != 0)
        arrays = [state.k, state.v] + (
            [state.ks, state.vs] if state.quantized else [])
        # Through the host tier, page by page.
        host = HostKVPool(cfg, 8, 8, jnp.float32, state.quantized)
        slots = [host.alloc() for _ in range(state.num_pages)]
        for r, slot in enumerate(slots):
            host.write(slot, *(a[:, r] for a in arrays))
        back = [
            np.stack([x for x in column], axis=1)
            for column in zip(*(
                [x for x in host.read(slot) if x is not None]
                for slot in slots
            ))
        ]
        # Through the wire.
        shipped = deserialize_kv_state(serialize_kv_state(
            replace(state, k=back[0], v=back[1],
                    ks=back[2] if state.quantized else None,
                    vs=back[3] if state.quantized else None)
        ))
        shipped.validate_for(cfg, 8, state.quantized)
        wired = [shipped.k, shipped.v] + (
            [shipped.ks, shipped.vs] if state.quantized else [])
        target = list(range(20, 20 + state.num_pages))
        _restore(engine, target, wired)
        for want, got in zip(arrays, _gather(engine, target)):
            assert want.dtype == got.dtype
            assert want.tobytes() == got.tobytes()
    finally:
        engine.shutdown()


def test_blob_of_the_unfolded_layout_restores_here():
    """tests/data/kv_handoff_parent_pr30.pkkv was serialized by the tree
    whose pools were [L, N, page_size, Hk, D] (make_parent_kv_blob.py, run
    from that commit). It deserialises, validates against this tree's pool,
    restores byte for byte, and a decode engine resumes from it onto the
    tokens that tree went on to serve: the wire format did not move."""
    with open(os.path.join(DATA, "kv_handoff_parent_pr30.json")) as f:
        record = json.load(f)
    with open(os.path.join(DATA, "kv_handoff_parent_pr30.pkkv"), "rb") as f:
        blob = f.read()
    state = deserialize_kv_state(blob)
    assert isinstance(state, KVHandoffState)
    assert list(state.k.shape) == record["k_shape"]
    config = dict(record["config"])
    config["prefill_buckets"] = tuple(config["prefill_buckets"])
    engine = InferenceEngine(EngineConfig(**config), seed=record["seed"])
    try:
        state.validate_for(engine.model_cfg, config["page_size"], False)
        assert engine.paged.k.shape[-1] == (
            engine.model_cfg.num_kv_heads * engine.model_cfg.head_dim)
        target = list(range(5, 5 + state.num_pages))
        _restore(engine, target, [state.k, state.v])
        got_k, got_v = _gather(engine, target)
        assert got_k.tobytes() == state.k.tobytes()
        assert got_v.tobytes() == state.v.tobytes()
        # This tree serializes the same prompt to the same bytes...
        _, mine = _drain(engine, prompt=record["prompt"], max_new_tokens=10,
                         seed=1, prefill_only=True)
        assert serialize_kv_state(mine) == blob
        # ...and resumes from the old blob onto the old tree's tokens.
        tokens, _ = _drain(engine, prompt="", max_new_tokens=10,
                           resume_state=state)
        assert tokens == record["tokens"]
    finally:
        engine.shutdown()


def test_stored_layout_is_head_folded_for_every_pool():
    for model in ("tiny-llama", "tiny-mixtral", "llama-3-8b"):
        cfg = get_config(model)
        fp = jax.eval_shape(lambda c=cfg: init_paged_kv(c, 8, 16))
        q = jax.eval_shape(
            lambda c=cfg: init_paged_kv(c, 8, 16, kv_dtype=jnp.int8))
        folded = cfg.num_kv_heads * cfg.head_dim
        assert fp.k.shape == fp.v.shape == (cfg.num_layers, 8, 16, folded)
        assert q.k.shape == (cfg.num_layers, 8, 16, folded)
        assert q.ks.shape == (cfg.num_layers, 8, 16, cfg.num_kv_heads)
        assert (fp.page_size, fp.num_pages) == (16, 8)
        page = np.arange(2 * 16 * folded).reshape(2, 16, cfg.num_kv_heads,
                                                  cfg.head_dim)
        assert np.array_equal(
            unfold_heads(fold_heads(page), cfg.head_dim), page)
