"""The stored KV layout (engine/kv_cache.py): ONE pool [L, N, 2, page_size,
Hk·D] (K and V of a page side by side: one DMA descriptor a page), kept whole
in the layer scan's carry and addressed by (layer, page) in place.

Three things are held here.

- STRUCTURE, a count from the compiled decode step: no instruction other than
  the pool parameters, the loops' carries, bitcasts and the in-place writes
  (the kernels' aliased outputs on TPU, the scatters elsewhere) has a result
  of one layer's K (or V) bytes or more, and the pool's output aliases its
  donated input. Before ISSUE 34 the step sliced a layer's pool out, folded
  it, and wrote it back: 8 such instructions in the step compiled for a v5e
  (74 % of the Mistral-7B step on the chip), 4 in the CPU's. This is the
  guard the next architecture's PR runs into first.
- PARITY: paged prefill + decode equal the non-paged forward through every
  path that addresses the layout (XLA gather/scatter, the Pallas kernels in
  interpret mode, int8 KV, tp = 2).
- BOUNDARY: the host tier and the handoff wire format keep K and V in
  arrays of their own, [..., Hk, D]; pages cross that boundary byte for
  byte, and a blob written by the tree BEFORE the fold — and so before K
  and V shared a page (tests/data/kv_handoff_parent_pr30.pkkv) — restores
  here.
"""

import importlib.util
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
    fold_pages,
    init_paged_kv,
    serialize_kv_state,
    unfold_heads,
    unfold_pages,
)
from polykey_tpu.models.config import TINY_LLAMA, get_config
from polykey_tpu.models.transformer import (
    forward,
    forward_paged,
    init_params,
)
from polykey_tpu.parallel.mesh import MeshConfig, create_mesh
from polykey_tpu.parallel.sharding import paged_kv_sharding, param_shardings
from test_models import folded_qkv

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load_script(name: str):
    path = os.path.join(os.path.dirname(DATA), os.pardir, "scripts",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# Reads a compiled module's text and compiles the engine's step functions
# for a described chip; it touches no topology while it is imported.
_CENSUS = _load_script("decode_step_census")

# -- structure ----------------------------------------------------------------

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_ITEMSIZE) + r")\[([\d,]*)\]")
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


def _module(hlo: str):
    """(computations, fused): every computation's instructions as (name,
    result type, opcode, line), and the names of the fused computations
    (their instructions materialise nothing themselves)."""
    return _CENSUS.computations(hlo), _CENSUS.fused_computations(hlo)


def _root(computations, name: str) -> tuple[str, str]:
    """(opcode, result type) of a computation's ROOT."""
    for _, result_type, op, line in computations.get(name, []):
        if line.lstrip().startswith("ROOT"):
            return op, result_type
    return "", ""


def pool_sized_instructions(hlo: str, layer_bytes: int) -> list[str]:
    """Instructions of a compiled module that MATERIALISE an array of one
    layer's pool bytes or more and are not one of `_HOLDERS`. A fusion is
    judged by what its computation returns (an in-place scatter of the pool
    is the XLA paths' write); instructions inside a fused computation
    materialise nothing themselves."""
    computations, fused = _module(hlo)
    found = []
    for comp, instructions in computations.items():
        if comp in fused:
            continue
        for name, result_type, op, line in instructions:
            if _largest(result_type) < layer_bytes or op in _HOLDERS:
                continue
            if op == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                if _root(computations, called)[0] == "scatter":
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
        L, N, _, ps, folded = engine.paged.kv.shape
        shape = f"f32[{L},{N},2,{ps},{folded // tp}]"
        return compiled.as_text(), N * ps * (folded // tp) * 4, shape
    finally:
        engine.shutdown()


@pytest.mark.parametrize("model,tp", [
    ("tiny-llama", 1), ("tiny-mixtral", 1), ("tiny-llama", 2),
    ("tiny-ouro", 1),        # a looped stack: the pool through both loops
])
def test_decode_step_moves_no_pool(model, tp):
    hlo, layer_bytes, pool_shape = _engine_decode_hlo(model, tp)
    assert pool_sized_instructions(hlo, layer_bytes) == []
    assert aliased_pool_parameters(hlo, pool_shape) == 1      # K and V in one


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


def _layout_probe(tp: int, loops: int):
    """The toy decoder the compiled-step counts run on; `loops` > 1: the
    same layers as a looped stack (sandwich norms, a pool `loops` deep)."""
    return replace(TINY_LLAMA, name="layout-probe", num_heads=2 * tp,
                   num_kv_heads=2 * tp, head_dim=128 if tp == 4 else 64,
                   use_post_norms=loops > 1, loop_steps=loops)


@pytest.mark.parametrize("loops", [1, 2], ids=["once", "looped"])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_decode_step_compiled_for_v5e_moves_no_pool(v5e, tp, loops):
    """The same count in the step the chip runs — both Pallas kernels on the
    stacked pool, `paged_kv_write` aliasing it — compiled for a v5e by the
    compiler installed here. Toy depth, real page geometry; the folded
    dimension a tp shard sees is 128 lanes, and at tp = 4 the 256 lanes
    (2 KV heads of 128) of a mixtral-8x7b shard, where the decode kernel
    takes a wider block than on 1024 lanes. A looped stack carries the one
    pool through the scan over its passes too: still ONE write and ONE
    read kernel in the module (a loop, not an unrolling), no copy."""
    cfg = _layout_probe(tp, loops)
    pages, ps = 1024, 16
    hlo = _CENSUS.compile_step(
        cfg, list(v5e.devices), tp=tp, lanes=8, pages=pages, page_size=ps,
        max_seq_len=8 * ps, steps=2,
    )
    folded = cfg.num_kv_heads * cfg.head_dim // tp
    assert pool_sized_instructions(hlo, pages * ps * folded * 2) == []
    assert aliased_pool_parameters(
        hlo, f"bf16[{cfg.kv_layers},{pages},2,{ps},{folded}]"
    ) == 1
    # One write and one read kernel per layer and step, under the names the
    # benchmark's readers hold fixed, on the whole stack.
    stack = f"bf16[{cfg.kv_layers * pages * 2},{ps},{folded}]"   # halves
    calls = [line.split(" custom-call(")[0] for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    writes = [c for c in calls if c.lstrip().startswith("%paged_kv_write")]
    reads = [c for c in calls
             if c.lstrip().startswith("%paged_attention_decode")]
    assert len(writes) == len(reads) == 1, calls
    assert writes[0].count(stack) == 1


@pytest.mark.parametrize("loops", [1, 2], ids=["once", "looped"])
@pytest.mark.parametrize("tp,rows", [(1, 1), (1, 2), (4, 2)])
def test_prefill_step_compiled_for_v5e_moves_no_pool(v5e, tp, rows, loops):
    """A prefill dispatch of one and of two 128-token rows, compiled for a
    v5e: the page-aligned scatters write whole [ps, Hk·D] page halves into
    the donated stack in place and the window gathers read it where it lies
    — no instruction materialises a layer's K (or V) bytes, and the pool's
    output aliases its input. (A ONE-row module compiles differently from
    the others, ISSUE 44: gathering through a [2N, ps, Hk·D] view of the
    pool passed at two rows and cost 1.25 s a dispatch at one, on the chip,
    PR 46.)"""
    cfg = _layout_probe(tp, loops)
    pages, ps = 1024, 16
    hlo = _CENSUS.compile_step(
        cfg, list(v5e.devices), tp=tp, pages=pages, page_size=ps,
        max_seq_len=8 * ps, prefill=(rows, 128),
    )
    folded = cfg.num_kv_heads * cfg.head_dim // tp
    assert pool_sized_instructions(hlo, pages * ps * folded * 2) == []
    assert aliased_pool_parameters(
        hlo, f"bf16[{cfg.kv_layers},{pages},2,{ps},{folded}]"
    ) == 1


def _reachable(computations, root: str) -> set[str]:
    """`root` and every computation its instructions call, fused ones
    included."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for _, _, _, line in computations[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
    return seen


def page_gathers(computations, names, ps: int, folded: int) -> list[int]:
    """Pages a row of each gather of pool pages in computations `names`
    (result [rows, pages, ps, folded])."""
    found = []
    for name in names:
        for _, result_type, op, _ in computations.get(name, []):
            m = re.match(rf"bf16\[\d+,(\d+),{ps},{folded}\]", result_type)
            if op == "gather" and m:
                found.append(int(m.group(1)))
    return found


@pytest.mark.parametrize("rows,width", [(2, 128), (2, 512)])
def test_prefill_step_compiled_for_v5e_gathers_a_turn_at_a_time(
        v5e, rows, width):
    """A prefill dispatch over 4,096-position tables, compiled for a v5e:
    every gather of pool pages moves ONE turn's pages a row (the window's
    own: `prefill_gather_keys`) inside the loop whose trip count is read
    from the positions, and writes them into the stage in place — the
    stage is held row-major, as the kernel reads it, and nothing of its
    size is copied, transposed or re-laid-out — and the layer body calls
    the blockwise kernel ONCE, on the stage, its key axis a dynamic grid
    bound. Before ISSUE 59 the layer body gathered the table whole, twice
    (K and V)."""
    from polykey_tpu.ops.paged_attention import prefill_gather_keys

    cfg = replace(TINY_LLAMA, name="layout-probe", num_heads=2,
                  num_kv_heads=2, head_dim=64)
    pages, ps, table = 1024, 16, 4096
    hlo = _CENSUS.compile_step(
        cfg, list(v5e.devices), tp=1, pages=pages, page_size=ps,
        max_seq_len=table, prefill=(rows, width),
    )
    Hk, D = cfg.num_kv_heads, cfg.head_dim
    piece = prefill_gather_keys(width, table, ps)
    assert piece == width
    computations, fused = _module(hlo)
    assert page_gathers(computations, computations, ps, Hk * D) == [
        piece // ps, piece // ps]
    # The gathers' loop: a while whose body (fusions included) holds both,
    # beside the in-place write and away from the kernel, inside the layer
    # scan.
    loops = [
        _reachable(computations, re.search(
            r"body=%?([\w.\-]+)", line).group(1))
        for instructions in computations.values()
        for _, _, op, line in instructions if op == "while"
    ]
    holding = [body for body in loops
               if page_gathers(computations, body, ps, Hk * D)]
    assert len(holding) == 2
    text = "\n".join(line for name in min(holding, key=len)
                     for *_, line in computations[name])
    assert "flash_attention" not in text and "dynamic-update-slice" in text
    # The stage: [rows, Hk, table, D], row-major wherever it is held, and
    # nothing of its size is made but the stage itself (zeros, once), the
    # turns' in-place writes and what carries it or moves it between
    # memories whole.
    stage = f"bf16[{rows},{Hk},{table},{D}]"
    whole = rows * table * D * Hk * 2
    made = set()
    for name, instructions in computations.items():
        for _, result_type, op, line in instructions:
            if stage in result_type:
                assert f"{stage}{{3,2,1,0" in result_type, line
            if name in fused or _largest(result_type) < whole:
                continue
            if op not in _HOLDERS:
                made.add(op)
    assert made <= {"broadcast", "fusion", "dynamic-update-slice",
                    "copy-start", "copy-done", "slice-start"}, made
    calls = [c for c in _kernel_calls(hlo) if c.startswith("%flash_attention")]
    assert len(calls) == 1, calls
    assert pool_sized_instructions(hlo, pages * ps * Hk * D * 2) == []


def _compile_pattern_step(v5e, monkeypatch, cfg, lanes=64, prefill=None):
    """(compiled, paged, state): the engine's decode step — its prefill
    step at `prefill` = (rows, width) — of a layer pattern with per-slot
    state, lowered from shapes for one described v5e chip with every
    kernel gate answering as the chip would."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from polykey_tpu.engine.kv_cache import init_slot_state
    from polykey_tpu.ops import hybrid_kernels, paged_attention_kernel

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(
        paged_attention_kernel, "use_paged_kernel", lambda Hk, D: True
    )
    monkeypatch.setattr(hybrid_kernels, "use_kernels", lambda: True)
    one = SingleDeviceSharding(v5e.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree,
        )

    B, pages, ps, tables = lanes, 1024, 16, 8
    params = shaped(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    paged = shaped(jax.eval_shape(
        lambda: init_paged_kv(cfg, pages, ps, jnp.bfloat16)))
    state = shaped(jax.eval_shape(
        lambda: init_slot_state(cfg, B, jnp.bfloat16)))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    try:
        if prefill is None:
            compiled = jax.jit(
                engine_mod._decode_fn,
                static_argnames=("cfg", "greedy", "steps", "eos_id",
                                 "candidates", "mesh"),
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
        else:
            N, T = prefill
            compiled = jax.jit(
                engine_mod._prefill_fn,
                static_argnames=("cfg", "greedy", "candidates", "mesh"),
                donate_argnames=("paged", "state"),
            ).lower(
                params, cfg, paged, arg((N, T), jnp.int32),
                arg((N,), jnp.int32), arg((N,), jnp.int32),
                arg((N, T // ps), jnp.int32), arg((N, 2), jnp.int32),
                arg((N,), jnp.float32), arg((N,), jnp.float32),
                arg((N,), jnp.int32), state, arg((N, 3), jnp.int32),
                greedy=True, candidates=0, mesh=None,
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    return compiled, paged, state


def _kernel_calls(hlo: str) -> list[str]:
    return [line.split(" custom-call(")[0].lstrip() for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def sorts(hlo: str) -> list[str]:
    """The module's sorts by an integer key (the router's top-k is a sort
    by a float32 score, in every module): the grouped product orders its
    rows by a counting sort, so no module has one."""
    return [line.strip()[:160] for line in hlo.splitlines()
            if " sort(" in line and "= (s32[" in line]


def test_hybrid_decode_step_compiled_for_v5e_aliases_its_state(v5e, monkeypatch):
    """The decode step of a hybrid stack (models/hybrid.py) compiled for a
    v5e at the published widths of its two new kernels — a state of
    [slots, 128, 64, 128] float32 a mixer layer, experts of 1024 x 2688 —
    at toy depth and with 8 experts held: the per-slot state and the pool
    are aliased input to output (donated, updated in place), no
    instruction copies a state-sized buffer, and both kernels are there
    under the names the benchmark's readers hold fixed."""
    from polykey_tpu.models.config import get_config

    cfg = replace(
        get_config("tiny-hybrid"), name="hybrid-probe", hidden_size=512,
        layer_pattern="M*E", num_layers=3, num_heads=4, num_kv_heads=2,
        head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
        ssm_state_size=128, ssm_groups=8, ssm_chunk=128,
        intermediate_size=2688, moe_latent_size=1024,
        moe_shared_intermediate=256, n_routed_experts=32, experts_held=8,
        num_experts_per_tok=6,
    )
    compiled, paged, state = _compile_pattern_step(v5e, monkeypatch, cfg)
    hlo = compiled.as_text()
    ssm = "f32[64,128,64,128]"
    assert aliased_pool_parameters(hlo, ssm) == 1
    assert [line for line in hlo.splitlines()
            if ssm in line and " copy(" in line] == []
    stats = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((paged, state)))
    assert stats.alias_size_in_bytes >= held
    calls = _kernel_calls(hlo)
    for name in ("%ssm_state_update", "%moe_held_experts", "%paged_kv_write",
                 "%paged_attention_decode"):
        assert sum(c.startswith(name) for c in calls) == 1, (name, calls)
    # The decode program holds the grouped call (ISSUE 56: the experts no
    # live lane chose are not read), whose order is a counting sort: no
    # sort by comparison.
    assert sum(c.startswith("%moe_held_experts_grouped") for c in calls) == 1
    assert sorts(hlo) == []


@pytest.mark.parametrize("prefill", [(4, 128), (1, 128)],
                         ids=["prefill-4x128", "prefill-1x128"])
def test_latent_prefill_compiled_for_v5e_runs_grouped(
        v5e, monkeypatch, prefill):
    """The same stack's prefill step at four 128-token windows and at one:
    the held experts' product is the grouped call (one kernel) the decode
    step has, at the published widths of a latent expert layer — the row
    count decides nothing (ISSUE 56)."""
    from polykey_tpu.models.config import get_config

    cfg = replace(
        get_config("tiny-hybrid"), name="hybrid-probe", hidden_size=512,
        layer_pattern="M*E", num_layers=3, num_heads=4, num_kv_heads=2,
        head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
        ssm_state_size=128, ssm_groups=8, ssm_chunk=128,
        intermediate_size=2688, moe_latent_size=1024,
        moe_shared_intermediate=256, n_routed_experts=32, experts_held=8,
        num_experts_per_tok=6,
    )
    compiled, _, _ = _compile_pattern_step(
        v5e, monkeypatch, cfg, prefill=prefill)
    hlo = compiled.as_text()
    held = [c for c in _kernel_calls(hlo)
            if c.startswith("%moe_held_experts")]
    assert len(held) == 1 and held[0].startswith(
        "%moe_held_experts_grouped"), held
    assert sorts(hlo) == []


@pytest.mark.parametrize("prefill", [None, (1, 128), (2, 512)],
                         ids=["decode", "prefill-1x128", "prefill-2x512"])
def test_operator_ffn_stack_compiled_for_v5e(v5e, monkeypatch, prefill):
    """The steps of an operator + feed-forward pattern (a gated short conv,
    QK-normed RoPE attention, a dense part and gated experts on the full
    hidden; one tied matrix) compiled for a v5e at the published widths of
    what it adds — hidden 2048, 32 / 8 heads of 64 (a page half of 512
    lanes), experts of 2048 x 1536 — at toy depth, 8 experts held and a
    short vocabulary. Decode: the conv columns and the pool are aliased
    input to output, every kernel is there once under the name the
    benchmark's readers hold fixed, and the gated product is the SAME
    kernel name the un-gated one has. Every module: q and k at head width
    64 stay plain matmuls (ISSUE 44's three forms are absent), although a
    norm over each head stands between the product and `rope`."""
    from polykey_tpu.models.config import get_config

    cfg = replace(
        get_config("tiny-lfm2"), name="operator-ffn-probe", vocab_size=4096,
        hidden_size=2048, layer_pattern="CD*E", num_layers=4, num_heads=32,
        num_kv_heads=8, head_dim=64, intermediate_size=1536,
        dense_intermediate_size=11776, n_routed_experts=64, experts_held=8,
    )
    compiled, paged, state = _compile_pattern_step(
        v5e, monkeypatch, cfg, prefill=prefill)
    hlo = compiled.as_text()
    wk_bytes = cfg.hidden_size * cfg.num_kv_heads * cfg.head_dim * 2
    assert head_window_products(hlo) == []
    assert staged_weight_slices(hlo, wk_bytes) == []
    assert layer_weight_copies(hlo, "bf16", cfg.hidden_size) == []
    calls = _kernel_calls(hlo)
    assert sum(c.startswith("%moe_held_experts") for c in calls) == 1, calls
    # 1,024 rows, a one-window prefill and a decode step alike run sorted
    # by expert (ISSUE 56). No module sorts by comparison.
    assert any(c.startswith("%moe_held_experts_grouped") for c in calls), calls
    assert sorts(hlo) == []
    assert jax.tree.leaves(state.ssm) == []
    if prefill is not None:
        # (At this probe's geometry a pattern's prefill module copies the
        # pool after its last attending layer's write, the sibling stack's
        # too; the cell's traced prefill on the chip shows no such copy,
        # PERF.md section 5. Copies are counted by a weight's shape here.)
        return
    assert weight_relayouts(hlo, wk_bytes) == []
    conv = "bf16[64,2,2048]"
    assert aliased_pool_parameters(hlo, conv) == 1
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((paged, state)))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    for name in ("%paged_kv_write", "%paged_attention_decode"):
        assert sum(c.startswith(name) for c in calls) == 1, (name, calls)


@pytest.mark.parametrize("prefill", [None, (1, 128), (2, 512)],
                         ids=["decode", "prefill-1x128", "prefill-2x512"])
def test_delta_attention_stack_compiled_for_v5e(v5e, monkeypatch, prefill):
    """The steps of a gated delta-rule / output-gated attention pattern
    (zero-centred norms, partial rotary, softmax-routed gated experts with
    a gated shared expert) compiled for a v5e at the published widths of
    what it adds — hidden 2048, 16 / 32 linear heads of 128 (a state of
    [slots, 32, 128, 128] float32 a layer), 16 / 2 heads of 256 with a
    gate as wide as the query, experts of 2048 x 512 — at toy depth, 8
    experts held and a short vocabulary. Decode: S, the conv columns and
    the pool are aliased input to output, no instruction copies a
    state-sized buffer, and every kernel is there once under the name the
    benchmark's readers hold fixed; the delta update is that ONE call a
    linear layer. Prefill: the chunked form, which never calls the decode
    kernel. Every module: q (with its gate) and k at head width 256 stay
    plain matmuls (ISSUE 44's three forms are absent)."""
    from polykey_tpu.models.config import get_config

    cfg = replace(
        get_config("tiny-qwen3-next"), name="delta-attention-probe",
        vocab_size=4096, hidden_size=2048, layer_pattern="LE*E", num_layers=4,
        num_heads=16, num_kv_heads=2, head_dim=256, delta_key_heads=16,
        delta_value_heads=32, delta_key_dim=128, delta_value_dim=128,
        delta_chunk=64, intermediate_size=512, moe_shared_intermediate=512,
        n_routed_experts=512, experts_held=8, num_experts_per_tok=10,
    )
    compiled, paged, state = _compile_pattern_step(
        v5e, monkeypatch, cfg, prefill=prefill)
    hlo = compiled.as_text()
    wk_bytes = cfg.hidden_size * cfg.num_kv_heads * cfg.head_dim * 2
    assert head_window_products(hlo) == []
    # (A one-row prefill reads its slot's float32 S by a slice: state, not
    # a weight. The weights are bf16.)
    assert [r for r in staged_weight_slices(hlo, wk_bytes)
            if not r.startswith("f32")] == []
    assert layer_weight_copies(hlo, "bf16", cfg.hidden_size) == []
    calls = _kernel_calls(hlo)
    assert sum(c.startswith("%moe_held_experts") for c in calls) == 2, calls
    assert all(c.startswith("%moe_held_experts_grouped")
               for c in calls if c.startswith("%moe_held_experts")), calls
    assert sorts(hlo) == []
    updates = sum(c.startswith("%gated_delta_state_update") for c in calls)
    assert updates == (1 if prefill is None else 0), calls
    if prefill is not None:
        return
    # (The conv's columns — 3 MB a layer, larger than `wk` here — are
    # copied once a layer into the layout the window's concatenation wants:
    # 7 us of a step at the chip's bandwidth. No weight is.)
    S, conv = "f32[64,32,128,128]", "bf16[64,3,8192]"
    assert [r for r in weight_relayouts(hlo, wk_bytes) if conv not in r] == []
    assert aliased_pool_parameters(hlo, S) == 1
    assert aliased_pool_parameters(hlo, conv) == 1
    assert [line for line in hlo.splitlines()
            if S in line and " copy(" in line] == []
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((paged, state)))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    for name in ("%paged_kv_write", "%paged_attention_decode"):
        assert sum(c.startswith(name) for c in calls) == 1, (name, calls)


@pytest.mark.parametrize("prefill", [None, (1, 128), (2, 512)],
                         ids=["decode", "prefill-1x128", "prefill-2x512"])
def test_delta_mha_stack_compiled_for_v5e(v5e, monkeypatch, prefill):
    """The steps of a gated delta-rule / NoPE multi-head attention pattern
    over dense MLPs, post-normed alone, compiled for a v5e at the published
    widths of what it adds — hidden 3840, 30 linear heads of 96 x 192 with
    as many key heads (a state of [slots, 15, 96, 384] float32 a layer: two
    heads side by side, rows of three whole lane tiles), 30 query heads on
    30 KV heads of 128 (groups of ONE: 7,680 columns of K and V a token),
    an MLP of 11008 — at toy depth and a short vocabulary. Decode: S in its
    stored layout, the conv columns and the pool are aliased input to
    output, no instruction copies a state-sized buffer, the delta update
    is ONE call a linear layer under the name the benchmark's readers hold
    fixed, the paged kernels take 30 KV heads as they take 2 or 8, and no
    expert product is there. Prefill: the chunked form on the heads apart,
    which never calls the decode kernel. Every module: q and k stay plain
    matmuls under the full-width norm (ISSUE 44's three forms absent)."""
    from polykey_tpu.models.config import get_config

    cfg = replace(
        get_config("tiny-olmo-hybrid"), name="delta-mha-probe",
        vocab_size=4096, hidden_size=3840, layer_pattern="LD*D", num_layers=4,
        num_heads=30, num_kv_heads=30, head_dim=128, delta_key_heads=30,
        delta_value_heads=30, delta_key_dim=96, delta_value_dim=192,
        delta_chunk=64, intermediate_size=11008,
        dense_intermediate_size=11008,
    )
    compiled, paged, state = _compile_pattern_step(
        v5e, monkeypatch, cfg, prefill=prefill)
    hlo = compiled.as_text()
    wk_bytes = cfg.hidden_size * cfg.num_kv_heads * cfg.head_dim * 2
    assert head_window_products(hlo) == []
    assert [r for r in staged_weight_slices(hlo, wk_bytes)
            if not r.startswith("f32")] == []
    assert layer_weight_copies(hlo, "bf16", cfg.hidden_size) == []
    calls = _kernel_calls(hlo)
    assert not any(c.startswith("%moe_held_experts") for c in calls), calls
    updates = sum(c.startswith("%gated_delta_state_update") for c in calls)
    assert updates == (1 if prefill is None else 0), calls
    # No module copies the pool (1,024 pages of 16 x 7,680 columns: 252 MB;
    # unbarred, the unrolled walk's prefill had the last layer's write
    # return the pool in two shapes and copied all of it, 3.75 GB of a
    # 4,096-page pool on a chip with 1.7 GB to spare), and it is aliased.
    pool = f"bf16[1,1024,2,16,{cfg.num_kv_heads * cfg.head_dim}]"
    assert [line for line in hlo.splitlines()
            if pool in line and " copy(" in line] == []
    assert aliased_pool_parameters(hlo, pool) == 1
    if prefill is not None:
        return
    S, conv = "f32[64,15,96,384]", "bf16[64,3,11520]"
    assert [s.shape for s in state.ssm] == [(64, 15, 96, 384)]
    assert [r for r in weight_relayouts(hlo, wk_bytes) if conv not in r] == []
    assert aliased_pool_parameters(hlo, S) == 1
    assert aliased_pool_parameters(hlo, conv) == 1
    assert [line for line in hlo.splitlines()
            if S in line and " copy(" in line] == []
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((paged, state)))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    for name in ("%paged_kv_write", "%paged_attention_decode"):
        assert sum(c.startswith(name) for c in calls) == 1, (name, calls)


@pytest.mark.parametrize("prefill", [None, (1, 128), (2, 512)],
                         ids=["decode", "prefill-1x128", "prefill-2x512"])
def test_latent_attention_stack_compiled_for_v5e(v5e, monkeypatch, prefill):
    """The steps of a latent-attention (MLA) pattern with sandwich norms
    compiled for a v5e at the published widths of what it adds — hidden
    7680, 128 heads of 128 + 64 / 128 over ranks 1536 / 512, a pool of ONE
    640-wide row a token and layer (576 published, in whole lane tiles),
    experts of 7680 x 2048 — at toy depth and a short vocabulary. Decode:
    the one-part pool is aliased input to output, every latent layer is ONE
    `mla_latent_decode` call behind ONE page write, nothing gathers a
    lane's table out of the pool, and the held experts' call fits its
    weight blocks in VMEM (the inner tile follows bytes: 256 of 2048).
    Prefill: the blockwise kernel over the gathered rows, once a latent
    layer, and never the decode kernel; the experts run grouped at every
    width, 256 rows a call."""
    from polykey_tpu.models.config import get_config

    cfg = replace(
        get_config("tiny-pangu"), name="latent-attention-probe",
        vocab_size=4096, hidden_size=7680, layer_pattern="ADAE", num_layers=4,
        num_heads=128, head_dim=192, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        dense_intermediate_size=18432, intermediate_size=2048,
        moe_shared_intermediate=2048, n_routed_experts=256, experts_held=8,
        num_experts_per_tok=8,
    )
    compiled, paged, state = _compile_pattern_step(
        v5e, monkeypatch, cfg, prefill=prefill)
    hlo = compiled.as_text()
    assert head_window_products(hlo) == []
    pool = "bf16[2,1024,1,16,640]"      # 2 layers x 1,024 pages, one part
    assert jax.tree.leaves(paged)[0].shape == (2, 1024, 1, 16, 640)
    assert jax.tree.leaves(state) == []
    assert aliased_pool_parameters(hlo, pool) == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * 1024 * 16 * 640 * 2)
    calls = _kernel_calls(hlo)
    reads = sum(c.startswith("%mla_latent_decode") for c in calls)
    flash = sum(c.startswith("%flash_attention") for c in calls)
    assert not any(c.startswith("%paged_attention_decode") for c in calls)
    held = [c for c in calls if c.startswith("%moe_held_experts")]
    if prefill is None:
        assert (reads, flash) == (2, 0), calls
        assert sum(c.startswith("%paged_kv_write") for c in calls) == 2
        assert len(held) == 1 and "grouped" in held[0], calls
        # No lane's window is gathered out of the pool: the only gathers
        # read the embedding's rows and the router's choices.
        tables = [line for line in hlo.splitlines()
                  if " gather(" in line and ",16,640]" in line]
        assert tables == []
    else:
        assert (reads, flash) == (0, 2), calls
        assert len(held) == (4 if prefill == (2, 512) else 1), calls
        assert all(c.startswith("%moe_held_experts_grouped")
                   for c in held), calls


# -- the q / k / v projections in the compiled steps (ISSUE 44) ---------------
#
# Where a dimension of 1 stands beside the rows ([B, 1, H] in the decode
# step, [1, T, H] in a one-row prefill) the TPU compiler folds the head split
# that follows the q and k products INTO them: a convolution with a window
# over heads on the weight viewed [heads, D, H]. That form relays the whole
# `wq` / `wk` stacks {1,2,0} on every dispatch, stages each layer's slice in
# VMEM through a fusion of its own and runs the product from there.
# `layers.qkv_project` holds the three results flat until the products are
# done; these cases hold the compiled steps to it, at the widths the
# benchmark's cells run.

_HEAD_WINDOW = re.compile(r"window=\{size=[^}]*\},? dim_labels=bf0_0oi->b0f")

_MISTRAL = dict(
    name="mistral-7b-widths", vocab_size=32768, hidden_size=4096,
    intermediate_size=14336, num_heads=32, num_kv_heads=8, head_dim=128,
    max_seq_len=8192, rope_theta=1e6, rms_norm_eps=1e-5,
    tie_embeddings=False,
)
# leaves, tp, layers, experts. bf16 at 32 layers does not fit one chip (its
# deployments are int8), and the verdict does not follow the depth: the
# layers are one scanned body.
_DECODE_CASES = {
    "mistral-7b-int8": ("int8", 1, 32, 0),
    "mistral-7b-bf16": ("none", 1, 4, 0),
    "mixtral-tp4-shard-int8": ("int8", 4, 2, 8),
    "mixtral-tp4-shard-bf16": ("none", 4, 2, 8),
}


@pytest.fixture
def expression(request, monkeypatch):
    """`flat`: the tree's `qkv_project`. `folded`: the parent's, put in its
    place; jit caches a trace by the step function, so the caches go before
    AND after (a later test must not be handed the folded trace)."""
    if request.param == "folded":
        from polykey_tpu.models import transformer

        monkeypatch.setattr(transformer, "qkv_project", folded_qkv)
        jax.clear_caches()
        yield request.param
        jax.clear_caches()
    else:
        yield request.param


def _step_at_cell_widths(v5e, leaves, tp, layers, experts, prefill=None):
    """(compiled text, one layer's `wk` bytes on a shard) of the engine's
    step at the cells' geometry: 16 lanes, 2,048 x 16-token pages, a
    4,096-position table, K = 8."""
    from polykey_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        **_MISTRAL, num_layers=layers, num_experts=experts,
        num_experts_per_tok=2 if experts else 0, moe_dispatch=bool(experts),
    )
    hlo = _CENSUS.compile_step(
        cfg, list(v5e.devices), quantize=leaves, tp=tp, prefill=prefill)
    itemsize = 1 if leaves == "int8" else 2
    wk_bytes = (cfg.hidden_size * cfg.num_kv_heads * cfg.head_dim // tp
                * itemsize)
    return hlo, wk_bytes


def weight_relayouts(hlo: str, nbytes: int) -> list[str]:
    """(a) `copy` / `transpose` instructions outside any fused computation
    with a result of `nbytes` or more."""
    computations, fused = _module(hlo)
    return [
        f"{op} {result_type} %{name}"
        for comp, instructions in computations.items() if comp not in fused
        for name, result_type, op, _ in instructions
        if op in ("copy", "transpose") and _largest(result_type) >= nbytes
    ]


def head_window_products(hlo: str) -> list[str]:
    """(b) convolutions with a window over heads (Mixtral's expert products,
    `0bf_0io->0bf`, are not this form and stay)."""
    return _HEAD_WINDOW.findall(hlo)


def staged_weight_slices(hlo: str, nbytes: int) -> list[str]:
    """(c) fusions whose ROOT is a `dynamic-slice` with a result of `nbytes`
    or more: a layer's weight cut out of its stack and materialised."""
    computations, fused = _module(hlo)
    found = []
    for comp, instructions in computations.items():
        if comp in fused:
            continue
        for name, _, op, line in instructions:
            if op != "fusion":
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
            root, result_type = _root(computations, called)
            if root == "dynamic-slice" and _largest(result_type) >= nbytes:
                found.append(f"{result_type} %{name}")
    return found


def layer_weight_copies(hlo: str, dtype: str, hidden: int) -> list[str]:
    """`copy` instructions outside any fused computation whose result has
    the shape of ONE layer's projection weight, [1, hidden, out] (what a
    one-row prefill of the parent makes of `wq` and `wk` every layer);
    activations of a prefill are larger than a weight, so bytes alone
    would not tell them apart there."""
    shape = re.compile(rf"^{dtype}\[(?:1,)?{hidden},\d+\]")
    computations, fused = _module(hlo)
    return [
        f"{result_type} %{name}"
        for comp, instructions in computations.items() if comp not in fused
        for name, result_type, op, _ in instructions
        if op == "copy" and shape.match(result_type)
    ]


@pytest.mark.parametrize("case", list(_DECODE_CASES))
def test_decode_step_compiled_for_v5e_projects_qkv_as_plain_matmuls(v5e, case):
    hlo, wk_bytes = _step_at_cell_widths(v5e, *_DECODE_CASES[case])
    assert weight_relayouts(hlo, wk_bytes) == []
    assert head_window_products(hlo) == []
    assert staged_weight_slices(hlo, wk_bytes) == []
    # Still the step the benchmark's readers know: both kernels, by name.
    assert "%paged_kv_write" in hlo and "%paged_attention_decode" in hlo


# The decode program around the paged decode kernel (ISSUE 50): the kernel
# walks the step's sequences in one program and hands back ONE normalised
# block in the activations' dtype, so nothing stands between rope and the
# call, or between the call and `wo`.
# heads, KV heads, head width, hidden, leaves, tp, experts, what reads the
# result. (At head width 256 in bf16 the `wo` product takes its operand in
# another layout and the compiler relays the result once: that copy stood
# behind the parent's normalising fusion too and is not the kernel's.)
_ATTEND_CASES = {
    "mistral-7b": (32, 8, 128, 4096, "int8", 1, 0, ["fusion"]),
    "mixtral-tp4-shard": (32, 8, 128, 4096, "int8", 4, 8, ["fusion"]),
    "head-width-256": (16, 2, 256, 2048, "none", 1, 0, ["copy"]),
}


def _around(hlo: str, call: str):
    """(opcodes that produce the call's operands, opcodes that consume its
    result — seen through a bitcast) in the call's own computation."""
    for instructions in _module(hlo)[0].values():
        by_name = {name: (op, line) for name, _, op, line in instructions}
        if call not in by_name:
            continue
        operands = re.findall(
            r"%([\w.\-]+)", by_name[call][1].split("custom-call(")[1]
            .split(")")[0])

        def users(name):
            found = []
            for user, (op, line) in by_name.items():
                if re.search(rf"\(.*%{re.escape(name)}\b", line):
                    found += users(user) if op == "bitcast" else [op]
            return found

        return [by_name[o][0] for o in operands if o in by_name], users(call)
    raise AssertionError(f"{call} is in no computation")


@pytest.mark.parametrize("case", list(_ATTEND_CASES))
def test_decode_step_compiled_for_v5e_calls_the_decode_kernel_bare(v5e, case):
    from polykey_tpu.models.config import ModelConfig

    Hq, Hk, D, hidden, leaves, tp, experts, readers = _ATTEND_CASES[case]
    cfg = ModelConfig(
        **{**_MISTRAL, "num_heads": Hq, "num_kv_heads": Hk, "head_dim": D,
           "hidden_size": hidden},
        num_layers=2, num_experts=experts,
        num_experts_per_tok=2 if experts else 0, moe_dispatch=bool(experts),
    )
    hlo = _CENSUS.compile_step(
        cfg, list(v5e.devices), quantize=leaves, tp=tp)
    # One call an attending layer (the layers are one scanned body), with
    # one result: the 16 lanes' heads in bf16, not three float32 states.
    calls = [c for c in _kernel_calls(hlo)
             if c.startswith("%paged_attention_decode")]
    assert len(calls) == 1, calls
    name, result = calls[0].lstrip("%").split(" = ")
    assert result.startswith(f"bf16[16,{Hq // tp},{D}]"), result
    # Nothing relays q on its way in, the result goes to `wo` as it is, and
    # no float32 form of it exists for XLA to normalise.
    producers, consumers = _around(hlo, name)
    assert not {"copy", "transpose"} & set(producers), producers
    assert consumers == readers, consumers
    computations, fused = _module(hlo)
    assert [name for comp, instructions in computations.items()
            if comp not in fused for name, result_type, _, _ in instructions
            if f"f32[16,{Hq // tp},{D}]" in result_type] == []
    # No pool-shaped operation: 2,048 pages of 16 positions a layer (at two
    # layers the weight stacks XLA prefetches are as large, and are not
    # pools: told apart by the page's shape).
    folded = Hk * D // tp
    assert [found for found in pool_sized_instructions(
        hlo, 2048 * 16 * folded * 2) if f",16,{folded}]" in found] == []


@pytest.mark.parametrize("expression", ["folded"], indirect=True)
def test_qkv_census_sees_the_folded_projection(v5e, expression):
    """The census has teeth: the parent's expression, compiled the same way,
    relays both stacks, stages both layers' slices and takes two products
    with a window over heads (32 for q, 8 for k)."""
    hlo, wk_bytes = _step_at_cell_widths(
        v5e, *_DECODE_CASES["mistral-7b-int8"])
    assert len(weight_relayouts(hlo, wk_bytes)) == 2
    assert len(head_window_products(hlo)) == 2
    assert len(staged_weight_slices(hlo, wk_bytes)) == 2


@pytest.mark.parametrize("expression", ["flat", "folded"], indirect=True)
@pytest.mark.parametrize("rows,width", [(1, 128), (1, 512), (2, 128), (2, 512)])
def test_prefill_step_compiled_for_v5e_copies_no_layer_weight(
        v5e, rows, width, expression):
    """The prefill modules of `mistral-7b` (int8, 32 layers): a ONE-row
    module folds like the decode step, and the parent's expression then
    copies the layer's `wq` and `wk` in VMEM every layer; a multi-row module
    never took that form and must compile as it did."""
    hlo, _ = _step_at_cell_widths(
        v5e, "int8", 1, 32, 0, prefill=(rows, width))
    folds = expression == "folded" and rows == 1
    assert len(head_window_products(hlo)) == (2 if folds else 0)
    assert len(layer_weight_copies(hlo, "s8", 4096)) == (2 if folds else 0)
    assert "%flash_attention" in hlo


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
    """[k, v] (+ [ks, vs] of an int8 pool) of `pages`, as the host tier
    and the wire hold them: K and V apart, the heads apart."""
    idx = np.zeros((engine.config.pages_per_seq,), np.int32)
    idx[:len(pages)] = pages
    outs = engine._jit_kv_gather(engine.paged, jnp.asarray(idx))
    arrays = list(unfold_pages(np.asarray(outs.kv), engine.model_cfg.head_dim))
    if outs.quantized:
        arrays += [np.asarray(outs.ks), np.asarray(outs.vs)]
    return [a[:, :len(pages)] for a in arrays]


def _restore(engine, pages, arrays):
    P = engine.config.pages_per_seq
    idx = np.zeros((P,), np.int32)
    idx[:len(pages)] = pages
    padded = []
    for a in arrays:
        padded.append(np.zeros((a.shape[0], P) + a.shape[2:], a.dtype))
        padded[-1][:, :len(pages)] = a
    padded += [None] * (4 - len(padded))
    engine.paged = engine._jit_kv_restore(
        engine.paged, jnp.asarray(idx), engine._page_upload(*padded))


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
        assert engine.paged.kv.shape[2:] == (
            2, config["page_size"],
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
        # K and V of a page side by side in ONE array.
        assert fp.kv.shape == q.kv.shape == (cfg.num_layers, 8, 2, 16, folded)
        assert (fp.ks, fp.vs) == (None, None) and q.kv.dtype == jnp.int8
        assert q.ks.shape == q.vs.shape == (
            cfg.num_layers, 8, 16, cfg.num_kv_heads)
        assert (fp.page_size, fp.num_pages) == (16, 8)
        assert (q.page_size, q.num_pages) == (16, 8)
        page = np.arange(2 * 16 * folded).reshape(2, 16, cfg.num_kv_heads,
                                                  cfg.head_dim)
        assert np.array_equal(
            unfold_heads(fold_heads(page), cfg.head_dim), page)
        # The host boundary: K and V pages apart <-> one stored page.
        stored = fold_pages(page, page + 1)
        assert stored.shape == (2, 2, 16, folded)
        assert np.array_equal(stored[:, 0], fold_heads(page))
        back_k, back_v = unfold_pages(stored, cfg.head_dim)
        assert np.array_equal(back_k, page)
        assert np.array_equal(back_v, page + 1)
