#!/usr/bin/env python3
"""The layer body of the compiled decode step, operation by operation.

Compiles `engine._decode_fn` (or `_prefill_fn`, `--prefill ROWSxWIDTH`) for a
DESCRIBED v5e — the chip's own compiler, installed here, no chip attached —
from `ShapeDtypeStruct`s at the widths, quantization, tp and engine geometry
a configuration file under `perfbench/configs/` states, and prints every
instruction of the layer scan's body: its result (with layout and memory
space, `S(1)` = VMEM), whether an operand comes straight from the scan's
carry (a weight stack or a pool: an HBM read inside the operation) and, for
a fusion, the dots / convolutions inside it. The operation names are the
ones `breakdown.device_ops` of a traced run carries (`fusion.160`,
`constant_dynamic-slice_fusion.4`, …), so a ledger line can be read against
this listing. About 4 s a compile at 32 layers. Nothing runs: it says what
the step IS, never how long it takes.

    JAX_PLATFORMS=cpu python scripts/decode_step_census.py perfbench/configs/mistral-7b.json
    JAX_PLATFORMS=cpu python scripts/decode_step_census.py perfbench/configs/mistral-7b.json --prefill 1x128
    JAX_PLATFORMS=cpu python scripts/decode_step_census.py perfbench/configs/mixtral-8x7b-tp4.json --layers 2

It imports the package only. `tests/test_paged_layout.py` loads it for the
compiled-step census of the q / k / v projections.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from polykey_tpu.engine import engine as engine_mod  # noqa: E402
from polykey_tpu.engine.kv_cache import init_paged_kv  # noqa: E402
from polykey_tpu.models import quant  # noqa: E402
from polykey_tpu.models.config import ModelConfig  # noqa: E402
from polykey_tpu.models.transformer import init_params  # noqa: E402
from polykey_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from polykey_tpu.parallel.sharding import (  # noqa: E402
    paged_kv_sharding,
    param_shardings,
)


def describe_v5e():
    """The devices of a described (not attached) v5e 2x2 host."""
    from jax.experimental import topologies

    return list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices)


def model_config(spec: dict, layers: int | None = None) -> ModelConfig:
    """A ModelConfig from a configuration file's HF-style keys (the GQA
    decoders and their top-k expert variant; a file that names an adapter
    of its own builds its ModelConfig there, not here)."""
    if "adapter" in spec:
        raise SystemExit(
            f"{spec['name']}: its ModelConfig is built by perfbench/adapters/"
            f"{spec['adapter']}.py, which this script does not import"
        )
    heads = spec["num_attention_heads"]
    experts = spec.get("num_local_experts", 0)
    return ModelConfig(
        name=spec["name"],
        vocab_size=spec["vocab_size"],
        hidden_size=spec["hidden_size"],
        intermediate_size=spec["intermediate_size"],
        num_layers=layers or spec["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec.get("head_dim") or spec["hidden_size"] // heads,
        max_seq_len=spec["engine_max_positions"],
        rope_theta=float(spec["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec.get("tie_word_embeddings", False)),
        num_experts=experts,
        num_experts_per_tok=spec.get("num_experts_per_tok", 0),
        moe_dispatch=bool(experts),
    )


@contextlib.contextmanager
def compiling_for_tpu():
    """The program's kernel gates ask `jax.default_backend()`, which is the
    CPU here; answer as the chip would while a step is traced, and keep the
    persistent compile cache out of it (a described chip cannot read an
    executable back, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    backend, cache_was_on = jax.default_backend, jax.config.jax_enable_compilation_cache
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.default_backend = backend
        jax.config.update("jax_enable_compilation_cache", cache_was_on)


def compile_step(
    cfg: ModelConfig, devices, *, quantize: str = "none", tp: int = 1,
    lanes: int = 16, pages: int = 2048, page_size: int = 16,
    max_seq_len: int = 4096, steps: int = 8,
    prefill: tuple[int, int] | None = None,
) -> str:
    """The compiled HLO text of the engine's decode step — of its prefill
    step at `prefill` = (rows, width) — for `devices[:tp]`, jitted as the
    engine jits it, from shapes alone. `quantize`: none | int8 | int4."""
    mesh = create_mesh(MeshConfig(tp=tp), devices=devices[:tp])
    repl = NamedSharding(mesh, P())

    def tree():
        params = init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
        if quantize == "none":
            return params
        return quant.quantize_params(params, cfg, bits=int(quantize[3:]))

    shapes = jax.eval_shape(tree)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, param_shardings(cfg, mesh, shapes),
    )
    pool_sh = paged_kv_sharding(mesh)
    pool = jax.eval_shape(
        lambda: init_paged_kv(cfg, pages, page_size, jnp.bfloat16))
    paged = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=pool_sh),
        pool,
    )
    pool_out = jax.tree.map(lambda s: pool_sh, pool)
    tables = max_seq_len // page_size

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    with compiling_for_tpu():
        if prefill is None:
            B = lanes
            lowered = jax.jit(
                engine_mod._decode_fn,
                static_argnames=("cfg", "greedy", "steps", "eos_id",
                                 "candidates", "mesh"),
                donate_argnames=("paged", "last_tokens", "seq_lens", "active",
                                 "state"),
                out_shardings=(repl, repl, repl, repl, pool_out, repl),
            ).lower(
                params, cfg, paged, arg((B,), jnp.int32), arg((B,), jnp.int32),
                arg((B, tables), jnp.int32), arg((B,), jnp.bool_),
                arg((B,), jnp.int32), arg((B, 2), jnp.int32),
                arg((B,), jnp.float32), arg((B,), jnp.float32),
                arg((B,), jnp.int32),
                greedy=True, steps=steps, eos_id=-1, candidates=0, mesh=mesh,
            )
        else:
            N, T = prefill
            lowered = jax.jit(
                engine_mod._prefill_fn,
                static_argnames=("cfg", "greedy", "candidates", "mesh"),
                donate_argnames=("paged", "state"),
                out_shardings=(repl, pool_out, repl),
            ).lower(
                params, cfg, paged, arg((N, T), jnp.int32),
                arg((N,), jnp.int32), arg((N,), jnp.int32),
                arg((N, tables), jnp.int32), arg((N, 2), jnp.int32),
                arg((N,), jnp.float32), arg((N,), jnp.float32),
                arg((N,), jnp.int32),
                greedy=True, candidates=0, mesh=mesh,
            )
        return lowered.compile().as_text()


# -- reading the module -----------------------------------------------------

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def computations(hlo: str) -> dict[str, list[tuple[str, str, str, str]]]:
    """{computation: [(name, result type, opcode, the whole line)]}."""
    out: dict[str, list[tuple[str, str, str, str]]] = {}
    current = None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            current.append((*m.groups(), line))
    return out


def fused_computations(hlo: str) -> set[str]:
    """The computations a fusion calls: their instructions materialise
    nothing themselves."""
    return set(re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", hlo))


def layer_body(hlo: str) -> str:
    """The name of the layer scan's body: of the `while` bodies that are not
    fused computations, the one that holds the most instructions the step
    spends its time in (fusions and custom calls)."""
    comps = computations(hlo)
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo))
    if not bodies:
        raise ValueError("the module holds no while loop")

    def weight(name):
        return sum(op in ("fusion", "custom-call", "convolution", "copy")
                   for _, _, op, _ in comps.get(name, []))

    return max(bodies, key=weight)


def census(hlo: str, body: str | None = None) -> list[dict]:
    """One dict per instruction of `body` (default: the layer body) that is
    not bookkeeping: name, op, result, `vmem` (the result lives in memory
    space S(1)), `carry_operands` (operands read straight from the loop's
    carry: weight stacks, pools) and `inside` (the dots, convolutions and
    dynamic-slices of a fusion's computation, each with its result)."""
    comps = computations(hlo)
    body = body or layer_body(hlo)
    carried = {name for name, _, op, _ in comps[body]
               if op == "get-tuple-element"}
    rows = []
    for name, result, op, line in comps[body]:
        if op in ("get-tuple-element", "parameter", "tuple", "constant",
                  "bitcast"):
            continue
        rest = line.split(f" {op}(", 1)[1]
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        called = re.search(r"calls=%?([\w.\-]+)", rest)
        inside = []
        if called:
            for n, r, o, more in comps.get(called.group(1), []):
                if o in ("dot", "convolution", "dynamic-slice", "copy",
                         "transpose"):
                    labels = re.search(r"dim_labels=[\w\->]+", more)
                    window = re.search(r"window=\{[^}]*\}", more)
                    inside.append(" ".join(filter(None, [
                        o, r, window and window.group(0),
                        labels and labels.group(0)])))
        rows.append({
            "name": name, "op": op, "result": result,
            "vmem": "S(1)" in result,
            "carry_operands": [o for o in operands if o in carried],
            "inside": inside,
        })
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a file under perfbench/configs/")
    ap.add_argument("--layers", type=int, help="depth (default: the file's)")
    ap.add_argument("--prefill", metavar="ROWSxWIDTH",
                    help="the prefill step at this shape, not the decode step")
    ap.add_argument("--quantize", choices=("none", "int8", "int4"),
                    help="default: the file's engine.quantize")
    ap.add_argument("--hlo", metavar="FILE",
                    help="also write the whole compiled module here")
    args = ap.parse_args()
    with open(args.config) as f:
        spec = json.load(f)
    eng = spec["engine"]
    cfg = model_config(spec, args.layers)
    prefill = (tuple(int(n) for n in args.prefill.split("x"))
               if args.prefill else None)
    hlo = compile_step(
        cfg, describe_v5e(), quantize=args.quantize or eng.get("quantize", "none"),
        tp=eng.get("tp", 1), lanes=eng["max_decode_slots"],
        pages=eng["num_pages"], page_size=eng["page_size"],
        max_seq_len=eng["max_seq_len"], steps=eng["decode_block_steps"],
        prefill=prefill,
    )
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    comps = computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo, re.M).group(1)
    print(f"# {cfg.name}: {'prefill ' + args.prefill if prefill else 'decode'}"
          f" step, {cfg.num_layers} layers, tp={eng.get('tp', 1)}")
    print("# entry computation: copies / transposes of a parameter")
    for name, result, op, _ in comps[entry]:
        if op in ("copy", "transpose"):
            print(f"  {op:<12} {name:<40} {result}")
    body = layer_body(hlo)
    print(f"# layer body {body}: operation, result, [VMEM], "
          "<- operands read from the carry")
    for row in census(hlo, body):
        print(f"  {row['op']:<12} {row['name']:<40} {row['result']}"
              f"{'  [VMEM]' if row['vmem'] else ''}"
              + (f"  <- {', '.join(row['carry_operands'])}"
                 if row["carry_operands"] else ""))
        for line in row["inside"]:
            print(f"      {line}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
