"""The system under test, as one child process of perfbench/run.py.

Starts the program's own gateway (`gateway.server.build_server`, the same
servicer, interceptor, health and reflection services as
`python -m polykey_tpu.gateway.server`) over the program's own
`InferenceEngine` + `TpuService.create`, with three things the stock
entry point cannot take from outside:

- the model's widths come from the benchmark's configuration file and are
  registered in `MODEL_REGISTRY` under the file's name (Mistral-7B is not a
  preset of the package);
- the weights are the engine's seeded random init with `--seed` (the stock
  entry point always uses seed 0);
- the output head is narrowed to printable ASCII (the scale of every other
  vocabulary column is set to 0), so that with the ByteTokenizer every
  generated token streams to the client as exactly one character. Shapes,
  dtypes and every matmul are unchanged; without it a 32k-vocabulary model
  with random weights emits ids the ByteTokenizer drops and the client sees
  no token arrive.

After SIGTERM, and before it exits, the child compares the served greedy
sample that the parent wrote to `<out>/sample.json` with the plain
reference (perfbench/reference.py) on the same weights and writes
`<out>/reference.json`.

Only this process touches JAX: the parent never imports it.

A configuration whose model these functions do not fit names an adapter
of its own and a reference module of its own: `hook` and `run_reference`
below, under the contract in the docstring of perfbench/run.py.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import extension  # noqa: E402


def hook(spec: dict, name: str, default):
    """The function `name` of the configuration's adapter
    (perfbench/adapters/<spec["adapter"]>), or the harness's own."""
    if "adapter" not in spec:
        return default
    return getattr(extension.load("adapters", spec["adapter"]), name, default)


def engine_settings(spec: dict, tiny: bool) -> dict:
    """The file's engine group, with the toy-size overrides when tiny."""
    eng = dict(spec["engine"])
    if tiny:
        eng.update(spec["tiny"]["engine"])
    return eng


def model_config_from(spec: dict, tiny: bool):
    """A ModelConfig from the configuration file's HF-style keys."""
    from polykey_tpu.models.config import ModelConfig

    src = spec["tiny"]["model"] if tiny else spec
    heads = src["num_attention_heads"]
    experts = src.get("num_local_experts", 0)
    return ModelConfig(
        name=spec["name"] + ("-tiny" if tiny else ""),
        vocab_size=src["vocab_size"],
        hidden_size=src["hidden_size"],
        intermediate_size=src["intermediate_size"],
        num_layers=src["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=src["num_key_value_heads"],
        head_dim=src.get("head_dim") or src["hidden_size"] // heads,
        max_seq_len=src["engine_max_positions"],
        rope_theta=float(src["rope_theta"]),
        rms_norm_eps=float(src["rms_norm_eps"]),
        tie_embeddings=bool(src.get("tie_word_embeddings", False)),
        num_experts=experts,
        num_experts_per_tok=src.get("num_experts_per_tok", 0),
        moe_dispatch=bool(experts) and not tiny,
    )


def engine_config_from(spec: dict, tiny: bool):
    """The engine geometry the file states; everything else is the
    program's default (what a user gets)."""
    from polykey_tpu.engine.config import EngineConfig

    eng = engine_settings(spec, tiny)
    quantize = eng.get("quantize", "none")
    config = EngineConfig(
        model=spec["name"] + ("-tiny" if tiny else ""),
        dtype=eng["dtype"],
        # A tree the benchmark made is int8 already (perfbench/weights.py);
        # the engine's own quantizer then has nothing to do.
        quantize=quantize != "none" and eng.get("weights") != "hashed_int8",
        quantize_bits=4 if quantize == "int4" else 8,
        tp=eng.get("tp", 1),
        compile_warmup=True,
        # Greedy requests only: the sampled variants of every step would
        # be compiled and loaded for nothing. A configuration whose
        # requests are sampled (its "sampling" group) has them on the
        # measured path.
        warm_sampled_variants=bool(spec.get("sampling")),
    )
    geometry = {k: eng[k] for k in (
        "max_decode_slots", "page_size", "num_pages", "max_seq_len",
        "decode_block_steps", "lookahead_blocks", "adaptive_block",
        "prefix_cache", "max_new_tokens_cap",
    ) if k in eng}
    if "prefill_buckets" in eng:
        geometry["prefill_buckets"] = tuple(eng["prefill_buckets"])
    return dataclasses.replace(config, **geometry)


def made_weights(spec: dict, tiny: bool, config, model_cfg, seed: int):
    """The tree for `engine.weights = "hashed_int8"`; None lets the engine
    run the package's own seeded init (and quantizer)."""
    if engine_settings(spec, tiny).get("weights") != "hashed_int8":
        return None
    import jax
    import jax.numpy as jnp

    import weights
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    # The mesh the engine builds for itself from the same config.
    mesh = create_mesh(MeshConfig(tp=config.tp),
                       devices=jax.devices()[:config.tp])
    return weights.hashed_int8(model_cfg, mesh, jnp.dtype(config.dtype), seed)


def narrow_head(engine) -> None:
    """Zero the output head outside printable ASCII (see module doc)."""
    import jax
    import jax.numpy as jnp

    from traffic import FIRST_ID, LAST_ID

    head = engine.params.get("lm_head")
    if head is None:
        raise SystemExit("perfbench: tied embeddings are not supported")
    vocab = head.shape[-1]
    ids = jnp.arange(vocab)
    mask = ((ids >= FIRST_ID) & (ids <= LAST_ID))
    def narrow(w):
        # Same shape, dtype and sharding as the served leaf: the warmed
        # executables take it without a recompile.
        out = jax.jit(lambda x: x * mask.astype(x.dtype))(w)
        return jax.device_put(out, w.sharding)

    if hasattr(head, "q"):      # QuantizedTensor: per-column scale
        narrowed = head.replace(s=narrow(head.s))
    else:
        narrowed = narrow(head)
    engine.params = {**engine.params, "lm_head": narrowed}


def release_pools(engine) -> None:
    """The engine has stopped: its KV pools make room for the reference's
    float32 temporaries."""
    for pool in ("paged", "d_paged"):
        if hasattr(engine, pool):
            setattr(engine, pool, None)


def compare_with_reference(engine, sample: dict, limits: dict) -> dict:
    """The verdict of the plain reference: perfbench/reference.py, or the
    module the "reference" group names (its `compare`, or its `forward`
    under the shared teacher forcing and `judge`); for a configuration
    whose requests are sampled, `reference.judge_sampled` on the sample's
    sampled companion beside it."""
    import reference

    forward = reference.forward
    if "module" not in limits:
        result = reference.compare(engine.params, engine.model_cfg, sample,
                                   limits)
    else:
        module = extension.load("references", limits["module"])
        forward = module.forward
        if hasattr(module, "compare"):
            result = module.compare(engine.params, engine.model_cfg, sample,
                                    limits)
        else:
            result = reference.compare(engine.params, engine.model_cfg,
                                       sample, limits, forward_fn=forward)
        result = {**result, "module": limits["module"]}
    if "sampled" in sample:
        drawn = reference.judge_sampled(forward, engine.params,
                                        engine.model_cfg, sample, limits)
        result = {**result, "ok": result["ok"] and drawn["ok"],
                  "why": result["why"] + drawn["why"],
                  "checks": result["checks"] + ", " + drawn["checks"],
                  "sampled": drawn}
    return result


def run_reference(engine, out_dir: str, spec: dict) -> None:
    sample_path = os.path.join(out_dir, "sample.json")
    if not os.path.exists(sample_path):
        return
    with open(sample_path) as f:
        sample = json.load(f)
    try:
        hook(spec, "release", release_pools)(engine)
        result = compare_with_reference(engine, sample, spec["reference"])
    except Exception as e:      # reported as an incorrect run, not a lost one
        result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
    with open(os.path.join(out_dir, "reference.json"), "w") as f:
        json.dump(result, f)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--address", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    with open(args.config) as f:
        spec = json.load(f)

    from polykey_tpu.engine.config import enable_persistent_compile_cache
    from polykey_tpu.engine.device import require_accelerator
    from polykey_tpu.engine.engine import InferenceEngine
    from polykey_tpu.gateway.health import HealthService
    from polykey_tpu.gateway.jsonlog import Logger
    from polykey_tpu.gateway.server import build_server
    from polykey_tpu.gateway.tpu_service import TpuService
    from polykey_tpu.models.config import MODEL_REGISTRY
    from polykey_tpu.obs import Observability

    logger = Logger(level="info")
    model_cfg = hook(spec, "model_config", model_config_from)(spec, args.tiny)
    config = hook(spec, "engine_config", engine_config_from)(spec, args.tiny)
    # Over any preset of the package, under the name the engine looks up.
    MODEL_REGISTRY[config.model] = model_cfg

    enable_persistent_compile_cache()
    identity = require_accelerator()
    if not args.tiny and identity["platform"] != "tpu":
        logger.error("perfbench needs a TPU", **identity)
        return 1
    obs = Observability()
    health = HealthService()
    # JAX keys are 32-bit: fold a driver-sized seed into that range.
    seed = args.seed % (2**31 - 1)
    engine = InferenceEngine(
        config,
        params=hook(spec, "weights", made_weights)(
            spec, args.tiny, config, model_cfg, seed),
        health=health, logger=logger, seed=seed,
    )
    narrow_head(engine)
    # A supervised restart would rebuild the engine without the narrowed
    # head; `correct` requires zero restarts anyway.
    service = TpuService.create(engine, health=health, logger=logger, obs=obs)
    logger.info("engine initialized", **identity, model=config.model,
                seed=args.seed)
    server, health, _ = build_server(
        service, logger, args.address, health=health, obs=obs
    )
    quit_event = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: quit_event.set())
    server.start()
    logger.info("server starting", address=args.address)
    quit_event.wait()
    logger.info("server shutting down")
    health.shutdown()
    server.stop(grace=2).wait()
    live = service.engine
    service.close()
    run_reference(live, args.out, spec)
    logger.info("server stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
