"""On the chip, at the published widths: what the comparison with the plain
reference of `nemotron-3-super-ep4` can and cannot see, and the state paths
the harness's one-window sample does not reach.

    python3 tests/perfbench/nemotron_h_controls.py controls \
        --samples <dir> --seeds 1 2 3 --out <file.json>
    python3 tests/perfbench/nemotron_h_controls.py long --seed 7 --out <file.json>

`controls` lays faults over the REFERENCE (the served sample and the
program's replayed logits stay what the sound program produced; the
configuration's `compare` - margins, `logit_floor`, `logit_distance`,
perfbench/references/nemotron_h.py - has to refuse what it reads), on
samples a run has left in perfbench/out/<cell>/ (copied aside as
<dir>/seed<N>.sample.json). Every fault is a change of the tree or of the
ModelConfig the reference reads, or of a function its layers call, so
the reference's file stays as it is:

  top11          11 experts a token for the configuration's 22
  state_zeroed   ONE mixer layer's state is zero before every step
                 (A = -inf there: nothing is carried from token to token)
  state_zeroed_all  the same in every mixer layer
  conv_dropped   the conv sees no column but the present one, in every mixer
  no_shared      the shared expert contributes nothing
  int8_weights   every matrix rounded to int8 per output channel
  int4_weights   ... to the 15 levels of int4
  bf16_state     the recurrent state rounded to bfloat16 after every step
                 (reduce_precision; reported whatever it reads)
  rotary_on      rotary embedding in the one attention layer (reported)

`long` serves a 200-token prompt (two 128-row windows of ONE dispatch: the
chained state path) and a 600-token prompt (a 512-wide chunk, then the tail
from the slot's stored state) through the gateway while 62 other lanes
decode, then compares both with the reference on the same weights.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import ROOT

import extension
import reference

CONFIG = os.path.join(ROOT, "perfbench", "configs", "nemotron-3-super-ep4.json")
CONTROLS = ("sound", "top11", "state_zeroed", "state_zeroed_all",
            "conv_dropped", "no_shared", "int8_weights", "int4_weights",
            "bf16_state", "rotary_on")


def load_spec() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def tree_of(spec: dict, seed: int, tiny: bool = False):
    """(params, model_cfg) as the server child makes them for `seed`."""
    import types

    import server_child

    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, tiny)
    engine_config = types.SimpleNamespace(
        dtype=server_child.engine_settings(spec, tiny)["dtype"])
    return adapter.weights(spec, tiny, engine_config, cfg,
                           seed % (2**31 - 1)), cfg


def int_round(w, levels: int = 127):
    """Per output channel, symmetric: what an int8 (levels 127) or int4
    (levels 7) weight path would read."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                    keepdims=True) / levels
    q = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return (q * scale).astype(w.dtype)


def faulted(control: str, params, cfg, ref):
    """(params, cfg) with `control` laid over them (`ref.recur` and
    `ref.f32` are patched by the caller for bf16_state and int8_weights: a
    second copy of the 9.3 GB tree does not fit beside the first)."""
    import jax.numpy as jnp

    layers = {k: [dict(p) for p in v] for k, v in params["layers"].items()}
    if control == "top11":
        cfg = dataclasses.replace(cfg, num_experts_per_tok=11)
    elif control == "rotary_on":
        cfg = dataclasses.replace(cfg, use_rope=True)
    elif control in ("state_zeroed", "state_zeroed_all"):
        middle = len(layers["mamba"]) // 2
        for i, p in enumerate(layers["mamba"]):
            if control == "state_zeroed_all" or i == middle:
                p["A_log"] = jnp.full_like(p["A_log"], 80.0)
    elif control == "conv_dropped":
        for p in layers["mamba"]:
            p["conv_w"] = p["conv_w"].at[:-1].set(0)
    elif control == "no_shared":
        for p in layers["moe"]:
            p["shared_down"] = jnp.zeros_like(p["shared_down"])
    return {**params,
            "layers": {k: tuple(v) for k, v in layers.items()}}, cfg


_WRAPPED: dict = {}


def rewrapped(control: str, ref) -> dict:
    """The reference's layer functions under new function objects, one
    set a control: `forward` jits by function, so a control that patches
    what the layers call is traced by itself, once."""
    if control not in _WRAPPED:
        def wrap(fn):
            return lambda x, p, cfg: fn(x, p, cfg)
        _WRAPPED[control] = {k: wrap(fn) for k, fn in ref.LAYERS.items()}
    return _WRAPPED[control]


def judged(control: str, params, cfg, sample: dict, limits: dict,
           replayed) -> dict:
    import jax

    ref = extension.load("references", limits["module"])
    plain = ref.recur, ref.f32, ref.LAYERS
    if control == "bf16_state":
        # reduce_precision: a convert to bfloat16 and back is removed by
        # the compiler (excess precision is allowed by default).
        ref.recur = lambda h, decay, add: jax.lax.reduce_precision(
            plain[0](h, decay, add), exponent_bits=8, mantissa_bits=7)
    if control in ("int8_weights", "int4_weights"):
        # Every matrix, as the reference reads it (embedding rows too).
        levels = 127 if control == "int8_weights" else 7
        ref.f32 = lambda w: plain[1](
            int_round(w, levels) if w.ndim >= 2 else w)
    if control in ("bf16_state", "int8_weights", "int4_weights"):
        ref.LAYERS = rewrapped(control, ref)
    try:
        p, c = faulted(control, params, cfg, ref)
        result = ref.compare(p, c, sample, limits, replayed=replayed)
    finally:
        ref.recur, ref.f32, ref.LAYERS = plain
    keep = ("ok", "why", "outliers", "mean_margin", "exact", "max_margin",
            "logit_floor", "logit_distance", "logit_distance_by_token",
            "replayed", "logit_std")
    return {k: result[k] for k in keep}


def run_controls(args) -> int:
    spec = load_spec()
    limits = spec["reference"]
    how = dict(limits["replay"])
    adapter = extension.load("adapters", how.pop("adapter"))
    out = {}
    for seed in args.seeds:
        with open(os.path.join(args.samples, f"seed{seed}.sample.json")) as f:
            sample = json.load(f)
        params, cfg = tree_of(spec, seed, args.tiny)
        # The program's side is the same under every fault: once a seed.
        replayed = adapter.replay(params, cfg, sample["prompt_ids"],
                                  sample["output_ids"], **how)
        out[str(seed)] = {}
        for control in CONTROLS:
            got = judged(control, params, cfg, sample, limits, replayed)
            out[str(seed)][control] = got
            print(seed, control, json.dumps(got), flush=True)
            with open(args.out, "w") as f:      # kept if a later one dies
                json.dump(out, f, indent=1)
        del params
    return 0


# -- long prompts under load -------------------------------------------------


def run_long(args) -> int:
    """Parent part: never imports JAX while the child holds the chip."""
    import grpc

    import loadgen
    import run as bench
    import traffic
    from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

    spec = load_spec()
    out_dir = os.path.join(ROOT, "perfbench", "out", "nemotron-h-long")
    os.makedirs(out_dir, exist_ok=True)
    address = f"127.0.0.1:{bench.free_port()}"
    log = open(os.path.join(out_dir, "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "perfbench", "server_child.py"),
         "--config", CONFIG, "--seed", str(args.seed), "--address", address,
         "--out", out_dir] + (["--tiny"] if args.tiny else []),
        cwd=ROOT, env=bench.child_env(args.tiny, 1), stdout=log,
        stderr=subprocess.STDOUT, start_new_session=True)
    samples = {}
    try:
        bench.wait_serving(proc, address)
        rng = random.Random(f"{args.seed}/long")
        # The CPU rehearsal: a quarter of every length (buckets of 64).
        scale = 4 if args.tiny else 1
        stop = threading.Event()

        def filler(i: int) -> None:
            with grpc.insecure_channel(address) as channel:
                stub = PolykeyServiceStub(channel)
                while not stop.is_set():
                    text = "".join(random.Random(f"{args.seed}/f/{i}").choices(
                        traffic.ALPHABET, k=(99 + i) // scale))
                    loadgen.stream(stub, text, 512 // scale,
                                   loadgen.new_record(i, 0, len(text) + 1, 512))

        fillers = [threading.Thread(target=filler, args=(i,), daemon=True)
                   for i in range(62)]
        for t in fillers:
            t.start()
        time.sleep(8.0)                 # the 62 lanes are decoding
        tools = bench.Tools(address)
        before = tools.stats()
        with grpc.insecure_channel(address) as channel:
            stub = PolykeyServiceStub(channel)
            for tokens in (200 // scale, 600 // scale):
                prompt = "".join(rng.choices(traffic.ALPHABET, k=tokens - 1))
                record = loadgen.new_record(-1, 0, tokens, 32)
                loadgen.stream(stub, prompt, 32, record, keep_text=True)
                text = "".join(record.pop("text"))
                samples[tokens] = {
                    "prompt_ids": [1] + [3 + b for b in prompt.encode()],
                    "output_ids": [3 + b for b in text.encode()],
                    "allowed_first": traffic.FIRST_ID,
                    "allowed_last": traffic.LAST_ID,
                }
        after = tools.stats()
        tools.close()
        stop.set()
        counters = {k: after[k] - before[k] for k in (
            "state_slots_reset", "state_windows_chained",
            "state_chunks_resumed")}
        counters["slots_busy"] = after["slots_busy"]
    finally:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        log.close()
    # The chip is free: the reference on the same seeded tree.
    params, cfg = tree_of(spec, args.seed, args.tiny)
    ref = extension.load("references", spec["reference"]["module"])
    result = {"seed": args.seed, "counters": counters}
    for tokens, sample in samples.items():
        got = reference.compare(params, cfg, sample, spec["reference"],
                                forward_fn=ref.forward)
        result[str(tokens)] = {k: got[k] for k in (
            "ok", "why", "outliers", "mean_margin", "exact", "max_margin",
            "margins")}
        # A greedy stream of seeded weights can fall into one repeated
        # character, and then the margins compare little: say so.
        result[str(tokens)]["distinct_served"] = len(set(sample["output_ids"]))
        print(tokens, json.dumps(result[str(tokens)]), flush=True)
    print(json.dumps(counters))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    sub = parser.add_subparsers(dest="what", required=True)
    c = sub.add_parser("controls")
    c.add_argument("--samples", required=True)
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--out", required=True)
    c.set_defaults(fn=run_controls)
    long = sub.add_parser("long")
    long.add_argument("--seed", type=int, required=True)
    long.add_argument("--out", required=True)
    long.set_defaults(fn=run_long)
    for mode in (c, long):
        mode.add_argument("--tiny", action="store_true",
                          help="the CPU rehearsal at toy size")
    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
