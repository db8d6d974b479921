"""On the chip, at the published widths: what the comparison with the plain
reference of `lfm2-24b-a2b-pp4` can and cannot see, and the state paths the
harness's one-window sample does not reach.

    python3 tests/perfbench/lfm2_moe_controls.py controls \
        --samples <dir> --seeds 1 2 3 --out <file.json> [--only sound int8_weights]
    python3 tests/perfbench/lfm2_moe_controls.py long --seed 7 --out <file.json>

`controls` lays faults over the REFERENCE (the served sample and the
program's replayed logits stay what the sound program produced; the
configuration's `compare` — perfbench/references/lfm2_moe.py, the sibling's
clause arithmetic over this model's `forward` — has to refuse what it
reads), on samples a run has left in perfbench/out/<cell>/ (copied aside as
<dir>/seed<N>.sample.json). Every fault is a change of the tree or of the
ModelConfig the reference reads, or of a function its layers call, so the
reference's file stays as it is:

  conv_dropped    the conv sees no column but the present one, every operator
  no_in_gate      B . left out: the conv runs over u alone
  no_out_gate     C . left out: W_out projects the conv's output alone
  qk_norm_off     no RMSNorm over the q and k heads
  rotary_off      no rotary embedding in the attention layers
  top2            2 experts a token for the configuration's 4
  no_expert_bias  the router chooses by score alone
  expert_layer_zeroed  ONE expert layer of eight contributes nothing
  int8_weights    every matrix rounded to int8 per output channel
  int4_weights    ... to the 15 levels of int4

`long` is the sibling script's (tests/perfbench/nemotron_h_controls.py
`run_long`, pointed at this configuration's file): a 200-token prompt (two
128-row windows of ONE dispatch: the chained conv columns) and a 600-token
prompt (a 512-wide chunk, then the tail from the slot's stored columns)
through the gateway while 62 other lanes decode, both compared with the
reference on the same weights.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import ROOT

import extension
import nemotron_h_controls as sibling

CONFIG = os.path.join(ROOT, "perfbench", "configs", "lfm2-24b-a2b-pp4.json")
CONTROLS = ("sound", "conv_dropped", "no_in_gate", "no_out_gate",
            "qk_norm_off", "rotary_off", "top2", "no_expert_bias",
            "expert_layer_zeroed", "int8_weights", "int4_weights")
# Controls that patch a function the reference's layers call.
PATCHED = {"no_in_gate", "no_out_gate", "int8_weights", "int4_weights"}


def load_spec() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


tree_of = sibling.tree_of      # (params, model_cfg) as the server child's


def faulted(control: str, params, cfg):
    """(params, cfg) with `control` laid over them (the function patches
    are `judged`'s: a second copy of the 10.5 GB tree does not fit)."""
    import jax.numpy as jnp

    layers = {k: [dict(p) for p in v] for k, v in params["layers"].items()}
    if control == "top2":
        cfg = dataclasses.replace(cfg, num_experts_per_tok=2)
    elif control == "rotary_off":
        cfg = dataclasses.replace(cfg, use_rope=False)
    elif control == "qk_norm_off":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    elif control == "conv_dropped":
        for p in layers["conv"]:
            p["conv_w"] = p["conv_w"].at[:-1].set(0)
    elif control == "no_expert_bias":
        for p in layers["moe"]:
            p["router_bias"] = jnp.zeros_like(p["router_bias"])
    elif control == "expert_layer_zeroed":
        middle = layers["moe"][len(layers["moe"]) // 2]
        middle["down"] = jnp.zeros_like(middle["down"])
    return {**params,
            "layers": {k: tuple(v) for k, v in layers.items()}}, cfg


_WRAPPED: dict = {}


def rewrapped(control: str, ref) -> dict:
    """The reference's layer functions under new function objects, one set
    a control: `forward` jits by function, so a control that patches what
    the layers call is traced by itself, once."""
    if control not in _WRAPPED:
        def wrap(fn):
            return lambda x, p, cfg: fn(x, p, cfg)
        _WRAPPED[control] = {k: wrap(fn) for k, fn in ref.LAYERS.items()}
    return _WRAPPED[control]


def judged(control: str, params, cfg, sample: dict, limits: dict,
           replayed) -> dict:
    ref = extension.load("references", limits["module"])
    plain = ref.f32, ref.in_gate, ref.out_gate, ref.LAYERS
    if control == "no_in_gate":
        ref.in_gate = lambda b, u: u
    if control == "no_out_gate":
        ref.out_gate = lambda c, y: y
    if control in ("int8_weights", "int4_weights"):
        # Every matrix, as the reference reads it (the tied one too).
        levels = 127 if control == "int8_weights" else 7
        ref.f32 = lambda w: plain[0](
            sibling.int_round(w, levels) if w.ndim >= 2 else w)
    if control in PATCHED:
        ref.LAYERS = rewrapped(control, ref)
    try:
        p, c = faulted(control, params, cfg)
        result = ref.compare(p, c, sample, limits, replayed=replayed)
    finally:
        ref.f32, ref.in_gate, ref.out_gate, ref.LAYERS = plain
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
        for control in args.only or CONTROLS:
            got = judged(control, params, cfg, sample, limits, replayed)
            out[str(seed)][control] = got
            print(seed, control, json.dumps(got), flush=True)
            with open(args.out, "w") as f:      # kept if a later one dies
                json.dump(out, f, indent=1)
        del params
    return 0


def run_long(args) -> int:
    """The sibling's, on this configuration's file."""
    sibling.CONFIG = CONFIG
    return sibling.run_long(args)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    sub = parser.add_subparsers(dest="what", required=True)
    c = sub.add_parser("controls")
    c.add_argument("--samples", required=True)
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--out", required=True)
    c.add_argument("--only", nargs="+", choices=CONTROLS,
                   help="these controls alone (all of them otherwise)")
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
