"""On the chip, at the published widths: what the comparison with the plain
reference of `openpangu-ultra-moe-ep32` can and cannot see, and the prefill
paths the harness's one-window sample does not reach.

    python3 tests/perfbench/pangu_ultra_moe_controls.py controls \
        --samples <dir> --seeds 1 2 3 --out <file.json> [--only sound fp8_row]
    python3 tests/perfbench/pangu_ultra_moe_controls.py long --seed 7 --out <file.json>

`controls` lays faults over the REFERENCE (the served sample and the
program's replayed logits stay what the sound program produced; the
configuration's `compare` — perfbench/references/pangu_ultra_moe.py, the
sibling's clause arithmetic over this model's `forward` — has to refuse
what it reads), on samples a run has left in perfbench/out/<cell>/ (copied
aside as <dir>/seed<N>.sample.json). Every fault is a change of the tree or
of the ModelConfig the reference reads, or of a function its layers call,
so the reference's file stays as it is:

  fp8_row             the cache row [latent | rotary key] rounded to an
                      8-bit float (3 bits of mantissa) between write and
                      read: one precision below the bfloat16 it is stated in
  int8_weights        every matrix rounded to int8 per output channel
  int4_weights        ... to the 15 levels of int4
  rope_score_dropped  the rotary part of the score left out (q_rope . k_r)
  post_norm_dropped   no second norm on any body's output
  top_half            half the configuration's experts a token (4 for 8)
  scale_off           routed_scaling_factor 1 for 2.5

`long` is the sibling script's (tests/perfbench/nemotron_h_controls.py
`run_long`, pointed at this configuration's file): a 200-token prompt (two
128-row windows of ONE dispatch, the second reading the first's rows
through the table) and a 600-token prompt (a 512-wide chunk, then the tail
in a second dispatch) through the gateway while 62 other lanes decode,
both compared with the reference on the same weights.
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

CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "openpangu-ultra-moe-ep32.json")
# A control that patches a function the reference's layers call, and the
# function's name in the reference (`stand_in` makes what replaces it).
PATCHED = {
    "fp8_row": "cached", "rope_score_dropped": "rope_score",
    "int8_weights": "f32", "int4_weights": "f32",
}
CONTROLS = ("sound", "fp8_row", "int8_weights", "int4_weights",
            "rope_score_dropped", "post_norm_dropped", "top_half", "scale_off")


def load_spec() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


tree_of = sibling.tree_of      # (params, model_cfg) as the server child's


def faulted(control: str, params, cfg):
    """(params, cfg) with `control` laid over them (the function patches
    are `judged`'s: a second copy of the 9.3 GB tree does not fit)."""
    layers = {k: [dict(p) for p in v] for k, v in params["layers"].items()}
    if control == "top_half":
        cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok // 2)
    elif control == "scale_off":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif control == "post_norm_dropped":
        cfg = dataclasses.replace(cfg, sandwich_norm=False)
    return {**params,
            "layers": {k: tuple(v) for k, v in layers.items()}}, cfg


def stand_in(control: str, plain: dict):
    """What replaces the reference's function PATCHED[control]; `plain`:
    the reference's own functions by name."""
    import jax

    # Every matrix, as the reference reads it (embed and head too).
    levels = {"int8_weights": 127, "int4_weights": 7}.get(control)

    def rounded(w):
        return plain["f32"](sibling.int_round(w, levels) if w.ndim >= 2 else w)

    return {
        # (Not a cast there and back: the chip's compiler may keep the
        # excess precision of such a pair.)
        "fp8_row": lambda row: jax.lax.reduce_precision(
            row, exponent_bits=8, mantissa_bits=3),
        "rope_score_dropped": lambda q_rope, k_r: 0.0,
        "int8_weights": rounded,
        "int4_weights": rounded,
    }[control]


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
    names = set(PATCHED.values()) | {"LAYERS"}
    plain = {name: getattr(ref, name) for name in names}
    if control in PATCHED:
        setattr(ref, PATCHED[control], stand_in(control, plain))
        ref.LAYERS = rewrapped(control, ref)
    try:
        p, c = faulted(control, params, cfg)
        result = ref.compare(p, c, sample, limits, replayed=replayed)
    finally:
        for name, fn in plain.items():
            setattr(ref, name, fn)
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
