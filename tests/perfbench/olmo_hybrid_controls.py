"""On the chip, at the published widths: what the comparison with the plain
reference of `olmo-hybrid-7b-pp2` can and cannot see, and the state paths
the harness's one-window sample does not reach.

    python3 tests/perfbench/olmo_hybrid_controls.py controls \
        --samples <dir> --seeds 1 2 3 --out <file.json> [--only sound int8_weights]
    python3 tests/perfbench/olmo_hybrid_controls.py long --seed 7 --out <file.json>

`controls` lays faults over the REFERENCE (the served sample and the
program's replayed logits stay what the sound program produced; the
configuration's `compare` — perfbench/references/olmo_hybrid.py, the
sibling's clause arithmetic over this model's `forward` — has to refuse
what it reads), on samples a run has left in perfbench/out/<cell>/ (copied
aside as <dir>/seed<N>.sample.json). Every fault is a change of a function
the reference's layers call, so the reference's file stays as it is; one,
`other_seed`, hands the reference another seed's tree:

  other_seed        the reference reads the weights of seed + 1
  beta_unscaled     beta = sigmoid(b), in (0, 1): the sibling's delta rule
  pre_norm          an RMSNorm (gain 1) before every body beside its post-norm
  qk_norm_per_head  the q/k norm a head at a time (its part of the gain)
  rope_on           a rotary embedding on q and k (theta 500000)
  bf16_state        S rounded to bfloat16 after every token
  int8_weights      every matrix rounded to int8 per output channel

`long` is the sibling script's (tests/perfbench/nemotron_h_controls.py
`run_long`, pointed at this configuration's file): a 200-token prompt (two
128-row windows of ONE dispatch: S and the conv columns chained row to row)
and a 600-token prompt (a 512-wide chunk, then the tail from the slot's
stored state) through the gateway while 62 other lanes decode, both
compared with the reference on the same weights.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import ROOT

import extension
import nemotron_h_controls as sibling

CONFIG = os.path.join(ROOT, "perfbench", "configs", "olmo-hybrid-7b-pp2.json")
# A control that patches a function the reference's layers call, and the
# function's name in the reference (`stand_in` makes what replaces it).
PATCHED = {
    "beta_unscaled": "strength", "pre_norm": "read",
    "qk_norm_per_head": "qk_normed", "rope_on": "positioned",
    "bf16_state": "carried", "int8_weights": "f32",
}
CONTROLS = ("sound", "other_seed", "beta_unscaled", "pre_norm",
            "qk_norm_per_head", "rope_on", "bf16_state", "int8_weights")
ROPE_THETA = 500_000.0      # the family's other members'


def load_spec() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


tree_of = sibling.tree_of      # (params, model_cfg) as the server child's


def stand_in(control: str, plain: dict):
    """What replaces the reference's function PATCHED[control]; `plain`:
    the reference's own functions by name."""
    import jax
    import jax.numpy as jnp

    def rounded(w):         # every matrix, as the reference reads it
        return plain["f32"](sibling.int_round(w) if w.ndim >= 2 else w)

    def per_head(y, gain, heads, eps):
        T = y.shape[0]
        return plain["rms_norm"](
            y.reshape(T, heads, -1), plain["f32"](gain).reshape(heads, -1),
            eps).reshape(T, -1)

    def rotated(x, positions):
        """Rotate-half over the whole head, x [T, heads, dim]."""
        half = x.shape[-1] // 2
        freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angles = positions[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    return {
        "beta_unscaled": jax.nn.sigmoid,
        "pre_norm": lambda x, eps: plain["rms_norm"](
            x, jnp.ones(x.shape[-1], jnp.float32), eps),
        "qk_norm_per_head": per_head,
        "rope_on": rotated,
        # (Not a cast there and back: the chip's compiler may keep the
        # excess precision of such a pair, and the control then reads as
        # the sound reference does, digit for digit.)
        "bf16_state": lambda S: jax.lax.reduce_precision(
            S, exponent_bits=8, mantissa_bits=7),
        "int8_weights": rounded,
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
    """`compare`'s verdict with `control` laid over the reference
    (`other_seed`: `params` is the other seed's tree, the caller's)."""
    ref = extension.load("references", limits["module"])
    names = set(PATCHED.values()) | {"LAYERS", "rms_norm"}
    plain = {name: getattr(ref, name) for name in names}
    if control in PATCHED:
        setattr(ref, PATCHED[control], stand_in(control, plain))
        ref.LAYERS = rewrapped(control, ref)
    try:
        result = ref.compare(params, cfg, sample, limits, replayed=replayed)
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
    wanted = args.only or CONTROLS
    out = {}
    for seed in args.seeds:
        with open(os.path.join(args.samples, f"seed{seed}.sample.json")) as f:
            sample = json.load(f)
        params, cfg = tree_of(spec, seed, args.tiny)
        # The program's side is the same under every fault: once a seed.
        replayed = adapter.replay(params, cfg, sample["prompt_ids"],
                                  sample["output_ids"], **how)
        out[str(seed)] = {}

        def record(control, tree):
            got = judged(control, tree, cfg, sample, limits, replayed)
            out[str(seed)][control] = got
            print(seed, control, json.dumps(got), flush=True)
            with open(args.out, "w") as f:      # kept if a later one dies
                json.dump(out, f, indent=1)

        for control in wanted:
            if control != "other_seed":
                record(control, params)
        del params          # two 8.2 GB trees do not fit side by side
        if "other_seed" in wanted:
            record("other_seed", tree_of(spec, seed + 1, args.tiny)[0])
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
