"""On the chip, at the published widths: what the comparison with the plain
reference of `ouro-2.6b` can and cannot see.

    python3 tests/perfbench/ouro_controls.py controls \
        --samples <dir> --seeds 1 2 3 --out <file.json> [--only sound int8_weights]

`controls` lays faults over the REFERENCE (the served sample and the
program's replayed logits stay what the sound program produced; the
configuration's `compare` — perfbench/references/ouro.py, the sibling's
clause arithmetic over this model's `forward` — has to refuse what it
reads), on samples a run has left in perfbench/out/<cell>/ (copied aside as
<dir>/seed<N>.sample.json). Every fault is a change of a function the
reference's `forward_passes` calls, so the reference's file stays as it
is; one, `other_seed`, hands the reference another seed's tree:

  other_seed       the reference reads the weights of seed + 1
  three_passes     one pass fewer than `loop_steps`
  no_norm_between  the final norm after the LAST pass alone
  shared_cache     pass u attends over pass 0's keys and values
  no_post_norms    N2 and N4 left out (a pre-normed block)
  int8_weights     every matrix rounded to int8 per output channel
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

CONFIG = os.path.join(ROOT, "perfbench", "configs", "ouro-2.6b.json")
# A control that patches a function the reference's forward calls, and the
# function's name in the reference (`stand_in` makes what replaces it).
PATCHED = {
    "three_passes": "passes", "no_norm_between": "closing_norm",
    "shared_cache": "own_cache", "no_post_norms": "post_norm",
    "int8_weights": "f32",
}
CONTROLS = ("sound", "other_seed", "three_passes", "no_norm_between",
            "shared_cache", "no_post_norms", "int8_weights")


def load_spec() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def tree_of(spec: dict, seed: int, tiny: bool = False):
    """(params, model_cfg) as the server child's engine makes them for
    `seed`: the package's own seeded init on one device (the file's
    `engine.weights` is "package_init": the adapter brings no `weights`)."""
    import jax
    import jax.numpy as jnp

    import server_child
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh
    from polykey_tpu.parallel.sharding import init_sharded_params

    cfg = extension.load("adapters", spec["adapter"]).model_config(spec, tiny)
    dtype = server_child.engine_settings(spec, tiny)["dtype"]
    mesh = create_mesh(MeshConfig(tp=1), devices=jax.devices()[:1])
    return init_sharded_params(
        jax.random.PRNGKey(seed % (2**31 - 1)), cfg, mesh,
        jnp.dtype(dtype)), cfg


def stand_in(control: str, plain: dict):
    """What replaces the reference's function PATCHED[control]; `plain`:
    the reference's own functions by name."""
    held = {}

    def shared(u, layer, k, v):
        if u == 0:
            held[layer] = (k, v)
        return held[layer]

    def rounded(w):         # every matrix, as the reference reads it
        return plain["f32"](sibling.int_round(w) if w.ndim >= 2 else w)

    return {
        "three_passes": lambda cfg: cfg.loop_steps - 1,
        "no_norm_between": lambda x, w, eps, u, last: (
            plain["rms_norm"](x, w, eps) if u == last else x),
        "shared_cache": shared,
        "no_post_norms": lambda y, w, eps: y,
        "int8_weights": rounded,
    }[control]


def sound_twin(params, cfg, sample: dict, limits: dict):
    """The bfloat16 twin's distance from the SOUND reference, a served
    position, on the seed's own tree: the yardstick of every control of
    that seed (a control stands for a fault of the program, and a faulty
    program is held to the sound function's sensitivity)."""
    import numpy as np

    ref = extension.load("references", limits["module"])
    prompt, out = sample["prompt_ids"], sample["output_ids"]
    fed, first = prompt + out[:-1], len(prompt) - 1
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    return ref.apart(
        ref.forward(params, cfg, fed, keep=ref.served)[first:, allowed],
        ref.forward(params, cfg, fed)[first:, allowed])


def judged(control: str, params, cfg, sample: dict, limits: dict,
           replayed, twin=None) -> dict:
    """`compare`'s verdict with `control` laid over the reference
    (`other_seed`: `params` is the other seed's tree, the caller's). The
    reference jits its layers by function: what it traced is dropped
    around a patch, so the patched functions are traced by themselves."""
    import jax

    ref = extension.load("references", limits["module"])
    names = set(PATCHED.values()) | {"rms_norm"}
    plain = {name: getattr(ref, name) for name in names}
    if control in PATCHED:
        setattr(ref, PATCHED[control], stand_in(control, plain))
        jax.clear_caches()
    try:
        result = ref.compare(params, cfg, sample, limits, replayed=replayed,
                             twin=twin)
    finally:
        for name, fn in plain.items():
            setattr(ref, name, fn)
        if control in PATCHED:
            jax.clear_caches()
    keep = ("ok", "why", "outliers", "mean_margin", "exact", "max_margin",
            "logit_floor", "logit_median", "logit_distance", "twin_floor",
            "twin_median", "twin_distance", "logit_distance_by_token",
            "twin_distance_by_token", "replayed", "logit_std")
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
        twin = sound_twin(params, cfg, sample, limits)
        out[str(seed)] = {}

        def record(control, tree):
            got = judged(control, tree, cfg, sample, limits, replayed, twin)
            out[str(seed)][control] = got
            print(seed, control, json.dumps(got), flush=True)
            with open(args.out, "w") as f:      # kept if a later one dies
                json.dump(out, f, indent=1)

        for control in wanted:
            if control != "other_seed":
                record(control, params)
        del params
        if "other_seed" in wanted:
            record("other_seed", tree_of(spec, seed + 1, args.tiny)[0])
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    sub = parser.add_subparsers(dest="what", required=True)
    c = sub.add_parser("controls")
    c.add_argument("--samples", required=True)
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--out", required=True)
    c.add_argument("--only", nargs="+", choices=CONTROLS,
                   help="these controls alone (all of them otherwise)")
    c.add_argument("--tiny", action="store_true",
                   help="the CPU rehearsal at toy size")
    c.set_defaults(fn=run_controls)
    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
