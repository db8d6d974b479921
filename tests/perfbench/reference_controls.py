"""Wrong-path controls of the comparison with the plain reference.

A disagreement between the served path and the reference is symmetric, so a
fault of the served path is rehearsed by laying it over the REFERENCE: the
served sample stays what the sound program produced, the reference is
perturbed, and `reference.judge` has to refuse what it reads. No file of the
program changes. The controls (ISSUE 29):

  other_seed      the reference reads another seed's tree
  layer_skipped   one layer contributes nothing (its two output projections 0)
  no_rotary       the rotary embedding is the identity
  top1_routing    one expert per token instead of the configuration's two
  kv_head_zeroed  one KV head's values are 0 in one layer: what a paged pool
                  that addressed the wrong head there would serve
  kv_head_all_layers  the same head, in every layer
  attention_off   attention contributes nothing in any layer: whether the
                  comparison sees the KV path at all
  experts_scaled  every expert's output times 1.02, in all layers: small on
                  every token, the case a limit on the LARGEST margin passes
  int4_weights    the lower-precision control: every int8 weight rounded to
                  the 15 levels of int4; at each position of the same prompt
                  and served tokens, the margin of the token IT puts first

Used by tests/perfbench/test_perfbench_reference_rule.py at toy size on the
CPU, and as a program on the chip at the cell's own size, on samples a run
has left in perfbench/out/<cell>/ (copied aside as <dir>/seed<N>.sample.json):

    python3 tests/perfbench/reference_controls.py --config mixtral-8x7b-tp4 \
        --samples <dir> --seeds 1 2 3 --out <file.json>
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import types

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)

import reference
import server_child

PARAM_CONTROLS = ("layer_skipped", "kv_head_zeroed", "kv_head_all_layers",
                  "attention_off", "experts_scaled")
# Laid over the sample's own tree; `other_seed` takes another tree and
# `int4_weights` consumes the one it is given (`read_int4`).
SAME_TREE_CONTROLS = PARAM_CONTROLS + ("no_rotary", "top1_routing")


def scale_columns(w, layer, columns, factor: float):
    """Output columns `columns` of `w`, in `layer`, times `factor`: on the
    per-column scale of an int8 weight, on the weight itself otherwise."""
    if hasattr(w, "q"):
        return w.replace(s=w.s.at[layer, ..., columns].multiply(factor))
    return w.at[layer, ..., columns].multiply(factor)


def perturbed(name: str, params: dict, cfg) -> dict:
    """The tree of one of PARAM_CONTROLS (shares every untouched leaf)."""
    layers = dict(params["layers"])
    mid, everything = cfg.num_layers // 2, slice(None)
    ffn = "experts" if "experts" in layers else "mlp"
    if name == "layer_skipped":
        layers["attn"] = {**layers["attn"], "wo": scale_columns(
            layers["attn"]["wo"], mid, everything, 0.0)}
        layers[ffn] = {**layers[ffn], "down": scale_columns(
            layers[ffn]["down"], mid, everything, 0.0)}
    elif name in ("kv_head_zeroed", "kv_head_all_layers"):
        where = mid if name == "kv_head_zeroed" else everything
        layers["attn"] = {**layers["attn"], "wv": scale_columns(
            layers["attn"]["wv"], where, slice(0, cfg.head_dim), 0.0)}
    elif name == "attention_off":
        layers["attn"] = {**layers["attn"], "wo": scale_columns(
            layers["attn"]["wo"], everything, everything, 0.0)}
    elif name == "experts_scaled":
        layers[ffn] = {**layers[ffn], "down": scale_columns(
            layers[ffn]["down"], everything, everything, 1.02)}
    else:
        raise KeyError(name)
    return {**params, "layers": layers}


@contextlib.contextmanager
def no_rotary():
    """`reference.rotary` is the identity inside. `reference.layer` is
    swapped for a copy too: `forward` jits it, and the copy's trace cannot
    be one that an unperturbed call left in jit's cache."""
    rotary, layer = reference.rotary, reference.layer
    reference.rotary = lambda x, positions, theta: x
    reference.layer = types.FunctionType(
        layer.__code__, layer.__globals__, "layer_no_rotary")
    try:
        yield
    finally:
        reference.rotary, reference.layer = rotary, layer


def to_int4(params: dict) -> dict:
    """Every int8 weight rounded to int4's levels (per output channel, kept
    in the int8 container: 18 * [-7, 7]), IN PLACE: the tree is donated, so
    two 47 B trees never exist."""
    import jax
    import jax.numpy as jnp

    is_q = lambda w: hasattr(w, "q")  # noqa: E731

    def requantize(tree):
        def leaf(w):
            if not is_q(w):
                return w
            q4 = jnp.round(w.q.astype(jnp.float32) * (7.0 / 127.0))
            return w.replace(q=(q4 * 18.0).astype(jnp.int8),
                             s=w.s * (127.0 / (7.0 * 18.0)))
        return jax.tree.map(leaf, tree, is_leaf=is_q)

    return jax.jit(requantize, donate_argnums=0)(params)


def read(name: str, params: dict, cfg, sample: dict, limits: dict) -> dict:
    """The verdict `reference.compare` gives with one of SAME_TREE_CONTROLS
    laid over the reference. (`other_seed` is `reference.compare` itself on
    another seed's tree.)"""
    if name in PARAM_CONTROLS:
        return reference.compare(perturbed(name, params, cfg), cfg, sample,
                                 limits)
    if name == "no_rotary":
        with no_rotary():
            return reference.compare(params, cfg, sample, limits)
    if name == "top1_routing":
        one = dataclasses.replace(cfg, num_experts_per_tok=1)
        return reference.compare(params, one, sample, limits)
    raise KeyError(name)


def read_int4(params: dict, cfg, sample: dict, limits: dict) -> dict:
    """The lower-precision control. CONSUMES `params` (see `to_int4`)."""
    import numpy as np

    prompt, served = sample["prompt_ids"], sample["output_ids"]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[sample["allowed_first"]:sample["allowed_last"] + 1] = True
    tokens = prompt + served[:-1]
    sound = reference.forward(params, cfg, tokens)[len(prompt) - 1:]
    low = reference.forward(to_int4(params), cfg, tokens)[len(prompt) - 1:]
    sound, low = (np.where(allowed, rows, -np.inf) for rows in (sound, low))
    margins = [float(row.max() - row[int(np.argmax(other))])
               for row, other in zip(sound, low)]
    return reference.judge(margins, 0, limits)


def old_rule(limits: dict) -> dict:
    """The clause as it stood before ISSUE 29: no outlier, no mean limit."""
    return {k: limits[k] for k in ("max_margin", "min_exact_share")}


def row(verdict: dict, limits: dict) -> dict:
    """A control's four numbers with the verdict of both rules."""
    old = reference.judge(verdict["margins"], verdict["outside_head"],
                          old_rule(limits))
    return {"outliers": verdict["outliers"],
            "max_margin": verdict["max_margin"],
            "mean_margin": verdict["mean_margin"], "exact": verdict["exact"],
            "tokens": verdict["tokens"], "new_rule_ok": verdict["ok"],
            "old_rule_ok": old["ok"], "why": verdict["why"],
            "margins": verdict["margins"]}


def served_tree(spec: dict, tiny: bool, seed: int):
    """The tree `server_child.py` serves for `seed`, head narrowed, and its
    model configuration; no engine."""
    model_cfg = server_child.model_config_from(spec, tiny)
    config = server_child.engine_config_from(spec, tiny)
    params = server_child.made_weights(spec, tiny, config, model_cfg,
                                       seed % (2**31 - 1))
    if params is None:
        raise SystemExit("controls read a tree the benchmark makes "
                         "(engine.weights = hashed_int8)")
    holder = types.SimpleNamespace(params=params)
    server_child.narrow_head(holder)
    return holder.params, model_cfg


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--samples", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)
    with open(os.path.join(perfbench_paths.BENCH, "configs",
                           args.config + ".json")) as f:
        spec = json.load(f)
    limits = spec["reference"]
    samples = {}
    for seed in args.seeds:
        with open(os.path.join(args.samples, f"seed{seed}.sample.json")) as f:
            samples[seed] = json.load(f)
    table = {}
    for position, seed in enumerate(args.seeds):
        params, cfg = served_tree(spec, args.tiny, seed)
        sample = samples[seed]
        rows = {"sound": row(reference.compare(params, cfg, sample, limits),
                             limits)}
        # This tree is "another seed's" for the sample before it.
        before = args.seeds[position - 1]
        if before != seed:
            table.setdefault(str(before), {})["other_seed"] = row(
                reference.compare(params, cfg, samples[before], limits),
                limits)
        for name in SAME_TREE_CONTROLS:
            rows[name] = row(read(name, params, cfg, sample, limits), limits)
        rows["int4_weights"] = row(read_int4(params, cfg, sample, limits),
                                   limits)
        del params
        table.setdefault(str(seed), {}).update(rows)
        for name, r in table[str(seed)].items():
            print(seed, name, {k: r[k] for k in r if k != "margins"},
                  flush=True)
        with open(args.out, "w") as f:
            json.dump({"limits": limits, "seeds": table}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
