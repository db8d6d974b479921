"""The reference clause of `correct` (ISSUE 29): `reference.judge` decides
from the per-token margins by four clauses, a configuration without the new
keys is judged as before, and the wrong-path controls
(reference_controls.py), laid over the reference at toy size, read as
tabled."""

import json
import os
import random

import pytest

from perfbench_paths import BENCH


def limits_of(config: str) -> dict:
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        return json.load(f)["reference"]


MOE = limits_of("mixtral-8x7b-tp4")
DENSE = limits_of("mistral-7b")
MOE_BEFORE = {"max_margin": 1.0, "min_exact_share": 0.4}   # until ISSUE 29
RULES = {"moe": MOE, "moe-before": MOE_BEFORE, "dense": DENSE}


def margins(*nonzero, tokens=32) -> list:
    return list(nonzero) + [0.0] * (tokens - len(nonzero))


# id: (margins, served ids outside the head,
#      {rule: None when ok, else a word of every clause `why` must name})
CASES = {
    "every token the reference's argmax":
        (margins(), 0, {"moe": None, "moe-before": None, "dense": None}),
    "seed 3800000039 (PR 27): one margin of 0.9455, 29 exact, mean 0.037":
        (margins(0.9455, 0.12, 0.1185), 0,
         {"moe": None, "moe-before": None, "dense": ["outliers 1"]}),
    "seed 2920000017 (PR 28's parent): 0.811, 26 exact, mean 0.036":
        (margins(0.811, 0.1, 0.08, 0.06, 0.05, 0.051), 0,
         {"moe": None, "moe-before": None, "dense": ["outliers 1"]}),
    "the fewest exact on record: 17 of 32, mean 0.125":
        (margins(*[0.6, 0.5, 0.4, 0.35] + [0.2] * 10 + [0.15]), 0,
         {"moe": None, "moe-before": None, "dense": ["outliers 4"]}),
    "the dense cells' largest on record: 0.051, 28 exact":
        (margins(0.051, 0.02, 0.01, 0.004), 0,
         {"moe": None, "moe-before": None, "dense": None}),
    "a routing flip at a tie: one margin of 1.3":
        (margins(1.3, 0.1), 0,
         {"moe": None, "moe-before": ["outliers 1"], "dense": ["outliers 1"]}),
    "two flips: 1.3 and 1.9":
        (margins(1.3, 1.9), 0,
         {"moe": None, "moe-before": ["outliers 2"], "dense": ["outliers 2"]}),
    "three outliers":
        (margins(1.3, 1.9, 1.01), 0,
         {"moe": ["outliers 3"], "moe-before": ["outliers 3"],
          "dense": ["outliers 3"]}),
    "a margin of exactly the threshold is no outlier":
        (margins(1.0, 1.0, 1.0), 0,
         {"moe": None, "moe-before": None, "dense": ["outliers 3"]}),
    "a small error on every other token: mean 0.3, no outlier":
        (margins(*[0.6] * 16), 0,
         {"moe": ["mean_margin 0.3"], "moe-before": None,
          "dense": ["outliers 16"]}),
    "two outliers that carry the mean over its limit":
        (margins(4.5, 4.0), 0,
         {"moe": ["mean_margin 0.2656"], "moe-before": ["outliers 2"],
          "dense": ["outliers 2"]}),
    "12 of 32 exact, by a hair each":
        (margins(*[0.01] * 20), 0,
         {"moe": ["exact 12"], "moe-before": ["exact 12"],
          "dense": ["exact 12"]}),
    "13 of 32 exact":
        (margins(*[0.01] * 19), 0,
         {"moe": None, "moe-before": None, "dense": ["exact 13"]}),
    "a served id outside the narrowed head":
        (margins(), 1, {"moe": ["outside_head 1"],
                        "moe-before": ["outside_head 1"],
                        "dense": ["outside_head 1"]}),
    "a wrong path: margins of 2-3 on every token":
        (margins(*[2.0 + 0.03 * i for i in range(32)]), 0,
         {rule: ["outliers 32", "mean_margin" if rule == "moe" else "exact 0",
                 "exact 0"] for rule in RULES}),
}


@pytest.mark.parametrize("rule", list(RULES))
@pytest.mark.parametrize("case", list(CASES))
def test_decision_of_the_reference_clause(case, rule):
    import reference

    values, outside, expected = CASES[case]
    verdict = reference.judge(values, outside, RULES[rule])
    names = expected[rule]
    assert verdict["ok"] is (names is None), verdict["why"]
    assert bool(verdict["why"]) is not verdict["ok"]
    for name in names or []:
        assert any(name in clause for clause in verdict["why"]), verdict["why"]
    if names is not None:       # no clause named that held
        assert len(verdict["why"]) == len(set(names))
    # Each number compared stands beside its limit, in every run.
    for word in ("outliers", "mean_margin", "exact", "outside_head"):
        assert word in verdict["checks"]
    assert verdict["margins"] == values and verdict["tokens"] == len(values)
    assert verdict["max_margin"] == max(values)
    assert verdict["tolerance"] == RULES[rule]["max_margin"]
    json.dumps(verdict)         # it rides in the result line


@pytest.mark.parametrize("case", list(CASES))
def test_without_the_new_keys_the_clause_is_the_one_before(case):
    """`max_outliers` absent = 0 and `max_mean_margin` absent = no limit:
    the verdict of `max(margins) <= max_margin and exact >= share * n and
    no id outside`, which both dense cells are still judged by."""
    import reference

    values, outside, _ = CASES[case]
    for limits in (MOE_BEFORE, DENSE):
        assert "max_outliers" not in limits and "max_mean_margin" not in limits
        before = (max(values) <= limits["max_margin"]
                  and sum(m <= 0.0 for m in values)
                  >= limits["min_exact_share"] * len(values)
                  and outside == 0)
        assert reference.judge(values, outside, limits)["ok"] is before


def test_the_configurations_state_the_limits_of_record():
    assert DENSE == {"max_margin": 0.25, "min_exact_share": 0.5}
    assert MOE == {"max_margin": 1.0, "max_outliers": 2,
                   "max_mean_margin": 0.25, "min_exact_share": 0.4}


# --- the controls, laid over the reference at toy size ----------------------

SEED, OTHER_SEED = 13, 11


def greedy_sample(params, cfg) -> dict:
    """What a sound serving path would produce: 32 greedy tokens by the
    reference itself over the narrowed head, after a 24-token prompt. One
    padded length throughout (causal: what follows a position cannot move
    it), so the reference compiles once."""
    import numpy as np

    import reference
    import traffic

    rng = random.Random(5)
    prompt = [1] + [3 + ord(c) for c in rng.choices(traffic.ALPHABET, k=23)]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[traffic.FIRST_ID:traffic.LAST_ID + 1] = True
    served = []
    for _ in range(32):
        tokens = prompt + served
        logits = reference.forward(params, cfg, tokens + [0] * (56 - len(tokens)))
        row = logits[len(tokens) - 1]
        served.append(int(np.argmax(np.where(allowed, row, -np.inf))))
    return {"prompt_ids": prompt, "output_ids": served,
            "allowed_first": traffic.FIRST_ID, "allowed_last": traffic.LAST_ID}


@pytest.fixture(scope="module")
def toy():
    import reference_controls as controls

    with open(os.path.join(BENCH, "configs", "mixtral-8x7b-tp4.json")) as f:
        spec = json.load(f)
    params, cfg = controls.served_tree(spec, True, SEED)
    other, _ = controls.served_tree(spec, True, OTHER_SEED)
    return {"spec": spec, "params": params, "other": other, "cfg": cfg,
            "sample": greedy_sample(params, cfg)}


def test_the_sound_reference_agrees_with_its_own_greedy_sample(toy):
    import reference

    verdict = reference.compare(toy["params"], toy["cfg"], toy["sample"], MOE)
    assert verdict["ok"] and verdict["why"] == []
    assert verdict["exact"] == 32 and verdict["max_margin"] <= 0.0
    assert 0.5 < verdict["logit_std"] < 2.0
    assert len(verdict["margins"]) == 32


# control: (refused by the rule before, refused by the rule now). At this
# size (2 layers, 4 experts, 4 KV heads) a zeroed head is a quarter of a
# layer's attention and is refused; 2% on the experts moves no token, and
# both rules pass it, here as on the chip (PERF.md section 4).
TABLED = {
    "other_seed": (True, True),
    "layer_skipped": (True, True),
    "no_rotary": (True, True),
    "top1_routing": (True, True),
    "kv_head_zeroed": (True, True),
    "kv_head_all_layers": (True, True),
    "attention_off": (True, True),
    "experts_scaled": (False, False),
}


def test_every_control_of_the_module_is_tabled():
    import reference_controls as controls

    assert set(TABLED) == {"other_seed", *controls.SAME_TREE_CONTROLS}


@pytest.mark.parametrize("control", list(TABLED))
def test_control_laid_over_the_reference_reads_as_tabled(toy, control):
    import reference
    import reference_controls as controls

    if control == "other_seed":
        verdict = reference.compare(toy["other"], toy["cfg"], toy["sample"], MOE)
    else:
        verdict = controls.read(control, toy["params"], toy["cfg"],
                                toy["sample"], MOE)
    row = controls.row(verdict, MOE)
    refused_before, refused_now = TABLED[control]
    assert row["old_rule_ok"] is not refused_before, row
    assert row["new_rule_ok"] is not refused_now, row
    assert bool(row["why"]) is refused_now
    if control in ("other_seed", "layer_skipped", "no_rotary"):
        # A fault on every token: no clause lets it through.
        assert row["mean_margin"] > 2 * MOE["max_mean_margin"]
        assert row["exact"] < MOE["min_exact_share"] * 32 / 1.5
        assert any("mean_margin" in c for c in row["why"])
        assert any("exact" in c for c in row["why"])


def test_rotary_is_whole_again_after_the_control(toy):
    import reference
    import reference_controls as controls

    with controls.no_rotary():
        assert reference.layer.__name__ == "layer_no_rotary"
    assert reference.layer.__name__ == "layer"
    verdict = reference.compare(toy["params"], toy["cfg"], toy["sample"], MOE)
    assert verdict["ok"] and verdict["exact"] == 32


def test_int4_control_reads_the_token_the_lower_precision_puts_first(toy):
    import reference_controls as controls

    # A tree of its own: the control rounds the one it is given in place.
    params, cfg = controls.served_tree(toy["spec"], True, SEED)
    row = controls.row(controls.read_int4(params, cfg, toy["sample"], MOE), MOE)
    assert row["tokens"] == 32 and len(row["margins"]) == 32
    assert min(row["margins"]) >= 0.0
    # Rounding every weight to 15 levels moves some token off the argmax.
    assert row["exact"] < 32 and row["max_margin"] > 0.0
