"""The comparison `nemotron-3-super-ep4` brings (perfbench/references/
nemotron_h.py `compare`): margins of the served tokens AND the program's
replayed logits against the reference's, at toy size on the CPU.

The toy program computes in float32, so its replay stands 1e-4 % from the
reference; the limits are the configuration file's own (set on the chip
between the bf16 program's readings and the int8 reference's, PERF.md
section 6). A control laid over the reference has to be refused by the
clause named here; the sound sample has to pass every clause."""

import json

import numpy as np
import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)

import nemotron_h_controls as controls

SEED = 4400000031


@pytest.fixture(scope="module")
def served():
    """(params, cfg, limits, sample, replayed): a greedy sample of the toy
    model, 24 + 32 tokens like the harness's, decoded by the reference
    (what a sound float32 program serves) over the narrowed head."""
    import extension
    import traffic

    spec = controls.load_spec()
    params, cfg = controls.tree_of(spec, SEED, tiny=True)
    limits = spec["reference"]
    ref = extension.load("references", limits["module"])
    rng = np.random.default_rng(SEED)
    ids = [1] + [int(t) for t in rng.integers(
        traffic.FIRST_ID, traffic.LAST_ID + 1, 23)]
    allowed = np.zeros(cfg.vocab_size, bool)
    allowed[traffic.FIRST_ID:traffic.LAST_ID + 1] = True
    total = len(ids) + 32
    for t in range(len(ids), total):
        # One shape for every step: a causal stack ignores the padding.
        logits = ref.forward(params, cfg, ids + [0] * (total - len(ids)))
        ids.append(int(np.argmax(np.where(allowed, logits[t - 1], -np.inf))))
    sample = {"prompt_ids": ids[:24], "output_ids": ids[24:],
              "allowed_first": traffic.FIRST_ID,
              "allowed_last": traffic.LAST_ID}
    how = dict(limits["replay"])
    adapter = extension.load("adapters", how.pop("adapter"))
    replayed = adapter.replay(params, cfg, sample["prompt_ids"],
                              sample["output_ids"], **how)
    return params, cfg, limits, sample, replayed


def test_limits_are_the_configuration_files(served):
    limits = served[2]
    spec = controls.load_spec()
    # The replay runs on the engine's own geometry.
    assert limits["replay"]["lanes"] == spec["engine"]["max_decode_slots"]
    assert limits["replay"]["page_size"] == spec["engine"]["page_size"]
    assert limits["replay"]["window"] == min(spec["engine"]["prefill_buckets"])
    assert limits.get("max_outliers", 0) == 0
    # The seeded dt_bias stands for the published step range.
    from polykey_tpu.models.hybrid import DT_INIT
    assert (spec["time_step_min"], spec["time_step_max"]) == DT_INIT


def test_sound_sample_passes_every_clause(served):
    params, cfg, limits, sample, replayed = served
    got = controls.judged("sound", params, cfg, sample, limits, replayed)
    assert got["ok"], got["why"]
    # float32 against float32: summation order only.
    assert got["logit_floor"] < 1e-2 and got["logit_distance"] < 1e-2
    assert got["replayed"] == got["exact"] == 32


@pytest.mark.parametrize("control, clause", [
    ("int8_weights", "logit_floor"),      # the precision below bf16
    ("int4_weights", "logit_floor"),
    ("top11", "logit_distance"),
    ("state_zeroed_all", "logit_distance"),
    ("conv_dropped", "logit_distance"),
    ("no_shared", "logit_distance"),
])
def test_control_over_the_reference_is_refused(served, control, clause):
    params, cfg, limits, sample, replayed = served
    got = controls.judged(control, params, cfg, sample, limits, replayed)
    assert not got["ok"]
    assert any(text.startswith(clause) for text in got["why"]), got["why"]


def test_one_zeroed_mixer_moves_every_position(served):
    """ONE mixer layer of five carries nothing from token to token: at the
    cell's own size the floor refuses it on 12 of 12 seeds (4.56-10.76
    against the limit: PERF.md section 4). The toy's five mixers weigh
    differently by seed - a distance of 8-26 %, on either side of the
    full-size limit of 18 - so the toy holds what every seed shows: the
    lowest eighth of the positions and all of them together move far
    beyond a sound run's reading."""
    params, cfg, limits, sample, replayed = served
    sound = controls.judged("sound", params, cfg, sample, limits, replayed)
    got = controls.judged("state_zeroed", params, cfg, sample, limits,
                          replayed)
    assert got["logit_floor"] > max(100 * sound["logit_floor"], 0.5)
    assert got["logit_distance"] > max(100 * sound["logit_distance"], 5.0)


def test_bf16_state_control_is_not_compiled_away(served):
    """A convert to bfloat16 and back is removed by the compiler; the
    control rounds with reduce_precision and has to move the logits."""
    params, cfg, limits, sample, replayed = served
    sound = controls.judged("sound", params, cfg, sample, limits, replayed)
    got = controls.judged("bf16_state", params, cfg, sample, limits, replayed)
    assert got["logit_distance"] > 10 * sound["logit_distance"]


def test_compare_runs_the_replay_itself(served):
    """As the server child calls it: no logits handed in."""
    import extension

    params, cfg, limits, sample, replayed = served
    ref = extension.load("references", limits["module"])
    got = ref.compare(params, cfg, sample, limits)
    assert got["ok"] and got["replayed"] == 32
    assert json.dumps(got)                      # the result line carries it
    assert len(got["logit_distance_by_token"]) == 32
