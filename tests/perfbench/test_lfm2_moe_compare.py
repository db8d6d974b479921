"""The comparison `lfm2-24b-a2b-pp4` brings (perfbench/references/
lfm2_moe.py `compare`: the sibling configuration's clause arithmetic over
this model's `forward`) and the files the configuration names, at toy size
on the CPU.

The toy program computes in float32, so its replay stands 1e-4 % from the
reference; the limits are the configuration file's own (set on the chip
between the bf16 program's readings and the int8 reference's, PERF.md
section 4). A control laid over the reference has to be refused by the
clause named here; the sound sample has to pass every clause."""

import gzip
import json
import os

import numpy as np
import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import DATA

import lfm2_moe_controls as controls

SEED = 4700000031


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


def test_the_tree_holds_one_vocabulary_matrix_narrowed_by_the_adapter(served):
    """`embed` is the one matrix (rows outside printable ASCII zero, BOS
    among them); `lm_head` is the empty leaf that lets the harness's
    `narrow_head` pass a tied tree."""
    import traffic

    params, cfg = served[0], served[1]
    assert cfg.tie_embeddings
    assert params["lm_head"].shape == (0, cfg.vocab_size)
    rows = np.asarray(np.any(np.asarray(params["embed"]) != 0, axis=1))
    assert rows[traffic.FIRST_ID:traffic.LAST_ID + 1].all()
    assert not rows[:traffic.FIRST_ID].any()
    assert not rows[traffic.LAST_ID + 1:].any()


def test_sound_sample_passes_every_clause(served):
    params, cfg, limits, sample, replayed = served
    got = controls.judged("sound", params, cfg, sample, limits, replayed)
    assert got["ok"], got["why"]
    # float32 against float32: summation order only.
    assert got["logit_floor"] < 1e-2 and got["logit_distance"] < 1e-2
    assert got["replayed"] == got["exact"] == 32


@pytest.mark.parametrize("control, clause", [
    # The precision below bf16. At toy widths (hidden 64) it reads a floor
    # under the published-width limit and is refused by the distance; on
    # the chip the floor alone refuses it on every saved seed (PERF.md §4).
    ("int8_weights", "logit_"),
    ("int4_weights", "logit_floor"),
    ("conv_dropped", "logit_distance"),
    ("no_in_gate", "logit_distance"),
    ("no_out_gate", "logit_distance"),
    ("qk_norm_off", "logit_"),
    ("rotary_off", "logit_"),
    ("top2", "logit_distance"),
    ("no_expert_bias", "logit_"),
    ("expert_layer_zeroed", "logit_"),           # ONE expert layer of eight
])
def test_control_over_the_reference_is_refused(served, control, clause):
    params, cfg, limits, sample, replayed = served
    got = controls.judged(control, params, cfg, sample, limits, replayed)
    assert not got["ok"]
    assert any(text.startswith(clause) for text in got["why"]), got["why"]


def test_compare_runs_the_replay_itself_and_leaves_the_sibling_alone(served):
    """As the server child calls it: no logits handed in. The sibling's
    module is loaded, not changed: its `forward` is still its own."""
    import extension

    params, cfg, limits, sample, replayed = served
    ref = extension.load("references", limits["module"])
    theirs = extension.load("references", "nemotron_h.py")
    before = theirs.forward
    got = ref.compare(params, cfg, sample, limits)
    assert got["ok"] and got["replayed"] == 32
    assert json.dumps(got)                      # the result line carries it
    assert len(got["logit_distance_by_token"]) == 32
    assert theirs.forward is before and theirs.compare.__globals__[
        "forward"] is before


# -- the files the configuration names ---------------------------------------


def test_configuration_states_the_catalog_row_and_the_cut():
    spec = controls.load_spec()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert spec["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in spec["reduced"]:
                assert spec[key] == value, key
        assert spec["published"]["layer_types"] == row["config"]["layer_types"]
    assert spec["reduced"] == ["num_hidden_layers", "layer_types"]
    assert spec["layer_types"] == spec["published"]["layer_types"][:10]
    assert spec["published"]["num_hidden_layers"] == 40
    assert spec["chips"] == spec["chips_sharing_a_layer"] == 1
    for width, value in {"hidden_size": 2048, "num_attention_heads": 32,
                         "num_key_value_heads": 8, "intermediate_size": 11776,
                         "num_experts": 64, "moe_intermediate_size": 1536,
                         "num_experts_per_tok": 4, "vocab_size": 65536,
                         "conv_L_cache": 3}.items():
        assert spec[width] == value
    assert spec["rope_parameters"]["rope_theta"] == 1_000_000


def test_published_widths_build_the_counts_of_the_name():
    """This stage holds 5.267 B parameters; the whole model, built from the
    published depth by the same adapter, 23.8 B."""
    import extension

    spec = controls.load_spec()
    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, tiny=False)
    assert cfg.layer_pattern == spec["layer_pattern"]
    assert cfg.head_dim == 64 and cfg.tie_embeddings and cfg.qk_norm
    assert cfg.experts_held == cfg.n_routed_experts == 64
    assert abs(cfg.num_params() / 5.267e9 - 1) < 1e-3
    whole = adapter.model_config({**spec, **spec["published"]}, tiny=False)
    assert whole.num_layers == 80 and whole.kv_layers == 10
    assert abs(whole.num_params() / 23.84e9 - 1) < 2e-3


def test_costs_are_the_shapes():
    import kernel_costs

    spec = controls.load_spec()
    costs = kernel_costs.for_spec(spec)
    weights = costs.decode_weight_bytes(spec)
    # Every matrix once: the 5.267 B parameters in bf16, the embedding
    # counted once (as the head), gains and biases not at all.
    assert abs(weights / (2 * 5.267e9) - 1) < 1e-3
    experts = 8 * 64 * 3 * 2048 * 1536 * 2
    assert 0.90 < experts / weights < 0.93
    step = costs.decode_step_bytes(spec, 64 * 450)
    state = 2 * 64 * 8 * 2 * 2048 * 2     # read + written, 8 conv operators
    kv = 64 * 450 * 2 * 2 * 8 * 64 * 2
    assert step == weights + state + kv
    call = costs.moe_held_experts(spec, 64)
    # The chosen pairs (ISSUE 58): 64 rows x top-4, every expert held.
    assert call["flops"] == 64 * 4 * 6 * 2048 * 1536
    assert abs(call["bytes"] / (experts / 8) - 1) < 2e-3
    # The shared decode kernel's reader reckons one call from this file.
    one = kernel_costs.paged_decode_call(spec, 64 * 450, 64)
    assert one["bytes"] == 64 * 450 * 2 * 8 * 64 * 2 + 2 * 64 * 32 * 64 * 2


def test_prefill_share_reader_reads_the_kernel_inside_the_prefill_program():
    import extension
    from run import Context

    reader = extension.load("metrics", "moe_held_experts_prefill_share.py")
    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        recorded = json.load(f)
    import trace_reduce

    trace = trace_reduce.reduce(recorded)
    # The recorded trace is a GQA decoder's: no such kernel, no number.
    assert reader.read(Context(trace=trace)) is None
    assert reader.read(Context(trace=None)) is None
    ops = dict(trace["ops"])
    ops["jit__prefill_fn/moe_held_experts.7_f32_512_2048_"] = {
        "total_s": 0.02 * trace["window_s"], "count": 4}
    ops["jit__prefill_fn/moe_held_experts.8_f32_512_2048_"] = {
        "total_s": 0.01 * trace["window_s"], "count": 4}
    ops["jit__decode_fn/moe_held_experts.9_f32_64_2048_"] = {
        "total_s": 0.5 * trace["window_s"], "count": 90}
    got = reader.read(Context(trace={**trace, "ops": ops}))
    assert got == pytest.approx(3.0)
