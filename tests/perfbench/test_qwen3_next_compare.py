"""The comparison `qwen3-next-80b-a3b-ep4` brings (perfbench/references/
qwen3_next.py `compare`: the sibling configuration's clause arithmetic over
this model's `forward`) and the files the configuration names, at toy size
on the CPU.

The toy program computes in float32, so its replay stands 1e-3 % from the
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

import qwen3_next_controls as controls

SEED = 4900000031


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


def test_the_tree_is_the_patterns_own(served):
    """Three linear-attention layers to one attending layer, experts under
    every one; an untied head (the harness narrows it); no router bias."""
    params, cfg = served[0], served[1]
    assert cfg.layer_pattern == "LELELE*E" * 2 and not cfg.tie_embeddings
    assert {k: len(v) for k, v in params["layers"].items() if v} == {
        "delta": 6, "attention": 2, "moe": 8}
    assert params["lm_head"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert "router_bias" not in params["layers"]["moe"][0]
    assert params["layers"]["attention"][0]["wq"].shape[1] == (
        2 * cfg.num_heads * cfg.head_dim)


def test_sound_sample_passes_every_clause(served):
    params, cfg, limits, sample, replayed = served
    got = controls.judged("sound", params, cfg, sample, limits, replayed)
    assert got["ok"], got["why"]
    # float32 against float32: summation order only.
    assert got["logit_floor"] < 1e-1 and got["logit_distance"] < 1e-1
    assert got["replayed"] == got["exact"] == 32


@pytest.mark.parametrize("control", [
    c for c in controls.CONTROLS if c != "sound"])
def test_control_over_the_reference_is_refused(served, control):
    """Each by a clause on the LOGITS (a gain of 0 under `norm_offset_off`
    silences the reference altogether: the distance reads inf or nan, and
    a clause that does not hold refuses)."""
    params, cfg, limits, sample, replayed = served
    got = controls.judged(control, params, cfg, sample, limits, replayed)
    assert not got["ok"]
    assert any(text.startswith("logit_") for text in got["why"]), got["why"]


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
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert spec["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in spec["reduced"]:
                assert spec[key] == value, key
        assert {k: row["config"][k] for k in spec["reduced"]} == {
            k: spec["published"][k] for k in spec["reduced"]}
    assert spec["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (spec["num_hidden_layers"], spec["num_experts"],
            spec["vocab_size"]) == (12, 128, 37984)
    assert spec["published"]["vocab_size"] == 4 * spec["vocab_size"]
    assert spec["router_width"] == spec["published"]["num_experts"] == 512
    assert spec["chips"] == 1 and spec["chips_sharing_a_layer"] == 4
    for width, value in {
            "hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "head_dim": 256,
            "partial_rotary_factor": 0.25, "rope_theta": 10_000_000,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
            "num_experts_per_tok": 10,
            "shared_expert_intermediate_size": 512}.items():
        assert spec[width] == value


def test_published_widths_build_the_counts_of_the_name():
    """This chip holds 5.42 B parameters; the whole model, built from the
    published depth, expert count and vocabulary by the same adapter,
    79.7 B, of which a token runs 3.6 B."""
    import extension

    spec = controls.load_spec()
    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, tiny=False)
    assert cfg.layer_pattern == spec["layer_pattern"] == "LELELE*E" * 3
    assert cfg.kv_layers == 3 and cfg.rotary_dim == 64
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.first_expert) == (
        512, 128, 0)
    assert cfg.norm_offset == 1.0 and cfg.attn_output_gate and cfg.qk_norm
    assert cfg.router_scoring == "softmax" and cfg.shared_expert_gate
    assert cfg.delta_conv_dim == 8192 and not cfg.tie_embeddings
    assert abs(cfg.num_params() / 5.42e9 - 1) < 1e-3
    whole = adapter.model_config(
        {**spec, **spec["published"], "num_experts": 512}, tiny=False)
    assert whole.num_layers == 96 and whole.kv_layers == 12
    assert whole.experts_held == 512
    assert abs(whole.num_params() / 79.67e9 - 1) < 1e-3
    # (num_active_params counts both vocabulary tables, as for every
    # model; less the lookup table, a gather, a token runs 3.56 B.)
    lookup = whole.vocab_size * whole.hidden_size
    assert abs((whole.num_active_params() - lookup) / 3.6e9 - 1) < 0.02


def test_state_pool_reads_what_the_configuration_holds():
    """9 linear layers x 64 slots x (2 MiB of S + 48 KiB of columns)."""
    import extension
    import jax

    from polykey_tpu.engine.kv_cache import init_slot_state

    spec = controls.load_spec()
    cfg = extension.load("adapters", spec["adapter"]).model_config(spec, False)
    state = jax.eval_shape(lambda: init_slot_state(cfg, 64))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert nbytes == 9 * 64 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert round(nbytes / 1e9, 2) == 1.24


def test_costs_are_the_shapes():
    import kernel_costs

    spec = controls.load_spec()
    costs = kernel_costs.for_spec(spec)
    weights = costs.decode_weight_bytes(spec)
    # Every matrix once: the 5.42 B parameters in bf16 less the embedding
    # (a gather), gains and A_log / dt_bias not at all.
    assert abs(weights / (2 * (5.423e9 - 37984 * 2048)) - 1) < 1e-3
    experts = 12 * 128 * 3 * 2048 * 512 * 2
    assert 0.89 < experts / weights < 0.92
    step = costs.decode_step_bytes(spec, 64 * 450)
    state = 2 * 64 * 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    kv = 64 * 450 * 3 * 2 * 2 * 256 * 2
    assert step == weights + state + kv
    assert 0.70 < experts / step < 0.74 and 0.17 < state / step < 0.19
    call = costs.moe_held_experts(spec, 64)
    # The chosen pairs (ISSUE 58): 64 rows x top-10 x 128 held of 512.
    assert call["flops"] == 64 * 10 * 128 / 512 * 6 * 2048 * 512
    assert abs(call["bytes"] / (experts / 12) - 1) < 2e-3
    update = costs.gated_delta_state_update(spec, 64)
    assert update["flops"] == 64 * 32 * 6 * 128 * 128
    assert abs(update["bytes"] / (2 * 64 * 32 * 128 * 128 * 4) - 1) < 2e-2
    # The shared decode kernel's reader reckons one call from this file.
    one = kernel_costs.paged_decode_call(spec, 64 * 450, 64)
    assert one["bytes"] == 64 * 450 * 2 * 2 * 256 * 2 + 2 * 64 * 16 * 256 * 2


def test_roofline_reader_reads_the_kernel_by_its_name():
    import extension
    import peaks
    from run import Context

    reader = extension.load("metrics", "gated_delta_state_update_roofline.py")
    spec = controls.load_spec()
    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        recorded = json.load(f)
    import trace_reduce

    trace = trace_reduce.reduce(recorded)
    # The recorded trace is a GQA decoder's: no such kernel, no number —
    # and none from a program that lacks the kernel (the parent's), or
    # from a configuration whose costs module does not reckon it.
    assert reader.read(Context(trace=trace, spec=spec)) is None
    assert reader.read(Context(trace=None, spec=spec)) is None
    chip = peaks.row("TPU v5 lite")
    least = (2 * 64 * 32 * 128 * 128 * 4 + 64 * 4 * 12288) / chip[
        "hbm_bytes_per_s"]
    kernels = {**trace["kernels"], "gated_delta_state_update": {
        "total_s": 90 * least / 0.8, "count": 90}}
    got = reader.read(Context(trace={**trace, "kernels": kernels}, spec=spec,
                              peaks=chip))
    assert got == pytest.approx(80.0)
    sibling = json.load(open(os.path.join(
        os.path.dirname(controls.CONFIG), "lfm2-24b-a2b-pp4.json")))
    assert reader.read(Context(trace={**trace, "kernels": kernels},
                               spec=sibling, peaks=chip)) is None
