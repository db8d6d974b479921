"""The comparison `olmo-hybrid-7b-pp2` brings (perfbench/references/
olmo_hybrid.py `compare`: the sibling configuration's clause arithmetic
over this model's `forward`) and the files the configuration names, at toy
size on the CPU.

The toy program computes in float32, so its replay stands 1e-3 % from the
reference; the limits are the configuration file's own (set on the chip
between the bf16 program's readings and the int8 reference's, PERF.md
section 4). A control laid over the reference has to be refused by a
clause on the logits; the sound sample has to pass every clause."""

import gzip
import json
import os

import numpy as np
import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import DATA, ROOT

import olmo_hybrid_controls as controls

SEED = 6000000031
CELL = "olmo-hybrid-7b-pp2.decode-wide"
SIBLING_CELL = "qwen3-next-80b-a3b-ep4.decode-wide"


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
    assert set(limits["why"]) >= {"margins", "max_logit_floor",
                                  "max_logit_distance", "min_replayed_share"}


def test_the_tree_is_the_patterns_own(served):
    """Three linear-attention layers to one attending layer, a dense MLP
    under every one; an untied head (the harness narrows it); a post-norm
    on every entry and no pre-norm; q/k gains as long as the projections."""
    params, cfg = served[0], served[1]
    assert cfg.layer_pattern == "LDLDLD*D" * 2 and not cfg.tie_embeddings
    assert {k: len(v) for k, v in params["layers"].items() if v} == {
        "delta": 6, "attention": 2, "dense": 8}
    assert params["lm_head"].shape == (cfg.hidden_size, cfg.vocab_size)
    for trees in params["layers"].values():
        for p in trees:
            assert "norm" not in p and "post_norm" in p
    attn = params["layers"]["attention"][0]
    assert attn["q_norm"].shape == (cfg.num_heads * cfg.head_dim,)
    assert attn["wk"].shape[1] == attn["wq"].shape[1]       # MHA


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
    """Each by a clause on the LOGITS."""
    spec = controls.load_spec()
    params, cfg, limits, sample, replayed = served
    if control == "other_seed":
        params, _ = controls.tree_of(spec, SEED + 1, tiny=True)
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
                       if r["name"] == "Olmo-Hybrid-7B")
        assert spec["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in spec["reduced"]:
                assert spec[key] == value, key
        assert {k: row["config"][k] for k in spec["reduced"]} == {
            k: spec["published"][k] for k in spec["reduced"]}
    assert spec["reduced"] == ["num_hidden_layers", "layer_types"]
    assert spec["num_hidden_layers"] == 16
    assert spec["layer_types"] == spec["published"]["layer_types"][:16]
    assert spec["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]) * 4
    assert spec["chips"] == 1 and spec["chips_sharing_a_layer"] == 1
    assert spec["engine"]["num_pages"] >= 3072 and spec["num_pages_reason"]
    for width, value in {
            "hidden_size": 3840, "intermediate_size": 11008,
            "num_attention_heads": 30, "num_key_value_heads": 30,
            "vocab_size": 100352, "linear_num_key_heads": 30,
            "linear_num_value_heads": 30, "linear_key_head_dim": 96,
            "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
            "linear_allow_neg_eigval": True,
            "rope_parameters": {"rope_theta": None}}.items():
        assert spec[width] == value


def test_published_widths_build_the_counts_of_the_name():
    """This chip holds 4.10 B parameters; the whole model, built from the
    published depth by the same adapter, 7.43 B — the name."""
    import extension

    spec = controls.load_spec()
    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, tiny=False)
    assert cfg.layer_pattern == spec["layer_pattern"] == "LDLDLD*D" * 4
    assert cfg.kv_layers == 4 and cfg.head_dim == 128
    assert cfg.num_heads == cfg.num_kv_heads == 30
    assert not cfg.use_rope and cfg.qk_norm
    assert cfg.qk_norm_span == "projection"
    assert not cfg.pre_norm and cfg.sandwich_norm
    assert cfg.delta_beta_scale == 2.0 and cfg.delta_heads_per_row == 2
    assert cfg.delta_conv_dim == 11520 and not cfg.tie_embeddings
    assert cfg.num_params() == 4_100_628_480
    whole = adapter.model_config({**spec, **spec["published"]}, tiny=False)
    assert whole.num_layers == 64 and whole.kv_layers == 8
    assert whole.num_params() == 7_430_553_600
    assert whole.num_active_params() == whole.num_params()      # dense
    # The parent of the PR that brought this file knows none of the three
    # facts: the adapter fails there at once, on the ModelConfig it asks.
    asked = {"qk_norm_span", "pre_norm", "delta_beta_scale"}
    assert asked <= {f for f in type(cfg).__dataclass_fields__}


def test_state_pool_reads_what_the_configuration_holds():
    """12 linear layers x 64 slots x (2.21 MB of S, two heads a row, + 69
    KB of columns): 1.75 GB, and the stored layout pads nothing."""
    import extension
    import jax

    from polykey_tpu.engine.kv_cache import init_slot_state

    spec = controls.load_spec()
    cfg = extension.load("adapters", spec["adapter"]).model_config(spec, False)
    state = jax.eval_shape(lambda: init_slot_state(cfg, 64))
    assert {s.shape for s in state.ssm} == {(64, 15, 96, 384)}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert nbytes == 12 * 64 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert round(nbytes / 1e9, 2) == 1.75


def test_costs_are_the_shapes():
    import kernel_costs

    spec = controls.load_spec()
    costs = kernel_costs.for_spec(spec)
    assert costs.stage_params(spec) == 4_100_628_480
    weights = costs.decode_weight_bytes(spec)
    # Every matrix once in bf16 less the embedding (a gather).
    assert weights == 2 * (4_100_628_480 - 100352 * 3840)
    step = costs.decode_step_bytes(spec, 38_000)
    state = 2 * 64 * 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    kv = 38_000 * 4 * 2 * 30 * 128 * 2
    assert step == weights + state + kv
    assert 0.25 < state / step < 0.28 and 0.16 < kv / step < 0.19
    assert costs.kv_bytes_per_token_layer(spec) == 15_360
    assert not hasattr(costs, "moe_held_experts")
    assert not hasattr(costs, "held_experts")
    update = costs.gated_delta_state_update(spec, 64)
    # PUBLISHED bytes, whatever the layout: 2 x lanes x 30 x 96 x 192 x 4.
    published = 2 * 64 * 30 * 96 * 192 * 4
    assert update["flops"] == 64 * 30 * 6 * 96 * 192
    assert published <= update["bytes"] < 1.02 * published
    # The shared decode kernel's reader reckons one call from this file.
    one = kernel_costs.paged_decode_call(spec, 64 * 450, 64)
    assert one["bytes"] == 64 * 450 * 2 * 30 * 128 * 2 + 2 * 64 * 30 * 128 * 2


def test_manifest_lists_the_cell_where_it_reads_something():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b-pp2", "decode-wide", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in manifest["configs"]
                  if c["name"] == "olmo-hybrid-7b-pp2")
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["file"] == "perfbench/configs/olmo-hybrid-7b-pp2.json"
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in manifest[group] if CELL in m.get("workloads", ())}
    assert listed == {
        "output_tok_s", "avg_lanes", "tokens_useful_fraction",
        "prefill_time_share", "peak_hbm_gb", "ttft_watch_ms_mean",
        "decode_overshoot_share", "prefill_padding_share",
        "host_ms_per_decode_block", "idle_gap_named_share",
        "prefill_table_read_share", "state_windows_chained_share",
        "paged_attention_decode_roofline",
        "gated_delta_state_update_roofline",
        "gated_delta_state_update_step_share"}
    assert not any(n.startswith(("moe_", "mla_", "ssm_", "sampled_"))
                   for n in listed)
    share = manifest["per_layer"][-1]
    assert share == {
        "name": "gated_delta_state_update_step_share", "unit": "%",
        "better": "lower", "source": "device_trace", "layer": "Kernels",
        "moves": "tpot_ms_mean", "workloads": [CELL, SIBLING_CELL]}


def recorded_trace():
    import trace_reduce

    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        return trace_reduce.reduce(json.load(f))


def test_roofline_reader_counts_published_bytes():
    import extension
    import peaks
    from run import Context

    reader = extension.load("metrics", "gated_delta_state_update_roofline.py")
    spec = controls.load_spec()
    trace = recorded_trace()
    assert reader.read(Context(trace=trace, spec=spec)) is None
    chip = peaks.row("TPU v5 lite")
    least = (2 * 64 * 30 * 96 * 192 * 4 + 64 * 4 * 17280) / chip[
        "hbm_bytes_per_s"]
    kernels = {**trace["kernels"], "gated_delta_state_update": {
        "total_s": 120 * least / 0.72, "count": 120}}
    got = reader.read(Context(trace={**trace, "kernels": kernels}, spec=spec,
                              peaks=chip))
    assert got == pytest.approx(72.0)


@pytest.mark.parametrize("config", [
    "olmo-hybrid-7b-pp2.json", "qwen3-next-80b-a3b-ep4.json"])
def test_step_share_reader_reads_the_kernel_inside_the_decode_program(config):
    """Present: the kernel's seconds inside `jit__decode_fn` over that
    program's. Absent — the recorded GQA decoder's trace, no trace at all,
    a kernel that ran in another program only — nothing, and no error."""
    import extension
    from run import Context

    reader = extension.load(
        "metrics", "gated_delta_state_update_step_share.py")
    with open(os.path.join(os.path.dirname(controls.CONFIG), config)) as f:
        spec = json.load(f)
    trace = recorded_trace()
    assert reader.read(Context(trace=trace, spec=spec)) is None
    assert reader.read(Context(trace=None, spec=spec)) is None
    modules = {**trace["modules"],
               "jit__decode_fn": {"total_s": 2.0, "count": 100}}
    kernel = {"total_s": 0.9, "count": 1200,
              "by_program": {"jit__decode_fn": 0.8, "jit_other": 0.1}}
    with_kernel = {**trace, "modules": modules, "kernels": {
        **trace["kernels"], "gated_delta_state_update": kernel}}
    assert reader.read(Context(trace=with_kernel, spec=spec)) == \
        pytest.approx(40.0)
    elsewhere = {**kernel, "by_program": {"jit_other": 0.9}}
    assert reader.read(Context(trace={**with_kernel, "kernels": {
        "gated_delta_state_update": elsewhere}}, spec=spec)) is None
    assert reader.read(Context(trace={**with_kernel, "modules": {}},
                               spec=spec)) is None
