"""The comparison `ouro-2.6b` brings (perfbench/references/ouro.py
`compare`: the sibling configuration's clause arithmetic over this model's
`forward`) and the files the configuration names, at toy size on the CPU.

The toy program computes in float32, so its replay stands 1e-4 % from the
reference; the limits are the configuration file's own: RATIOS to what the
reference's own bfloat16 twin reads on the same seed (set on the chip
between the bf16 program's ratios and the int8 reference's, PERF.md
section 4). A control laid over the reference has to be refused by a
clause on the logits; the sound sample has to pass every clause."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import ROOT

import ouro_controls as controls

SEED = 6400000031
CELL = "ouro-2.6b.chat-narrow"
NEW_METRICS = ("loop_exit_step_mean", "loop_weight_reread_share")
GAINED = (
    "output_tok_s", "avg_lanes", "tokens_useful_fraction",
    "prefill_time_share", "peak_hbm_gb", "ttft_watch_ms_mean",
    "decode_overshoot_share", "paged_attention_decode_roofline",
    "prefill_padding_share", "host_ms_per_decode_block",
    "idle_gap_named_share", "prefill_table_read_share")


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


@pytest.fixture(scope="module")
def twin(served):
    params, cfg, limits, sample, _ = served
    return controls.sound_twin(params, cfg, sample, limits)


def test_limits_are_the_configuration_files(served):
    limits = served[2]
    spec = controls.load_spec()
    # The replay runs on the engine's own geometry (the sibling hybrid
    # configuration's `replay`: `forward_slots` + `unembed`, any model).
    assert limits["replay"]["adapter"] == "nemotron_h.py"
    assert limits["replay"]["lanes"] == spec["engine"]["max_decode_slots"]
    assert limits["replay"]["page_size"] == spec["engine"]["page_size"]
    assert limits["replay"]["window"] == min(spec["engine"]["prefill_buckets"])
    assert limits.get("max_outliers", 0) == 0
    assert set(limits["why"]) >= {"margins", "max_floor_ratio",
                                  "max_median_ratio", "min_replayed_share"}
    assert "max_logit_floor" not in limits      # no fixed percentage here


def test_the_tree_is_the_loops_own(served):
    """ONE stack of layers (stacked on a leading axis), four gains a layer,
    an untied head (the harness narrows it), the exit gate's w and b."""
    params, cfg = served[0], served[1]
    assert cfg.loop_steps == 2 and cfg.kv_layers == 2 * cfg.num_layers
    assert cfg.use_post_norms and not cfg.layer_pattern
    layers = params["layers"]
    for gain in ("ln1", "ln2", "post_ln1", "post_ln2"):
        assert layers[gain].shape == (cfg.num_layers, cfg.hidden_size)
    assert layers["attn"]["wk"].shape == layers["attn"]["wq"].shape   # MHA
    assert params["lm_head"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert params["exit_gate"]["w"].shape == (cfg.hidden_size, 1)
    assert params["exit_gate"]["b"].shape == (1,)
    assert float(np.abs(np.asarray(params["exit_gate"]["b"])).max()) == 0.0


def test_sound_sample_passes_every_clause(served):
    params, cfg, limits, sample, replayed = served
    got = controls.judged("sound", params, cfg, sample, limits, replayed)
    assert got["ok"], got["why"]
    # float32 against float32: summation order only — a thousandth of what
    # rounding q, k, v and the residual stream to bfloat16 moves this seed.
    assert got["logit_floor"] < 1e-2 and got["logit_distance"] < 1e-2
    assert 0.1 < got["twin_floor"] <= got["twin_median"] < 3.0
    assert got["replayed"] == got["exact"] == 32


@pytest.mark.parametrize("control", [
    c for c in controls.CONTROLS if c != "sound"])
def test_control_over_the_reference_is_refused(served, twin, control):
    """Each by a clause on the LOGITS, held to the SOUND twin."""
    spec = controls.load_spec()
    params, cfg, limits, sample, replayed = served
    if control == "other_seed":
        params, _ = controls.tree_of(spec, SEED + 1, tiny=True)
    got = controls.judged(control, params, cfg, sample, limits, replayed,
                          twin)
    assert not got["ok"]
    assert any(text.startswith("logit_") for text in got["why"]), got["why"]


def test_compare_runs_the_replay_itself_and_leaves_the_sibling_alone(served):
    """As the server child calls it: no logits handed in. The sibling's
    module is loaded, not changed: its `forward` is still its own."""
    import extension

    params, cfg, limits, sample, _ = served
    ref = extension.load("references", limits["module"])
    theirs = extension.load("references", "nemotron_h.py")
    before = theirs.forward
    got = ref.compare(params, cfg, sample, limits)
    assert got["ok"] and got["replayed"] == 32
    assert json.dumps(got)                      # the result line carries it
    assert len(got["logit_distance_by_token"]) == 32
    assert theirs.forward is before and theirs.compare.__globals__[
        "forward"] is before


def test_reference_takes_the_threshold_as_an_argument(served):
    """At the file's threshold (1) every position reads the last pass; at
    0.5 the rule sends some to an earlier one, and `forward` then returns
    THAT pass's logits for them."""
    import extension

    params, cfg, limits, sample, _ = served
    ref = extension.load("references", limits["module"])
    ids = sample["prompt_ids"] + sample["output_ids"]
    by_pass, lambdas, exits = ref.forward_passes(params, cfg, ids)
    assert by_pass.shape[:2] == (cfg.loop_steps, len(ids))
    assert set(exits) == {cfg.loop_steps - 1}
    np.testing.assert_array_equal(ref.forward(params, cfg, ids), by_pass[-1])
    early = ref.exit_steps(lambdas, 0.5)
    assert set(early) == {0, 1}
    np.testing.assert_array_equal(
        ref.forward(params, cfg, ids, threshold=0.5),
        by_pass[early, np.arange(len(ids))])
    # The rule by hand on one position: p_0 = λ_0, the last takes the rest.
    assert np.array_equal(early == 0, lambdas[0] >= 0.5)


# -- the files the configuration names ---------------------------------------


def test_configuration_states_the_catalog_row_uncut():
    spec = controls.load_spec()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert spec["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert spec[key] == value, key
    assert spec["reduced"] == []
    assert spec["chips"] == 1 and spec["chips_sharing_a_layer"] == 1
    assert spec["engine"]["num_pages"] >= 320 and spec["num_pages_reason"]
    assert spec["engine"]["max_decode_slots"] == 8
    assert spec["engine"]["quantize"] == "none"
    assert spec["engine"]["weights"] == "package_init"
    for width, value in {
            "hidden_size": 2048, "intermediate_size": 5632,
            "num_hidden_layers": 48, "num_attention_heads": 16,
            "num_key_value_heads": 16, "head_dim": 128, "vocab_size": 49152,
            "total_ut_steps": 4, "early_exit_threshold": 1,
            "rope_theta": 1000000, "rms_norm_eps": 1e-06,
            "sliding_window": None, "tie_word_embeddings": False}.items():
        assert spec[width] == value
    assert set(spec["layer_types"]) == {"full_attention"}
    assert set(spec["assumed"]) >= {
        "block", "no_bias_no_qk_norm", "norm_between_passes", "cache_index",
        "exit_gate_and_rule", "seeded_fills", "tokenizer"}
    assert spec["tiny"]["model"]["total_ut_steps"] == 2
    assert spec["tiny"]["model"]["num_hidden_layers"] == 3


def test_published_widths_build_the_counts_of_the_name():
    import extension

    from polykey_tpu.engine.kv_cache import kv_pool_bytes

    spec = controls.load_spec()
    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, tiny=False)
    assert (cfg.num_layers, cfg.loop_steps, cfg.kv_layers) == (48, 4, 192)
    assert cfg.early_exit_threshold == 1.0 and cfg.use_post_norms
    assert cfg.num_heads == cfg.num_kv_heads == 16 and cfg.head_dim == 128
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6
    assert not cfg.tie_embeddings and not cfg.layer_pattern
    assert cfg.num_params() == 2_667_974_657
    pool = kv_pool_bytes(cfg, spec["engine"]["num_pages"],
                         spec["engine"]["page_size"])
    assert pool == spec["engine"]["num_pages"] * 16 * 1_572_864
    # The parent of the PR that brought this file knows neither fact: the
    # adapter fails there at once, on the ModelConfig it asks.
    assert {"loop_steps", "early_exit_threshold"} <= set(
        type(cfg).__dataclass_fields__)


@pytest.mark.parametrize("key,value,match", [
    ("layer_types", ["full_attention"] * 47 + ["sliding_attention"],
     "full_attention"),
    ("sliding_window", 4096, "sliding_window"),
    ("num_hidden_layers", 47, "layer_types"),
])
def test_adapter_refuses_what_it_does_not_run(key, value, match):
    import extension

    spec = controls.load_spec()
    adapter = extension.load("adapters", spec["adapter"])
    with pytest.raises(ValueError, match=match):
        adapter.model_config({**spec, key: value}, tiny=False)


def test_costs_are_the_shapes():
    import kernel_costs

    spec = controls.load_spec()
    costs = kernel_costs.for_spec(spec)
    assert costs.layer_params(spec) == 51_380_224
    assert costs.model_params(spec) == 2_667_974_657
    # Held once: the layers' matrices and the head.
    assert costs.decode_weight_bytes(spec) == 2 * (
        48 * 51_380_224 + 49152 * 2048)
    assert costs.cache_layers(spec) == 192
    assert costs.kv_bytes_per_token_layer(spec) == 8_192
    assert costs.kv_bytes_per_token(spec) == 1_572_864
    parts = costs.decode_step_parts(spec, 4000)
    assert parts == {"layer_reads": 19_730_006_016, "head": 201_326_592,
                     "kv_read": 4000 * 1_572_864}
    step = costs.decode_step_bytes(spec, 4000)
    assert step == sum(parts.values())
    assert 0.74 < parts["layer_reads"] / step < 0.77
    # Three of the four reads are RE-reads: the program's count of layer
    # applications a step (192) less one of each layer.
    assert costs.reread_bytes(spec, 192) == 3 * 48 * 2 * 51_380_224
    assert costs.reread_bytes(spec, 48) == costs.reread_bytes(spec, 6) == 0
    assert 0.55 < costs.reread_bytes(spec, 192) / step < 0.58
    # The shared decode kernel's reader reckons one CALL from this file's
    # keys (kernel_costs.paged_decode_call): 16 KV heads, one cache layer.
    one = kernel_costs.paged_decode_call(spec, 8 * 500, 8)
    assert one["bytes"] == 8 * 500 * 8_192 + 2 * 8 * 16 * 128 * 2
    assert spec["kernels"] == [] and not hasattr(costs, "paged_decode_call")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_states_the_configuration_and_the_cell():
    cell = next(w for w in manifest()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "chat-narrow", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in manifest()["configs"] if c["name"] == "ouro-2.6b")
    assert config["reduced"] == []
    assert config["file"] == "perfbench/configs/ouro-2.6b.json"
    assert config["source"] == controls.load_spec()["source"]
    assert len(config["why"]) <= 200


@pytest.mark.parametrize("name", GAINED + NEW_METRICS)
def test_manifest_lists_the_cell_under(name):
    entry = next(m for group in ("end_to_end", "per_layer")
                 for m in manifest()[group] if m["name"] == name)
    assert CELL in entry["workloads"]
    if name in NEW_METRICS:
        assert entry == {
            "name": name, "unit": {"loop_exit_step_mean": "count"}.get(
                name, "%"),
            "better": {"loop_exit_step_mean": "higher"}.get(name, "lower"),
            "source": "program_counter", "layer": "Model step",
            "moves": "tpot_ms_mean", "workloads": [CELL]}


def test_manifest_lists_the_cell_nowhere_else():
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in manifest()[group] if CELL in m.get("workloads", ())}
    assert listed == set(GAINED + NEW_METRICS)
    assert not any(n.startswith((
        "moe_", "mla_", "ssm_", "gated_delta_", "collective_", "sampled_",
        "ttft_ms", "ttft_queue", "first_token_", "flash_")) for n in listed)


# -- the two readers -----------------------------------------------------------


def context(start: dict, stop: dict, spec=None, requests=()):
    from run import Context

    traced = {"start": 10.0, "stop": 14.0, "stats_start": start,
              "stats_stop": stop}
    return Context(
        spec=spec or controls.load_spec(), trace=None,
        samples={"meta": {"traced": traced}, "requests": list(requests)})


LIVE = [{"times": [9.0, 15.0], "counts": [1, 1], "final": 16.0,
         "prompt_tokens": 499}] * 8        # 8 x 500 live tokens throughout


def test_exit_step_mean_reads_the_counters():
    import extension

    reader = extension.load("metrics", "loop_exit_step_mean.py")
    start = {"loop_exits_by_step": [0, 0, 0, 100]}
    stop = {"loop_exits_by_step": [0, 0, 0, 900]}
    assert reader.read(context(start, stop)) == 4.0
    early = {"loop_exits_by_step": [100, 100, 0, 700]}
    assert reader.read(context(start, early)) == pytest.approx(
        (100 * 1 + 100 * 2 + 600 * 4) / 800)
    # The first reading precedes the first block: the counter not there yet.
    assert reader.read(context({"steps_dispatched": 0}, stop)) == 4.0


@pytest.mark.parametrize("start,stop", [
    ({}, {}),                                             # the parent
    ({"steps_dispatched": 5}, {"steps_dispatched": 50}),  # a dense cell
    (None, None),                                         # no capture
    ({"loop_exits_by_step": [0, 0, 0, 9], "loop_layer_passes": 192,
      "steps_dispatched": 1},) * 2,                       # no block between
])
@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_nothing_without_the_counters(name, start, stop):
    import extension

    reader = extension.load("metrics", name + ".py")
    assert reader.read(context(start, stop, requests=LIVE)) is None


def test_reread_share_reads_the_counters_and_the_costs():
    import extension

    reader = extension.load("metrics", "loop_weight_reread_share.py")
    start = {"loop_layer_passes": 192 * 100, "steps_dispatched": 100}
    stop = {"loop_layer_passes": 192 * 900, "steps_dispatched": 900}
    got = reader.read(context(start, stop, requests=LIVE))
    step = 19_730_006_016 + 201_326_592 + 8 * 500 * 1_572_864
    assert got == pytest.approx(100 * 3 * 48 * 2 * 51_380_224 / step)
    assert 55 < got < 58
    # A configuration whose costs count no re-read: nothing, and no error.
    with open(os.path.join(os.path.dirname(controls.CONFIG),
                           "mistral-7b.json")) as f:
        dense = json.load(f)
    assert reader.read(context(start, stop, spec=dense, requests=LIVE)) is None


# -- the cell, end to end at toy size --------------------------------------------


def test_traced_rehearsal_reports_the_loop(tmp_path):
    """`--trace 1 --tiny`: the cell's own command on the CPU. Both new
    metrics are on the line; the exit step reads the toy's two passes."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "6400000077", "--seconds", "3",
         "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["reference"]["module"] == "ouro.py"
    assert line["reference"]["replayed"] == 32
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["loop_exit_step_mean"] == 2.0
    assert "loop_weight_reread_share" in metrics
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["avg_lanes"] <= 8
