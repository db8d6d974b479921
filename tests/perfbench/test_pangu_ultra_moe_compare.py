"""The comparison `openpangu-ultra-moe-ep32` brings (perfbench/references/
pangu_ultra_moe.py `compare`: the sibling configuration's clause arithmetic
over this model's `forward`, the EXPANDED latent attention) and the files
the configuration names, at toy size on the CPU.

The toy program computes in float32, so its replay stands 1e-3 % from the
reference; the limits are the configuration file's own (set on the chip
between the bf16 program's readings and the controls', PERF.md section 4).
A control laid over the reference has to be refused by the clause named
here; the sound sample has to pass every clause."""

import gzip
import json
import os

import numpy as np
import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)
from perfbench_paths import DATA

import pangu_ultra_moe_controls as controls

SEED = 5500000003
CELL = "openpangu-ultra-moe-ep32.decode-wide"


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
    """A latent layer over a dense part, then over experts; an untied head
    (the harness narrows it); the routers' bias zero; W_ukv's halves a head
    at a time, and a post-norm in every entry."""
    params, cfg = served[0], served[1]
    assert cfg.layer_pattern == "ADAEAE" and not cfg.tie_embeddings
    assert cfg.latent_kv and cfg.sandwich_norm and not cfg.stateful
    assert {k: len(v) for k, v in params["layers"].items() if v} == {
        "latent": 3, "dense": 1, "moe": 2}
    assert params["lm_head"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert not np.any(np.asarray(params["layers"]["moe"][0]["router_bias"]))
    latent = params["layers"]["latent"][0]
    assert latent["w_uk"].shape == (4, 16, 32)
    assert latent["w_uv"].shape == (4, 32, 16)
    assert all("post_norm" in p for v in params["layers"].values() for p in v)


def test_sound_sample_passes_every_clause(served):
    params, cfg, limits, sample, replayed = served
    got = controls.judged("sound", params, cfg, sample, limits, replayed)
    assert got["ok"], got["why"]
    # float32 against float32: summation order only.
    assert got["logit_floor"] < 1e-1 and got["logit_distance"] < 1e-1
    assert got["replayed"] == got["exact"] == 32


@pytest.mark.parametrize("control", [
    c for c in controls.CONTROLS if c not in ("sound", "int8_weights")])
def test_control_over_the_reference_is_refused(served, control):
    """Each by a clause on the LOGITS."""
    params, cfg, limits, sample, replayed = served
    got = controls.judged(control, params, cfg, sample, limits, replayed)
    assert not got["ok"]
    assert any(text.startswith("logit_") for text in got["why"]), got["why"]


def test_int8_weights_read_far_from_sound_and_under_the_chips_limit_at_toy_size(
        served):
    """The one control the toy cannot refuse by the chip's limits: a
    64-wide toy's int8 rounding reads a floor of ~1.9 % (the sound toy:
    1e-4), just under the 2.1 % that stands between the chip's sound
    0.96-1.18 and its int8 readings of 3.87-4.51 at the published widths
    (PERF.md section 4); at a limit of half the sound chip reading it is
    refused here too."""
    params, cfg, limits, sample, replayed = served
    got = controls.judged("int8_weights", params, cfg, sample, limits,
                          replayed)
    assert 1.0 < got["logit_floor"] < limits["max_logit_floor"]
    tight = {**limits, "max_logit_floor": 0.5}
    refused = controls.judged("int8_weights", params, cfg, sample, tight,
                              replayed)
    assert not refused["ok"]
    assert any(t.startswith("logit_floor") for t in refused["why"])


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
                       if r["name"] == "openPangu-Ultra-MoE-718B")
        assert spec["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in spec["reduced"]:
                assert spec[key] == value, key
        assert {k: row["config"][k] for k in spec["reduced"]} == {
            k: spec["published"][k] for k in spec["reduced"]}
    assert spec["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert [spec[k] for k in spec["reduced"]] == [7, 1, 8, 19200, 0]
    assert spec["published"]["vocab_size"] == 8 * spec["vocab_size"]
    assert spec["router_width"] == spec["published"]["n_routed_experts"] == 256
    assert spec["chips"] == 1 and spec["chips_sharing_a_layer"] == 32
    for width, value in {
            "hidden_size": 7680, "num_attention_heads": 128,
            "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 18432,
            "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
            "n_shared_experts": 1, "rope_theta": 25_600_000,
            "routed_scaling_factor": 2.5, "sandwich_norm": True}.items():
        assert spec[width] == value
    for key in ("latent_norms", "rope_pairing", "no_yarn_factor",
                "post_norm_place", "router", "stored_row_width",
                "mtp_left_out"):
        assert key in spec["assumed"]


def test_published_widths_build_the_counts_of_the_name():
    """This chip holds 4.66 B parameters; the whole model, built from the
    published depth, expert count and vocabulary by the same adapter,
    719 B (the name's 718 B; the multi-token-prediction module is left
    out, and both vocabulary tables are counted)."""
    import extension

    spec = controls.load_spec()
    adapter = extension.load("adapters", spec["adapter"])
    cfg = adapter.model_config(spec, tiny=False)
    assert cfg.layer_pattern == spec["layer_pattern"] == "AD" + "AE" * 6
    assert cfg.kv_layers == 7 and (cfg.kv_parts, cfg.kv_row_width) == (1, 640)
    assert cfg.latent_width == 576 and cfg.q_scale == 192 ** -0.5
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.first_expert) == (
        256, 8, 0)
    assert cfg.sandwich_norm and cfg.router_scoring == "sigmoid"
    assert cfg.moe_shared_intermediate == 2048 and not cfg.tie_embeddings
    assert abs(cfg.num_params() / 4.6554e9 - 1) < 1e-3
    whole = adapter.model_config(
        {**spec, **spec["published"], "n_routed_experts": 256}, tiny=False)
    assert whole.num_layers == 122 and whole.kv_layers == 61
    assert whole.layer_pattern.count("D") == 3 and whole.experts_held == 256
    assert abs(whole.num_params() / 718e9 - 1) < 5e-3


def test_pool_reads_what_the_configuration_holds():
    """8,192 pages x 16 rows x 7 layers x 640 stored columns x 2 bytes; at
    the published 576 columns 1.06 GB; expanded K and V would be 71 x."""
    import extension
    import jax

    from polykey_tpu.engine.kv_cache import init_paged_kv, kv_pool_bytes

    spec = controls.load_spec()
    cfg = extension.load("adapters", spec["adapter"]).model_config(spec, False)
    eng = spec["engine"]
    pool = jax.eval_shape(lambda: init_paged_kv(
        cfg, eng["num_pages"], eng["page_size"]))
    assert pool.kv.shape == (7, 8192, 1, 16, 640)
    nbytes = kv_pool_bytes(cfg, eng["num_pages"], eng["page_size"])
    assert nbytes == pool.kv.size * 2 == 8192 * 16 * 7 * 640 * 2
    assert round(nbytes / 1e9, 2) == 1.17
    assert round(nbytes * 576 / 640 / 1e9, 2) == 1.06
    expanded = 128 * (192 + 128) * 2 * 7 * 8192 * 16
    assert round(expanded / (nbytes * 576 / 640)) == 71


def test_costs_are_the_shapes():
    import kernel_costs

    spec = controls.load_spec()
    costs = kernel_costs.for_spec(spec)
    weights = costs.decode_weight_bytes(spec)
    # Every matrix once: the 4.66 B parameters in bf16 less the embedding
    # (a gather), gains not at all.
    assert abs(weights / (2 * (4.6554e9 - 19200 * 7680)) - 1) < 1e-3
    experts = 6 * 8 * 3 * 7680 * 2048 * 2
    latent = 7 * costs.latent_layer_params(spec) * 2
    assert costs.latent_layer_params(spec) == 196_575_232
    assert 0.48 < experts / weights < 0.51 and 0.29 < latent / weights < 0.31
    assert costs.kv_bytes_per_token_layer(spec) == 1152
    step = costs.decode_step_bytes(spec, 64 * 450)
    assert step == weights + 64 * 450 * 7 * 1152
    assert 0.02 < (step - weights) / step < 0.03
    call = costs.moe_held_experts(spec, 64)
    # The chosen pairs (ISSUE 58): 64 rows x top-8 x 8 held of 256.
    assert call["flops"] == 64 * 8 * 8 / 256 * 6 * 7680 * 2048
    assert abs(call["bytes"] / (experts / 6) - 1) < 1e-2
    assert costs.held_experts(spec) == 8
    # The latent read: 450 rows of 1,152 B and 278,528 B of heads a lane;
    # 278,528 FLOP a token: 157 FLOP a byte, 242 in the limit.
    read = costs.mla_latent_decode(spec, 64, live_tokens=64 * 450)
    assert read["bytes"] == 64 * (450 * 1152 + 278_528)
    assert read["flops"] == 64 * 450 * 278_528
    assert round(read["flops"] / read["bytes"]) == 157
    assert costs.mla_latent_decode(spec, 64) == read
    far = costs.mla_latent_decode(spec, 64, live_tokens=64 * 10**6)
    assert round(far["flops"] / far["bytes"]) == 242


def trace_with(kernel_s: float, count: int, program_s: float) -> dict:
    import trace_reduce

    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        trace = trace_reduce.reduce(json.load(f))
    kernels = {**trace["kernels"], "mla_latent_decode": {
        "total_s": kernel_s, "count": count,
        "by_program": {"jit__decode_fn": kernel_s}}}
    modules = {**trace["modules"], "jit__decode_fn": {
        "count": 10, "total_s": program_s}}
    return trace, {**trace, "kernels": kernels, "modules": modules}


def live_context(**kw):
    """A capture with 64 streams of 450 tokens live throughout."""
    from run import Context

    traced = {"start": 100.0, "stop": 104.0, "stop_call_s": 60.0}
    requests = [{"times": [90.0, 110.0], "counts": [1, 1], "final": None,
                 "prompt_tokens": 449}] * 64
    return Context(samples={"meta": {"traced": traced},
                            "requests": requests}, **kw)


def test_roofline_reader_reads_the_kernel_by_its_name():
    import extension
    import peaks

    reader = extension.load("metrics", "mla_latent_decode_roofline.py")
    spec = controls.load_spec()
    chip = peaks.row("TPU v5 lite")
    # 64 x (450 x 1,152 + 278,528) bytes at 819 GB/s: memory-bound.
    least = 64 * (450 * 1152 + 278_528) / chip["hbm_bytes_per_s"]
    recorded, trace = trace_with(560 * least / 0.4, 560, 1.0)
    # The recorded trace is a GQA decoder's: no such kernel, no number —
    # and none from a program that lacks the kernel (the parent's), or
    # from a configuration whose costs module does not reckon it.
    assert reader.read(live_context(trace=recorded, spec=spec)) is None
    assert reader.read(live_context(trace=None, spec=spec)) is None
    got = reader.read(live_context(trace=trace, spec=spec, peaks=chip))
    assert got == pytest.approx(40.0)
    sibling = json.load(open(os.path.join(
        os.path.dirname(controls.CONFIG), "lfm2-24b-a2b-pp4.json")))
    assert reader.read(
        live_context(trace=trace, spec=sibling, peaks=chip)) is None


def test_step_share_reader_divides_by_the_decode_programs_time():
    import extension

    reader = extension.load("metrics", "mla_latent_decode_step_share.py")
    spec = controls.load_spec()
    recorded, trace = trace_with(0.3, 560, 2.0)
    assert reader.read(live_context(trace=trace, spec=spec)) == \
        pytest.approx(15.0)
    assert reader.read(live_context(trace=recorded, spec=spec)) is None
    assert reader.read(live_context(trace=None, spec=spec)) is None
    no_program = {**trace, "modules": {}}
    assert reader.read(live_context(trace=no_program, spec=spec)) is None


def test_manifest_lists_the_cell_where_the_issue_says():
    import run

    manifest = run.load_manifest()
    names = [m["name"] for m in run.metrics_for(manifest, CELL, "per_layer")]
    for name in ("mla_latent_decode_roofline", "mla_latent_decode_step_share",
                 "moe_held_experts_roofline", "moe_held_experts_hit_share",
                 "decode_mbu", "decode_step_device_ms", "peak_hbm_gb"):
        assert name in names
    assert "paged_attention_decode_roofline" not in names
    end = [m["name"] for m in run.metrics_for(manifest, CELL, "end_to_end")]
    assert end == ["output_tok_s", "tpot_ms_mean", "setup_s"]
    for name in ("mla_latent_decode_roofline", "mla_latent_decode_step_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
