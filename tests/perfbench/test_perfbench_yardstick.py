"""The yardstick's arithmetic: traffic, estimators, trace reduction, peaks,
kernel costs, the plain reference. Pure Python except the reference."""

import gzip
import json
import os
import statistics

import pytest

from perfbench_paths import BENCH, DATA

import estimators
import kernel_costs
import noise_study
import peaks
import quantile_table
import trace_reduce
import traffic

TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_shape_is_the_same_for_every_seed(name):
    mix = traffic.load(name)
    a, b = traffic.Plan(mix, 1), traffic.Plan(mix, 4000000007)
    assert a.shape() == b.shape()
    assert a.prefix != b.prefix or mix["shared_prefix_tokens"] <= 1
    for client in range(mix["clients"]):
        ra, rb = a.request(client, 1), b.request(client, 1)
        assert ra.prompt != rb.prompt
        assert ra.prompt_tokens == len(ra.prompt) + 1
    # One seed gives the same inputs again.
    assert a.request(0, 3) == traffic.Plan(mix, 1).request(0, 3)


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_tables_are_the_stated_quantiles(name):
    assert traffic.load(name)["rows_by_client"] == quantile_table.tables()[name]


@pytest.mark.parametrize("name", TRAFFIC)
def test_first_requests_are_phased_and_prefix_is_shared(name):
    mix = traffic.load(name)
    plan = traffic.Plan(mix, 7)
    n = mix["clients"]
    firsts = [plan.request(i, 0) for i in range(n)]
    assert all(r.output_tokens % 8 == 0 and r.think_s == 0 for r in firsts)
    full = plan.rows[n - 1][0][1]
    assert firsts[-1].output_tokens == full
    shared = mix["shared_prefix_tokens"]
    if shared:
        heads = {plan.request(i, k).prompt[:shared - 1]
                 for i in range(n) for k in range(3)}
        assert len(heads) == 1
    lo, hi = mix["think_ms"]
    for k in range(1, 5):
        assert lo / 1000 <= plan.request(0, k).think_s <= hi / 1000


@pytest.mark.parametrize("name", TRAFFIC)
def test_tiny_scale_keeps_the_shape(name):
    mix = traffic.load(name)
    small = traffic.scaled(mix, tiny=True)
    assert traffic.scaled(mix, tiny=False) is mix
    assert [len(r) for r in small["rows_by_client"]] == \
        [len(r) for r in mix["rows_by_client"]]
    assert max(p for rows in small["rows_by_client"] for p, _ in rows) <= 64


def bursty(period=0.464, lanes=16, per_burst=8, seconds=60.0, jitter=0.003):
    """Synthetic decode traffic: every `period` each lane gets a burst of
    tokens within a few milliseconds."""
    requests = []
    for lane in range(lanes):
        times, t = [], 0.05
        while t < seconds:
            times += [t + jitter * lane / lanes + 1e-4 * j
                      for j in range(per_burst)]
            t += period
        requests.append({"client": lane, "index": 0, "send": 0.0,
                         "times": times, "counts": [1] * len(times),
                         "usage": None})
    return requests


def samples_of(requests, t_open, t_close):
    return {"meta": {"t_open": t_open, "t_close": t_close},
            "requests": requests}


@pytest.mark.parametrize("shift", [0.0, 0.1, 0.232, 0.4])
def test_edge_aligned_rate_ignores_where_the_window_falls(shift):
    requests = bursty()
    true_rate = 16 * 8 / 0.464
    base = estimators.output_tok_s(samples_of(requests, 5.0, 50.0))
    moved = estimators.output_tok_s(samples_of(requests, 5.0 + shift, 50.0 + shift))
    assert base == pytest.approx(true_rate, rel=2e-3)
    assert moved == pytest.approx(base, rel=2e-3)


def test_fixed_wall_rate_gains_or_loses_a_burst():
    requests = bursty()
    rates = [estimators.output_tok_s_fixed_wall(
        samples_of(requests, 5.0 + s, 25.0 + s)) for s in (0.0, 0.15)]
    assert abs(rates[0] - rates[1]) / rates[0] > 5e-3


def test_stream_statistics_on_known_streams():
    requests = bursty(lanes=4, seconds=30.0)
    s = samples_of(requests, 1.0, 29.0)
    assert estimators.tpot_ms_p50(s) == pytest.approx(464 / 8, rel=0.02)
    assert estimators.tpot_ms_mean(s) == pytest.approx(464 / 8, rel=0.02)
    # Token-weighted: a stream cut to 40 tokens with a long first gap moves
    # the plain mean over streams five times as far.
    cut = dict(requests[0], times=[1.0] + requests[0]["times"][30:69],
               counts=[1] * 40)
    mixed = samples_of(requests[1:] + [cut], 0.5, 29.0)
    whole = samples_of(requests, 0.5, 29.0)
    plain = (estimators.tpot_ms_stream_mean(mixed)
             - estimators.tpot_ms_stream_mean(whole))
    weighted = estimators.tpot_ms_mean(mixed) - estimators.tpot_ms_mean(whole)
    assert plain > 3 * weighted > 0
    assert estimators.tpot_ms_p50(samples_of(requests, 1.0, 2.0)) is None
    for i, r in enumerate(requests):
        r["send"] = 2.0 + i
        r["times"] = [t for t in r["times"] if t > r["send"] + 0.5]
        r["counts"] = [1] * len(r["times"])
    ttfts = estimators.ttfts_ms(s)
    assert len(ttfts) == 4 and all(500 <= t <= 1000 for t in ttfts)
    assert estimators.ttft_ms_mean(s) == pytest.approx(statistics.fmean(ttfts))
    assert estimators.ttft_ms_p90(s) <= max(ttfts)
    assert estimators.requests_in_window(s) == 4.0


def test_percentile_trim_and_spread():
    values = list(range(1, 101))
    assert estimators.percentile(values, 50) == pytest.approx(50.5)
    assert estimators.percentile(values, 90) == pytest.approx(90.1)
    assert estimators.trimmed_mean(values + [10_000]) < 60
    q1, _, q3 = statistics.quantiles([1, 2, 3, 4, 5, 6], n=4)
    assert estimators.spread([1, 2, 3, 4, 5, 6]) == pytest.approx((q3 - q1) / 3.5)


def test_noise_study_table_from_sample_files(tmp_path):
    for label, scale in (("setA", 1.0), ("setB", 1.01)):
        cell = tmp_path / label / "some.cell"
        cell.mkdir(parents=True)
        for seed in (1, 2, 3):
            requests = bursty(period=0.464 * scale, seconds=40.0,
                              jitter=0.001 * seed)
            doc = samples_of(requests, 2.0, 38.0)
            doc["meta"]["setup_s"] = 40.0 + seed
            doc["meta"]["t_start"] = 100.0 * seed
            with gzip.open(cell / f"seed{seed}.trace0.samples.json.gz", "wt") as f:
                json.dump(doc, f)
    dirs = [str(tmp_path / "setA"), str(tmp_path / "setB")]
    rows = noise_study.table(dirs)
    rate = next(r for r in rows if r[1] == "output_tok_s" and r[2] is None)
    assert rate[3][0][0] == pytest.approx(16 * 8 / 0.464, rel=5e-3)
    assert rate[3][1][0] < rate[3][0][0]
    assert "output_tok_s.fixed_wall" in noise_study.render(rows, dirs)


def recorded():
    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        return json.load(f)


def test_trace_reduction_on_the_recorded_trace():
    reduced = trace_reduce.reduce(recorded())
    assert reduced["planes"] == 1
    assert reduced["window_s"] == pytest.approx(0.228956185)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(0.228938996)
    assert reduced["kernels"]["flash_attention"]["count"] == 32
    assert reduced["kernels"]["flash_attention"]["total_s"] == \
        pytest.approx(0.004829793)
    assert set(reduced["kernels"]["paged_attention_decode"]["by_program"]) == \
        {"jit__decode_fn"}
    assert reduced["modules"]["jit__prefill_fn"]["count"] == 1
    assert reduced["modules"]["jit__decode_fn"]["gaps_s"][0] == \
        pytest.approx(0.1051, abs=1e-3)     # the prefill ran in between
    assert reduced["collective_s"] == 0.0
    top, seconds = reduced["device_ops"][0]
    assert top.startswith("jit__decode_fn/") and seconds > 0.013
    assert len(reduced["idle_gaps"]) <= 10
    assert all(name == "no_annotation" or name.startswith("polykey/")
               for name, _ in reduced["idle_gaps"])


def test_trace_reduction_leaves_out_containers_and_finds_collectives():
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f(1)", 0, 1000, {}]]},
        {"name": "XLA Ops", "events": [
            ["while.1", 0, 1000, {}],
            ["fusion.1 bf16[8]", 0, 400, {}],
            ["all-reduce.1 bf16[8]", 300, 300, {}],
            ["fusion.2 bf16[8]", 700, 200, {}],
        ]}]}
    reduced = trace_reduce.reduce({"planes": [plane], "annotations": [
        ["polykey/decode", 880, 200]]})
    assert "jit_f/while.1" not in reduced["ops"]
    assert reduced["collective_s"] == pytest.approx(300e-9)
    assert reduced["collective_exposed_s"] == pytest.approx(200e-9)
    assert reduced["busy_s"] == pytest.approx(1000e-9)
    assert trace_reduce.union_s([(0, 10), (5, 20), (30, 40)]) == 30e-9
    assert trace_reduce.gaps([(0, 10), (5, 20), (30, 40)]) == [(20, 30)]


def test_short_name_of_hlo_text():
    assert trace_reduce.short_name(
        "%copy.48 = bf16[4096,8,8,128]{3,2,1,0:T(8,128)(2,1)} copy(bf16[4096] %x)"
    ) == "copy.48 bf16[4096,8,8,128]"
    assert trace_reduce.short_name(
        "%paged_attention_decode.9 = (f32[16,32,128]{2,1,0}, f32[16]) custom-call()"
    ) == "paged_attention_decode.9 f32[16,32,128]"
    assert trace_reduce.short_name("jit__decode_fn(12)") == "jit__decode_fn(12)"


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.row("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.row("TPU v9 imaginary")


@pytest.mark.parametrize("name", CONFIGS)
def test_kernel_costs_from_shapes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        spec = json.load(f)
    tp = spec["engine"]["tp"]
    weights = kernel_costs.decode_weight_bytes(spec)
    # int8: one byte a parameter; 7.25 B dense, 46.7 B with 8 experts,
    # less the embedding table, split over the chips.
    params = 46.7e9 if spec.get("num_local_experts") else 7.25e9
    embed = spec["vocab_size"] * spec["hidden_size"]
    assert weights == pytest.approx((params - embed) / tp, rel=0.01)
    assert kernel_costs.kv_bytes_per_token_layer(spec) == 2 * 8 * 128 * 2 / tp
    step = kernel_costs.decode_step_bytes(spec, 1000)
    assert step == weights + 1000 * 32 * kernel_costs.kv_bytes_per_token_layer(spec)
    row = peaks.row("TPU v5 lite")
    decode = kernel_costs.paged_decode_call(spec, 8000, 16)
    assert kernel_costs.roofline_seconds(decode, row)[1] == "memory"
    long_prompt = kernel_costs.flash_prefill_call(spec, 2048)
    assert kernel_costs.roofline_seconds(long_prompt, row)[1] == "compute"
    assert long_prompt["flops"] == 4 * (32 / tp) * 128 * 2048 * 2048 / 2


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-mixtral"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_reference_agrees_with_the_package_forward(preset, quantized):
    """The plain reference against the package's own non-paged forward in
    float32 on seeded random weights (int8: same dequantized values)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.generate import unembed
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.models.transformer import forward, init_params

    cfg = get_config(preset)
    params = init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    if quantized:
        params = quantize_params(params, cfg)
    tokens = [1] + [3 + (7 * i) % 250 for i in range(23)]
    ours = reference.forward(params, cfg, tokens)
    hidden, _ = forward(params, cfg, jnp.asarray(tokens)[None],
                        jnp.arange(len(tokens))[None])
    theirs = np.asarray(unembed(params, cfg, hidden))[0]
    # float32 both sides: only the order of summation differs.
    assert np.abs(ours - theirs).max() < 1e-4
    assert ours.std() > 0.5


def test_reference_comparison_catches_a_wrong_token():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import init_params

    cfg = get_config("tiny-llama")
    params = init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    prompt = [1] + [3 + 0x41 + i for i in range(10)]
    served = []
    for _ in range(6):      # greedy by the reference itself, over all ids
        logits = reference.forward(params, cfg, prompt + served)
        served.append(int(np.argmax(logits[-1])))
    sample = {"prompt_ids": prompt, "output_ids": served,
              "allowed_first": 0, "allowed_last": cfg.vocab_size - 1}
    limits = {"max_margin": 0.25, "min_exact_share": 0.5}
    good = reference.compare(params, cfg, sample, limits)
    assert good["ok"] and good["exact"] == 6 and good["max_margin"] <= 0
    worst = int(np.argmin(reference.forward(params, cfg, prompt)[-1]))
    bad = reference.compare(
        params, cfg, {**sample, "output_ids": [worst] + served[1:]}, limits)
    assert not bad["ok"] and bad["max_margin"] > 1.0


def test_hashed_int8_weights_have_the_package_tree_and_spread():
    """perfbench/weights.py: the package's tree structure and shardings,
    int8 uniform in [-127, 127], a scale that gives the init's spread, no
    repeated layer or expert, another tree for another seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference
    import weights
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.models.transformer import init_params
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    cfg = get_config("tiny-mixtral")
    mesh = create_mesh(MeshConfig(), devices=jax.devices()[:1])
    made = weights.hashed_int8(cfg, mesh, jnp.float32, 7)
    package = quantize_params(
        init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg)
    assert jax.tree.structure(made) == jax.tree.structure(package)
    for ours, theirs in zip(jax.tree.leaves(made), jax.tree.leaves(package)):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
    up = made["layers"]["experts"]["up"]
    q = np.asarray(up.q)
    assert q.dtype == np.int8 and q.min() >= -127 and q.max() == 127
    assert abs(q.astype(np.float64).std() - weights.UNIFORM_INT8_STD) < 1.0
    assert not np.array_equal(q[0], q[1]) and not np.array_equal(q[0, 0], q[0, 1])
    dense = np.asarray(reference.f32(up))
    assert dense.std() == pytest.approx(cfg.hidden_size ** -0.5, rel=0.03)
    assert np.asarray(made["final_norm"]).min() == 1.0
    router = np.asarray(made["layers"]["router"])
    assert router.std() == pytest.approx(cfg.hidden_size ** -0.5, rel=0.1)
    again = weights.hashed_int8(cfg, mesh, jnp.float32, 7)
    other = weights.hashed_int8(cfg, mesh, jnp.float32, 8)
    assert np.array_equal(np.asarray(again["embed"].q), np.asarray(made["embed"].q))
    assert not np.array_equal(np.asarray(other["embed"].q), np.asarray(made["embed"].q))
    logits = reference.forward(made, cfg, [1] + list(range(40, 60)))
    assert 0.5 < logits.std() < 2.0
