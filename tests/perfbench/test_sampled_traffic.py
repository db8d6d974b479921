"""A configuration whose requests are SAMPLED (ISSUE 58: its file's
"sampling" group, as a model's generation parameters are published with
the model; `nemotron-3-super-ep4` alone states one): every request of the
window carries the group's parameters and a seed of its own, a function of
(`--seed`, client, request index); the traffic files and their tables of
lengths are what they were; the server warms the sampled variants for such
a configuration and for no other; the served sample is then taken from the
sampled programs with every slot in use, a greedy request among sampled
companions, one companion's draws held to the reference's top-k; and why
the group states a top-k — the benchmark's narrowed head leaves 0, not
-inf, outside printable ASCII."""

import json
import os

import numpy as np
import pytest

from perfbench_paths import BENCH, ROOT

import loadgen
import quantile_table
import reference
import run
import server_child
import traffic
from test_moe_grouped_share import hybrid_cells

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
HYBRID = hybrid_cells(MANIFEST)
SEED = 5800000207


def entry_of(cell: str) -> dict:
    return next(w for w in MANIFEST["workloads"] if w["name"] == cell)


def spec_of(cell: str) -> dict:
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == entry_of(cell)["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        return json.load(f)


def mix_of(cell: str) -> dict:
    return traffic.load(entry_of(cell)["traffic"])


def plan_of(cell: str, seed: int = SEED) -> traffic.Plan:
    return traffic.Plan(mix_of(cell), seed, spec_of(cell).get("sampling"))


SAMPLED = [c for c in CELLS if spec_of(c).get("sampling")]


def test_the_cell_whose_streams_collapsed_is_the_one_that_samples():
    assert SAMPLED == ["nemotron-3-super-ep4.decode-wide"]
    assert set(SAMPLED) <= set(HYBRID) and len(HYBRID) == 4
    # One traffic file for the four hybrid cells: they differ in model alone.
    assert {entry_of(c)["traffic"] for c in HYBRID} == {"decode-wide"}


@pytest.mark.parametrize("cell", CELLS)
def test_the_configuration_says_whether_its_requests_are_sampled(cell):
    assert "sampling" not in mix_of(cell)
    asked = plan_of(cell).request(0, 0).sampling
    assert (asked is not None) == (cell in SAMPLED)
    config = server_child.engine_config_from(spec_of(cell), True)
    assert config.warm_sampled_variants == (cell in SAMPLED)


@pytest.mark.parametrize("cell", SAMPLED)
def test_every_request_has_a_sampling_seed_of_its_own(cell):
    plan = plan_of(cell)
    asked = [plan.request(i, k).sampling
             for i in range(plan.clients) for k in range(4)]
    asked += [plan.sampling(-1, i) for i in range(plan.clients)]
    seeds = [a["seed"] for a in asked]
    assert len(set(seeds)) == len(seeds)
    # A Struct number is a double: the served API refuses more than 2**53.
    assert all(isinstance(s, int) and 0 <= s < 2**48 for s in seeds)
    stated = spec_of(cell)["sampling"]
    assert stated["temperature"] > 0
    assert all({k: v for k, v in a.items() if k != "seed"} == stated
               for a in asked)


@pytest.mark.parametrize("cell", SAMPLED)
def test_one_seed_asks_for_the_same_streams_and_another_for_others(cell):
    a, again, other = (plan_of(cell, s) for s in (SEED, SEED, SEED + 1))
    for client in (0, 17, a.clients - 1):
        for k in (0, 1, 5):
            assert a.request(client, k) == again.request(client, k)
            assert (a.request(client, k).sampling["seed"]
                    != other.request(client, k).sampling["seed"])


@pytest.mark.parametrize("cell", SAMPLED)
def test_the_request_on_the_wire_carries_the_parameters(cell):
    asked = plan_of(cell).request(3, 2)
    sent = dict(loadgen.generate_request(
        asked.prompt, asked.output_tokens, asked.sampling).parameters)
    assert sent == {"prompt": asked.prompt,
                    "max_tokens": asked.output_tokens, **asked.sampling}
    # The seed survives the double it travels as.
    assert int(sent["seed"]) == asked.sampling["seed"]
    greedy = dict(loadgen.generate_request("abc", 8).parameters)
    assert greedy == {"prompt": "abc", "max_tokens": 8}


@pytest.mark.parametrize("cell", HYBRID)
def test_the_tables_of_lengths_are_untouched(cell):
    mix = mix_of(cell)
    assert mix["rows_by_client"] == \
        quantile_table.tables()[entry_of(cell)["traffic"]]
    sampled = traffic.Plan(mix, SEED, {"temperature": 1.0, "top_k": 8})
    greedy = traffic.Plan(mix, SEED)
    assert sampled.shape() == greedy.shape()
    for client in (0, 31, 63):
        a, b = sampled.request(client, 1), greedy.request(client, 1)
        assert b.sampling is None and a.sampling["top_k"] == 8
        assert (a.prompt, a.output_tokens, a.think_s) == \
            (b.prompt, b.output_tokens, b.think_s)


@pytest.fixture
def wire(monkeypatch):
    """The served API's stub, answering every stream at once with the
    length asked; `sent`: the parameters of every request, in order."""
    from polykey_tpu.proto import polykey_v2_grpc
    from polykey_tpu.proto import polykey_v2_pb2 as pk

    sent = []

    class Stub:
        def __init__(self, channel):
            pass

        def ExecuteToolStream(self, request, timeout):
            asked = dict(request.parameters)
            sent.append(asked)
            return iter([
                pk.ExecuteToolStreamChunk(delta="a" * int(asked["max_tokens"])),
                pk.ExecuteToolStreamChunk(final=True)])

    monkeypatch.setattr(polykey_v2_grpc, "PolykeyServiceStub", Stub)
    return sent


@pytest.mark.parametrize("cell", CELLS)
def test_the_served_sample_is_taken_from_the_programs_the_window_runs(
        cell, wire, tmp_path):
    """Greedy requests: one request alone, prompt and length and nothing
    else. Sampled requests: the greedy sample LAST, after clients - 1
    sampled companions with seeds of their own, one of them kept beside it
    with the top-k it was drawn under."""
    plan = plan_of(cell)
    records = run.serve_sample("127.0.0.1:1", SEED, str(tmp_path), plan)
    with open(tmp_path / "sample.json") as f:
        sample = json.load(f)
    assert len(sample["output_ids"]) == run.SAMPLE_OUTPUT_TOKENS
    assert sorted(wire[-1]) == ["max_tokens", "prompt"]
    assert records[0]["asked"] == run.SAMPLE_OUTPUT_TOKENS
    if cell not in SAMPLED:
        assert len(wire) == len(records) == 1 and "sampled" not in sample
        return
    assert len(wire) == len(records) == plan.clients
    stated = spec_of(cell)["sampling"]
    companions = wire[:-1]
    assert all({k: s[k] for k in stated} == stated for s in companions)
    assert len({s["seed"] for s in companions}) == plan.clients - 1
    # The companions outlast the sample; the kept one is of its own size.
    assert {int(s["max_tokens"]) for s in companions[:-1]} == \
        {4 * run.SAMPLE_OUTPUT_TOKENS}
    assert int(companions[-1]["max_tokens"]) == run.SAMPLE_OUTPUT_TOKENS
    kept = sample["sampled"]
    assert kept["top_k"] == stated["top_k"]
    assert len(kept["prompt_ids"]) == run.SAMPLE_PROMPT_TOKENS
    assert len(kept["output_ids"]) == run.SAMPLE_OUTPUT_TOKENS
    assert kept["prompt_ids"] != sample["prompt_ids"]


class Cfg:
    vocab_size = 512


def drawn_sample(rows, served, top_k=8):
    return {"allowed_first": traffic.FIRST_ID, "allowed_last": traffic.LAST_ID,
            "sampled": {"prompt_ids": [1, 40, 41], "output_ids": served,
                        "top_k": top_k}}


@pytest.mark.parametrize("fault, clause", [
    (None, None),
    ("a token the cut should have dropped", "sampled_outliers"),
    ("no truncation", "sampled_mean_margin"),
    ("an id outside the head", "sampled_outside_head"),
])
def test_a_sampled_companion_is_held_to_the_references_top_k(fault, clause):
    """`reference.judge_sampled` on a stand-in forward: draws from the 8
    largest allowed logits pass; the faults a sampled step can have that a
    greedy token cannot show are refused, each by the clause named."""
    rng = np.random.default_rng(58)
    tokens, first, last = 32, traffic.FIRST_ID, traffic.LAST_ID
    logits = np.zeros((3 + tokens - 1, Cfg.vocab_size), np.float32)
    logits[:, first:last + 1] = 2.0 * rng.standard_normal(
        (len(logits), last + 1 - first))
    rows = logits[2:]
    order = np.argsort(-rows[:, first:last + 1], axis=1) + first
    served = [int(order[t, rng.integers(8)]) for t in range(tokens)]
    if fault == "a token the cut should have dropped":
        served[5] = int(order[5, 60])
    elif fault == "no truncation":
        served = [int(order[t, rng.integers(40)]) for t in range(tokens)]
    elif fault == "an id outside the head":
        served[7] = last + 9
    limits = {"max_margin": 1.5, "max_mean_margin": 0.1}
    verdict = reference.judge_sampled(
        lambda params, cfg, ids: logits[:len(ids)], None, Cfg,
        drawn_sample(rows, served), limits)
    assert verdict["ok"] == (fault is None), verdict["checks"]
    failed = [w.split()[0] for w in verdict["why"]]
    assert clause in failed if clause else not failed
    # Each number compared stands beside its limit, sound or not.
    assert all(name in verdict["checks"] for name in (
        "sampled_outliers", "sampled_mean_margin", "sampled_outside_head"))


def test_the_greedy_margin_is_the_sampled_one_at_top_k_one():
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((6, 300)).astype(np.float32)
    allowed = np.zeros(300, bool)
    allowed[traffic.FIRST_ID:traffic.LAST_ID + 1] = True
    served = [40, 50, 60, 70, 80, 90]
    greedy = [float(np.max(np.where(allowed, row, -np.inf))) - float(row[t])
              for row, t in zip(rows, served)]
    assert np.allclose(reference.sampled_margins(rows, served, allowed, 1),
                       greedy)


@pytest.mark.parametrize("top_k, inside", [(8, True), (0, False)])
def test_why_the_group_states_a_top_k(top_k, inside):
    """`server_child.narrow_head` zeroes the head's columns outside
    printable ASCII: their logits are 0, not -inf (the head is a matmul
    without a bias: there is nothing else the harness could set). A greedy
    token never falls there (some of 95 logits is above 0); a token drawn
    from the whole distribution nearly always does (32,673 ids at e^0
    against 95 at about e^0.5); one drawn from the 8 largest never does (8
    of 95 symmetric logits are above 0 but for 1e-18 of the steps)."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.engine.sampling import sample_dynamic_rows

    rows, vocab = 64, 32768
    rng = np.random.default_rng(58)
    logits = np.zeros((rows, vocab), np.float32)
    logits[:, traffic.FIRST_ID:traffic.LAST_ID + 1] = rng.standard_normal(
        (rows, traffic.LAST_ID + 1 - traffic.FIRST_ID))
    drawn = np.asarray(sample_dynamic_rows(
        jnp.asarray(logits), jax.random.split(jax.random.PRNGKey(58), rows),
        jnp.ones((rows,)), jnp.ones((rows,)),
        jnp.full((rows,), top_k, jnp.int32)))
    within = (drawn >= traffic.FIRST_ID) & (drawn <= traffic.LAST_ID)
    assert within.all() if inside else within.mean() < 0.1
    if inside:
        assert all(spec_of(c)["sampling"]["top_k"] == top_k for c in SAMPLED)
        assert len(set(drawn.tolist())) > 20      # and it is no argmax


def sort_share(ops: dict, spec: dict):
    import extension

    reader = extension.load("metrics", "sampled_head_sort_step_share.py")
    trace = {"modules": {"jit__decode_fn": {"total_s": 3.78, "count": 27}},
             "ops": {k: {"total_s": v, "count": 27} for k, v in ops.items()}}
    return reader.read(run.Context(trace=trace, spec=spec))


@pytest.mark.parametrize("cell", SAMPLED)
def test_the_sampled_heads_sort_is_read_as_a_share_of_the_step(cell):
    """My chip run, PR 58 (seed 5600000101 traced): `sort.64
    f32[64,32768]` took 0.2948 s of the capture; a router's small sort,
    another program's and another width's are not the sampled head's."""
    spec = spec_of(cell)
    ops = {f"jit__decode_fn/sort.64 f32[64,{spec['vocab_size']}]": 0.2948,
           "jit__decode_fn/sort.3 f32[64,512]": 0.01,
           f"jit__prefill_rows_fn/sort.9 f32[8,{spec['vocab_size']}]": 0.02,
           "jit__decode_fn/moe_held_experts_grouped.22 f32[128,1024]": 0.34}
    assert sort_share(ops, spec) == pytest.approx(100 * 0.2948 / 3.78)
    # A greedy step sorts nothing: the reader returns nothing, never 0.
    greedy = {k: v for k, v in ops.items() if "sort.64" not in k}
    assert sort_share(greedy, spec) is None
    entry = next(m for m in MANIFEST["per_layer"]
                 if m["name"] == "sampled_head_sort_step_share")
    assert entry["workloads"] == SAMPLED and entry["moves"] == "tpot_ms_mean"
