"""The exact sampler's thresholds from a bounded sorted head (ISSUE 61):
`engine/sampling.py` `_trunc_thresholds` reads the k-th largest value and
the nucleus' smallest kept value out of each row's `HEAD_WIDTH` largest
values (`_sorted_head`), and sorts the whole vocabulary only on a call
where a live sampled row has `top_k > HEAD_WIDTH` or a nucleus that does
not close inside the head. Every threshold here is compared with the
parent's rule — one descending sort of the whole row — kept below as the
plain reference; the sampled decode program counts the sub-steps that took
the sort (`sampler_full_sort_steps_total` of `sampler_steps_total`), and
the greedy programs hold no sampler at all."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine import engine as engine_mod
from polykey_tpu.engine import sampling
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.kv_cache import SlotState
from polykey_tpu.engine.metrics import EngineMetrics
from polykey_tpu.models.config import get_config
from polykey_tpu.models.transformer import init_params
from polykey_tpu.obs.exposition import engine_collector
from pattern_stack import PAGES_PER_SEQ, SLOTS, SlotBatch

W = sampling.HEAD_WIDTH
ROWS = 6
VOCABS = (64, 32768, 65536)
TOP_KS = {"k1": 1, "k8": 8, "kW": W, "kW+1": W + 1, "k0": 0}
TOP_PS = (1.0, 0.95, 0.5, 0.01, 0.0)
TIES = ("distinct", "tie_at_kth", "tie_at_nucleus_edge")


# -- the plain reference: the parent's rule, one sort of the whole row -------

def reference_thresholds(scaled, top_p, top_k):
    """(thr_p, thr_k, kept) [N] in numpy float32 as the parent computed
    them: softmax over the SORTED row, exclusive cumulative mass < p, the
    first entry always kept; the k-th value, or the last where top_k is
    off. `kept` is how many entries the nucleus holds."""
    sorted_desc = np.asarray(jnp.sort(scaled, axis=-1))[:, ::-1]
    probs = np.asarray(jax.nn.softmax(jnp.asarray(sorted_desc), axis=-1))
    keep = np.asarray(jnp.cumsum(jnp.asarray(probs), axis=-1)) - probs \
        < np.asarray(top_p, np.float32)[:, None]
    keep[:, 0] = True
    thr_p = np.where(keep, sorted_desc, np.inf).min(axis=-1)
    V = sorted_desc.shape[-1]
    kidx = np.clip(np.where(top_k > 0, top_k, V) - 1, 0, V - 1)
    thr_k = np.take_along_axis(sorted_desc, kidx[:, None], axis=-1)[:, 0]
    return thr_p, thr_k, keep.sum(axis=-1)


def reference_masked(logits, temperature, top_p, top_k):
    """The parent's exact `_masked_rows`: the scaled logits with everything
    under either threshold at -inf."""
    scaled = np.asarray(logits) / np.maximum(temperature, 1e-6)[:, None]
    thr_p, thr_k, _ = reference_thresholds(jnp.asarray(scaled), top_p, top_k)
    return np.where((scaled < thr_p[:, None]) | (scaled < thr_k[:, None]),
                    -np.inf, scaled).astype(np.float32)


# -- seeded inputs ----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def seeded_logits(V: int, ties: str, top_k: int, top_p: float):
    """[ROWS, V] float32: rows 0-3 are a served head's — a handful of ids
    far above a wide rest, so that a usual nucleus closes early — rows 4-5
    are nearly flat, so that it does not. `ties` plants equal values where
    a threshold falls: at the k-th largest value, or where the nucleus
    ends by the reference's own count."""
    rng = np.random.default_rng(V * 7 + 1)
    x = rng.standard_normal((ROWS, V)).astype(np.float32)
    lifted = min(V, 12)
    x[:4, :lifted] += np.linspace(14.0, 6.0, lifted, dtype=np.float32)
    x[4:] *= 0.05
    order = np.argsort(-x, axis=-1, kind="stable")
    rows = np.arange(ROWS)
    if ties == "tie_at_kth" and 0 < top_k < V:
        # The k-th value three times: ranks k-1, k and k+1 (where there).
        for rank in (top_k, top_k + 1):
            if rank < V:
                x[rows, order[:, rank]] = x[rows, order[:, top_k - 1]]
    elif ties == "tie_at_nucleus_edge":
        kept = reference_thresholds(
            jnp.asarray(x), np.full((ROWS,), top_p, np.float32),
            np.zeros((ROWS,), np.int32))[2]
        edge = np.clip(kept, 1, V - 1)
        x[rows, order[rows, edge]] = x[rows, order[rows, edge - 1]]
    return x


@functools.lru_cache(maxsize=None)
def thresholds_fn():
    return jax.jit(sampling._trunc_thresholds)


def settings(top_p, top_k, rows=ROWS):
    return (np.full((rows,), top_p, np.float32),
            np.full((rows,), top_k, np.int32))


def head_thresholds(x, top_p, top_k):
    thr_p, thr_k, full = thresholds_fn()(
        jnp.asarray(x), jnp.asarray(top_p), jnp.asarray(top_k))
    return np.asarray(thr_p)[:, 0], np.asarray(thr_k)[:, 0], bool(full)


# -- the sorted head --------------------------------------------------------

@pytest.mark.parametrize("V", [64, 65, 259, 1000, 32768, 50257, 65536])
@pytest.mark.parametrize("width", [1, 8, W])
def test_sorted_head_is_the_rows_largest_values_in_order(V, width):
    """Duplicates included, at a width the row divides and one it does
    not (the padding is -inf and never reaches a head)."""
    rng = np.random.default_rng(V + width)
    x = np.round(rng.standard_normal((4, V)) * 4).astype(np.float32) / 4
    assert len(np.unique(x[0])) < V            # the rows do hold ties
    width = min(width, V)
    got = np.asarray(jax.jit(sampling._sorted_head, static_argnums=1)(
        jnp.asarray(x), width))
    np.testing.assert_array_equal(got, -np.sort(-x, axis=-1)[:, :width])


# -- thresholds against the full sort --------------------------------------

CASES = [(V, k, p, t) for V in VOCABS for k in TOP_KS for p in TOP_PS
         for t in TIES]


@functools.lru_cache(maxsize=None)      # the aggregate test reads it again
def compare_case(V, k, top_p, ties):
    """How many thresholds differ from the reference's where the rule is
    on, plus 1 where the call's need of the sort differs from what the
    reference's own count of the nucleus says."""
    top_k = TOP_KS[k]
    x = seeded_logits(V, ties, top_k, top_p)
    tp, tk = settings(top_p, top_k)
    want_p, want_k, kept = reference_thresholds(jnp.asarray(x), tp, tk)
    got_p, got_k, full = head_thresholds(x, tp, tk)
    differ = 0
    if top_k > 0:
        np.testing.assert_array_equal(got_k, want_k)    # copies of values
    else:
        assert np.all(np.isneginf(got_k))
    if top_p < 1.0:
        differ += int((got_p != want_p).sum())
    else:
        assert np.all(np.isneginf(got_p))               # 1.0 -> disabled
    head = min(V, W)
    needs = (top_k > head) | ((top_p < 1.0) & (kept >= head) & (head < V))
    differ += int(full != needs.any())
    return differ


@pytest.mark.parametrize("V, k, top_p, ties", CASES)
def test_head_thresholds_equal_the_full_sorts(V, k, top_p, ties):
    """Both thresholds, ties kept, and the sort taken exactly when the
    reference's own count says a row's nucleus reaches the head's end or
    its top_k lies past it. The head's probabilities are normalised by
    logsumexp over the row as it lies, the reference's by a softmax over
    the sorted row: where the two could part by one entry at an exact
    boundary, the aggregate test below counts it."""
    assert compare_case(V, k, top_p, ties) == 0


def test_boundary_disagreements_over_all_seeded_inputs_are_counted():
    differ = sum(compare_case(*case) for case in CASES)
    assert differ == 0, (
        f"{differ} thresholds of {len(CASES) * ROWS * 2} moved by one entry "
        "at an exact boundary between the two normalisers")


def test_a_row_is_answered_from_its_head_whatever_its_neighbours_need():
    """Row 0 alone, and beside a row that sends the call to the sort: the
    same two numbers, so a request's stream cannot depend on the batch."""
    x = seeded_logits(32768, "distinct", 8, 0.9)
    tp, tk = settings(0.9, 8)
    alone_p, alone_k, full = head_thresholds(x[:4], tp[:4], tk[:4])
    assert not full
    tk[5] = W + 1
    beside_p, beside_k, full = head_thresholds(x, tp, tk)
    assert full
    np.testing.assert_array_equal(beside_p[:4], alone_p)
    np.testing.assert_array_equal(beside_k[:4], alone_k)


# -- which rows ask for the sort ----------------------------------------

PEAKED, FLAT = 0, 4        # rows of seeded_logits


@pytest.mark.parametrize("row, temperature, live, top_p, top_k, want", [
    (PEAKED, 1.0, True, 1.0, W + 1, True),      # top_k past the head
    (PEAKED, 1.0, True, 1.0, W, False),         # ... at its end
    (FLAT, 1.0, True, 0.9, 0, True),            # a nucleus that stays open
    (PEAKED, 1.0, True, 0.9, 0, False),         # one that closes
    (FLAT, 1.0, True, 0.9, 8, True),            # open, whatever top_k adds
    (FLAT, 1.0, True, 1.0, 0, False),           # untruncated
    (FLAT, 1.0, True, 1.0, 8, False),           # top_p 1.0 is disabled
    (FLAT, 0.0, True, 0.9, W + 1, False),       # greedy: stale settings
    (FLAT, 1.0, False, 0.9, W + 1, False),      # an idle decode lane
    (FLAT, 0.0, False, 0.5, 0, False),
])
def test_needs_full_only_for_live_sampled_rows_past_their_head(
        row, temperature, live, top_p, top_k, want):
    x = seeded_logits(32768, "distinct", 8, 0.9)[row:row + 1]
    tp, tk = settings(top_p, top_k, rows=1)
    full = sampling._masked_rows(
        jnp.asarray(x), jnp.full((1,), temperature), jnp.asarray(tp),
        jnp.asarray(tk), 0, jnp.asarray([live]))[4]
    assert bool(full) is want
    if live:       # without the mask every row counts as live
        assert bool(sampling._masked_rows(
            jnp.asarray(x), jnp.full((1,), temperature), jnp.asarray(tp),
            jnp.asarray(tk), 0)[4]) is want


def test_the_prefiltered_path_never_sorts_the_vocabulary():
    x = seeded_logits(32768, "distinct", 8, 0.9)
    tp, tk = settings(0.9, W + 1)
    assert not bool(sampling._masked_rows(
        jnp.asarray(x), jnp.ones((ROWS,)), jnp.asarray(tp), jnp.asarray(tk),
        32)[4])


# -- the draws ------------------------------------------------------------

MIXED = {                 # a row each: temperature, top_p, top_k
    "head": ([1.0, 0.7, 1.0, 0.9, 0.0, 1.0], [1.0, 0.9, 0.5, 0.95, 0.3, 1.0],
             [8, 0, 4, W, 0, 8]),
    "full_sort": ([1.0, 0.7, 1.0, 0.9, 0.0, 1.0],
                  [1.0, 0.9, 0.5, 0.95, 0.3, 0.9], [W + 1, 0, 4, 200, 0, 0]),
}


@pytest.mark.parametrize("branch", list(MIXED))
@pytest.mark.parametrize("V", [259, 32768])
def test_sample_dynamic_rows_draws_the_parents_tokens(V, branch):
    """The masked logits are the parent's, so the same keys draw the same
    tokens — through the head alone, and on a call that sorts."""
    temperature, top_p, top_k = (np.asarray(v, np.float32) for v in MIXED[branch])
    top_k = top_k.astype(np.int32)
    x = seeded_logits(V, "distinct", 8, 0.9)
    keys = jax.random.split(jax.random.PRNGKey(61), ROWS)
    full = sampling._masked_rows(
        jnp.asarray(x), jnp.asarray(temperature), jnp.asarray(top_p),
        jnp.asarray(top_k), 0)[4]
    assert bool(full) is (branch == "full_sort")
    masked = reference_masked(x, temperature, top_p, top_k)
    want = np.where(
        temperature == 0.0, x.argmax(axis=-1),
        np.asarray(sampling._row_categorical(keys, jnp.asarray(masked))))
    for draw in range(4):
        got = sampling.sample_dynamic_rows(
            jnp.asarray(x), keys, jnp.asarray(temperature),
            jnp.asarray(top_p), jnp.asarray(top_k))
        np.testing.assert_array_equal(np.asarray(got), want)
        keys = jax.vmap(lambda k: jax.random.fold_in(k, 7))(keys)
        want = np.where(
            temperature == 0.0, x.argmax(axis=-1),
            np.asarray(sampling._row_categorical(keys, jnp.asarray(masked))))


@pytest.mark.parametrize("branch", list(MIXED))
def test_truncated_dist_agrees_with_the_plain_sampler(branch):
    """The speculative pair's distribution has the plain sampler's support
    and its renormalised probabilities, row by row, on either branch."""
    temperature, top_p, top_k = (np.asarray(v, np.float32) for v in MIXED[branch])
    top_k = top_k.astype(np.int32)
    sampled = temperature > 0                   # callers handle greedy rows
    x = seeded_logits(32768, "distinct", 8, 0.9)[sampled]
    temperature, top_p, top_k = temperature[sampled], top_p[sampled], top_k[sampled]
    dist = np.asarray(sampling.truncated_dist(
        jnp.asarray(x), jnp.asarray(temperature), jnp.asarray(top_p),
        jnp.asarray(top_k), 0))
    masked = np.asarray(sampling._masked_rows(
        jnp.asarray(x), jnp.asarray(temperature), jnp.asarray(top_p),
        jnp.asarray(top_k), 0)[1])
    np.testing.assert_array_equal(dist > 0, np.isfinite(masked))
    np.testing.assert_allclose(
        dist, np.asarray(jax.nn.softmax(jnp.asarray(masked), axis=-1)),
        rtol=1e-5, atol=1e-9)
    np.testing.assert_array_equal(
        np.isfinite(masked), np.isfinite(reference_masked(
            x, temperature, top_p, top_k)))


def test_static_top_p_filter_cuts_at_the_same_threshold():
    """`sample`'s static filter (models' generate) goes through the one
    helper: scalar p, leading dimensions of any rank."""
    x = seeded_logits(32768, "distinct", 8, 0.9).reshape(2, 3, 32768)
    got = np.asarray(sampling._apply_top_p(jnp.asarray(x), 0.9))
    want_p = reference_thresholds(
        jnp.asarray(x.reshape(ROWS, -1)), np.full((ROWS,), 0.9, np.float32),
        np.zeros((ROWS,), np.int32))[0]
    np.testing.assert_array_equal(
        np.isfinite(got).reshape(ROWS, -1),
        x.reshape(ROWS, -1) >= want_p[:, None])


# -- the programs ---------------------------------------------------------

STEPS = 4
SAMPLER_PRIMITIVES = ("top_k", "sort", "random_bits", "threefry2x32")


def primitives(jaxpr) -> collections.Counter:
    """How often each primitive appears, sub-programs included."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += primitives(inner)
    return found


def toy_programs(greedy: bool):
    """(decode, prefill) of tiny-llama as functions of abstract arguments
    alone, and those arguments."""
    cfg = get_config("tiny-llama")
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    paged, _ = jax.eval_shape(SlotBatch(cfg, None, None).fresh)
    ints = jnp.ones((SLOTS,), jnp.int32)
    floats = jnp.ones((SLOTS,), jnp.float32)
    tables = jnp.zeros((SLOTS, PAGES_PER_SEQ), jnp.int32)
    seeds = jnp.zeros((SLOTS, 2), jnp.uint32)

    def decode(params, paged):
        return engine_mod._decode_fn(
            params, cfg, paged, ints, ints, tables, ints > 0, ints + 8, seeds,
            floats, floats, ints * 8, SlotState(), greedy=greedy, steps=STEPS,
            eos_id=-1)

    def prefill(params, paged):
        return engine_mod._prefill_fn(
            params, cfg, paged, jnp.ones((SLOTS, 16), jnp.int32), ints * 0,
            ints * 3, tables, seeds, floats, floats, ints * 8, greedy=greedy)

    return decode, prefill, (params, paged)


def test_greedy_programs_hold_no_sampler_and_keep_packed(monkeypatch):
    """A greedy decode block and a greedy prefill are an argmax: no
    `top_k`, no `sort`, no random bits, and `packed` is [steps, B] — what
    they compiled to before the head existed. The sampled variants hold
    the head's `top_k`, the sort and ONE `cond` more than the greedy ones
    (the model's own stay), and the decode block one more row."""
    sampled_decode, sampled_prefill, args = toy_programs(greedy=False)
    sampled = [primitives(jax.make_jaxpr(program)(*args).jaxpr)
               for program in (sampled_decode, sampled_prefill)]
    packed = jax.eval_shape(sampled_decode, *args)[0]
    assert packed.shape == (STEPS + 1, SLOTS) and packed.dtype == jnp.int32

    def never(*args, **kwargs):
        raise AssertionError("a greedy program entered the sampler")

    for name in ("_trunc_thresholds", "_sorted_head", "_masked_rows",
                 "lane_keys"):
        monkeypatch.setattr(sampling, name, never)
    decode, prefill, args = toy_programs(greedy=True)
    for program, with_sampler in zip((decode, prefill), sampled):
        found = primitives(jax.make_jaxpr(program)(*args).jaxpr)
        assert not any(found[name] for name in SAMPLER_PRIMITIVES)
        assert all(with_sampler[name] for name in SAMPLER_PRIMITIVES[:3])
        assert with_sampler["cond"] == found["cond"] + 1
    packed = jax.eval_shape(decode, *args)[0]
    assert packed.shape == (STEPS, SLOTS) and packed.dtype == jnp.int32


def test_the_samplers_row_counts_the_sub_steps_that_sorted():
    """`_decode_fn` on real arrays: lane 0 (top_k past the head) stops at
    its cap after two sub-steps, lane 1 (top_k 8) runs all four, lanes 2
    and 3 are idle with a top_k past the head: two sub-steps sorted."""
    cfg = get_config("tiny-llama")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    paged, _ = SlotBatch(cfg, None, None).fresh()
    seq = np.asarray([3, 3, 1, 1], np.int32)
    tables = np.stack([SlotBatch.table(s) for s in range(SLOTS)])
    packed = np.asarray(engine_mod._decode_fn(
        params, cfg, paged, jnp.ones((SLOTS,), jnp.int32), jnp.asarray(seq),
        jnp.asarray(tables), jnp.asarray([True, True, False, False]),
        jnp.asarray(seq + np.asarray([2, 100, 0, 0], np.int32)),
        jnp.zeros((SLOTS, 2), jnp.uint32), jnp.ones((SLOTS,), jnp.float32),
        jnp.ones((SLOTS,), jnp.float32),
        jnp.asarray([W + 1, 8, W + 1, W + 1], jnp.int32), SlotState(),
        greedy=False, steps=STEPS, eos_id=-1)[0])
    assert packed.shape == (STEPS + 1, SLOTS)
    assert ((packed[:-1] >= 0) == [[True, True, False, False]] * 2
            + [[False, True, False, False]] * 2).all()
    assert (packed[-1] == 2).all()


# -- through the engine ------------------------------------------------------

TOY = EngineConfig(
    model="tiny-llama", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=64, max_seq_len=96,
    prefill_buckets=(16, 32), max_new_tokens_cap=32, decode_block_steps=4,
)
KEYS = ("sampler_steps_total", "sampler_full_sort_steps_total")


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(TOY, seed=61)
    yield eng
    eng.shutdown()


def stream(engine, **sampling_parameters):
    request = GenRequest(prompt="the sampled head", max_new_tokens=9, seed=61,
                         **sampling_parameters)
    engine.submit(request)
    ids = []
    while True:
        kind, value = request.out.get(timeout=120)
        if kind == "token":
            ids.append(value)
        elif kind == "done":
            return ids
        else:
            raise AssertionError(value)


def moved(engine, **sampling_parameters):
    before = engine.stats()
    ids = stream(engine, **sampling_parameters)
    after = engine.stats()
    return (len(ids),) + tuple(
        after.get(key, 0) - before.get(key, 0) for key in KEYS)


def test_engine_counts_sampled_steps_and_the_sorts_among_them(engine):
    """tiny-llama's 259 logits are nearly flat: a nucleus of 0.9 runs past
    64 of them, one of 0.01 is the first alone. The first token is the
    prefill's; each of the others is one live sub-step of a sampled
    block."""
    assert all(key not in engine.stats() for key in KEYS)
    tokens, steps, sorts = moved(engine)                    # greedy
    assert (steps, sorts) == (0, 0)
    assert all(key not in engine.stats() for key in KEYS)
    tokens, steps, sorts = moved(engine, temperature=1.0, top_k=8)
    assert steps == tokens - 1 > 0 and sorts == 0
    tokens, steps, sorts = moved(engine, temperature=1.0, top_p=0.01)
    assert steps == tokens - 1 and sorts == 0
    tokens, steps, sorts = moved(engine, temperature=1.0)   # untruncated
    assert steps == tokens - 1 and sorts == 0
    tokens, steps, sorts = moved(engine, temperature=1.0, top_k=W + 1)
    assert steps == sorts == tokens - 1
    tokens, steps, sorts = moved(engine, temperature=1.0, top_p=0.9)
    assert steps == sorts == tokens - 1


def test_engine_streams_do_not_depend_on_a_neighbour_that_sorts(engine):
    """A seeded top-k 8 stream alone, and beside a request whose top_k
    sends every shared sub-step to the sort."""
    alone = stream(engine, temperature=1.0, top_k=8)
    other = GenRequest(prompt="a wide neighbour", max_new_tokens=24,
                       temperature=1.0, top_k=W + 1, seed=3)
    engine.submit(other)
    beside = stream(engine, temperature=1.0, top_k=8)
    while other.out.get(timeout=120)[0] != "done":
        pass
    assert beside == alone


def test_engine_exports_both_counters(engine):
    stream(engine, temperature=1.0, top_k=W + 1)
    stats = engine.stats()
    body = "\n".join(engine_collector(engine)())
    for key in KEYS:
        assert f"# TYPE polykey_{key} counter" in body
        assert f"polykey_{key} {stats[key]}" in body


def test_the_hook_adds_to_both_counters_under_one_lock():
    metrics = EngineMetrics()
    assert all(key not in metrics.snapshot() for key in KEYS)
    metrics.on_sampler_steps(4, 0)
    metrics.on_sampler_steps(3, 2)
    snap = metrics.snapshot()
    assert tuple(snap[key] for key in KEYS) == (7, 2)
