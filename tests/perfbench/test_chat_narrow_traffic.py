"""`perfbench/traffic/chat-narrow.json` states its table the way its
siblings do: evenly spaced quantiles dealt to the clients, built with
`quantile_table`'s own functions. (`quantile_table.tables()` does not list
it: that file is the accepted benchmark's, and the PR that brought this
traffic could not edit it — so
`test_perfbench_yardstick.py::test_traffic_tables_are_the_stated_quantiles
[chat-narrow]` fails with a KeyError until a `benchmark` PR adds the line;
this file holds the table to the same functions meanwhile.)"""

import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)

import traffic
from quantile_table import deal, lognormal_quantiles, scattered

MIX = traffic.load("chat-narrow")
CLOSED = traffic.load("chat-closed")


def stated_rows() -> list:
    prompts = scattered(lognormal_quantiles(32, 200, 0.5, 32, 480), 48)
    outs = deal(lognormal_quantiles(48, 256, 0.32, 128, 512, 8), 8)
    return [[[prompts[6 * i + k], o] for k, o in enumerate(mine)]
            for i, mine in enumerate(outs)]


def test_rows_are_the_stated_quantiles():
    assert MIX["rows_by_client"] == stated_rows()


def test_shape_is_chat_closed_at_half_its_clients():
    assert MIX["clients"] == 8 == CLOSED["clients"] // 2
    for key in ("loop", "think_ms", "shared_prefix_tokens",
                "first_request_phasing", "tiny", "columns"):
        assert MIX[key] == CLOSED[key], key
    assert (MIX["loop"], MIX["think_ms"], MIX["shared_prefix_tokens"]) == (
        "closed", [0, 0], 0)
    assert all(len(rows) == 6 for rows in MIX["rows_by_client"])


def test_lengths_lie_inside_what_the_cell_states():
    rows = [row for mine in MIX["rows_by_client"] for row in mine]
    prompts, outs = zip(*rows)
    assert (min(prompts), max(prompts)) == (68, 480)
    assert (min(outs), max(outs)) == (128, 512)
    assert all(o % 8 == 0 for o in outs)
    # A request's pages (prompt + output, allocated at admission) fit the
    # configuration's max_seq_len of 1,024.
    assert max(p + o for p, o in rows) <= 992


def test_clients_carry_about_the_same_work():
    sums = [sum(p + o for p, o in mine) for mine in MIX["rows_by_client"]]
    assert max(sums) <= 1.2 * min(sums)


@pytest.mark.parametrize("seed", [1, 4000000007])
def test_eight_lanes_of_it_fit_the_configurations_pool(seed):
    """Pages are allocated at admission for prompt + output: whatever row
    each of the eight clients is on, their requests' pages stay under the
    configuration's pool on average, and one client's largest always fits
    beside the seven others' means."""
    import json
    import os

    from perfbench_paths import BENCH

    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        engine = json.load(f)["engine"]
    plan = traffic.Plan(MIX, seed)
    page = engine["page_size"]
    pages = [[-(-(p + o) // page) for p, o in mine] for mine in plan.rows]
    means = [sum(mine) / len(mine) for mine in pages]
    usable = engine["num_pages"] - 1
    assert sum(means) < 0.8 * usable
    for i, mine in enumerate(pages):
        assert max(mine) + sum(means) - means[i] < usable
