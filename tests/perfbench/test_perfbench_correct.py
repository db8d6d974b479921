"""The decision of `correct` (ISSUE 27), without a server: engine failures
are judged at the window's close, and what `requests_failed` gains after
it is held to the streams the harness itself cancelled."""

import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)

import run

REFERENCE_OK = {"ok": True, "tokens": 32, "exact": 32, "max_margin": 0.0}


def record(error=None, asked=8, streamed=None, client=0, index=0) -> dict:
    """A request as perfbench/loadgen.py records it; by default one that
    ended by itself with every token asked."""
    streamed = asked if streamed is None else streamed
    ended = error is None
    return {
        "client": client, "index": index, "prompt_tokens": 25, "asked": asked,
        "send": 1.0, "times": [1.5] * (streamed > 0), "counts": [streamed],
        "final": 2.0 if ended else None, "status": 200 if ended else None,
        "error": error,
        "usage": {"prompt_tokens": 25, "completion_tokens": streamed,
                  "ttft_ms": 5.0} if ended else None,
    }


def cut(n: int) -> list:
    """n streams the harness cancelled at the window's end."""
    return [record(error="cancelled", streamed=3, client=i) for i in range(n)]


def stats(failed: int, restarts: int = 0) -> dict:
    return {"requests_failed": failed, "engine_restarts": restarts}


# id: (failed at ready / close / end, restarts, records, reference,
#      correct, what `why` must name)
CASES = {
    "clean run, nothing cancelled":
        ((0, 0, 0), 0, [record()], REFERENCE_OK, True, None),
    "the run that refused PR 25: 0 -> 16 with 16 cancelled":
        ((0, 0, 16), 0, [record()] + cut(16), REFERENCE_OK, True, None),
    "fewer counted than cancelled (13 of 16)":
        ((0, 0, 13), 0, cut(16), REFERENCE_OK, True, None),
    "the engine has not processed the block yet (0 of 16)":
        ((0, 0, 0), 0, cut(16), REFERENCE_OK, True, None),
    "failures before the run do not count":
        ((5, 5, 8), 0, cut(3), REFERENCE_OK, True, None),
    "one failure inside the window":
        ((0, 1, 1), 0, [record()], REFERENCE_OK, False, "failed_in_window 1"),
    "a failure inside the window is not excused by cancellations":
        ((0, 1, 17), 0, cut(16), REFERENCE_OK, False, "failed_in_window 1"),
    "more late failures than cancellations (17 against 16)":
        ((0, 0, 17), 0, cut(16), REFERENCE_OK, False,
         "failed_after_close 17 (limit 16)"),
    "a late failure with nothing cancelled":
        ((0, 0, 1), 0, [record()], REFERENCE_OK, False,
         "failed_after_close 1 (limit 0)"),
    "a counter that went backwards":
        ((0, 4, 0), 0, cut(4), REFERENCE_OK, False, "failed_after_close -4"),
    "an engine restart":
        ((0, 0, 0), 1, [record()], REFERENCE_OK, False, "engine_restarts 1"),
    "no verdict of the reference":
        ((0, 0, 0), 0, [record()], None, False, "no verdict"),
    "the reference disagrees":
        ((0, 0, 0), 0, [record()], {"ok": False}, False, "disagrees"),
    "a request with a real error":
        ((0, 0, 0), 0, [record(), record(error="UNAVAILABLE: gone")],
         REFERENCE_OK, False, "request_faults 1"),
    "a request that streamed fewer tokens than asked":
        ((0, 0, 0), 0, [record(streamed=5)], REFERENCE_OK, False,
         "request_faults 1"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decision_of_correct(case):
    failed, restarts, records, reference, correct, names = CASES[case]
    ready, close, end = (stats(n) for n in failed)
    end["engine_restarts"] = restarts
    verdict = run.decide_correct(ready, close, end, records, reference)
    assert verdict["correct"] is correct
    assert bool(verdict["why"]) is not correct
    if names is not None:
        assert any(names in reason for reason in verdict["why"]), verdict["why"]
    assert verdict["attempted"] == len(records)
    value, limit = verdict["checks"]["failed_after_close"]
    assert value == failed[2] - failed[1]
    assert limit == sum(r["error"] == "cancelled" for r in records)


def test_a_request_never_sent_is_neither_attempted_nor_a_cancellation():
    unsent = record(error="cancelled")
    unsent["send"] = None
    verdict = run.decide_correct(stats(0), stats(0), stats(1),
                                 [record(), unsent], REFERENCE_OK)
    assert verdict["attempted"] == 1
    assert verdict["checks"]["failed_after_close"] == (1, 0)
    assert verdict["correct"] is False
