"""What `perfbench/costs/<file>.py` `moe_held_experts` counts (ISSUE 58): the
bytes of the held experts some lane hit, and the operations of the CHOSEN
(row, held expert) pairs — what the sorted kernel computes — never the
masked form's every-row-by-every-held-expert, which nothing computes since
PR 56 and which read a call at a 10 % hit share as 198 % of its roofline."""

import pytest

import perfbench_paths  # noqa: F401  (puts perfbench/ on sys.path)

import kernel_costs
import peaks
from test_perfbench_capture import spec_of

PEAKS = peaks.row("TPU v5 lite")
ROWS = 64
# configuration -> (top-k, router width, experts held, the bytes of one call
# at 64 rows with every held expert counted, as they stood before this PR:
# ledger, PR 50, and PR 55's costs file).
CELLS = {
    "nemotron-3-super-ep4": (22, 512, 128, 1409712128),
    "lfm2-24b-a2b-pp4": (4, 64, 64, 1208762368),
    "qwen3-next-80b-a3b-ep4": (10, 512, 128, 806125568),
    "openpangu-ultra-moe-ep32": (8, 256, 8, 757925888),
}


@pytest.mark.parametrize("share", [0.10, 0.266, 0.50, 1.0])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_call_is_bound_by_its_bytes_at_every_hit_share(name, share):
    spec = spec_of(name)
    costs = kernel_costs.for_spec(spec)
    top_k, width, held, whole = CELLS[name]
    cost = costs.moe_held_experts(spec, ROWS, hit_share=share)
    assert costs.chosen_pairs(spec, ROWS) == ROWS * top_k * held / width
    # The pairs' products: 2 flop a multiply-add, over the expert's matrices.
    per_pair = 2 * costs.held_experts_params(spec) / held
    assert cost["flops"] == pytest.approx(
        costs.chosen_pairs(spec, ROWS) * per_pair)
    least, bound = kernel_costs.roofline_seconds(cost, PEAKS)
    assert bound == "memory"
    assert least == cost["bytes"] / PEAKS["hbm_bytes_per_s"]
    # The bytes scale in the experts' matrices alone, and at 1.0 are to the
    # byte what they were.
    full = costs.moe_held_experts(spec, ROWS)["bytes"]
    assert full == whole
    matrices = costs.held_experts_params(spec) * 2
    assert cost["bytes"] == pytest.approx(full - (1 - share) * matrices)


def test_the_recorded_low_seed_call_reads_under_its_roofline():
    """PR 56's traced nemotron seed 5600000101: hit share 10.45 %, 0.2312 ms
    a call (PERF.md §7). By the masked form's operations the least time was
    90.2 GFLOP / 197 TFLOP/s = 0.458 ms: 198 %."""
    spec = spec_of("nemotron-3-super-ep4")
    costs = kernel_costs.for_spec(spec)
    cost = costs.moe_held_experts(spec, ROWS, hit_share=0.1045)
    least, bound = kernel_costs.roofline_seconds(cost, PEAKS)
    assert bound == "memory"
    assert 100.0 * least / 0.2312e-3 == pytest.approx(78.0, abs=0.5)
    masked = {**cost, "flops": ROWS * 128 * 4 * spec["moe_latent_size"]
              * spec["moe_intermediate_size"]}
    assert 100.0 * kernel_costs.roofline_seconds(masked, PEAKS)[0] \
        / 0.2312e-3 == pytest.approx(198.0, abs=1.0)
