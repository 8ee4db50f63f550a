"""Share of the (token, slot) pairs that landed on experts this chip
holds, over all the pairs its routers chose, from the program's own
counters on its ``sn.step.fence`` spans (``moe_pairs_held`` over
``moe_pairs`` x ``moe_layers``): the mean over the fences of the traced
window (a fence carries its LAST step's counters).  16 of 256 experts
held read 6.25 % under a level router; the held experts' grouped matmuls
do that share of the routed work, in the lumps one sequence's sigmoid
router gives."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(
        summary, "moe_pairs_held",
        lambda s: 100.0 * int(s["moe_pairs_held"])
        / (int(s["moe_pairs"]) * int(s["moe_layers"])))
