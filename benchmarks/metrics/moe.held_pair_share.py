"""Share of the (token, slot) pairs that landed on experts this chip
holds, over all the pairs its routers chose, from the program's own
counters on its ``sn.step.fence`` spans (``moe_pairs_held`` over
``moe_pairs`` x ``moe_layers``): the mean over the fences of the traced
window (a fence carries its LAST step's counters, so this is two steps of
six layers).  8 of 256 experts held read 3.125 % under a level router.
Near initialisation one sequence's tokens look alike to a router and a
layer's pairs fall on about 8 of its outputs, others for another
sequence, so a single step reads 0 to 30 %; and through a window the
share drifts up (1-5 % at the first fence, 8-15 % at the 21st: only the
held experts answer on this chip, so only their router columns are
taught; PERF.md section 5).  The held experts' grouped matmuls do that
share of the routed work, in those lumps."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(
        summary, "moe_pairs_held",
        lambda s: 100.0 * int(s["moe_pairs_held"])
        / (int(s["moe_pairs"]) * int(s["moe_layers"])))
