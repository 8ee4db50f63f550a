"""Share of the share-holding MoE layers' steps that ran at their capacity
(``ops/moe.py``: the held pairs' rows only, 6,144 of a layer's 32,768 pairs at
8 of 256 experts) and not over all T·k sorted rows, from the program's own
counters on its ``sn.step.fence`` spans (``moe_compact_layers`` over
``moe_layers``, PR 35): the mean over the fences of the traced window (a
fence carries its LAST step's counters, so this is two steps of five
layers).  The host counts with the predicate the device branched on
(``takes_compact``), asked of the same ``load`` the fence already reads.
100 means every layer-step dispatched at the capacity; a layer whose
router sends more than 6,144 of a sequence's 32,768 pairs to the held
experts falls back to the exact path over all rows and is not counted.
A program without the counter (the parent of PR 35) gives nothing."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(
        summary, "moe_compact_layers",
        lambda s: 100.0 * int(s["moe_compact_layers"])
        / int(s["moe_layers"]))
