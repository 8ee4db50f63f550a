"""The attention core's share of its roofline: the least time the chip
could take for QK^T, softmax and AV, forward and backward, the causal
half only (``harness/lm_flops.py``: ``attn<i>.core``), over chip 0's
device self time under the program's ``A.core`` scope."""

from benchmarks.metrics._lm_scopes import part_roofline


def read(summary, run):
    return part_roofline(summary, run, "attn_core", "A.core")
