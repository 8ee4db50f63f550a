"""The gated attention layer's causal core (16 query heads over 2
key/value heads of 256; t + 1 keys a query, QK^T and PV over 256 each,
forward and backward: ``harness/linear_flops.py gated_core_row``) against
its roofline, over chip 0's device self time under ``A.core`` (this
configuration's one attention layer)."""

from benchmarks.metrics._decoder_scopes import part_roofline


def read(summary, run):
    return part_roofline(summary, run, "gated_core", "A.core")
