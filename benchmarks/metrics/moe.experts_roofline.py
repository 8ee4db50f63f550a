"""The grouped expert matmuls' share of their roofline: the least time the
chip could take for the three grouped matmuls (gate, up, down), forward
and both backward passes (``harness/lm_flops.py``: ``moe<i>.experts``),
over chip 0's device self time under the program's ``M.experts`` scope."""

from benchmarks.metrics._lm_scopes import part_roofline


def read(summary, run):
    return part_roofline(summary, run, "grouped", "M.experts")
