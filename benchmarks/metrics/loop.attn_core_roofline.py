"""The looped blocks' causal attention cores (one a block and pass) against
their roofline: t + 1 keys a query, QK^T and PV over 128, forward and
backward, every pass counted (``harness/looped_flops.py core_row``), over
chip 0's device self time under ``A.core`` in those layers."""

from benchmarks.metrics._hybrid_scopes import kind_roofline


def read(summary, run):
    return kind_roofline(summary, run, "loop_core", "A.core")
