"""The full-causal differential-attention cores (the full layer's and
every cross-attention's, which reads the full layer's keys and values)
against their roofline: t + 1 keys a query, QK^T over 64 and PV over 128,
forward and backward (``harness/hybrid_flops.py full_core_row``), over
chip 0's device self time under ``A.core`` in those layers."""

from benchmarks.metrics._hybrid_scopes import kind_roofline


def read(summary, run):
    return kind_roofline(summary, run, "full_core", "A.core")
