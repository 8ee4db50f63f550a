"""The full layers' causal cores against their roofline: over the full
layers and three passes, the least time the chip could take
(``harness/window_flops.py core_row``: t + 1 keys a query over 48 heads
of 128, QK^T and PV; q, k, v read and o written once a pass), over chip
0's device self time under ``A.core`` in those layers."""

from benchmarks.metrics._hybrid_scopes import kind_roofline


def read(summary, run):
    return kind_roofline(summary, run, "full_core", "A.core")
