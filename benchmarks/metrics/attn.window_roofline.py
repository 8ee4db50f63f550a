"""The windowed differential-attention core against its roofline: both
softmax maps of every head pair over the band, min(t + 1, 512) keys a
query, QK^T over 64 and PV over 128, forward and backward
(``harness/hybrid_flops.py window_core_row``: max(ops / 197 T, bytes /
819 G) over three passes), over chip 0's device self time under
``A.core`` in the window layers.  The backward's recomputed QK^T is time
and not work."""

from benchmarks.metrics._hybrid_scopes import kind_roofline


def read(summary, run):
    return kind_roofline(summary, run, "window_core", "A.core")
