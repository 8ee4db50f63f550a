"""The sliding layers' causal cores against their roofline: over the
window layers and three passes, the least time the chip could take
(``harness/window_flops.py core_row``: max(ops / 197 T, bytes / 819 G)
with min(t + 1, 512) keys a query over 64 heads of 128, QK^T and PV, and
q, k, v read and o written once a pass: what the MASK asks, whatever
kernel computes it), over chip 0's device self time under ``A.core`` in
those layers.  The half-masked 512-wide blocks a ``LocalMask`` leaves the
kernels to visit (31 x 512 x 512 pairs a head for the mask's 4,063,488)
and the backward's second QK^T are time and not work."""

from benchmarks.metrics._hybrid_scopes import kind_roofline


def read(summary, run):
    return kind_roofline(summary, run, "window_core", "A.core")
