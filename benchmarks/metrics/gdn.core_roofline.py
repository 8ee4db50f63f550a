"""The gated delta rule against its roofline: over the DeltaNet layers and
three passes, the least time the chip could take (``harness/linear_flops.py
delta_core_row``: max(ops / 197 T, bytes / 819 G) with 7 operations a
state element a token and q, k, v, g, beta read and o written once a pass,
the state never through HBM: the RECURRENCE's work, whatever algorithm
computes it), over chip 0's device self time under the program's
``D.delta`` scope.  The chunked form's own arithmetic (its inverse, W,
U_0) and the backward's second forming of them are time and not work."""

from benchmarks.metrics._linear_scopes import core_roofline


def read(summary, run):
    return core_roofline(summary, run)
