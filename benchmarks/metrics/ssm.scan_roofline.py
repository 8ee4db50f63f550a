"""The selective scan against its roofline: over the scan layers and three
passes, the least time the chip could take (``harness/hybrid_flops.py
scan_row``: max(ops / 197 T, bytes / 819 G) with c, Δ, B and C read and y
written once a pass and the state never through HBM), over chip 0's
device self time under the program's ``R.scan`` scope (discretisation,
recurrence, read-out and skip, forward and backward).  The backward's
second walk over a chunk's states is time and not work."""

from benchmarks.metrics._hybrid_scopes import kind_roofline


def read(summary, run):
    return kind_roofline(summary, run, "scan", "R.scan")
