"""Longest over median wall of the program's fences (``sn.step.fence`` solo,
``sn.round.fence`` in the trainer) on the main thread between the
process's last compile and the traced window, the first of them left out
(first touch): 1.0 in a clean run, about 4 with one stalled chunk.  The
table on stderr names that fence's ``it``, the main thread's wait in it
for a feed that was not ready, and what the feed threads were inside."""

from benchmarks.metrics._flight import metric


def read(summary, run):
    return metric(summary, "step.fence_max_over_median")
