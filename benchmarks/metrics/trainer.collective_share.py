"""Share of chip 0's busy time spent in collective ops (the pmean of the
model after tau local steps).  The part with no compute running beside it
is in the trace summary as ``collective_exposed_s``."""

from benchmarks.metrics._common import first_chip


def read(summary, run):
    chip = first_chip(summary)
    if chip is None or not chip["busy_s"] or run.get("chips", 1) < 2:
        return None
    return 100.0 * chip["collective_s"] / chip["busy_s"]
