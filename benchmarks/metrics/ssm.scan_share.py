"""Share of chip 0's device self time under the program's ``R.scan``
scope: the selective scans of every Mamba layer, forward and backward."""

from benchmarks.metrics._hybrid_scopes import scan_share


def read(summary, run):
    return scan_share(summary)
