"""Share of chip 0's device self time under ``A.core`` in the attention
layers of both kinds (the sliding layers' windowed cores and the full
layers' cores), forward and backward."""

from benchmarks.metrics._window_scopes import core_share


def read(summary, run):
    return core_share(summary, run)
