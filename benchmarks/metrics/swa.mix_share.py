"""Share of chip 0's device self time in the attention layers OUTSIDE
their ``A.core`` scope: the head-major projections in and out, the rotary
pass (``A.rope``: plain over a whole head, YaRN over half of one) and the
head-wise gate (``A.gate``), forward and backward."""

from benchmarks.metrics._window_scopes import mix_share


def read(summary, run):
    return mix_share(summary, run)
