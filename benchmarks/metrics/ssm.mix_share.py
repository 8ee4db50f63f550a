"""Share of chip 0's device self time in the Mamba layers OUTSIDE their
``R.scan`` scope: the four projections, the depthwise convolution, the
SiLUs and the gate, forward and backward."""

from benchmarks.metrics._hybrid_scopes import mix_share


def read(summary, run):
    return mix_share(summary, run)
