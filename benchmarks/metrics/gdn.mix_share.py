"""Share of chip 0's device self time in the DeltaNet layers OUTSIDE their
``D.delta`` scope: the two projections in, the depthwise convolution, the
SiLUs, the gated RMSNorm and the projection out, forward and backward."""

from benchmarks.metrics._linear_scopes import mix_share


def read(summary, run):
    return mix_share(summary, run)
