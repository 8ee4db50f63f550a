"""Share of chip 0's device self time under the program's ``D.delta``
scope: the gated delta rule of every DeltaNet layer (gates, q/k
normalisation, the chunked rule), forward and backward."""

from benchmarks.metrics._linear_scopes import core_share


def read(summary, run):
    return core_share(summary)
