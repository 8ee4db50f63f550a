"""Share of chip 0's device self time under the expert layers'
``M.route``, ``M.dispatch`` and ``M.combine`` scopes where the layer holds
a share of its experts: the 256-wide sigmoid router and its biased top-8,
the sort of ALL the (token, slot) pairs, the gathers to and from
expert-major order and the weighted sum, forward and backward.  The held
experts' grouped matmuls (``M.experts``) and the shared expert
(``M.shared``) are not in it."""

from benchmarks.metrics._decoder_scopes import share_of_busy


def read(summary, run):
    return share_of_busy(summary, "M.route", "M.dispatch", "M.combine")
