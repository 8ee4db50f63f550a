"""Share of chip 0's device self time under the MoE layer's ``M.route``,
``M.dispatch`` and ``M.combine`` scopes: the router, the sort of the
(token, slot) pairs, the gathers to and from expert-major order and the
weighted sum, forward and backward; everything of the layer that is not
its grouped matmuls."""

from benchmarks.metrics._lm_scopes import share_of_busy


def read(summary, run):
    return share_of_busy(summary, "M.route", "M.dispatch", "M.combine")
