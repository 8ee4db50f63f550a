"""Share of chip 0's device self time under the expert layers'
``M.route``, ``M.dispatch`` and ``M.combine`` scopes where the router is
512 wide and takes 10 a token: the softmax over 512 outputs and its
top-10, the auxiliary loss, the sort of ALL 40,960 (token, slot) pairs a
sequence, the gathers to and from expert-major order and the weighted
sum, forward and backward.  The held experts' grouped matmuls
(``M.experts``) and the shared expert (``M.shared``) are not in it."""

from benchmarks.metrics._decoder_scopes import share_of_busy


def read(summary, run):
    return share_of_busy(summary, "M.route", "M.dispatch", "M.combine")
