"""Share of chip 0's device self time under the four expert layers'
``M.route``, ``M.dispatch`` and ``M.combine`` scopes where each holds 16
of its router's 256 outputs: the sigmoid over 256 scores, its top-8 and
their renormalisation, the auxiliary loss, the sort of ALL 65,536 (token,
slot) pairs of an 8,192-token sequence, and the movers' loops over the
live tiles of the held pairs, forward and backward.  The held experts'
grouped matmuls (``M.experts``) and the shared expert (``M.shared``) are
not in it.  ``moe.route_share``'s and ``moe.wide_route_share``'s reader;
their entries list the cells they were written for."""

from benchmarks.metrics._decoder_scopes import share_of_busy


def read(summary, run):
    return share_of_busy(summary, "M.route", "M.dispatch", "M.combine")
