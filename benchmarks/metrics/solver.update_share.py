"""Share of chip 0's device self time under the program's ``S.update``
scope (the optimizer update inside the jitted train step).  Part of
``solver.unscoped_share``, which still counts it."""

from benchmarks.metrics._program_spans import scope_share


def read(summary, run):
    return scope_share(summary, "S.update")
