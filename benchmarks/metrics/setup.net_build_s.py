"""Seconds inside the program's ``sn.solver.nets`` spans: the construction of
the train net and every test net in ``Solver.__init__`` (layer set-up in
``compiler/graph.py``); part of ``setup.solver_build_s``."""

from benchmarks.metrics._flight import metric


def read(summary, run):
    return metric(summary, "setup.net_build_s")
