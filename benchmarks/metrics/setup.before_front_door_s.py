"""Seconds from the process's creation (``/proc/self/stat``) to the start of
the program's ``sn.main`` span (``cli.main``'s entry): the interpreter, the
imports of ``sparknet_tpu`` and jax, the chip's attach, and in the
benchmark ``run.py``'s own loading and its data set.  What a job pays
before the front door sees it."""

from benchmarks.metrics._flight import metric


def read(summary, run):
    return metric(summary, "setup.before_front_door_s")
