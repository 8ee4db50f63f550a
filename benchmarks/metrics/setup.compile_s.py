"""Seconds jax spent tracing, lowering and compiling or loading executables
inside the program's spans during set-up: the ``compile_s`` stat
(``obs/sentinel.py``: the union of jax's trace, lowering and
backend-compile events on the span's thread) summed over the spans that
began before the process's last backend compile ended and that no other
compile-carrying span of their thread contains (``sn.solver.build``,
``sn.trainer.build``, ``sn.feed.open``, the first ``sn.step`` /
``sn.round`` of every solver and trainer, a feed thread's
``sn.feed.augment``).  What the benchmark's reference check compiles
outside every ``sn.*`` span is left out; where the check drives the
program's own spans it is in: in ``alexnet-tau10-x4`` its round check's
trainer and one round (the table's first ``sn.round``; PERF.md section 6
gives that share)."""

from benchmarks.metrics._flight import metric


def read(summary, run):
    return metric(summary, "setup.compile_s")
