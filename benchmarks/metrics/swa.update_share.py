"""Share of chip 0's device self time under the program's ``S.update``
scope in the window-and-full attention decoder cell: the AdamW update of
0.49 B parameters (f32 parameter, two moments and the gradient each read,
three written: 28 B a parameter, 16.8 ms at the chip's bandwidth).  The
same reader as ``solver.update_share`` and ``lm.update_share``, whose
entries list the cells they were written for."""

from benchmarks.metrics._program_spans import scope_share


def read(summary, run):
    return scope_share(summary, "S.update")
