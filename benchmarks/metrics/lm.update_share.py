"""Share of chip 0's device self time under the program's ``S.update``
scope in the language-model cells: the AdamW update of 0.47 B parameters
(f32 parameter, two moments and the gradient each read, three written).
The same reader as ``solver.update_share``, whose entry lists the CNN
cells."""

from benchmarks.metrics._program_spans import scope_share


def read(summary, run):
    return scope_share(summary, "S.update")
