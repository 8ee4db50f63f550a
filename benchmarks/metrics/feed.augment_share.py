"""Share of chip 0's device self time under the program's ``S.augment``
scope (``DeviceAugment.__call__`` where a jitted caller carries it: the
trainer's ``aug4``/``aug5``; the solo job's eager ``device_fn`` carries
no scope, so the reader finds nothing there).  Part of
``solver.unscoped_share``, which still counts it."""

from benchmarks.metrics._program_spans import scope_share


def read(summary, run):
    return scope_share(summary, "S.augment")
