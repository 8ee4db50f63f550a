"""Seconds inside the program's ``sn.setup.net`` (prototxt to net and solver
messages), ``sn.solver.build`` (``Solver.__init__``: the train and test
``Network``s, init, slots) and ``sn.trainer.build``
(``ParallelTrainer.__init__``: mesh, replication, placement) spans that
began before the process's last backend compile ended: set-up, not the
windows.  Every solver and trainer the process built by then: in
``alexnet-tau10-x4`` the job's two trainers and the round check's own.
Unfenced: what init leaves running on the device is booked to whatever
blocks next."""

from benchmarks.metrics._flight import metric


def read(summary, run):
    return metric(summary, "setup.solver_build_s")
