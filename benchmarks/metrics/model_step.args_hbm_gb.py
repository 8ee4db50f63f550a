"""HBM a device the TIMED step program's arguments take, in GB: the
state (parameters, the optimizer's slots, what the layers keep), the
batch and the key, as XLA's ``memory_analysis()`` gives them for the
executable that ran (``hbm_args_bytes`` on the ``sn.step`` / ``sn.round``
span inside which the program was compiled, PR 52; the newest such span
with the most ``hbm_devices``: ``metrics/_step_account.py``).  The
donated part is written over by the outputs, so it is held once.  A
program without the stat (the parent of PR 52) gives nothing."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "model_step.args_hbm_gb")
