"""HBM a device the TIMED step program's temporaries take, in GB:
what the forward keeps for the backward, the gradients and every scratch
array, as XLA's ``memory_analysis()`` gives them for the executable that
ran (``hbm_temps_bytes`` of the same span as ``model_step.args_hbm_gb``).
Recomputation, a kernel that keeps a chunk in VMEM or a partial array
that leaves the program move this one.  A program without the stat (the
parent of PR 52) gives nothing."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "model_step.temps_hbm_gb")
