"""HBM a device the TIMED step program needs while it runs, in GB:
arguments + outputs - aliased (donated arguments the outputs are written
over) + temporaries + code, of the executable that ran (the same span as
``model_step.args_hbm_gb``): ``benchmarks/scratch/aot_compile_decoder.py``'s
``peak_estimate_gb``, which PRs 47-51 quoted by hand from a second
compile for a described chip.  Under the chip's ``bytes_limit`` by
construction: a program over it does not load.  A program without the
stats (the parent of PR 52) gives nothing."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "model_step.program_hbm_gb")
