"""The mean exit step, mean over tokens of sum_t t p_t (1-based), from the
program's own counter (the exit-weighted loss keeps it of the last step
before a fence and ``Solver.step`` puts it on its ``sn.step.fence`` span
as ``exit_mean_step``): the mean over the fences of the traced window.
Between 1 and ``ut_steps``; 2.5 is a gate that cannot tell 4 steps apart."""

from benchmarks.metrics._loop_scopes import mean_exit_step


def read(summary, run):
    return mean_exit_step(summary)
