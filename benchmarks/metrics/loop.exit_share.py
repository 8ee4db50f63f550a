"""Share of chip 0's device self time in the exit path behind the looped
region: the head and the exit gate over every pass's state and the
exit-weighted loss (``L.lm_head``, ``L.exit_gate``, ``L.loss``), forward
and backward, over the self total."""

from benchmarks.metrics._loop_scopes import exit_share


def read(summary, run):
    return exit_share(summary)
