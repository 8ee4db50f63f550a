"""How near the timed step is to not fitting, in % of the chip's
``bytes_limit``: 100 x (``device.live_hbm_gb``'s largest live reading +
the timed program's ``hbm_temps_bytes``) / ``hbm_limit_bytes``: what stays
on the chip between steps plus what a step needs on top while it runs.
The donated state is in the live reading and aliased into the outputs, so
it is counted once.  Over 100 a counter is wrong (the step ran): the
reader then gives nothing and says why on stderr.  A program without the
stats (the parent of PR 52) gives nothing."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "device.hbm_fill")
