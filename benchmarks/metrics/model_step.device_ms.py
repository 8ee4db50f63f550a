"""Device busy time per training step (union of op intervals over the
traced window / steps in it), mean over the chips used."""

from benchmarks.metrics._common import device_step_s


def read(summary, run):
    s = device_step_s(summary, run)
    return None if s is None else 1e3 * s
