"""Share of device self time outside every ``L.<layer>`` scope: the
optimizer update, the device augment, loss glue, collectives.  Chip 0."""

from benchmarks.metrics._common import first_chip, self_total


def read(summary, run):
    chip = first_chip(summary)
    if chip is None or not self_total(chip):
        return None
    return 100.0 * sum(chip["unscoped_s"].values()) / self_total(chip)
