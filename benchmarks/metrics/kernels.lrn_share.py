"""Share of device self time under the LRN layers' scopes (AlexNet:
``L.norm1`` + ``L.norm2``).  Absent where the net has no LRN layer."""

from benchmarks.metrics._common import first_chip, layer_s, self_total


def read(summary, run):
    chip = first_chip(summary)
    if chip is None or not run.get("lrn_layers") or not self_total(chip):
        return None
    return 100.0 * sum(layer_s(chip, n) for n in run["lrn_layers"]) / self_total(chip)
