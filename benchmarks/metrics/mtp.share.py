"""Share of chip 0's device self time under the multi-token-prediction
module's layers (every ``L.mtp_*`` scope: its two norms, the projection
W_eh, its whole block, its norm, head and cross-entropy), forward and
backward.  From ``harness.trace.summarize``'s per-layer sums."""

from benchmarks.metrics._common import first_chip, self_total


def read(summary, run):
    chip = first_chip(summary)
    if chip is None or not self_total(chip):
        return None
    s = sum(v for part in ("layer_fwd_s", "layer_bwd_s")
            for k, v in chip[part].items() if k.startswith("mtp_"))
    return 100.0 * s / self_total(chip) if s else None
