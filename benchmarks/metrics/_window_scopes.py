"""What the window-and-full attention decoder cell's readers take from a
run's trace: chip 0's device self time under ``A.core``
(``ops/attention.py``) LAYER BY LAYER, which ``metrics/_hybrid_scopes.py``
already books (its ``scope_seconds`` and ``kind_roofline``; the two
rooflines call the latter themselves): the cores of the sliding layers
and of the full ones are different work (``harness/window_flops.py
core_row``: kinds ``window_core`` and ``full_core``) and are told apart
by their layer's ``L.<name>`` alone.  ``A.rope`` and ``A.gate`` lie in
their layer's time outside ``A.core`` and have no reader of their own.

Where there is no trace, or the program carries no such scope (the parent
of PR 50, another cell), the readers get nothing and return None.
``swa.held_pair_share`` takes the fence's counters from
``metrics/_decoder_scopes.py``.
"""

from __future__ import annotations

from benchmarks.metrics._common import first_chip, layer_s, self_total
from benchmarks.metrics._hybrid_scopes import scope_seconds

CORE_KINDS = ("window_core", "full_core")


def _layers(run) -> set[str]:
    """The attention layers: those with a core row of either kind."""
    return {r["name"].split(".")[0] for r in run.get("decoder_parts", ())
            if r["kind"] in CORE_KINDS}


def core_share(summary, run) -> float | None:
    """Both kinds' ``A.core`` time over the self total."""
    chip = first_chip(summary)
    s = scope_seconds(summary, "A.core", _layers(run))
    if chip is None or s is None or not self_total(chip):
        return None
    return 100.0 * s / self_total(chip)


def mix_share(summary, run) -> float | None:
    """The attention layers' self time OUTSIDE ``A.core`` (projections,
    ``A.rope``, ``A.gate``) over the self total."""
    chip = first_chip(summary)
    layers = _layers(run)
    s = scope_seconds(summary, "A.core", layers)
    if chip is None or s is None or not self_total(chip):
        return None
    whole = sum(layer_s(chip, name) for name in layers)
    return 100.0 * (whole - s) / self_total(chip)
