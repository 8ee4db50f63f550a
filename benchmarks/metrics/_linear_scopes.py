"""What the linear-attention decoder cell's readers take from a run's
trace, beyond ``harness.trace.summarize``'s per-layer sums: chip 0's device
self time under the program's ``D.delta`` scope (``ops/linear_attention.py``:
the gates, the normalisation of q and k and the chunked delta rule,
forward and backward), LAYER BY LAYER, each nested in its layer's
``L.<name>`` scope.

``metrics/_hybrid_scopes.py``'s reduction with this configuration's
scope: read once per process from the newest ``*.xplane.pb`` under the
benchmark's trace directory.  Where there is no trace, or the program
carries no such scope (the parent of PR 47, another cell), the readers
get nothing and return None.  A summary may carry the reduction itself
(``linear_scopes``: tests).  The cell's other readers take the expert
layers' and the attention core's scopes and the fence's counters from
``metrics/_decoder_scopes.py``.
"""

from __future__ import annotations

import re

from benchmarks.harness import flops, trace
from benchmarks.metrics._common import first_chip, layer_s, self_total
from benchmarks.metrics._program_spans import newest_xplane

SCOPE = "D.delta"
_MISSING = object()
_cached = _MISSING


def reduce(tr: dict) -> dict:
    """``tr``: ``trace.load_xplane``'s neutral form.  -> {"layer_s":
    {layer: seconds of chip 0 self time under ``D.delta`` inside the
    window}}."""
    out: dict[str, float] = {}
    chips = tr["chips"]
    if chips:
        rows = chips[min(chips, key=int)]
        for ns, _, scope in trace.self_times(trace.clip(rows, tr["window"])):
            layer = trace.layer_of(scope)
            if layer is not None and re.search(
                    rf"\b{re.escape(SCOPE)}\b", scope):
                out[layer] = out.get(layer, 0.0) + ns / 1e9
    return {"layer_s": out}


def linear_scopes(summary) -> dict | None:
    global _cached
    if not summary:
        return None
    if "linear_scopes" in summary:
        return summary["linear_scopes"]
    if _cached is _MISSING:
        path = newest_xplane()
        _cached = reduce(trace.load_xplane(path)) if path else None
    return _cached


def _cores(run) -> list[dict]:
    return [r for r in run.get("decoder_parts", ())
            if r["kind"] == "delta_core"]


def core_seconds(summary, layers=None) -> float | None:
    """Chip 0 self seconds under ``D.delta`` in the given layers (all when
    None); None where the trace has no op there."""
    ls = linear_scopes(summary)
    if not ls:
        return None
    s = sum(v for k, v in ls["layer_s"].items()
            if layers is None or k in layers)
    return s or None


def core_share(summary) -> float | None:
    chip, s = first_chip(summary), core_seconds(summary)
    if chip is None or s is None or not self_total(chip):
        return None
    return 100.0 * s / self_total(chip)


def mix_share(summary, run) -> float | None:
    """The DeltaNet layers' self time outside ``D.delta`` over the self
    total."""
    chip = first_chip(summary)
    layers = {r["name"].split(".")[0] for r in _cores(run)}
    s = core_seconds(summary, layers)
    if chip is None or s is None or not self_total(chip):
        return None
    whole = sum(layer_s(chip, name) for name in layers)
    return 100.0 * (whole - s) / self_total(chip)


def core_roofline(summary, run) -> float | None:
    """Over the ``delta_core`` parts (``harness/linear_flops.py``): the
    least time the chip could take, max(ops / peak, bytes / peak) over
    three passes, times the steps traced, over the self time under
    ``D.delta`` in those parts' layers."""
    rows = _cores(run)
    s = core_seconds(summary, {r["name"].split(".")[0] for r in rows})
    if s is None or "peaks" not in run or not run.get("steps_traced"):
        return None
    p = run["peaks"]
    floor = sum(flops.layer_floor_s(r, p["bf16_flops"],
                                    p["hbm_bytes_per_s"])[0] for r in rows)
    return 100.0 * floor * run["steps_traced"] / s if floor else None
