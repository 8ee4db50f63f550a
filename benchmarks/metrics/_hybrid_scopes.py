"""What the hybrid decoder cells' readers take from a run's trace, beyond
``harness.trace.summarize``'s per-layer sums: chip 0's device self time
under the program's INNER scopes, LAYER BY LAYER (``R.scan`` in
``ops/ssm.py``, ``R.gate`` in ``ops/blocks.py``, ``A.core`` in
``ops/attention.py``; each nested in its layer's ``L.<name>`` scope, and
the window, full and cross cores are told apart by that name alone).

``metrics/_decoder_scopes.py``'s reduction keyed by (layer, scope): read
once per process from the newest ``*.xplane.pb`` under the benchmark's
trace directory.  Where there is no trace, or the program carries no such
scope (the parent of PR 32, another cell), the readers get nothing and
return None.  A summary may carry the reduction itself
(``hybrid_scopes``: tests).
"""

from __future__ import annotations

import re

from benchmarks.harness import flops, trace
from benchmarks.metrics._common import first_chip, layer_s, self_total
from benchmarks.metrics._program_spans import newest_xplane

SCOPES = ("R.scan", "R.gate", "A.core")
_MISSING = object()
_cached = _MISSING


def reduce(tr: dict) -> dict:
    """``tr``: ``trace.load_xplane``'s neutral form.  -> {"layer_scope_s":
    {"<layer>/<scope>": seconds of chip 0 self time inside the window}}."""
    out: dict[str, float] = {}
    chips = tr["chips"]
    if chips:
        rows = chips[min(chips, key=int)]
        for ns, _, scope in trace.self_times(trace.clip(rows, tr["window"])):
            layer = trace.layer_of(scope)
            if layer is None:
                continue
            for name in SCOPES:
                if re.search(rf"\b{re.escape(name)}\b", scope):
                    key = f"{layer}/{name}"
                    out[key] = out.get(key, 0.0) + ns / 1e9
                    break
    return {"layer_scope_s": out}


def hybrid_scopes(summary) -> dict | None:
    global _cached
    if not summary:
        return None
    if "hybrid_scopes" in summary:
        return summary["hybrid_scopes"]
    if _cached is _MISSING:
        path = newest_xplane()
        _cached = reduce(trace.load_xplane(path)) if path else None
    return _cached


def _rows(run, kind: str) -> list[dict]:
    return [r for r in run.get("decoder_parts", ()) if r["kind"] == kind]


def scope_seconds(summary, scope: str, layers=None) -> float | None:
    """Chip 0 self seconds under ``scope`` in the given layers (all when
    None); None where the trace has no op there."""
    hs = hybrid_scopes(summary)
    if not hs:
        return None
    s = sum(v for k, v in hs["layer_scope_s"].items()
            if k.endswith("/" + scope)
            and (layers is None or k.split("/")[0] in layers))
    return s or None


def kind_roofline(summary, run, kind: str, scope: str) -> float | None:
    """Over the parts of ``kind`` (``run["decoder_parts"]``, from
    ``harness/hybrid_flops.py``): the least time the chip could take,
    max(ops / peak, bytes / peak) over three passes, times the steps
    traced, over the self time under ``scope`` in those parts' layers."""
    rows = _rows(run, kind)
    s = scope_seconds(summary, scope, {r["name"].split(".")[0] for r in rows})
    if s is None or "peaks" not in run or not run.get("steps_traced"):
        return None
    p = run["peaks"]
    floor = sum(flops.layer_floor_s(r, p["bf16_flops"],
                                    p["hbm_bytes_per_s"])[0] for r in rows)
    return 100.0 * floor * run["steps_traced"] / s if floor else None


def scan_share(summary) -> float | None:
    chip, s = first_chip(summary), scope_seconds(summary, "R.scan")
    if chip is None or s is None or not self_total(chip):
        return None
    return 100.0 * s / self_total(chip)


def mix_share(summary, run) -> float | None:
    """The scan layers' self time outside ``R.scan`` over the self total."""
    chip = first_chip(summary)
    layers = {r["name"].split(".")[0] for r in _rows(run, "scan")}
    s = scope_seconds(summary, "R.scan", layers)
    if chip is None or s is None or not self_total(chip):
        return None
    whole = sum(layer_s(chip, name) for name in layers)
    return 100.0 * (whole - s) / self_total(chip)
