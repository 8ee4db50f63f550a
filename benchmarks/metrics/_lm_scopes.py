"""What the language-model cells' readers take from a run's trace, beyond
``harness.trace.summarize``'s per-layer sums: chip 0's device self time
under the program's INNER scopes (``M.route`` / ``M.dispatch`` /
``M.experts`` / ``M.combine`` in ``ops/moe.py``, ``A.core`` in
``ops/attention.py``; each nested in its layer's ``L.<name>`` scope, so
``summarize`` books the same time under the layer), and the expert-load
stats the program puts on its ``sn.step.fence`` spans.

Read once per process from the newest ``*.xplane.pb`` under the
benchmark's trace directory (``jobkit.traced`` has just written it).
Where there is no trace, or the program carries no such scope or stat
(the parent of PR 26, a CNN cell), the readers get nothing and return
None.  A summary may carry the reduction itself (``lm_scopes``: tests).
"""

from __future__ import annotations

import re

from benchmarks.harness import flops, trace
from benchmarks.metrics._common import first_chip, self_total
from benchmarks.metrics._program_spans import newest_xplane

SCOPES = ("M.route", "M.dispatch", "M.experts", "M.combine", "A.core")
_MISSING = object()
_cached = _MISSING


def reduce(tr: dict, fences: list[dict]) -> dict:
    """``tr``: ``trace.load_xplane``'s neutral form.  -> {"scope_s":
    {scope: seconds of chip 0 self time inside the window}, "load":
    [stats of each sn.step.fence span inside the window that has them]}."""
    scope_s = dict.fromkeys(SCOPES, 0.0)
    chips = tr["chips"]
    if chips:
        rows = chips[min(chips, key=int)]
        for ns, _, scope in trace.self_times(trace.clip(rows, tr["window"])):
            for name in SCOPES:
                if re.search(rf"\b{re.escape(name)}\b", scope):
                    scope_s[name] += ns / 1e9
                    break
    w0, w1 = tr["window"]
    load = [f["stats"] for f in fences
            if w0 <= f["start_ns"] <= w1 and "moe_load_max" in f["stats"]]
    return {"scope_s": scope_s, "load": load}


def _fence_spans(path: str) -> list[dict]:
    from benchmarks.harness import xplane

    out = []
    for plane in xplane.read(path, lambda p, l: p.startswith("/host:CPU")):
        for line in plane["lines"]:
            out += [ev for ev in line["events"]
                    if ev["name"] == "sn.step.fence"]
    return out


def lm_scopes(summary) -> dict | None:
    global _cached
    if not summary:
        return None
    if "lm_scopes" in summary:
        return summary["lm_scopes"]
    if _cached is _MISSING:
        path = newest_xplane()
        _cached = reduce(trace.load_xplane(path),
                         _fence_spans(path)) if path else None
    return _cached


def scope_seconds(summary, *scopes: str) -> float | None:
    """Chip 0 self seconds under the given inner scopes; None where the
    trace has no op under any of them."""
    ls = lm_scopes(summary)
    if not ls:
        return None
    s = sum(ls["scope_s"].get(name, 0.0) for name in scopes)
    return s or None


def share_of_busy(summary, *scopes: str) -> float | None:
    chip = first_chip(summary)
    s = scope_seconds(summary, *scopes)
    if chip is None or s is None or not self_total(chip):
        return None
    return 100.0 * s / self_total(chip)


def part_roofline(summary, run, kind: str, scope: str) -> float | None:
    """Over the parts of ``kind`` (``harness/lm_flops.py``): the least
    time the chip could take, max(ops / peak, bytes / peak) over three
    passes, times the steps traced, over the self time under ``scope``."""
    s = scope_seconds(summary, scope)
    if s is None or "peaks" not in run or not run.get("steps_traced"):
        return None
    p = run["peaks"]
    floor = sum(
        flops.layer_floor_s(r, p["bf16_flops"], p["hbm_bytes_per_s"])[0]
        for r in run.get("lm_parts", ()) if r["kind"] == kind)
    return 100.0 * floor * run["steps_traced"] / s if floor else None
