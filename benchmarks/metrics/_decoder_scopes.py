"""What the latent-attention decoder cells' readers take from a run's
trace, beyond ``harness.trace.summarize``'s per-layer sums: chip 0's device
self time under the program's INNER scopes (``A.latent`` and ``A.core`` in
``ops/attention.py``; ``M.route`` / ``M.dispatch`` / ``M.experts`` /
``M.combine`` / ``M.shared`` in ``ops/moe.py``; each nested in its layer's
``L.<name>`` scope), and what the program puts on its ``sn.step.fence``
spans about the share of the experts it holds and its balancing bias
(``moe_pairs_held``, ``moe_layers``, ``moe_bias_min`` / ``moe_bias_max``
beside ``moe_load_max`` / ``moe_pairs`` / ``moe_experts``).

``metrics/_lm_scopes.py``'s reduction with this configuration's scopes:
read once per process from the newest ``*.xplane.pb`` under the
benchmark's trace directory.  Where there is no trace, or the program
carries no such scope or stat (the parent of PR 30, another cell), the
readers get nothing and return None.  A summary may carry the reduction
itself (``decoder_scopes``: tests).
"""

from __future__ import annotations

import re

from benchmarks.harness import flops, trace
from benchmarks.metrics._common import first_chip, self_total
from benchmarks.metrics._lm_scopes import _fence_spans
from benchmarks.metrics._program_spans import newest_xplane

SCOPES = ("A.latent", "A.core", "M.route", "M.dispatch", "M.experts",
          "M.combine", "M.shared")
_MISSING = object()
_cached = _MISSING


def reduce(tr: dict, fences: list[dict]) -> dict:
    """``tr``: ``trace.load_xplane``'s neutral form.  -> {"scope_s":
    {scope: seconds of chip 0 self time inside the window}, "fences":
    [stats of each sn.step.fence span inside the window that counts held
    pairs or carries the bias's extremes]}."""
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
    kept = [f["stats"] for f in fences if w0 <= f["start_ns"] <= w1
            and ("moe_pairs_held" in f["stats"] or "moe_bias_max" in f["stats"])]
    return {"scope_s": scope_s, "fences": kept}


def decoder_scopes(summary) -> dict | None:
    global _cached
    if not summary:
        return None
    if "decoder_scopes" in summary:
        return summary["decoder_scopes"]
    if _cached is _MISSING:
        path = newest_xplane()
        _cached = reduce(trace.load_xplane(path),
                         _fence_spans(path)) if path else None
    return _cached


def scope_seconds(summary, *scopes: str) -> float | None:
    """Chip 0 self seconds under the given inner scopes; None where the
    trace has no op under any of them."""
    ds = decoder_scopes(summary)
    if not ds:
        return None
    s = sum(ds["scope_s"].get(name, 0.0) for name in scopes)
    return s or None


def share_of_busy(summary, *scopes: str) -> float | None:
    chip = first_chip(summary)
    s = scope_seconds(summary, *scopes)
    if chip is None or s is None or not self_total(chip):
        return None
    return 100.0 * s / self_total(chip)


def part_roofline(summary, run, kind: str, scope: str) -> float | None:
    """Over the parts of ``kind`` (``run["decoder_parts"]``, from the
    configuration's flop module): the least time the chip could take,
    max(ops / peak, bytes / peak) over three passes, times the steps
    traced, over the self time under ``scope``."""
    s = scope_seconds(summary, scope)
    if s is None or "peaks" not in run or not run.get("steps_traced"):
        return None
    p = run["peaks"]
    floor = sum(
        flops.layer_floor_s(r, p["bf16_flops"], p["hbm_bytes_per_s"])[0]
        for r in run.get("decoder_parts", ()) if r["kind"] == kind)
    return 100.0 * floor * run["steps_traced"] / s if floor else None


def fence_mean(summary, key: str, value) -> float | None:
    """Mean over the traced window's fences that carry ``key`` of
    ``value(stats)``."""
    ds = decoder_scopes(summary)
    rows = [s for s in (ds["fences"] if ds else ()) if key in s]
    if not rows:
        return None
    return sum(value(s) for s in rows) / len(rows)
