"""What the looped decoder cell's readers take from a run's trace, beyond
``harness.trace.summarize``'s per-layer sums: chip 0's device self time
under the looped region's enclosing scope (``LOOP.<name>`` in
``compiler/graph.py``: every pass of the region, which is expanded when
the net is built, and every op inside it, forward and backward; the
region's layers keep their ``L.<name>`` scopes inside it), and the loop's counters that the program puts on its
``sn.step.fence`` spans (``ut_steps``, ``ut_loss_<t>``, ``exit_mean_step``).

Read once per process from the newest ``*.xplane.pb`` under the
benchmark's trace directory (``jobkit.traced`` has just written it).
Where there is no trace, or the program carries no such scope or counter
(the parent of PR 41, another cell), the readers get nothing and return
None.  A summary may carry the reduction itself (``loop_scopes``: tests).
"""

from __future__ import annotations

import re

from benchmarks.harness import trace
from benchmarks.metrics._common import first_chip, layer_s, self_total
from benchmarks.metrics._program_spans import newest_xplane

_LOOP = re.compile(r"\bLOOP\.[\w.\-]+")
# the exit path behind the region, by the zoo's layer names: the head and
# the gate over every pass's state, and the exit-weighted loss
EXIT_LAYERS = ("lm_head", "exit_gate", "loss")
_MISSING = object()
_cached = _MISSING


def reduce(tr: dict, fences: list[dict]) -> dict:
    """``tr``: ``trace.load_xplane``'s neutral form.  -> {"loop_s": seconds
    of chip 0 self time under a ``LOOP.*`` scope inside the window,
    "fences": [stats of each sn.step.fence span inside the window that
    carries ``exit_mean_step``]}."""
    loop_s = 0.0
    chips = tr["chips"]
    if chips:
        rows = chips[min(chips, key=int)]
        for ns, _, scope in trace.self_times(trace.clip(rows, tr["window"])):
            if _LOOP.search(scope):
                loop_s += ns / 1e9
    w0, w1 = tr["window"]
    return {"loop_s": loop_s,
            "fences": [f["stats"] for f in fences
                       if w0 <= f["start_ns"] <= w1
                       and "exit_mean_step" in f["stats"]]}


def loop_scopes(summary) -> dict | None:
    global _cached
    if not summary:
        return None
    if "loop_scopes" in summary:
        return summary["loop_scopes"]
    if _cached is _MISSING:
        from benchmarks.metrics._lm_scopes import _fence_spans

        path = newest_xplane()
        _cached = reduce(trace.load_xplane(path),
                         _fence_spans(path)) if path else None
    return _cached


def body_share(summary) -> float | None:
    chip, ls = first_chip(summary), loop_scopes(summary)
    if chip is None or not ls or not ls["loop_s"] or not self_total(chip):
        return None
    return 100.0 * ls["loop_s"] / self_total(chip)


def exit_share(summary) -> float | None:
    """Only where the trace has a looped region at all: another net's
    ``lm_head`` is not an exit path."""
    chip, ls = first_chip(summary), loop_scopes(summary)
    if chip is None or not ls or not ls["loop_s"] or not self_total(chip):
        return None
    return 100.0 * sum(layer_s(chip, name)
                       for name in EXIT_LAYERS) / self_total(chip)


def mean_exit_step(summary) -> float | None:
    ls = loop_scopes(summary)
    if not ls or not ls["fences"]:
        return None
    steps = [float(s["exit_mean_step"]) for s in ls["fences"]]
    return sum(steps) / len(steps)
