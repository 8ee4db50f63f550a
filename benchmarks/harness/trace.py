"""Reduction of a profiler trace to what the per-layer metrics read.

A corrected copy of the program's ``utils/op_profile.py`` (PERF.md,
inventory):

* busy time is the UNION of device-op intervals per chip, not a sum of
  durations, so an idle share exists;
* time is attributed by SELF time: an op that contains others on its line
  (a ``while`` around a scanned tau round) keeps only what its children
  do not cover, so a loop is not counted twice;
* idle gaps are attributed to what the host was doing (the benchmark's
  ``bench.*`` spans, on the profiler's own clock);
* it reads the ``.xplane.pb`` itself (``jax.profiler.ProfileData``), not
  the capped chrome-JSON export.

Layer attribution is the program's ``jax.named_scope("L.<layer>")``
(``compiler/graph.py``); the backward pass carries ``transpose(jvp(`` in
its scope path.

The neutral form every function here takes (and the recorded test trace
is written in):

    {"window": [start_ns, end_ns],
     "chips": {"0": [[start_ns, dur_ns, name, scope], ...], ...},
     "host":  [[start_ns, dur_ns, name], ...]}
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

_LAYER = re.compile(r"\bL\.([\w.\-]+)")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
WINDOW_SPAN = "bench.window"
_OPS_LINE = "xla ops"


# ------------------------------------------------------------------ loading
def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """The neutral form of one ``.xplane.pb`` (harness/xplane.py decodes
    it: an op's scope path is the ``tf_op`` stat of its event METADATA,
    which ``jax.profiler.ProfileData`` does not expose)."""
    from benchmarks.harness import xplane

    def want(plane: str, line: str) -> bool:
        return (plane.startswith("/device:TPU:") and line.strip().lower() == _OPS_LINE) \
            or plane.startswith("/host:CPU")

    chips: dict[str, list] = {}
    host: list = []
    for plane in xplane.read(path, want):
        m = re.match(r"/device:TPU:(\d+)$", plane["name"])
        for line in plane["lines"]:
            if m and line["name"].strip().lower() == _OPS_LINE:
                rows = chips.setdefault(m.group(1), [])
                for ev in line["events"]:
                    name = ev["display"] or ev["name"].split(" = ")[0].lstrip("%")
                    rows.append([int(ev["start_ns"]), int(ev["dur_ns"]), name,
                                 str(ev["stats"].get("tf_op", ""))])
            elif not m:
                for ev in line["events"]:
                    if ev["name"].startswith("bench."):
                        host.append([int(ev["start_ns"]), int(ev["dur_ns"]),
                                     ev["name"]])
    trace = {"chips": chips, "host": host}
    trace["window"] = window_of(trace)
    return trace


def window_of(trace: dict) -> list[int]:
    """The traced window: the ``bench.window`` span where there is one,
    else first device-op start to last device-op end."""
    spans = [h for h in trace["host"] if h[2] == WINDOW_SPAN]
    if spans:
        s = max(spans, key=lambda h: h[1])
        return [s[0], s[0] + s[1]]
    evs = [e for rows in trace["chips"].values() for e in rows]
    if not evs:
        return [0, 0]
    return [min(e[0] for e in evs), max(e[0] + e[1] for e in evs)]


# --------------------------------------------------------------- arithmetic
def union(intervals) -> list[list[int]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(rows, window):
    """Events cut to the window (start, end, name, scope)."""
    w0, w1 = window
    out = []
    for s, d, name, scope in rows:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((a, b, name, scope))
    return out


def self_times(events):
    """[(self_ns, name, scope)] for events on ONE line: each event's
    duration less what the events nested directly inside it cover."""
    evs = sorted(events, key=lambda e: (e[0], -(e[1] - e[0])))
    selfs: list[list] = []  # [self_ns, name, scope]
    stack: list[tuple[int, int]] = []  # (end, index into selfs)
    for a, b, name, scope in evs:
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            selfs[stack[-1][1]][0] -= min(b, stack[-1][0]) - a
        selfs.append([b - a, name, scope])
        stack.append((b, len(selfs) - 1))
    return [(max(ns, 0), name, scope) for ns, name, scope in selfs]


def layer_of(scope: str) -> str | None:
    m = _LAYER.search(scope)
    return m.group(1) if m else None


def is_backward(scope: str) -> bool:
    return "transpose(jvp(" in scope


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def gaps(busy, window):
    """Idle intervals of the window given the merged busy intervals."""
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if window[1] > at:
        out.append([at, window[1]])
    return out


def attribute_gaps(idle, host_spans, window) -> dict[str, int]:
    """Idle nanoseconds by the host span that covers them.  Where spans
    nest, the innermost (shortest) one that overlaps takes the time; what
    no ``bench.*`` span covers is ``(no span)``."""
    spans = sorted(
        ((s, s + d, n) for s, d, n in host_spans if n != WINDOW_SPAN),
        key=lambda x: x[1] - x[0])
    out: dict[str, int] = defaultdict(int)
    for g0, g1 in idle:
        left = [[g0, g1]]
        for s, e, n in spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    out[n] += hi - lo
                    if a < lo:
                        nxt.append([a, lo])
                    if hi < b:
                        nxt.append([hi, b])
                else:
                    nxt.append([a, b])
            left = nxt
        out["(no span)"] += sum(b - a for a, b in left)
    return {k: v for k, v in out.items() if v > 0}


# ------------------------------------------------------------------ summary
def summarize(trace: dict) -> dict:
    """Everything the metric readers take from a trace, in seconds."""
    window = trace["window"]
    window_ns = max(window[1] - window[0], 0)
    chips = {}
    for chip, rows in sorted(trace["chips"].items(), key=lambda kv: int(kv[0])):
        evs = clip(rows, window)
        busy = union((a, b) for a, b, _, _ in evs)
        layer_fwd: dict[str, int] = defaultdict(int)
        layer_bwd: dict[str, int] = defaultdict(int)
        unscoped: dict[str, int] = defaultdict(int)
        coll_ns = 0
        for ns, name, scope in self_times(evs):
            if is_collective(name):
                coll_ns += ns
            layer = layer_of(scope)
            if layer is None:
                unscoped[scope.rstrip(":") or name] += ns
            elif is_backward(scope):
                layer_bwd[layer] += ns
            else:
                layer_fwd[layer] += ns
        coll = union((a, b) for a, b, n, _ in evs if is_collective(n))
        other = union((a, b) for a, b, n, _ in evs
                      if not is_collective(n) and not _is_container(n))
        exposed = total(coll) - _overlap(coll, other)
        idle = gaps(busy, window)
        chips[chip] = {
            "busy_s": total(busy) / 1e9,
            "idle_gaps_s": {k: v / 1e9 for k, v in attribute_gaps(
                idle, trace["host"], window).items()},
            "layer_fwd_s": {k: v / 1e9 for k, v in layer_fwd.items()},
            "layer_bwd_s": {k: v / 1e9 for k, v in layer_bwd.items()},
            "unscoped_s": {k: v / 1e9 for k, v in unscoped.items()},
            "collective_s": coll_ns / 1e9,
            "collective_exposed_s": exposed / 1e9,
            "events": len(evs),
        }
    return {"window_s": window_ns / 1e9, "chips": chips}


def _is_container(name: str) -> bool:
    """Ops that only wrap others on the line (their time is their
    children's): loops and conditionals."""
    return bool(re.match(r"(while|conditional|call)([.\d]|$)", name))


def _overlap(a, b) -> int:
    i = j = 0
    out = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def breakdown(summary: dict, chip: str | None = None) -> dict:
    """The ledger's view of one chip: top-10 device operations (layers as
    ``L.<name>.fwd`` / ``.bwd``, unscoped ops by their scope path, or by
    HLO name where they carry none) and top-10 idle
    gaps by host span.  Default chip: the one with most idle time."""
    chips = summary["chips"]
    if not chips:
        return {"device_ops": [], "idle_gaps": []}
    if chip is None:
        chip = min(chips, key=lambda c: chips[c]["busy_s"])
    c = chips[chip]
    ops = [(f"L.{k}.fwd", v) for k, v in c["layer_fwd_s"].items()]
    ops += [(f"L.{k}.bwd", v) for k, v in c["layer_bwd_s"].items()]
    ops += list(c["unscoped_s"].items())
    top = lambda rows: [[k, v] for k, v in sorted(rows, key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(c["idle_gaps_s"].items())}
