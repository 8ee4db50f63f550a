"""The program's own spans (``sn.*``, PR 24) read out of a run's trace.

The program annotates the feed, the step and the round from inside, on
the profiler's clock (``sparknet_tpu/obs/recorder.py`` ``Span``:
``sn.feed.read`` / ``decode`` / ``collate`` / ``stack`` / ``put`` /
``augment`` / ``full`` / ``wait``, ``sn.step`` / ``sn.step.fence``,
``sn.round`` / ``.data`` / ``.dispatch`` / ``.fence``), each with its
``it`` and, where the work has a size, ``images`` and ``bytes``.  This
file reads them, once per process, from the newest ``*.xplane.pb`` under
the benchmark's trace directory (``jobkit.traced`` has just written it),
and gives the ``feed.*_ms`` readers their sums and a table on stderr:
per span and thread the count, total, mean and ms per 1,000 images, then
the idlest chip's idle seconds by the innermost ``sn.*`` span that covers
them, main thread and feed threads apart.

Where there is no trace, or the program carries no ``sn.*`` span (the
parent of PR 24; a CPU rehearsal has spans and no chip), the readers get
nothing and return None.

A later ``benchmark`` issue folds this into ``harness.trace.summarize``
(the summary then carries ``program_spans`` itself, which
``program_spans()`` already prefers) and retires the ``bench.*``
wrappers of ``harness/front_door.py`` that these spans supersede.

The neutral form (the recorded test trace is written in it):

    {"window": [start_ns, end_ns],
     "chips": {"0": [[start_ns, dur_ns, name, scope], ...], ...},
     "host":  [[start_ns, dur_ns, name, thread, {"images": n, ...}], ...]}
"""

from __future__ import annotations

import glob
import os
import re
import sys

from benchmarks.harness import dataset, trace

PREFIX = "sn."
PARENTS = ("sn.step", "sn.round")  # the StepTraceAnnotations
OPEN_AT_START = "(span open at trace start)"
_MISSING = object()
_cached = _MISSING
_listed = False


# ------------------------------------------------------------------ loading
def newest_xplane() -> str | None:
    paths = glob.glob(os.path.join(dataset.CACHE_DIR, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load_xplane(path: str) -> dict:
    """The neutral form of one ``.xplane.pb``: the ``sn.*`` spans and the
    ``bench.window`` of every host thread, the op intervals of every chip."""
    from benchmarks.harness import xplane

    def want(plane: str, line: str) -> bool:
        return plane.startswith("/host:CPU") or (
            plane.startswith("/device:TPU:")
            and line.strip().lower() == trace._OPS_LINE)

    chips: dict[str, list] = {}
    host: list = []
    for plane in xplane.read(path, want):
        m = re.match(r"/device:TPU:(\d+)$", plane["name"])
        for index, line in enumerate(plane["lines"]):
            if m:
                chips.setdefault(m.group(1), []).extend(
                    [int(ev["start_ns"]), int(ev["dur_ns"]), ev["name"], ""]
                    for ev in line["events"])
                continue
            # threads share a line name ("python"): the index tells them apart
            thread = f"{line['name']}#{index}"
            for ev in line["events"]:
                if ev["name"].startswith(PREFIX) or ev["name"] == trace.WINDOW_SPAN:
                    stats = {k: v for k, v in ev["stats"].items()
                             if k in ("it", "step_num", "images", "bytes")}
                    host.append([int(ev["start_ns"]), int(ev["dur_ns"]),
                                 ev["name"], thread, stats])
    out = {"chips": chips, "host": host}
    out["window"] = trace.window_of(
        {"chips": chips, "host": [h[:3] for h in host]})
    return out


# ---------------------------------------------------------------- reduction
def reduce(tr: dict) -> dict | None:
    """Per (span, thread) the sums over the spans WHOLLY inside the
    window, and the idlest chip's idle gaps by innermost ``sn.*`` span,
    the window's own thread (main) and the other threads (feed) apart.
    None where the trace holds no ``sn.*`` span."""
    w0, w1 = tr["window"]
    spans = [h for h in tr["host"] if h[2].startswith(PREFIX)]
    if not spans or w1 <= w0:
        return None
    main = next((h[3] for h in tr["host"] if h[2] == trace.WINDOW_SPAN), None)
    rows: dict[tuple, dict] = {}
    for s, d, name, thread, stats in spans:
        if s < w0 or s + d > w1:
            continue  # straddles the window's edge: left out, not cut
        row = rows.setdefault((name, thread), {
            "name": name, "thread": thread,
            "role": "main" if thread == main else "feed",
            "count": 0, "total_s": 0.0, "images": 0, "bytes": 0})
        row["count"] += 1
        row["total_s"] += d / 1e9
        row["images"] += int(stats.get("images", 0))
        row["bytes"] += int(stats.get("bytes", 0))
    out = {"window_s": (w1 - w0) / 1e9, "main_thread": main,
           "spans": sorted(rows.values(),
                           key=lambda r: (r["role"] != "main", r["thread"],
                                          -r["total_s"])),
           "threads": {}}
    # per thread, the part of ITS window its stage spans cover (cut to the
    # window, so a straddler counts its inside part; the per-step parents
    # sn.step / sn.round are left out: they cover their stages' gaps too).
    # A thread's window starts at its first recorded span: the profiler
    # drops a span that was already open when the session started (a feed
    # thread is nearly always inside a read), so before that it saw nothing
    for thread in sorted({h[3] for h in spans}):
        mine = [(h[0], h[1], h[2], "") for h in spans if h[3] == thread]
        seen = [max(w0, min(h[0] for h in mine)), w1]
        stages = [h for h in mine if h[2] not in PARENTS]
        out["threads"][thread] = {
            "seen_s": (seen[1] - seen[0]) / 1e9,
            "covered_s": trace.total(trace.union(
                (a, b) for a, b, _, _ in trace.clip(stages, seen))) / 1e9}
    if tr["chips"]:
        busy = {c: trace.union((a, b) for a, b, _, _ in
                               trace.clip(rows_, tr["window"]))
                for c, rows_ in tr["chips"].items()}
        chip = min(busy, key=lambda c: trace.total(busy[c]))
        idle = trace.gaps(busy[chip], tr["window"])
        out["idle_chip"] = chip
        out["idle_s"] = {}
        for role in ("main", "feed"):
            mine = [h[:3] for h in spans if (h[3] == main) == (role == "main")]
            first = min((h[0] for h in mine), default=w1)
            if first > w0:  # see above: not unspanned work, unseen work
                mine.append([w0, first - w0, OPEN_AT_START])
            out["idle_s"][role] = {
                k: v / 1e9 for k, v in
                trace.attribute_gaps(idle, mine, tr["window"]).items()}
    return out


def per_kimg(ps: dict | None, name: str) -> float | None:
    """Milliseconds per 1,000 images over every span called ``name``."""
    rows = [r for r in (ps or {}).get("spans", ()) if r["name"] == name]
    images = sum(r["images"] for r in rows)
    if not images:
        return None
    return 1e6 * sum(r["total_s"] for r in rows) / images


def table(ps: dict) -> str:
    lines = [f"sn.* spans wholly inside the {ps['window_s']:.3f}s window "
             f"(main thread: {ps['main_thread']})",
             f"{'span':22s} {'thread':12s} {'role':5s} {'count':>6s} "
             f"{'total_s':>9s} {'mean_ms':>9s} {'ms/kimg':>9s}"]
    for r in ps["spans"]:
        kimg = f"{1e6 * r['total_s'] / r['images']:9.2f}" if r["images"] else f"{'-':>9s}"
        lines.append(
            f"{r['name']:22s} {r['thread']:12s} {r['role']:5s} "
            f"{r['count']:6d} {r['total_s']:9.4f} "
            f"{1e3 * r['total_s'] / r['count']:9.3f} {kimg}")
    for thread, t in ps["threads"].items():
        lines.append(
            f"thread {thread}: stage spans cover {t['covered_s']:.4f}s = "
            f"{100 * t['covered_s'] / (t['seen_s'] or 1):.2f}% of the "
            f"{t['seen_s']:.4f}s from its first recorded span to the "
            "window's end")
    for role, gaps in ps.get("idle_s", {}).items():
        total = sum(gaps.values())
        lines.append(f"chip {ps['idle_chip']} idle {total:.4f}s by innermost "
                     f"{role}-thread span:")
        lines += [f"  {k:22s} {v:9.4f}s {100 * v / (total or 1):6.2f}%"
                  for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])]
    return "\n".join(lines)


# ------------------------------------------------------------ the run's own
def program_spans(summary) -> dict | None:
    """The reduction for this run: the summary's own where it carries one
    (tests; ``trace.summarize`` once this is folded in), else read once
    per process from the newest trace.  None for an untraced run."""
    global _cached
    if not summary:
        return None
    if "program_spans" in summary:
        return summary["program_spans"]
    if _cached is _MISSING:
        path = newest_xplane()
        _cached = reduce(load_xplane(path)) if path else None
        if _cached:
            print(table(_cached), file=sys.stderr, flush=True)
    return _cached


def scope_share(summary, scope: str) -> float | None:
    """Chip 0: share (%) of device self time in unscoped ops whose scope
    path names ``scope`` (an ``S.*`` scope: outside every ``L.<layer>``,
    so it stays in ``unscoped_s``, keyed by the path).  None where no op
    carries it.  The first call prints what ``unscoped_s`` holds, largest
    first, so a run says what is still unnamed."""
    global _listed
    from benchmarks.metrics._common import first_chip, self_total

    chip = first_chip(summary)
    if chip is None or not self_total(chip):
        return None
    total = self_total(chip)
    if not _listed:
        _listed = True
        rows = sorted(chip["unscoped_s"].items(), key=lambda kv: -kv[1])
        print("\n".join(
            ["chip 0 device self time outside every L.<layer> scope, by "
             f"scope path ({len(rows)} paths, largest 12):"]
            + [f"  {100 * v / total:6.2f}%  {k}" for k, v in rows[:12]]),
            file=sys.stderr, flush=True)
    hit = [v for k, v in chip["unscoped_s"].items() if scope in k]
    if not hit:
        return None
    return 100.0 * sum(hit) / total
