"""The program's own record of its spans (``obs.recorder.flight``, PR 34)
read at the end of a traced run: set-up, the timed window and the traced
window, which the run's process has all been through by the time the
readers are called.

A ``Span`` keeps ``(name, thread, start_ns, wall_ns, counts)`` whether or
not a profiler session is open, so this file sees what the xplane cannot:
where ``setup_s`` goes (``sn.main``, ``sn.setup.net``, ``sn.solver.build``
with its ``.nets`` and ``.init``, ``sn.trainer.build``, ``sn.feed.open``,
the first ``sn.step`` / ``sn.round``, each with the ``compiles`` and
``compile_s`` of its thread), and every fence and feed wait of the 30 s
window instead of the 8-32 traced steps.  It is read once per process,
printed as a table on stderr, and written as ``flight.json`` beside the
run's xplane.

The record's clock is ``time.time_ns()``; the xplane's starts at its
profiler session.  The ``sn.step`` / ``sn.round`` spans of the traced
window are in both, so their ``it`` anchors one on the other: ``trace``
in the neutral form holds the offset (median and spread over the pairs)
and the ``bench.window`` laid on the record's clock.

Where the program has no ``flight`` (the parent of PR 34) the readers get
None and the line leaves their metrics out.

The neutral form (``flight.json``; the tests write it by hand):

    {"process_start_ns": n, "dropped": n, "last_compile_ns": n,
     "compile_seconds": {thread: {"trace": s, "lower": s, ...}, ...},
     "trace": {"offset_ns": n, "offset_spread_ns": n, "pairs": n,
               "window": [start_ns, end_ns]},
     "spans": [[name, thread, start_ns, wall_ns, {"it": n, ...}], ...]}

Set-up is what began before the process's last backend compile ended
(``last_compile_ns``): every ``Solver`` and trainer the process built by
then, the job's and the benchmark check's alike.  The solo cells' checks
build none and step through no ``sn.*`` span; ``alexnet-tau10-x4``'s
round check builds a trainer of its own and runs one round on it (the
first ``sn.trainer.build`` and the first ``sn.round`` of the table), and
the job two (one device, four): the table lists each, PERF.md says which
share is the check's.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

BUILD = ("sn.setup.net", "sn.solver.build", "sn.trainer.build")
STEPS = ("sn.step", "sn.round")  # the spans the xplane holds by step_num
FENCES = ("sn.step.fence", "sn.round.fence")
WAIT = "sn.feed.wait"
COMPILE_KEYS = ("compiles", "compile_s", "cache_hits")
_MISSING = object()
_cached = _MISSING


# ------------------------------------------------------------------ taking
def process_start_ns() -> int | None:
    """When the kernel created this process, on ``time.time_ns()``'s clock:
    ``/proc/self/stat`` field 22 (ticks since boot) against the boot-time
    clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime_ns(time.CLOCK_BOOTTIME)
               - ticks * 10**9 // os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.time_ns() - age


def take() -> dict | None:
    """This process's record in the neutral form, without ``trace``; None
    where the program keeps no record."""
    try:
        from sparknet_tpu.obs.recorder import flight
        from sparknet_tpu.obs.sentinel import EVENT_LABELS, get_sentinel
    except ImportError:
        return None
    spans, dropped = flight()
    sentinel = get_sentinel()
    seconds = {t: sentinel.thread_seconds(t) for t in {s[1] for s in spans}}
    return {"process_start_ns": process_start_ns(), "dropped": dropped,
            "last_compile_ns": sentinel.last_compile_ns,
            "compile_seconds": {
                str(t): {w: by[e] for e, w in EVENT_LABELS.items() if e in by}
                for t, by in seconds.items() if by},
            "spans": [[n, t, s, w, dict(c)] for n, t, s, w, c in spans]}


def anchor(rec: dict, xplane_path: str) -> dict | None:
    """``trace`` for ``rec``: the clock offset (xplane minus record) over
    the step spans both hold, and the ``bench.window`` on the record's
    clock.  None where they share no step."""
    from benchmarks.harness import trace, xplane

    steps, window = {}, None
    for plane in xplane.read(xplane_path,
                             lambda p, line: p.startswith("/host:")):
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev["name"] in STEPS and "step_num" in ev["stats"]:
                    steps[ev["name"], int(ev["stats"]["step_num"])] = ev
                elif ev["name"] == trace.WINDOW_SPAN:
                    window = ev
    # two trainers count their rounds from 0: the traced one ran last
    mine = {(s[0], s[4].get("it")): s for s in rec["spans"] if s[0] in STEPS}
    diffs = [ev["start_ns"] - mine[key][2]
             for key, ev in steps.items() if key in mine]
    if not diffs:
        return None
    offset = statistics.median(diffs)
    out = {"offset_ns": offset, "pairs": len(diffs),
           "offset_spread_ns": max(diffs) - min(diffs)}
    if window is not None:
        w0 = window["start_ns"] - offset
        out["window"] = [w0, w0 + window["dur_ns"]]
    return out


# ---------------------------------------------------------------- reduction
def _end(span) -> int:
    return span[2] + span[3]


def _top_level(spans) -> list:
    """Of the spans that carry compile stats, per thread those no other
    such span of the thread contains: a compile inside nested spans is on
    each of them, and is counted once."""
    out = []
    threads: dict = {}
    for s in spans:
        if any(k in s[4] for k in COMPILE_KEYS):
            threads.setdefault(s[1], []).append(s)
    for mine in threads.values():
        covered_to = -1
        for s in sorted(mine, key=lambda s: (s[2], -s[3])):
            if s[2] >= covered_to:
                out.append(s)
                covered_to = _end(s)
    return out


def _overlap(span, lo, hi) -> int:
    return max(0, min(_end(span), hi) - max(span[2], lo))


def reduce(rec: dict) -> dict | None:
    """The six metrics and what the table shows, from the neutral form.
    None where the record holds no span."""
    from sparknet_tpu.obs.recorder import SETUP_STAGES, stages

    spans = sorted(rec.get("spans") or (), key=lambda s: s[2])
    if not spans:
        return None
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    out: dict = {"metrics": {}, "dropped": rec.get("dropped", 0)}
    m = out["metrics"]

    # the timed interval: from the last compile to the traced window;
    # set-up: what began before it
    t_hi = (rec.get("trace") or {}).get("window", [_end(spans[-1]) + 1])[0]
    t_lo = rec.get("last_compile_ns") or 0
    if not 0 < t_lo <= t_hi:
        t_lo = max((_end(s) for s in _top_level(spans) if _end(s) <= t_hi),
                   default=0)
    setup = [s for s in spans if s[2] < (t_lo or t_hi)]
    timed = [s for s in spans if s[2] >= t_lo and _end(s) <= t_hi]

    front = by_name.get("sn.main", [None])[0]
    main = front[1] if front else spans[0][1]
    if front and rec.get("process_start_ns"):
        m["setup.before_front_door_s"] = (
            front[2] - rec["process_start_ns"]) / 1e9
    out["stages"] = stages(setup)
    walls = {r["name"]: r["wall_s"] for r in out["stages"]}
    if any(n in walls for n in BUILD):
        m["setup.solver_build_s"] = sum(walls.get(n, 0.0) for n in BUILD)
    if "sn.solver.nets" in walls:
        m["setup.net_build_s"] = walls["sn.solver.nets"]
    top = _top_level(setup)
    if walls or any(s[0] in STEPS for s in setup):
        m["setup.compile_s"] = sum(s[4].get("compile_s", 0.0) for s in top)
    # by thread, what compiled outside the stages: each first step or
    # round, a feed thread's first augment
    out["compiled"] = [
        {"name": s[0], "thread": "main" if s[1] == main else "feed",
         "it": s[4].get("it"), "wall_s": s[3] / 1e9,
         "at_s": (s[2] - (rec.get("process_start_ns") or spans[0][2])) / 1e9,
         **{k: s[4].get(k, 0) for k in COMPILE_KEYS}}
        for s in top if s[0] not in SETUP_STAGES]
    out["compile_seconds"] = {}
    for thread, by in (rec.get("compile_seconds") or {}).items():
        side = out["compile_seconds"].setdefault(
            "main" if thread == str(main) else "feed", {})
        for label, v in by.items():
            side[label] = side.get(label, 0.0) + v

    out["interval_s"] = (t_hi - t_lo) / 1e9
    rows: dict[str, list] = {}
    for s in timed:
        rows.setdefault(s[0], []).append(s[3] / 1e9)
    out["timed"] = [
        {"name": n, "count": len(v), "total_s": sum(v),
         "p50_ms": 1e3 * statistics.median(v), "max_ms": 1e3 * max(v)}
        for n, v in sorted(rows.items(), key=lambda kv: -sum(kv[1]))]

    # first touch: the fence that follows the last compile is left out
    fences = [s for s in timed if s[0] in FENCES and s[1] == main][1:]
    if len(fences) >= 2:
        longest = max(fences, key=lambda s: s[3])
        median = statistics.median(s[3] for s in fences)
        if median > 0:
            m["step.fence_max_over_median"] = longest[3] / median
        lo, hi = longest[2], _end(longest)
        inside: dict[str, int] = {}
        for s in spans:  # what the other threads were inside meanwhile
            if s[1] != main and _overlap(s, lo, hi):
                inside[s[0]] = inside.get(s[0], 0) + _overlap(s, lo, hi)
        ends = [_end(s) for s in fences]
        chunks = [b - a for a, b in zip(ends, ends[1:])]
        out["longest_fence"] = {
            "name": longest[0], "it": longest[4].get("it"),
            "wall_s": longest[3] / 1e9, "median_s": median / 1e9,
            "fences": len(fences),
            "feed_wait_not_ready_s": sum(
                _overlap(s, lo, hi) for s in by_name.get(WAIT, ())
                if s[1] == main and not s[4].get("ready", 1)) / 1e9,
            "feed_threads_inside_s": {
                k: v / 1e9 for k, v in sorted(inside.items(),
                                              key=lambda kv: -kv[1])}}
        if chunks:  # fence end to fence end: a stall in the steps shows here
            worst = max(range(len(chunks)), key=chunks.__getitem__)
            out["longest_chunk"] = {
                "it": fences[worst + 1][4].get("it"),
                "wall_s": chunks[worst] / 1e9,
                "median_s": statistics.median(chunks) / 1e9}
    waits = [s for s in timed if s[0] == WAIT and "ready" in s[4]]
    if waits:
        m["feed.ahead_share"] = 100.0 * sum(
            1 for s in waits if s[4]["ready"]) / len(waits)

    # how much of the main thread's set-up the stage spans account for:
    # from the front door's hand-over to the first fence of the program
    first_fence = next((s for n in FENCES for s in by_name.get(n, ())), None)
    if front and first_fence:
        lo, hi = _end(front), _end(first_fence)
        mine = sorted(
            (s for s in spans if s[1] == main and lo <= s[2] < hi
             and s[0] in (*BUILD, "sn.feed.open", *STEPS, *FENCES)),
            key=lambda s: s[2])
        covered, gaps, at, last = 0, [], lo, "sn.main"
        for s in mine:
            if s[2] > at:
                gaps.append({"after": last, "before": s[0],
                             "s": (s[2] - at) / 1e9})
            if min(_end(s), hi) > at:
                covered += min(_end(s), hi) - max(at, s[2])
                at, last = min(_end(s), hi), s[0]
        out["setup_cover"] = {
            "span_s": (hi - lo) / 1e9, "covered_s": covered / 1e9,
            "gaps": sorted(gaps, key=lambda g: -g["s"])[:6]}
    return out


def table(red: dict, trace: dict | None = None) -> str:
    lines = ["the program's record (obs.recorder.flight): set-up by stage, "
             "up to the last compile",
             f"{'stage':18s} {'count':>5s} {'wall_s':>9s} {'compiles':>8s} "
             f"{'compile_s':>9s} {'cache_hits':>10s}  stats"]
    for r in red["stages"]:
        lines.append(
            f"{r['name']:18s} {r['count']:5d} {r['wall_s']:9.3f} "
            f"{r['compiles']:8d} {r['compile_s']:9.3f} "
            f"{r['cache_hits']:10d}  " + ", ".join(
                f"{k} {'/'.join(map(str, v))}"
                for k, v in r["stats"].items()))
    for r in red["compiled"]:
        lines.append(
            f"compiled at {r['at_s']:.1f}s in {r['name']} it={r['it']} "
            f"({r['thread']} thread): "
            f"{r['compiles']} compiles, {r['compile_s']:.3f}s of its "
            f"{r['wall_s']:.3f}s, {r['cache_hits']} from the cache")
    for side, by in red["compile_seconds"].items():
        lines.append(
            f"{side} thread's compile seconds by jax's events (they nest; "
            "compile_s is their union): "
            + ", ".join(f"{k} {v:.3f}" for k, v in by.items()))
    cover = red.get("setup_cover")
    if cover:
        lines.append(
            f"main thread from sn.main's end to the first fence: "
            f"{cover['span_s']:.3f}s, of which the set-up and step spans "
            f"cover {cover['covered_s']:.3f}s "
            f"({100 * cover['covered_s'] / (cover['span_s'] or 1):.1f}%); "
            "largest gaps: " + ", ".join(
                f"{g['s']:.3f}s {g['after']} -> {g['before']}"
                for g in cover["gaps"]))
    lines += [f"from the last compile to the traced window "
              f"({red['interval_s']:.3f}s):",
              f"{'span':22s} {'count':>6s} {'total_s':>9s} {'p50_ms':>9s} "
              f"{'max_ms':>9s}"]
    for r in red["timed"]:
        lines.append(f"{r['name']:22s} {r['count']:6d} {r['total_s']:9.4f} "
                     f"{r['p50_ms']:9.3f} {r['max_ms']:9.3f}")
    f = red.get("longest_fence")
    if f:
        lines.append(
            f"longest of {f['fences']} fences: {f['name']} it={f['it']} "
            f"{f['wall_s']:.4f}s against a median of {f['median_s']:.4f}s; "
            f"in it the main thread waited {f['feed_wait_not_ready_s']:.4f}s "
            "for a feed that was not ready; the feed threads were inside "
            + (", ".join(f"{k} {v:.4f}s" for k, v in
                         f["feed_threads_inside_s"].items()) or "no span"))
    c = red.get("longest_chunk")
    if c:
        lines.append(f"longest fence-to-fence chunk: it={c['it']} "
                     f"{c['wall_s']:.4f}s against a median of "
                     f"{c['median_s']:.4f}s")
    if trace:
        lines.append(
            f"clock: xplane - record = {trace['offset_ns']:.0f} ns (median "
            f"of {trace['pairs']} step spans, spread "
            f"{trace['offset_spread_ns']:.0f} ns)")
    if red["dropped"]:
        lines.append(f"the record's bound dropped {red['dropped']} spans")
    return "\n".join(lines)


# ------------------------------------------------------------ the run's own
def flight_metrics(summary) -> dict | None:
    """``{metric name: value}`` for this run: the summary's own where it
    carries a record (tests), else taken once per process, anchored on
    the newest xplane, printed, and written beside it."""
    global _cached
    if not summary:
        return None
    if "flight" in summary:
        red = reduce(summary["flight"])
        return red["metrics"] if red else None
    if _cached is _MISSING:
        from benchmarks.metrics._program_spans import newest_xplane

        t = time.perf_counter()
        _cached = None
        rec = take()
        if rec is not None:
            path = newest_xplane()
            rec["trace"] = anchor(rec, path) if path else None
            red = reduce(rec)
            if red:
                _cached = red["metrics"]
                print(table(red, rec["trace"]), file=sys.stderr, flush=True)
            if path:
                out = os.path.join(os.path.dirname(path), "flight.json")
                with open(out, "w") as f:
                    json.dump(rec, f, default=float)
            print(f"record of {len(rec['spans'])} spans read, reduced and "
                  f"written in {time.perf_counter() - t:.3f}s",
                  file=sys.stderr, flush=True)
    return _cached


def metric(summary, name: str) -> float | None:
    return (flight_metrics(summary) or {}).get(name)
