"""Profiler-trace aggregation: fused-step time attributed to layers.

The reference's ``caffe time`` walks the layer vector calling
Forward/Backward per layer with a cudaEvent timer (ref:
caffe/tools/caffe.cpp:290-380 + util/benchmark.cpp) — honest there,
meaningless on TPU where XLA fuses the whole step into one program and
per-layer dispatch measures launch overhead, not compute.  The TPU-native
equivalent: run the REAL fused step under ``jax.profiler``, parse the
exported trace, and attribute device-op time back to prototxt layers via
the ``L.<name>`` scopes the graph compiler stamps into HLO metadata
(compiler/graph.py).  The per-layer table then sums to ~the measured
step time instead of to a dispatch artifact.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import tempfile
from collections import defaultdict

_SCOPE = re.compile(r"\bL\.([\w.\-]+)")


def trace_step(step_fn, args, iters: int, thread_fn=None) -> dict:
    """One traced segment: run ``step_fn(*args)`` ``iters`` times under
    the profiler.  Returns {"events", "wall_step_us", "trace_dir"}.

    The caller is responsible for having warmed the function up (compile
    time must not pollute the trace).  Kept small so callers can run a
    SHORT segment first and write its parsed result out before a
    longer one.

    ``thread_fn(args, out) -> args``: feeds each call's output back into
    the next call's arguments, the way training threads its state.
    Solver-step callers pass ``lambda a, o: (o[0], o[1]) + a[2:]`` to
    thread (variables, slots) — required when the step donates them.
    """
    import time

    import jax

    from sparknet_tpu.common import value_fence

    tmp = tempfile.mkdtemp(prefix="tpunet_time_")
    jax.profiler.start_trace(tmp)
    try:
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = step_fn(*args)
            if thread_fn is not None:
                args = thread_fn(args, out)
        value_fence(out)
        wall = (time.perf_counter() - t0) / iters
    finally:
        jax.profiler.stop_trace()
    return {
        "events": _device_events(tmp),
        "wall_step_us": wall * 1e6,
        "trace_dir": tmp,
        # threaded end state, so a FOLLOW-UP traced segment can seed its
        # first dispatch from here instead of repeating this one's
        "final_args": args,
    }


def profile_step(step_fn, args, iters: int = 5, thread_fn=None) -> dict:
    """Warm up once (outside the trace), then one traced segment.  With
    ``thread_fn`` (see ``trace_step``) the warm call's output seeds the
    traced segment's args."""
    from sparknet_tpu.common import value_fence

    out = step_fn(*args)
    value_fence(out)
    if thread_fn is not None:
        args = thread_fn(args, out)
    return trace_step(step_fn, args, iters, thread_fn=thread_fn)


def _device_events(log_dir: str, full: bool = False) -> list:
    """(op name, duration µs) complete-events from device lanes of every
    exported Chrome trace under ``log_dir``.

    ``full=True`` returns the RAW event dicts (same lane selection) so
    cost-payload consumers (tools/traffic_report.py) share this lane
    policy instead of re-implementing it — the stacked-lane rules here
    carry the triple-counting fix and must stay single-sourced.
    """
    events: list = []
    for path in glob.glob(
        os.path.join(log_dir, "**", "*.trace.json.gz"), recursive=True
    ):
        with gzip.open(path, "rt") as f:
            trace = json.load(f)
        raw = trace.get("traceEvents", [])
        # pid -> process name; device lanes carry the XLA op timeline
        pnames = {
            e.get("pid"): e.get("args", {}).get("name", "")
            for e in raw
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        device_pids = {
            pid
            for pid, name in pnames.items()
            if any(tag in name for tag in ("/device:", "TPU", "GPU", "XLA"))
            and "CUPTI" not in name
        }
        # A device pid exports several STACKED lanes for the same wall
        # interval — on TPU: Steps / XLA Modules / XLA Ops (summing
        # them triple-counts the step).  Only the op-level lane carries per-op rows, so
        # when thread names are present keep just lanes that look
        # op-level; an unnamed-lane trace (CPU chrome export) passes
        # through unfiltered.
        named_lanes: dict = {}
        for e in raw:
            if (e.get("ph") == "M" and e.get("name") == "thread_name"
                    and e.get("pid") in device_pids):
                named_lanes.setdefault(e["pid"], {})[e.get("tid")] = (
                    e.get("args", {}).get("name", "").lower())
        lane_events: dict = {}
        for e in raw:
            if e.get("ph") == "X" and e.get("pid") in named_lanes:
                key = (e["pid"], e.get("tid"))
                lane_events[key] = lane_events.get(key, 0) + 1
        # Lane policy per named device pid.  TPU xprof exports STACK
        # several views of the same wall interval (Steps / XLA Modules /
        # XLA Ops / overlays) — summing them triple-counts the step, so
        # exactly ONE lane may survive: the XLA-Ops-style lane if named,
        # else the busiest non-aggregate lane.  GPU-style exports
        # instead put CONCURRENT streams under one pid — distinct real
        # work, so dropping to one lane would undercount; there the
        # aggregate lanes are excluded by name and every stream lane
        # survives.
        AGG = ("step", "module", "overlay")
        op_tids = set()
        for pid, lanes in named_lanes.items():
            def is_ops(lname):
                return "ops" in lname and "async" not in lname
            ops_lanes = [t for t, ln in lanes.items() if is_ops(ln)]
            if ops_lanes:  # stacked-views export: ONE op lane only
                best = min(
                    ops_lanes,
                    key=lambda t: (0 if "xla" in lanes[t] else 1,
                                   -lane_events.get((pid, t), 0)))
                op_tids.add((pid, best))
                continue
            streams = [t for t, ln in lanes.items()
                       if not any(a in ln for a in AGG)
                       and "async" not in ln]
            if streams:  # stream-per-lane export: keep them all
                op_tids.update((pid, t) for t in streams)
            else:  # only aggregates named: busiest lane, counted once
                best = min(lanes,
                           key=lambda t: -lane_events.get((pid, t), 0))
                op_tids.add((pid, best))
        named_device_pids = set(named_lanes)
        for e in raw:
            if e.get("ph") != "X" or e.get("pid") not in device_pids:
                continue
            if (e["pid"] in named_device_pids
                    and (e["pid"], e.get("tid")) not in op_tids):
                continue
            dur = e.get("dur")
            if not dur:
                continue
            if full:
                events.append(e)
                continue
            name = e.get("name", "")
            args = e.get("args", {})
            # search BOTH metadata fields: on TPU ``long_name`` is raw
            # HLO text (no scope) while ``tf_op`` carries the op_name
            # path with the L.<layer> scopes; CPU exports vary
            scope = f"{args.get('tf_op', '')}|{args.get('long_name', '')}"
            events.append((f"{name}|{scope}", float(dur)))
    return events


def aggregate_by_layer(
    events: list[tuple[str, float]], iters: int
) -> tuple[dict[str, float], float]:
    """Per-layer µs/step from scoped events; unattributed time under
    '(other)'.  Returns (layer -> us, total device us/step)."""
    per_layer: dict[str, float] = defaultdict(float)
    total = 0.0
    for name, dur in events:
        total += dur
        m = _SCOPE.search(name)
        per_layer[m.group(1) if m else "(other)"] += dur
    return (
        {k: v / iters for k, v in per_layer.items()},
        total / iters,
    )


def aggregate_fwd_bwd(
    events: list[tuple[str, float]], iters: int
) -> dict[str, tuple[float, float]]:
    """Per-layer (forward µs, backward µs) per step — the reference's
    ``caffe time`` table splits each layer's Forward and Backward walls
    (ref: caffe/tools/caffe.cpp:290-380).  Under jax autodiff the
    backward ops carry ``transpose(jvp(L.<name>))`` in their HLO scope
    path and forward ops plain ``L.<name>``/``jvp(L.<name>)``, so the
    trace classifies mechanically; fused ops spanning both count as
    backward when any transpose marker is present."""
    split: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0])
    for name, dur in events:
        m = _SCOPE.search(name)
        layer = m.group(1) if m else "(other)"
        is_bwd = "transpose(jvp(" in name
        split[layer][1 if is_bwd else 0] += dur
    return {k: (f / iters, b / iters) for k, (f, b) in split.items()}


def layer_time_table(step_fn, args, layer_names, iters: int = 5,
                     thread_fn=None) -> dict:
    """The ``tpunet time --trace`` payload: per-layer device µs/step (in
    net order, then the rest), total device time, and wall step time.
    ``thread_fn`` as in ``trace_step``."""
    prof = profile_step(step_fn, args, iters, thread_fn=thread_fn)
    return table_from_trace(prof, layer_names, iters)


def table_from_trace(prof: dict, layer_names, iters: int) -> dict:
    """Aggregate one trace_step/profile_step result into the per-layer
    payload (split out so staged callers can table each segment as soon
    as it lands, before risking the next one)."""
    fwd_bwd = aggregate_fwd_bwd(prof["events"], iters)
    per_layer, device_total = aggregate_by_layer(prof["events"], iters)
    ordered: list[tuple[str, float]] = []
    for name in layer_names:
        key = name.replace("/", ".")
        if key in per_layer:
            ordered.append((name, per_layer.pop(key)))
    ordered.extend(sorted(per_layer.items(), key=lambda kv: -kv[1]))
    return {
        "rows": ordered,
        # (layer, fwd us, bwd us) in the same order — the caffe time
        # Forward/Backward split (keyed to ordered rows' names)
        "rows_fwd_bwd": [
            (name, *fwd_bwd.get(name.replace("/", "."),
                                fwd_bwd.get(name, (0.0, 0.0))))
            for name, _ in ordered
        ],
        "device_us_per_step": device_total,
        "wall_us_per_step": prof["wall_step_us"],
        "trace_dir": prof["trace_dir"],
        "attributed_frac": (
            sum(us for name, us in ordered if name != "(other)")
            / device_total
            if device_total
            else 0.0
        ),
    }
