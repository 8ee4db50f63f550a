"""Profiler-trace aggregation (tpunet time --trace plumbing).

Device-op lanes only exist on accelerator backends, so the parsing and
layer-attribution logic is pinned here against a synthetic Chrome trace
shaped like a real TPU export (process_name metadata + X events with
L.<layer> scopes in long_name); the live path is exercised for its
graceful no-device-lane fallback on CPU.
"""

import gzip
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from sparknet_tpu.utils.op_profile import (
    _device_events,
    aggregate_by_layer,
    layer_time_table,
)


def _write_trace(tmp_path, events, pname="/device:TPU:0"):
    d = tmp_path / "plugins" / "profile" / "run1"
    os.makedirs(d, exist_ok=True)
    raw = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": pname}},
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
    ]
    for name, scope, dur, pid in events:
        raw.append({
            "ph": "X", "pid": pid, "tid": 0, "ts": 0, "dur": dur,
            "name": name, "args": {"long_name": scope},
        })
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": raw}, f)
    return str(tmp_path)


def test_device_events_filters_host_lane(tmp_path):
    root = _write_trace(tmp_path, [
        ("fusion.1", "jit(step)/L.conv1/conv", 100.0, 7),
        ("python_call", "", 999.0, 1),  # host lane: excluded
    ])
    events = _device_events(root)
    assert len(events) == 1
    assert events[0][1] == 100.0


def test_aggregate_by_layer_scopes_and_other(tmp_path):
    root = _write_trace(tmp_path, [
        ("fusion.1", "jit(step)/L.conv1/conv_general", 100.0, 7),
        ("fusion.2", "jit(step)/L.conv1/add", 50.0, 7),
        ("fusion.3", "jit(step)/L.ip1/dot_general", 30.0, 7),
        ("copy.4", "", 20.0, 7),  # unscoped: optimizer/copies
    ])
    per_layer, total = aggregate_by_layer(_device_events(root), iters=2)
    assert per_layer["conv1"] == 75.0  # (100+50)/2
    assert per_layer["ip1"] == 15.0
    assert per_layer["(other)"] == 10.0
    assert total == 100.0


def test_aggregate_googlenet_style_names(tmp_path):
    # compiler flattens '/' in layer names to '.' before named_scope
    root = _write_trace(tmp_path, [
        ("fusion.9", "jit(x)/L.inception_3a.1x1/conv", 40.0, 7),
    ])
    per_layer, _ = aggregate_by_layer(_device_events(root), iters=1)
    assert per_layer == {"inception_3a.1x1": 40.0}


def test_aggregate_fwd_bwd_split(tmp_path):
    """Backward ops carry transpose(jvp(L.<name>)) in the HLO scope path
    (verified against jax lowering); forward ops plain or jvp-wrapped —
    the caffe time Forward/Backward per-layer split (caffe.cpp:290-380)."""
    from sparknet_tpu.utils.op_profile import aggregate_fwd_bwd

    root = _write_trace(tmp_path, [
        ("fusion.1", "jit(step)/jvp(L.conv1)/conv_general", 100.0, 7),
        ("fusion.2", "jit(step)/transpose(jvp(L.conv1))/conv_general",
         200.0, 7),
        ("fusion.3", "jit(step)/L.ip1/dot_general", 30.0, 7),  # eval-style
        ("copy.4", "", 20.0, 7),
    ])
    split = aggregate_fwd_bwd(_device_events(root), iters=2)
    assert split["conv1"] == (50.0, 100.0)
    assert split["ip1"] == (15.0, 0.0)
    assert split["(other)"] == (10.0, 0.0)


def test_table_from_trace_fwd_bwd_rows(tmp_path):
    from sparknet_tpu.utils.op_profile import table_from_trace

    root = _write_trace(tmp_path, [
        ("f1", "jit(s)/jvp(L.conv1)/conv", 40.0, 7),
        ("f2", "jit(s)/transpose(jvp(L.conv1))/conv", 80.0, 7),
    ])
    prof = {"events": _device_events(root), "wall_step_us": 130.0,
            "trace_dir": str(tmp_path)}
    t = table_from_trace(prof, ["conv1"], iters=1)
    assert t["rows"] == [("conv1", 120.0)]
    assert t["rows_fwd_bwd"] == [("conv1", 40.0, 80.0)]


def test_layer_time_table_cpu_fallback():
    """On CPU the trace has no device lanes: empty rows, measured wall
    time still reported, nothing raises."""
    import jax

    f = jax.jit(lambda x: (x @ x).sum())
    x = np.eye(64, dtype=np.float32)
    table = layer_time_table(f, (x,), ["a", "b"], iters=2)
    assert table["wall_us_per_step"] > 0
    assert table["rows"] == [] or all(
        isinstance(n, str) for n, _ in table["rows"]
    )


def test_trace_report_renders_rows(tmp_path):
    """tools/trace_report.py renders full and partial artifacts (partial =
    the failed-mid-trace case the staged writes exist for)."""
    import json
    import subprocess
    import sys

    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_report.py")
    art = {
        "stage": "final", "argv_solver": "zoo:alexnet", "batch": 256,
        "dtype": "bf16", "utc": "t", "device_kind": "v5e",
        "wall_ms_per_step": 20.0, "img_per_sec": 12800.0,
        "gflop_per_step": 986.0, "hbm_gb_per_step": 12.3,
        "mfu": 0.25, "mfu_vs_peak": "v5e_bf16",
        "rows": [["conv1", 2000.0], ["norm1", 5000.0], ["(other)", 1000.0]],
        "rows_fwd_bwd": {"conv1": [800.0, 1200.0], "norm1": [2000.0, 3000.0]},
        "device_us_per_step": 8000.0, "attributed_frac": 0.875,
    }
    # the live table_from_trace payload serializes triples
    # [name, fwd, bwd] (see test_table_from_trace_fwd_bwd_rows); the
    # report also accepts the dict / (name, (f, b)) shapes
    triples = [[k, f, b] for k, (f, b) in art["rows_fwd_bwd"].items()]
    for fb in (art["rows_fwd_bwd"], triples,
               [[k, [f, b]] for k, f, b in triples]):
        art["rows_fwd_bwd"] = fb
        p = tmp_path / "a.json"
        p.write_text(json.dumps(art))
        out = subprocess.run(
            [sys.executable, tool, str(p)],
            capture_output=True, text=True, check=True).stdout
        assert "| norm1 | 2.000 | 3.000 | 5.000 | 62.5% |" in out
        assert "TOTAL (device)" in out and "87.5%" in out

    partial = {"stage": "wall_untraced", "argv_solver": "zoo:alexnet",
               "batch": 256, "dtype": "bf16",
               "wall_ms_per_step_untraced": 20.5,
               "img_per_sec_untraced": 12500.0,
               "gflop_per_step": 986.0, "hbm_gb_per_step": 12.3,
               "fence_protocol": "loss-value+threaded-args"}
    p = tmp_path / "b.json"
    p.write_text(json.dumps(partial))
    out = subprocess.run(
        [sys.executable, tool, str(p)],
        capture_output=True, text=True, check=True).stdout
    assert "No per-layer rows banked" in out and "20.500 ms" in out

    # an UNSTAMPED untraced wall is refused with an explanatory note,
    # not silently rendered — an unfenced wall times the enqueue
    del partial["fence_protocol"]
    p = tmp_path / "c.json"
    p.write_text(json.dumps(partial))
    out = subprocess.run(
        [sys.executable, tool, str(p)],
        capture_output=True, text=True, check=True).stdout
    assert "20.500 ms" not in out
    assert "no `fence_protocol` stamp" in out


def _write_tpu_style_trace(tmp_path, lanes, ops):
    """TPU xprof export shape: ONE device pid with stacked named lanes
    (Steps / XLA Modules / XLA Ops), scopes in args.tf_op, args.long_name
    carrying raw HLO text (no scopes)."""
    d = tmp_path / "plugins" / "profile" / "run1"
    os.makedirs(d, exist_ok=True)
    raw = [{"ph": "M", "name": "process_name", "pid": 3,
            "args": {"name": "/device:TPU:0"}}]
    for tid, lname in lanes.items():
        raw.append({"ph": "M", "name": "thread_name", "pid": 3,
                    "tid": tid, "args": {"name": lname}})
    for tid, name, tf_op, dur in ops:
        raw.append({
            "ph": "X", "pid": 3, "tid": tid, "ts": 0, "dur": dur,
            "name": name,
            "args": {"tf_op": tf_op,
                     "long_name": "%fusion.1 = f32[8,8]{1,0:T(8,128)}"},
        })
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": raw}, f)
    return str(tmp_path)


def test_tpu_stacked_lanes_counted_once(tmp_path):
    """The stacked-lane regression: Steps + XLA Modules + XLA Ops lanes each
    carry the full step interval; only the op lane may be summed (the
    artifact shipped 80.5 ms 'device total' for a 26.8 ms step), and the
    L.<layer> scope lives in tf_op, not long_name (raw HLO on TPU)."""
    root = _write_tpu_style_trace(
        tmp_path,
        lanes={1: "Steps", 2: "XLA Modules", 3: "XLA Ops",
               4: "Async XLA Ops"},
        ops=[
            (1, "0", "", 1000.0),               # step marker
            (2, "jit_step(123)", "", 1000.0),   # module marker
            (3, "fusion.7", "jit(step)/jvp(L.conv1)/conv_general_dilated:", 600.0),
            (3, "fusion.9", "jit(step)/transpose(jvp(L.conv1))/mul:", 300.0),
            (3, "copy.1", "", 100.0),
            (4, "async-copy", "", 500.0),       # async lane: excluded
        ])
    per_layer, total = aggregate_by_layer(_device_events(root), iters=1)
    assert total == 1000.0  # op lane only — no triple count
    assert per_layer["conv1"] == 900.0
    assert per_layer["(other)"] == 100.0


def test_named_lanes_without_ops_name_pick_busiest(tmp_path):
    """An export whose op lane is named unrecognizably must not fall
    back to summing every stacked lane: the busiest lane wins."""
    root = _write_tpu_style_trace(
        tmp_path,
        lanes={1: "Steps", 2: "op timeline (v2)"},
        ops=[
            (1, "0", "", 1000.0),
            (2, "fusion.1", "jit(step)/L.fc/dot_general:", 700.0),
            (2, "fusion.2", "", 200.0),
            (2, "fusion.3", "", 100.0),
        ])
    per_layer, total = aggregate_by_layer(_device_events(root), iters=1)
    assert total == 1000.0  # busiest lane (3 events), not Steps + it
    assert per_layer["fc"] == 700.0


def test_gpu_style_stream_lanes_all_counted(tmp_path):
    """Concurrent named stream lanes under one device pid are DISTINCT
    real work (the GPU export shape), not stacked views — every stream
    must be summed, with only aggregate lanes (Steps/Modules) excluded."""
    root = _write_tpu_style_trace(
        tmp_path,
        lanes={1: "Steps", 14: "Stream #14(compute)",
               15: "Stream #15(memcpy)"},
        ops=[
            (1, "0", "", 1000.0),
            (14, "kern.1", "jit(step)/L.conv1/conv:", 600.0),
            (15, "memcpy.1", "", 250.0),
        ])
    per_layer, total = aggregate_by_layer(_device_events(root), iters=1)
    assert total == 850.0  # both streams, no Steps aggregate
    assert per_layer["conv1"] == 600.0
    assert per_layer["(other)"] == 250.0


def test_reparse_trace_rewrites_artifact(tmp_path):
    """tools/reparse_trace.py: a banked artifact whose per-layer rows
    came out wrong (a parser bug) is re-derived offline from
    its raw trace dir — iters honored, wall fallback to the untraced
    stage, reparse provenance stamped."""
    import json as _json
    import subprocess
    import sys as _sys

    root = _write_tpu_style_trace(
        tmp_path,
        lanes={1: "Steps", 3: "XLA Ops"},
        ops=[
            (1, "0", "", 1000.0),
            (3, "fusion.1", "jit(step)/jvp(L.ip)/dot_general:", 800.0),
            (3, "fusion.2", "", 200.0),
        ])
    art = tmp_path / "trace.artifact.json"
    art.write_text(_json.dumps({
        "stage": "wall_timed",  # truncated run: no final wall written
        "iters": 2,
        "wall_ms_per_step_untraced": 0.6,
        "rows": [["(other)", 3000.0]],  # the triple-counted bad parse
        "attributed_frac": 0.0,
        "trace_dir": root,
    }))
    out = subprocess.run(
        [_sys.executable,
         os.path.join(ROOT, "tools", "reparse_trace.py"), str(art)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    a = _json.loads(art.read_text())
    rows = dict((n, us) for n, us in a["rows"])
    assert rows["ip"] == 400.0          # 800 us over iters=2
    assert a["device_us_per_step"] == 500.0  # op lane only, per step
    assert a["attributed_frac"] == 0.8
    assert a["reparse_note"]
