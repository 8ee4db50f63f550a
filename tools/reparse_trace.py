#!/usr/bin/env python
"""Re-attribute a ``tpunet time --trace`` artifact from its raw trace dirs.

The staged artifact keeps ``trace_dir``/``trace_dir_short`` pointing at
the exported profiler data, precisely so attribution can be re-derived
OFFLINE after a parser fix — chip time is budgeted, raw traces are
kept.  (An early parser preferred ``long_name`` — raw HLO text on TPU,
no scopes — and summed the stacked Steps/Modules/Ops lanes, so tables
came out 0%-attributed and triple-counted.  op_profile.py reads
``tf_op`` and keeps only the op lane; this tool backfills artifacts
captured before such a fix.)

    python tools/reparse_trace.py docs/evidence_r4/trace_alexnet_b256.artifact.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparknet_tpu.utils.op_profile import _device_events, table_from_trace  # noqa: E402


def reparse(path: str) -> int:
    with open(path) as f:
        a = json.load(f)
    touched = []
    for dir_key, iters_guess, prefix in (
        ("trace_dir_short", 1, "_short"),
        ("trace_dir", None, ""),
    ):
        tdir = a.get(dir_key)
        if not tdir or not os.path.isdir(tdir):
            continue
        if iters_guess:
            iters = iters_guess
        elif "iters" in a:
            iters = int(a["iters"])
        else:
            # pre-fix artifacts never banked iters; 10 is cmd_time's
            # default, but say so rather than silently scaling
            iters = 10
            a["reparse_iters_assumed"] = 10
        events = _device_events(tdir)
        if not events:
            continue
        wall_ms = a.get("wall_ms_per_step") or a.get(
            "wall_ms_per_step_untraced") or 0.0
        prof = {"events": events,
                "wall_step_us": wall_ms * 1e3,
                "trace_dir": tdir}
        # layer order is cosmetic here; pass the names we already banked
        names = [r[0] for r in (a.get("rows") or []) if r[0] != "(other)"]
        t = table_from_trace(prof, names, iters=iters)
        if prefix:
            a["rows_short"] = [(n, round(us, 1)) for n, us in t["rows"]]
            a["device_us_per_step_short"] = round(t["device_us_per_step"], 1)
            a["attributed_frac_short"] = round(t["attributed_frac"], 3)
        else:
            a["rows"] = [(n, round(us, 1)) for n, us in t["rows"]]
            a["rows_fwd_bwd"] = [
                (n, round(f, 1), round(b, 1)) for n, f, b in t["rows_fwd_bwd"]]
            a["device_us_per_step"] = round(t["device_us_per_step"], 1)
            a["attributed_frac"] = round(t["attributed_frac"], 3)
        touched.append(dir_key)
    if not touched:
        print(f"{path}: no readable trace dirs (raw /tmp data gone?)",
              file=sys.stderr)
        return 1
    a["reparsed_utc"] = time.strftime("%Y-%m-%d %H:%M:%SZ", time.gmtime())
    a["reparse_note"] = ("per-layer rows re-derived offline from the raw "
                        "trace dirs by tools/reparse_trace.py after the "
                        "op_profile lane/tf_op parser fix")
    with open(path + ".tmp", "w") as f:
        json.dump(a, f, indent=1, default=str)
    os.replace(path + ".tmp", path)
    print(f"{path}: reparsed {touched}, attributed "
          f"{a.get('attributed_frac', 0) * 100:.0f}%")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("artifacts", nargs="+")
    args = ap.parse_args()
    rc = 0
    for p in args.artifacts:
        rc |= reparse(p)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
