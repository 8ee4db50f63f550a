#!/usr/bin/env python
"""Bandwidth attribution from a raw ``tpunet time --trace`` dir.

The HLO-byte roofline misestimates physical HBM
traffic in BOTH directions — it misses tile padding and fusion-boundary
materialization (undercount) and it counts on-chip-reuse traffic as if
it hit HBM (overcount; GoogLeNet b128's implied bandwidth lands at
1.11x the HBM peak, which is impossible for HBM-only bytes).  So
``roofline_frac`` measures distance from an idealized same-decomposition
program, not from the hardware.  Hardware traffic counters are not in
the xprof export, but the per-op record is: every device op carries its
cost-analysis ``bytes_accessed``/``model_flops`` AND its measured
``dur`` — so per op we can compute the **implied bandwidth** (HLO bytes
/ measured time) and attribute where a step's residue physically sits
(memory-bound ops below peak BW, compute-bound ops by their op rate).

Output per trace: device-busy/step, HLO GB/step, implied mean GB/s and
its fraction of the 819 GB/s v5e peak (the honest ceiling the step can
approach under the SAME compiler decomposition), plus per-category and
top-op tables.  Zero chip time — runs on any copied trace dir.

    python tools/traffic_report.py /tmp/tpunet_time_82g3ov25 --iters 10
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparknet_tpu.common import V5E_HBM_BYTES_S  # noqa: E402

_SCOPE = re.compile(r"\bL\.([\w.\-]+)")


def device_op_events(log_dir: str) -> list[dict]:
    """Device-op-lane complete events WITH their args payload — the lane
    selection (stacked-views vs stream-per-lane, the triple-count
    fix) is single-sourced in op_profile._device_events."""
    from sparknet_tpu.utils.op_profile import _device_events

    return _device_events(log_dir, full=True)


def summarize(log_dir: str, iters: int, peak_bw: float = V5E_HBM_BYTES_S
              ) -> dict:
    ops = device_op_events(log_dir)
    if not ops:
        return {"error": f"no XLA Ops device events under {log_dir}"}
    per_cat: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0, 0.0])
    per_op: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0, 0.0])
    tot_us = tot_b = tot_f = 0.0
    for e in ops:
        a = e.get("args", {})
        us = float(e.get("dur", 0.0))
        b = float(a.get("bytes_accessed", 0) or 0)
        fl = float(a.get("model_flops", 0) or 0)
        cat = a.get("hlo_category", "?")
        # attribute to the prototxt layer scope when stamped
        m = _SCOPE.search(a.get("tf_op", "") or "")
        opkey = m.group(1) if m else e.get("name", "?").split(".")[0]
        for d, k in ((per_cat, cat), (per_op, opkey)):
            d[k][0] += us
            d[k][1] += b
            d[k][2] += fl
        tot_us += us
        tot_b += b
        tot_f += fl

    def rows(d, n):
        out = []
        for k, (us, b, fl) in sorted(d.items(), key=lambda kv: -kv[1][0])[:n]:
            out.append({
                "key": k,
                "ms_per_step": round(us / iters / 1e3, 3),
                "hlo_gb_per_step": round(b / iters / 1e9, 3),
                "implied_gb_s": round(b / (us / 1e6) / 1e9, 1) if us else None,
                "bw_frac_of_peak": round(b / (us / 1e6) / peak_bw, 3)
                if us else None,
                "gflop_per_step": round(fl / iters / 1e9, 1),
            })
        return out

    return {
        "trace_dir": log_dir,
        "iters": iters,
        "device_busy_ms_per_step": round(tot_us / iters / 1e3, 3),
        "hlo_gb_per_step": round(tot_b / iters / 1e9, 3),
        "gflop_per_step": round(tot_f / iters / 1e9, 1),
        "implied_mean_gb_s": round(tot_b / (tot_us / 1e6) / 1e9, 1),
        "implied_bw_frac_of_peak": round(tot_b / (tot_us / 1e6) / peak_bw, 3),
        "note": ("implied = HLO bytes / measured device time.  The HLO "
                 "byte count estimates physical HBM traffic in NEITHER "
                 "direction: it misses tile padding and fusion-boundary "
                 "materialization (undercount -> implied below peak on "
                 "memory-bound ops) AND counts on-chip-reuse traffic as "
                 "if it hit HBM (overcount -> implied can exceed peak, "
                 "e.g. GoogLeNet b128 at 1.11x).  Sub-peak fractions on "
                 "FLOP-heavy ops are compute-boundness, not optimism."),
        "by_category": rows(per_cat, 12),
        "top_ops": rows(per_op, 15),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--iters", type=int, required=True,
                    help="iterations the traced segment ran (divides totals)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    s = summarize(args.trace_dir, args.iters)
    text = json.dumps(s, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}")
    else:
        print(text)
    return 0 if "error" not in s else 1


if __name__ == "__main__":
    raise SystemExit(main())
