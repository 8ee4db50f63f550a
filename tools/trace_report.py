#!/usr/bin/env python
"""Render a ``tpunet time --trace`` artifact into the per-layer markdown
table the reference prints from ``caffe time`` (ref:
caffe/tools/caffe.cpp:290-380 — per-layer Forward/Backward walls plus
totals).  Reads the staged artifact JSON (any stage: a partial artifact
from a failed run still renders whatever stages landed) and writes
markdown to stdout or --out.

    python tools/trace_report.py tpunet_trace.json
"""

from __future__ import annotations

import argparse
import json


def render(a: dict) -> str:
    lines = []
    name = a.get("argv_solver", "?")
    lines.append(f"# Per-layer device time — `{name}` "
                 f"(batch {a.get('batch', '?')}, {a.get('dtype', '?')})")
    lines.append("")
    lines.append(f"Stage banked: **{a.get('stage', '?')}** "
                 f"({a.get('utc', '?')}, {a.get('device_kind') or a.get('platform', '?')}).")
    wall = a.get("wall_ms_per_step")
    mfu = a.get("mfu")
    img_s = a.get("img_per_sec")
    # Untraced-wall fallback is accepted ONLY from artifacts stamped with
    # a fence protocol: a wall whose timing loop fenced nothing is the
    # enqueue time, not the step.
    refused_untraced = False
    if not wall and a.get("wall_ms_per_step_untraced") is not None:
        if a.get("fence_protocol"):
            wall = a.get("wall_ms_per_step_untraced")
            mfu = a.get("mfu_untraced")
            img_s = a.get("img_per_sec_untraced")
        else:
            refused_untraced = True
    if refused_untraced:
        lines.append("")
        lines.append("**Note:** this artifact carries an untraced wall "
                     "but no `fence_protocol` stamp — the value is "
                     "withheld: an unfenced wall times the enqueue, not "
                     "the step.")
    if wall:
        lines.append(
            f"Step: **{wall:.3f} ms** "
            f"({img_s or 0:,.0f} img/s), "
            f"{a.get('gflop_per_step', 0):.0f} GFLOP, "
            f"{a.get('hbm_gb_per_step', 0):.2f} GB HBM"
            + (f", MFU {mfu:.3f} vs {a.get('mfu_vs_peak')}" if mfu else "") + ".")
    lines.append("")

    rows = a.get("rows") or a.get("rows_short") or []
    # table_from_trace emits (name, fwd_us, bwd_us) triples; accept the
    # {name: (fwd, bwd)} / (name, (fwd, bwd)) shapes too for hand-built
    # artifacts
    raw_fb = a.get("rows_fwd_bwd") or {}
    if isinstance(raw_fb, dict):
        fb = raw_fb
    else:
        fb = {r[0]: (r[1] if len(r) == 2 else r[1:]) for r in raw_fb}
    frac = a.get("attributed_frac") or a.get("attributed_frac_short")
    dev_total = a.get("device_us_per_step") or a.get("device_us_per_step_short")
    if not rows:
        lines.append("_No per-layer rows banked (trace stage did not land; "
                     "wall/MFU stages above are still evidence)._")
        return "\n".join(lines) + "\n"

    lines.append("| layer | fwd ms | bwd ms | total ms | % of device step |")
    lines.append("|---|---|---|---|---|")
    for layer, us in rows:
        f, b = fb.get(layer, (None, None))
        pct = 100.0 * us / dev_total if dev_total else 0.0
        fm = f"{f / 1e3:.3f}" if f is not None else "—"
        bm = f"{b / 1e3:.3f}" if b is not None else "—"
        lines.append(f"| {layer} | {fm} | {bm} | {us / 1e3:.3f} | {pct:.1f}% |")
    if dev_total:
        lines.append(f"| **TOTAL (device)** | | | **{dev_total / 1e3:.3f}** | 100% |")
    lines.append("")
    if frac is not None:
        lines.append(f"Attributed to named layer scopes: {100 * frac:.1f}% "
                     "(rest is optimizer/data movement/unscoped fusions "
                     "under `(other)`).")
    return "\n".join(lines) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("artifact")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    with open(args.artifact) as f:
        text = render(json.load(f))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out}")
    else:
        print(text, end="")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
