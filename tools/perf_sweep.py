"""TPU perf sweep: run the headline bench across dtype/batch variants.

One command to characterize AlexNet training throughput on the real chip
when hardware is available (the bench proper prints only the single
headline JSON line; this sweep is the tuning tool behind it).

    python tools/perf_sweep.py            # full sweep
    python tools/perf_sweep.py --quick    # bf16/f32 at batch 256 only

Each variant runs in a subprocess so trace-time config can't leak
between configurations; the parent stays off jax (the chip belongs to
one process at a time), and the variants share the persistent compile
cache bench.py places.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_variant(dtype: str, batch: int, timeout: int = 900,
                model: str = "") -> dict:
    # this parent never imports jax, so each child gets the chip to
    # itself, one after the other
    env = dict(os.environ, SPARKNET_BENCH_DTYPE=dtype,
               SPARKNET_BENCH_BATCH=str(batch))
    if model:
        env["SPARKNET_BENCH_MODEL"] = model
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return {"dtype": dtype, "batch": batch, "error": "timeout"}
    if out.returncode != 0:
        tail = (out.stderr or out.stdout).strip().splitlines()
        return {
            "dtype": dtype, "batch": batch,
            "error": tail[-1][:200] if tail else f"exit {out.returncode}",
        }
    lines = out.stdout.strip().splitlines()
    try:
        rec = json.loads(lines[-1]) if lines else {}
    except json.JSONDecodeError:
        rec = {}
    if "value" not in rec:
        return {"dtype": dtype, "batch": batch,
                "error": f"no JSON result in output: {lines[-1][:200] if lines else ''}"}
    rec.update({"dtype": dtype, "batch": batch})
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--model", default="",
                    help="alexnet (default) | caffenet | googlenet | "
                    "resnet50 | vgg16")
    args = ap.parse_args()

    variants = (
        [("bf16", 256), ("f32", 256)]
        if args.quick
        else [("bf16", 128), ("bf16", 256), ("bf16", 512),
              ("f32", 128), ("f32", 256)]
    )
    results = []
    for dtype, batch in variants:
        rec = run_variant(dtype, batch, model=args.model)
        results.append(rec)
        print(json.dumps(rec), flush=True)

    ok = [r for r in results if "value" in r]
    if ok:
        best = max(ok, key=lambda r: r["value"])
        print(json.dumps({"best": best}), flush=True)


if __name__ == "__main__":
    main()
