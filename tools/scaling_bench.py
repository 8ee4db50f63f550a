"""Sync-SGD weak-scaling efficiency across a device mesh.

BASELINE.json's north-star metric has two axes: images/sec/chip (bench.py)
and **1→N-worker sync-SGD scaling efficiency** — the axis the reference
measured as its Spark cluster speedups (SparkNet paper §5; the engine's
own multi-GPU numbers: ~1.8x on 2 / ~3.5x on 4 GPUs weak-scaling,
caffe/docs/multigpu.md:26).  This tool measures ours: the tau=1 GSPMD
data-parallel step (gradient psum over ICI inserted by XLA) at per-chip
batch B on 1 device and on N devices, reporting

    efficiency = (img_s_N / N) / img_s_1

Weak scaling: the global batch grows with N (B per chip), matching the
reference's multigpu.md protocol ("effective batch size scales with the
number of GPUs").

    python tools/scaling_bench.py                    # all visible devices
    python tools/scaling_bench.py --devices 4
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/scaling_bench.py --allow-cpu    # plumbing check

Like bench.py: every record names its device, a run that finds no
accelerator exits 2 with no record, and a pinned-CPU run is plumbing only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(n_devices: int, batch_per_device: int, iters: int, warmup: int,
            model: str, crop: int, dtype_name: str) -> float:
    """img/s of the jitted train step sharded over the first n devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import bench

    global_batch = batch_per_device * n_devices
    step, variables, slots, key, feeds = bench._build_step(
        global_batch, model, crop, dtype_name)

    mesh = Mesh(np.asarray(jax.devices()[:n_devices]), ("data",))
    data_sh = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    # params/opt state replicated, batch sharded: XLA partitions the step
    # and inserts the gradient all-reduce over the mesh (the P2PSync role)
    variables = jax.device_put(variables, repl)
    slots = jax.device_put(slots, repl)
    feeds = {k: jax.device_put(v, data_sh) for k, v in feeds.items()}

    for i in range(warmup):
        variables, slots, loss = step(variables, slots, i, feeds, key)
    float(loss)
    t0 = time.perf_counter()
    for i in range(warmup, warmup + iters):
        variables, slots, loss = step(variables, slots, i, feeds, key)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final), final
    return global_batch * iters / dt


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="N for the scaled leg (default: all visible)")
    ap.add_argument("--batch-per-device", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    from sparknet_tpu.models import BENCH_CROPS

    ap.add_argument("--model", default="alexnet",
                    choices=sorted(BENCH_CROPS))
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on a (virtual) CPU mesh — plumbing only")
    args = ap.parse_args()

    import jax

    from sparknet_tpu.common import require_chip

    stamp = require_chip("scaling_bench")  # no chip, no pin: exit 2
    on_accel = stamp["platform"] != "cpu"
    if not on_accel and not args.allow_cpu:
        print("scaling_bench: CPU backend; pass --allow-cpu for a "
              "plumbing-only run", file=sys.stderr)
        return 2

    n = args.devices or len(jax.devices())
    n = min(n, len(jax.devices()))
    batch = args.batch_per_device if on_accel else 8
    iters = args.iters if on_accel else 2
    warmup = 3 if on_accel else 1
    crop = BENCH_CROPS[args.model]

    img_s_1 = measure(1, batch, iters, warmup, args.model, crop, args.dtype)
    rec = {
        "metric": "sync_dp_scaling_efficiency",
        "model": args.model,
        "dtype": args.dtype,
        "batch_per_device": batch,
        "img_s_1": round(img_s_1, 1),
        "measured": on_accel,
        **stamp,
    }
    if n > 1:
        img_s_n = measure(n, batch, iters, warmup, args.model, crop, args.dtype)
        rec.update({
            "devices": n,
            "img_s_n": round(img_s_n, 1),
            "speedup": round(img_s_n / img_s_1, 3),
            "value": round((img_s_n / n) / img_s_1, 4),
            "reference_weak_scaling": "~1.8x@2 / ~3.5x@4 GPUs "
            "(caffe/docs/multigpu.md:26)",
        })
    else:
        rec.update({"devices": 1, "value": 1.0,
                    "note": "single device visible: efficiency trivially 1; "
                    "run on a pod (or a virtual CPU mesh) for the N-leg"})
    if not on_accel:
        rec["plumbing_only_cpu"] = True
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
