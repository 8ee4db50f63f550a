"""The solo feed's augment alone (``data/device_transform.py``), at the
two CNN solo cells' shapes: [1024, 3, 256, 256] uint8 -> 227
(``alexnet-solo``) and [256, 3, 256, 256] -> 224 (``resnet50-solo``),
mirror, per-channel mean.

    python tools/augment_bench.py [--reps 20]
    JAX_PLATFORMS=cpu python tools/augment_bench.py --rehearse

On the chip (run through the chip tool), milliseconds a batch, each
alone, of: ``eager``, ``DeviceAugment.__call__`` dispatched op by op
(what ``device_fn`` ran before PR 39 and still runs where it falls
back); ``aug4``, the trainer adapter's jitted ``_augment``; ``one_pass``,
``DeviceAugment.device_fn`` as the prefetcher calls it.  Every row says
how far its result lies from ``_augment``'s under the same key
(``max_abs_diff``: 0) and ``one_pass`` whether the batch took the kernel
(``fused``: 1 on a TPU).  One JSON line a row, each naming its device,
also appended to ``chiprun_out/augment_bench.jsonl``; exit code 1 if a
result differs or the one pass was not taken on a TPU; without a chip it
exits 2 and prints no number.  ``--rehearse`` walks the script on a
pinned CPU at 128 images (``device_fn`` falls back there: no TPU).

The crop's other forms were timed here once and are gone from the tree;
their times are in PERF.md section 6 (PR 39).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MEAN = (104.0, 117.0, 123.0)
SHAPES = (((1024, 3, 256, 256), 227), ((256, 3, 256, 256), 224))
IT = 3  # the batch index every row draws its crops and mirrors from


def timed(fn, x, reps):
    import jax
    jax.block_until_ready(fn(x))
    jax.block_until_ready(fn(x))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(x)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t) / reps * 1e3, 3)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true",
                    help="128 images a batch: a CPU walk of the script")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.common import require_chip
    from sparknet_tpu.data.device_transform import DeviceAugment
    from sparknet_tpu.data.transform import TransformConfig

    stamp = require_chip("augment_bench")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    wrong = 0
    with open(os.path.join(out_dir, "augment_bench.jsonl"), "a") as sink:
        for shape, crop in SHAPES:
            if a.rehearse:
                shape = (128,) + shape[1:]
            aug = DeviceAugment(TransformConfig(
                crop_size=crop, mirror=True, mean_value=MEAN), layout="nchw")
            x = jax.device_put(np.random.RandomState(0).randint(
                0, 256, shape).astype(np.uint8))
            key = jax.random.fold_in(jax.random.key(1234), IT)
            want = jax.jit(aug._augment, static_argnums=2)(x, key, True)
            solo, trainer = aug.device_fn(), aug.trainer_device_fn()
            rows = {"eager": lambda x: aug(x, key),
                    "aug4": lambda x: trainer({"data": x}, IT)["data"],
                    "one_pass": lambda x: solo({"data": x}, IT)["data"]}
            for name, fn in rows.items():
                row = {"what": name, "shape": list(shape), "crop": crop,
                       "max_abs_diff": float(jnp.max(jnp.abs(fn(x) - want))),
                       "ms": timed(fn, x, a.reps), **stamp}
                if name == "one_pass":
                    row["fused"] = solo.fused({"data": x})
                    wrong += row["fused"] != (stamp["platform"] == "tpu")
                wrong += row["max_abs_diff"] != 0.0
                print(json.dumps(row), flush=True)
                sink.write(json.dumps(row) + "\n")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
