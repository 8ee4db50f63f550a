"""The seeded data set: a RecordDB of uint8 256x256x3 records.

Stands in for ILSVRC-2012 train (1.28 M JPEGs resized to 256x256 and
stored raw in an LMDB, the bvlc_alexnet recipe): same record geometry,
same on-disk record format the program reads through ``--data db:``,
labels in [0, 1000).  Pixels and labels are drawn from ``--seed``; the
file is served from the page cache, which is what a training host with
its shard resident sees.

One DB is kept per checkout: a run with a new seed writes its DB and
removes the others, so a check's many seeds never hold more than one
0.8 GB file and every run with a fresh seed does the same set-up work.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache")


def ensure_db(seed: int, records: int, chw: tuple[int, int, int],
              classes: int) -> str:
    """Path of the RecordDB for ``seed``, written if it is not there."""
    from sparknet_tpu.data.createdb import create_db

    c, h, w = chw
    tag = f"db-s{seed}-n{records}-{c}x{h}x{w}"
    root = os.path.join(CACHE_DIR, "data")
    path = os.path.join(root, tag)
    stamp = path + ".ok"
    if os.path.exists(stamp):
        return path
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, records)
    rec_bytes = c * h * w

    def samples():
        # bulk draws: one generator call per 256 records, not per record
        for lo in range(0, records, 256):
            n = min(256, records - lo)
            block = np.frombuffer(rng.bytes(n * rec_bytes), np.uint8)
            block = block.reshape(n, c, h, w)
            for i in range(n):
                yield block[i], int(labels[lo + i])

    n = create_db(path, samples())
    with open(stamp, "w") as f:
        json.dump({"records": n, "seed": seed, "chw": [c, h, w]}, f)
    return path
