"""The seeded token file: flat ``uint16`` ids, nothing else.

Stands in for a tokenised pre-training corpus in the on-disk form the
program's ``--data tokens:`` source reads (OLMo / Megatron: documents
already tokenised and concatenated).  Ids are drawn from ``--seed`` with
Zipf(s) frequencies over the configuration's vocabulary rows (real text
is Zipfian: the embedding's gradient is a scatter-add with collisions),
the rank -> id map a seeded permutation.  The file is served from the
page cache.

One file is kept per checkout, like ``dataset.py``'s DB: a run with a new
seed writes its own and removes the others.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np

from benchmarks.harness.dataset import CACHE_DIR


def ensure_tokens(seed: int, count: int, vocab: int, zipf_s: float = 1.0) -> str:
    """Path of the token file for ``seed``, written if it is not there."""
    if vocab > 1 << 16:
        raise ValueError(f"{vocab} ids do not fit uint16")
    root = os.path.join(CACHE_DIR, "tokens")
    path = os.path.join(root, f"tok-s{seed}-n{count}-v{vocab}.bin")
    stamp = path + ".ok"
    if os.path.exists(stamp):
        return path
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    rng = np.random.default_rng(seed)
    weight = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_s
    cdf = np.cumsum(weight / weight.sum())
    ranks = np.searchsorted(cdf, rng.random(count), side="right")
    ids = rng.permutation(vocab)[np.minimum(ranks, vocab - 1)]
    ids.astype(np.uint16).tofile(path)
    with open(stamp, "w") as f:
        json.dump({"tokens": count, "seed": seed, "vocab": vocab,
                   "zipf_s": zipf_s}, f)
    return path
