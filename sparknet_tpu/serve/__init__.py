"""AOT-batched serving engine (ROADMAP item 1).

SparkNet's own inference story is batch-scoring Spark apps —
FeaturizerApp / ImageNetRunDBApp drain an RDD through a TEST-phase net
(ref: apps/FeaturizerApp.scala:1, SURVEY §1) — i.e. throughput-shaped,
latency-blind.  This package is the TPU-native rebuild of that arc as a
*request-serving* engine in the train→serve system-design shape of the
TensorFlow paper (1605.08695, PAPERS.md): single-image requests enter a
queue, a dynamic batcher coalesces them into padded batches against a
small set of AOT pre-compiled bucket sizes, and a deadline flush bounds
tail latency under trickle load.

Three load-bearing design points, each machine-checked elsewhere:

* **AOT buckets, zero steady-state compiles** — every bucket program is
  ``jax.jit(...).lower().compile()``-ed at model-load time, so no
  traffic pattern can trigger a recompile mid-serve (a compile on the
  request path is seconds of tail latency).  The obs recompile sentinel
  pins
  post-warmup compiles == 0 (tests/test_serve.py).
* **Padded batches are EXACT** — eval-mode zoo forwards have no
  cross-example ops, so row i of a padded bucket is bit-identical to a
  batch-1 run of the same request (not allclose: exact; the gate in
  tests/test_serve.py pins it for >= 3 families x {f32, fold-BN, int8}).
* **Residency is priced before any load** — the banked batch-fit table
  (``docs/mem_contracts/batch_fit.json``) prices each model's worst-case
  bucket footprint, and the engine REFUSES a load the table predicts
  won't fit next to the already-resident models — refused before
  anything compiles.

Deploy arms ride the existing paths unchanged: ``f32`` (plain TEST
forward), ``fold_bn`` (models/fold_bn.py), ``int8`` (quant.py PTQ,
folded first per the DeployNet ordering contract).

Pod scale (ROADMAP item 2): ``router.py``'s :class:`ReplicaRouter`
sprays tickets across K single-device engine copies
(least-outstanding-work), with elastic membership (kill/join between
flushes, zero-drop steal/adopt re-route), deadline-aware shedding
(``DynamicBatcher.shed``), and per-replica hot swap;
``continuous.py``'s :class:`ContinuousDecoder` batches the charlm
family at SLOT granularity per decode step over one fixed-shape AOT
arena program.

See docs/SERVING.md for the architecture and latency vocabulary.
"""

from sparknet_tpu.serve.batcher import DynamicBatcher, Ticket
from sparknet_tpu.serve.continuous import ContinuousDecoder
from sparknet_tpu.serve.engine import (
    AdmissionRefused,
    ServeEngine,
    ServedModel,
    build_serve_program,
)
from sparknet_tpu.serve.residency import (
    AdmissionPolicy,
    load_fit_table,
    price_residency,
)
from sparknet_tpu.serve.router import Replica, ReplicaRouter

__all__ = [
    "AdmissionPolicy",
    "AdmissionRefused",
    "ContinuousDecoder",
    "DynamicBatcher",
    "Replica",
    "ReplicaRouter",
    "ServeEngine",
    "ServedModel",
    "Ticket",
    "build_serve_program",
    "load_fit_table",
    "price_residency",
]
