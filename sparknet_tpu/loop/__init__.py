"""Train-to-serve production loop: the model improves while it serves.

The reference's whole pitch was ONE driver program owning both training
and scoring (ref: apps/FeaturizerApp.scala:1 — train a net, then score
an RDD with it, in the same app; SURVEY §1).  PRs 6–9 rebuilt every
stage TPU-first — streaming feed, elastic τ-rounds, AOT serving
engine — and this package composes them into that single
system: a :class:`ProductionLoop` drives

    shard feed -> ElasticTrainer rounds -> atomic checkpoint ->
    deploy-arm candidate (f32/fold-BN/int8) -> hot-reload into the
    live ServeEngine

with the hot-reload protocol owned by serve/engine.py
(``build_candidate`` compiles off the request path, ``swap_model``
flips routing under the pump lock and drains the incumbent with its own
executables, ``rollback`` restores the previous ``ServedModel``
bitwise) and every transition journaled as ``loop``/``serve`` obsnet
events.  Chip-free verification: ``python -m sparknet_tpu.obs dryrun
--loop`` and dryrun mode 19 (docs/ARCHITECTURE.md "Production loop").
"""

from sparknet_tpu.loop.controller import ProductionLoop
from sparknet_tpu.loop.deploy import variables_from_checkpoint
from sparknet_tpu.loop.feed import synthetic_shard_feed
from sparknet_tpu.loop.watcher import CheckpointWatcher

__all__ = [
    "CheckpointWatcher",
    "ProductionLoop",
    "synthetic_shard_feed",
    "variables_from_checkpoint",
]
