"""ImageNet (AlexNet/CaffeNet) distributed training app.

ref: src/main/scala/apps/ImageNetApp.scala:19-193 — S3 tar shards →
decode/resize 256×256 → distributed mean → per-phase preprocessing
closures (mean-subtract + random 227×227 crop train / center crop test,
:124-176) → τ=50 sync loop.  Here the ingest is a local directory of tar
shards (zero egress), decode/augment is vectorized on the host behind the
prefetcher, and the sync loop is the jitted tau-round.
"""

from __future__ import annotations

import numpy as np

from sparknet_tpu import models
from sparknet_tpu.data import (
    DataTransformer,
    ImageNetLoader,
    TransformConfig,
    compute_mean_from_minibatches,
    make_minibatches_compressed,
)
from sparknet_tpu.parallel.trainer import ParallelTrainer
from sparknet_tpu.solvers.solver import Solver
from sparknet_tpu.utils import EventLogger

TAU = 50  # ref: ImageNetApp.scala:151
RESIZE = 256  # ref: ImageNetApp.scala fullHeight/fullWidth
CROP = 227  # ref: ImageNetApp.scala croppedHeight/croppedWidth


class ImageNetApp:
    def __init__(
        self,
        shard_dir: str,
        label_file: str,
        mesh=None,
        tau: int = TAU,
        batch: int = 256,
        model: str = "caffenet",
        num_classes: int = 1000,
        log_dir: str = ".",
        seed: int = 0,
        mean_image: np.ndarray | None = None,
    ):
        self.log = EventLogger(log_dir, prefix="imagenet_training_log")
        self.loader = ImageNetLoader(shard_dir, label_file)
        self.log(f"{len(self.loader)} tar shards")
        self.batch = batch
        self.tau = tau

        build = models.caffenet if model == "caffenet" else models.alexnet
        solver_cfg = models.caffenet_solver()
        solver = Solver(solver_cfg, build(batch, num_classes=num_classes, crop=CROP))
        self.trainer = ParallelTrainer(solver, mesh=mesh, tau=tau)
        self.num_workers = self.trainer.num_workers

        if mean_image is None:
            self.log("computing mean image over shard 0")
            mean_image = compute_mean_from_minibatches(
                make_minibatches_compressed(
                    self.loader.shard(0, max(len(self.loader), 1)),
                    batch, RESIZE, RESIZE,
                ),
                (3, RESIZE, RESIZE),
            )
        self.mean_image = mean_image
        self.transform = DataTransformer(
            TransformConfig(
                crop_size=CROP, mirror=True, mean_image=mean_image, seed=seed
            )
        )

    # ------------------------------------------------------------------
    def minibatch_stream(self, worker: int = 0):
        """Decoded (images, labels) minibatches of this worker's shard slice."""
        return make_minibatches_compressed(
            self.loader.shard(worker, self.num_workers), self.batch, RESIZE, RESIZE
        )

    def _tau_feeds(self, streams):
        """Pack tau consecutive global minibatches into [tau, B_global, ...]
        with the train-phase transform applied.  ``streams`` holds one
        decoded-minibatch stream per worker so every worker trains on its
        own shard slice (the RDD partition, ImageNetLoader.scala:91-96)."""
        datas, labels = [], []
        for _ in range(self.tau):
            for stream in streams:
                imgs, labs = next(stream)
                datas.append(self.transform(imgs, train=True))
                labels.append(labs)
        B_global = self.batch * self.num_workers
        data = np.concatenate(datas).reshape(
            (self.tau, B_global, 3, CROP, CROP)
        )
        lab = np.concatenate(labels).reshape((self.tau, B_global))
        return {"data": data, "label": lab.astype(np.int32)}

    def _round_feeds(self, it):
        """The trainer's data fn, ONE for every round (so each round is
        placed ahead, ``ParallelTrainer.train_round``): the streams' next
        tau x workers minibatches, a new epoch's where they run out."""
        try:
            return self._tau_feeds(self._streams)
        except StopIteration:
            self._streams = [  # new epoch
                self.minibatch_stream(w) for w in range(self.num_workers)
            ]
            try:
                return self._tau_feeds(self._streams)
            except StopIteration:
                raise ValueError(
                    f"dataset too small: tau={self.tau} x batch="
                    f"{self.batch} x {self.num_workers} workers needs "
                    f"{self.tau * self.batch * self.num_workers} decoded "
                    "images per round (and every worker needs >=1 shard) "
                    "— reduce tau/batch or add shards"
                ) from None

    # ------------------------------------------------------------------
    def run(self, num_outer: int = 10) -> float:
        self._streams = [
            self.minibatch_stream(w) for w in range(self.num_workers)
        ]
        loss = float("nan")
        try:
            for outer in range(num_outer):
                self.log("training", i=outer)
                loss = self.trainer.train_round(self._round_feeds)
                self.log(f"loss: {loss:.5f}", i=outer)
        finally:
            # the round placed past the last one is of these streams,
            # which end here (and their decode pools with them)
            self.trainer.close()
            del self._streams
        return loss
