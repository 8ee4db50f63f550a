"""End-to-end ImageNetApp run on synthetic tar shards.

The reference validated its ImageNet path only on a live cluster
(ImageNetLoaderSpec is ``ignore``d without S3 credentials); here the
whole pipeline — tar shards → JPEG decode pool → resize 256 → mean →
random-crop/mirror transform → τ-round trainer on a device mesh — runs
against generated fixtures in CI (ref: ImageNetApp.scala:32-192).
"""

import io
import os
import tarfile

import numpy as np
import pytest

PIL = pytest.importorskip("PIL")
from PIL import Image  # noqa: E402


@pytest.fixture(scope="module")
def shard_dir(tmp_path_factory):
    """Two tar shards x 24 JPEGs with a learnable class signal, plus the
    train.txt filename->label map (ref: ImageNetLoader.scala:41-54)."""
    root = tmp_path_factory.mktemp("imagenet_shards")
    rs = np.random.RandomState(0)
    lines = []
    idx = 0
    for shard in range(2):
        tar_path = os.path.join(root, f"shard_{shard:02d}.tar")
        with tarfile.open(tar_path, "w") as tf:
            for _ in range(24):
                label = rs.randint(0, 4)
                # pixel-scale class signal: one bright quadrant per class
                img = (rs.rand(64, 60, 3) * 60).astype(np.uint8)
                r, c = (label % 2) * 32, (label // 2) * 30
                img[r : r + 32, c : c + 30] += 120
                buf = io.BytesIO()
                Image.fromarray(img).save(buf, format="JPEG", quality=90)
                name = f"img_{idx:04d}.jpg"
                idx += 1
                info = tarfile.TarInfo(name)
                info.size = buf.getbuffer().nbytes
                buf.seek(0)
                tf.addfile(info, buf)
                lines.append(f"{name} {label}")
    label_file = os.path.join(root, "train.txt")
    with open(label_file, "w") as f:
        f.write("\n".join(lines) + "\n")
    return str(root), label_file


@pytest.fixture(scope="module")
def app_and_logs(shard_dir, tmp_path_factory):
    from sparknet_tpu.apps.imagenet_app import ImageNetApp
    from sparknet_tpu.parallel.mesh import data_parallel_mesh

    root, label_file = shard_dir
    logs = tmp_path_factory.mktemp("imagenet_logs")
    return ImageNetApp(
        root,
        label_file,
        mesh=data_parallel_mesh(2),  # 2 workers, one shard each
        tau=2,
        batch=3,
        model="caffenet",
        num_classes=4,
        log_dir=str(logs),
    ), logs


def test_imagenet_app_end_to_end(app_and_logs):
    app, tmp_path = app_and_logs
    assert app.num_workers == 2
    assert app.mean_image.shape == (3, 256, 256)
    # mean of raw pixels: strictly inside (0, 255)
    assert 0.0 < float(app.mean_image.mean()) < 255.0

    loss = app.run(num_outer=2)
    assert np.isfinite(loss)
    # 24 imgs/shard, tau(2) x batch(3) = 6 per worker per round: 2 rounds
    # consume 12 of 24 per shard without re-epoching
    logs = [f for f in os.listdir(tmp_path) if f.startswith("imagenet_training_log")]
    assert logs, "event log missing"


def test_imagenet_app_places_every_round_once(app_and_logs):
    """The app hands the trainer ONE data fn, so a round is asked for,
    put and trained once, the next one while this one trains (three rounds
    and the one placed past the last, which ``run`` lets go of): a new
    function a round would stage each round twice."""
    app, _ = app_and_logs
    events = []
    put, feeds = app.trainer._put_feeds, app._tau_feeds

    def noting_put(raw, **kw):
        events.append("put")
        return put(raw, **kw)

    def noting_feeds(streams):
        events.append("data")
        return feeds(streams)

    app.trainer._put_feeds, app._tau_feeds = noting_put, noting_feeds
    try:
        # 3 + 1 rounds of 6 per worker: the 24 of a shard, no new epoch
        assert np.isfinite(app.run(num_outer=3))
    finally:
        del app.trainer._put_feeds, app._tau_feeds
    assert events == ["data", "put"] * 4
    assert app.trainer._ahead is None


def test_imagenet_app_dataset_too_small(shard_dir, tmp_path):
    from sparknet_tpu.apps.imagenet_app import ImageNetApp
    from sparknet_tpu.parallel.mesh import data_parallel_mesh

    root, label_file = shard_dir
    app = ImageNetApp(
        root,
        label_file,
        mesh=data_parallel_mesh(2),
        tau=30,  # 30 x 3 = 90 > 24 images per worker shard
        batch=3,
        model="caffenet",
        num_classes=4,
        log_dir=str(tmp_path),
    )
    with pytest.raises(ValueError, match="dataset too small"):
        app.run(num_outer=1)
