"""Data-plane tests: loader formats, sampler window semantics, transformer
crops, minibatch packing, prefetcher overlap.

Mirrors the reference's pure-JVM data tests (ref:
src/test/scala/libs/MinibatchSamplerSpec.scala:4-44 pull-ordering on
synthetic data; CifarLoader exercised through CifarSpec).
"""

import io
import os
import tarfile

import numpy as np
import pytest

from sparknet_tpu.data import (
    CifarLoader,
    DataTransformer,
    DevicePrefetcher,
    ImageNetLoader,
    MinibatchSampler,
    TransformConfig,
    compute_mean,
    compute_mean_from_minibatches,
    make_minibatches,
    make_minibatches_compressed,
)
from sparknet_tpu.data.cifar import write_synthetic_cifar
from sparknet_tpu.data.sampler import partition_feed


# ---------------------------------------------------------------- CIFAR
def test_cifar_loader_roundtrip(tmp_path):
    write_synthetic_cifar(str(tmp_path), seed=3)
    loader = CifarLoader(str(tmp_path), seed=1)
    assert loader.train_images.shape == (500, 3, 32, 32)
    assert loader.test_images.shape == (100, 3, 32, 32)
    assert loader.train_labels.min() >= 0 and loader.train_labels.max() < 10
    # mean-subtracted train set has ~zero mean
    x, y = loader.train_arrays()
    assert abs(float(x.mean())) < 1.0
    # deterministic shuffle
    loader2 = CifarLoader(str(tmp_path), seed=1)
    np.testing.assert_array_equal(loader.train_labels, loader2.train_labels)


def test_cifar_loader_missing_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        CifarLoader(str(tmp_path / "nope"))


# ---------------------------------------------------------------- sampler
def test_sampler_contiguous_window():
    batches = [{"i": np.full(2, k)} for k in range(10)]
    s = MinibatchSampler(batches, num_sampled_batches=4, seed=7)
    got = [int(b["i"][0]) for b in s]
    assert got == list(range(s.start, s.start + 4))
    assert 0 <= s.start <= 6


def test_sampler_from_iterator_matches_sequence():
    batches = [{"i": np.full(1, k)} for k in range(8)]
    s1 = MinibatchSampler(batches, num_sampled_batches=3, seed=5)
    s2 = MinibatchSampler(iter(batches), total_num_batches=8,
                          num_sampled_batches=3, seed=5)
    assert [int(b["i"][0]) for b in s1] == [int(b["i"][0]) for b in s2]


def test_sampler_too_many_raises():
    with pytest.raises(ValueError):
        MinibatchSampler([{"a": 1}], num_sampled_batches=2)


def test_partition_feed_tau_stack():
    images = np.arange(40 * 3 * 4 * 4, dtype=np.uint8).reshape(40, 3, 4, 4)
    labels = np.arange(40) % 10
    fn = partition_feed(images, labels, batch_size=4, tau=3, seed=0)
    feeds = fn(0)
    assert feeds["data"].shape == (3, 4, 3, 4, 4)
    assert feeds["label"].shape == (3, 4)
    # window is contiguous in the partition
    flat = feeds["label"].reshape(-1)
    start = flat[0]
    np.testing.assert_array_equal(flat, (np.arange(12) + start) % 10)


# ---------------------------------------------------------------- transform
def test_transform_center_vs_random_crop():
    cfg = TransformConfig(crop_size=8, mirror=True, seed=0)
    t = DataTransformer(cfg)
    x = np.random.RandomState(0).randint(0, 255, (16, 3, 12, 12)).astype(np.uint8)
    test_out = t(x, train=False)
    assert test_out.shape == (16, 3, 8, 8)
    np.testing.assert_allclose(test_out, x[:, :, 2:10, 2:10].astype(np.float32))
    train_out = t(x, train=True)
    assert train_out.shape == (16, 3, 8, 8)
    # every train crop is an actual window of the source image
    src = x.astype(np.float32)
    for i in range(4):
        found = any(
            np.array_equal(train_out[i], w) or np.array_equal(train_out[i], w[:, :, ::-1])
            for ho in range(5) for wo in range(5)
            for w in [src[i, :, ho:ho+8, wo:wo+8]]
        )
        assert found, i


def test_transform_mean_value_and_scale():
    cfg = TransformConfig(mean_value=(10.0, 20.0, 30.0), scale=0.5)
    t = DataTransformer(cfg)
    x = np.full((2, 3, 4, 4), 40.0, np.float32)
    out = t(x, train=True)
    np.testing.assert_allclose(out[:, 0], 15.0)
    np.testing.assert_allclose(out[:, 2], 5.0)


def test_transform_mean_image():
    mean = np.ones((3, 4, 4), np.float32) * 7
    t = DataTransformer(TransformConfig(mean_image=mean))
    out = t(np.full((2, 3, 4, 4), 10.0), train=False)
    np.testing.assert_allclose(out, 3.0)


# ---------------------------------------------------------------- minibatch
def _jpeg_bytes(arr_hwc: np.ndarray) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr_hwc).save(buf, format="JPEG")
    return buf.getvalue()


def test_make_minibatches_drops_ragged_tail():
    images = np.zeros((10, 3, 4, 4), np.uint8)
    labels = np.arange(10)
    out = list(make_minibatches(images, labels, batch_size=4))
    assert len(out) == 2
    assert out[0][0].shape == (4, 3, 4, 4)


def test_make_minibatches_compressed_decodes_and_drops_bad():
    rs = np.random.RandomState(0)
    good = [( _jpeg_bytes(rs.randint(0, 255, (20, 30, 3)).astype(np.uint8)), k)
            for k in range(5)]
    bad = [(b"not a jpeg", 99)]
    out = list(make_minibatches_compressed(good[:3] + bad + good[3:],
                                           batch_size=2, height=8, width=8))
    assert len(out) == 2  # 5 good images -> 2 full batches of 2, tail dropped
    assert out[0][0].shape == (2, 3, 8, 8)
    assert 99 not in np.concatenate([b[1] for b in out])


def test_make_minibatches_compressed_pooled_matches_serial():
    """Thread-pooled decode yields byte-identical batches in identical
    order to the serial path, including broken-image drops."""
    rs = np.random.RandomState(1)
    samples = [(_jpeg_bytes(rs.randint(0, 255, (16, 16, 3)).astype(np.uint8)), k)
               for k in range(9)]
    samples.insert(4, (b"broken", 99))
    serial = list(make_minibatches_compressed(samples, 3, 8, 8, workers=1))
    pooled = list(make_minibatches_compressed(samples, 3, 8, 8, workers=4))
    assert len(serial) == len(pooled) == 3
    for (si, sl), (pi, pl) in zip(serial, pooled):
        np.testing.assert_array_equal(si, pi)
        np.testing.assert_array_equal(sl, pl)


def test_compute_mean_streaming_matches_direct():
    rs = np.random.RandomState(0)
    images = rs.randint(0, 255, (30, 3, 5, 5)).astype(np.uint8)
    labels = np.zeros(30, np.int64)
    direct = compute_mean(images)
    streamed = compute_mean_from_minibatches(
        make_minibatches(images, labels, 10), (3, 5, 5))
    np.testing.assert_allclose(direct, streamed, atol=1e-5)


# ---------------------------------------------------------------- archive
def test_imagenet_loader_tar_shards(tmp_path):
    rs = np.random.RandomState(0)
    names, labels = [], {}
    for shard in range(2):
        tar_path = tmp_path / f"shard{shard}.tar"
        with tarfile.open(tar_path, "w") as tf:
            for i in range(4):
                name = f"img_{shard}_{i}.jpg"
                data = _jpeg_bytes(rs.randint(0, 255, (10, 10, 3)).astype(np.uint8))
                info = tarfile.TarInfo(name)
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
                labels[name] = shard * 4 + i
    label_file = tmp_path / "train.txt"
    label_file.write_text("".join(f"{n} {l}\n" for n, l in labels.items()))

    loader = ImageNetLoader(str(tmp_path), str(label_file))
    assert len(loader) == 2
    # worker sharding partitions the archives
    s0 = list(loader.shard(0, 2))
    s1 = list(loader.shard(1, 2))
    assert len(s0) == 4 and len(s1) == 4
    assert {l for _, l in s0} == {0, 1, 2, 3}
    assert {l for _, l in s1} == {4, 5, 6, 7}
    # pipeline composes into decoded minibatches
    batches = list(make_minibatches_compressed(s0, 2, 8, 8))
    assert len(batches) == 2


# ---------------------------------------------------------------- prefetch
def test_prefetcher_yields_all_in_order():
    made = []

    def data_fn(it):
        made.append(it)
        return {"x": np.full((2, 2), it, np.float32)}

    pf = DevicePrefetcher(data_fn, num_iters=6)
    got = [int(np.asarray(f["x"])[0, 0]) for f in pf]
    assert got == list(range(6))
    assert made == list(range(6))


def test_prefetcher_close_releases_worker():
    import threading

    def data_fn(it):
        return {"x": np.zeros((4, 4), np.float32)}

    pf = DevicePrefetcher(data_fn, num_iters=1000, depth=2)
    it = iter(pf)
    next(it)  # consume one, then abandon
    pf.close()
    assert not pf._feed.thread.is_alive()
    assert pf._feed._q.qsize() == 0
    # active threads back to baseline (no leaked workers)
    assert threading.active_count() < 20


def test_partition_feed_too_small_raises():
    with pytest.raises(ValueError, match="contiguous window"):
        partition_feed(np.zeros((10, 3, 4, 4)), np.zeros(10), batch_size=4, tau=3)


def test_prefetcher_reiteration_returns_immediately():
    pf = DevicePrefetcher(lambda it: {"x": np.zeros(1)}, num_iters=3)
    assert len(list(pf)) == 3
    assert list(pf) == []  # exhausted stream: no deadlock, no items


def test_prefetcher_propagates_errors():
    def data_fn(it):
        if it == 2:
            raise RuntimeError("boom")
        return {"x": np.zeros(1)}

    pf = DevicePrefetcher(data_fn, num_iters=5)
    with pytest.raises(RuntimeError, match="boom"):
        list(pf)


def test_feed_bench_tool_smoke():
    """tools/feed_bench.py variants run and report sane numbers."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "feed_bench",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools", "feed_bench.py"),
    )
    fb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fb)

    rec = fb.bench_transform("numpy", batch=8, iters=2)
    assert rec["value"] > 0 and "numpy" in rec["metric"]
    pre = fb.bench_prefetch(batch=8, iters=3)
    assert pre["value"] > 0
