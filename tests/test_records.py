"""Record-streaming ring sources (`data/records.py`) + the device-arm
e2e feed (ISSUE 12): byte-offset shard indexes make db/tar cursors
epoch-addressable, decode rides the ring workers as the `decode` stage,
and the uint8 wire feeds DeviceAugment post-placement.

Pins the tentpole contracts: deterministic ``(epoch, index)``
addressing per backend, LMDB locator == reader-value bytes, the
SIGKILL-respawn exact-contents resume THROUGH a record stream, the
uint8-wire >= 3.9x byte ratio, device-arm feed equivalence vs the
host-transform twin in both layouts, and the trainers' post-placement
augment hook.
"""

import io
import os
import signal
import tarfile
import time

import numpy as np
import pytest

from sparknet_tpu.data.createdb import create_db, db_minibatches
from sparknet_tpu.data.pipeline import ProcessPipeline
from sparknet_tpu.data.records import RecordShardSource, probe_record_backend

pytestmark = pytest.mark.smoke


@pytest.fixture(autouse=True)
def no_leaked_shm():
    """Ring tests must leave /dev/shm exactly as found (the
    unlink-on-close contract test_pipeline.py pins for every source)."""
    if not os.path.isdir("/dev/shm"):
        yield
        return
    before = set(os.listdir("/dev/shm"))
    yield
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = set(os.listdir("/dev/shm")) - before
        if not leaked:
            return
        time.sleep(0.1)
    assert not leaked, f"leaked shared-memory segments: {leaked}"


def _samples(n, shape=(3, 8, 8)):
    rs = np.random.RandomState(0)
    return [(rs.randint(0, 255, shape).astype(np.uint8), i % 10)
            for i in range(n)]


def _jpeg_tar(tmp_path, n=10, side=16, mapped=None):
    """A plain tar of JPEGs + train.txt label map; ``mapped`` limits how
    many members the map names (the rest must be skipped)."""
    from PIL import Image

    rs = np.random.RandomState(3)
    tar_p = str(tmp_path / "shard.tar")
    names = []
    with tarfile.open(tar_p, "w") as tf:
        for i in range(n):
            buf = io.BytesIO()
            Image.fromarray(
                rs.randint(0, 255, (side, side, 3), np.uint8)
            ).save(buf, format="JPEG")
            data = buf.getvalue()
            info = tarfile.TarInfo(f"img_{i}.jpg")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
            names.append(info.name)
    lm = str(tmp_path / "train.txt")
    with open(lm, "w") as f:
        for i, name in enumerate(names[:mapped or n]):
            f.write(f"{name} {i * 3}\n")
    return tar_p, lm


# ------------------------------------------------------- backend probing


def test_probe_detects_every_backend(tmp_path):
    create_db(str(tmp_path / "lm"), _samples(4), backend="lmdb")
    create_db(str(tmp_path / "r.rdb"), _samples(4), backend="record")
    create_db(str(tmp_path / "lv"), _samples(4), backend="leveldb")
    tar_p, _ = _jpeg_tar(tmp_path, n=2)
    assert probe_record_backend(str(tmp_path / "lm")) == "lmdb"
    assert probe_record_backend(str(tmp_path / "r.rdb")) == "record"
    assert probe_record_backend(str(tmp_path / "lv")) == "leveldb"
    assert probe_record_backend(tar_p) == "tar"
    other = tmp_path / "noise.bin"
    other.write_bytes(b"\x00" * 64)
    assert probe_record_backend(str(other)) == "unknown"


# --------------------------------------- (epoch, index) determinism / order


@pytest.mark.parametrize("backend", ["record", "lmdb"])
def test_db_batches_match_threaded_cursor_order(tmp_path, backend):
    """The index walk reproduces exactly what the stateful cursor
    (db_minibatches, the threaded feed) would have yielded — migrating
    a db: feed to the ring changes the transport, not the data."""
    samples = _samples(24)
    p = str(tmp_path / "db")
    create_db(p, samples, backend=backend)
    src = RecordShardSource(p, 8)
    ref = db_minibatches(p, 8)
    for i in range(3):
        got = src.get(0, i)
        want = next(ref)
        np.testing.assert_array_equal(
            got["data"].astype(np.float32), want["data"])
        np.testing.assert_array_equal(got["label"], want["label"])
    # pure function of (epoch, index): same address, same bytes
    np.testing.assert_array_equal(src.get(0, 1)["data"],
                                  src.get(0, 1)["data"])
    assert src.batches_per_epoch == 3
    assert src.consume_decode_s > 0  # decode wall surfaced for the ring


def test_nhwc_wire_is_worker_side_transpose(tmp_path):
    p = str(tmp_path / "db")
    create_db(p, _samples(8), backend="record")
    chw = RecordShardSource(p, 8).get(0, 0)["data"]
    hwc = RecordShardSource(p, 8, layout="nhwc").get(0, 0)["data"]
    assert hwc.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(hwc, chw.transpose(0, 2, 3, 1))


def test_tar_backend_decodes_mapped_members_only(tmp_path):
    tar_p, lm = _jpeg_tar(tmp_path, n=10, mapped=8)
    src = RecordShardSource(tar_p, 4, layout="nhwc",
                            decode_size=(12, 12), label_map=lm)
    assert src.batches_per_epoch == 2  # 8 mapped // 4
    b = src.get(0, 0)
    assert b["data"].shape == (4, 12, 12, 3)
    assert b["data"].dtype == np.uint8
    assert b["label"].tolist() == [0, 3, 6, 9]
    np.testing.assert_array_equal(b["data"], src.get(0, 0)["data"])
    # layout twins decode the same pixels
    chw = RecordShardSource(tar_p, 4, decode_size=(12, 12), label_map=lm)
    np.testing.assert_array_equal(chw.get(0, 0)["data"],
                                  b["data"].transpose(0, 3, 1, 2))


def test_shuffle_is_per_epoch_seeded_and_covering(tmp_path):
    p = str(tmp_path / "db")
    samples = _samples(24)
    create_db(p, samples, backend="record")
    src = RecordShardSource(p, 8, shuffle=True, seed=5)
    a = src.get(1, 0)["data"]
    np.testing.assert_array_equal(a, src.get(1, 0)["data"])  # re-producible
    assert not np.array_equal(a, src.get(2, 0)["data"])  # epochs re-draw
    got = np.sort(np.concatenate(
        [src.get(3, i)["label"] for i in range(src.batches_per_epoch)]))
    np.testing.assert_array_equal(
        got, np.sort(np.asarray([s[1] for s in samples], np.int32)))


def test_stride_offset_reproduces_shared_db_interleave(tmp_path):
    """stride/offset = the shared-DB multi-process thread interleave:
    process p takes batches p, p+n, ... of the looped stream."""
    p = str(tmp_path / "db")
    create_db(p, _samples(24), backend="record")
    full = RecordShardSource(p, 8)
    s0 = RecordShardSource(p, 8, stride=2, offset=0)
    s1 = RecordShardSource(p, 8, stride=2, offset=1)
    for i, b in [(0, 0), (1, 2), (2, 1)]:  # (i*2) % 3
        np.testing.assert_array_equal(s0.get(0, i)["data"],
                                      full.get(0, b)["data"])
    np.testing.assert_array_equal(s1.get(0, 0)["data"],
                                  full.get(0, 1)["data"])
    assert s0.batches_per_epoch == full.batches_per_epoch == 3


# ----------------------------------------------------------- LMDB locators


def test_lmdb_locators_address_exact_value_bytes(tmp_path):
    """Every (offset, size) the locator walk yields slices the SAME
    bytes the reader's cursor returns — inline nodes and overflow
    (F_BIGDATA) values both."""
    from sparknet_tpu.data.lmdb_io import LmdbReader, LmdbWriter, _data_file

    items = [(f"k{i:03d}".encode(), os.urandom(20 + 400 * i))
             for i in range(12)]  # tails large enough to overflow a page
    p = str(tmp_path / "db")
    with LmdbWriter(p) as w:
        for k, v in items:
            w.put(k, v)
    with open(_data_file(p), "rb") as f:
        raw = f.read()
    with LmdbReader(p) as r:
        via_cursor = dict(r)
        locs = list(r.iter_locators())
    assert len(locs) == len(items)
    for key, off, size in locs:
        assert raw[off:off + size] == via_cursor[key]


# ---------------------------------------------------------------- refusals


def test_leveldb_refused_naming_convert_db(tmp_path):
    p = str(tmp_path / "lv")
    create_db(p, _samples(4), backend="leveldb")
    with pytest.raises(ValueError, match="convert_db"):
        RecordShardSource(p, 2)


def test_compressed_tar_refused(tmp_path):
    tar_p, lm = _jpeg_tar(tmp_path, n=2)
    gz = tar_p + ".gz"
    import gzip

    with open(tar_p, "rb") as f, gzip.open(gz, "wb") as g:
        g.write(f.read())
    with pytest.raises(ValueError, match="repack as plain .tar"):
        RecordShardSource(gz, 2, decode_size=(8, 8), label_map=lm)


def test_tar_needs_decode_size_and_label_map(tmp_path):
    tar_p, lm = _jpeg_tar(tmp_path, n=4)
    with pytest.raises(ValueError, match="decode_size"):
        RecordShardSource(tar_p, 2, label_map=lm)
    with pytest.raises(ValueError, match="label map"):
        RecordShardSource(tar_p, 2, decode_size=(8, 8))


def test_process_feed_refusal_names_migration_path(tmp_path):
    """The remaining stateful sources' refusal tells the operator HOW to
    migrate (RecordShardSource / convert_db), not just no."""
    from sparknet_tpu.data.feed import process_feed

    def stateful(it):
        return {"x": np.zeros(2, np.float32)}

    with pytest.raises(SystemExit, match="RecordShardSource"):
        process_feed(stateful, 4, 0, lambda *a, **k: None)


# ----------------------------------------------- through the process ring


@pytest.mark.parametrize("backend", ["record", "lmdb"])
def test_record_stream_through_ring_matches_direct(tmp_path, backend):
    p = str(tmp_path / "db")
    create_db(p, _samples(24), backend=backend)
    src = RecordShardSource(p, 8, layout="nhwc")
    with ProcessPipeline(src, None, num_batches=6, workers=2,
                         name="feed.rec") as pipe:
        got = [{k: np.array(v) for k, v in f.items()}
               for f in pipe.batches()]
        stats = dict(pipe.stats)
    for g, feeds in enumerate(got):
        e, i = divmod(g, src.batches_per_epoch)
        ref = src.get(e, i)
        np.testing.assert_array_equal(feeds["data"], ref["data"])
        np.testing.assert_array_equal(feeds["label"], ref["label"])
    # decode runs IN the workers and journals as its own stage
    assert stats["decode"] > 0.0


def test_sigkill_respawn_resumes_exact_record_stream(tmp_path):
    """ISSUE 12 acceptance pin: SIGKILL a ring worker mid-record-stream;
    the respawned worker resumes at the exact undelivered
    ``(epoch, index)`` and the stream's total contents are bitwise what
    the index defines — across an epoch boundary."""
    p = str(tmp_path / "db")
    create_db(p, _samples(32), backend="lmdb")
    src = RecordShardSource(p, 8, shuffle=True, seed=9)
    N = 12  # 3 epochs of 4 batches: the resume crosses epochs
    with ProcessPipeline(src, None, num_batches=N, workers=2,
                         max_respawns=2, name="feed.rec") as pipe:
        it = pipe.batches()
        got = [{k: np.array(v) for k, v in next(it).items()}
               for _ in range(3)]
        os.kill(pipe._procs[0].pid, signal.SIGKILL)
        got += [{k: np.array(v) for k, v in next(it).items()}
                for _ in range(N - 3)]
        assert pipe._respawns_used == 1
    assert len(got) == N
    for g, feeds in enumerate(got):
        e, i = divmod(g, src.batches_per_epoch)
        ref = src.get(e, i)
        np.testing.assert_array_equal(feeds["data"], ref["data"])
        np.testing.assert_array_equal(feeds["label"], ref["label"])


# ------------------------------------------------------- uint8 wire pin


def test_uint8_wire_at_least_3_9x_smaller_than_f32():
    """The thin-wire claim, pinned against the real slot allocator: the
    raw=True spec of the AlexNet wire is >= 3.9x smaller than the f32
    spec at the SAME geometry."""
    from sparknet_tpu.data.pipeline import FeedSpec
    from sparknet_tpu.ops.data_layers import wire_spec

    shapes = {"data": (256, 227, 227, 3), "label": (256,)}

    def slot_bytes(raw):
        spec = FeedSpec(tuple(
            (name, shape, dtype)
            for name, (shape, dtype) in wire_spec(shapes, raw=raw).items()))
        return spec.slot_bytes

    ratio = slot_bytes(False) / slot_bytes(True)
    assert ratio >= 3.9, ratio


# ------------------------------------- device arm vs host-transform twin


def _cpu_augment(cfg_kwargs, layout):
    from sparknet_tpu.data.device_transform import DeviceAugment
    from sparknet_tpu.data.transform import TransformConfig

    return DeviceAugment(TransformConfig(**cfg_kwargs), layout=layout)


def test_device_arm_test_mode_bitwise_matches_host_twin(tmp_path):
    """TEST-mode e2e equivalence: uint8 records through the ring +
    DeviceAugment == the host DataTransformer on the same records,
    bitwise, in both layouts."""
    import jax

    from sparknet_tpu.data.transform import DataTransformer, TransformConfig

    p = str(tmp_path / "db")
    create_db(p, _samples(16, shape=(3, 16, 16)), backend="record")
    rs = np.random.RandomState(2)
    mean = rs.rand(3, 16, 16).astype(np.float32) * 255
    cfg = dict(mean_image=mean, crop_size=12, scale=0.004)
    host = DataTransformer(TransformConfig(**cfg))
    key = jax.random.key(11)
    for layout in ("nchw", "nhwc"):
        src = RecordShardSource(p, 8, layout=layout)
        with ProcessPipeline(src, None, num_batches=1, workers=1,
                             name="feed.dev") as pipe:
            wire = {k: np.array(v)
                    for k, v in next(pipe.batches()).items()}
        assert wire["data"].dtype == np.uint8
        out = np.asarray(_cpu_augment(cfg, layout)(
            wire["data"], key, train=False))
        want = host(src.get(0, 0)["data"] if layout == "nchw"
                    else src.get(0, 0)["data"].transpose(0, 3, 1, 2),
                    False)
        if layout == "nhwc":
            out = out.transpose(0, 3, 1, 2)
        np.testing.assert_array_equal(out, want)


def test_device_arm_train_mode_same_key_same_crops_both_layouts():
    """TRAIN-mode draw-order pin: the SAME key produces the SAME crop
    offsets and mirror coins in both layouts — nchw output is exactly
    the transpose of the nhwc output."""
    import jax

    rs = np.random.RandomState(4)
    x_chw = rs.randint(0, 255, (6, 3, 16, 16)).astype(np.uint8)
    mean = rs.rand(3, 16, 16).astype(np.float32) * 255
    cfg = dict(mean_image=mean, crop_size=12, mirror=True, scale=0.004)
    key = jax.random.key(21)
    o_chw = np.asarray(_cpu_augment(cfg, "nchw")(x_chw, key, train=True))
    o_hwc = np.asarray(_cpu_augment(cfg, "nhwc")(
        np.ascontiguousarray(x_chw.transpose(0, 2, 3, 1)), key,
        train=True))
    np.testing.assert_array_equal(o_chw, o_hwc.transpose(0, 3, 1, 2))


# ------------------------------------------- trainer post-placement hook


def test_trainer_device_fn_key_policy_rank4_and_rank5():
    """The trainers' post-placement adapter: rank-4 feeds augment with
    ``fold_in(base, it)``; rank-5 [tau, B, ...] feeds give slot t the
    documented ``fold_in(fold_in(base, it), t)`` key — independent
    draws per slot, same family as the solo device_fn."""
    import jax

    rs = np.random.RandomState(5)
    x = rs.randint(0, 255, (4, 3, 16, 16)).astype(np.uint8)
    cfg = dict(crop_size=12, mirror=True)
    aug = _cpu_augment(cfg, "nchw")
    fn = aug.trainer_device_fn(pid=2, seed=3)
    out4 = np.asarray(fn({"data": x}, 7)["data"])
    assert out4.shape == (4, 3, 12, 12)
    x5 = np.stack([x, x])
    out5 = np.asarray(fn({"data": x5}, 7)["data"])
    assert out5.shape == (2, 4, 3, 12, 12)
    base = jax.random.key(1234 + 2 + 3)
    k_it = jax.random.fold_in(base, 7)
    for t in range(2):
        want = np.asarray(aug(x, jax.random.fold_in(k_it, t), train=True))
        np.testing.assert_array_equal(out5[t], want)
    # identical slot inputs still draw independently
    assert not np.array_equal(out5[0], out5[1])


def test_cli_train_device_arm_tau_process_feed(tmp_path, monkeypatch):
    """End-to-end: db record source -> process ring (uint8 wire) ->
    rounds.stack_tau -> ParallelTrainer.feed_device_fn augment post-placement.
    Threaded and process feeds must deliver the same training sequence
    (the ring reproduces the cursor order)."""
    from sparknet_tpu.cli import main
    from sparknet_tpu.common import set_config

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SPARKNET_TRAIN_LOG_DIR", str(tmp_path))
    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (1, 28, 28)).astype(np.uint8), i % 10)
               for i in range(64)]
    p = str(tmp_path / "train_lmdb")
    create_db(p, samples, backend="lmdb")
    args = ["--platform", "cpu", "train", "--solver", "zoo:lenet",
            "--batch", "8", "--iterations", "4", "--tau", "2",
            "--data", f"db:{p}", "--augment", "device", "--seed", "0"]
    assert main(args + ["--output", str(tmp_path / "m_thread")]) == 0
    set_config(feed="process")
    try:
        assert main(args + ["--output", str(tmp_path / "m_proc")]) == 0
    finally:
        set_config(feed="threaded")
    a = np.load(str(tmp_path / "m_thread.solverstate.npz"))
    b = np.load(str(tmp_path / "m_proc.solverstate.npz"))
    for k in a.files:
        if a[k].dtype.kind in "fiu":
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
