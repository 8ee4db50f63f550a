"""Clean-room LMDB codec + Caffe-dataset ingest compatibility.

ref: caffe/src/caffe/util/db_lmdb.cpp (the reference's LMDB Cursor/
Transaction).  No liblmdb exists in this environment, so the format is
pinned two ways: round-trips through our own reader/writer, and
byte-level invariants against the published on-disk layout (meta magic /
version / dual-meta txnid rule, page flags, node packing).
"""

import os
import struct

import numpy as np
import pytest

from sparknet_tpu.data import lmdb_io
from sparknet_tpu.data.createdb import (
    convert_db,
    create_db,
    db_minibatches,
    decode_datum,
)
from sparknet_tpu.data.io_utils import datum_to_array
from sparknet_tpu.data.lmdb_io import LmdbReader, LmdbWriter, is_lmdb


def _write(path, items, subdir=True):
    with LmdbWriter(str(path), subdir=subdir) as w:
        for k, v in items:
            w.put(k, v)
    return str(path)


class TestRoundTrip:
    def test_small(self, tmp_path):
        items = [(f"{i:08d}".encode(), f"value-{i}".encode()) for i in range(5)]
        p = _write(tmp_path / "db", items)
        with LmdbReader(p) as r:
            assert len(r) == 5
            assert list(r) == items

    def test_keys_returned_in_sorted_order(self, tmp_path):
        items = [(b"zeta", b"3"), (b"alpha", b"1"), (b"mid", b"2")]
        p = _write(tmp_path / "db", items)
        with LmdbReader(p) as r:
            assert [k for k, _ in r] == [b"alpha", b"mid", b"zeta"]

    def test_multipage_tree(self, tmp_path):
        # thousands of entries forces multiple leaves + branch levels
        items = [
            (f"{i:08d}".encode(), os.urandom(50 + i % 100)) for i in range(3000)
        ]
        p = _write(tmp_path / "db", items)
        with LmdbReader(p) as r:
            assert len(r) == 3000
            got = list(r)
        assert got == sorted(items)

    def test_overflow_values(self, tmp_path):
        # > half-page values go to OVERFLOW page runs (the ImageNet JPEG
        # case); include a multi-page one and an exact-page-boundary one
        items = [
            (b"big-a", os.urandom(3000)),
            (b"big-b", os.urandom(5 * 4096)),
            (b"big-c", os.urandom(4096 - 16)),  # exactly one overflow page
            (b"small", b"x"),
        ]
        p = _write(tmp_path / "db", items)
        with LmdbReader(p) as r:
            assert dict(r) == dict(items)

    def test_empty_db(self, tmp_path):
        p = _write(tmp_path / "db", [])
        with LmdbReader(p) as r:
            assert len(r) == 0
            assert list(r) == []

    def test_nosubdir_file(self, tmp_path):
        p = _write(tmp_path / "data.mdb", [(b"k", b"v")], subdir=False)
        assert os.path.isfile(p)
        with LmdbReader(p) as r:
            assert list(r) == [(b"k", b"v")]


class TestFormatInvariants:
    """Byte-level checks against the published LMDB layout."""

    def test_meta_pages(self, tmp_path):
        p = _write(tmp_path / "db", [(b"k", b"v")])
        raw = open(os.path.join(p, "data.mdb"), "rb").read()
        assert len(raw) % 4096 == 0
        for pgno in (0, 1):
            off = pgno * 4096
            # page header: pgno, pad, flags(P_META=0x08)
            hdr_pgno, _, flags, _, _ = struct.unpack_from("<QHHHH", raw, off)
            assert hdr_pgno == pgno and flags == 0x08
            magic, version = struct.unpack_from("<II", raw, off + 16)
            assert magic == 0xBEEFC0DE and version == 1
        # dual-meta rule: differing txnids, reader takes the newer
        tail = 16 + 24 + 2 * 48
        txn0 = struct.unpack_from("<Q", raw, tail + 8)[0]
        txn1 = struct.unpack_from("<Q", raw, 4096 + tail + 8)[0]
        assert {txn0, txn1} == {0, 1}

    def test_leaf_page_flags_and_node(self, tmp_path):
        p = _write(tmp_path / "db", [(b"key0", b"val0")])
        raw = open(os.path.join(p, "data.mdb"), "rb").read()
        # single-leaf DB: root page is page 2, a LEAF (0x02)
        _, _, flags, lower, upper = struct.unpack_from("<QHHHH", raw, 2 * 4096)
        assert flags == 0x02
        n = (lower - 16) // 2
        assert n == 1
        (ptr,) = struct.unpack_from("<H", raw, 2 * 4096 + 16)
        assert ptr == upper
        lo, hi, nflags, ksize = struct.unpack_from("<HHHH", raw, 2 * 4096 + ptr)
        assert (lo | hi << 16) == 4 and nflags == 0 and ksize == 4
        node = raw[2 * 4096 + ptr + 8 :][:8]
        assert node == b"key0val0"

    def test_detection(self, tmp_path):
        p = _write(tmp_path / "db", [(b"k", b"v")])
        assert is_lmdb(p)
        other = tmp_path / "not_lmdb"
        other.write_bytes(b"\x00" * 8192)
        assert not is_lmdb(str(other))

    def test_corrupt_magic_rejected(self, tmp_path):
        p = _write(tmp_path / "db", [(b"k", b"v")])
        f = os.path.join(p, "data.mdb")
        raw = bytearray(open(f, "rb").read())
        raw[16:20] = b"\x00\x00\x00\x00"  # meta 0 magic
        raw[4096 + 16 : 4096 + 20] = b"\x00\x00\x00\x00"  # meta 1 magic
        open(f, "wb").write(bytes(raw))
        with pytest.raises(ValueError, match="meta"):
            LmdbReader(f if os.path.isfile(f) else p)


class TestWriterValidation:
    def test_key_bounds(self, tmp_path):
        w = LmdbWriter(str(tmp_path / "db"))
        with pytest.raises(ValueError, match="key length"):
            w.put(b"", b"v")
        with pytest.raises(ValueError, match="key length"):
            w.put(b"k" * 512, b"v")

    def test_duplicate_key_last_wins(self, tmp_path):
        p = _write(tmp_path / "db", [(b"k", b"first"), (b"k", b"second")])
        with LmdbReader(p) as r:
            assert dict(r) == {b"k": b"second"}


class TestDataLayerIngest:
    """The round-trip: a fixture LMDB (Caffe Datum values) feeds
    the Data-layer minibatch path unchanged."""

    def _images(self, n, shape=(3, 8, 8)):
        rs = np.random.RandomState(0)
        return [
            (rs.randint(0, 255, shape).astype(np.uint8), i % 10)
            for i in range(n)
        ]

    @pytest.mark.smoke
    def test_lmdb_feeds_db_minibatches(self, tmp_path):
        samples = self._images(20)
        p = str(tmp_path / "caffe_lmdb")
        n = create_db(p, samples, backend="lmdb")
        assert n == 20 and is_lmdb(p)
        batches = list(db_minibatches(p, 8))
        assert len(batches) == 2  # 20 // 8, remainder dropped
        np.testing.assert_array_equal(
            batches[0]["data"][0], samples[0][0].astype(np.float32)
        )
        assert batches[0]["label"][:4].tolist() == [0, 1, 2, 3]

    def test_lmdb_values_are_real_datums(self, tmp_path):
        samples = self._images(3)
        p = str(tmp_path / "caffe_lmdb")
        create_db(p, samples, backend="lmdb")
        with LmdbReader(p) as r:
            for (key, value), (img, label) in zip(r, samples):
                arr, lab = datum_to_array(value)
                np.testing.assert_array_equal(arr, img)
                assert lab == label

    def test_convert_lmdb_to_recorddb(self, tmp_path):
        samples = self._images(12)
        src = str(tmp_path / "caffe_lmdb")
        dst = str(tmp_path / "native.rdb")
        create_db(src, samples, backend="lmdb")
        n = convert_db(src, dst, backend="record")
        assert n == 12
        batches = list(db_minibatches(dst, 12))
        np.testing.assert_array_equal(
            batches[0]["data"], np.stack([s[0] for s in samples]).astype(np.float32)
        )

    def test_convert_recorddb_to_lmdb(self, tmp_path):
        samples = self._images(7)
        src = str(tmp_path / "native.rdb")
        dst = str(tmp_path / "out_lmdb")
        create_db(src, samples, backend="record")
        n = convert_db(src, dst, backend="lmdb")
        assert n == 7 and is_lmdb(dst)
        with LmdbReader(dst) as r:
            arr, lab = datum_to_array(dict(r)[b"00000003"])
            np.testing.assert_array_equal(arr, samples[3][0])
            assert lab == 3

    def test_cli_convert_db(self, tmp_path, capsys):
        import json

        from sparknet_tpu.cli import main

        samples = self._images(5)
        src = str(tmp_path / "caffe_lmdb")
        dst = str(tmp_path / "native.rdb")
        create_db(src, samples, backend="lmdb")
        assert main(["convert_db", "--src", src, "--dst", dst]) == 0
        out = json.loads(capsys.readouterr().out.strip())
        assert out["records"] == 5


def test_cli_train_from_lmdb(tmp_path, capsys, monkeypatch):
    """tpunet train --data db:<lmdb> — the CifarDBApp flow end to end
    from a real Caffe-format LMDB through the CLI."""
    import numpy as np

    monkeypatch.chdir(tmp_path)  # cmd_train writes its event log to cwd

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [
        (rs.randint(0, 255, (1, 28, 28)).astype(np.uint8), i % 10)
        for i in range(64)
    ]
    p = str(tmp_path / "train_lmdb")
    create_db(p, samples, backend="lmdb")
    out = str(tmp_path / "model")
    assert main([
        "train", "--solver", "zoo:lenet", "--batch", "16",
        "--iterations", "2", "--data", f"db:{p}", "--output", out,
    ]) == 0


def _write_tiny_data_net(tmp_path, *, source, batch=4, num_output=3,
                         transform_param="", name="tiny"):
    """The minimal Data-layer train_val + solver pair the CLI tests share
    (only source/batch/transform vary per case)."""
    tp = (f"  transform_param {{ {transform_param} }}\n"
          if transform_param else "")
    (tmp_path / "net.prototxt").write_text(
        f'name: "{name}"\n'
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        f'  data_param {{ source: "{source}" batch_size: {batch} }}\n'
        f"{tp}"
        "}\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
        f"  inner_product_param {{ num_output: {num_output} }} }}\n"
        'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
        'bottom: "label" top: "loss" }\n'
    )
    (tmp_path / "solver.prototxt").write_text(
        'net: "net.prototxt"\nbase_lr: 0.01\nmax_iter: 2\ndisplay: 0\n'
    )
    return str(tmp_path / "solver.prototxt")


def test_cli_train_data_layer_prototxt_from_db(tmp_path, capsys, monkeypatch):
    """A reference-style train_val prototxt whose source is a DB-backed
    ``Data`` layer (no declared geometry anywhere) trains end to end:
    the CLI peeks the first datum of --data db: for the blob shape, the
    way Caffe's DataLayerSetUp reads datum 0 (ref: data_layer.cpp:40-48)."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [
        (rs.randint(0, 255, (3, 12, 12)).astype(np.uint8), i % 4)
        for i in range(32)
    ]
    db = str(tmp_path / "train_lmdb")
    create_db(db, samples, backend="lmdb")

    _write_tiny_data_net(tmp_path, source="missing_on_this_host_lmdb",
                         batch=8, num_output=4, name="dbnet")
    assert main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--data", f"db:{db}", "--iterations", "2",
        "--output", str(tmp_path / "out"),
    ]) == 0
    assert (tmp_path / "out.solverstate.npz").exists()


def test_cli_train_data_layer_crop_from_db(tmp_path, monkeypatch):
    """transform_param.crop_size on a Data layer: records larger than the
    net's blob are cropped host-side (random in TRAIN / center in TEST,
    ref: data_transformer.cpp:49,83) — the AlexNet-from-256-pixel-DB
    recipe in miniature."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 16, 16)).astype(np.uint8), i % 4)
               for i in range(24)]
    db = str(tmp_path / "big_lmdb")
    create_db(db, samples, backend="lmdb")

    _write_tiny_data_net(
        tmp_path, source="not_here_lmdb", batch=8, num_output=4,
        transform_param="crop_size: 10 mirror: true scale: 0.0039",
        name="cropnet")
    assert main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--data", f"db:{db}", "--iterations", "2",
        "--output", str(tmp_path / "out"),
    ]) == 0


def test_cli_train_data_proto_streams_own_source(tmp_path, monkeypatch):
    """``tpunet train --solver x --data proto`` with a Data-layer net whose
    data_param.source is on disk = the ``caffe train --solver=x`` flow:
    the net's own DB streams, transform_param applies, nothing else needed
    (ref: data_layer.cpp DataReader + DataTransformer)."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 14, 14)).astype(np.uint8), i % 3)
               for i in range(16)]
    create_db(str(tmp_path / "own_lmdb"), samples, backend="lmdb")

    _write_tiny_data_net(
        tmp_path, source="own_lmdb", batch=4,
        transform_param="crop_size: 12 scale: 0.0039", name="selffeed")
    assert main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--data", "proto", "--iterations", "2",
        "--output", str(tmp_path / "out"),
    ]) == 0
    assert (tmp_path / "out.solverstate.npz").exists()


def test_cli_data_auto_streams_own_source(tmp_path, monkeypatch, capsys):
    """Default --data (auto): a prototxt whose Data layer has a readable
    source trains from IT — `caffe train --solver=x` semantics — with no
    data flag at all."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 10, 10)).astype(np.uint8), i % 3)
               for i in range(12)]
    create_db(str(tmp_path / "auto_lmdb"), samples, backend="lmdb")
    _write_tiny_data_net(tmp_path, source="auto_lmdb", name="auto")
    assert main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--iterations", "2", "--output", str(tmp_path / "out"),
    ]) == 0


def test_cli_time_and_extract_features_db_peek(tmp_path, monkeypatch, capsys):
    """Every brew shares the DB-geometry peek: `time --hlo` and
    `extract_features` on a Data-layer prototxt + --data db: work like
    train/test do."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    import json

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 10, 10)).astype(np.uint8), i % 3)
               for i in range(16)]
    db = str(tmp_path / "peek_lmdb")
    create_db(db, samples, backend="lmdb")
    _write_tiny_data_net(tmp_path, source="elsewhere_lmdb", name="peek")
    assert main(["time", "--hlo", "--solver", str(tmp_path / "solver.prototxt"),
                 "--data", f"db:{db}"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["flops_per_step"] > 0 and out["batch"] == 4

    assert main(["extract_features",
                 "--solver", str(tmp_path / "solver.prototxt"),
                 "--data", f"db:{db}", "--blob", "ip",
                 "--iterations", "2",
                 "--out", str(tmp_path / "f.npy")]) == 0
    feats = np.load(tmp_path / "f.npy")
    assert feats.shape == (8, 3)  # 2 batches x 4, ip num_output 3


def test_cli_data_auto_missing_source_is_loud(tmp_path, monkeypatch):
    """auto must NOT fall back to random noise when the net points at a
    source that cannot stream — that silent substitution would train a
    garbage model."""
    import pytest

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main

    (tmp_path / "net.prototxt").write_text(
        'name: "x"\n'
        'layer { name: "d" type: "ImageData" top: "data" top: "label"\n'
        '  image_data_param { source: "no_such_list.txt" batch_size: 2 }\n'
        "  transform_param { crop_size: 4 } }\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
        "  inner_product_param { num_output: 2 } }\n"
        'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
        'bottom: "label" top: "loss" }\n'
    )
    (tmp_path / "solver.prototxt").write_text(
        'net: "net.prototxt"\nbase_lr: 0.01\nmax_iter: 1\n'
    )
    with pytest.raises(SystemExit, match="cannot stream"):
        main(["train", "--solver", str(tmp_path / "solver.prototxt"),
              "--iterations", "1"])


def test_data_layer_peeks_its_own_source(tmp_path, monkeypatch):
    """When data_param.source IS on disk, the net shape-infers with no
    feed help at all — Network.feed_shapes() carries the peeked geometry
    (with transform_param crop applied, ref: data_transformer
    InferBlobShape)."""
    import numpy as np

    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.data.createdb import create_db
    from sparknet_tpu.proto.text_format import parse

    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 16, 16)).astype(np.uint8), 0)
               for _ in range(4)]
    db = str(tmp_path / "src_lmdb")
    create_db(db, samples, backend="lmdb")

    net = parse(
        'name: "n"\n'
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        f'  data_param {{ source: "{db}" batch_size: 6 }}\n'
        "  transform_param { crop_size: 10 }\n"
        "}\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
        "  inner_product_param { num_output: 2 } }\n"
        'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
        'bottom: "label" top: "loss" }\n'
    )
    shapes = Network(net, Phase.TRAIN).feed_shapes()
    assert shapes["data"] == (6, 3, 10, 10)
    assert shapes["label"] == (6,)


def test_cli_train_db_shape_mismatch(tmp_path, monkeypatch):
    import numpy as np
    import pytest

    monkeypatch.chdir(tmp_path)  # cmd_train writes its event log to cwd

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 8, 8)).astype(np.uint8), 0)
               for _ in range(8)]
    p = str(tmp_path / "bad_lmdb")
    create_db(p, samples, backend="lmdb")
    with pytest.raises(SystemExit, match="do not match"):
        main(["train", "--solver", "zoo:lenet", "--batch", "4",
              "--iterations", "1", "--data", f"db:{p}"])


def test_peek_db_shape_invalidates_on_rebuild(tmp_path):
    """A DB rebuilt at the same path in-process (CifarDBApp
    re-materialize, convert_db, tests) must not serve stale geometry
    from the peek cache (ADVICE r3: createdb lru_cache by path)."""
    import shutil
    import time

    import numpy as np

    from sparknet_tpu.data.createdb import create_db, peek_db_shape

    rs = np.random.RandomState(0)
    p = str(tmp_path / "db")
    create_db(p, [(rs.randint(0, 255, (1, 8, 8)).astype(np.uint8), 0)])
    assert peek_db_shape(p) == (1, 8, 8)
    shutil.rmtree(p, ignore_errors=True) or os.path.exists(p) and os.remove(p)
    time.sleep(0.01)  # ensure a distinct mtime_ns on coarse filesystems
    create_db(p, [(rs.randint(0, 255, (3, 12, 12)).astype(np.uint8), 0)])
    assert peek_db_shape(p) == (3, 12, 12)


def test_cli_test_stream_honors_test_phase_transform(tmp_path, monkeypatch):
    """A TEST-phase Data layer declaring its OWN transform_param (here a
    different crop) drives the test stream; before the r4 fix the TRAIN
    layer's params were applied to both phases (ADVICE r3: cli db:
    branch), which mis-shapes the eval feed."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [
        (rs.randint(0, 255, (3, 12, 12)).astype(np.uint8), i % 4)
        for i in range(32)
    ]
    db = str(tmp_path / "lmdb")
    create_db(db, samples, backend="lmdb")

    (tmp_path / "net.prototxt").write_text(
        'name: "phases"\n'
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        '  include { phase: TRAIN }\n'
        f'  data_param {{ source: "{db}" batch_size: 8 }}\n'
        "  transform_param { crop_size: 10 }\n"
        "}\n"
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        '  include { phase: TEST }\n'
        f'  data_param {{ source: "{db}" batch_size: 8 }}\n'
        "  transform_param { crop_size: 8 }\n"
        "}\n"
        'layer { name: "conv" type: "Convolution" bottom: "data" top: "c"\n'
        "  convolution_param { num_output: 2 kernel_size: 3 } }\n"
        'layer { name: "pool" type: "Pooling" bottom: "c" top: "p"\n'
        "  pooling_param { pool: AVE global_pooling: true } }\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "p" top: "ip"\n'
        "  inner_product_param { num_output: 4 } }\n"
        'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
        'bottom: "label" top: "loss" }\n'
    )
    (tmp_path / "solver.prototxt").write_text(
        'net: "net.prototxt"\nbase_lr: 0.01\nmax_iter: 2\ndisplay: 0\n'
    )
    assert main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--data", f"db:{db}", "--iterations", "2", "--test-iters", "1",
        "--output", str(tmp_path / "out"),
    ]) == 0


def test_cli_test_phase_without_transform_gets_defaults(tmp_path,
                                                        monkeypatch):
    """A TEST-phase Data layer with NO transform_param gets Caffe's
    defaults (no crop) — it must not inherit the TRAIN declaration."""
    import numpy as np

    monkeypatch.chdir(tmp_path)

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    rs = np.random.RandomState(0)
    samples = [
        (rs.randint(0, 255, (3, 12, 12)).astype(np.uint8), i % 4)
        for i in range(32)
    ]
    db = str(tmp_path / "lmdb")
    create_db(db, samples, backend="lmdb")

    (tmp_path / "net.prototxt").write_text(
        'name: "defaults"\n'
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        '  include { phase: TRAIN }\n'
        f'  data_param {{ source: "{db}" batch_size: 8 }}\n'
        "  transform_param { crop_size: 10 }\n"
        "}\n"
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        '  include { phase: TEST }\n'
        f'  data_param {{ source: "{db}" batch_size: 8 }}\n'
        "}\n"
        'layer { name: "conv" type: "Convolution" bottom: "data" top: "c"\n'
        "  convolution_param { num_output: 2 kernel_size: 3 } }\n"
        'layer { name: "pool" type: "Pooling" bottom: "c" top: "p"\n'
        "  pooling_param { pool: AVE global_pooling: true } }\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "p" top: "ip"\n'
        "  inner_product_param { num_output: 4 } }\n"
        'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
        'bottom: "label" top: "loss" }\n'
    )
    (tmp_path / "solver.prototxt").write_text(
        'net: "net.prototxt"\nbase_lr: 0.01\nmax_iter: 2\ndisplay: 0\n'
    )
    assert main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--data", f"db:{db}", "--iterations", "2", "--test-iters", "1",
        "--output", str(tmp_path / "out"),
    ]) == 0
