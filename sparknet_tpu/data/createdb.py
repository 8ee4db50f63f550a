"""Dataset -> DB materialization and DB-backed minibatch reading.

The reference's alternative "Caffe-native data source" path: executors
write their partition into per-worker LMDB/LevelDBs through the C API
(ref: src/main/scala/preprocessing/CreateDB.scala:10-52, commit every
1000 records) and training reads them through Caffe's own DataLayer
(ref: src/main/scala/apps/CifarDBApp.scala:96-131).  Two backends here:

- ``record`` — the native RecordDB (C++ data plane), value layout:
  little-endian u32 c,h,w, i32 label, then c*h*w raw uint8 pixels (the
  Datum role, ref: caffe.proto:30-41, without the protobuf dependency);
- ``lmdb`` — real LMDB environments with protobuf ``Datum`` values, the
  reference's own format (ref: db_lmdb.cpp), via the clean-room codec in
  :mod:`sparknet_tpu.data.lmdb_io` — existing Caffe datasets load as-is;
- ``leveldb`` — real LevelDB environments (ref: db_leveldb.cpp — the
  backend CifarDBApp/CreateDB actually use), via the clean-room codec in
  :mod:`sparknet_tpu.data.leveldb_io` (log replay + SSTables + snappy
  block decode).

``db_minibatches`` auto-detects the backend per path.
"""

from __future__ import annotations

import functools
import itertools
import os
import struct
from typing import Iterable, Iterator

import numpy as np

from sparknet_tpu.native import RecordDB
from sparknet_tpu.obs import get_recorder

_HDR = struct.Struct("<IIIi")
COMMIT_EVERY = 1000  # ref: CreateDB.scala commit_db_txn cadence


def encode_datum(image: np.ndarray, label: int) -> bytes:
    c, h, w = image.shape
    return _HDR.pack(c, h, w, int(label)) + np.ascontiguousarray(
        image, np.uint8
    ).tobytes()


def decode_datum(value: bytes) -> tuple[np.ndarray, int]:
    c, h, w, label = _HDR.unpack_from(value)
    img = np.frombuffer(value, np.uint8, c * h * w, _HDR.size).reshape(c, h, w)
    return img, label


def create_db(
    path: str,
    samples: Iterable[tuple[np.ndarray, int]],
    commit_every: int = COMMIT_EVERY,
    backend: str = "record",
) -> int:
    """Write (uint8 CHW image, label) samples; returns the record count.

    ``backend='lmdb'`` writes a real LMDB environment with protobuf
    Datum values (Caffe-readable); default is the native RecordDB."""
    writer = _open_writer(path, backend)
    encode = _value_encoder(backend)
    n = 0
    with writer as db:
        for image, label in samples:
            db.put(f"{n:08d}".encode(), encode(image, label))
            n += 1
            if n % commit_every == 0:
                db.commit()
        db.commit()
    return n


def _open_writer(path: str, backend: str):
    if backend == "record":
        return RecordDB(path, "w")
    if backend == "lmdb":
        from sparknet_tpu.data.lmdb_io import LmdbWriter

        return LmdbWriter(path)
    if backend == "leveldb":
        from sparknet_tpu.data.leveldb_io import LevelDbWriter

        return LevelDbWriter(path)
    raise ValueError(
        f"unknown db backend {backend!r} (record | lmdb | leveldb)")


def _value_encoder(backend: str):
    if backend in ("lmdb", "leveldb"):
        from sparknet_tpu.data.io_utils import array_to_datum

        return lambda image, label: array_to_datum(
            np.ascontiguousarray(image, np.uint8), label
        )
    return encode_datum


def _open_reader(path: str):
    """(db, decode) for any backend; LMDB detected by meta magic,
    LevelDB by its CURRENT file (both hold Caffe Datum values)."""
    from sparknet_tpu.data import lmdb_io

    if lmdb_io.is_lmdb(path):
        from sparknet_tpu.data.io_utils import datum_to_array

        return lmdb_io.LmdbReader(path), datum_to_array
    from sparknet_tpu.data import leveldb_io

    if leveldb_io.is_leveldb(path):
        from sparknet_tpu.data.io_utils import datum_to_array

        return leveldb_io.LevelDbReader(path), datum_to_array
    return RecordDB(path, "r"), decode_datum


def convert_db(src: str, dst: str, backend: str = "record") -> int:
    """Re-materialize ``src`` (either backend) as ``dst`` in ``backend``
    format — the LMDB-ingest bridge: existing Caffe LMDBs convert to the
    native RecordDB (or the reverse) with keys preserved."""
    db, decode = _open_reader(src)
    writer = _open_writer(dst, backend)
    encode = _value_encoder(backend)
    n = 0
    with db, writer:
        for key, value in db:
            image, label = decode(value)
            writer.put(key, encode(image, label))
            n += 1
            if n % COMMIT_EVERY == 0:
                writer.commit()
        writer.commit()
    return n


def _db_stamp(path: str) -> tuple:
    """mtime/size fingerprint of the DB path (recursed one level for
    directory-shaped DBs), so the shape cache invalidates when a DB is
    REBUILT at the same path in-process (CifarDBApp re-materialize,
    convert_db, tests) instead of serving stale geometry."""
    try:
        st = os.stat(path)
        stamp = [st.st_mtime_ns, st.st_size]
        if os.path.isdir(path):
            for name in sorted(os.listdir(path)):
                try:
                    s2 = os.stat(os.path.join(path, name))
                    stamp += [name, s2.st_mtime_ns, s2.st_size]
                except OSError:
                    continue
        return tuple(stamp)
    except OSError:
        return ()


def peek_db_shape(path: str) -> tuple[int, ...]:
    """(C, H, W) of the first record — Caffe parity: a DataLayer's blob
    geometry is defined by its DB, read at setup from datum 0 (ref:
    data_layer.cpp:40-48 DataLayerSetUp -> data_transformer InferBlobShape).
    Cached per (path, content fingerprint): shape inference consults it
    from several sites per run, and the fingerprint keys out stale
    entries when the DB is rebuilt at the same path."""
    return _peek_db_shape_cached(path, _db_stamp(path))


@functools.lru_cache(maxsize=64)
def _peek_db_shape_cached(path: str, _stamp: tuple) -> tuple[int, ...]:
    db, decode = _open_reader(path)
    with db:
        for _, value in db:
            image, _ = decode(value)
            return tuple(image.shape)
    raise ValueError(f"record db {path!r} is empty")


def db_mean(path: str, batch_size: int = 256) -> np.ndarray:
    """Mean image over every record in a DB (the compute_image_mean job:
    probe the shape from one record, then stream with the remainder kept)."""
    from sparknet_tpu.data.minibatch import compute_mean_from_minibatches

    try:
        first = next(db_minibatches(path, 1))
    except StopIteration:
        raise ValueError(f"record db {path!r} is empty") from None
    return compute_mean_from_minibatches(
        (
            (b["data"], b["label"])
            for b in db_minibatches(path, batch_size, drop_remainder=False)
        ),
        first["data"].shape[1:],
    )


def db_minibatches(
    path: str,
    batch_size: int,
    loop: bool = False,
    drop_remainder: bool = True,
    dtype=np.float32,
) -> Iterator[dict[str, np.ndarray]]:
    """Feed dicts from a record DB.  ``drop_remainder=True`` (the training
    contract) yields only full batches; ``False`` yields the final short
    batch too (stats passes — compute_image_mean must see every record).
    ``loop=True`` restarts the cursor each epoch (the DataLayer's rewind).
    ``dtype=np.uint8`` hands back raw pixels (skip the float cast when a
    transformer will cast anyway).

    Each record is copied ONCE, from the DB's storage to its row of the
    batch, the cast riding on that assignment.  ``next(gen)`` allocates
    the batch and its caller owns it.  ``gen.send(out)`` hands the cursor
    a destination instead: ``out["data"]`` (``[batch_size, C, H, W]``)
    and ``out["label"]`` (``[batch_size]``), of any dtype, are filled in
    place and come back as the batch (their first rows for a final short
    one), and nothing batch-sized is allocated.  A generator takes no
    ``send`` before its first ``next``."""
    db, decode = _open_reader(path)
    with db:
        if loop and (
            len(db) == 0 or (len(db) < batch_size and drop_remainder)
        ):
            raise ValueError(
                f"db holds {len(db)} records < batch_size {batch_size}; "
                "loop=True would spin forever yielding nothing"
            )
        # RecordDB: views of its storage; LMDB/LevelDB values are bytes
        records = getattr(db, "views", db.__iter__)
        n = 0  # the cursor's own batch index: the spans' ``it``
        out = None  # where the next batch goes; None: a fresh array

        def fill(cursor):
            """Up to ``batch_size`` records, each copied once into its row
            of ``out["data"]`` (a fresh batch's without one); (data,
            labels)."""
            data = out["data"] if out else None
            if out and not len(data) == len(out["label"]) == batch_size:
                raise ValueError(
                    f"destination holds {len(data)} images and "
                    f"{len(out['label'])} labels, the batch is {batch_size}")
            labels = []
            for _, value in itertools.islice(cursor, batch_size):
                img, label = decode(value)
                if data is None:
                    data = np.empty((batch_size, *img.shape), dtype)
                elif img.shape != data.shape[1:]:
                    raise ValueError(
                        f"record {len(labels)} of batch {n} is {img.shape}, "
                        f"the batch holds {data.shape[1:]}")
                data[len(labels)] = img
                labels.append(label)
            return data, labels

        def collate(data, labels):
            count = len(labels)
            with get_recorder().span("sn.feed.collate", host=True, it=n,
                                     images=count):
                if out:
                    out["label"][:count] = labels
                    labels = out["label"]
                else:
                    labels = np.asarray(labels, np.int32)
                if count < batch_size:  # the final short batch
                    data, labels = data[:count], labels[:count]
                return {"data": data, "label": labels}

        while True:
            cursor = records()
            while True:
                # one span per batch around the per-record loop and its
                # one copy, closed before the yield: a span never stays
                # open across one
                with get_recorder().span("sn.feed.decode", host=True,
                                         it=n, images=batch_size):
                    data, labels = fill(cursor)
                if len(labels) < batch_size:
                    break
                out = yield collate(data, labels)
                n += 1
            if labels and not drop_remainder:
                out = yield collate(data, labels)
                n += 1
            if not loop:
                return
