"""Destination passing in the host feed: the cursor writes each record
once, into the array its consumer hands it.

``db_minibatches`` fresh and into a destination, ``cli._stack_tau`` /
``_widen_batch`` over their one persistent buffer, and the
``DevicePrefetcher``'s ring of host batches, all against the records the
DB was written from.  Everything runs on the CPU, where ``device_put``
may alias host memory: the ring is also forced on through a
``device_put`` that copies.
"""

import itertools
import json

import jax
import numpy as np
import pytest

from sparknet_tpu import cli
from sparknet_tpu.data import prefetch
from sparknet_tpu.data.createdb import _open_reader, create_db, db_minibatches
from sparknet_tpu.data.prefetch import DevicePrefetcher, fresh_bytes
from sparknet_tpu.obs.recorder import Recorder, set_recorder

BATCH = 6
RECORDS = 48  # 8 batches an epoch
IMAGE = (3, 16, 16)

NET = (
    'name: "dest"\n'
    'layer { name: "d" type: "Data" top: "data" top: "label"\n'
    f'  data_param {{ source: "unused" batch_size: {BATCH} }}\n'
    "  transform_param { crop_size: 12 mirror: true } }\n"
    'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
    "  inner_product_param { num_output: 4 } }\n"
    'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
    'bottom: "label" top: "loss" }\n'
)


def samples(n, shape=IMAGE, seed=0):
    rs = np.random.RandomState(seed)
    return [(rs.randint(0, 256, shape).astype(np.uint8), i % 7)
            for i in range(n)]


def base_pointer(a):
    return a.__array_interface__["data"][0]


# ------------------------------------------------------------ the cursor
@pytest.mark.parametrize("loop", [False, True])
@pytest.mark.parametrize("drop_remainder", [True, False])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("backend", ["record", "lmdb", "leveldb"])
def test_fresh_and_into_a_destination_equal_the_records(
        tmp_path, backend, dtype, drop_remainder, loop):
    batch, n = 4, 10
    path = str(tmp_path / backend)
    create_db(path, samples(n, (3, 5, 7)), backend=backend)
    db, decode = _open_reader(path)
    with db:  # record by record, through the backend's own decoder
        records = [(np.array(img), label)
                   for img, label in (decode(v) for _, v in db)]
    assert len(records) == n
    epoch = [records[i:i + batch] for i in range(0, n, batch)]
    if drop_remainder:
        epoch = [b for b in epoch if len(b) == batch]
    want = epoch * 3 if loop else epoch

    def check(got, rows):
        assert got["data"].dtype == dtype and got["label"].dtype == np.int32
        assert got["data"].shape == (len(rows), 3, 5, 7)
        np.testing.assert_array_equal(
            got["data"], np.stack([r[0] for r in rows]).astype(dtype))
        np.testing.assert_array_equal(got["label"], [r[1] for r in rows])

    def open_cursor():
        return db_minibatches(path, batch, loop=loop,
                              drop_remainder=drop_remainder, dtype=dtype)

    # the plain call: fresh arrays, the caller's own
    fresh = list(itertools.islice(open_cursor(), len(want)))
    assert len(fresh) == len(want)
    for got, rows in zip(fresh, want):
        check(got, rows)
    for a, b in itertools.combinations(fresh, 2):
        assert not np.shares_memory(a["data"], b["data"])
        assert not np.shares_memory(a["label"], b["label"])
    if not loop:
        assert len(list(open_cursor())) == len(want)  # and then it ends

    # into a destination: the same batches, in the arrays handed in
    cursor = open_cursor()
    check(next(cursor), want[0])
    outs = [{"data": np.full((batch, 3, 5, 7), 255, dtype),
             "label": np.full(batch, -1, np.int32)} for _ in range(2)]
    for i, rows in enumerate(want[1:]):
        out = outs[i % 2]
        got = cursor.send(out)
        check(got, rows)
        assert fresh_bytes(got, out) == 0
        if len(rows) == batch:
            assert got["data"] is out["data"] and got["label"] is out["label"]
    if not loop:
        with pytest.raises(StopIteration):
            cursor.send(outs[0])


def test_a_destination_of_the_wrong_size_or_shape_is_refused(tmp_path):
    path = str(tmp_path / "db")
    create_db(path, samples(8, (3, 5, 7)))
    for shape, labels in (((3, 3, 5, 7), 3), ((4, 3, 5, 7), 3),
                          ((4, 1, 5, 7), 4), ((4, 3, 5), 4)):
        cursor = db_minibatches(path, 4, loop=True, dtype=np.uint8)
        next(cursor)
        with pytest.raises(ValueError):
            cursor.send({"data": np.zeros(shape, np.uint8),
                         "label": np.zeros(labels, np.int32)})
    with pytest.raises(TypeError):  # python's own rule for a generator
        db_minibatches(path, 4, dtype=np.uint8).send(
            {"data": np.zeros((4, 3, 5, 7), np.uint8),
             "label": np.zeros(4, np.int32)})


def test_records_of_another_shape_than_the_batch_are_refused(tmp_path):
    path = str(tmp_path / "db")
    create_db(path, samples(3, (3, 5, 7)) + samples(1, (3, 5, 8)))
    with pytest.raises(ValueError, match="batch holds"):
        next(db_minibatches(path, 4))


def test_a_record_view_is_read_only_and_equals_the_copy(tmp_path):
    from sparknet_tpu.native import RecordDB

    path = str(tmp_path / "db")
    create_db(path, samples(5, (3, 5, 7)))
    with RecordDB(path) as db:
        copies = list(db)
        views = [(k, bytes(v)) for k, v in db.views()]
        assert views == copies and all(
            isinstance(v, bytes) for _, v in copies)
        for _, v in db.views():
            assert v.readonly and not np.frombuffer(v, np.uint8).flags.writeable


# ------------------------------------------------- the front door's data fn
@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """A RecordDB of uint8 16x16 records, a net that crops them on the
    device, and the ``tpunet train`` flags that name both."""
    tmp = tmp_path_factory.mktemp("dest")
    records = samples(RECORDS)
    db = str(tmp / "db")
    create_db(db, records)
    (tmp / "net.prototxt").write_text(NET)
    (tmp / "solver.prototxt").write_text(
        f'net: "{tmp}/net.prototxt"\nbase_lr: 0.01\nmax_iter: 100\n'
        "display: 0\n")
    flags = ["--solver", str(tmp / "solver.prototxt"), "--data", f"db:{db}",
             "--prefetch", "3", "--augment", "device"]
    return flags, records


def with_train_fn(flags, body):
    """``body(new_train_fn)`` where ``tpunet train <flags>`` would train:
    every ``new_train_fn()`` is the front door's host data fn, on a cursor
    of its own from the DB's first record."""
    def as_train(args):
        net_param, solver_cfg = cli._build_net_and_solver(args)
        solver = cli._make_solver(solver_cfg, net_param, args)
        body(lambda: cli._data_fns(args, solver.train_net,
                                   test_net=solver.test_net)[0])
        return 0

    orig = cli.cmd_train
    cli.cmd_train = as_train
    try:
        assert cli.main(["train", *flags]) == 0
    finally:
        cli.cmd_train = orig


def batch_of(records, i):
    """Batch ``i`` of the looping cursor, from the records themselves."""
    rows = [records[(i * BATCH + j) % RECORDS] for j in range(BATCH)]
    return (np.stack([r[0] for r in rows]),
            np.asarray([r[1] for r in rows], np.int32))


def journaled(path, name):
    """``{count: value}`` of every ``name`` span in an armed journal."""
    with open(path) as f:
        events = [json.loads(line) for line in f]
    return [dict((kv.split("=")[0], int(kv.split("=")[1]))
                 for kv in e["note"].split())
            for e in events if e["event"] == "span" and e["name"] == name]


def test_the_db_data_fn_takes_a_destination_and_says_what_it_allocated(
        job, tmp_path):
    flags, records = job
    journal = str(tmp_path / "journal.jsonl")

    def body(new_train_fn):
        fn = new_train_fn()
        assert fn.takes_out is True
        rec = set_recorder(Recorder(journal, run_id="t"))
        try:
            first, second = fn(0), fn(1)  # the plain call: the caller's own
            out = {"data": np.zeros((BATCH, *IMAGE), np.uint8),
                   "label": np.zeros(BATCH, np.int32)}
            third = fn(2, out=out)
        finally:
            rec.close()
            set_recorder(None)
        assert not np.shares_memory(first["data"], second["data"])
        assert third["data"] is out["data"]
        for i, got in enumerate((first, second, third)):
            np.testing.assert_array_equal(got["data"], batch_of(records, i)[0])
            np.testing.assert_array_equal(got["label"], batch_of(records, i)[1])

    with_train_fn(flags, body)
    nbytes = BATCH * (3 * 16 * 16 + 4)
    reads = journaled(journal, "sn.feed.read")
    assert [r["alloc_bytes"] for r in reads] == [nbytes, nbytes, 0]
    assert [r["images"] for r in reads] == [BATCH] * 3


# ----------------------------------------------------- the trainer's packs
@pytest.mark.parametrize("hook", [True, False])
@pytest.mark.parametrize("pack", ["stack_tau", "widen_batch"])
def test_a_pack_fills_one_buffer_and_equals_the_plain_batches(
        job, tmp_path, pack, hook):
    flags, records = job
    tau, workers, calls = (3, 2, 3) if pack == "stack_tau" else (1, 2, 4)
    journal = str(tmp_path / "journal.jsonl")
    got = []

    def body(new_train_fn):
        train_fn = new_train_fn()
        if not hook:  # a data fn that cannot take a destination
            inner = train_fn
            train_fn = lambda it: inner(it)  # noqa: E731
        fn = (cli._stack_tau(train_fn, tau, workers) if pack == "stack_tau"
              else cli._widen_batch(train_fn, workers))
        rec = set_recorder(Recorder(journal, run_id="t"))
        try:
            for it in range(calls):
                feeds = fn(it)
                got.append(({k: v.copy() for k, v in feeds.items()},
                            {k: base_pointer(v) for k, v in feeds.items()}))
        finally:
            rec.close()
            set_recorder(None)

    with_train_fn(flags, body)
    per_call = tau * workers
    for call, (feeds, _) in enumerate(got):
        # np.stack / np.concatenate of the same plain batches, in order:
        # tau x workers fresh ones a call
        plain = [batch_of(records, call * per_call + i)
                 for i in range(per_call)]
        for key, part in (("data", 0), ("label", 1)):
            want = np.stack([
                np.concatenate([plain[t * workers + w][part]
                                for w in range(workers)])
                for t in range(tau)])
            if pack == "widen_batch":
                want = want[0]
            np.testing.assert_array_equal(feeds[key], want)
            assert feeds[key].dtype == want.dtype
    assert len({tuple(sorted(p.items())) for _, p in got}) == 1  # one buffer

    nbytes = BATCH * (3 * 16 * 16 + 4)
    reads = journaled(journal, "sn.feed.read")
    stacks = journaled(journal, "sn.feed.stack")
    assert len(reads) == calls * per_call and len(stacks) == calls * tau
    assert all(s["images"] == workers * BATCH for s in stacks)
    # the first call makes the buffer (and reads its first batch to learn
    # the shapes); nothing batch-sized is allocated by the pack after that
    assert [s["alloc_bytes"] for s in stacks] == (
        [per_call * nbytes] + [0] * (len(stacks) - 1))
    if hook:
        assert [r["alloc_bytes"] for r in reads] == (
            [nbytes] + [0] * (len(reads) - 1))
    else:
        assert all(r["alloc_bytes"] == nbytes for r in reads)


def test_widen_batch_keeps_as_many_batches_as_it_is_asked_to(job):
    flags, records = job
    held = []

    def body(new_train_fn):
        fn = cli._widen_batch(new_train_fn(), 2, keep=3)
        held.extend(fn(it) for it in range(3))  # a scan chunk's worth
        held.append({k: v.copy() for k, v in fn(3).items()})

    with_train_fn(flags, body)
    for it, feeds in enumerate(held):
        want = np.concatenate([batch_of(records, 2 * it + w)[0]
                               for w in range(2)])
        if it == 0:  # the fourth call took the first one's slot
            want = np.concatenate([batch_of(records, 6 + w)[0]
                                   for w in range(2)])
        np.testing.assert_array_equal(feeds["data"], want)


# ----------------------------------------------------- the prefetcher's ring
def force_the_ring_on(monkeypatch):
    """A ``device_put`` that copies, as a chip's does, and a prefetcher
    that believes it; the host arrays it was given, in order."""
    sources = []
    real = jax.device_put

    def copying_put(x, *a, **k):
        sources.append(base_pointer(x["data"]))
        return real(jax.tree_util.tree_map(np.array, x), *a, **k)

    monkeypatch.setattr(prefetch.jax, "device_put", copying_put)
    monkeypatch.setattr(prefetch, "_aliases_host", lambda placed: False)
    return sources


@pytest.mark.parametrize("ring", ["aliasing_cpu", "forced_on"])
def test_no_placed_batch_is_overwritten_while_queued_or_held(
        job, monkeypatch, ring):
    flags, records = job
    n = 4 * prefetch.RING + 5
    if ring == "forced_on":
        sources = force_the_ring_on(monkeypatch)
    held = []

    def body(new_train_fn):
        with DevicePrefetcher(new_train_fn(), n, depth=3) as pf:
            held.extend(pf)  # the consumer keeps every batch it was given

    with_train_fn(flags, body)
    assert len(held) == n
    for i, feeds in enumerate(held):
        np.testing.assert_array_equal(np.asarray(feeds["data"]),
                                      batch_of(records, i)[0])
        np.testing.assert_array_equal(np.asarray(feeds["label"]),
                                      batch_of(records, i)[1])
    if ring == "forced_on":
        # the first batch fresh, then the ring's slots in turn
        assert len(sources) == n
        assert len(set(sources[1:])) == prefetch.RING
        assert sources[1:] == (sources[1:1 + prefetch.RING] * n)[:n - 1]


def test_a_slot_whose_placed_batch_was_deleted_is_given_up(job, monkeypatch):
    flags, records = job
    sources = force_the_ring_on(monkeypatch)
    got = []

    def body(new_train_fn):
        with DevicePrefetcher(new_train_fn(), 8, depth=1) as pf:
            for feeds in pf:
                got.append(np.asarray(feeds["data"]).copy())
                feeds["data"].delete()  # as a donating consumer would

    with_train_fn(flags, body)
    for i, data in enumerate(got):
        np.testing.assert_array_equal(data, batch_of(records, i)[0])
    assert len(set(sources)) > 1 + prefetch.RING  # slots were replaced
