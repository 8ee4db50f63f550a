"""Destination passing in the host feed: the cursor writes each record
once, into the array its consumer hands it.

``db_minibatches`` fresh and into a destination, ``rounds.widen_batch``
over its one persistent buffer, ``rounds.stack_tau`` over its three (its feed
thread fills round n+1 while round n is out), and the
``DevicePrefetcher``'s ring of host batches, all against the records the
DB was written from.  Everything runs on the CPU, where ``device_put``
may alias host memory: the ring is also forced on through a
``device_put`` that copies.
"""

import itertools
import json
import threading

import jax
import numpy as np
import pytest

from sparknet_tpu import cli
from sparknet_tpu.data import prefetch
from sparknet_tpu.data.createdb import _open_reader, create_db, db_minibatches
from sparknet_tpu.data.feed import Feed
from sparknet_tpu.data.prefetch import DevicePrefetcher, fresh_bytes
from sparknet_tpu.data.rounds import lock_of, stack_tau, widen_batch
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
    """``widen_batch``: one buffer.  ``stack_tau``: three, in turn."""
    flags, records = job
    tau, workers, calls = (3, 2, 7) if pack == "stack_tau" else (1, 2, 4)
    buffers = 3 if pack == "stack_tau" else 1
    journal = str(tmp_path / "journal.jsonl")
    got = []

    def body(new_train_fn):
        train_fn = new_train_fn()
        if not hook:  # a data fn that cannot take a destination
            inner = train_fn
            train_fn = lambda it: inner(it)  # noqa: E731
        fn = (stack_tau(train_fn, tau, workers) if pack == "stack_tau"
              else widen_batch(train_fn, workers))
        rec = set_recorder(Recorder(journal, run_id="t"))
        try:
            for it in range(calls):
                feeds = fn(it)
                got.append(({k: v.copy() for k, v in feeds.items()},
                            {k: base_pointer(v) for k, v in feeds.items()}))
        finally:
            if pack == "stack_tau":
                fn.close()  # its thread journals its reads
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
    where = [tuple(sorted(p.items())) for _, p in got]
    assert len(set(where)) == buffers
    assert where == (where[:buffers] * calls)[:calls]  # in turn

    # the spans of the rounds handed out (a round feed reads one round
    # more, or part of one, before it is closed)
    nbytes = BATCH * (3 * 16 * 16 + 4)
    reads = journaled(journal, "sn.feed.read")[:calls * per_call]
    stacks = journaled(journal, "sn.feed.stack")[:calls * tau]
    assert len(reads) == calls * per_call and len(stacks) == calls * tau
    assert all(s["images"] == workers * BATCH for s in stacks)
    # the very first read learns the shapes and every buffer is made
    # then (a buffer says so in the first slot it hands out); nothing
    # batch-sized is allocated by the pack after that
    made = [call * tau for call in range(buffers)]
    assert [s["alloc_bytes"] for s in stacks] == [
        per_call * nbytes if i in made else 0 for i in range(len(stacks))]
    if hook:
        assert [r["alloc_bytes"] for r in reads] == [
            nbytes if i == 0 else 0 for i in range(len(reads))]
    else:
        assert all(r["alloc_bytes"] == nbytes for r in reads)
    waits = journaled(journal, "sn.feed.wait")
    if pack == "stack_tau":  # one wait a round, each saying if it waited
        assert [w["it"] for w in waits] == list(range(calls))
        assert all(w["ready"] in (0, 1) for w in waits)
        assert waits[0]["ready"] == 0  # nothing is read before it is asked
    else:
        assert not waits


# ------------------------------------------------ the round feed's thread
TAU, WORKERS = 3, 2
ROUND = TAU * WORKERS  # batches a round


def numbered(on_call=lambda index: None):
    """A data fn whose batch ``index`` holds ``index`` everywhere, and the
    indices it was asked for, in order.  ``on_call(index)`` runs inside
    each call, after the index was noted."""
    seen = []

    def fn(index):
        seen.append(index)
        on_call(index)
        return {"data": np.full((BATCH, 2), index, np.int32),
                "label": np.full(BATCH, index, np.int32)}

    return fn, seen


def batches_of(feeds):
    """The batch indices of one round of ``numbered``, in slot order."""
    return feeds["label"][:, ::BATCH].ravel().tolist()


def test_the_next_round_is_read_before_it_is_asked_for():
    last_of = {n: threading.Event() for n in range(3)}

    def on_call(index):
        if index % ROUND == ROUND - 1 and index // ROUND in last_of:
            last_of[index // ROUND].set()

    data_fn, seen = numbered(on_call)
    fn = stack_tau(data_fn, TAU, WORKERS)
    try:
        assert seen == []  # a feed that is never asked reads nothing
        first = fn(0)
        assert batches_of(first) == list(range(ROUND))
        # round 1 is read to its last batch with nobody asking for it
        assert last_of[1].wait(timeout=30)
        assert batches_of(first) == list(range(ROUND))
    finally:
        fn.close()
    assert seen == list(range(2 * ROUND))  # and not one batch of round 2


def test_the_feed_is_one_round_ahead_and_leaves_the_two_rounds_in_hand_alone():
    rounds = 6
    filled = {n: threading.Event() for n in range(rounds + 1)}
    in_hand = {}  # the last two rounds handed out, by their number

    def untouched():
        for m, feeds in list(in_hand.items()):
            assert batches_of(feeds) == list(range(m * ROUND, (m + 1) * ROUND))

    def on_call(index):
        n, i = divmod(index, ROUND)
        # while round n is read, rounds n-1 and n-2 are in hand (the one
        # being placed and the one that trains): untouched
        untouched()
        if i == ROUND - 1:
            filled[n].set()

    data_fn, seen = numbered(on_call)
    fn = stack_tau(data_fn, TAU, WORKERS)
    try:
        for n in range(rounds):
            in_hand.pop(n - 2, None)  # fenced: its buffer is the feed's
            in_hand[n] = fn(n * TAU)
            untouched()
            # round n+1 is read whole, and there the thread stops: it
            # would write round n+2 into the buffer of round n-1, which
            # is valid until the call after this one returns
            assert filled[n + 1].wait(timeout=30)
            assert max(seen) == (n + 2) * ROUND - 1
            untouched()
    finally:
        fn.close()  # joins: whatever the thread was going to read, it has
    assert seen == list(range((rounds + 1) * ROUND))  # every batch once


def test_two_feeds_over_one_data_fn_neither_raise_nor_lose_a_batch(job):
    """The benchmark's order (jobs/tau_round.py): a one-device and a mesh
    feed over the SAME data fn, a warm-up round each, the one-device
    phase, then the mesh phase while the one-device feed's thread is
    still a round ahead.  ``db_stream`` drives one generator: two threads
    inside it raise ``ValueError: generator already executing``."""
    flags, records = job
    handed = {"one": [], "mesh": []}
    returned = []  # the cursor's batches in the order it gave them

    def body(new_train_fn):
        inner = new_train_fn()

        def train_fn(it, out=None):
            assert lock_of(train_fn).locked()
            feeds = inner(it, out=out)
            feeds["label"][...] = len(returned)  # which of its batches
            returned.append(feeds["data"].copy())
            return feeds

        train_fn.takes_out = True
        one_fn = stack_tau(train_fn, TAU, 1)
        mesh_fn = stack_tau(train_fn, TAU, WORKERS)
        try:
            for name, fn, rounds in (("one", one_fn, 1), ("mesh", mesh_fn, 1),
                                     ("one", one_fn, 4), ("mesh", mesh_fn, 4)):
                for _ in range(rounds):
                    feeds = fn(len(handed[name]) * TAU)
                    handed[name].append(
                        {k: v.copy() for k, v in feeds.items()})
        finally:
            one_fn.close()
            mesh_fn.close()

    with_train_fn(flags, body)
    # the cursor was read in its own order, batch after batch
    for i, data in enumerate(returned):
        np.testing.assert_array_equal(data, batch_of(records, i)[0])
    # every round handed out is TAU x workers of those batches, whole; a
    # feed's own come in the cursor's order, and none went to both
    taken = {}
    for name, workers in (("one", 1), ("mesh", WORKERS)):
        assert len(handed[name]) == 5
        taken[name] = []
        for feeds in handed[name]:
            data = feeds["data"].reshape(TAU * workers, BATCH, *IMAGE)
            labels = feeds["label"].reshape(TAU * workers, BATCH)
            for part, label in zip(data, labels):
                assert len(set(label)) == 1
                np.testing.assert_array_equal(part, returned[label[0]])
                taken[name].append(int(label[0]))
        assert taken[name] == sorted(set(taken[name]))
    assert not set(taken["one"]) & set(taken["mesh"])
    # what was read and not handed out is the two feeds' look-ahead
    left = len(returned) - len(taken["one"]) - len(taken["mesh"])
    assert 0 <= left <= TAU * (1 + WORKERS)


def test_the_data_fns_lock_serves_its_waiters_in_turn():
    """A feed with many reads to make does not starve one with few: the
    thread that releases the lock and asks again at once goes behind the
    one that was already waiting."""
    lock = lock_of(lambda it: None)
    order = []
    asked = threading.Event()

    def waiter():
        asked.set()
        with lock:
            order.append("waiter")

    with lock:
        assert lock.locked()
        thread = threading.Thread(target=waiter, daemon=True)
        thread.start()
        assert asked.wait(timeout=30)
        while lock._next < 2:  # until the waiter has drawn its ticket
            assert thread.is_alive()
    with lock:  # asked for at once, and still served second
        order.append("releaser")
    thread.join(timeout=30)
    assert order == ["waiter", "releaser"] and not lock.locked()


@pytest.mark.parametrize("kind", ["plain", "feed"])
def test_many_feeds_over_one_cursor_share_it_without_a_lost_batch(kind):
    """More feeds than cores over ONE generator, each drained by a thread
    of its own under a short switch interval: a generator entered twice
    raises, a batch counted twice or not at all breaks the census.  One
    lock per data fn, a plain function's (``lock_of``) as a ``Feed``'s."""
    import sys
    import time

    def cursor():
        i = 0
        while True:
            time.sleep(0)  # gives the interpreter away inside the generator
            yield {"data": np.full((BATCH, 2), i, np.int32),
                   "label": np.full(BATCH, i, np.int32)}
            i += 1

    gen = cursor()
    read = []

    def data_fn(_):
        feeds = next(gen)
        read.append(int(feeds["label"][0]))
        return feeds

    if kind == "feed":
        data_fn = Feed(data_fn)

    feeds_n, rounds = 12, 25
    got = [[] for _ in range(feeds_n)]
    errors = []

    def drain(k, fn):
        try:
            for n in range(rounds):
                got[k].extend(batches_of(fn(n * TAU)))
        except BaseException as e:  # told on the main thread, below
            errors.append(e)

    fns = [stack_tau(data_fn, TAU, WORKERS) for _ in range(feeds_n)]
    consumers = [threading.Thread(target=drain, args=(k, fn), daemon=True)
                 for k, fn in enumerate(fns)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for c in consumers:
            c.start()
        for c in consumers:
            c.join(timeout=120)
        assert not any(c.is_alive() for c in consumers)
    finally:
        sys.setswitchinterval(interval)
        for fn in fns:
            fn.close()
    assert not errors, errors
    assert read == list(range(len(read)))  # the cursor's order, no gap
    for mine in got:
        assert len(mine) == rounds * ROUND and mine == sorted(mine)
    handed = sorted(i for mine in got for i in mine)
    assert len(set(handed)) == len(handed) and set(handed) <= set(read)
    # read and not handed out: at most a round ahead per feed
    assert len(read) - len(handed) <= feeds_n * ROUND


@pytest.mark.parametrize("error", [SystemExit, ValueError])
def test_an_error_in_the_data_fn_surfaces_from_the_feed(error):
    def on_call(index):
        if index == ROUND + 2:
            raise error("the cursor failed")

    data_fn, _ = numbered(on_call)
    fn = stack_tau(data_fn, TAU, WORKERS)
    try:
        assert batches_of(fn(0)) == list(range(ROUND))
        for _ in range(2):  # and again, however often it is asked
            with pytest.raises(error, match="the cursor failed"):
                fn(TAU)
    finally:
        fn.close()


def test_close_joins_the_feed_thread():
    before = set(threading.enumerate())
    data_fn, seen = numbered()
    fn = stack_tau(data_fn, TAU, WORKERS)
    fn.close()  # never asked: no thread yet, nothing to join
    assert set(threading.enumerate()) == before and seen == []
    fn(0)
    (thread,) = set(threading.enumerate()) - before
    assert thread.daemon  # a feed nobody closes cannot hold the process
    fn.close()
    assert not thread.is_alive()
    assert len(seen) <= 2 * ROUND
    with pytest.raises(RuntimeError, match="closed"):
        fn(TAU)


def test_rounds_over_the_feed_equal_rounds_over_batches_packed_by_hand(
        job, tmp_path):
    """Three tau-rounds on a CPU mesh of two, fed by ``stack_tau``, and
    three fed the same batches stacked and concatenated by hand: the
    losses and every parameter bit for bit."""
    from sparknet_tpu.parallel.mesh import data_parallel_mesh
    from sparknet_tpu.parallel.trainer import ParallelTrainer

    flags, records = job
    # the job's net with an output per label of its records (0..6) and a
    # rate their raw 0..255 pixels can take
    (tmp_path / "net.prototxt").write_text(
        NET.replace("num_output: 4", "num_output: 7"))
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{tmp_path}/net.prototxt"\nbase_lr: 1e-6\nmax_iter: 100\n'
        "display: 0\n")
    flags = ["--solver", str(solver), *flags[2:]]
    ends = {}

    def by_hand(n):
        plain = [batch_of(records, n * ROUND + i) for i in range(ROUND)]
        return {key: np.stack([
            np.concatenate([plain[t * WORKERS + w][part]
                            for w in range(WORKERS)])
            for t in range(TAU)]) for key, part in (("data", 0), ("label", 1))}

    def as_train(args):
        for feed in ("stack_tau", "by_hand"):
            net_param, solver_cfg = cli._build_net_and_solver(args)
            solver = cli._make_solver(solver_cfg, net_param, args)
            train_fn, _ = cli._data_fns(args, solver.train_net,
                                        test_net=solver.test_net)
            trainer = ParallelTrainer(
                solver, mesh=data_parallel_mesh(WORKERS), tau=args.tau)
            trainer.feed_device_fn = train_fn.trainer_device_fn
            if feed == "stack_tau":
                fn = stack_tau(train_fn, args.tau,
                                    trainer.num_local_workers)
            else:
                fn = lambda it: by_hand(it // TAU)  # noqa: E731
            losses = [trainer.train_round(fn) for _ in range(3)]
            if feed == "stack_tau":
                fn.close()
            ends[feed] = (losses, jax.tree_util.tree_map(
                np.asarray, trainer.variables))
        return 0

    orig = cli.cmd_train
    cli.cmd_train = as_train
    try:
        assert cli.main(["train", *flags, "--tau", str(TAU)]) == 0
    finally:
        cli.cmd_train = orig
    (losses, variables), (want_losses, want) = ends["stack_tau"], ends["by_hand"]
    assert losses == want_losses and all(np.isfinite(losses))
    assert len(set(losses)) == 3  # the rounds saw different batches
    got_leaves, want_leaves = (jax.tree_util.tree_leaves(v)
                               for v in (variables, want))
    assert len(got_leaves) == len(want_leaves) > 0
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(a, b)


def test_widen_batch_keeps_as_many_batches_as_it_is_asked_to(job):
    flags, records = job
    held = []

    def body(new_train_fn):
        fn = widen_batch(new_train_fn(), 2, keep=3)
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
    sources, kept = [], []
    real = jax.device_put

    def copying_put(x, *a, **k):
        sources.append(base_pointer(x["data"]))
        kept.append(x["data"])  # so no later array gets a given-up one's address
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
