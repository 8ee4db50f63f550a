"""``ParallelTrainer.train_round`` places the tau-round one round ahead:
round n+1 is asked of the data fn, placed and augmented between round n's
dispatch and its fence (``_place_ahead``), over ``rounds.stack_tau``'s
three host buffers (the host-buffer rule, ``data/rounds.py``).

Everything runs on the CPU mesh, where a placed array may BE its host
buffer: the order of the work changes, nothing a run computes does.
"""

import json
import threading

import jax
import numpy as np
import pytest

from sparknet_tpu import cli
from sparknet_tpu.data.createdb import create_db
from sparknet_tpu.data.rounds import stack_tau
from sparknet_tpu.layers_dsl import (
    InnerProductLayer,
    NetParam,
    RDDLayer,
    SoftmaxWithLoss,
)
from sparknet_tpu.obs.recorder import Recorder, set_recorder
from sparknet_tpu.parallel.mesh import data_parallel_mesh
from sparknet_tpu.parallel.trainer import ParallelTrainer
from sparknet_tpu.solvers import Solver, SolverConfig

BATCH = 6
RECORDS = 48
TAU, WORKERS = 2, 2
ROUND = TAU * WORKERS  # batches a round

NET = (
    'name: "ahead"\n'
    'layer { name: "d" type: "Data" top: "data" top: "label"\n'
    f'  data_param {{ source: "unused" batch_size: {BATCH} }}\n'
    "  transform_param { crop_size: 12 mirror: true scale: 0.0039 } }\n"
    'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
    "  inner_product_param { num_output: 4 } }\n"
    'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
    'bottom: "label" top: "loss" }\n'
)


def leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


# ------------------------------------------------ plain feeds, a plain net
def plain_trainer(workers=WORKERS, tau=TAU, elastic_alpha=0.0):
    net = NetParam(
        "ahead",
        RDDLayer("data", shape=[BATCH, 4]),
        RDDLayer("label", shape=[BATCH]),
        InnerProductLayer("ip", ["data"], num_output=4),
        SoftmaxWithLoss("loss", ["ip", "label"]),
    )
    return ParallelTrainer(Solver(SolverConfig(base_lr=1e-4), net),
                           mesh=data_parallel_mesh(workers), tau=tau,
                           elastic_alpha=elastic_alpha)


def round_of(n, workers=WORKERS, slots=TAU):
    """Round ``n``'s feeds: every value of ``data`` is ``n``.  ``slots``:
    the length of the tau axis, None for the tau=1 contract's none."""
    shape = (workers * BATCH,) if slots is None else (slots, workers * BATCH)
    return {"data": np.full((*shape, 4), n, np.float32),
            "label": np.full(shape, n % 4, np.int32)}


def recording(trainer, events, data=round_of):
    """``trainer`` with its put, hook and round program wrapped the way
    the benchmark wraps them (instance attributes, set after it was
    built), and a data fn: each notes when it was called, and for what."""
    put, train = trainer._put_feeds, trainer._train

    def data_fn(it):
        events.append(f"data {it}")
        return data(it // trainer.tau)

    def hook(feeds, it):
        events.append(f"hook {it}")
        return feeds

    def noting(name, fn):
        def wrapped(*a, **k):
            events.append(name)
            return fn(*a, **k)
        return wrapped

    trainer._put_feeds = noting("put", put)
    trainer._train = noting("train", train)
    trainer.feed_device_fn = hook
    return data_fn


def test_the_next_round_is_placed_between_the_dispatch_and_the_fence():
    trainer, events = plain_trainer(), []
    data_fn = recording(trainer, events)
    for n in range(3):
        trainer.train_round(data_fn)
        events.append(f"fenced {n * TAU}")
    stage = lambda it: [f"data {it}", "put", f"hook {it}"]  # noqa: E731
    assert events == [
        *stage(0), "train", *stage(TAU), "fenced 0",  # the first: its own
        "train", *stage(2 * TAU), f"fenced {TAU}",
        "train", *stage(3 * TAU), f"fenced {2 * TAU}"]


@pytest.mark.parametrize("mode,ahead", [
    ("tau", True), ("easgd_tau1", True), ("dp", False)])
def test_only_the_tau_shaped_contract_looks_ahead(mode, ahead):
    tau, alpha, slots = {"tau": (TAU, 0.0, TAU),
                         "easgd_tau1": (1, 0.9 / WORKERS, 1),
                         "dp": (1, 0.0, None)}[mode]
    trainer, events = plain_trainer(tau=tau, elastic_alpha=alpha), []
    data_fn = recording(trainer, events, lambda n: round_of(n, slots=slots))
    for _ in range(3):
        trainer.train_round(data_fn)
    asked = [int(e.split()[1]) for e in events if e.startswith("data")]
    assert asked == [n * tau for n in range(4 if ahead else 3)]
    assert (trainer._ahead is not None) == ahead


def test_a_round_trains_on_the_data_fn_it_was_handed():
    """A caller with a new function every round gets the serial order: a
    round placed ahead from another function is not this call's round."""
    losses, puts = {}, {}
    for kind in ("same", "new"):
        trainer, events = plain_trainer(), []
        recording(trainer, events)  # its data fn is not used
        puts[kind] = lambda events=events: events.count("put")
        data = [round_of(n) for n in (3, 1, 2)]
        if kind == "same":
            fn = lambda it: data[it // TAU]  # noqa: E731
            losses[kind] = [trainer.train_round(fn) for _ in range(3)]
        else:
            losses[kind] = [
                trainer.train_round(lambda it, feeds=feeds: feeds)
                for feeds in data]
    assert losses["same"] == losses["new"]
    assert len(set(losses["same"])) == 3
    # ... and pays for it: every round is also placed once in vain (an
    # app hands the trainer ONE function, tests/test_imagenet_e2e.py)
    assert (puts["same"](), puts["new"]()) == (3, 2 * 3)  # (no 4th round)


@pytest.mark.parametrize("error", [IndexError, SystemExit, ValueError])
def test_an_error_of_the_early_call_is_raised_by_the_round_it_belonged_to(
        error):
    trainer, events = plain_trainer(), []

    def data(n):
        if n >= 3:
            raise error("the feed has ended")
        return round_of(n)

    data_fn = recording(trainer, events, data)
    # a feed of three rounds trains three, as it did in the serial order
    losses = [trainer.train_round(data_fn) for _ in range(3)]
    assert all(np.isfinite(losses)) and trainer.iter == 3 * TAU
    assert events[-1] == f"data {3 * TAU}"  # asked, and held
    for _ in range(2):  # and again, however often it is asked
        with pytest.raises(error, match="the feed has ended"):
            trainer.train_round(data_fn)
        assert trainer.iter == 3 * TAU and events[-1] == f"data {3 * TAU}"
    assert events.count("train") == 3


def test_a_finite_feed_under_stack_tau_trains_the_rounds_it_has():
    batches = [{"data": np.full((BATCH, 4), i, np.float32),
                "label": np.full(BATCH, i % 4, np.int32)}
               for i in range(3 * ROUND)]
    trainer = plain_trainer()
    fn = stack_tau(lambda i: batches[i], TAU, WORKERS)
    try:
        losses = [trainer.train_round(fn) for _ in range(3)]
        assert all(np.isfinite(losses))
        with pytest.raises(IndexError):
            trainer.train_round(fn)
    finally:
        fn.close()


@pytest.mark.parametrize("what,kept", [
    ("set_weights", False), ("restore", False), ("close", False),
    ("sync_to_solver", True),
    ("save", True), ("test", True), ("get_weights", True)])
def test_what_drops_the_round_placed_ahead_and_what_does_not(
        tmp_path, what, kept):
    if what in ("save", "restore"):
        pytest.importorskip("orbax.checkpoint")
    trainer, events = plain_trainer(), []
    data_fn = recording(trainer, events)
    trainer.train_round(data_fn)
    assert trainer._ahead[0] == TAU
    if what == "set_weights":
        trainer.set_weights(trainer.get_weights())
    elif what == "restore":
        trainer.restore(trainer.save(str(tmp_path / "live")))
    elif what == "test":
        trainer.test(1, lambda b: round_of(b, slots=None))
    elif what == "save":
        trainer.save(str(tmp_path / "live"))
    else:
        getattr(trainer, what)()
    assert (trainer._ahead is not None) == kept
    del events[:]
    trainer.train_round(data_fn)
    # kept: straight to the dispatch; dropped: the round stages its own
    own = [] if kept else [f"data {TAU}", "put", f"hook {TAU}"]
    assert events == [*own, "train", f"data {2 * TAU}", "put",
                      f"hook {2 * TAU}"]


def test_a_round_placed_for_another_iteration_is_dropped():
    trainer, events = plain_trainer(), []
    data_fn = recording(trainer, events)
    trainer.train_round(data_fn)
    trainer.iter = 10 * TAU  # whoever moves it
    del events[:]
    trainer.train_round(data_fn)
    assert events[:4] == [f"data {10 * TAU}", "put", f"hook {10 * TAU}",
                          "train"]


def test_the_staged_stat_is_0_on_a_trainers_first_round_and_1_after(
        tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    trainer = plain_trainer()
    rec = set_recorder(Recorder(journal, run_id="t"))
    try:
        fn = lambda it: round_of(it // TAU)  # noqa: E731
        for _ in range(4):
            trainer.train_round(fn)
    finally:
        rec.close()
        set_recorder(None)
    with open(journal) as f:
        events = [json.loads(line) for line in f]
    notes = [e["note"] for e in events
             if e["event"] == "span" and e["name"] == "sn.round.data"]
    assert notes == ["it=0 staged=0"] + [
        f"it={n * TAU} staged=1" for n in range(1, 5)]
    # the round record is the fenced round's own, not the placed one's
    rounds = [e for e in events if e["event"] == "round"]
    assert [r["iteration"] for r in rounds] == [TAU, 2 * TAU, 3 * TAU,
                                                4 * TAU]
    assert all(r["batch"] == WORKERS * BATCH and r["iters"] == TAU
               for r in rounds)


# ------------------------------------------------- the host-buffer rule
@pytest.mark.parametrize("workers", [1, WORKERS])
def test_no_round_is_written_between_its_hand_out_and_its_fence(workers):
    """The feed thread running free under the trainer's own order: it
    never writes a buffer whose round is placed or training, and every
    round trains on its own batches (one device: a placed array may BE
    its buffer)."""
    rounds = 7
    per_round = TAU * workers
    live = {}  # rounds handed out and not fenced yet, by their ``it``
    filled = {n: threading.Event() for n in range(rounds + 3)}

    def data_fn(index, out=None):
        n, i = divmod(index, per_round)
        if out is not None:
            for it, feeds in list(live.items()):
                assert not any(np.shares_memory(out[k], feeds[k])
                               for k in out), (n, it)
            out["data"][...] = index
            out["label"][...] = index % 4
        else:  # the very first batch: nothing to write into yet
            out = {"data": np.full((BATCH, 4), index, np.float32),
                   "label": np.full(BATCH, index % 4, np.int32)}
        if i == per_round - 1:
            filled[n].set()
        return out

    data_fn.takes_out = True
    tau_fn = stack_tau(data_fn, TAU, workers)

    def fn(it):
        live[it] = tau_fn(it)
        return live[it]

    trainer = plain_trainer(workers)
    trained, train = [], trainer._train

    def noting(variables, slots, it, feeds, key):
        trained.append(feeds)
        return train(variables, slots, it, feeds, key)

    trainer._train = noting
    try:
        for n in range(rounds):
            trainer.train_round(fn)
            del live[n * TAU]  # fenced: its buffer is the feed's again
            # the thread has read all it may (the round after the one
            # placed), and the round just trained still held its own
            assert filled[n + 2].wait(timeout=30)
            stamps = np.asarray(trained[n]["data"])[:, ::BATCH, 0]
            assert stamps.ravel().tolist() == list(
                range(n * per_round, (n + 1) * per_round))
    finally:
        tau_fn.close()


# ------------------------------------------ the front door's feed and hook
@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """A RecordDB of uint8 16x16 records, a net that crops and mirrors
    them on the device, and the ``tpunet train`` flags that name both."""
    tmp = tmp_path_factory.mktemp("ahead")
    rs = np.random.RandomState(0)
    db = str(tmp / "db")
    create_db(db, [(rs.randint(0, 255, (3, 16, 16)).astype(np.uint8), i % 4)
                   for i in range(RECORDS)])
    (tmp / "net.prototxt").write_text(NET)
    (tmp / "solver.prototxt").write_text(
        f'net: "{tmp}/net.prototxt"\nbase_lr: 0.01\nmax_iter: 100\n'
        "display: 0\n")
    return ["--solver", str(tmp / "solver.prototxt"), "--data", f"db:{db}",
            "--augment", "device", "--tau", str(TAU)]


def test_rounds_placed_ahead_equal_the_serial_order_bit_for_bit(job):
    """Seven rounds of ``stack_tau`` over a seeded ``db:`` feed with the
    device augment, against a loop that stages, dispatches and fences one
    round at a time with the same ``it`` keys: the same losses, the same
    parameters and momentum, to the bit."""
    ends = {}

    def as_train(args):
        for order in ("ahead", "serial"):
            net_param, solver_cfg = cli._build_net_and_solver(args)
            solver = cli._make_solver(solver_cfg, net_param, args)
            train_fn, _ = cli._data_fns(args, solver.train_net,
                                        test_net=solver.test_net)
            trainer = ParallelTrainer(
                solver, mesh=data_parallel_mesh(WORKERS), tau=args.tau)
            trainer.feed_device_fn = train_fn.trainer_device_fn
            assert trainer.feed_device_fn is not None
            fn = stack_tau(train_fn, args.tau, trainer.num_local_workers)
            losses = []
            try:
                for _ in range(7):
                    if order == "ahead":
                        losses.append(trainer.train_round(fn))
                        continue
                    it = trainer.iter
                    feeds = trainer._stage_feeds(fn(it), it,
                                                 with_tau_axis=True)
                    trainer.variables, trainer.slots, loss = trainer._train(
                        trainer.variables, trainer.slots, it, feeds,
                        solver._key)
                    trainer.iter += args.tau
                    losses.append(float(loss))
            finally:
                fn.close()
            ends[order] = (losses, leaves(trainer.variables),
                           leaves(trainer.slots))
        return 0

    orig = cli.cmd_train
    cli.cmd_train = as_train
    try:
        assert cli.main(["train", *job]) == 0
    finally:
        cli.cmd_train = orig
    (losses, params, slots), (want_losses, want, want_slots) = (
        ends["ahead"], ends["serial"])
    assert losses == want_losses and all(np.isfinite(losses))
    assert len(set(losses)) == 7  # the rounds saw different batches
    assert len(params) == len(want) > 0 and len(slots) == len(want_slots) > 0
    for a, b in zip(params + slots, want + want_slots):
        np.testing.assert_array_equal(a, b)


def test_tpunet_train_lets_go_of_the_round_placed_past_the_last(
        job, tmp_path, monkeypatch):
    seen, close = [], ParallelTrainer.close

    def noting(trainer):
        seen.append((trainer.iter, trainer._ahead[0]))
        close(trainer)
        seen.append(trainer._ahead)

    monkeypatch.setattr(ParallelTrainer, "close", noting)
    monkeypatch.setenv("SPARKNET_TRAIN_LOG_DIR", str(tmp_path))
    assert cli.main(["train", *job, "--iterations", str(3 * TAU),
                     "--output", str(tmp_path / "out")]) == 0
    assert seen == [(3 * TAU, 3 * TAU), None]
