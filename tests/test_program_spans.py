"""The ``sn.*`` spans and ``S.*`` scopes the program carries on the
profiler's clock (docs/OBSERVABILITY.md, "Spans on the profiler's clock").

Everything runs on the CPU: a ``jax.profiler`` trace on the CPU backend
holds the host annotations, and ``jax.profiler.ProfileData`` reads them
back.  Two traces are taken once per module, through the front door's
own functions (``cli.main`` with ``cmd_train`` swapped for the body, the
way the benchmark drives it): two ``Solver.step`` chunks fed by a
``DevicePrefetcher`` from a tiny ``db:`` feed, and two tau=2
``ParallelTrainer.train_round`` calls on two virtual devices, fed by
``rounds.stack_tau``, whose thread reads a round ahead of the round the
trainer places ahead.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import cli
from sparknet_tpu.data import DeviceAugment, TransformConfig
from sparknet_tpu.data.createdb import create_db
from sparknet_tpu.data.device_transform import AUGMENT_SCOPE
from sparknet_tpu.data.prefetch import DevicePrefetcher
from sparknet_tpu.data.rounds import stack_tau
from sparknet_tpu.obs import recorder
from sparknet_tpu.obs.recorder import (
    Recorder, Span, feed_counts, set_recorder)
from sparknet_tpu.parallel.mesh import data_parallel_mesh
from sparknet_tpu.parallel.trainer import ParallelTrainer
from sparknet_tpu.solvers.solver import UPDATE_SCOPE
from sparknet_tpu.utils import profiling

# ProfileData's stats mapping warns about a builtin type on this jax
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type:DeprecationWarning")

BATCH = 6
RECORDS = 48  # 8 batches an epoch
STEPS = 3  # per Solver.step chunk; two chunks are traced
TAU, WORKERS = 2, 2

NET = (
    'name: "spans"\n'
    'layer { name: "d" type: "Data" top: "data" top: "label"\n'
    f'  data_param {{ source: "unused" batch_size: {BATCH} }}\n'
    "  transform_param { crop_size: 12 mirror: true scale: 0.0039 } }\n"
    'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
    "  inner_product_param { num_output: 4 } }\n"
    'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
    'bottom: "label" top: "loss" }\n'
)


def read_spans(trace_dir):
    """Every host event of the newest trace under ``trace_dir`` as
    ``{name, thread, start, end, stats}`` (thread: the line's index)."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                out.append({"name": ev.name, "thread": thread,
                            "start": ev.start_ns,
                            "end": ev.start_ns + ev.duration_ns,
                            "stats": dict(ev.stats)})
    return sorted(out, key=lambda e: e["start"])


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def inside(inner, outer):
    return (inner["thread"] == outer["thread"]
            and outer["start"] <= inner["start"]
            and inner["end"] <= outer["end"])


def run_as_train(flags, body):
    """``tpunet train <flags>`` with ``body(args)`` in cmd_train's place."""
    orig = cli.cmd_train
    cli.cmd_train = body
    try:
        assert cli.main(["train", *flags]) == 0
    finally:
        cli.cmd_train = orig


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """The tiny job's files and flags: a RecordDB of uint8 16x16 records,
    a net that crops them to 12x12 on the device."""
    tmp = tmp_path_factory.mktemp("spans")
    rs = np.random.RandomState(0)
    db = str(tmp / "db")
    create_db(db, [(rs.randint(0, 255, (3, 16, 16)).astype(np.uint8), i % 4)
                   for i in range(RECORDS)])
    (tmp / "net.prototxt").write_text(NET)
    (tmp / "solver.prototxt").write_text(
        f'net: "{tmp}/net.prototxt"\nbase_lr: 0.01\nmax_iter: 100\n'
        "display: 0\n")
    flags = ["--solver", str(tmp / "solver.prototxt"), "--data", f"db:{db}",
             "--prefetch", "2", "--augment", "device"]
    return tmp, flags


def build(args):
    net_param, solver_cfg = cli._build_net_and_solver(args)
    solver = cli._make_solver(solver_cfg, net_param, args)
    train_fn, _ = cli._data_fns(args, solver.train_net,
                                test_net=solver.test_net)
    return solver, train_fn


@pytest.fixture(scope="module")
def solo(job):
    """(spans, HLO text of the train step) of two traced step chunks."""
    tmp, flags = job
    out = {}

    def body(args):
        solver, train_fn = build(args)
        pf = DevicePrefetcher(train_fn, 1 << 30, depth=args.prefetch,
                              start_iter=solver.iter,
                              device_fn=train_fn.device_fn)
        batches = iter(pf)
        with pf:
            solver.step(2, lambda it: next(batches))  # compile
            with profiling.trace(str(tmp / "solo")):
                for _ in range(2):
                    solver.step(STEPS, lambda it: next(batches))
            fn, variables, slots, key = solver.jitted_train_step(donate=False)
            out["hlo"] = fn.lower(variables, slots, 0, next(batches),
                                  key).as_text(debug_info=True)
        return 0

    run_as_train(flags, body)
    return read_spans(str(tmp / "solo")), out["hlo"]


@pytest.fixture(scope="module")
def rounds(job):
    """Spans of two traced tau=2 rounds over two virtual devices."""
    tmp, flags = job

    def body(args):
        solver, train_fn = build(args)
        trainer = ParallelTrainer(solver, mesh=data_parallel_mesh(WORKERS),
                                  tau=args.tau)
        trainer.feed_device_fn = train_fn.trainer_device_fn
        tau_fn = stack_tau(train_fn, args.tau, trainer.num_local_workers)
        trainer.train_round(tau_fn)  # compile; round 1 is placed ahead
        with profiling.trace(str(tmp / "rounds")):
            trainer.train_round(tau_fn)  # round 1; places 2, the feed reads 3
            trainer.train_round(tau_fn)
            tau_fn.close()  # the feed's thread ends inside the trace
        return 0

    run_as_train([*flags, "--tau", str(TAU)], body)
    return read_spans(str(tmp / "rounds"))


# ------------------------------------------------------------ the span type
@pytest.mark.parametrize("armed", [False, True])
def test_a_span_annotates_armed_or_not_and_journals_only_armed(
        tmp_path, armed):
    journal = str(tmp_path / "journal.jsonl")
    rec = set_recorder(Recorder(journal if armed else None, run_id="t"))
    try:
        with profiling.trace(str(tmp_path / "trace")):
            with rec.span("sn.test.span", host=True, it=3, images=5,
                          bytes=7) as span:
                span.set(alloc_bytes=9)  # known only once the work is done
    finally:
        rec.close()
        set_recorder(None)
    (span,) = named(read_spans(str(tmp_path / "trace")), "sn.test.span")
    assert span["stats"] == {"it": 3, "images": 5, "bytes": 7,
                             "alloc_bytes": 9}
    if not armed:
        assert not os.path.exists(journal)
        return
    with open(journal) as f:
        events = [json.loads(line) for line in f]
    (ev,) = [e for e in events if e["event"] == "span"]
    assert ev["name"] == "sn.test.span" and ev["host"] is True
    assert ev["note"] == "it=3 images=5 bytes=7 alloc_bytes=9"


def test_an_exception_inside_a_span_closes_its_annotation(tmp_path):
    rec = Recorder(None)
    with profiling.trace(str(tmp_path / "trace")):
        with pytest.raises(RuntimeError):
            with rec.span("sn.test.raises", host=True):
                raise RuntimeError("inside")
        with rec.span("sn.test.after", host=True):
            pass
    spans = read_spans(str(tmp_path / "trace"))
    (raised,), (after,) = named(spans, "sn.test.raises"), named(spans, "sn.test.after")
    # closed: it has an end, and the next span is beside it, not inside
    assert raised["end"] >= raised["start"]
    assert after["start"] >= raised["end"]


@pytest.mark.parametrize("shape,lead,images", [
    ((6, 3, 16, 16), 1, 6), ((2, 12, 3, 16, 16), 2, 24)])
def test_feed_counts(shape, lead, images):
    feeds = {"data": np.zeros(shape, np.uint8),
             "label": np.zeros(shape[:lead], np.int32)}
    assert feed_counts(feeds, lead) == {
        "images": images,
        "bytes": feeds["data"].nbytes + feeds["label"].nbytes}


# ------------------------------------------------------- the solo step loop
@pytest.mark.parametrize("name", [
    "sn.feed.read", "sn.feed.decode", "sn.feed.collate", "sn.feed.put",
    "sn.feed.augment", "sn.feed.wait", "sn.step", "sn.step.fence"])
def test_the_solo_trace_holds(solo, name):
    spans, _ = solo
    assert named(spans, name), sorted({s["name"] for s in spans})


def test_read_contains_decode_and_collate(solo):
    spans, _ = solo
    reads = named(spans, "sn.feed.read")
    # the profiler drops a span open when the session starts or stops:
    # the feed thread may be inside a read then, whose parts it still
    # records.  Before the first read on record and after the last one
    # lie the parts of those two reads, and of no other
    seen_from = min(r["start"] for r in reads)
    seen_to = max(r["end"] for r in reads)
    # (a read has one collate and one decode, two at an epoch's end)
    for kind, a_read in (("sn.feed.decode", 2), ("sn.feed.collate", 1)):
        parts = [p for p in named(spans, kind)
                 if seen_from <= p["start"] < seen_to]
        assert parts and all(
            any(inside(p, r) for r in reads) for p in parts)
        early = [p for p in named(spans, kind) if p["start"] < seen_from]
        late = [p for p in named(spans, kind) if p["start"] >= seen_to]
        assert len(early) <= a_read and len(late) <= a_read, (early, late)
    for r in reads:
        # each read holds one collate (the hand-over) after its decodes
        # (the per-record loop with its one copy; an epoch's end adds an
        # empty one), and every record of the batch is counted once
        (collate,) = [c for c in named(spans, "sn.feed.collate")
                      if inside(c, r)]
        decodes = [d for d in named(spans, "sn.feed.decode") if inside(d, r)]
        assert decodes and all(d["end"] <= collate["start"] for d in decodes)
        assert collate["stats"]["images"] == BATCH


def test_the_feed_works_on_another_thread_than_the_wait(solo):
    spans, _ = solo
    (main,) = {s["thread"] for s in named(spans, "sn.feed.wait")}
    assert {s["thread"] for s in named(spans, "sn.step")} == {main}
    assert {s["thread"] for s in named(spans, "sn.step.fence")} == {main}
    for name in ("sn.feed.read", "sn.feed.put", "sn.feed.augment"):
        threads = {s["thread"] for s in named(spans, name)}
        assert len(threads) == 1 and main not in threads


def test_one_read_per_batch_and_none_per_record(solo):
    spans, _ = solo
    waits, reads = named(spans, "sn.feed.wait"), named(spans, "sn.feed.read")
    assert len(waits) == len(named(spans, "sn.step")) == 2 * STEPS
    # the feed runs at most its queue depth (+ the batch in hand) ahead
    assert 2 * STEPS - 3 <= len(reads) <= 2 * STEPS + 3
    for r in reads:
        assert r["stats"]["images"] == BATCH and "it" in r["stats"]
        # on the CPU a placed batch may BE its host array, so the
        # prefetcher hands out no ring slot: every batch is a fresh one
        assert r["stats"]["alloc_bytes"] == BATCH * (3 * 16 * 16 + 4)
    its = [r["stats"]["it"] for r in reads]
    assert its == list(range(its[0], its[0] + len(its)))  # one a batch
    for p in named(spans, "sn.feed.put"):
        assert p["stats"]["images"] == BATCH
        assert p["stats"]["bytes"] == BATCH * (3 * 16 * 16 + 4)
    # nothing per record: no sn.* name occurs more than ~once a batch
    for name in {s["name"] for s in spans if s["name"].startswith("sn.")}:
        assert len(named(spans, name)) <= 2 * len(reads) + 2, name


def test_a_step_waits_inside_itself_and_fences_outside(solo):
    spans, _ = solo
    steps = named(spans, "sn.step")
    assert all(any(inside(w, s) for s in steps)
               for w in named(spans, "sn.feed.wait"))
    fences = named(spans, "sn.step.fence")
    assert len(fences) == 2  # one per Solver.step call (no callback)
    assert not any(inside(f, s) for f in fences for s in steps)
    assert [s["stats"]["step_num"] for s in steps] == list(
        range(steps[0]["stats"]["step_num"],
              steps[0]["stats"]["step_num"] + 2 * STEPS))


# ---------------------------------------------------------- the tau round
@pytest.mark.parametrize("name", [
    "sn.round", "sn.round.data", "sn.feed.wait", "sn.feed.read",
    "sn.feed.stack", "sn.feed.put", "sn.feed.augment", "sn.round.dispatch",
    "sn.round.fence"])
def test_the_round_trace_holds(rounds, name):
    assert named(rounds, name), sorted({s["name"] for s in rounds})


def test_a_round_is_dispatch_the_next_rounds_data_put_augment_and_its_fence(
        rounds):
    order = ["sn.round.dispatch", "sn.round.data", "sn.feed.put",
             "sn.feed.augment", "sn.round.fence"]
    outer = named(rounds, "sn.round")
    assert len(outer) == 2
    # the round on the main thread, the reads and stacks on the feed's
    (main,) = {s["thread"] for s in outer}
    for name in [*order, "sn.feed.wait"]:
        assert {s["thread"] for s in named(rounds, name)} == {main}, name
    (feed,) = {s["thread"] for s in rounds
               if s["name"] in ("sn.feed.read", "sn.feed.stack")}
    assert feed != main
    images = TAU * WORKERS * BATCH
    nbytes = images * (3 * 16 * 16 + 4)
    waits = []
    for rnd in outer:
        it = rnd["stats"]["step_num"]
        stages = [next(s for s in named(rounds, n) if inside(s, rnd))
                  for n in order]
        for a, b in zip(stages, stages[1:]):
            assert a["end"] <= b["start"], (a["name"], b["name"])
        # the dispatch and the fence are the round's own; what lies
        # between them is for the NEXT round and carries its ``it``
        assert stages[0]["stats"]["it"] == stages[4]["stats"]["it"] == it
        assert [s["stats"]["it"] for s in stages[1:4]] == [it + TAU] * 3
        assert stages[1]["stats"]["staged"] == 1
        # the data is a wait for the feed, which says whether the round
        # was filled before it was asked for
        (wait,) = [w for w in named(rounds, "sn.feed.wait")
                   if inside(w, stages[1])]
        assert wait["stats"]["it"] == it + TAU
        assert wait["stats"]["ready"] in (0, 1)
        waits.append(wait)
        assert stages[2]["stats"]["images"] == images
        assert stages[2]["stats"]["bytes"] == nbytes
    assert outer[1]["stats"]["step_num"] == outer[0]["stats"]["step_num"] + TAU

    # the feed reads round 3 (the warm-up was round 0 and placed round 1)
    # from the moment round 2 is handed out, inside round 1, and has it
    # whole before it hands it out: TAU x WORKERS reads in the data fn's
    # order, a stack after each slot's
    per_round = TAU * WORKERS
    reads = [r for r in named(rounds, "sn.feed.read")
             if r["stats"]["it"] // per_round == 3]
    assert [r["stats"]["it"] for r in reads] == list(
        range(3 * per_round, 4 * per_round))
    assert waits[0]["end"] <= reads[0]["start"]
    stacks = [s for s in named(rounds, "sn.feed.stack")
              if reads[0]["start"] <= s["start"] <= waits[1]["end"]]
    assert len(stacks) == TAU and stacks[-1]["end"] <= waits[1]["end"]
    for t, stack in enumerate(stacks):
        mine = reads[t * WORKERS:(t + 1) * WORKERS]
        assert all(r["end"] <= stack["start"] for r in mine)
        assert all(stack["end"] <= r["start"]
                   for r in reads[(t + 1) * WORKERS:])
        # it names the round it is for (the one placed inside the second
        # traced round); all three buffers were made before the trace:
        # nothing allocated
        assert stack["stats"] == {
            "it": outer[1]["stats"]["step_num"] + TAU,
            "images": WORKERS * BATCH,
            "bytes": nbytes // TAU, "alloc_bytes": 0}
    assert all(r["stats"]["alloc_bytes"] == 0 for r in reads)
    # and never a batch of the round after the one read ahead
    assert all(r["stats"]["it"] < 5 * per_round
               for r in named(rounds, "sn.feed.read"))


# ------------------------------------------------------------ names, scopes
def test_no_span_is_a_benchmark_span_and_no_scope_a_layer(solo, rounds):
    names = {s["name"] for s in solo[0] + rounds}
    assert not [n for n in names if n.startswith("bench.")]
    assert {n.split(".")[0] for n in names if "." in n} >= {"sn"}
    for scope in (UPDATE_SCOPE, AUGMENT_SCOPE):
        assert scope.startswith("S.") and not scope.startswith("L.")


def test_the_compile_cache_key_names_the_scopes(monkeypatch):
    """A cached executable carries its source's scope names and jax's key
    strips them: the program adds them, so a checkout with other scopes
    on a shared cache is never served this one's executables."""
    from jax._src import cache_key

    from sparknet_tpu import common

    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    common.enable_compile_cache()
    assert cache_key.custom_hook() == common.CACHE_SCOPES
    for scope in ("L.", UPDATE_SCOPE, AUGMENT_SCOPE):
        assert scope in common.CACHE_SCOPES


def test_the_train_step_names_its_update(solo):
    _, hlo = solo
    assert UPDATE_SCOPE in hlo
    assert "L.ip" in hlo  # the layers keep theirs


@pytest.mark.parametrize("rank", [4, 5])
def test_the_jitted_augment_names_itself(rank):
    aug = DeviceAugment(TransformConfig(crop_size=12, mirror=True))
    x = jnp.zeros((2, BATCH, 3, 16, 16)[5 - rank:], jnp.uint8)
    if rank == 4:
        fn = jax.jit(lambda x, k: aug(x, k))
    else:
        fn = jax.jit(lambda x, k: jax.vmap(aug)(x, jax.random.split(k, 2)))
    assert AUGMENT_SCOPE in fn.lower(
        x, jax.random.key(0)).as_text(debug_info=True)


# ------------------------------------------------------------- the record
# What a span measured is kept in memory, with no profiler session and no
# SPARKNET_OBS: set-up and every step of a run, not a traced window of it
# (docs/OBSERVABILITY.md, "The record").
def flight_since(mark):
    """The spans recorded since ``mark`` (``flight_mark()``), as dicts."""
    spans, dropped = recorder.flight()
    new = len(spans) + dropped - mark
    assert 0 <= new <= len(spans)
    return [{"name": n, "thread": t, "start": s, "end": s + w, "stats": c}
            for n, t, s, w, c in spans[len(spans) - new:]]


def flight_mark():
    spans, dropped = recorder.flight()
    return len(spans) + dropped


@pytest.fixture(scope="module")
def solo_flight(job):
    """The record of set-up and two untraced step chunks of a fresh job."""
    _, flags = job
    assert not os.environ.get("SPARKNET_OBS")
    mark = flight_mark()

    def body(args):
        solver, train_fn = build(args)
        pf = DevicePrefetcher(train_fn, 1 << 30, depth=args.prefetch,
                              start_iter=solver.iter,
                              device_fn=train_fn.device_fn)
        batches = iter(pf)
        with pf:
            for _ in range(2):
                solver.step(STEPS, lambda it: next(batches))
        return 0

    run_as_train(flags, body)
    return flight_since(mark)


@pytest.fixture(scope="module")
def rounds_flight(job):
    """The record of set-up and three untraced tau=2 rounds."""
    _, flags = job
    mark = flight_mark()

    def body(args):
        solver, train_fn = build(args)
        trainer = ParallelTrainer(solver, mesh=data_parallel_mesh(WORKERS),
                                  tau=args.tau)
        trainer.feed_device_fn = train_fn.trainer_device_fn
        tau_fn = stack_tau(train_fn, args.tau, trainer.num_local_workers)
        for _ in range(3):
            trainer.train_round(tau_fn)
        tau_fn.close()
        return 0

    run_as_train([*flags, "--tau", str(TAU)], body)
    return flight_since(mark)


SETUP_SPANS = ["sn.main", "sn.setup.net", "sn.solver.build",
               "sn.solver.nets", "sn.solver.init", "sn.feed.open"]


@pytest.mark.parametrize("name", [
    *SETUP_SPANS, "sn.feed.read", "sn.feed.decode", "sn.feed.collate",
    "sn.feed.put", "sn.feed.augment", "sn.feed.wait", "sn.step",
    "sn.step.fence"])
def test_the_solo_record_holds(solo_flight, name):
    assert named(solo_flight, name), sorted({s["name"] for s in solo_flight})


@pytest.mark.parametrize("name", [
    *SETUP_SPANS, "sn.trainer.build", "sn.round", "sn.round.data",
    "sn.feed.wait", "sn.feed.read", "sn.feed.stack", "sn.feed.put",
    "sn.feed.augment", "sn.round.dispatch", "sn.round.fence"])
def test_the_round_record_holds(rounds_flight, name):
    assert named(rounds_flight, name), sorted(
        {s["name"] for s in rounds_flight})


def test_the_solo_record_has_every_step_with_its_it_thread_and_stats(
        solo_flight):
    steps = named(solo_flight, "sn.step")
    assert [s["stats"]["it"] for s in steps] == list(range(2 * STEPS))
    (main,) = {s["thread"] for s in steps}
    assert {s["thread"] for s in named(solo_flight, "sn.main")} == {main}
    assert {s["thread"] for s in named(solo_flight, "sn.step.fence")} == {main}
    # what Span.set added once the work was done rides along
    waits = named(solo_flight, "sn.feed.wait")
    assert len(waits) == 2 * STEPS
    assert all(w["thread"] == main and w["stats"]["ready"] in (0, 1)
               and any(inside(w, s) for s in steps) for w in waits)
    reads = named(solo_flight, "sn.feed.read")
    assert all(r["thread"] != main and r["stats"]["images"] == BATCH
               and "alloc_bytes" in r["stats"] for r in reads)
    # appended as each closes: a thread's spans end in the record's order,
    # and the wall clock they start on does not run backwards
    for thread in {s["thread"] for s in solo_flight}:
        ends = [s["end"] for s in solo_flight if s["thread"] == thread]
        assert ends == sorted(ends)
    starts = [s["start"] for s in steps]
    assert starts == sorted(starts) and starts[0] > 1.6e18  # ns since 1970


def test_the_round_record_has_every_round_and_the_feeds_readiness(
        rounds_flight):
    rounds_ = named(rounds_flight, "sn.round")
    assert [r["stats"]["it"] for r in rounds_] == [0, TAU, 2 * TAU]
    (main,) = {r["thread"] for r in rounds_}
    for rnd in rounds_:
        fence, = [f for f in named(rounds_flight, "sn.round.fence")
                  if inside(f, rnd)]
        assert fence["stats"]["it"] == rnd["stats"]["it"]
    (build_,) = named(rounds_flight, "sn.trainer.build")
    assert build_["stats"]["devices"] == WORKERS and build_["thread"] == main
    waits = [w for w in named(rounds_flight, "sn.feed.wait")]
    assert waits and all(w["stats"]["ready"] in (0, 1) for w in waits)
    staged = [d["stats"]["staged"] for d in named(rounds_flight,
                                                 "sn.round.data")]
    assert staged[0] == 0 and set(staged[1:]) == {1}


@pytest.mark.parametrize("which", ["solo", "rounds"])
def test_only_the_first_step_compiles_and_says_for_how_long(
        solo_flight, rounds_flight, which):
    spans = solo_flight if which == "solo" else rounds_flight
    first, *later = named(spans, "sn.step" if which == "solo" else "sn.round")
    assert first["stats"]["compiles"] >= 1
    assert 0 < first["stats"]["compile_s"] <= (
        first["end"] - first["start"]) / 1e9
    for s in later:  # a warm step's span is what it was
        assert not {"compiles", "compile_s", "cache_hits"} & set(s["stats"])


@pytest.mark.parametrize("which", ["solo", "rounds"])
def test_one_solver_build_contains_its_nets_and_its_init(
        solo_flight, rounds_flight, which):
    spans = solo_flight if which == "solo" else rounds_flight
    (build_,) = named(spans, "sn.solver.build")
    (nets,) = named(spans, "sn.solver.nets")
    (init,) = named(spans, "sn.solver.init")
    assert inside(nets, build_) and inside(init, build_)
    assert nets["end"] <= init["start"]
    assert nets["stats"]["nets"] == 2 and nets["stats"]["layers"] == 6
    assert init["stats"]["params"] == 3 * 12 * 12 * 4 + 4
    # what the eager init compiled (nothing, where an earlier test's
    # solver already did) the build that contains it compiled too
    assert build_["stats"].get("compiles", 0) >= init["stats"].get(
        "compiles", 0)
    # set-up in the front door's order, each stage after the one before
    order = [named(spans, n)[0] for n in (
        "sn.main", "sn.setup.net", "sn.solver.build", "sn.feed.open")]
    for a, b in zip(order, order[1:]):
        assert a["end"] <= b["start"], (a["name"], b["name"])
    (opened,) = named(spans, "sn.feed.open")
    assert opened["stats"]["source"] == "db"


def test_a_zoo_solver_build_is_one_span_with_its_parameter_count():
    from sparknet_tpu import models
    from sparknet_tpu.solvers.solver import Solver

    mark = flight_mark()
    solver = Solver(models.lenet_solver(), models.lenet(4))
    spans = flight_since(mark)
    (build_,) = named(spans, "sn.solver.build")
    (nets,) = named(spans, "sn.solver.nets")
    (init,) = named(spans, "sn.solver.init")
    assert inside(nets, build_) and inside(init, build_)
    assert nets["stats"]["layers"] == len(solver.train_net.layers) + len(
        solver.test_net.layers)
    assert init["stats"]["params"] == sum(
        int(p.size) for ps in solver.variables.params.values() for p in ps)
    assert {s["name"] for s in spans} == {
        "sn.solver.build", "sn.solver.nets", "sn.solver.init"}


def test_a_compile_on_a_feed_thread_is_booked_to_that_threads_span():
    import threading

    fn = jax.jit(lambda x: x * 3 + 1)  # never compiled before
    mark = flight_mark()

    def feed():
        with Span(None, "sn.test.feed", host=True, compile_stats=True):
            fn(jnp.ones(7)).block_until_ready()

    with Span(None, "sn.test.main", host=True, compile_stats=True):
        t = threading.Thread(target=feed)
        t.start()
        t.join(60)
        assert not t.is_alive()
    spans = flight_since(mark)
    (on_feed,), (on_main,) = (named(spans, "sn.test.feed"),
                              named(spans, "sn.test.main"))
    assert on_feed["thread"] != on_main["thread"]
    assert on_feed["stats"]["compiles"] >= 1
    assert on_feed["stats"]["compile_s"] > 0
    assert on_main["stats"] == {}  # the main thread compiled nothing


def test_the_record_is_bounded_and_counts_what_it_dropped(monkeypatch):
    import collections

    monkeypatch.setattr(recorder, "_flight", collections.deque(maxlen=8))
    monkeypatch.setattr(recorder, "_flight_count", 0)
    for i in range(5):
        with Span(None, "sn.test.bound", host=True, it=i):
            pass
    spans, dropped = recorder.flight()
    assert [s[4]["it"] for s in spans] == list(range(5)) and dropped == 0
    for i in range(5, 20):
        with Span(None, "sn.test.bound", host=True, it=i):
            pass
    spans, dropped = recorder.flight()
    assert [s[4]["it"] for s in spans] == list(range(12, 20))
    assert dropped == 12
    assert recorder.FLIGHT_MAX == 65536


def test_a_trace_that_compiles_nothing_leaves_the_span_as_it_was():
    """The solo feed's eager ``device_fn`` re-traces on every batch and
    ends in jit's cache: ~0.1 ms of trace events, no compile."""
    fn = jax.jit(lambda x: x * 11 - 4)
    fn(jnp.ones(5)).block_until_ready()
    mark = flight_mark()
    with Span(None, "sn.test.warm", host=True, compile_stats=True, it=2):
        jax.jit(lambda x: x)  # built, never called
        fn(jnp.ones(5)).block_until_ready()
    (warm,) = flight_since(mark)
    assert warm["stats"] == {"it": 2}


ROWS = [("sn.solver.build", 1, 100, 4 * 10**9,
         {"compiles": 3, "compile_s": 2.5, "cache_hits": 1}),
        ("sn.trainer.build", 1, 200, 10**9, {"devices": 4}),
        ("sn.round", 1, 250, 10**9, {"it": 0, "compiles": 1,
                                     "compile_s": 0.5}),
        ["sn.trainer.build", 1, 300, 2 * 10**9,
         {"devices": 1, "compiles": 2, "compile_s": 0.25}]]  # its JSON form


@pytest.mark.parametrize("names,expected", [
    (recorder.SETUP_STAGES, [
        {"name": "sn.solver.build", "count": 1, "wall_s": 4.0, "compiles": 3,
         "compile_s": 2.5, "cache_hits": 1, "stats": {}},
        {"name": "sn.trainer.build", "count": 2, "wall_s": 3.0, "compiles": 2,
         "compile_s": 0.25, "cache_hits": 0, "stats": {"devices": [4, 1]}}]),
    (("sn.round",), [
        {"name": "sn.round", "count": 1, "wall_s": 1.0, "compiles": 1,
         "compile_s": 0.5, "cache_hits": 0, "stats": {}}]),
    (("sn.feed.open",), [])])
def test_the_record_reduces_by_stage(names, expected):
    assert recorder.stages(ROWS, names) == expected


def test_the_record_keeps_a_span_that_raised_and_a_steps_it():
    mark = flight_mark()
    with pytest.raises(RuntimeError):
        with Span(None, "sn.test.raises", host=True, it=1) as sp:
            sp.set(ready=0)
            raise RuntimeError("inside")
    with profiling.step_span("sn.test.step", 41):
        pass
    raised, step = flight_since(mark)
    assert raised["name"] == "sn.test.raises"
    assert raised["stats"] == {"it": 1, "ready": 0}
    assert step["stats"] == {"it": 41} and step["end"] >= step["start"]


def test_a_step_span_is_still_a_step_annotation_in_the_trace(tmp_path):
    with profiling.trace(str(tmp_path / "trace")):
        with profiling.step_span("sn.test.step", 7):
            pass
    (span,) = named(read_spans(str(tmp_path / "trace")), "sn.test.step")
    assert span["stats"]["step_num"] == 7


# -------------------------------------------------- the compile listener
def test_the_sentinel_keeps_the_seconds_it_is_given_by_event_and_thread():
    from sparknet_tpu.obs.sentinel import get_sentinel

    sentinel = get_sentinel().install()
    before = sentinel.thread_seconds()
    n0, s0, _ = sentinel.thread_compile()
    last0 = sentinel.last_compile_ns
    jax.jit(lambda x: x * 5 - 2)(jnp.ones(3)).block_until_ready()
    after = sentinel.thread_seconds()
    for stage in ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                  "backend_compile_duration"):
        key = f"/jax/core/compile/{stage}"
        assert after[key] > before.get(key, 0.0), stage
    n1, s1, _ = sentinel.thread_compile()
    assert n1 - n0 >= 1 and s1 > s0
    # the stages of one compilation do not overlap: union == sum here
    assert s1 - s0 <= sum(after.values()) - sum(before.values()) + 1e-6
    assert sentinel.last_compile_ns > last0


@pytest.mark.parametrize("events,union_s", [
    # two traces inside a third: the outer one's seconds, once
    ([(1.0, 2.0), (3.0, 4.0), (0.5, 5.0)], 4.5),
    # disjoint stages add up
    ([(0.0, 1.0), (1.0, 1.5), (2.0, 4.0)], 3.5),
    # a later event that starts inside an earlier one adds only its rest
    ([(0.0, 2.0), (1.0, 3.0)], 3.0)])
def test_nested_compile_stages_are_counted_once(events, union_s):
    from sparknet_tpu.obs.sentinel import RecompileSentinel

    sentinel = RecompileSentinel()
    for start, end in events:
        sentinel._cover(1, int(start * 1e9), int(end * 1e9))
    assert sentinel._busy[1][0] == int(union_s * 1e9)


@pytest.mark.parametrize("extra,stages", [
    ([], ["solver build", "init", "feed open", "first step", "its fence"]),
    (["--tau", str(TAU)], ["solver build", "trainer build", "feed open",
                           "first step", "its fence"])])
def test_train_logs_one_set_up_line_from_the_record(job, capsys, extra,
                                                    stages):
    tmp, flags = job
    assert cli.main(["train", *flags, *extra, "--iterations", "4",
                     "--output", str(tmp / "out")]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines()
             if "set-up:" in l]
    assert len(lines) == 1, lines
    assert "from the front door to the first fenced step" in lines[0]
    for stage in stages:
        assert stage in lines[0], (stage, lines[0])
    assert "compiles" in lines[0]  # the first step's program, at least
    # what each stage built, and the listener's seconds by event
    built = ["(nets 2, layers 6)",
             f"(programs 1, params {3 * 12 * 12 * 4 + 4})",
             "(source db)"] + (["(devices "] if extra else [])
    for stat in built:
        assert stat in lines[0], (stat, lines[0])
    assert "this thread's compile seconds by event: trace " in lines[0]
    assert "compile or load " in lines[0]
