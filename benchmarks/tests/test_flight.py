"""The readers of the program's record (``obs.recorder.flight``, PR 34) on
a record written by hand.  Seconds below are from the process's creation;
the arithmetic is beside each span."""

import sys
import types

import pytest

from benchmarks.harness import load_by_name
from benchmarks.metrics import _flight

T0 = 10**12
MAIN, FEED = 1, 2


def ns(seconds):
    return int(round(seconds * 1e9))


def span(name, thread, start, wall, **counts):
    return [name, thread, T0 + ns(start), ns(wall), counts]


def record(**over):
    """A tau job: set-up, a warm-up round that compiles, five timed rounds
    of which one stalls, and a traced window the readers leave out."""
    spans = [
        span("sn.main", MAIN, 12.0, 0.1),  # before the front door: 12.0
        span("sn.setup.net", MAIN, 12.2, 0.3),
        # the build's compiles are its init's: counted once
        span("sn.solver.build", MAIN, 12.5, 8.0, compiles=300, compile_s=6.0),
        span("sn.solver.nets", MAIN, 12.6, 1.5, nets=2, layers=48),
        span("sn.solver.init", MAIN, 14.1, 6.0, params=61_000_000,
             compiles=300, compile_s=6.0),
        span("sn.trainer.build", MAIN, 21.0, 1.0, devices=4, compiles=2,
             compile_s=0.5),
        span("sn.feed.open", MAIN, 22.5, 0.5, source="db"),
        # the warm-up round holds its augment's compiles too
        span("sn.round", MAIN, 30.0, 5.0, it=0, compiles=5, compile_s=3.0,
             cache_hits=1),
        span("sn.feed.augment", MAIN, 30.1, 1.0, it=0, compiles=4,
             compile_s=0.8),
        span("sn.round.fence", MAIN, 34.0, 0.9, it=0),  # first touch
        # a feed thread's compile is its own
        span("sn.feed.augment", FEED, 31.0, 0.4, it=1, compiles=1,
             compile_s=0.25),
        span("sn.feed.wait", MAIN, 35.5, 0.01, it=10, ready=1),
        span("sn.round.fence", MAIN, 36.0, 1.0, it=10),
        span("sn.feed.wait", MAIN, 37.5, 0.01, it=20, ready=1),
        span("sn.round.fence", MAIN, 38.0, 1.0, it=20),
        span("sn.round.fence", MAIN, 40.0, 4.0, it=30),  # the stall
        span("sn.feed.wait", MAIN, 40.5, 2.0, it=30, ready=0),
        span("sn.feed.read", FEED, 40.2, 3.0, it=31, images=256),
        span("sn.feed.wait", MAIN, 44.5, 0.01, it=40, ready=1),
        span("sn.round.fence", MAIN, 45.0, 1.0, it=40),
        span("sn.round.fence", MAIN, 47.0, 1.0, it=50),
        # the traced window (50-52): left out
        span("sn.feed.wait", MAIN, 50.2, 0.1, it=60, ready=0),
        span("sn.round.fence", MAIN, 50.5, 9.0, it=60),
    ]
    rec = {"process_start_ns": T0, "dropped": 0,
           "last_compile_ns": T0 + ns(33.5),
           "trace": {"offset_ns": -T0, "offset_spread_ns": 900, "pairs": 2,
                     "window": [T0 + ns(50.0), T0 + ns(52.0)]},
           "spans": spans}
    rec.update(over)
    return rec


def read(metric, summary):
    return load_by_name("metrics", metric).read(summary, {})


@pytest.mark.parametrize("metric,value", [
    ("setup.before_front_door_s", 12.0),
    ("setup.solver_build_s", 0.3 + 8.0 + 1.0),
    ("setup.net_build_s", 1.5),
    ("setup.compile_s", 6.0 + 0.5 + 3.0 + 0.25),  # nested: not twice
    ("step.fence_max_over_median", 4.0),  # 4.0 s against 1.0 s
    ("feed.ahead_share", 75.0),  # three of four waits found it ready
])
def test_every_reader_by_hand(metric, value):
    assert read(metric, {"flight": record()}) == pytest.approx(value)


def test_without_the_sentinels_clock_the_last_compiling_span_bounds_it():
    """The round that compiled ends at 35.0: its own fence (34.0) is left
    out, the next (it=10) is the first touch, and 20..50 remain."""
    red = _flight.reduce(record(last_compile_ns=0))
    assert red["metrics"]["step.fence_max_over_median"] == pytest.approx(4.0)
    assert red["longest_fence"]["fences"] == 4
    assert red["interval_s"] == pytest.approx(50.0 - 35.0)


def test_the_stalled_fence_is_named_with_what_the_feed_was_doing():
    red = _flight.reduce(record())
    fence = red["longest_fence"]
    assert (fence["name"], fence["it"], fence["fences"]) == (
        "sn.round.fence", 30, 5)
    assert fence["wall_s"] == pytest.approx(4.0)
    assert fence["median_s"] == pytest.approx(1.0)
    assert fence["feed_wait_not_ready_s"] == pytest.approx(2.0)
    assert fence["feed_threads_inside_s"] == {
        "sn.feed.read": pytest.approx(3.0)}
    # fence ends 37, 39, 44, 46, 48: the chunk that ends the stall is 5 s
    assert red["longest_chunk"] == {
        "it": 30, "wall_s": pytest.approx(5.0),
        "median_s": pytest.approx(2.0)}


def test_set_up_by_stage_and_how_much_of_it_the_spans_cover():
    red = _flight.reduce(record())
    stages = {r["name"]: r for r in red["stages"]}
    assert stages["sn.solver.build"]["compiles"] == 300
    assert stages["sn.solver.init"]["compile_s"] == pytest.approx(6.0)
    assert stages["sn.trainer.build"]["wall_s"] == pytest.approx(1.0)
    compiled = {(r["name"], r["thread"]): r for r in red["compiled"]}
    assert set(compiled) == {("sn.round", "main"),
                             ("sn.feed.augment", "feed")}
    assert compiled["sn.round", "main"]["cache_hits"] == 1
    # sn.main's end 12.1 to the first fence's end 34.9: net 0.3, build 8.0,
    # trainer 1.0, open 0.5 and the round from 30.0 = 14.7 of 22.8
    cover = red["setup_cover"]
    assert cover["span_s"] == pytest.approx(22.8)
    assert cover["covered_s"] == pytest.approx(14.7)
    assert cover["gaps"][0] == {"after": "sn.feed.open", "before": "sn.round",
                                "s": pytest.approx(7.0)}
    assert "sn.feed.open -> sn.round" in _flight.table(red, record()["trace"])


@pytest.mark.parametrize("late", [
    span("sn.solver.build", MAIN, 48.5, 0.7),  # in the timed window
    span("sn.trainer.build", MAIN, 50.1, 0.4, devices=4),  # in the traced
    span("sn.round", MAIN, 49.0, 0.9, it=55, compile_s=0.2),
])
def test_set_up_ends_with_the_last_compile(late):
    """What begins after the process's last compile (33.5) is no set-up:
    not a second build in the windows, not a late trace's seconds."""
    rec = record()
    rec["spans"].append(late)
    with_late, without = (_flight.reduce(r)["metrics"]
                          for r in (rec, record()))
    for metric in ("setup.solver_build_s", "setup.compile_s"):
        assert with_late[metric] == pytest.approx(without[metric])


def test_the_table_prints_what_each_stage_built_and_the_compile_events():
    rec = record(compile_seconds={
        str(MAIN): {"trace": 2.5, "lower": 1.0, "compile or load": 6.5,
                    "of it cache loads": 0.25, "which saved": 40.0},
        str(FEED): {"trace": 0.05, "compile or load": 0.25}})
    red = _flight.reduce(rec)
    stats = {r["name"]: r["stats"] for r in red["stages"]}
    assert stats["sn.solver.nets"] == {"nets": [2], "layers": [48]}
    assert stats["sn.solver.init"] == {"params": [61_000_000]}
    assert stats["sn.trainer.build"] == {"devices": [4]}
    assert stats["sn.feed.open"] == {"source": ["db"]}
    assert red["compile_seconds"]["main"]["which saved"] == 40.0
    text = _flight.table(red)
    for piece in ("nets 2, layers 48", "params 61000000", "devices 4",
                  "source db", "main thread's compile seconds",
                  "trace 2.500, lower 1.000, compile or load 6.500, "
                  "of it cache loads 0.250, which saved 40.000",
                  "feed thread's compile seconds"):
        assert piece in text, piece


def test_the_timed_rows_leave_out_set_up_and_the_traced_window():
    red = _flight.reduce(record())
    rows = {r["name"]: r for r in red["timed"]}
    assert red["interval_s"] == pytest.approx(50.0 - 33.5)
    assert rows["sn.round.fence"]["count"] == 6  # 0 .. 50, not 60
    assert rows["sn.round.fence"]["max_ms"] == pytest.approx(4000.0)
    assert rows["sn.feed.wait"]["count"] == 4
    assert "sn.solver.build" not in rows


@pytest.mark.parametrize("rec", [{}, {"spans": []}])
def test_an_empty_record_reads_nothing(rec):
    assert _flight.reduce(rec) is None
    assert read("setup.compile_s", {"flight": rec}) is None


def test_a_record_of_steps_alone_reports_what_it_can():
    """A library user's process: no front door, no set-up span."""
    spans = [s for s in record()["spans"] if s[0].startswith("sn.round")]
    m = _flight.reduce({"spans": spans})["metrics"]
    assert set(m) == {"setup.compile_s", "step.fence_max_over_median"}


@pytest.mark.parametrize("metric", [
    "setup.before_front_door_s", "setup.solver_build_s", "setup.net_build_s",
    "setup.compile_s", "step.fence_max_over_median", "feed.ahead_share"])
def test_a_parent_without_a_record_gives_none(monkeypatch, metric):
    """The parent of PR 34: ``obs.recorder`` has no ``flight``."""
    monkeypatch.setitem(sys.modules, "sparknet_tpu.obs.recorder",
                        types.ModuleType("sparknet_tpu.obs.recorder"))
    monkeypatch.setattr(_flight, "_cached", _flight._MISSING)
    assert _flight.take() is None
    assert read(metric, {"chips": {}}) is None
    assert read(metric, None) is None  # and an untraced run reads nothing


def test_this_process_gives_its_record_and_its_creation_time():
    import time

    from sparknet_tpu.obs.recorder import Span

    with Span(None, "sn.test.flight", host=True, it=3):
        pass
    rec = _flight.take()
    assert ["sn.test.flight", {"it": 3}] in [[s[0], s[4]] for s in rec["spans"]]
    assert 0 < time.time_ns() - rec["process_start_ns"] < 3600 * 10**9
    # the listener's seconds by thread, under the words the table prints
    for by in rec["compile_seconds"].values():
        assert set(by) <= {"trace", "lower", "compile or load",
                           "of it cache loads", "which saved"}
