"""The readers of the program's own spans and scopes (PR 24) on a small
hand-written trace; the arithmetic is in the fixture's ``_comment``."""

import json
import os

import pytest

from benchmarks.harness import load_by_name, trace
from benchmarks.metrics import _program_spans as ps

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def summary_of(tr):
    """What a traced run hands the readers, with the program spans in it."""
    summary = trace.summarize({**tr, "host": [h[:3] for h in tr["host"]]})
    summary["program_spans"] = ps.reduce(
        {**tr, "host": [[*h, "python#0", {}][:5] for h in tr["host"]]})
    return summary


@pytest.fixture(scope="module")
def summary():
    return summary_of(load("program_spans_trace.json"))


def read(metric, summary, run=None):
    return load_by_name("metrics", metric).read(summary, run or {})


@pytest.mark.parametrize("metric,value", [
    ("feed.read_ms", 0.03125), ("feed.put_ms", 0.00625),
    ("feed.stack_ms", 0.001875),
    ("solver.update_share", 100 * 150 / 450),
    ("feed.augment_share", 100 * 100 / 450),
    ("solver.unscoped_share", 100 * 350 / 450),  # still counts both scopes
])
def test_every_reader_by_hand(summary, metric, value):
    assert read(metric, summary) == pytest.approx(value)


def test_spans_are_grouped_by_name_and_thread_and_a_straddler_is_left_out(summary):
    rows = {(r["name"], r["role"]): r for r in summary["program_spans"]["spans"]}
    read_row = rows["sn.feed.read", "feed"]
    assert (read_row["count"], read_row["images"]) == (2, 16)  # not read C
    assert read_row["total_s"] == pytest.approx(500e-9)
    assert rows["sn.feed.decode", "feed"]["total_s"] == pytest.approx(310e-9)
    assert rows["sn.feed.collate", "feed"]["total_s"] == pytest.approx(130e-9)
    assert rows["sn.feed.put", "feed"]["bytes"] == 1600
    assert rows["sn.feed.wait", "main"]["thread"] == "python#0"
    assert summary["program_spans"]["main_thread"] == "python#0"
    assert {r["thread"] for r in rows.values() if r["role"] == "feed"} == {"python#1"}


def test_idle_gaps_go_to_the_innermost_span_of_each_thread(summary):
    idle = summary["program_spans"]["idle_s"]
    ns = lambda d: {k: round(v * 1e9) for k, v in d.items()}
    assert ns(idle["main"]) == {
        "sn.feed.wait": 350, "sn.step.fence": 100, "sn.feed.stack": 30,
        "(no span)": 70}
    assert ns(idle["feed"]) == {
        "sn.feed.decode": 160, "sn.feed.read": 90, "sn.feed.collate": 50,
        "sn.feed.put": 40, "(no span)": 210}
    for role in idle.values():  # each view accounts for all of the idle time
        assert sum(role.values()) == pytest.approx(550e-9)


def test_stage_spans_cover_their_thread_from_its_first_recorded_span(summary):
    """Main (first span at 50): wait 60-450 + fence 500-900 + stack 910-940
    = 820 of 950 (sn.step is a parent, left out).  Feed (first span at
    100): 100-340, 400-760 and read C's inside part 950-1000 = 240 + 360 +
    50 = 650 of 900."""
    threads = summary["program_spans"]["threads"]
    assert threads["python#0"] == pytest.approx(
        {"seen_s": 950e-9, "covered_s": 820e-9})
    assert threads["python#1"] == pytest.approx(
        {"seen_s": 900e-9, "covered_s": 650e-9})


def test_idle_before_a_threads_first_recorded_span_is_named_as_unseen():
    """The profiler drops a span that was open when the session started,
    so the feed thread's first 40 ns are unseen, not unspanned: chip idle
    0-90, feed thread's first recorded span 40-90."""
    got = ps.reduce({
        "window": [0, 100], "chips": {"0": [[90, 10, "fusion.1", ""]]},
        "host": [[0, 100, "bench.window", "m", {}],
                 [0, 100, "sn.step", "m", {"step_num": 0}],
                 [40, 50, "sn.feed.put", "f", {"images": 1, "bytes": 1}]]})
    assert got["idle_s"]["main"] == pytest.approx({"sn.step": 90e-9})
    assert got["idle_s"]["feed"] == pytest.approx(
        {ps.OPEN_AT_START: 40e-9, "sn.feed.put": 50e-9})
    assert got["threads"]["f"] == pytest.approx(
        {"seen_s": 60e-9, "covered_s": 50e-9})


def test_the_table_names_every_span_and_both_views(summary):
    text = ps.table(summary["program_spans"])
    for word in ("sn.feed.read", "sn.feed.wait", "python#1", "main-thread",
                 "feed-thread", "(no span)"):
        assert word in text


NEW = ["feed.read_ms", "feed.put_ms", "feed.stack_ms", "solver.update_share",
       "feed.augment_share"]


@pytest.mark.parametrize("metric", NEW)
def test_readers_find_nothing_without_the_spans_and_scopes(metric):
    """The parent of PR 24: a trace with ``bench.*`` spans and ``L.*``
    scopes only.  Also an untraced run (no summary at all)."""
    assert read(metric, summary_of(load("small_trace.json"))) is None
    assert read(metric, None) is None


def test_no_trace_on_disk_reads_as_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(ps.dataset, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(ps, "_cached", ps._MISSING)
    summary = trace.summarize(
        {**load("small_trace.json")})  # no program_spans key: goes to disk
    assert ps.newest_xplane() is None
    assert read("feed.read_ms", summary) is None


def test_benchmark_json_lists_the_new_metrics_last():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-5:]] == NEW
    assert all(not m["name"].startswith("bench.") for m in per_layer)
