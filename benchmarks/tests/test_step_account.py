"""The readers of the step program's account, the fences' live bytes and
the kernel-path counters (PR 52) on a record written by hand, in
``_flight.py``'s neutral form.  Seconds below are from the process's
creation; the arithmetic is beside each span."""

import pytest

from benchmarks.harness import load_by_name
from benchmarks.metrics import _step_account

T0 = 10**12
MAIN, FEED = 1, 2
GB = 10**9
LIMIT = 15_750_000_000
METRICS = ("model_step.args_hbm_gb", "model_step.temps_hbm_gb",
           "model_step.program_hbm_gb", "device.live_hbm_gb",
           "device.hbm_fill", "kernels.path_share", "feed.fused_share")


def ns(seconds):
    return int(round(seconds * 1e9))


def span(name, thread, start, wall, **counts):
    return [name, thread, T0 + ns(start), ns(wall), counts]


def account(devices, args, temps, **over):
    """A step program's account: the state (args less a 0.1 GB batch) is
    donated and aliased into the outputs."""
    stats = dict(hbm_args_bytes=args, hbm_out_bytes=args - GB // 10,
                 hbm_alias_bytes=args - GB // 10, hbm_temps_bytes=temps,
                 hbm_code_bytes=GB // 100, hbm_devices=devices,
                 hbm_limit_bytes=LIMIT, hbm_account_ms=2.5, compiles=1,
                 compile_s=3.0)
    stats.update(over)
    return stats


def record(**over):
    """A decoder's solo job: a first step that compiles, four timed fences
    of 8 steps, two traced fences; a feed thread that augments."""
    kernels = dict(ssm_layers=2, ssm_kernel_layers=2, attn_core_layers=4,
                   attn_kernel_layers=3, swa_window_layers=1,
                   swa_band_layers=1, swa_block_share=22.79)
    spans = [
        span("sn.main", MAIN, 12.0, 0.1),
        span("sn.solver.build", MAIN, 12.5, 8.0, compiles=300, compile_s=6.0),
        span("sn.step", MAIN, 30.0, 5.0, it=0, **account(
            1, 6 * GB, 7 * GB)),
        span("sn.feed.augment", FEED, 30.5, 0.4, it=1, fused=1, compiles=1,
             compile_s=0.25),  # before the last compile: not counted
        span("sn.step.fence", MAIN, 35.0, 0.9, it=8, hbm_live_bytes=5 * GB,
             **kernels),
        # the timed interval: 36.0 (the last compile) to 50.0
        span("sn.feed.augment", FEED, 36.5, 0.01, it=9, fused=1),
        span("sn.step", MAIN, 37.0, 0.01, it=8),  # a warm step: no account
        span("sn.step.fence", MAIN, 38.0, 1.0, it=16,
             hbm_live_bytes=6_500_000_000, **kernels),
        span("sn.feed.augment", FEED, 39.5, 0.01, it=17, fused=1),
        span("sn.step.fence", MAIN, 40.0, 1.0, it=24,
             hbm_live_bytes=6_600_000_000, **kernels),
        span("sn.feed.augment", FEED, 41.5, 0.01, it=25, fused=0),
        span("sn.step.fence", MAIN, 44.0, 1.0, it=32,
             hbm_live_bytes=6_550_000_000, **kernels),
        span("sn.feed.augment", FEED, 45.5, 0.01, it=33, fused=1),
        # the traced window (50-52): the live bytes leave it out, the
        # kernel counters read it alone
        span("sn.step.fence", MAIN, 50.5, 0.5, it=40, hbm_live_bytes=9 * GB,
             **kernels),
        span("sn.step.fence", MAIN, 51.2, 0.5, it=48, hbm_live_bytes=9 * GB,
             **dict(kernels, attn_kernel_layers=4)),
        span("sn.feed.augment", FEED, 51.5, 0.01, it=49, fused=0),
    ]
    rec = {"process_start_ns": T0, "dropped": 0,
           "last_compile_ns": T0 + ns(36.0),
           "trace": {"offset_ns": -T0, "offset_spread_ns": 900, "pairs": 2,
                     "window": [T0 + ns(50.0), T0 + ns(52.0)]},
           "spans": spans}
    rec.update(over)
    return rec


def without(rec, *prefixes):
    """The record as a program without those stats keeps it."""
    rec["spans"] = [
        [*s[:4], {k: v for k, v in s[4].items()
                  if not k.startswith(prefixes)}] for s in rec["spans"]]
    return rec


def read(metric, summary):
    return load_by_name("metrics", metric).read(summary, {})


@pytest.mark.parametrize("metric,value", [
    ("model_step.args_hbm_gb", 6.0),
    ("model_step.temps_hbm_gb", 7.0),
    # 6 + 5.9 out - 5.9 aliased + 7 + 0.01 of code
    ("model_step.program_hbm_gb", 13.01),
    ("device.live_hbm_gb", 6.6),  # the fences at 38, 40, 44: not 35, not 50
    ("device.hbm_fill", 100 * (6.6 + 7.0) / 15.75),
    # the two traced fences: (2 + 3) + (2 + 4) of (2 + 4) + (2 + 4)
    ("kernels.path_share", 100 * 11 / 12),
    ("feed.fused_share", 75.0),  # the augments at 36.5, 39.5, 45.5 of four
])
def test_every_reader_by_hand(metric, value):
    assert read(metric, {"flight": record()}) == pytest.approx(value)


@pytest.mark.parametrize("metric", METRICS[:5])
def test_the_parents_record_has_no_account_and_gives_nothing(metric):
    assert read(metric, {"flight": without(record(), "hbm_")}) is None


def test_the_parents_record_still_names_the_paths_it_took():
    """The kernel counters and ``fused`` are older than their readers."""
    summary = {"flight": without(record(), "hbm_")}
    assert read("kernels.path_share", summary) == pytest.approx(100 * 11 / 12)
    assert read("feed.fused_share", summary) == pytest.approx(75.0)
    bare = {"flight": without(record(), "hbm_", "ssm_", "attn_", "fused")}
    assert read("kernels.path_share", bare) is None
    assert read("feed.fused_share", bare) is None


@pytest.mark.parametrize("metric", METRICS)
def test_without_the_record_every_reader_gives_nothing(metric, monkeypatch):
    assert read(metric, None) is None
    assert read(metric, {"flight": {"spans": []}}) is None
    # a program that keeps no record: taken once, nothing cached but None
    monkeypatch.setattr(_step_account, "_cached", _step_account._MISSING)
    monkeypatch.setattr(_step_account._flight, "take", lambda: None)
    assert read(metric, {"chips": {}}) is None
    assert _step_account._cached is None


def test_the_record_is_taken_once_a_process(monkeypatch):
    taken = []
    monkeypatch.setattr(_step_account, "_cached", _step_account._MISSING)
    monkeypatch.setattr(_step_account._flight, "take",
                        lambda: taken.append(1) or record())
    monkeypatch.setattr(_step_account, "_fence_read_us", lambda: None)
    monkeypatch.setattr("benchmarks.metrics._program_spans.newest_xplane",
                        lambda: None)
    for metric in METRICS:
        read(metric, {"chips": {}})
    assert taken == [1]
    # no xplane to anchor it on: the live bytes run to the record's end,
    # and there is no traced window to read the kernel counters in
    assert read("device.live_hbm_gb", {"chips": {}}) == pytest.approx(9.0)
    assert read("kernels.path_share", {"chips": {}}) is None


def test_a_fill_over_the_chip_is_refused_and_says_why(capsys):
    rec = record()
    rec["spans"][2][4]["hbm_temps_bytes"] = 10 * GB  # 6.6 + 10 of 15.75
    assert read("device.hbm_fill", {"flight": rec}) is None
    assert "device.hbm_fill refused" in capsys.readouterr().err
    # the parts are still reported
    assert read("model_step.temps_hbm_gb", {"flight": rec}) == 10.0
    assert read("device.live_hbm_gb", {"flight": rec}) == pytest.approx(6.6)


def test_without_a_limit_there_is_no_fill():
    rec = without(record(), "hbm_limit")
    assert read("device.hbm_fill", {"flight": rec}) is None
    assert read("model_step.program_hbm_gb", {"flight": rec}) == pytest.approx(
        13.01)


def test_the_timed_program_is_the_newest_of_the_widest():
    """``alexnet-tau10-x4``: the round check's trainer (four chips, first),
    the one-device phase's, the four-chip phase's, and a later round of
    the one-device trainer that met a new cache entry."""
    rec = record()
    rec["spans"][2:3] = [
        span("sn.round", MAIN, 20.0, 2.0, it=0, **account(
            4, 1 * GB, 2 * GB)),
        span("sn.round", MAIN, 25.0, 2.0, it=0, **account(
            1, 3 * GB, 3 * GB)),
        span("sn.round", MAIN, 30.0, 5.0, it=0, **account(
            4, 4 * GB, 5 * GB)),
        span("sn.round", MAIN, 37.5, 0.1, it=10, **account(
            1, 3 * GB, 3 * GB, compiles=0)),
    ]
    red = _step_account.reduce(rec)
    assert red["program"][2] == T0 + ns(30.0)
    assert len(red["accounts"]) == 4
    assert red["metrics"]["model_step.args_hbm_gb"] == 4.0
    assert red["metrics"]["model_step.temps_hbm_gb"] == 5.0
    table = _step_account.table(red)
    assert table.count("<- the timed program") == 1
    assert "hbm_live_bytes over 3 fences" in table


def test_the_entries_list_the_cells_that_have_something_to_read():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    cnn = [c for c in cells if c.split("-")[0] in ("alexnet", "resnet50")]
    # appended in this order (not "last": later PRs append behind them)
    at = [[m["name"] for m in bench["per_layer"]].index(n) for n in METRICS]
    assert at == sorted(at)
    for name in METRICS[:5]:
        assert entries[name]["workloads"] == cells
    assert entries["kernels.path_share"]["workloads"] == [
        c for c in cells if c not in cnn]
    assert entries["feed.fused_share"]["workloads"] == [
        c for c in cnn if c.endswith("-solo")]
    for name in METRICS:
        assert (entries[name]["source"], entries[name]["moves"]) == (
            "program_counter", "images_per_s")
