"""The trace reducer on a small recorded trace, every number checked by
hand (the arithmetic is in the fixture's ``_comment``)."""

import json
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        t = json.load(f)
    return t, trace.summarize(t)["chips"]["0"], trace.summarize(t)


def test_busy_is_the_union_not_the_sum(small):
    t, chip, _ = small
    assert sum(e[1] for e in t["chips"]["0"]) == 880
    assert chip["busy_s"] == pytest.approx(600e-9)


def test_layer_attribution_and_forward_backward_split(small):
    _, chip, _ = small
    assert chip["layer_fwd_s"] == pytest.approx({"conv1": 100e-9, "fc": 80e-9})
    # the all-reduce starts inside conv1's backward fusion: the overlap is
    # counted once and goes to the later op, so self times add up to busy
    assert chip["layer_bwd_s"] == pytest.approx({"conv1": 50e-9, "fc": 150e-9})


def test_a_loop_keeps_only_its_self_time(small):
    _, chip, _ = small
    assert chip["unscoped_s"]["jit(round)/while"] == pytest.approx(70e-9)
    assert chip["unscoped_s"]["jit(train_step)/jit(main)/sub"] == pytest.approx(50e-9)
    assert chip["unscoped_s"]["jit(round)/pmean"] == pytest.approx(100e-9)
    # self times add up to the busy union, whatever nests or overlaps
    total = (sum(chip["layer_fwd_s"].values()) + sum(chip["layer_bwd_s"].values())
             + sum(chip["unscoped_s"].values()))
    assert total == pytest.approx(chip["busy_s"]) == pytest.approx(600e-9)


def test_idle_gaps_go_to_the_innermost_host_span(small):
    _, chip, _ = small
    gaps = chip["idle_gaps_s"]
    assert gaps["bench.feed_wait"] == pytest.approx(50e-9)
    assert gaps["bench.put"] == pytest.approx(50e-9)
    assert gaps["bench.round"] == pytest.approx((50 + 150 + 100) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(400e-9)  # window - busy


def test_collective_time_and_its_exposed_part(small):
    _, chip, _ = small
    assert chip["collective_s"] == pytest.approx(100e-9)
    assert chip["collective_exposed_s"] == pytest.approx(50e-9)


def test_breakdown_lists_layers_and_gaps(small):
    _, _, summary = small
    b = trace.breakdown(summary)
    assert b["device_ops"][0] == ["L.fc.bwd", pytest.approx(150e-9)]
    assert b["idle_gaps"][0] == ["bench.round", pytest.approx(300e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_window_falls_back_to_the_device_events():
    t = {"chips": {"0": [[10, 5, "a", ""], [30, 10, "b", ""]]}, "host": []}
    assert trace.window_of(t) == [10, 40]


def test_metric_readers_on_the_small_trace(small):
    from benchmarks.harness import load_by_name

    _, _, summary = small
    run = {"steps_traced": 2, "window_wall_s": 10.0, "feed_wait_s": 4.0,
           "chips": 4, "lrn_layers": ["conv1"], "memory_peak_bytes": 5e9,
           "flops_per_step": 197e12 * 300e-9 / 4,  # a quarter of peak while busy
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "layer_rows": []}
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    assert read("feed.wait_share") == pytest.approx(40.0)
    assert read("device.idle_share") == pytest.approx(40.0)
    assert read("model_step.device_ms") == pytest.approx(300e-9 * 1e3)
    assert read("model_step.mfu_busy") == pytest.approx(25.0)
    assert read("solver.unscoped_share") == pytest.approx(100 * 220 / 600)
    assert read("kernels.lrn_share") == pytest.approx(100 * 150 / 600)
    assert read("trainer.collective_share") == pytest.approx(100 * 100 / 600)
    assert read("device.peak_hbm_gb") == pytest.approx(5.0)
    assert read("kernels.matmul_roofline") is None  # no layer rows: nothing to read
    assert load_by_name("metrics", "device.idle_share").read(None, run) is None


def test_xplane_decoder_reads_a_real_profile(tmp_path):
    """Decode a trace jax writes here: the benchmark's span is found on
    the host plane with the duration ProfileData gives it."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.feed_wait"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    t = trace.load_xplane(path)
    names = {h[2] for h in t["host"]}
    assert {"bench.window", "bench.feed_wait"} <= names
    want = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    want[ev.name] = (ev.start_ns, ev.duration_ns)
    for start, dur, name in t["host"]:
        assert start == pytest.approx(want[name][0], abs=2)
        assert dur == pytest.approx(want[name][1], abs=2)
    assert t["chips"] == {}  # no TPU plane on the CPU
    assert t["window"][1] - t["window"][0] == pytest.approx(want["bench.window"][1], abs=2)
