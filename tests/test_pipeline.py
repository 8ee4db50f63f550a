"""Streaming data plane (`data/pipeline.py`): ring ordering, worker
failure surfacing, shared-memory hygiene, NHWC zero-transpose wire, obs
feed telemetry, and the `_decoded_pairs` decode overlap fix.

Small shapes throughout — the smoke tier runs all of it; the throughput
gate itself lives in ``tools/feed_bench.py --pipeline`` (host-side,
banked per docs/BENCHMARKS.md "Feed").
"""

import os
import signal
import time

import numpy as np
import pytest

from sparknet_tpu.data.pipeline import (
    ArraySource,
    DataFnSource,
    FeedSpec,
    PrestagedSource,
    ProcessPipeline,
    SyntheticImageSource,
    TransformStage,
    device_feed,
)
from sparknet_tpu.data.transform import DataTransformer, TransformConfig

pytestmark = pytest.mark.smoke


@pytest.fixture(autouse=True)
def no_leaked_shm():
    """Every test must leave /dev/shm exactly as it found it — the
    unlink-on-close contract (ISSUE 6 satellite), asserted in teardown."""
    if not os.path.isdir("/dev/shm"):
        yield
        return
    before = set(os.listdir("/dev/shm"))
    yield
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = set(os.listdir("/dev/shm")) - before
        if not leaked:
            return
        time.sleep(0.1)
    assert not leaked, f"leaked shared-memory segments: {leaked}"


# ---------------------------------------------------------------- ordering


def test_delivery_is_global_order_and_deterministic():
    src = SyntheticImageSource(batch=4, shape=(3, 12, 12), seed=7)
    with ProcessPipeline(src, num_batches=6, workers=2) as pipe:
        got = [f["label"].copy() for f in pipe.batches()]
    assert len(got) == 6
    for g, labels in enumerate(got):
        np.testing.assert_array_equal(labels, src.get(0, g)["label"])


def test_skewed_workers_still_deliver_in_order():
    """The reorder-deadlock shape: one worker much slower than the
    other, more batches than ring slots — per-worker slot ownership
    must keep the stream both live and ordered."""

    def skew(it):
        time.sleep(0.04 if it % 2 == 0 else 0.0)
        return {"x": np.full(2, it, np.float32)}

    with ProcessPipeline(DataFnSource(skew), num_batches=16,
                         workers=2) as pipe:
        vals = [int(f["x"][0]) for f in pipe.batches()]
    assert vals == list(range(16))


def test_transform_runs_in_workers():
    src = SyntheticImageSource(batch=4, shape=(3, 12, 12), seed=1)
    stage = TransformStage(TransformConfig(crop_size=8, mirror=True,
                                           seed=2), train=True)
    with ProcessPipeline(src, stage, num_batches=3, workers=1) as pipe:
        for feeds in pipe.batches():
            assert feeds["data"].shape == (4, 3, 8, 8)
            assert feeds["data"].dtype == np.float32
            assert feeds["label"].dtype == np.int32


def test_epoch_assignment_walks_array_source():
    arrays = {"data": np.arange(24, dtype=np.float32).reshape(12, 2),
              "label": np.arange(12, dtype=np.int32)}
    src = ArraySource(arrays, batch=4)  # 3 batches/epoch
    assert src.batches_per_epoch == 3
    with ProcessPipeline(src, num_batches=7, workers=2) as pipe:
        firsts = [int(f["label"][0]) for f in pipe.batches()]
    # epochs wrap deterministically: batches 0,4,8 | 0,4,8 | 0
    assert firsts == [0, 4, 8, 0, 4, 8, 0]


def test_spec_mismatch_is_a_worker_error():
    state = {"n": 0}

    def fn(it):
        return {"x": np.zeros(3 if it == 2 else 2, np.float32)}

    with ProcessPipeline(DataFnSource(fn), num_batches=4,
                         workers=1) as pipe:
        with pytest.raises(RuntimeError, match="FeedSpec"):
            list(pipe.batches())


# ---------------------------------------------------------------- failure


def test_worker_exception_surfaces_promptly():
    def fn(it):
        if it == 2:
            raise ValueError("decode exploded")
        return {"x": np.zeros(2, np.float32)}

    t0 = time.monotonic()
    with ProcessPipeline(DataFnSource(fn), num_batches=8,
                         workers=2) as pipe:
        with pytest.raises(RuntimeError, match="decode exploded"):
            list(pipe.batches())
    assert time.monotonic() - t0 < 30.0  # promptly, not a hang


def test_silent_worker_death_detected():
    slow = DataFnSource(
        lambda it: (time.sleep(0.1), {"x": np.zeros(2, np.float32)})[1])
    pipe = ProcessPipeline(slow, num_batches=50, workers=1)
    try:
        it = pipe.batches()
        next(it)
        os.kill(pipe._procs[0].pid, signal.SIGKILL)
        with pytest.raises(RuntimeError, match="died with exitcode"):
            for _ in it:
                pass
    finally:
        pipe.close()


def test_respawn_completes_stream_with_correct_contents():
    """Opt-in bounded respawn (ISSUE 8 satellite): a SIGKILLed worker's
    shard is deterministically re-owned by a replacement and the stream
    still delivers every batch, in order, with the exact bytes the
    source defines for each global id."""
    src = SyntheticImageSource(batch=4, shape=(3, 12, 12), seed=7)
    N = 24
    with ProcessPipeline(src, num_batches=N, workers=2,
                         max_respawns=2) as pipe:
        it = pipe.batches()
        got = [{k: np.array(v) for k, v in next(it).items()}
               for _ in range(4)]
        os.kill(pipe._procs[0].pid, signal.SIGKILL)
        got += [{k: np.array(v) for k, v in next(it).items()}
                for _ in range(N - 4)]
        assert pipe._respawns_used == 1
    for g, feeds in enumerate(got):
        ref = src.get(0, g)
        for k in ref:
            np.testing.assert_array_equal(feeds[k], ref[k])


def test_respawn_budget_zero_keeps_raising():
    """Default FeedSpec.max_respawns == 0 preserves the PR 6 contract:
    the first death raises (test_silent_worker_death_detected pins the
    silent-kill arm; this pins that respawn never engages unasked)."""
    assert FeedSpec.from_arrays({"x": np.zeros(2, np.float32)}
                                ).max_respawns == 0
    # a spec carrying a policy still EQUALS one probed from arrays:
    # max_respawns is policy, not geometry (compare=False)
    a = FeedSpec.from_arrays({"x": np.zeros(2, np.float32)})
    b = FeedSpec(a.fields, max_respawns=3)
    assert a == b


def test_respawn_exhausted_budget_raises():
    """A deterministically-raising source kills its replacement too:
    the bounded budget drains and the original error surfaces."""
    def fn(it):
        if it == 2:
            raise ValueError("decode exploded")
        return {"x": np.zeros(2, np.float32)}

    with ProcessPipeline(DataFnSource(fn), num_batches=8, workers=2,
                         max_respawns=1) as pipe:
        with pytest.raises(RuntimeError, match="decode exploded"):
            list(pipe.batches())
        assert pipe._respawns_used == 1


def test_respawn_journals_feed_stall_event(tmp_path):
    """Every absorbed death lands in the obs journal as a ``feed``
    stall event naming the worker and the re-owned shard start."""
    from sparknet_tpu.obs import schema
    from sparknet_tpu.obs.recorder import Recorder, set_recorder

    out = str(tmp_path / "feed.jsonl")
    set_recorder(Recorder(out))
    try:
        src = SyntheticImageSource(batch=2, shape=(3, 8, 8), seed=3)
        with ProcessPipeline(src, num_batches=12, workers=2,
                             max_respawns=1, name="spawny") as pipe:
            it = pipe.batches()
            next(it)
            os.kill(pipe._procs[1].pid, signal.SIGKILL)
            for _ in range(11):
                next(it)
    finally:
        set_recorder(None)
    n, errors = schema.validate_journal(out)
    assert not errors, errors
    stalls = [e for e in schema.load_journal(out)
              if e["event"] == "feed" and e["name"] == "spawny.respawn"]
    assert len(stalls) == 1
    assert "worker 1 died" in stalls[0]["note"]
    assert "respawn 1/1" in stalls[0]["note"]


def test_close_mid_consumption_releases_everything():
    """The ctrl-C shape: abandon the stream mid-run; close() must stop
    workers and unlink the ring (the autouse fixture asserts /dev/shm)."""
    src = SyntheticImageSource(batch=4, shape=(3, 8, 8))
    pipe = ProcessPipeline(src, num_batches=200, workers=2)
    it = pipe.batches()
    next(it)
    next(it)
    pipe.close()
    for p in pipe._procs:
        assert not p.is_alive()
    pipe.close()  # idempotent


def test_prefetcher_error_surfaces_promptly():
    """DevicePrefetcher twin of the worker-raise contract: a data_fn
    that raises must reach the consumer, not hang the queue."""
    from sparknet_tpu.data.prefetch import DevicePrefetcher

    def fn(it):
        if it == 1:
            raise RuntimeError("thread feed boom")
        return {"x": np.zeros(2, np.float32)}

    t0 = time.monotonic()
    pf = DevicePrefetcher(fn, num_iters=10)
    with pytest.raises(RuntimeError, match="thread feed boom"):
        list(pf)
    pf.close()
    assert time.monotonic() - t0 < 30.0


# ---------------------------------------------------------------- layout


def test_nhwc_pipeline_is_zero_transpose_end_to_end():
    """The PR-4 cash-out, pinned: a channels-last pipeline run does
    zero rank-4 host transposes (native NHWC synthesis + transform;
    C-contiguous channels-last views; the host adapter never runs) and
    zero ENTRY transposes (the DeviceAugment program the feed dispatches
    lowers with no rank-4 transpose — the layout census machinery)."""
    import jax

    from sparknet_tpu.analysis.graphcheck import layout_census
    from sparknet_tpu.data.device_transform import DeviceAugment
    from sparknet_tpu.ops import layout as L

    calls = {"n": 0}
    orig = L.feeds_to_internal

    def counting(feeds, layout=None):
        calls["n"] += 1
        return orig(feeds, layout)

    src = SyntheticImageSource(batch=2, shape=(3, 12, 12), seed=5,
                               layout="nhwc")
    stage = TransformStage(TransformConfig(mean_value=(1.0, 2.0, 3.0)),
                           train=True, layout="nhwc", out_dtype="|u1")
    L.feeds_to_internal = counting
    try:
        with ProcessPipeline(src, stage, num_batches=3,
                             workers=1) as pipe:
            for feeds in pipe.batches():
                data = feeds["data"]
                assert data.shape == (2, 12, 12, 3)  # channels-last wire
                assert data.flags.c_contiguous  # no lazy transpose view
    finally:
        L.feeds_to_internal = orig
    assert calls["n"] == 0  # the canonical->internal host adapter never ran

    # the entry program: device-side augment on the NHWC uint8 wire batch
    aug = DeviceAugment(TransformConfig(crop_size=8, mirror=True),
                        layout="nhwc")
    batch = src.get(0, 0)["data"]
    lowered = jax.jit(aug).lower(batch, jax.random.key(0))
    census = layout_census(lowered.as_text(),
                           lowered.compile().as_text())
    assert census["stablehlo_transposes_4d"] == 0, census


def test_nhwc_host_transformer_matches_nchw_math():
    """Same seed, same canonical pixels: the channels-last transformer
    must produce the transpose of the NCHW result (identical crops and
    mirror coins — the RNG draw order is layout-invariant)."""
    rs = np.random.RandomState(3)
    nchw = rs.randint(0, 255, (4, 3, 12, 12)).astype(np.uint8)
    nhwc = np.ascontiguousarray(nchw.transpose(0, 2, 3, 1))
    mean = rs.rand(3, 12, 12).astype(np.float32) * 255
    cfg = dict(mean_image=mean, crop_size=8, mirror=True, seed=11)
    out_nchw = DataTransformer(TransformConfig(**cfg))(nchw, True)
    out_nhwc = DataTransformer(TransformConfig(**cfg),
                               layout="nhwc")(nhwc, True)
    np.testing.assert_allclose(out_nhwc, out_nchw.transpose(0, 2, 3, 1),
                               atol=1e-5)
    # and the deterministic TEST path is bit-identical
    out_nchw = DataTransformer(TransformConfig(**cfg))(nchw, False)
    out_nhwc = DataTransformer(TransformConfig(**cfg),
                               layout="nhwc")(nhwc, False)
    np.testing.assert_array_equal(out_nhwc,
                                  out_nchw.transpose(0, 2, 3, 1))


def test_decode_jpeg_nhwc_skips_the_transpose():
    import io

    from PIL import Image

    from sparknet_tpu.data.minibatch import decode_jpeg

    buf = io.BytesIO()
    arr = np.random.RandomState(0).randint(
        0, 255, (16, 16, 3)).astype(np.uint8)
    Image.fromarray(arr).save(buf, format="JPEG")
    chw = decode_jpeg(buf.getvalue(), 8, 8)
    hwc = decode_jpeg(buf.getvalue(), 8, 8, layout="nhwc")
    assert chw.shape == (3, 8, 8)
    assert hwc.shape == (8, 8, 3)
    np.testing.assert_array_equal(hwc, chw.transpose(1, 2, 0))
    assert hwc.flags.c_contiguous


def test_wire_spec_from_net_shapes():
    from sparknet_tpu.ops.data_layers import wire_spec

    shapes = {"data": (8, 227, 227, 3), "label": (8,)}
    spec = wire_spec(shapes, raw=True)
    assert spec["data"] == ((8, 227, 227, 3), "|u1")
    assert spec["label"] == ((8,), "<i4")
    assert wire_spec(shapes)["data"][1] == "<f4"


# ---------------------------------------------------------------- device


def test_device_feed_yields_device_batches_in_order():
    import jax

    src = SyntheticImageSource(batch=2, shape=(3, 8, 8), seed=9)
    pipe = ProcessPipeline(src, num_batches=5, workers=2)
    with pipe, device_feed(pipe, depth=2) as pf:
        labels = []
        for feeds in pf:
            assert isinstance(feeds["data"], jax.Array)
            labels.append(np.asarray(feeds["label"]))
    assert len(labels) == 5
    for g, got in enumerate(labels):
        np.testing.assert_array_equal(got, src.get(0, g)["label"])


def test_as_data_fn_serves_solver_contract():
    src = SyntheticImageSource(batch=2, shape=(3, 8, 8), seed=4)
    with ProcessPipeline(src, num_batches=4, workers=1) as pipe:
        fn = pipe.as_data_fn(copy=True)
        feeds = [fn(i) for i in range(4)]
    for g, f in enumerate(feeds):
        np.testing.assert_array_equal(f["label"], src.get(0, g)["label"])


# ---------------------------------------------------------------- obs


def test_feed_events_are_schema_valid(tmp_path):
    from sparknet_tpu.obs import schema
    from sparknet_tpu.obs.recorder import Recorder, set_recorder

    journal = str(tmp_path / "feed.jsonl")
    rec = set_recorder(Recorder(journal))
    try:
        src = PrestagedSource({"data": np.zeros((2, 8, 8, 3), np.uint8),
                               "label": np.zeros(2, np.int32)})
        with ProcessPipeline(src, num_batches=6, workers=1,
                             obs_every=2) as pipe:
            for _ in pipe.batches():
                pass
        rec.close()
    finally:
        set_recorder(None)
    n_lines, errors = schema.validate_journal(journal)
    assert not errors, errors
    feed_events = list(schema.iter_events(journal, "feed"))
    assert feed_events, "no feed telemetry journaled"
    for ev in feed_events:
        assert set(ev["stages"]) <= {"slot_wait", "source", "decode",
                                     "transform", "write", "put"}
        assert ev["batches"] > 0 and ev["images"] > 0


def test_feed_disarmed_writes_nothing(tmp_path):
    """SPARKNET_OBS off => zero journal writes from the pipeline (the
    obs off-contract extends to the feed)."""
    marker = tmp_path / "should_not_exist.jsonl"
    src = SyntheticImageSource(batch=2, shape=(3, 8, 8))
    with ProcessPipeline(src, num_batches=3, workers=1) as pipe:
        for _ in pipe.batches():
            pass
        assert pipe.stats["batches"] == 3  # attribution still accumulates
    assert not marker.exists()


def test_report_renders_feed_stage_table(tmp_path):
    from sparknet_tpu.obs.recorder import Recorder, set_recorder
    from sparknet_tpu.obs.report import render_path

    journal = str(tmp_path / "feed.jsonl")
    rec = set_recorder(Recorder(journal))
    try:
        src = SyntheticImageSource(batch=2, shape=(3, 8, 8))
        with ProcessPipeline(src, num_batches=4, workers=1,
                             obs_every=2, name="feed.test") as pipe:
            for _ in pipe.batches():
                pass
        rec.close()
    finally:
        set_recorder(None)
    text = render_path(journal)
    assert "feed stages (host-side)" in text
    assert "feed.test" in text
    assert "slot_wait" in text


# ---------------------------------------------------------------- decode


def test_decoded_pairs_overlap_across_chunk_boundary():
    """The satellite fix pinned structurally: with the pipelined window
    the pool pulls sample ``chunk`` before yielding result 1 (the old
    ``pool.map``-per-chunk flush pulled it only after the whole first
    chunk had been yielded)."""
    from sparknet_tpu.data import minibatch as mb

    events = []

    def sample_stream(n):
        for i in range(n):
            events.append(("pull", i))
            yield (b"x%d" % i, i)

    def fake_decode(data, h, w, layout="nchw"):
        return np.zeros((3, h, w), np.uint8)

    orig = mb.decode_jpeg
    mb.decode_jpeg = fake_decode
    try:
        for arr, label in mb._decoded_pairs(sample_stream(10), 4, 4,
                                            workers=2, chunk=4):
            events.append(("yield", label))
    finally:
        mb.decode_jpeg = orig
    labels = [e[1] for e in events if e[0] == "yield"]
    assert labels == list(range(10))  # order identical to serial
    # overlap: sample 4 (second chunk) is pulled before result 1 yields
    assert events.index(("pull", 4)) < events.index(("yield", 1)), events


def test_pooled_decode_output_identical_with_broken_images():
    """Order + drop semantics unchanged by the overlap fix (belt and
    braces beside tests/test_data.py's pooled-vs-serial pin)."""
    import io

    from PIL import Image

    from sparknet_tpu.data.minibatch import make_minibatches_compressed

    rs = np.random.RandomState(5)

    def jpeg(i):
        buf = io.BytesIO()
        Image.fromarray(rs.randint(0, 255, (12, 12, 3)).astype(np.uint8)
                        ).save(buf, format="JPEG")
        return (buf.getvalue(), i)

    samples = [jpeg(i) for i in range(7)]
    samples.insert(2, (b"broken", 99))
    serial = list(make_minibatches_compressed(samples, 2, 8, 8, workers=1))
    pooled = list(make_minibatches_compressed(samples, 2, 8, 8, workers=3))
    assert len(serial) == len(pooled)
    for (si, sl), (pi, pl) in zip(serial, pooled):
        np.testing.assert_array_equal(si, pi)
        np.testing.assert_array_equal(sl, pl)


# ---------------------------------------------------------------- misc


def test_ring_too_small_raises():
    src = SyntheticImageSource(batch=2, shape=(3, 8, 8))
    with pytest.raises(ValueError, match="deadlock"):
        ProcessPipeline(src, num_batches=2, workers=2, slots=2)


def test_feed_spec_roundtrip():
    feeds = {"data": np.zeros((2, 4, 4, 3), np.uint8),
             "label": np.zeros(2, np.int32)}
    spec = FeedSpec.from_arrays(feeds)
    assert spec.slot_bytes == 2 * 4 * 4 * 3 + 2 * 4
    buf = bytearray(spec.slot_bytes)
    views = spec.views(memoryview(buf), 0)
    assert views["data"].shape == (2, 4, 4, 3)
    assert views["label"].dtype == np.int32
    spec.check(feeds)
    with pytest.raises(ValueError, match="FeedSpec"):
        spec.check({"data": feeds["data"],
                    "label": feeds["label"].astype(np.int64)})
