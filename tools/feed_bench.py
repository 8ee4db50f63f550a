"""Input-pipeline benchmark: the host feed path the reference measured.

The reference's #1 measured bottleneck was its per-minibatch feed: the
JNA callback doing crop+mean for a 256-image 227x227 AlexNet batch cost
~1.2 s (ref: src/test/scala/apps/CallbackBenchmarkSpec.scala:3-17
"fancy indexing very expensive").  This tool times OUR equivalent —
the DataTransformer (mean-subtract + random 227 crop + mirror) over the
same batch shape, numpy and multithreaded C++ backends, plus the
prefetcher's overlap — and prints one JSON line per variant:

    python tools/feed_bench.py [--batch 256] [--iters 20]
    python tools/feed_bench.py --pipeline [--bank]   # process-feed arms

``--pipeline`` benches the multi-process shared-memory feed
(``data/pipeline.py``) against the headline ingest gate: AlexNet wire
shapes (b256 uint8 227x227), PURE ingest (prestaged batches, the
workers' only per-batch work is the slot memcpy — the ring transport
itself), sustained over >= 64 batches, vs the banked r5 headline
12,290 img/s (docs/BENCHMARKS.md).  A threaded twin (same work on the
legacy daemon-thread feed), the in-worker host-transform attribution
arm, and the DEVICE arm (raw uint8 ring, no worker transform — the
augment runs post-placement in XLA; the gate pins its in-worker
transform share <= 15% of the e2e wall vs the banked 81% host-arm
wall) print alongside; ``--sweep-workers 1,2,4`` adds per-worker-count
ingest + e2e rows (the multi-core scaling claim as one command);
``--bank`` routes the gate record through ``common.bank_guard`` to
docs/feed_bench_last.json; exits 1 if the gate record measured
nothing.

Timing-contract note: every timed loop here is HOST-side — numpy/PIL
transforms, the prefetcher's queue, and the pipeline's shared-memory
ring — so no value fence is needed; nothing in this module dispatches
to a device inside a timing window.  The device arm keeps that
contract: its timed loop is the uint8 ring alone, and the XLA augment
is rehearsed ONCE outside any timing window on a forced-CPU backend
(zero chip time; jax is reached only through ``sparknet_tpu.*``
imports).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REF_MS_PER_BATCH = 1200.0  # the reference's measured cost per 256-IMAGE batch

# The ingest gate: the AlexNet b256 bf16 rate an earlier session
# reported (not re-measured on the local chip — ROADMAP S1/S7) — the
# feed must sustain at least what the chip consumes, or the pipeline is
# the new bottleneck.
HEADLINE_IMG_S = 12290.0
LAST_PATH = "docs/feed_bench_last.json"


def bench_transform(backend: str, batch: int, iters: int) -> dict:
    from sparknet_tpu.data.transform import DataTransformer, TransformConfig

    rs = np.random.RandomState(0)
    raw = rs.randint(0, 256, (batch, 3, 256, 256), dtype=np.uint8)
    mean = rs.rand(3, 256, 256).astype(np.float32) * 255
    xform = DataTransformer(
        TransformConfig(
            mean_image=mean, crop_size=227, mirror=True, seed=1,
            backend=backend,
        )
    )
    out = xform(raw, True)  # warm (native lib load, allocator)
    assert out.shape == (batch, 3, 227, 227), out.shape
    t0 = time.perf_counter()
    for _ in range(iters):
        out = xform(raw, True)
    dt_ms = (time.perf_counter() - t0) / iters * 1e3
    # normalize the reference cost to this batch size before comparing
    ref_ms = REF_MS_PER_BATCH * batch / 256.0
    return {
        "metric": f"feed_transform_{backend}_ms_per_batch",
        "value": round(dt_ms, 2),
        "unit": f"ms/{batch}-img batch",
        "vs_reference_callback": round(ref_ms / dt_ms, 1),
    }


def bench_decode(batch: int, iters: int, workers: int) -> dict:
    """JPEG decode throughput, serial vs thread-pooled (PIL's C decode
    releases the GIL, so the pool scales with host cores — the
    per-executor decode parallelism of the reference's Spark ingest)."""
    import io

    from PIL import Image

    from sparknet_tpu.data.minibatch import make_minibatches_compressed

    rs = np.random.RandomState(0)
    jpegs = []
    for _ in range(batch):
        buf = io.BytesIO()
        Image.fromarray(rs.randint(0, 255, (256, 256, 3), np.uint8)).save(
            buf, format="JPEG")
        jpegs.append((buf.getvalue(), 0))

    def run_once():
        return sum(1 for _ in make_minibatches_compressed(
            jpegs, batch, 227, 227, workers=workers))

    n = run_once()  # warmup OUTSIDE the timed loop (and not in an assert:
    assert n == 1   # python -O must not silently drop the warmup)
    t0 = time.perf_counter()
    for _ in range(iters):
        run_once()
    dt_ms = (time.perf_counter() - t0) / iters * 1e3
    return {
        "metric": f"feed_decode_workers{workers}_ms_per_batch",
        "value": round(dt_ms, 2),
        "unit": f"ms/{batch}-img batch (256px jpeg -> 227px chw)",
    }


def bench_prefetch(batch: int, iters: int) -> dict:
    """Producer/consumer overlap: batches/s through the device prefetcher
    with a 10 ms synthetic producer (the decode+augment stand-in)."""
    from sparknet_tpu.data.prefetch import DevicePrefetcher

    def data_fn(it):
        time.sleep(0.010)
        return {"data": np.zeros((batch, 8), np.float32)}

    pre = DevicePrefetcher(data_fn, num_iters=iters + 1, depth=3)
    it = iter(pre)
    next(it)  # spin-up
    t0 = time.perf_counter()
    for _ in range(iters):
        next(it)
    dt_ms = (time.perf_counter() - t0) / iters * 1e3
    pre.close()
    return {
        "metric": "prefetch_ms_per_batch",
        "value": round(dt_ms, 2),
        "unit": "ms (10 ms producer, depth 3)",
    }


def _wire_batch(batch: int, side: int = 227) -> dict:
    """One AlexNet-wire batch: uint8 channels-last (the decoder's native
    HWC order — ops/layout.py wire contract) + int32 labels."""
    rs = np.random.RandomState(0)
    return {
        "data": rs.randint(0, 256, (batch, side, side, 3), dtype=np.uint8),
        "label": rs.randint(0, 1000, batch).astype(np.int32),
    }


def _consume(feeds: dict) -> int:
    """The consumer's per-batch touch: one byte per array proves the
    views are live without re-reading the whole slot (ingest delivers
    bytes; the step, not the feed, streams them)."""
    return sum(int(np.asarray(v).flat[0]) for v in feeds.values())


def bench_pipeline_ingest(batch: int, batches: int,
                          workers: int | None = None) -> dict:
    """Sustained pure-ingest img/s through the process pipeline:
    prestaged wire batches, worker work = slot memcpy only."""
    from sparknet_tpu.data.pipeline import PrestagedSource, ProcessPipeline

    feeds = _wire_batch(batch)
    warm = 8
    with ProcessPipeline(PrestagedSource(feeds), num_batches=batches + warm,
                         workers=workers, name="feed.ingest") as pipe:
        it = pipe.batches()
        for _ in range(warm):
            _consume(next(it))
        t0 = time.perf_counter()
        for _ in range(batches):
            _consume(next(it))
        dt = time.perf_counter() - t0
        stats = dict(pipe.stats)
        nworkers = pipe.workers
    img_s = batch * batches / dt
    n = max(int(stats.get("batches", 1)), 1)
    return {
        "metric": "feed_pipeline_ingest_img_s",
        "value": round(img_s, 1),
        "unit": f"img/s (b{batch} uint8 227x227 pure ingest, "
                f"{batches} batches sustained)",
        "workers": nworkers,
        "stages_ms_per_batch": {
            k: round(v / n * 1e3, 3) for k, v in stats.items()
            if k != "batches"},
    }


def bench_threaded_ingest(batch: int, batches: int) -> dict:
    """The threaded twin of the ingest arm: the SAME slot-memcpy work
    (copy into a ring of preallocated buffers) on the legacy
    daemon-thread feed — what the pipeline replaces, doing what the
    pipeline does, GIL and all."""
    import queue as q
    import threading

    feeds = _wire_batch(batch)
    slots = [{k: np.empty_like(v) for k, v in feeds.items()}
             for _ in range(4)]
    free: q.Queue = q.Queue()
    full: q.Queue = q.Queue()
    for s in range(len(slots)):
        free.put(s)
    warm = 8
    total = batches + warm

    def producer():
        for _ in range(total):
            s = free.get()
            for k in slots[s]:
                np.copyto(slots[s][k], feeds[k])
            full.put(s)

    th = threading.Thread(target=producer, daemon=True)
    th.start()
    for _ in range(warm):
        s = full.get()
        _consume(slots[s])
        free.put(s)
    t0 = time.perf_counter()
    for _ in range(batches):
        s = full.get()
        _consume(slots[s])
        free.put(s)
    dt = time.perf_counter() - t0
    th.join(timeout=5.0)
    return {
        "metric": "feed_threaded_ingest_img_s",
        "value": round(batch * batches / dt, 1),
        "unit": f"img/s (b{batch} uint8 227x227 pure ingest, "
                "daemon-thread feed twin)",
    }


def bench_pipeline_transform(batch: int, batches: int,
                             workers: int | None = None) -> dict:
    """The end-to-end attribution arm: synthetic 256px wire batches,
    DataTransformer (227 crop + mirror + mean) IN the workers, uint8
    slots — per-stage walls say where a real feed's time goes."""
    from sparknet_tpu.data.pipeline import (
        ProcessPipeline,
        SyntheticImageSource,
        TransformStage,
    )
    from sparknet_tpu.data.transform import TransformConfig

    rs = np.random.RandomState(1)
    mean = (rs.rand(3, 256, 256).astype(np.float32) * 255)
    stage = TransformStage(
        TransformConfig(mean_image=mean, crop_size=227, mirror=True,
                        seed=1),
        train=True, layout="nhwc", out_dtype="<f4")
    src = SyntheticImageSource(batch, (3, 256, 256), seed=3,
                               layout="nhwc")
    with ProcessPipeline(src, stage, num_batches=batches,
                         workers=workers, name="feed.e2e") as pipe:
        t0 = time.perf_counter()
        for feeds in pipe.batches():
            _consume(feeds)
        dt = time.perf_counter() - t0
        stats = dict(pipe.stats)
        nworkers = pipe.workers
    n = max(int(stats.get("batches", 1)), 1)
    return {
        "metric": "feed_pipeline_e2e_img_s",
        "value": round(batch * batches / dt, 1),
        "unit": f"img/s (b{batch} 256px synth -> crop227+mirror+mean f32,"
                " in-worker transform)",
        "workers": nworkers,
        "stages_ms_per_batch": {
            k: round(v / n * 1e3, 3) for k, v in stats.items()
            if k != "batches"},
    }


def bench_pipeline_device(batch: int, batches: int,
                          workers: int | None = None,
                          rehearse: bool = False,
                          platform: str = "") -> dict:
    """The device-arm e2e twin of :func:`bench_pipeline_transform`: the
    SAME synthetic 256px wire, but the ring ships raw uint8 with NO
    worker transform stage — crop/mirror/mean run post-placement in XLA
    (``data/device_transform.py``), so the host's per-image work
    collapses to decode + slot memcpy and the wire carries ~4x fewer
    bytes than f32 crops.  The timed loop is the ring alone (host-side,
    honest); with ``rehearse=True`` one delivered batch is copied out
    BEFORE the timing window and pushed through ``DeviceAugment`` on a
    forced-CPU backend afterwards — shape/dtype proof that the uint8
    wire feeds the augment, zero chip time."""
    from sparknet_tpu.data.pipeline import (
        ProcessPipeline,
        SyntheticImageSource,
    )

    src = SyntheticImageSource(batch, (3, 256, 256), seed=3,
                               layout="nhwc")
    sample = None
    with ProcessPipeline(src, None, num_batches=batches + 1,
                         workers=workers, name="feed.e2e_device") as pipe:
        it = pipe.batches()
        first = next(it)  # warm + the rehearsal copy, outside the timing
        if rehearse:
            sample = {k: np.array(v, copy=True) for k, v in first.items()}
        _consume(first)
        t0 = time.perf_counter()
        for feeds in it:
            _consume(feeds)
        dt = time.perf_counter() - t0
        stats = dict(pipe.stats)
        nworkers = pipe.workers
    n = max(int(stats.get("batches", 1)), 1)
    row = {
        "metric": "feed_pipeline_e2e_device_img_s",
        "value": round(batch * batches / dt, 1),
        "unit": f"img/s (b{batch} 256px synth raw uint8 wire, augment "
                "deferred to XLA post-placement)",
        "workers": nworkers,
        "stages_ms_per_batch": {
            k: round(v / n * 1e3, 3) for k, v in stats.items()
            if k != "batches"},
    }
    if rehearse and sample is not None:
        row["device_rehearsal"] = _rehearse_device_augment(sample, platform)
    return row


def _rehearse_device_augment(sample: dict, platform: str = "") -> dict:
    """One forced-CPU DeviceAugment pass over a copied wire batch —
    proves the raw uint8 ring output is exactly what the XLA augment
    consumes (HWC uint8 in, f32 crops out), without any device work
    inside a timing window and without taking the chip."""
    from sparknet_tpu.common import force_platform
    from sparknet_tpu.data.device_transform import DeviceAugment
    from sparknet_tpu.data.transform import TransformConfig

    if not platform:
        # zero-chip by contract
        force_platform("cpu")
    rs = np.random.RandomState(1)
    mean = rs.rand(3, 256, 256).astype(np.float32) * 255
    aug = DeviceAugment(
        TransformConfig(mean_image=mean, crop_size=227, mirror=True),
        layout="nhwc")
    out = np.asarray(aug.device_fn(pid=0)(sample, 0)["data"])
    assert out.shape == (sample["data"].shape[0], 227, 227, 3), out.shape
    assert out.dtype == np.float32, out.dtype
    u8 = sum(int(np.asarray(v).nbytes) for v in sample.values())
    f32 = (int(out.nbytes)
           + int(np.asarray(sample["label"]).nbytes))
    return {
        "in": list(sample["data"].shape) + ["|u1"],
        "out": list(out.shape) + ["<f4"],
        "wire_bytes_u8": u8,
        "f32_crop_bytes": f32,
        # full-size u8 wire vs the f32 crops the host arm would ship
        "wire_ratio_u8_vs_f32": round(f32 / max(u8, 1), 3),
    }


def _transform_share(row: dict, batch: int) -> float:
    """In-worker transform wall as a fraction of the arm's e2e wall
    (ms/batch from img/s — the acceptance gate's 15% denominator)."""
    wall_ms = batch / max(row["value"], 1e-9) * 1e3
    return row["stages_ms_per_batch"].get("transform", 0.0) / wall_ms


def host_roofline(batch: int) -> dict:
    """The box's physical ingest ceiling: one straight memcpy of the
    wire batch into a preallocated buffer — no ring, no queues, no
    second process.  Any pipeline number above this is a measurement
    bug; the gap below it is the transport's true overhead."""
    feeds = _wire_batch(batch)
    dst = {k: np.empty_like(v) for k, v in feeds.items()}
    for k in dst:
        np.copyto(dst[k], feeds[k])  # warm (page faults)
    best = float("inf")
    for _ in range(30):
        t0 = time.perf_counter()
        for k in dst:
            np.copyto(dst[k], feeds[k])
        best = min(best, time.perf_counter() - t0)
    return {
        # BEST-iteration memcpy rate: a genuine upper bound (no
        # sustained ring number may exceed the fastest bare copy the
        # box produced — the no-value-above-its-roofline house rule)
        "roofline_img_s_upper_bound": round(batch / best, 1),
        "roofline_basis": "best-of-30 single memcpy of the wire batch "
                          "(one writer pass; the ring adds a bounded-"
                          "queue round trip and cross-process "
                          "scheduling on top)",
        "cores": os.cpu_count() or 1,
    }


def run_pipeline_arms(args) -> int:
    """The --pipeline mode: ingest gate + threaded twin + attribution,
    one JSON line each, then the combined gate record (banked via
    common.bank_guard under --bank)."""
    batches = max(args.iters, 64)  # "sustained" floor for the gate
    # median of 5 interleaved trials per arm: single-core scheduling
    # noise swings either twin ~20% run to run; one trial could crown
    # either architecture by luck
    ingest_trials, threaded_trials = [], []
    for _ in range(5):
        ingest_trials.append(bench_pipeline_ingest(
            args.batch, batches, workers=args.workers or None))
        threaded_trials.append(bench_threaded_ingest(args.batch, batches))
    ingest = sorted(ingest_trials, key=lambda r: r["value"])[2]
    threaded = sorted(threaded_trials, key=lambda r: r["value"])[2]
    ingest = {**ingest,
              "trials_img_s": [r["value"] for r in ingest_trials]}
    threaded = {**threaded,
                "trials_img_s": [r["value"] for r in threaded_trials]}
    print(json.dumps(ingest))
    print(json.dumps(threaded))
    e2e = bench_pipeline_transform(args.batch, max(batches // 8, 4),
                                   workers=args.workers or None)
    print(json.dumps(e2e))
    e2e_dev = bench_pipeline_device(args.batch, max(batches // 8, 4),
                                    workers=args.workers or None,
                                    rehearse=True,
                                    platform=getattr(args, "platform", ""))
    print(json.dumps(e2e_dev))
    sweep = []
    for w in sorted({int(s) for s in
                     (args.sweep_workers or "").split(",") if s.strip()}):
        sb = max(batches // 4, 16)
        ing_w = bench_pipeline_ingest(args.batch, sb, workers=w)
        host_w = bench_pipeline_transform(args.batch, max(sb // 4, 4),
                                          workers=w)
        dev_w = bench_pipeline_device(args.batch, max(sb // 4, 4),
                                      workers=w)
        row = {
            "metric": "feed_workers_sweep_row",
            "workers": w,
            "ingest_img_s": ing_w["value"],
            "e2e_host_img_s": host_w["value"],
            "e2e_device_img_s": dev_w["value"],
            "e2e_host_stages_ms_per_batch": host_w["stages_ms_per_batch"],
            "e2e_device_stages_ms_per_batch": dev_w["stages_ms_per_batch"],
        }
        sweep.append(row)
        print(json.dumps(row))
    roof = host_roofline(args.batch)

    met = ingest["value"] >= HEADLINE_IMG_S
    host_share = _transform_share(e2e, args.batch)
    dev_share = _transform_share(e2e_dev, args.batch)
    record = {
        "metric": "feed_pipeline_gate",
        "value": ingest["value"],
        "unit": f"img/s (b{args.batch} uint8 227x227 pure ingest)",
        "target_img_s": HEADLINE_IMG_S,
        "met_target": met,
        "trials_img_s": ingest["trials_img_s"],
        "threaded_img_s": threaded["value"],
        "threaded_trials_img_s": threaded["trials_img_s"],
        "process_beats_threaded": ingest["value"] > threaded["value"],
        "process_vs_threaded": round(
            ingest["value"] / max(threaded["value"], 1.0), 3),
        "e2e_img_s": e2e["value"],
        "workers": ingest["workers"],
        "stages_ms_per_batch": ingest["stages_ms_per_batch"],
        "e2e_stages_ms_per_batch": e2e["stages_ms_per_batch"],
        # the device arm: raw uint8 ring, augment deferred to XLA — the
        # acceptance gate pins its in-worker transform share <= 15%
        "e2e_device_img_s": e2e_dev["value"],
        "e2e_device_stages_ms_per_batch": e2e_dev["stages_ms_per_batch"],
        "host_transform_share": round(host_share, 4),
        "device_transform_share": round(dev_share, 4),
        "device_arm_met": dev_share <= 0.15,
        "device_rehearsal": e2e_dev.get("device_rehearsal"),
        **({"workers_sweep": sweep} if sweep else {}),
        **roof,
        # host-side measurement: real walls on this box, no chip involved
        "measured": True,
        "host_side": True,
    }
    bound = roof["roofline_img_s_upper_bound"]
    if record["value"] > bound:
        # never print/bank a throughput above its own stated roofline
        # (CLAUDE.md house rule; the obs report refuses such records)
        record["bound_inconsistency"] = (
            f"sustained {record['value']:,} img/s exceeds the best "
            f"bare-memcpy bound {bound:,} img/s — measurement bug, "
            "not evidence")
        record["met_target"] = False
    if not record["met_target"] or (roof["cores"] == 1
                                    and not record["process_beats_threaded"]):
        # the documented-roofline arm: name the physical limit.  On one
        # core the two architectures do the SAME serialized memcpy work
        # — transport parity is the physical outcome (the process feed's
        # win condition, GIL-free parallel decode/transform, needs
        # cores > 1; the e2e stage walls show what it would parallelize)
        record["attribution"] = (
            f"{roof['cores']} core(s): producer and consumer serialize "
            f"on the same CPU, so process-vs-threaded = "
            f"{record['process_vs_threaded']} is scheduling noise "
            f"around transport parity; ingest wall is the slot memcpy "
            f"itself (per-stage ms {ingest['stages_ms_per_batch']}, "
            f"bare-memcpy bound {bound:,.0f} img/s); the host-arm "
            f"transform "
            f"({e2e['stages_ms_per_batch'].get('transform', 0):.0f} "
            f"ms/batch, {host_share:.0%} of its e2e wall) is the "
            f"serialized stage the DEVICE arm removes entirely "
            f"({dev_share:.0%} in-worker transform share — the augment "
            f"is chip work), and the remaining in-worker decode is the "
            f"stage --sweep-workers scales on a cores > 1 host")
    elif roof["cores"] > 1:
        record["attribution"] = (
            f"{roof['cores']} cores: in-worker decode+transform "
            f"parallelize across ring workers (workers_sweep rows bank "
            f"the per-count scaling); the device arm drops the host "
            f"transform share from {host_share:.0%} to {dev_share:.0%} "
            f"of the e2e wall and ships ~4x fewer wire bytes (uint8 vs "
            f"f32 crops) — what remains on the host is decode + slot "
            f"memcpy only")
    print(json.dumps(record))
    if args.bank:
        from sparknet_tpu.common import bank_guard

        bank_guard(LAST_PATH, record, measured=record["measured"])
    return 0 if record["measured"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--pipeline", action="store_true",
                    help="bench the process feed (data/pipeline.py): "
                    "pure-ingest gate vs the 12,290 img/s headline, "
                    "threaded twin, per-stage attribution")
    ap.add_argument("--workers", type=int, default=0,
                    help="pipeline worker processes (0 = auto)")
    ap.add_argument("--sweep-workers", default="",
                    help="comma-separated worker counts (e.g. 1,2,4): "
                    "adds per-count ingest + e2e host/device rows to "
                    "the --pipeline gate record (the multi-core scaling "
                    "claim as one banked command)")
    ap.add_argument("--bank", action="store_true",
                    help="bank the --pipeline gate record to "
                    f"{LAST_PATH} via common.bank_guard")
    ap.add_argument("--platform", default="",
                    help="force a jax platform for the prefetch leg")
    args = ap.parse_args()
    if args.platform:
        from sparknet_tpu.common import force_platform

        force_platform(args.platform)
    if args.pipeline:
        return run_pipeline_arms(args)

    print(json.dumps(bench_transform("numpy", args.batch, args.iters)))
    from sparknet_tpu import native

    if native.available():
        print(json.dumps(bench_transform("native", args.batch, args.iters)))
    else:
        print(json.dumps({"metric": "feed_transform_native_ms_per_batch",
                          "skipped": "libsparknet_native unavailable"}))
    import os

    decode_iters = max(args.iters // 4, 2)  # decode is the slow leg
    print(json.dumps(bench_decode(args.batch, decode_iters, workers=1)))
    n = min(os.cpu_count() or 1, 8)
    if n > 1:
        print(json.dumps(bench_decode(args.batch, decode_iters, workers=n)))
    print(json.dumps(bench_prefetch(args.batch, args.iters)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
