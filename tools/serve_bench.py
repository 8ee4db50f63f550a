"""Serving-engine load benchmark: throughput-per-latency-budget.

The serving twin of tools/feed_bench.py: drive the AOT-batched engine
(``sparknet_tpu/serve``) under synthetic load and print one JSON line
per arm, then a combined gate record (banked to
``docs/serve_bench_last.json`` under ``--bank``):

* **closed-loop** — per-bucket saturation: exact-fit bursts through
  each ladder bucket, requests/s and per-request p50/p99 (how much
  traffic a bucket sustains when demand always fills it).
* **open-loop** — Poisson arrivals at ``--rate`` req/s against the
  ``max_wait_ms`` deadline flush: the tail-latency claim under trickle
  load is that NO request's queue wait exceeds max_wait_ms by more
  than one scheduler tick (arrivals don't wait for service — the
  generator enqueues on schedule even when the engine lags).
* **swap** (``--swap``) — the hot-reload arm: a full rollout
  (candidate AOT-compiled on a builder thread, ``swap_model`` under
  the pump lock — sparknet_tpu/loop protocol) lands mid-stream under
  the same Poisson load; reports the swap-gap (max request stall and
  p99 over requests overlapping the swap) next to the lock-hold wall.
  With this arm the compile gate moves to the per-thread ledger
  (``engine.serve_path_compiles`` must read 0 — builder compiles are
  by design), and any unresolved ticket voids the record.

House rules: the recompile sentinel must read 0 post-warmup compiles
across both arms (AOT buckets — any recompile voids the run);
per-request latencies come from the engine's journaled decomposition;
every record names its device, and a run that finds no accelerator and
was not pinned to the CPU exits 2.  CPU runs are labeled host-side
provenance (``platform: cpu``, ``chip_measured: false``).

ref: apps/ImageNetRunDBApp.scala:1 (the reference's batch-scoring
consumer; request-level load generation is new TPU-first surface).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAST_PATH = "docs/serve_bench_last.json"


def _pctl(vals, q):
    from sparknet_tpu.serve.engine import percentile

    return percentile(list(vals), q)


def bench_closed_loop(engine, model, burst: int, rounds: int) -> dict:
    """Saturate one bucket: ``rounds`` exact-fit bursts of ``burst``
    requests, pumped back to back."""
    from sparknet_tpu.serve.loadgen import synthetic_items

    served = engine._models[model]
    n0 = len(served.lat_total_ms)
    rs = np.random.RandomState(burst)
    items = synthetic_items(served, burst, rs)
    t0 = time.perf_counter()
    for _ in range(rounds):
        for item in items:
            engine.submit(model, item)
        engine.pump(force=True)
    dt = time.perf_counter() - t0
    lats = served.lat_total_ms[n0:]
    return {
        "metric": f"serve_closed_b{burst}_rps",
        "value": round(burst * rounds / dt, 1),
        "unit": f"req/s (bucket {burst}, {rounds} exact-fit bursts)",
        "p50_ms": round(_pctl(lats, 50), 3),
        "p99_ms": round(_pctl(lats, 99), 3),
    }


def bench_open_loop(engine, model, rate: float, seconds: float,
                    max_wait_ms: float, seed: int = 7) -> dict:
    """Poisson arrivals at ``rate`` req/s: the deadline-flush arm.

    The generator sleeps to each exponential inter-arrival time and
    never blocks on results — queue waits measure the BATCHER's
    deadline policy, not generator backpressure.  A worker thread
    drains flushes as they come due, exactly the ``serve_forever``
    production path.
    """
    import threading

    from sparknet_tpu.serve.loadgen import synthetic_items

    served = engine._models[model]
    n0 = len(served.lat_total_ms)
    q0 = len(served.lat_queue_ms)
    rs = np.random.RandomState(seed)
    n = max(1, int(rate * seconds))
    items = synthetic_items(served, min(n, 64), rs)
    gaps = rs.exponential(1.0 / rate, n)
    stop = threading.Event()
    worker = threading.Thread(
        target=lambda: engine.serve_forever(until=stop.is_set),
        daemon=True)
    worker.start()
    tickets = []
    t0 = time.perf_counter()
    for i in range(n):
        target = t0 + float(np.sum(gaps[:i + 1]))
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        tickets.append(engine.submit(model, items[i % len(items)]))
    for t in tickets:
        t.wait(timeout=60.0)
    dt = time.perf_counter() - t0
    stop.set()
    worker.join(timeout=5.0)
    waits = served.lat_queue_ms[q0:]
    lats = served.lat_total_ms[n0:]
    # one scheduler tick of slack: wait_due wakes AT the deadline, but
    # the wake itself is at the mercy of the host scheduler
    tick_ms = 15.0
    bounded = _pctl(waits, 100) <= max_wait_ms + tick_ms
    return {
        "metric": "serve_open_poisson_p99_ms",
        "value": round(_pctl(lats, 99), 3),
        "unit": f"ms total latency (open loop, {rate:g} req/s Poisson, "
                f"{n} requests)",
        "p50_ms": round(_pctl(lats, 50), 3),
        "queue_max_ms": round(_pctl(waits, 100), 3),
        "max_wait_ms": max_wait_ms,
        "deadline_bounded": bool(bounded),
        "achieved_rps": round(n / dt, 1),
    }


def bench_swap_gap(engine, model, rate: float, seconds: float,
                   family: str, arm: str, buckets: tuple,
                   seed: int = 11) -> dict:
    """The hot-reload arm: open-loop Poisson load with a full rollout
    mid-stream (sparknet_tpu/loop protocol — candidate AOT-compiled on
    a builder thread, ``swap_model`` under the pump lock).

    The swap-gap claim: the candidate's compile cost never reaches the
    request path — the only request-visible stall is the pump-lock hold
    (queue steal + dict flip, microseconds) plus natural device
    contention from draining the incumbent.  Reported as the max total
    latency over requests whose lifetime OVERLAPS the swap interval,
    next to the run's overall p99 and the lock-hold wall itself.
    """
    import threading

    from sparknet_tpu.serve.loadgen import synthetic_items

    served = engine._models[model]
    n0 = len(served.lat_total_ms)
    rs = np.random.RandomState(seed)
    n = max(1, int(rate * seconds))
    items = synthetic_items(served, min(n, 64), rs)
    gaps = rs.exponential(1.0 / rate, n)
    stop = threading.Event()
    worker = threading.Thread(
        target=lambda: engine.serve_forever(until=stop.is_set),
        daemon=True)
    worker.start()

    swap: dict = {}

    def builder() -> None:
        # build + swap land mid-run; engine.clock stamps the interval
        # in the same timebase as the tickets' t_submit/t_done
        time.sleep(seconds * 0.4)
        b0 = time.perf_counter()
        cand = engine.build_candidate(model, family=family, arm=arm,
                                      buckets=buckets, seed=seed)
        swap["build_s"] = time.perf_counter() - b0
        swap["t0"] = engine.clock()
        swap.update(engine.swap_model(model, cand))
        swap["t1"] = engine.clock()

    bthread = threading.Thread(target=builder, daemon=True)
    tickets = []
    t0 = time.perf_counter()
    bthread.start()
    for i in range(n):
        target = t0 + float(np.sum(gaps[:i + 1]))
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        tickets.append(engine.submit(model, items[i % len(items)]))
    for t in tickets:
        t.wait(timeout=60.0)
    bthread.join(timeout=120.0)
    stop.set()
    worker.join(timeout=5.0)

    overlap = [t for t in tickets
               if t.t_done is not None and t.t_done >= swap["t0"]
               and t.t_submit <= swap["t1"]]
    stalls = [(t.t_done - t.t_submit) * 1e3 for t in overlap]
    # every request the swap could have touched resolved — the
    # zero-dropped-tickets half of the hot-reload contract
    dropped = sum(1 for t in tickets if not t.done())
    lats = [ms for m in (engine._models[model],
                         engine._models[model].previous) if m
            for ms in m.lat_total_ms[n0 if m is served else 0:]]
    swap_wall_ms = swap.get("swap_wall_s", 0.0) * 1e3
    return {
        "metric": "serve_swap_gap_ms",
        "value": round(max(stalls) if stalls else swap_wall_ms, 3),
        "unit": f"ms max request stall overlapping the hot swap "
                f"(open loop, {rate:g} req/s Poisson, {n} requests)",
        "p99_ms_during": round(_pctl(stalls, 99), 3) if stalls else 0.0,
        "p99_ms_overall": round(_pctl(lats, 99), 3),
        "swap_wall_ms": round(swap_wall_ms, 3),
        "candidate_build_s": round(swap.get("build_s", 0.0), 3),
        "overlapping_requests": len(overlap),
        "drained": swap.get("drained", 0),
        "version": swap.get("version", 0),
        "dropped": dropped,
    }


def bench_replica_aggregate(replicas: int, family: str, arm: str,
                            buckets: tuple, max_wait_ms: float,
                            rate: float, seconds: float,
                            seed: int = 11,
                            chunk_s: float = 0.005) -> dict:
    """The pod arm: K replicas under one open-loop Poisson stream.

    Arrivals are submitted in ~``chunk_s`` chunks through the router's
    ``submit_many`` (one lock walk per chunk — at pod offered rates
    per-request locking alone is measurable against the serving
    budget), with ``shed=True`` so overload rejects at the door.  One
    pump thread runs the fair sweep (``router.serve_forever``).

    Two latency views: the overall queue p99, and the WARM p99 over
    requests submitted after a 0.5 s ramp — cold-start arrivals land
    before the drain-rate estimators have any evidence, so their
    waits measure the admission rule's blind window, not its steady
    state.  The deadline gate reads the warm view and says so.
    """
    import threading

    from sparknet_tpu.serve.engine import SHED_TICK_MS
    from sparknet_tpu.serve.loadgen import (open_loop_schedule,
                                            synthetic_items)
    from sparknet_tpu.serve.router import ReplicaRouter

    router = ReplicaRouter(replicas=replicas, family=family, arm=arm,
                           buckets=buckets, max_wait_ms=max_wait_ms,
                           seed=seed)
    rs = np.random.RandomState(seed)
    router.warmup(rs)
    items = synthetic_items(
        next(iter(router._replicas.values())).model, 512, rs)
    stop = threading.Event()
    worker = threading.Thread(target=router.serve_forever,
                              kwargs={"until": stop.is_set},
                              daemon=True)
    worker.start()
    sched = open_loop_schedule(rate, seconds, seed=seed)
    tickets: list = []
    shed = 0
    t0 = time.perf_counter()
    i = 0
    while i < len(sched):
        now = time.perf_counter() - t0
        j = i
        horizon = now + chunk_s
        while j < len(sched) and sched[j] <= horizon:
            j += 1
        if j == i:  # next arrival beyond the horizon: sleep to it
            time.sleep(min(chunk_s, sched[i] - now))
            continue
        adm, n_shed = router.submit_many(
            [items[k % len(items)] for k in range(i, j)], shed=True)
        tickets.extend(adm)
        shed += n_shed
        i = j
    deadline = time.perf_counter() + 60.0
    while (any(not t.done() for t in tickets)
           and time.perf_counter() < deadline):
        time.sleep(0.002)
    wall = time.perf_counter() - t0
    stop.set()
    worker.join(timeout=10.0)
    dropped = sum(1 for t in tickets if not t.done())
    stats = router.stats()
    router.shutdown()

    ramp_s = 0.5
    first = tickets[0].t_submit if tickets else 0.0
    waits = [(t.t_batch - t.t_submit) * 1e3 for t in tickets
             if t.t_batch is not None]
    warm = [(t.t_batch - t.t_submit) * 1e3 for t in tickets
            if t.t_batch is not None
            and t.t_submit - first > ramp_s]
    bound_ms = max_wait_ms + SHED_TICK_MS
    warm_p99 = _pctl(warm, 99)
    return {
        "metric": "serve_replica_aggregate_rps",
        "value": round(len(tickets) / wall, 1) if wall > 0 else 0.0,
        "unit": f"req/s aggregate (open loop, {replicas} replica(s), "
                f"{rate:g} req/s offered Poisson, {len(sched)} "
                f"arrivals, {chunk_s * 1e3:g} ms submit chunks)",
        "replicas": replicas,
        "offered_rps": rate,
        "admitted": len(tickets),
        "shed": shed,
        "dropped": dropped,
        "rerouted": stats["rerouted"],
        "queue_p99_ms": round(_pctl(waits, 99), 3),
        "queue_p99_warm_ms": round(warm_p99, 3),
        "warm_ramp_s": ramp_s,
        "deadline_bound_ms": bound_ms,
        "deadline_bounded": bool(warm_p99 <= bound_ms),
        "serve_path_compiles": stats["serve_path_compiles"],
        "wall_s": round(wall, 3),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--family", default="cifar10_quick")
    ap.add_argument("--arm", default="f32",
                    choices=("f32", "fold_bn", "int8"))
    ap.add_argument("--buckets", default="1,8,64,256")
    ap.add_argument("--rounds", type=int, default=8,
                    help="closed-loop bursts per bucket")
    ap.add_argument("--rate", type=float, default=40.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="open-loop duration")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the POD arm instead of the single-copy "
                    "arms: K replicas (sparknet_tpu/serve/router) "
                    "under one open-loop Poisson stream, chunked "
                    "submit_many + deadline shed; clamped to the "
                    "visible device count (the clamp is recorded, "
                    "never silent)")
    ap.add_argument("--agg-rate", type=float, default=16000.0,
                    help="pod-arm offered rate (req/s)")
    ap.add_argument("--agg-seconds", type=float, default=2.0,
                    help="pod-arm open-loop duration")
    ap.add_argument("--swap", action="store_true",
                    help="add the hot-reload arm: a full "
                    "build_candidate + swap_model rollout mid-stream "
                    "under open-loop Poisson load, measuring the "
                    "swap-gap (max request stall and p99 during the "
                    "hot reload — sparknet_tpu/loop protocol)")
    ap.add_argument("--platform", default="",
                    help="force a jax platform; cpu = host-side run")
    ap.add_argument("--bank", action="store_true",
                    help=f"bank the gate record to {LAST_PATH} via "
                    "common.bank_guard")
    args = ap.parse_args()

    if args.platform == "cpu" and args.replicas > 1:
        # a CPU pod rehearsal needs K virtual devices, not one — same
        # mesh pin as the dryrun/graphcheck (must land before the
        # backend initializes)
        from sparknet_tpu.analysis.graphcheck import _pin_cpu_mesh

        _pin_cpu_mesh(max(8, args.replicas))
    elif args.platform:
        from sparknet_tpu.common import force_platform

        force_platform(args.platform)
    import jax

    from sparknet_tpu.common import require_chip

    stamp = require_chip("serve_bench")  # no chip, no pin: exit 2
    on_accel = stamp["platform"] != "cpu"

    from sparknet_tpu.obs.sentinel import get_sentinel
    from sparknet_tpu.serve.engine import ServeEngine
    from sparknet_tpu.serve.loadgen import synthetic_items

    if args.replicas:
        # pod mode replaces the single-copy arms wholesale: transformer
        # family on the serve ladder's lower rungs (the pod headline is
        # row throughput under a 25 ms deadline, docs/SERVING.md
        # "Replication & elasticity")
        get_sentinel().install()
        asked = args.replicas
        replicas = min(asked, len(jax.devices()))
        record = bench_replica_aggregate(
            replicas, family="transformer", arm=args.arm,
            buckets=(1, 8, 64), max_wait_ms=25.0,
            rate=args.agg_rate, seconds=args.agg_seconds)
        record.update({
            "family": "transformer",
            "arm": args.arm,
            "buckets": [1, 8, 64],
            "max_wait_ms": 25.0,
            "replicas_requested": asked,
            **stamp,
            "measured": True,
            "host_side": not on_accel,
            "chip_measured": on_accel,
        })
        if record["serve_path_compiles"] != 0:
            record["measured"] = False
            record["compile_inconsistency"] = (
                f"{record['serve_path_compiles']} serving-path "
                "compile(s) post-warmup — the pod AOT contract is "
                "broken; latencies include compile walls")
        if record["dropped"] != 0:
            record["measured"] = False
            record["drop_inconsistency"] = (
                f"{record['dropped']} admitted ticket(s) unresolved — "
                "the zero-drop ledger is broken")
        if not record["deadline_bounded"]:
            record["measured"] = False
            record["deadline_inconsistency"] = (
                f"warm queue p99 {record['queue_p99_warm_ms']} ms over "
                f"the {record['deadline_bound_ms']:g} ms bound — the "
                "shed rule failed to hold the tail")
        print(json.dumps(record))
        if args.bank:
            from sparknet_tpu.common import bank_guard

            bank_guard(LAST_PATH, record, measured=record["measured"])
        return 0 if record["measured"] else 1

    buckets = tuple(int(b) for b in args.buckets.split(","))
    sentinel = get_sentinel().install()
    engine = ServeEngine(buckets=buckets, max_wait_ms=args.max_wait_ms)
    t0 = time.perf_counter()
    engine.load_model("m", family=args.family, arm=args.arm)
    load_s = time.perf_counter() - t0
    served = engine._models["m"]
    # warmup: one flush per bucket, then snapshot the sentinel — the
    # AOT claim is zero compiles caused by TRAFFIC
    rs = np.random.RandomState(0)
    for b in buckets:
        for item in synthetic_items(served, max(1, b // 2), rs):
            engine.submit("m", item)
        engine.pump(force=True)
    compiles0 = sentinel.count

    arms = []
    for b in buckets:
        r = bench_closed_loop(engine, "m", b, args.rounds)
        arms.append(r)
        print(json.dumps(r))
    open_arm = bench_open_loop(engine, "m", args.rate, args.seconds,
                               args.max_wait_ms)
    print(json.dumps(open_arm))
    swap_arm = None
    if args.swap:
        swap_arm = bench_swap_gap(engine, "m", args.rate, args.seconds,
                                  args.family, args.arm, buckets)
        print(json.dumps(swap_arm))
    # with --swap the builder thread's candidate compiles are by design;
    # what must stay zero is the engine's serving-path ledger (per-thread
    # sentinel attribution, obs/sentinel.py)
    compiles_post = (engine.serve_path_compiles if args.swap
                     else sentinel.count - compiles0)
    engine.shutdown()

    best = max(arms, key=lambda r: r["value"])
    record = {
        "metric": "serve_bench_gate",
        "value": best["value"],
        "unit": best["unit"],
        "family": args.family,
        "arm": args.arm,
        "buckets": list(buckets),
        "aot_load_s": round(load_s, 3),
        "closed_loop": {r["metric"]: {k: r[k] for k in
                        ("value", "p50_ms", "p99_ms")} for r in arms},
        "open_loop": open_arm,
        **({"swap": swap_arm} if swap_arm else {}),
        "compiles_post_warmup": compiles_post,
        "max_wait_ms": args.max_wait_ms,
        **stamp,
        # host-side provenance on CPU: real walls on this box, but NOT
        # chip numbers — those ride the r7 queue's serve_latency job
        "measured": True,
        "host_side": not on_accel,
        "chip_measured": on_accel,
    }
    if compiles_post != 0:
        record["measured"] = False
        record["compile_inconsistency"] = (
            f"{compiles_post} backend compile(s) during steady-state "
            "traffic — the AOT-bucket contract is broken; latencies "
            "include compile walls and are not evidence")
    if swap_arm is not None and swap_arm["dropped"] != 0:
        record["measured"] = False
        record["swap_inconsistency"] = (
            f"{swap_arm['dropped']} ticket(s) unresolved across the "
            "hot swap — the zero-dropped drain contract is broken")
    print(json.dumps(record))
    if args.bank:
        from sparknet_tpu.common import bank_guard

        bank_guard(LAST_PATH, record, measured=record["measured"])
    return 0 if record["measured"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
