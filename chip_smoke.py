#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the training main path still
starts on the chip.

One process drives, at the full width of AlexNet (``models.alexnet(256)``,
3x227x227, 1000 classes, bf16 compute / f32 params,
``models.alexnet_solver()``) and on whatever chips are visible:

1. census   — platform must be ``tpu`` and ``device_kind`` a row of
              ``common.TPU_PEAK_FLOPS``;
2. solo     — ``Solver.jitted_train_step(donate=True)`` fed by the default
              threaded feed; step-0 loss and logits against the same net and
              feeds evaluated in f32 on ``jax.devices("cpu")``;
3. tau      — ``ParallelTrainer(tau=2)`` for two rounds, then one ``tau=1``
              sync-DP round, on every visible chip;
4. snapshot — ``save`` -> ``restore`` -> ``Solver.step`` -> ``test()``;
5. kernels  — each Pallas kernel compiled (not interpreted) at its caller's
              full-width shape against its XLA twin (the selective scan at
              a small shape that tiles, against its ``lax.scan`` loop form).

Every phase runs even when an earlier one failed, so one call shows all
that is broken; any failure makes the exit code 1 and withholds the result
line.  Times and losses printed here are smoke facts, not benchmark
metrics.

Usage:
    python chip_smoke.py [--out DIR]       # needs a TPU; exits 2 without one
    python chip_smoke.py --rehearse-cpu    # tiny sizes, interpret-mode
                                           # kernels, every line marked

The last stdout line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# per-chip batch, crop, classes; steps = warm-up + measured
FULL = dict(batch=256, crop=227, classes=1000, warm=3, steps=5,
            lrn=[("norm1", (256, 96, 55, 55)), ("norm2", (256, 256, 27, 27))],
            # [B, H, S, D]: the zoo char LM's attention, then long context
            flash=[((8, 4, 128, 16), "float32"),
                   ((2, 8, 2048, 64), "bfloat16"),
                   ((2, 8, 2048, 128), "bfloat16")],
            # (B, T, H, D, num_blocks, blocks_per_slot): charlm decode
            # geometry, then the planning point
            paged=[(8, 16, 4, 16, 64, 8), (8, 64, 8, 64, 128, 16)],
            # (B, S, d_inner, d_state): the selective scan, 4 time blocks
            # of 2 d-blocks
            scan=[(2, 256, 1024, 16)],
            kernel_impl="pallas")
REHEARSAL = dict(batch=4, crop=67, classes=10, warm=1, steps=5,
                 lrn=[("norm1", (2, 16, 9, 9))],
                 flash=[((1, 2, 160, 16), "float32")],
                 paged=[(3, 8, 2, 8, 16, 3)],
                 scan=[(1, 24, 256, 8)],
                 kernel_impl="interpret")

_prefix = ""


def log(msg: str) -> None:
    print(f"{_prefix}{msg}", flush=True)


def synthetic_feeds(it: int, batch: int, crop: int, classes: int) -> dict:
    """Pixel-scale batch, deterministic in ``it``: the zoo fillers are
    calibrated for mean-subtracted 0..255 inputs, so noise x40 and a +80
    class signal on a class-dependent band of rows."""
    import numpy as np

    from sparknet_tpu.ops.layout import feeds_to_internal

    rng = np.random.default_rng(1000 + it)
    label = rng.integers(0, classes, batch).astype(np.int32)
    data = rng.standard_normal((batch, 3, crop, crop), dtype=np.float32)
    data *= 40.0
    for b, c in enumerate(label):
        r = int(c) * 7 % (crop - 8)
        data[b, :, r:r + 8, :] += 80.0
    return feeds_to_internal({"data": data, "label": label})


def make_solver(size: dict):
    """The model under test: AlexNet with its own solver recipe."""
    from sparknet_tpu import models
    from sparknet_tpu.solvers.solver import Solver

    return Solver(models.alexnet_solver(), models.alexnet(
        size["batch"], size["classes"], size["crop"]))


def feed_fn(size: dict):
    """``it -> feeds`` for one per-chip batch of the model under test."""
    return lambda it: synthetic_feeds(
        it, size["batch"], size["crop"], size["classes"])


def first_and_steady(fn, *args, reps: int = 3):
    """(first-call seconds — trace + compile + one run, steady ms, result),
    each call fenced with ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return first, (time.perf_counter() - t0) / reps * 1e3, out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# --------------------------------------------------------------------------
def phase_census(rehearse: bool) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from sparknet_tpu.common import tpu_peak_flops

    devs = jax.devices()
    d0 = devs[0]
    if not rehearse and d0.platform != "tpu":
        # stderr only, and before anything reaches stdout
        print(f"chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{d0.platform!r}; --rehearse-cpu runs the CPU rehearsal",
              file=sys.stderr)
        raise SystemExit(2)
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    log(f"census: platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    if not rehearse:
        peak = tpu_peak_flops(d0.device_kind)  # unknown kind raises
        log(f"census: peak table row {peak}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------------
def _reference_forward(net):
    """loss + logits of the TRAIN net at iteration 0 — the quantity the
    train step differentiates (same key derivation as the solver)."""
    from sparknet_tpu.common import step_key

    loss_layer = next(l for l in net.layers if l.IS_LOSS)
    logits_blob = loss_layer.bottoms[0]

    def fwd(variables, feeds, key):
        blobs, _, loss = net.apply(variables, feeds, rng=step_key(key, 0))
        return loss, blobs[logits_blob]

    return fwd


def phase_solo(size: dict, state: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.common import get_config, set_config
    from sparknet_tpu.data.prefetch import DevicePrefetcher

    batch = size["batch"]
    solver = make_solver(size)
    step, variables, slots, key = solver.jitted_train_step(donate=True)
    data_fn = feed_fn(size)

    # the f32 CPU reference, before the first step donates the buffers
    fwd = _reference_forward(solver.train_net)
    host_vars = jax.device_get(variables)
    feeds0 = data_fn(0)
    t0 = time.perf_counter()
    chip_loss0, chip_logits = jax.block_until_ready(
        jax.jit(fwd)(variables, feeds0, key))
    chip_logits = np.asarray(chip_logits, np.float32)
    cpu = jax.devices("cpu")[0]
    compute = get_config().compute_dtype
    set_config(compute_dtype=jnp.float32)
    try:
        with jax.default_device(cpu):
            ref_loss, ref_logits = jax.jit(fwd)(
                *jax.device_put((host_vars, feeds0, key), cpu))
            ref_loss, ref_logits = float(ref_loss), np.asarray(ref_logits)
    finally:
        set_config(compute_dtype=compute)
    log(f"solo: reference forward (chip {jnp.dtype(compute).name} + cpu "
        f"f32) {time.perf_counter() - t0:.1f} s")

    n = size["warm"] + size["steps"]
    losses, walls = [], []
    with DevicePrefetcher(data_fn, n) as feed:  # the default threaded feed
        for it, feeds in enumerate(feed):
            t0 = time.perf_counter()
            variables, slots, loss = step(variables, slots, it, feeds, key)
            losses.append(float(jax.block_until_ready(loss)))
            walls.append(time.perf_counter() - t0)
    check(len(losses) == n, f"feed delivered {len(losses)} of {n} batches")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    steady = sorted(walls[size["warm"]:])
    log(f"solo: set-up (trace+compile+step 0) {walls[0]:.1f} s; steady step "
        f"median {steady[len(steady) // 2] * 1e3:.1f} ms over "
        f"{len(steady)} steps (batch {batch}; dispatch -> loss ready, "
        "batch already on the device)")
    log("solo: loss " + " ".join(f"{l:.4f}" for l in losses))

    # bf16 keeps ~3 significant digits; the loss is a mean over the batch
    # and the logits go through eight bf16 layers
    loss_err = abs(losses[0] - ref_loss) / abs(ref_loss)
    scale = float(np.max(np.abs(ref_logits)))
    logit_err = float(np.max(np.abs(chip_logits - ref_logits))) / scale
    log(f"solo: step-0 loss chip {losses[0]:.5f} vs cpu-f32 {ref_loss:.5f} "
        f"(rel {loss_err:.2e}); forward loss {float(chip_loss0):.5f}; "
        f"logits max|err|/max|ref| {logit_err:.2e} (max|ref| {scale:.3g})")
    check(loss_err < 2e-2, f"step-0 loss off the f32 reference: {loss_err}")
    check(abs(float(chip_loss0) - ref_loss) / abs(ref_loss) < 2e-2,
          "forward loss off the f32 reference")
    check(logit_err < 5e-2, f"logits off the f32 reference: {logit_err}")

    after = jax.device_get(variables.params)
    unchanged = [f"{ln}[{i}]" for ln, plist in host_vars.params.items()
                 for i, p in enumerate(plist)
                 if p.size and np.array_equal(p, after[ln][i])]
    check(not unchanged, f"parameters did not change: {unchanged}")

    # hand the live state back to the solver for the snapshot phase
    solver.variables, solver.slots, solver.iter = variables, slots, n
    state["solver"] = solver


# --------------------------------------------------------------------------
def phase_tau(size: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.parallel.trainer import ParallelTrainer

    batch = size["batch"]
    devs = jax.devices()
    tau = 2
    data_fn = feed_fn(size)

    def global_batch(it, workers):
        parts = [data_fn(it * workers + w) for w in range(workers)]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    trainer = ParallelTrainer(make_solver(size), tau=tau)
    R = trainer.num_workers
    check(R == len(devs), f"mesh spans {R} of {len(devs)} devices")
    for leaf in jax.tree_util.tree_leaves((trainer.variables, trainer.slots)):
        shards = leaf.addressable_shards
        check(leaf.shape[0] == R and len(shards) == len(devs)
              and {s.device for s in shards} == set(devs)
              and all(s.data.shape == (1,) + leaf.shape[1:] for s in shards),
              f"stacked leaf {leaf.shape} is not one [1, ...] shard per "
              f"device: {[(s.device.id, s.data.shape) for s in shards]}")
    log(f"tau: {R} worker(s), every stacked leaf one [1, ...] shard per device")

    def tau_fn(it):
        slots = [global_batch(it + t, R) for t in range(tau)]
        return {k: np.stack([s[k] for s in slots]) for k in slots[0]}

    spread = jax.jit(lambda v: jax.tree_util.tree_map(
        lambda x: jnp.max(jnp.abs(x - x[:1])), v))
    # host synthesis stays out of the wall; one data fn for both rounds,
    # so round 0 places round 1 while it trains (asked for a third, the
    # fn raises, and the trainer holds that for a round nobody trains)
    rounds = [tau_fn(r * tau) for r in range(2)]

    def placed_fn(it):
        return rounds[it // tau]

    for r in range(2):
        t0 = time.perf_counter()
        loss = trainer.train_round(placed_fn)  # fetches the loss
        wall = time.perf_counter() - t0
        check(np.isfinite(loss), f"tau round {r} loss {loss}")
        worst = max(float(x) for x in
                    jax.tree_util.tree_leaves(spread(trainer.variables)))
        check(worst == 0.0, f"replicas differ after the average: {worst}")
        log(f"tau: round {r} (tau={tau}, per-chip batch {batch}) "
            f"{'set-up ' if r == 0 else ''}{wall:.2f} s (put + round), "
            f"loss {loss:.4f}, replicas identical")
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"tau: device {d.id} peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    del trainer

    trainer = ParallelTrainer(make_solver(size), tau=1)
    feeds = global_batch(0, R)
    t0 = time.perf_counter()
    loss = trainer.train_round(lambda it: feeds)
    check(np.isfinite(loss), f"sync-DP loss {loss}")
    log(f"tau: tau=1 sync-DP round on {R} chip(s) set-up "
        f"{time.perf_counter() - t0:.2f} s, loss {loss:.4f}")


# --------------------------------------------------------------------------
def phase_snapshot(size: dict, state: dict, out_dir: str) -> None:
    import jax
    import numpy as np

    check("solver" in state, "solo phase left no solver to snapshot")
    solver = state.pop("solver")
    prefix = os.path.join(out_dir, "smoke_snapshot")
    try:
        t0 = time.perf_counter()
        path = solver.save(prefix)
        t_save = time.perf_counter() - t0
        restored = make_solver(size)
        t0 = time.perf_counter()
        restored.restore(path)
        t_restore = time.perf_counter() - t0
        check(restored.iter == solver.iter,
              f"restored iter {restored.iter} != {solver.iter}")
        for a, b in zip(
                jax.tree_util.tree_leaves((solver.variables, solver.slots)),
                jax.tree_util.tree_leaves((restored.variables,
                                           restored.slots))):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  "restored state differs from the saved state")
        log(f"snapshot: save {t_save:.1f} s, restore {t_restore:.1f} s, "
            f"iter {restored.iter}, state bit-identical")
    finally:
        for f in glob.glob(prefix + "*"):
            os.unlink(f)  # ~0.7 GB at full width; nothing is kept
    del solver

    data_fn = feed_fn(size)
    t0 = time.perf_counter()
    restored.step(1, data_fn)
    check(restored.iter == size["warm"] + size["steps"] + 1
          and np.isfinite(restored.smoothed_loss),
          f"step after restore: iter {restored.iter}, "
          f"loss {restored.smoothed_loss}")
    log(f"snapshot: one more step {time.perf_counter() - t0:.1f} s, "
        f"loss {restored.smoothed_loss:.4f}")
    t0 = time.perf_counter()
    scores = restored.test(2, lambda b: data_fn(10_000 + b))
    check(scores and all(np.isfinite(v) for v in scores.values()),
          f"test() scores {scores}")
    log(f"snapshot: test() {time.perf_counter() - t0:.1f} s, " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(scores.items())))


# --------------------------------------------------------------------------
def phase_kernels(size: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops import pallas_kernels as pk

    impl = size["kernel_impl"]
    failures = []
    keys = iter(jax.random.split(jax.random.key(0), 64))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        # made on the device: the host RNG is the slow part at these sizes
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def compare(name, kernel, twin, args, tol):
        try:
            first, ms, got = first_and_steady(jax.jit(kernel), *args)
            # the twin is the reference: keep the chip's default one-pass
            # bf16 matmul out of it
            with jax.default_matmul_precision("highest"):
                _, twin_ms, want = first_and_steady(jax.jit(twin), *args)
            g = np.asarray(got, np.float32)
            w = np.asarray(want, np.float32)
            err = float(np.max(np.abs(g - w))) / (float(np.max(np.abs(w)))
                                                  or 1.0)
            ok = np.isfinite(g).all() and err <= tol
            log(f"kernels: {name} [{impl}] set-up {first:.2f} s, {ms:.3f} ms "
                f"(xla twin {twin_ms:.3f} ms), max|err|/max|ref| {err:.2e} "
                f"{'ok' if ok else f'EXCEEDS {tol:g}'}")
            if not ok:
                failures.append(f"{name}: err {err}")
        except Exception as e:  # one kernel's refusal must not hide the rest
            traceback.print_exc()
            log(f"kernels: {name} [{impl}] FAILED {type(e).__name__}: "
                f"{str(e).splitlines()[0][:300] if str(e) else ''}")
            failures.append(f"{name}: {type(e).__name__}")

    # LRN at AlexNet's two norm layers (post-ReLU activations)
    lrn = lambda f: (lambda x: pk.lrn_across_channels(  # noqa: E731
        x, 5, 1e-4, 0.75, 1.0, force=f))
    for lname, shape in size["lrn"]:
        for dt, tol in (("bfloat16", 2e-2), ("float32", 1e-5)):
            x = jnp.abs(normal(shape, dt, 40.0))
            compare(f"lrn {lname} {shape} {dt}", lrn(impl), lrn("xla"),
                    (x,), tol)
    lname, shape = size["lrn"][0]
    x = jnp.abs(normal(shape, jnp.bfloat16, 40.0))
    lrn_grad = lambda f: jax.grad(  # noqa: E731
        lambda t: jnp.sum(lrn(f)(t).astype(jnp.float32) ** 2))
    compare(f"lrn {lname} fwd+bwd bfloat16", lrn_grad(impl), lrn_grad("xla"),
            (x,), 2e-2)

    for (B, H, S, D), dt in size["flash"]:
        q, k, v = (normal((B, H, S, D), dt) for _ in range(3))
        for causal in (False, True):
            att = lambda f: (lambda q, k, v: pk.flash_attention(  # noqa: E731
                q, k, v, causal=causal, force=f))
            compare(f"flash B{B} H{H} S{S} D{D} {dt} causal={causal}",
                    att(impl), att("xla"), (q, k, v), 2e-2)

    for B, T, H, D, NB, MB in size["paged"]:
        q, kp, vp = normal((B, H, D)), normal((NB, T, H, D)), \
            normal((NB, T, H, D))
        tables = jax.random.randint(next(keys), (B, MB), 0, NB, jnp.int32)
        pos = jax.random.randint(next(keys), (B,), 0, MB * T, jnp.int32)
        pg = lambda f: (lambda *a: pk.paged_attention(*a, force=f))  # noqa: E731
        compare(f"paged B{B} T{T} H{H} D{D} ({MB} blocks/slot)",
                pg(impl), pg("xla"), (q, kp, vp, tables, pos), 1e-5)

    # the selective scan: the kernels' forward against the loop form
    # (ops/ssm.py; the state, Δ and the exponential are f32 in both)
    from sparknet_tpu.ops import ssm

    for B, S, d, n in size["scan"]:
        args = (normal((B, S, d), jnp.bfloat16), normal((B, S, d)) - 4.0,
                normal((B, S, n), jnp.bfloat16),
                normal((B, S, n), jnp.bfloat16),
                jnp.log(jax.random.uniform(next(keys), (d, n), jnp.float32,
                                           1.0, n)), normal((d,)))
        kernel = lambda *a: ssm.selective_scan_kernel(  # noqa: E731
            *a, interpret=impl == "interpret")
        d_pre = lambda f: jax.grad(lambda *a: jnp.sum(  # noqa: E731
            f(*a).astype(jnp.float32) ** 2), argnums=1)
        compare(f"scan B{B} S{S} d{d} N{n}", kernel,
                lambda *a: ssm._selective_scan(*a, None), args, 1e-2)
        # the gradient against the time-step oracle: the loop form's own
        # lies 2e-3 from it on the chip (PERF.md section 6, PR 33)
        compare(f"scan B{B} S{S} d{d} N{n} fwd+bwd d(dt_pre) vs oracle",
                d_pre(kernel), d_pre(ssm.selective_scan_steps), args, 1e-4)

    check(not failures, f"{len(failures)} kernel check(s) failed: {failures}")


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    global _prefix
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny-size CPU rehearsal of every phase (interpret-"
                    "mode kernels); every output line says so")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for everything the smoke writes")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        _prefix = "REHEARSAL(cpu, not a chip result) "
    else:
        # the f32 reference runs on jax.devices("cpu") in this process: a
        # JAX_PLATFORMS that names only the chip would hide the host
        # backend.  The chip stays first (the default) and still must
        # initialize — listed platforms that fail raise.
        plats = os.environ.get("JAX_PLATFORMS", "")
        if plats and "cpu" not in plats.split(","):
            os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    t_start = time.perf_counter()
    import jax.numpy as jnp

    from sparknet_tpu.common import enable_compile_cache, set_config

    cache = enable_compile_cache()
    device = phase_census(args.rehearse_cpu)
    log(f"compile cache: {cache} "
        f"({len(os.listdir(cache)) if os.path.isdir(cache) else 0} entries "
        "at start)")
    os.makedirs(args.out, exist_ok=True)
    size = REHEARSAL if args.rehearse_cpu else FULL
    set_config(compute_dtype=jnp.bfloat16)

    state: dict = {}
    failed = []
    for name, fn in (
            ("solo", lambda: phase_solo(size, state)),
            ("tau", lambda: phase_tau(size)),
            ("snapshot", lambda: phase_snapshot(size, state, args.out)),
            ("kernels", lambda: phase_kernels(size))):
        t0 = time.perf_counter()
        try:
            fn()
            log(f"phase {name}: ok ({time.perf_counter() - t0:.1f} s)")
        except Exception:
            # keep going so one call shows every broken phase; the run
            # still exits 1 and prints no result line
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED ({time.perf_counter() - t0:.1f} s)")
    log(f"total {time.perf_counter() - t_start:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    log(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
