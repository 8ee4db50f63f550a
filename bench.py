"""Headline benchmark: AlexNet-class (CaffeNet-recipe) training throughput.

Mirrors the reference's own benchmark protocol — time 20 solver iterations
at batch 256 on one chip and report images/sec (ref:
caffe/docs/performance_hardware.md:17-24: K40 26.5 s/20 iter = 193 img/s,
cuDNN 19.2 s = 267 img/s).  ``vs_baseline`` is measured against the best
published single-GPU number (267 img/s, K40 + cuDNN).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/s", "measured": true,
   "platform": "tpu", "device_kind": ..., "device_count": N, ...}

The number is a device metric, so it comes from a chip or not at all:

* a run that finds no accelerator exits 2 and prints no record;
* a device ``common.TPU_PEAK_FLOPS`` does not list is an error (no
  utilization against an assumed peak);
* an explicit ``JAX_PLATFORMS=cpu`` run is a REHEARSAL of the code path
  at a tiny size — its record is named ``<model>_train_cpu_rehearsal``,
  stamped ``"measured": false, "rehearsal": true``, and never carries
  the metric's name.

One process: the chip belongs to whoever touches jax first, so nothing
here probes it from a child.
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_IMG_S = 267.0  # K40 + cuDNN CaffeNet training (performance_hardware.md:22-24)

from sparknet_tpu.common import (  # noqa: E402
    enable_compile_cache,
    require_chip,
    tpu_peak_flops,
)

# "bytes accessed" extraction + GB rounding come from the byte model so
# the step_gbytes figure and the `bytes` engine share one definition
# (stdlib-only module: importing it never initializes a backend).
from sparknet_tpu.analysis.byte_model import (  # noqa: E402
    gbytes,
    xla_cost_step_bytes,
)

# obs journaling (sparknet_tpu/obs, off unless SPARKNET_OBS is set).
# Importing obs never initializes a backend.
from sparknet_tpu.obs import get_recorder  # noqa: E402


def _parse_compiler_options(env_val: str) -> dict:
    """Parse SPARKNET_BENCH_COMPILER_OPTIONS ("k=v,k2=v2").  Called once
    at startup so a malformed value dies before anything compiles, and
    again in _build_step for the values."""
    opts = {}
    for kv in env_val.split(","):
        if not kv.strip():
            continue
        if "=" not in kv:
            raise SystemExit(
                "SPARKNET_BENCH_COMPILER_OPTIONS entries must be "
                f"key=value (got {kv!r})")
        k, v = kv.split("=", 1)
        opts[k.strip()] = v.strip()
    return opts


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        raise SystemExit(f"{name} must be an integer (got {raw!r})") from None
    if v <= 0:
        raise SystemExit(f"{name} must be positive (got {v})")
    return v


def _bench_params():
    """(model, crop) from env, validated."""
    from sparknet_tpu.models import BENCH_CROPS as crops

    model = os.environ.get("SPARKNET_BENCH_MODEL", "alexnet")
    if model not in crops:
        raise SystemExit(
            f"SPARKNET_BENCH_MODEL must be one of {sorted(crops)} (got {model!r})"
        )
    return model, crops[model]


def _bench_dtype(default: str) -> str:
    """Normalized SPARKNET_BENCH_DTYPE (one alias table for every path)."""
    name = os.environ.get("SPARKNET_BENCH_DTYPE", default)
    return {"bfloat16": "bf16", "float32": "f32"}.get(name, name)


def _build_step(batch: int, model: str, crop: int, dtype_name: str,
                scan: int = 1):
    """Solver + jitted step + device feeds for the measured run.

    ``scan > 1``: the returned fn fuses that many solver iterations into
    ONE device dispatch (lax.scan) and returns a [scan] loss vector —
    the TPU-native loop: host dispatch stays out of the timed steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.solvers.solver import Solver

    # Set the compute dtype EXPLICITLY for both cases: set_config state
    # persists across calls in one process, so an f32 build after a bf16
    # build must reset it or it silently lowers in bf16.
    from sparknet_tpu.common import set_config

    # A/B knob: store params AND optimizer slots in bf16 (pure-bf16
    # training).  The step is bytes-bound and param+slot+grad round trips
    # are ~1.7 GB of AlexNet b256's 12.26 GB — halving them raises the
    # roofline itself.  Off by default: f32 master weights are the
    # accuracy-safe mixed-precision design.
    param_bf16 = os.environ.get("SPARKNET_BENCH_PARAM_DTYPE", "f32") == "bf16"
    set_config(
        compute_dtype=jnp.bfloat16 if dtype_name == "bf16" else jnp.float32,
        param_dtype=jnp.bfloat16 if param_bf16 else jnp.float32,
    )

    net_param = getattr(models, model)(batch)
    solver_cfg = getattr(models, f"{model}_solver")()
    # A/B knob: the bf16 step is HBM-bound (the roofline's bytes term
    # dominates), so recomputing activations under grad can trade cheap
    # MXU flops for traffic.  Off by default — flip on to measure.
    # "1" is the legacy boolean (SolverConfig.remat → plain
    # jax.checkpoint = the "full" policy); a policy name ("full",
    # "dots", "blocks") routes Config.remat through solvers/solver.py
    # apply_remat — the same knob the banked
    # docs/byte_contracts/remat_policy.json winner rides, so a remat
    # A/B measures exactly what the byte model scored.
    remat_env = os.environ.get("SPARKNET_BENCH_REMAT", "0")
    if remat_env == "1":
        import dataclasses

        solver_cfg = dataclasses.replace(solver_cfg, remat=True)
    elif remat_env not in ("", "0"):
        set_config(remat=remat_env)
    # A/B knob: bf16 activation STORAGE with f32 compute
    # (Config.activation_dtype) — the saved-activation round trip is
    # the largest single slice of the train step's bytes and storage
    # narrowing halves it without touching accumulation.  "bf16"
    # resolves to the banked docs/num_contracts/mixed_policy.json
    # winner (what `num --mixed` scored and error-gated); a policy
    # name ("io", "blocks", "full") pins that policy directly, so an
    # A/B measures exactly what the byte model scored.  Off by default — the default path is bit-identical to
    # every banked manifest.
    act_env = os.environ.get("SPARKNET_BENCH_ACT_DTYPE", "")
    if act_env in ("bf16", "bfloat16"):
        from sparknet_tpu.parallel.modes import _banked_act_policy

        set_config(activation_dtype=_banked_act_policy(model))
    elif act_env not in ("", "0", "f32"):
        set_config(activation_dtype=act_env)
    solver = Solver(solver_cfg, net_param)
    if scan > 1:
        step, variables, slots, key = solver.jitted_scan_steps(scan, donate=True)
    else:
        step, variables, slots, key = solver.jitted_train_step(donate=True)

    rs = np.random.RandomState(0)
    # feed in the INTERNAL layout (ops/layout.py): canonical NCHW bytes
    # by default, transposed once on the host when SPARKNET_LAYOUT=nhwc
    # flips the step channels-last (the layout A/B rides this)
    from sparknet_tpu.ops.layout import to_internal

    feeds = jax.device_put({
        "data": jnp.asarray(
            to_internal(rs.randn(batch, 3, crop, crop) * 50), jnp.float32),
        "label": jnp.asarray(rs.randint(0, 1000, batch), jnp.int32),
    })

    # A/B knob: per-compile XLA options ("k=v,k2=v2") handed to the TPU
    # compiler through the Compile call.  An option the compiler
    # rejects fails the run with a clean INVALID_ARGUMENT — an A/B
    # verdict either way.  Skipped on CPU: the host compiler would
    # reject TPU-only options.
    copts_env = os.environ.get("SPARKNET_BENCH_COMPILER_OPTIONS", "")
    if copts_env and jax.devices()[0].platform != "cpu":
        opts = _parse_compiler_options(copts_env)

        class _OptStep:
            """Timed calls run the options-compiled executable; .lower
            stays on the jit wrapper so measured_run's post-run cost
            analysis keeps working.  That analysis then describes the
            DEFAULT compile — compiler options do not change the
            operations the step requires."""

            def __init__(self, jitted, compiled):
                self._jitted, self._compiled = jitted, compiled

            def __call__(self, *a):
                return self._compiled(*a)

            def lower(self, *a, **k):
                return self._jitted.lower(*a, **k)

        step = _OptStep(
            step,
            step.lower(variables, slots, 0, feeds, key).compile(
                compiler_options=opts))
    return step, variables, slots, key, feeds


def measured_run(batch: int, iters: int, warmup: int, model: str, crop: int,
                 dtype_name: str, scan: int = 1) -> dict:
    """Time ``iters`` solver iterations on the default device and return
    the record.  ``scan``: iterations fused per device dispatch (see
    _build_step); the protocol is unchanged — ``iters`` total solver
    iterations are timed — only the dispatch granularity moves.

    On a TPU the record is the metric, with MFU against the peak of the
    run's own ``device_kind``; on a CPU it is a rehearsal record that
    carries no metric name and no utilization."""
    import numpy as np

    # no accelerator and no explicit CPU pin: exit 2, nothing printed
    stamp = require_chip("bench")
    rehearsal = stamp["platform"] == "cpu"
    # an unlisted accelerator fails here, before anything is timed
    peak = None if rehearsal else tpu_peak_flops(stamp["device_kind"])

    requested_scan = scan
    scan = max(1, min(scan, iters))
    if iters % scan:
        scan = 1  # keep the timed iteration count exact
    if scan != requested_scan:
        print(
            f"bench: SPARKNET_BENCH_SCAN={requested_scan} does not divide "
            f"iters={iters}; running scan={scan} instead",
            file=sys.stderr, flush=True,
        )

    step, variables, slots, key, feeds = _build_step(
        batch, model, crop, dtype_name, scan=scan)

    def fence(loss):
        # fetching the loss waits for the chain that produced it; with
        # scan>1 the step returns a [scan] loss vector — its last element
        return float(np.asarray(loss).ravel()[-1])

    it = 0
    for _ in range(max(1, warmup // scan)):
        variables, slots, loss = step(variables, slots, it, feeds, key)
        it += scan
    fence(loss)

    t0 = time.perf_counter()
    for _ in range(iters // scan):
        variables, slots, loss = step(variables, slots, it, feeds, key)
        it += scan
    final_loss = fence(loss)
    dt = time.perf_counter() - t0
    if not np.isfinite(final_loss):
        raise RuntimeError(f"bench: non-finite loss {final_loss}")

    img_s = batch * iters / dt
    rec = {
        "metric": (f"{model}_train_cpu_rehearsal" if rehearsal
                   else f"{model}_train_images_per_sec_per_chip"),
        "value": round(img_s, 1),
        "unit": "img/s",
        "measured": not rehearsal,
        **stamp,
        "batch": batch,
        "iters": iters,
        "dtype": dtype_name,
    }
    if rehearsal:
        rec["rehearsal"] = True
        rec["note"] = ("CPU rehearsal of the bench code path — not a "
                       "device metric")
    from sparknet_tpu.common import get_config

    if get_config().layout != "nchw":
        # non-default internal layout (SPARKNET_LAYOUT / ops/layout.py):
        # stamp it so an nhwc A/B record can never be mistaken for the
        # headline
        rec["layout"] = get_config().layout
    if scan > 1:
        rec["scan"] = scan  # iterations fused per dispatch
    if os.environ.get("SPARKNET_BENCH_PARAM_DTYPE", "f32") == "bf16":
        rec["param_dtype"] = "bf16"
    remat_env = os.environ.get("SPARKNET_BENCH_REMAT", "0")
    if remat_env not in ("", "0"):
        # "1" is the legacy boolean = the "full" policy; names are
        # Config.remat policies out of docs/byte_contracts/remat_policy.json
        rec["remat"] = "full" if remat_env == "1" else remat_env
    act_env = os.environ.get("SPARKNET_BENCH_ACT_DTYPE", "")
    if act_env not in ("", "0", "f32"):
        # stamp the RESOLVED policy — "bf16" rode the banked
        # mixed_policy.json winner, so the record names what actually ran
        rec["activation_dtype"] = get_config().activation_dtype
    # the K40 baseline is a CaffeNet-class (AlexNet/CaffeNet) number; a
    # ratio against it is meaningless for other architectures, and for
    # anything but a chip run
    if not rehearsal and model in ("alexnet", "caffenet"):
        rec["vs_baseline"] = round(img_s / BASELINE_IMG_S, 3)

    if not rehearsal:
        # Operations and bytes from the ACTUAL compiled executable, after
        # the timed run (lower().compile() does not share the jit
        # dispatch cache).  HloCostAnalysis counts a while/scan BODY
        # once, independent of trip count, so the scan program's cost is
        # already per-solver-iteration — do NOT divide by scan.
        cost = step.lower(variables, slots, 0, feeds, key).compile(
        ).cost_analysis()
        bytes_accessed = xla_cost_step_bytes(cost)
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops = float((cost or {}).get("flops", 0.0))
        if flops <= 0:
            raise RuntimeError("bench: the compiled step reports no flops")
        rec["step_gflop"] = round(flops / 1e9, 1)
        rec["step_gbytes"] = gbytes(bytes_accessed)
        # achieved matmul-FLOP rate over the chip's peak in the measured
        # dtype; above 1 the cost analysis described another program
        mfu = flops * img_s / batch / peak[dtype_name]
        if mfu > 1.0:
            raise RuntimeError(
                f"bench: {img_s:.1f} img/s implies MFU {mfu:.3f} > 1 "
                f"against the {stamp['device_kind']} {dtype_name} peak — "
                "the timing or the cost analysis is wrong")
        rec["mfu"] = round(mfu, 4)
        rec["mfu_vs_peak"] = f"{stamp['device_kind']} {dtype_name}"
    # journal the finished record through the obs Recorder — its wall
    # was closed by fence() above, a value fetch of the step's own loss
    obs = get_recorder()
    if obs:
        obs.bench(rec, wall_s=dt, fence_value=final_loss, fenced=True)
    return rec


def main() -> int:
    model, crop = _bench_params()
    # fail fast on a malformed A/B options string
    _parse_compiler_options(
        os.environ.get("SPARKNET_BENCH_COMPILER_OPTIONS", ""))
    get_recorder()  # a no-op unless SPARKNET_OBS is armed
    enable_compile_cache()
    on_chip = require_chip("bench")["platform"] != "cpu"
    batch = _env_int("SPARKNET_BENCH_BATCH", 256 if on_chip else 16)
    iters = 20 if on_chip else 2
    warmup = 3 if on_chip else 1
    # Iterations fused per dispatch (lax.scan).  Default on the chip:
    # the whole timed run in ONE dispatch — the TPU-native loop.
    # SPARKNET_BENCH_SCAN=1 gives the dispatch-per-iteration A/B.
    scan = _env_int("SPARKNET_BENCH_SCAN", iters if on_chip else 1)
    # Mixed precision is the TPU-native design point: bf16 activations /
    # conv+matmul FLOPs (full MXU rate on v5e; f32 matmuls are emulated at
    # a fraction of peak), f32 master params and optimizer state.
    # SPARKNET_BENCH_DTYPE=f32 forces the baseline's full-f32 arithmetic.
    dtype_name = _bench_dtype("bf16" if on_chip else "f32")

    rec = measured_run(batch, iters, warmup, model, crop, dtype_name,
                       scan=scan)
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
