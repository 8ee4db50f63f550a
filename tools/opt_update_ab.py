"""Optimizer-update A/B: the per-blob XLA chain vs the fused arena sweep.

Two levels, one verdict each:

* isolated (default): ONLY the update — the real model's param
  geometry (blobs, lr/decay multipliers, slot count) driven through
  ``solvers/updates.apply_update`` (per-blob chain) vs
  ``solvers/arena.arena_apply_update`` (one-pass fused sweep,
  ``ops/pallas_kernels.fused_update``) for ``--iters`` steps fused into
  one scanned dispatch.  This is the kernel-level number: what the
  single-pass sweep buys on the update's own bytes, uncontaminated by
  the forward/backward.  The fused arm also reports the implied HBM
  bandwidth against the kernel's analytic single-pass traffic model
  (``fused_update_hbm_bytes``) — self-refusing any value above the
  819 GB/s v5e roofline.
* ``--framework``: both arms through the REAL headline path —
  ``bench._build_step`` with ``SPARKNET_BENCH_FUSED`` flipped — full
  train step (forward, backward, donation, scan).  The isolated-vs-
  framework delta says how much of the kernel win the step keeps.
  ``--storage bf16`` adds the bf16-storage arm (fused arenas in bf16,
  f32 register math) to both levels.

Timing protocol (both levels): all iters in ONE scanned dispatch,
state threaded through the carry (no two steps see identical bytes),
warm and timed dispatches salted apart, fenced on the scalar VALUE of
the program's own output (common.value_fence).

Run:                   python tools/opt_update_ab.py [--model alexnet]
                       python tools/opt_update_ab.py --framework
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_state(model: str, solver_type: str, storage: str):
    """(cfg, layout, params, slots, grads, specs) at the real zoo
    geometry — built once on host, no training step involved."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.common import Phase, set_config
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.solvers import arena, updates

    set_config(storage_dtype=storage)
    cfg = dataclasses.replace(getattr(models, f"{model}_solver")(),
                              solver_type=solver_type)
    net = Network(getattr(models, model)(8), Phase.TRAIN)
    variables = net.init(jax.random.PRNGKey(0))
    specs = net.param_specs_for(variables)
    layout = arena.build_layout(variables.params, specs, cfg,
                                storage_dtype=storage)
    slots = updates.init_slots(cfg.solver_type, variables.params)
    rs = np.random.RandomState(1)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rs.randn(*p.shape) * 1e-3, p.dtype),
        variables.params)
    return cfg, layout, variables.params, slots, grads, specs


def measure_isolated(arm: str, model: str, solver_type: str, iters: int,
                     storage: str):
    """Time ``iters`` update sweeps (no forward/backward) in one
    scanned dispatch.  ``arm``: 'unfused' (per-blob chain) | 'fused'
    (arena sweep, impl auto: pallas on TPU, xla elsewhere)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sparknet_tpu.common import (
        V5E_HBM_BYTES_S,
        value_fence as fence,
    )
    from sparknet_tpu.ops.pallas_kernels import fused_update_hbm_bytes
    from sparknet_tpu.solvers import arena, updates

    cfg, layout, params, slots, grads, specs = _build_state(
        model, solver_type, storage if arm != "unfused" else "f32")
    rate = jnp.float32(cfg.base_lr)

    def checksum(tree):
        # in-program reduction over EVERY final state byte: returning a
        # single element would let XLA dead-code-eliminate the other
        # blobs' independent update chains entirely (observed: the
        # per-blob arm timed 0.12 ms/step for 61M params on the CPU
        # rehearsal — 2 TB/s, i.e. nothing ran).  One extra read of the
        # final state, outside the per-step cost, amortized over iters.
        return sum(jnp.sum(l.astype(jnp.float32))
                   for l in jax.tree_util.tree_leaves(tree))

    if arm == "unfused":
        def chained(params, slots, grads, salt):
            def body(carry, i):
                p, s = carry
                # salt grads off the carry: every step's bytes differ,
                # and the chain is serialized through the state
                probe = jax.tree_util.tree_leaves(p)[0].ravel()[0]
                g = jax.tree_util.tree_map(
                    lambda x: x + (probe * 1e-24).astype(x.dtype), grads)
                p, s = updates.apply_update(cfg, p, g, s, specs, rate, i)
                return (p, s), None

            (p, s), _ = lax.scan(body, (params, slots),
                                 jnp.arange(iters) + jnp.int32(salt))
            return checksum(p) + checksum(s)

        cfn = jax.jit(chained)
        args = (params, slots, grads)
    else:
        P = arena.pack(layout, params)
        S = arena.pack_slots(layout, slots)
        G = arena.pack(layout, grads)

        def chained(P, S, G, salt):
            def body(carry, i):
                P, S = carry
                g = G + (P[0] * 1e-24).astype(G.dtype)
                P, S = arena.arena_apply_update(cfg, layout, P, g, S,
                                                rate, i)
                return (P, S), None

            (P, S), _ = lax.scan(body, (P, S),
                                 jnp.arange(iters) + jnp.int32(salt))
            return checksum(P) + checksum(S)

        cfn = jax.jit(chained)
        args = (P, S, G)

    fence(cfn(*args, 0))  # warm: compiles + runs the full chain once
    t0 = time.perf_counter()
    out = cfn(*args, 1)
    fence(out)
    dt = time.perf_counter() - t0
    from sparknet_tpu.common import device_stamp

    stamp = device_stamp()
    platform = stamp["platform"]
    ms = dt / iters * 1e3
    rec = {
        "metric": f"{model}_{solver_type.lower()}_update_sweep_ms",
        "arm": arm if arm == "unfused" or storage == "f32"
        else f"{arm}_{storage}",
        "value": round(ms, 4), "unit": "ms/step", "iters": iters,
        "measured": platform != "cpu", **stamp,
    }
    if arm != "unfused":
        model_bytes = fused_update_hbm_bytes(layout.total_bytes,
                                             layout.n_slots)
        rec["arena_bytes"] = layout.total_bytes
        rec["single_pass_hbm_bytes"] = model_bytes
        implied = model_bytes / (dt / iters)
        if implied <= V5E_HBM_BYTES_S and platform != "cpu":
            rec["implied_bw_gb_s"] = round(implied / 1e9, 1)
            rec["implied_bw_frac"] = round(implied / V5E_HBM_BYTES_S, 3)
        elif platform != "cpu":
            # never print a value above its own stated roofline bound
            rec["implied_bw_gb_s_conflicting"] = round(implied / 1e9, 1)
            rec["bound_inconsistency"] = (
                "implied bandwidth exceeds the 819 GB/s v5e peak — the "
                "traffic model mismatches the program; treat the timing "
                "as unverified")
    return rec


def measure_framework(arm: str, model: str, batch: int, iters: int,
                      dtype_name: str, storage: str):
    """One arm through the exact headline construction
    (bench._build_step, which reads SPARKNET_BENCH_FUSED /
    SPARKNET_BENCH_STORAGE_DTYPE) — full train step, scan-fused."""
    import jax

    import bench
    from sparknet_tpu.common import set_config
    from sparknet_tpu.common import value_fence as fence
    from sparknet_tpu.models import BENCH_CROPS

    crop = BENCH_CROPS[model]
    prior = {k: os.environ.get(k) for k in
             ("SPARKNET_BENCH_FUSED", "SPARKNET_BENCH_STORAGE_DTYPE")}
    os.environ["SPARKNET_BENCH_FUSED"] = "0" if arm == "unfused" else "1"
    os.environ["SPARKNET_BENCH_STORAGE_DTYPE"] = (
        storage if arm == "fused_storage" else "f32")
    try:
        step, variables, slots, key, feeds = bench._build_step(
            batch, model, crop, dtype_name, scan=max(iters, 2))
        variables, slots, loss = step(variables, slots, 0, feeds, key)
        fence(loss)  # warm dispatch ran the chain; timed args now differ
        t0 = time.perf_counter()
        variables, slots, loss = step(variables, slots, iters, feeds, key)
        fence(loss)
        dt = time.perf_counter() - t0
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        set_config(fused_update=False, storage_dtype="f32")
    from sparknet_tpu.common import device_stamp

    stamp = device_stamp()
    platform = stamp["platform"]
    return {
        "metric": f"{model}_framework_train_img_s",
        "arm": arm if arm != "fused_storage" else f"fused_{storage}",
        "value": round(batch * max(iters, 2) / dt, 1), "batch": batch,
        "iters": max(iters, 2), "dtype": dtype_name,
        "measured": platform != "cpu", **stamp,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="alexnet")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--solver-type", default="SGD",
                    help="rule for the isolated sweep (SGD|Nesterov|"
                    "AdaGrad|RMSProp|AdaDelta|Adam)")
    ap.add_argument("--dtype", default="bf16",
                    help="framework-arm compute dtype")
    ap.add_argument("--storage", default="bf16",
                    help="adds a fused bf16-storage arm when 'bf16' "
                    "('f32' skips it)")
    ap.add_argument("--framework", action="store_true",
                    help="A/B the full train step via bench._build_step "
                    "instead of the update-only sweep")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (cpu for offline checks)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from sparknet_tpu.common import require_chip

    on_accel = require_chip("opt_update_ab")["platform"] != "cpu"
    if not on_accel:  # offline plumbing check: tiny batch/iters, f32
        args.batch, args.iters, args.dtype = 2, 2, "f32"

    if args.framework:
        arms = ["unfused", "fused"]
        if args.storage == "bf16":
            arms.append("fused_storage")
        run = lambda a: measure_framework(  # noqa: E731
            a, args.model, args.batch, args.iters, args.dtype,
            args.storage)
    else:
        arms = ["unfused", "fused"]
        if args.storage == "bf16":
            arms.append("fused_bf16")
        run = lambda a: measure_isolated(  # noqa: E731
            "fused" if a == "fused_bf16" else a, args.model,
            args.solver_type, args.iters,
            "bf16" if a == "fused_bf16" else "f32")

    results = [run(a) for a in arms]
    for r in results:
        print(json.dumps(r), flush=True)

    if not on_accel:
        # plumbing check only — never bank as chip evidence
        print("opt_update_ab: cpu run, not banking", file=sys.stderr)
        return 0

    out_path = args.out
    if out_path is None:
        stem = ("opt_update_ab_fw_last" if args.framework
                else "opt_update_ab_last")
        out_path = f"docs/{stem}.json"
    if not os.path.isabs(out_path):
        out_path = os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), out_path)
    from sparknet_tpu.common import bank_guard

    if bank_guard(out_path,
                  {"mode": "framework" if args.framework else "isolated",
                   "model": args.model, "solver_type": args.solver_type,
                   "arms": results,
                   "utc": time.strftime("%Y-%m-%d %H:%M:%SZ",
                                        time.gmtime())},
                  measured=on_accel) is None:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
