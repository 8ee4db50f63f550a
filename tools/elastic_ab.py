"""Elastic wall-clock A/B: straggler-injected pool vs fixed mesh.

The measurement PR 8 left open: the elastic τ-averaging claim is not
just loss-trajectory equivalence (tests/test_elastic.py pins that) but
that a straggling worker costs the POOL only its proportional capacity
— the round proceeds at width W-1 instead of stalling the collective
until the straggler catches up.  Two arms, same family/tau/rounds:

* **fixed** — ElasticTrainer at full width, no faults: the baseline
  per-round wall.
* **straggler** — identical run with a FaultPlan ``delay`` parking one
  worker mid-run: per-round walls at the reduced width, plus the
  rejoin round.

Per-round walls come from the train callback; every round ends in the
HOST-SIDE blob-wise weighted average (parallel/elastic.py pulls worker
rows to np before mixing), so the wall includes device execution by
construction — no separate value fence needed.  The first round at
each mesh width is that width's compile round and is excluded from
steady-state medians; compile rounds are reported separately.

One JSON line per arm + a combined gate record, banked to
``docs/elastic_ab_last.json`` under ``--bank``.  Every record names its
device; a run that finds no accelerator and was not pinned to the CPU
exits 2; CPU runs are host-side provenance only.

ref: src/main/scala/libs/WorkerStore.scala:1 (the reference keeps a
static worker registry; surviving membership change is new surface).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAST_PATH = "docs/elastic_ab_last.json"


def _median(vals):
    return float(np.median(np.asarray(vals, np.float64))) if vals else 0.0


def run_arm(name: str, family, per_device: int, width: int, tau: int,
            rounds: int, plan, devices) -> dict:
    """One timed ElasticTrainer run; returns per-width steady medians."""
    from sparknet_tpu.parallel.elastic import ElasticTrainer
    from sparknet_tpu.parallel.modes import _feeds_for
    from sparknet_tpu.solvers.solver import Solver

    el = ElasticTrainer(
        Solver(family.solver(), family.net(per_device)),
        width=width, tau=tau, plan=plan, devices=devices)
    walls: list[tuple[int, float]] = []  # (width, round_wall_s)
    t_last = [time.perf_counter()]

    def cb(rnd, loss):
        now = time.perf_counter()
        walls.append((el.width, now - t_last[0]))
        t_last[0] = now

    t0 = time.perf_counter()
    el.train(rounds, lambda g: _feeds_for(
        family, per_device, np.random.RandomState(g % 997)), callback=cb)
    wall_s = time.perf_counter() - t0

    # first round at each width = that width's compile round
    seen: set[int] = set()
    steady: dict[int, list[float]] = {}
    compile_rounds: dict[int, float] = {}
    examples = 0
    for w, dt in walls:
        examples += tau * w * per_device
        if w in seen:
            steady.setdefault(w, []).append(dt)
        else:
            seen.add(w)
            compile_rounds[w] = round(dt, 4)
    return {
        "metric": f"elastic_{name}_round_ms",
        "value": round(_median([dt for ws in steady.values()
                                for dt in ws]) * 1e3, 2),
        "unit": f"ms/round median, steady-state (tau={tau}, "
                f"per-device batch {per_device})",
        "rounds": rounds,
        "widths_seen": sorted(seen),
        "steady_round_ms": {str(w): round(_median(v) * 1e3, 2)
                            for w, v in sorted(steady.items())},
        "compile_round_s": compile_rounds,
        "examples": examples,
        "wall_s": round(wall_s, 3),
        "img_s": round(examples / wall_s, 1),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--family", default="cifar10_quick")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--per-device", type=int, default=2)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--straggle-at", type=int, default=4,
                    help="round the straggler parks at")
    ap.add_argument("--straggle-steps", type=int, default=8,
                    help="local steps the straggler falls behind")
    ap.add_argument("--platform", default="",
                    help="force a jax platform")
    ap.add_argument("--bank", action="store_true",
                    help=f"bank the gate record to {LAST_PATH}")
    args = ap.parse_args()

    if args.platform == "cpu":
        # host run: the virtual mesh needs the device-count XLA flag set
        # BEFORE the backend initializes, not just the platform pin
        from sparknet_tpu.analysis.graphcheck import _pin_cpu_mesh

        _pin_cpu_mesh(args.devices)
    elif args.platform:
        from sparknet_tpu.common import force_platform

        force_platform(args.platform)
    import jax

    from sparknet_tpu.common import require_chip

    stamp = require_chip("elastic_ab")  # no chip, no pin: exit 2
    on_accel = stamp["platform"] != "cpu"

    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES
    from sparknet_tpu.parallel.elastic import FaultPlan, delay

    family = GRAPH_SWEEP_FAMILIES[args.family]
    devices = jax.devices()[:args.devices]
    W = len(devices)
    if W < 2:
        print(f"elastic_ab: need >= 2 devices, have {W}", file=sys.stderr)
        return 2

    fixed = run_arm("fixed", family, args.per_device, W, args.tau,
                    args.rounds, None, devices)
    print(json.dumps(fixed))
    plan = FaultPlan([delay(0, at_round=args.straggle_at,
                            steps=args.straggle_steps)])
    strag = run_arm("straggler", family, args.per_device, W, args.tau,
                    args.rounds, plan, devices)
    print(json.dumps(strag))

    # the gate: while the straggler is parked the pool runs width W-1
    # rounds whose wall tracks the fixed-mesh round (it must NOT inherit
    # the straggler's delay) — overhead is reduced-width round wall over
    # the fixed baseline, ~1.0x when the collective isn't stalled
    base_ms = fixed["value"]
    reduced = strag["steady_round_ms"].get(str(W - 1))
    overhead = round(reduced / base_ms, 3) if reduced and base_ms else None
    record = {
        "metric": "elastic_ab_gate",
        "value": overhead,
        "unit": "reduced-width round wall / fixed-mesh round wall "
                "(1.0 = straggler costs only its capacity share)",
        "family": args.family,
        "tau": args.tau,
        "width": W,
        "fixed": fixed,
        "straggler": strag,
        **stamp,
        "measured": overhead is not None,
        "host_side": not on_accel,
        "chip_measured": on_accel and overhead is not None,
    }
    print(json.dumps(record))
    if args.bank:
        from sparknet_tpu.common import bank_guard

        bank_guard(LAST_PATH, record, measured=record["measured"])
    return 0 if record["measured"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
