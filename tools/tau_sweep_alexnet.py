"""The SparkNet tau tradeoff at AlexNet scale.

The paper's fig. 5 axis — accuracy vs synchronization cadence at a
fixed per-worker local-step budget — measured with the ACTUAL AlexNet
topology (conv stack, LRN, grouped convs, dropout; ref:
caffe/models/bvlc_alexnet/train_val.prototxt) rather than LeNet, and
with the ImageNet recipe's tau=50 cadence represented (ref:
ImageNetApp.scala:151 runs 50 local iterations between syncs).

Input scale: this box is a 1-core CPU host driving a virtual 8-device
mesh, so the spatial size is reduced (``--crop 67`` keeps every layer
shape-valid: 67 -> conv1/4 -> 15 -> pool 7 -> pool2 3 -> pool5 1) and
the data is synthetic-but-structured — 10 fixed pixel-scale class
templates + heavy noise, a task whose gradient structure (not its
semantics) is what the sync-cadence claim is about.

Run:  python tools/tau_sweep_alexnet.py [--budget 100] [--taus 1,10,50]
Writes docs/tau_sweep_alexnet.json and prints one JSON line per row.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--taus", default="1,10,50")
    p.add_argument("--budget", type=int, default=100,
                   help="local steps per worker (fixed across taus)")
    p.add_argument("--crop", type=int, default=67)
    p.add_argument("--batch", type=int, default=8,
                   help="per-worker minibatch")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--noise", type=float, default=40.0,
                   help="pixel-noise sigma (templates are +-80); lower = "
                   "higher SNR so every arm escapes the softmax plateau "
                   "inside the budget")
    p.add_argument("--out", default="docs/tau_sweep_alexnet.json")
    return p.parse_args()


def make_task(classes: int, crop: int, seed: int = 0, noise: float = 40.0):
    """Fixed pixel-scale templates (+-80) + N(0, noise) pixels (the zoo
    fillers are calibrated for raw-pixel inputs — see
    .claude/skills/verify)."""
    import numpy as np

    rs = np.random.RandomState(seed)
    templates = rs.randn(classes, 3, crop, crop).astype(np.float32) * 80

    def sample(rng, n):
        y = rng.randint(0, classes, n)
        x = templates[y] + (
            rng.randn(n, 3, crop, crop).astype(np.float32) * noise)
        return x, y.astype(np.int32)

    return sample


def main() -> int:
    args = parse_args()
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.parallel.mesh import data_parallel_mesh
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    from sparknet_tpu.solvers.solver import Solver
    from sparknet_tpu.solvers.solver import SolverConfig

    sample = make_task(args.classes, args.crop, noise=args.noise)
    eval_rs = np.random.RandomState(99)
    xte, yte = sample(eval_rs, 256)
    B = args.batch
    mesh = data_parallel_mesh()
    workers = mesh.shape["data"]

    def test_fn(b):
        return {"data": xte[b * 32:(b + 1) * 32],
                "label": yte[b * 32:(b + 1) * 32]}

    # AlexNet recipe hyperparameters, shortened schedule (ref:
    # caffe/models/bvlc_alexnet/solver.prototxt -- step policy, momentum
    # 0.9, weight_decay 5e-4); base_lr tuned down only if it diverges at
    # this reduced spatial scale.
    cfg = SolverConfig(base_lr=args.lr, lr_policy="fixed", momentum=0.9,
                       weight_decay=5e-4, solver_type="SGD")

    rows = []
    for tau in (int(t) for t in args.taus.split(",")):
        rounds = args.budget // tau
        if rounds == 0:
            # never bank a row for an arm that trained zero steps (the
            # previous arm's loss would leak into it)
            print(json.dumps({"tau_row_skipped": {
                "tau": tau,
                "reason": f"budget {args.budget} < tau {tau}",
            }}), flush=True)
            continue
        net = models.alexnet(B if tau > 1 else B * workers,
                             num_classes=args.classes, crop=args.crop)
        solver = Solver(cfg, net)
        trainer = ParallelTrainer(solver, mesh=mesh, tau=tau)
        rng = np.random.RandomState(7)

        def data_fn(it):
            if tau == 1:
                x, y = sample(rng, B * workers)
                return {"data": x, "label": y}
            stack_x, stack_y = [], []
            for _ in range(tau):
                x, y = sample(rng, B * workers)
                stack_x.append(x)
                stack_y.append(y)
            return {"data": np.stack(stack_x), "label": np.stack(stack_y)}

        t0 = time.time()
        for _ in range(rounds):
            loss = trainer.train_round(data_fn)
        wall = time.time() - t0
        acc = trainer.test(8, test_fn)["accuracy"]
        row = {
            "tau": tau,
            "sync_rounds": rounds,
            "local_steps_per_worker": rounds * tau,
            "test_accuracy": round(float(acc), 4),
            "final_loss": round(float(loss), 4),
            "seconds": round(wall, 1),
        }
        rows.append(row)
        print(json.dumps({"tau_row": row}), flush=True)

    out = {
        "model": "alexnet", "crop": args.crop, "workers": workers,
        "per_worker_batch": B, "budget": args.budget,
        "recipe": "bvlc_alexnet solver (fixed lr variant)",
        "noise_sigma": args.noise,
        "rows": rows,
        "utc": time.strftime("%Y-%m-%d %H:%M:%SZ", time.gmtime()),
    }
    out_path = args.out
    if not os.path.isabs(out_path):
        # bank relative outputs under the repo root regardless of cwd —
        # a multi-hour sweep must not lose its evidence to a wrong cwd
        out_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            out_path)
    rc = 0
    try:
        with open(out_path + ".tmp", "w") as f:
            json.dump(out, f, indent=1)
        os.replace(out_path + ".tmp", out_path)
    except OSError as e:
        print(f"tau_sweep: could not write {out_path}: {e}", file=sys.stderr)
        rc = 1
    print(json.dumps(out))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
