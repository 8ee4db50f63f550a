"""Quantify the τ-round model-averaging sync cost vs model size.

The reference's sync round is a Spark star topology: every worker
serializes the full `WeightCollection` to the driver, the driver
tree-reduces and broadcasts back (~2 directions × model bytes × workers,
through JNA float-by-float copies — ref: src/main/scala/libs/Net.scala:131-171,
CifarApp.scala:132-134, measured as the hot spot in
WeightCollectionSpec.scala:20-32).  Here the same round is ONE in-program
`lax.pmean` over the mesh: weights never leave HBM and the transport is
ICI.  This tool measures the averaging program per model and prints the
analytic ICI payload math next to it (docs/BENCHMARKS.md records the
results).

Run: python tools/sync_cost.py [--platform cpu] [--devices 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    if args.platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparknet_tpu import models
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.common import Phase
    from sparknet_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh(args.devices)
    p = mesh.shape["data"]
    if p < args.devices:
        raise SystemExit(
            f"only {p} device(s) visible, {args.devices} requested — on a "
            "CPU-only host pass --platform cpu so the virtual mesh flag "
            "is set before jax initializes"
        )
    spec = NamedSharding(mesh, P("data"))

    # v5e public specs for the analytic column: per-chip ICI egress
    # ~4 links x 45 GB/s; ring all-reduce moves 2*S*(p-1)/p bytes/chip.
    ICI_BW = 180e9

    rows = []
    for name, builder in (
        ("lenet", lambda: models.lenet(8)),
        ("cifar10_quick", lambda: models.cifar10_quick(8)),
        ("alexnet", lambda: models.alexnet(8, num_classes=1000)),
    ):
        net = Network(builder(), Phase.TRAIN)
        variables = net.init(jax.random.PRNGKey(0))
        nbytes = sum(
            int(np.prod(b.shape)) * 4
            for bl in variables.params.values()
            for b in bl
        )
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(
                jnp.broadcast_to(x[None], (p,) + x.shape), spec
            ),
            variables.params,
        )
        # Each dispatch consumes the previous dispatch's DEVICE-side
        # probe scalar as a perturbation of the first leaf: the calls
        # form a serial value chain (no two carry identical args, no
        # host round-trip inside the timed loop), and the probe sums
        # one element of EVERY averaged leaf so none is dead code.  The
        # 1e-6 scale is representable against ~1e-1 params (a smaller
        # epsilon would be absorbed by f32, leaving the probe constant
        # and the chain fake); the single end-of-loop fence fetches the
        # probe VALUE (common.value_fence).
        def avg_fn(t, salt):
            leaves, treedef = jax.tree_util.tree_flatten(t)
            outs = []
            for i, x in enumerate(leaves):
                if i == 0:
                    x = x + (salt * 1e-6).astype(x.dtype)
                outs.append(x.mean(0))
            probe = sum(o.ravel()[0].astype(jnp.float32) for o in outs)
            return jax.tree_util.tree_unflatten(treedef, outs), probe

        avg = jax.jit(avg_fn)
        from sparknet_tpu.common import value_fence as fence

        _, probe = avg(stacked, jnp.float32(0.0))  # warm
        fence(probe)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            _, probe = avg(stacked, probe)
        fence(probe)
        dt = (time.perf_counter() - t0) / args.iters

        analytic_ici_ms = 2 * nbytes * (p - 1) / p / ICI_BW * 1e3
        # the reference's round: 2 directions x model bytes serialized
        # through the driver per WORKER, at its measured JNA copy rate
        # (~61M floats in ~a second each way, WeightCollectionSpec)
        rows.append({
            "model": name,
            "param_mb": round(nbytes / 1e6, 1),
            "measured_avg_ms": round(dt * 1e3, 2),
            "analytic_ici_allreduce_ms": round(analytic_ici_ms, 3),
            "workers": p,
        })
        print(json.dumps(rows[-1]))

    print(json.dumps({"sync_cost_table": rows,
                      "platform": jax.devices()[0].platform}))


if __name__ == "__main__":
    main()
