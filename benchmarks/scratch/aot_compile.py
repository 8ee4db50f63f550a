"""Compile the cells' programs at FULL size for a described v5e:2x2,
without a chip (on-chip-measurement guide, section 2).  Run by hand
before a chip call; never imported, never part of a run:

    JAX_PLATFORMS=cpu python benchmarks/scratch/aot_compile.py \
        [--config alexnet-b256-bf16] [--batch 256] [--tau 10] [--mesh]

Prints ``memory_analysis()`` of the solo step program and, with
``--mesh``, of the four-chip tau round (per device), plus the bytes the
round's staged feed takes.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def report(label, compiled, t0):
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    print(json.dumps({
        "program": label, "compile_s": round(time.time() - t0, 1),
        "argument_gb": gb(m.argument_size_in_bytes),
        "output_gb": gb(m.output_size_in_bytes),
        "alias_gb": gb(m.alias_size_in_bytes),
        "temp_gb": gb(m.temp_size_in_bytes),
        "peak_estimate_gb": gb(m.argument_size_in_bytes + m.output_size_in_bytes
                               - m.alias_size_in_bytes + m.temp_size_in_bytes),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="alexnet-b256-bf16")
    ap.add_argument("--batch", type=int, default=0, help="override batch_size")
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--mesh", action="store_true", help="also the 4-chip round")
    a = ap.parse_args()

    import re
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from benchmarks.harness import dataset, front_door

    jax.config.update("jax_enable_compilation_cache", False)
    dataset.CACHE_DIR = os.path.join(ROOT, "benchmarks", ".cache", "aot")
    with open(os.path.join(ROOT, "benchmarks", "configs", a.config + ".json")) as f:
        config = json.load(f)
    batch = a.batch or config["batch_per_worker"]
    work = os.path.join(dataset.CACHE_DIR, "configs")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    for suffix in (".solver.prototxt", ".train.prototxt"):
        with open(os.path.join(ROOT, "benchmarks", "configs", a.config + suffix)) as f:
            text = re.sub(r"batch_size: \d+", f"batch_size: {batch}", f.read())
        with open(os.path.join(work, a.config + suffix), "w") as f:
            f.write(text)
    ds = config["dataset"]
    db = dataset.ensure_db(0, 4, tuple(ds["chw"]), ds["classes"])
    flags = [x.replace("{db}", db).replace("{configs}", work)
             for x in config["train_flags"]] + ["--tau", str(a.tau)]

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    c, h, w = config["input_chw"]

    def body(args) -> int:
        solver = front_door.build_solver(args)
        one = SingleDeviceSharding(topo.devices[0])
        sds = lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s)
        tree = lambda t, s: jax.tree_util.tree_map(lambda x: sds(x, s), t)
        feeds = {"data": jax.ShapeDtypeStruct((batch, c, h, w), jnp.float32, sharding=one),
                 "label": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one)}
        it = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        t0 = time.time()
        compiled = jax.jit(solver._make_train_step(debug=False),
                           donate_argnums=(0, 1)).lower(
            tree(solver.variables, one), tree(solver.slots, one), it, feeds,
            sds(solver._key, one)).compile()
        report(f"{a.config} solo step b{batch}", compiled, t0)
        img = c * h * w
        print(json.dumps({
            "feed": "one solo batch", "uint8_wire_gb": round(batch * 3 * 256 * 256 / 1e9, 3),
            "f32_augmented_gb": round(batch * img * 4 / 1e9, 3)}))
        if not a.mesh:
            return 0

        from sparknet_tpu.parallel.trainer import ParallelTrainer

        trainer = ParallelTrainer(solver, tau=a.tau)  # CPU mesh, for its closures
        R = trainer.num_workers
        mesh = Mesh(np.array(topo.devices[:R]), (trainer.data_axis,))
        trainer.mesh = mesh  # _make_tau_round reads self.mesh at trace time
        stacked = NamedSharding(mesh, P(trainer.data_axis))
        rep = NamedSharding(mesh, P())
        fshard = NamedSharding(mesh, P(None, trainer.data_axis))
        feeds = {"data": jax.ShapeDtypeStruct((a.tau, R * batch, c, h, w), jnp.float32, sharding=fshard),
                 "label": jax.ShapeDtypeStruct((a.tau, R * batch), jnp.int32, sharding=fshard)}
        t0 = time.time()
        compiled = jax.jit(trainer._make_tau_round(), donate_argnums=(0, 1)).lower(
            tree(trainer.variables, stacked), tree(trainer.slots, stacked),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), feeds,
            sds(solver._key, rep)).compile()
        report(f"{a.config} tau={a.tau} round, {R} chips, b{batch}/chip (per device)",
               compiled, t0)
        text = compiled.as_text()
        print(json.dumps({
            "collectives": {k: text.count(k) for k in
                            ("all-reduce(", "all-reduce-start(", "all-gather(", "collective-permute(")},
            "staged_feed_per_chip": {
                "uint8_wire_gb": round(a.tau * batch * 3 * 256 * 256 / 1e9, 3),
                "f32_augmented_gb": round(a.tau * batch * img * 4 / 1e9, 3)}}))
        return 0

    front_door.run_as_train(flags, body)


if __name__ == "__main__":
    main()
