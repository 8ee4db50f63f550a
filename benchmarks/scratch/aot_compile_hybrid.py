"""Compile the hybrid decoder cell's REFERENCE CHECK program at full size
for a described v5e, without a chip (on-chip-measurement guide, section
2).  Run by hand before a chip call; never imported, never part of a run:

    JAX_PLATFORMS=cpu python benchmarks/scratch/aot_compile_hybrid.py \
        [--config phi4-mini-flash-l6-v8-bf16] [--bf16]

The step program itself is ``aot_compile_decoder.py --config <name>``'s
(it takes any configuration that names its zoo builder).  This script is
its ``--reference`` for ``harness/hybrid_check.py``: ``memory_analysis()``
and the size of the serialized executable of ``run_reference`` on one
sequence, abstract parameters (nothing is materialized).  A compile that
passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="phi4-mini-flash-l6-v8-bf16")
    ap.add_argument("--bf16", action="store_true",
                    help="the all-bf16 reading instead of the reference proper")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import load_by_name
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.proto.text_format import parse_file
    from sparknet_tpu.solvers.solver import SolverConfig, load_solver_net

    jax.config.update("jax_enable_compilation_cache", False)
    configs = os.path.join(ROOT, "benchmarks", "configs")
    with open(os.path.join(configs, a.config + ".json")) as f:
        config = json.load(f)
    msg = parse_file(os.path.join(configs, a.config + ".solver.prototxt"))
    cfg = SolverConfig.from_proto(msg)
    net = Network(load_solver_net(msg, root=configs), Phase.TRAIN)
    params = jax.eval_shape(lambda k: net.init(k, None, None).params,
                            jax.random.key(0))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    ids = jax.ShapeDtypeStruct((1, config["seq_len"]), jnp.int32, sharding=one)
    ref = load_by_name("reference", config["reference"])
    checker = load_by_name("harness", config["check"])
    rcfg, which = checker.reference_config(config), checker.leaves(config)
    dtype = jnp.bfloat16 if a.bf16 else jnp.float32

    def check(params, data, label):
        with jax.default_matmul_precision("highest"):
            (loss, logits), g = jax.value_and_grad(ref.loss, has_aux=True)(
                params, data, label, rcfg, dtype)
        g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
        return (loss, logits[:, -checker.LAST:],
                checker._adamw_changes(ref, params, g, cfg, which))

    t0 = time.time()
    compiled = jax.jit(check).lower(on_chip(params), ids, ids).compile()
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    print(json.dumps({
        "program": f"{a.config} reference check, 1 sequence, "
                   f"{'bf16' if a.bf16 else 'f32 highest'}",
        "compile_s": round(time.time() - t0, 1),
        "argument_gb": gb(m.argument_size_in_bytes),
        "output_gb": gb(m.output_size_in_bytes),
        "temp_gb": gb(m.temp_size_in_bytes),
        "peak_estimate_gb": gb(m.argument_size_in_bytes + m.output_size_in_bytes
                               - m.alias_size_in_bytes + m.temp_size_in_bytes),
        "generated_code_mb": round(m.generated_code_size_in_bytes / 1e6, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
