"""Compile a decoder cell's step program at FULL size for a described
v5e, without a chip (on-chip-measurement guide, section 2).  Run by hand
before a chip call; never imported, never part of a run:

    JAX_PLATFORMS=cpu python benchmarks/scratch/aot_compile_decoder.py \
        [--config joyai-llm-flash-l5-ep32-bf16] [--sequences 1] [--reference]

``aot_compile_lm.py`` for the configurations that name their own check
(``config["check"]``): prints ``memory_analysis()`` of the solo step
program (abstract state: nothing is materialized) and, with
``--reference``, of the plain reference's check program on one sequence.
The program picks its kernels by ``jax.default_backend()``, which says
"cpu" here, so THIS SCRIPT points it at "tpu" for the lowering; nothing in
the program offers that switch.  A compile that passes is not a chip run.
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


def report(label, compiled, t0):
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    text = compiled.as_text()
    print(json.dumps({
        "program": label, "compile_s": round(time.time() - t0, 1),
        "argument_gb": gb(m.argument_size_in_bytes),
        "output_gb": gb(m.output_size_in_bytes),
        "alias_gb": gb(m.alias_size_in_bytes),
        "temp_gb": gb(m.temp_size_in_bytes),
        "peak_estimate_gb": gb(m.argument_size_in_bytes + m.output_size_in_bytes
                               - m.alias_size_in_bytes + m.temp_size_in_bytes),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "splash_kernels": text.count("splash_mha"),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="joyai-llm-flash-l5-ep32-bf16")
    ap.add_argument("--sequences", type=int, default=0)
    ap.add_argument("--reference", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import load_by_name
    from sparknet_tpu.common import Phase, set_config
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.proto.text_format import parse_file
    from sparknet_tpu.solvers.solver import (
        SolverConfig, abstract_train_state, build_train_step, load_solver_net)

    jax.config.update("jax_enable_compilation_cache", False)
    configs = os.path.join(ROOT, "benchmarks", "configs")
    with open(os.path.join(configs, a.config + ".json")) as f:
        config = json.load(f)
    batch = a.sequences or config["sequences_per_step"]
    set_config(compute_dtype=jnp.bfloat16)  # the recipe's --dtype bf16
    msg = parse_file(os.path.join(configs, a.config + ".solver.prototxt"))
    cfg = SolverConfig.from_proto(msg)
    net = Network(load_solver_net(msg, root=configs), Phase.TRAIN)
    variables, slots = abstract_train_state(cfg, net)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # see the module docstring
    tree = lambda t, dt=None: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dt or x.dtype, sharding=one), t)
    ids = lambda n: jax.ShapeDtypeStruct((n, config["seq_len"]), jnp.int32,
                                         sharding=one)
    t0 = time.time()
    compiled = jax.jit(
        build_train_step(cfg, net, net.param_specs_for(variables)),
        donate_argnums=(0, 1)).lower(
        tree(variables), tree(slots),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        {"data": ids(batch), "label": ids(batch)},
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)).compile()
    report(f"{a.config} solo step, {batch} sequences", compiled, t0)
    if not a.reference:
        return
    ref = load_by_name("reference", config["reference"])
    checker = load_by_name("harness", config["check"])
    rcfg, which = checker.reference_config(config), checker.leaves(config)
    bias = {name: st["bias"] for name, st in variables.state.items()
            if "bias" in st}

    def check(params, bias, data, label):
        with jax.default_matmul_precision("highest"):
            (total, (_, (logits, mtp_logits, routing))), g = jax.value_and_grad(
                ref.loss, has_aux=True)(params, bias, data, label, rcfg)
        return (total, logits[:, -checker.LAST:], mtp_logits[:, -checker.LAST:],
                routing, checker._adamw_changes(ref, params, g, cfg, which))

    t0 = time.time()
    compiled = jax.jit(check).lower(
        tree(variables.params, jnp.float32), tree(bias), ids(1), ids(1)).compile()
    report(f"{a.config} reference check, 1 sequence", compiled, t0)


if __name__ == "__main__":
    main()
