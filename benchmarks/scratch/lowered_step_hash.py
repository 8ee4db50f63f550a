"""The lowered text of every configuration's train step, hashed: the
chip-free proof that a change to shared code (``compiler/graph.py``,
``solvers/``, ``ops/``) left a cell's program as it was.  Run by hand in
two checkouts and compare; never imported, never part of a run:

    JAX_PLATFORMS=cpu python benchmarks/scratch/lowered_step_hash.py \
        [--configs olmoe-1b-7b-l1-bf16,...] [--dump <dir>]

Per configuration of BENCHMARK.json (all by default; one that a checkout
cannot build is reported and skipped): the solver prototxt's net and
solver, abstract state (nothing is materialized), the solo step
(``solvers/solver.py build_train_step``) lowered for a described v5e with
the program's kernel choice pointed at "tpu" as ``aot_compile_decoder.py``
does, and the sha256 of ``lowered.as_text()`` (StableHLO without source
locations, so moved lines do not show), twice: as it is, and with every
Mosaic kernel's serialized body (``tpu_custom_call``'s base64 MLIR
bytecode, which DOES carry the Python call stack that traced it, so an
edit that moves a line of ``compiler/graph.py`` shows in it) parsed and
printed again without its locations.  Equal second hashes are equal
programs.  Lowering only: seconds a configuration.  The trainer cell's round program applies the same
``Network.apply`` to the same net as its configuration's solo step.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def without_kernel_locations(text: str) -> str:
    """``text`` with each kernel body replaced by the hash of its MLIR
    printed without debug information."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation \
                .get_asm(enable_debug_info=False)
        return "body: " + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="")
    ap.add_argument("--dump", default="",
                    help="a directory for the texts, to diff two checkouts")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.common import Phase, set_config
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.proto.text_format import parse_file
    from sparknet_tpu.solvers.solver import (
        SolverConfig, build_train_step, load_solver_net)
    from sparknet_tpu.solvers.updates import init_slots

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [c["name"] for c in json.load(f)["configs"]]
    names = a.configs.split(",") if a.configs else names
    configs = os.path.join(ROOT, "benchmarks", "configs")
    set_config(compute_dtype=jnp.bfloat16)  # every recipe's --dtype bf16
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # the program's kernel choice
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    tree = lambda t: jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), t)
    for name in names:
        with open(os.path.join(configs, name + ".json")) as f:
            config = json.load(f)
        try:
            msg = parse_file(os.path.join(configs, name + ".solver.prototxt"))
            cfg = SolverConfig.from_proto(msg)
            net = Network(load_solver_net(msg, root=configs), Phase.TRAIN)
            if "seq_len" in config:
                n = config["sequences_per_step"]
                feeds = {"data": sds((n, config["seq_len"]), jnp.int32),
                         "label": sds((n, config["seq_len"]), jnp.int32)}
                shapes = None
            else:
                n = config["batch_per_worker"]
                shapes = {"data": (n, *config["input_chw"]), "label": (n,)}
                feeds = {"data": sds(shapes["data"], jnp.float32),
                         "label": sds(shapes["label"], jnp.int32)}
            variables = jax.eval_shape(
                lambda k: net.init(k, shapes),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            slots = jax.eval_shape(
                lambda p: init_slots(cfg.solver_type, p), variables.params)
            lowered = jax.jit(
                build_train_step(cfg, net, net.param_specs_for(variables)),
                donate_argnums=(0, 1)).lower(
                tree(variables), tree(slots), sds((), jnp.int32), feeds,
                sds((), jax.random.key(0).dtype))
            text = lowered.as_text()
        except Exception as e:  # a checkout without this configuration's layers
            print(json.dumps({"config": name, "cannot_build": repr(e)[:200]}),
                  flush=True)
            continue
        if a.dump:
            os.makedirs(a.dump, exist_ok=True)
            with open(os.path.join(a.dump, name + ".txt"), "w") as f:
                f.write(text)
        sha = lambda t: hashlib.sha256(t.encode()).hexdigest()
        print(json.dumps({
            "config": name, "bytes": len(text), "sha256": sha(text),
            "kernels": text.count("tpu_custom_call"),
            "sha256_without_kernel_locations":
                sha(without_kernel_locations(text))}), flush=True)


if __name__ == "__main__":
    main()
