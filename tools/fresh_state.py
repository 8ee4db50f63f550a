"""A solver's ONE init program (``solvers/solver.py fresh_train_state``)
at a benchmark configuration's full size.  Run by hand; never imported.

    # chip-free: compile the init for a described v5e, print its
    # ``memory_analysis()`` (the state is its output: nothing materializes)
    JAX_PLATFORMS=cpu python tools/fresh_state.py --aot \
        [--configs phi4-mini-flash-l6-v8-bf16,...]

    # on the chip: the jitted state against the eager composition
    # (``net.init`` + ``init_slots``, a program a filler and shape), leaf
    # by leaf, and what each took with its compiles
    python tools/fresh_state.py --compare \
        [--configs alexnet-b1024-bf16,olmoe-1b-7b-l1-bf16] [--seed 530101]

A compile that passes is not a chip run; ``--compare`` on the CPU
(``JAX_PLATFORMS=cpu``) says so in its line (``platform``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
LARGEST = ("phi4-mini-flash-l6-v8-bf16,qwen3-next-80b-a3b-l4-ep16-v8-bf16,"
           "joyai-llm-flash-l5-ep32-bf16")


def load(name):
    """``(cfg, net, feed_shapes)`` of a configuration, as its cell's
    ``Solver`` meets them."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.proto.text_format import parse_file
    from sparknet_tpu.solvers.solver import SolverConfig, load_solver_net

    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    msg = parse_file(os.path.join(CONFIGS, name + ".solver.prototxt"))
    net = Network(load_solver_net(msg, root=CONFIGS), Phase.TRAIN)
    shapes = None
    if "seq_len" not in config:
        n = config["batch_per_worker"]
        shapes = {"data": (n, *config["input_chw"]), "label": (n,)}
    return SolverConfig.from_proto(msg), net, shapes


def aot(names) -> None:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.solvers.solver import fresh_train_state

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    gb = lambda b: round(b / 1e9, 3)
    for name in names:
        cfg, net, shapes = load(name)
        t0 = time.time()
        compiled = fresh_train_state(cfg, net, shapes).lower(key).compile()
        m = compiled.memory_analysis()
        print(json.dumps({
            "config": name, "program": "fresh state, described v5e",
            "compile_s": round(time.time() - t0, 1),
            "output_gb": gb(m.output_size_in_bytes),
            "temp_gb": gb(m.temp_size_in_bytes),
            "code_gb": gb(m.generated_code_size_in_bytes),
            "total_gb": gb(m.output_size_in_bytes + m.temp_size_in_bytes
                           + m.generated_code_size_in_bytes
                           + m.argument_size_in_bytes),
        }), flush=True)


def compare(names, seed) -> None:
    import jax
    import numpy as np

    from sparknet_tpu.obs.sentinel import get_sentinel
    from sparknet_tpu.solvers.solver import fresh_train_state
    from sparknet_tpu.solvers.updates import init_slots

    device = jax.devices()[0]
    sentinel = get_sentinel().install()

    def timed(fn):
        before, t0 = sentinel.thread_compile(), time.time()
        out = jax.block_until_ready(fn())
        wall, after = time.time() - t0, sentinel.thread_compile()
        return out, {"wall_s": round(wall, 3),
                     "compiles": after[0] - before[0],
                     "compile_s": round(after[1] - before[1], 3)}

    def eager(net, cfg, shapes, key):
        variables = net.init(key, shapes)
        return variables, init_slots(cfg.solver_type, variables.params)

    for name in names:
        cfg, net, shapes = load(name)
        key = jax.random.key(seed)
        one, t_one = timed(lambda: fresh_train_state(cfg, net, shapes)(key))
        # to the host and off the chip: the largest states fit it once
        b = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(one)]
        del one
        many, t_many = timed(lambda: eager(net, cfg, shapes, key))
        a = jax.tree_util.tree_leaves(many)
        off, ulps, entries = [], 0, 0
        for i, (x, y) in enumerate(zip(a, b)):
            assert x.shape == y.shape and x.dtype == y.dtype, i
            x = np.asarray(x)
            if x.tobytes() != y.tobytes():
                bits = np.dtype(f"i{x.dtype.itemsize}")
                off.append(i)
                entries += int((x != y).sum())
                ulps = max(ulps, int(np.abs(
                    x.view(bits).astype(np.int64)
                    - y.view(bits).astype(np.int64)).max()))
        print(json.dumps({
            "config": name, "seed": seed, "platform": device.platform,
            "device_kind": device.device_kind, "leaves": len(a),
            "leaves_differing": len(off), "entries_differing": entries,
            "largest_ulps": ulps, "one_program": t_one, "eager": t_many,
        }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--configs", default="")
    ap.add_argument("--seed", type=int, default=530101)
    a = ap.parse_args()
    if a.aot == a.compare:
        ap.error("one of --aot and --compare")

    import jax.numpy as jnp

    from sparknet_tpu.common import set_config

    set_config(compute_dtype=jnp.bfloat16)  # every recipe's --dtype bf16
    if a.aot:
        aot((a.configs or LARGEST).split(","))
    else:
        compare((a.configs or "alexnet-b1024-bf16,olmoe-1b-7b-l1-bf16")
                .split(","), a.seed)


if __name__ == "__main__":
    main()
