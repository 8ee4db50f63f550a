"""Count the whole-array copies of expert matrices and of activations in a
compiled train step, without a chip (on-chip-measurement guide, section 2).

    JAX_PLATFORMS=cpu python tools/expert_copies.py \
        [--config olmoe-1b-7b-l1-bf16 | joyai-llm-flash-l5-ep32-bf16 |
                  ouro-2.6b-l4-ut4-v8-bf16 | phi4-mini-flash-l6-v8-bf16]

Compiles the solo step program of a decoder configuration under
``benchmarks/configs/`` at full size for a described v5e (abstract state:
nothing is materialized), and prints ``memory_analysis()`` and the
``copy`` instructions of the ENTRY computation whose result has the shape
of an f32 expert matrix (a rank-3 blob of an ``MoE`` layer: the matrix
itself or one of its AdamW moments); exit code 1 if there is one.  Where
a layer holds a share of its experts it also lists the program's
``gather`` / ``scatter`` instructions by the rows they move
(``row_moves``): since PR 49 such a layer moves its rows inside loops
over its live tiles, 512 rows a trip, so outside the loops nothing may
move more rows than the step has tokens (exit code 1 otherwise: a mover
over a capacity's or all T·k pairs' rows).  PR 31 found 18 expert copies
of 537 MB in the OLMoE step and 90 of 50 MB in JoyAI's: XLA had folded the
transpose megablox put behind its weight gradient into the layout of the
update, and converted the matrix and both moments there and back in
every step (``ops/moe.py grouped_matmul``).  The count is 0 while the
gradient arrives as the matrices are stored;
``tests/test_moe_grad_layout.py`` holds a toy step to that.

Since PR 44 it prints ``activation_copies`` too: the ENTRY ``copy``
results of at least ``sequences x seq_len x hidden`` elements in the
compute dtype, by shape (no part of the exit code).  Multi-head
attention's head split and merge were ten of them a layer-pass, 160 in
Ouro's step and 14 in OLMoE's; the same test file holds toy steps to
none between token-major and head-major.

The program picks its kernels by ``jax.default_backend()``, which says
"cpu" here, so ``lowering_for_tpu`` points it at "tpu" for the lowering;
nothing in the program offers that switch.  A compile that passes is not
a chip run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# `%copy.157 = f32[64,1024,2048]{1,2,0:T(8,128)} copy(f32[...]{2,1,0...} %p)`
_COPY = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?P<layout>\{[^}]*\})? copy\((?P<operand>.*?)\)(?:,|$)")


def v5e_chip():
    """One device of a described v5e host (raises where none can be)."""
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return topo.devices[0]


@contextlib.contextmanager
def lowering_for_tpu():
    """``jax.default_backend()`` says "tpu" inside: the program then takes
    the branches it takes on the chip (see the module docstring)."""
    import jax
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


def compile_step(cfg, net, batch_shape, device):
    """The solo AdamW/SGD step program of ``net`` under solver ``cfg`` on
    [batch, seq_len] token ids, compiled for ``device`` from shapes alone
    -> (compiled, variables)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.solvers.solver import abstract_train_state, build_train_step

    one = SingleDeviceSharding(device)
    variables, slots = abstract_train_state(cfg, net)
    tree = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    ids = jax.ShapeDtypeStruct(tuple(batch_shape), jnp.int32, sharding=one)
    with lowering_for_tpu():
        compiled = jax.jit(
            build_train_step(cfg, net, net.param_specs_for(variables)),
            donate_argnums=(0, 1)).lower(
            tree(variables), tree(slots),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
            {"data": ids, "label": ids},
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                 sharding=one)).compile()
    return compiled, variables


def expert_shapes(net, variables) -> set[tuple[int, ...]]:
    """Shapes of the rank-3 f32 blobs of the net's ``MoE`` layers."""
    import jax.numpy as jnp
    return {tuple(b.shape)
            for layer in net.layers if layer.TYPE == "MoE"
            for b in variables.params[layer.name]
            if len(b.shape) == 3 and b.dtype == jnp.float32}


def entry_computation(hlo_text: str) -> list[str]:
    """The lines of the ENTRY computation of ``compiled.as_text()``."""
    lines = hlo_text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY "))
    # a custom call's attributes may span lines that start with "}}"
    end = next(i for i in range(start, len(lines)) if lines[i].rstrip() == "}")
    return lines[start + 1:end]


def expert_copies(hlo_text: str, shapes) -> list[dict]:
    """The ENTRY computation's ``copy`` instructions whose result is an
    f32 array of one of ``shapes``: name, shape, layouts, bytes."""
    found = []
    for line in entry_computation(hlo_text):
        m = _COPY.match(line)
        if not m or m["dtype"] != "f32":
            continue
        dims = tuple(int(d) for d in m["dims"].split(",") if d)
        if dims not in shapes:
            continue
        found.append({"name": m["name"], "shape": list(dims),
                      "to_layout": m["layout"], "operand": m["operand"],
                      "bytes": 4 * math.prod(dims)})
    return found


def activation_copies(hlo_text: str, elements: int,
                      dtype: str = "bf16") -> dict[str, int]:
    """{shape: count} of the ENTRY computation's ``copy`` instructions
    whose result is a ``dtype`` (the compute dtype) array of at least
    ``elements`` elements: an activation passes through HBM once more
    for each."""
    found: dict[str, int] = {}
    for line in entry_computation(hlo_text):
        m = _COPY.match(line)
        if not m or m["dtype"] != dtype:
            continue
        dims = [int(d) for d in m["dims"].split(",") if d]
        if math.prod(dims) >= elements:
            shape = f"{dtype}{dims}".replace(" ", "")
            found[shape] = found.get(shape, 0) + 1
    return found


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>[\w.\-]+) \(.*\{$")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)="
    r"%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def computations(hlo_text: str) -> dict[str, list[str]]:
    """{computation: its instruction lines} of ``compiled.as_text()``."""
    found, name = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m["name"]
            found[name] = []
        elif line.rstrip() == "}":
            name = None
        elif name:
            found[name].append(line)
    return found


def _callees(line: str) -> list[str]:
    return [n.strip().lstrip("%") for one, many in _CALLED.findall(line)
            for n in ([one] if one else many.split(","))]


_MOVE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = \w+\[(?P<dims>[\d,]*)\]\S* "
    r"(?P<op>gather|scatter)\((?P<operands>[^)]*)\)(?P<attrs>.*)$")
_DEFINED = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = \w+\[(?P<dims>[\d,]*)\]")


def row_moves(hlo_text: str) -> list[dict]:
    """Every ``gather`` and ``scatter`` of the compiled program that moves
    rows wider than one element: {"op", "rows", "width", "in_loop"}.
    ``rows`` is how many index positions it moves (a gather's result
    without its offset dims, a scatter's updates without their window
    dims), ``width`` the elements of one of them, ``in_loop`` whether a
    ``while``'s body (or what it calls) holds it.  ``ops/moe.py``'s
    share-holding layer moves its rows inside loops over the live tiles,
    512 rows a trip (PR 49): outside the loops no mover of such a layer
    is left, so none there moves more rows than the step has tokens."""
    comps = computations(hlo_text)
    in_loops: set[str] = set()

    def mark(name):
        if name in comps and name not in in_loops:
            in_loops.add(name)
            for line in comps[name]:
                for callee in _callees(line):
                    mark(callee)

    for lines in comps.values():
        for line in lines:
            if " while(" in line:
                for body in re.findall(r"body=%([\w.\-]+)", line):
                    mark(body)
    dims_of = lambda text: [int(d) for d in text.split(",") if d]
    listed = lambda attrs, key: [int(d) for d in re.search(
        key + r"=\{([\d,]*)\}", attrs)[1].split(",") if d]
    found = []
    for name, lines in comps.items():
        defined = {m["name"]: dims_of(m["dims"])
                   for m in map(_DEFINED.match, lines) if m}
        for line in lines:
            m = _MOVE.match(line)
            if not m:
                continue
            if m["op"] == "gather":
                dims, wide = dims_of(m["dims"]), listed(m["attrs"],
                                                        "offset_dims")
            else:  # (operand, indices, updates): the updates are what moves
                updates = m["operands"].split(",")[-1].split()[-1].lstrip("%")
                dims, wide = defined[updates], listed(m["attrs"],
                                                      "update_window_dims")
            width = math.prod(d for i, d in enumerate(dims) if i in wide)
            if width > 1:
                found.append({
                    "op": m["op"], "width": width, "in_loop": name in in_loops,
                    "rows": math.prod(d for i, d in enumerate(dims)
                                      if i not in wide)})
    return found


def report(label, compiled, hlo_text, copies, activations, seconds) -> dict:
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    return {
        "program": label, "compile_s": round(seconds, 1),
        "expert_copies": len(copies),
        "expert_copy_gb": gb(sum(c["bytes"] for c in copies)),
        "activation_copies": sum(activations.values()),
        "activation_copies_by_shape": activations,
        "argument_gb": gb(m.argument_size_in_bytes),
        "output_gb": gb(m.output_size_in_bytes),
        "alias_gb": gb(m.alias_size_in_bytes),
        "temp_gb": gb(m.temp_size_in_bytes),
        "peak_estimate_gb": gb(m.argument_size_in_bytes + m.output_size_in_bytes
                               - m.alias_size_in_bytes + m.temp_size_in_bytes),
        "tpu_custom_calls": hlo_text.count("tpu_custom_call"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="olmoe-1b-7b-l1-bf16")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import Phase, set_config
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.proto.text_format import parse_file
    from sparknet_tpu.solvers.solver import SolverConfig, load_solver_net

    jax.config.update("jax_enable_compilation_cache", False)
    configs = os.path.join(ROOT, "benchmarks", "configs")
    with open(os.path.join(configs, a.config + ".json")) as f:
        config = json.load(f)
    set_config(compute_dtype=jnp.bfloat16)  # the recipe's --dtype bf16
    msg = parse_file(os.path.join(configs, a.config + ".solver.prototxt"))
    cfg = SolverConfig.from_proto(msg)
    net = Network(load_solver_net(msg, root=configs), Phase.TRAIN)
    batch = (config["sequences_per_step"], config["seq_len"])
    t0 = time.time()
    compiled, variables = compile_step(cfg, net, batch, v5e_chip())
    text = compiled.as_text()
    copies = expert_copies(text, expert_shapes(net, variables))
    activations = activation_copies(
        text, math.prod(batch) * config["hidden_size"])
    row = report(f"{a.config} solo step, {batch[0]} sequences", compiled,
                 text, copies, activations, time.time() - t0)
    # a share-holding layer moves its rows inside loops over its live tiles
    shares = [l for l in net.layers
              if l.TYPE == "MoE" and l.experts_held < l.num_experts]
    moves = row_moves(text) if shares else []
    by_rows = lambda ms: {str(r): sum(m["rows"] == r for m in ms)
                          for r in sorted({m["rows"] for m in ms})}
    wide = [m for m in moves
            if not m["in_loop"] and m["rows"] > math.prod(batch)]
    row["row_moves_in_loops"] = by_rows([m for m in moves if m["in_loop"]])
    row["row_moves_over_the_tokens_outside_loops"] = by_rows(wide)
    print(json.dumps(row), flush=True)
    for c in copies:
        print(json.dumps(c))
    return 1 if copies or wide else 0


if __name__ == "__main__":
    sys.exit(main())
