"""graphcheck: jaxpr/StableHLO/HLO-level static contract analysis.

The second analysis engine, one layer below graftlint: where the AST
linter checks what the *source* promises, this lowers each parallel
mode's train step on the virtual 8-device CPU mesh and machine-checks
what the *compiled program* actually does — the same move TensorFlow
made when placement/partition invariants became graph-validated
(Abadi et al., OSDI 2016; ref integrity analog: the reference's Spark
DAG validated its own shuffle boundaries).  Everything here is
chip-free: lowering + CPU compilation only, never an execution, so it
runs — like the linter — on a box with no chip.

Five contract families per mode:

1. **comm budget** — census every collective in the post-SPMD HLO
   (count, bytes, inside-a-loop-body or not) and assert it against the
   analytic tau-averaging model in ``comm_model.py``.  This is the
   paper's own claim made executable: one weight-sized pmean per tau
   steps, grad-sized all-reduce per step at tau=1, and NO model-sized
   collective inside the local-step loop.
2. **sharding audit** — a mode that declares tensor/expert parallelism
   must actually shard at least one param (accidental full replication
   is silent and costs the whole TP win); the train-step carry must
   come back with the shardings it went in with (a changed spec means
   every round pays a reshard); resharding collectives (all-gather) are
   forbidden in pure-DP modes.
3. **dtype audit** — in bf16 configs every dot_general/convolution
   operand must be bf16.  The structural allowlist: anything that is
   NOT a dot/conv (softmax exps, BN statistics, loss accumulation, the
   f32 master-param update) may run f32 freely — those are the blessed
   upcasts; a f32 matmul is a smuggled one, burning the 4x MXU rate
   the bf16 config exists to buy (the unexplained 27.7% bf16 headline
   gap is exactly the class this hunts).
4. **donation/recompile audit** — train-step carries (variables,
   slots, center) must be donated or every step holds 2x params+slots
   in HBM; and lowering the step twice (iteration counter bumped) must
   produce byte-identical StableHLO or the step recompiles per call.
5. **layout census** — a transpose/data-formatting census over both
   the lowered StableHLO (what OUR frontend emits: rank-4 transposes
   are image-blob reorientations — data formatting by construction;
   rank-2 weight transposes from plain matmuls exist in every layout
   and are not counted against the contract) and the compiled module
   (what the backend's layout assignment adds).  The nhwc modes
   (``solo_nhwc``/``dp_nhwc``) pin ZERO interior rank-4 StableHLO
   transposes — the whole point of the channels-last path is that the
   orientation rides ``dimension_numbers``, never a transpose op —
   while the nchw manifests record today's counts as the banked
   baseline the on-chip A/B (tools/layout_ab.py --framework) prices.

Golden manifests are banked per mode in ``docs/graph_contracts/`` and
diffed on every run: any change to the lowered communication structure
of any mode is a finding until the manifests are regenerated
(``--update``), making the repo's central performance theory a
machine-checked regression gate.

Import contract: this module stays importable with stdlib only; jax
and the trainer stack load lazily inside :func:`run_graphcheck` after
the CPU platform is pinned.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Any, Iterator

from sparknet_tpu.analysis.comm_model import (
    COLLECTIVE_KINDS,
    CommExpectation,
    expected_comm,
)
from sparknet_tpu.analysis.core import Finding

__all__ = [
    "GRAPH_RULES",
    "GRAPH_SOURCE_PATTERNS",
    "Artifacts",
    "audit_target",
    "collective_census",
    "census_summary",
    "dtype_census",
    "layout_census",
    "manifest_path",
    "run_graphcheck",
    "sources_fingerprint",
    "trace_artifacts",
]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MANIFEST_DIR = os.path.join(_REPO, "docs", "graph_contracts")

# the graph-rule catalog (graftlint's RULES analog, for --list-rules)
GRAPH_RULES = {
    "graph-comm-missing": "a collective family the mode's comm model "
    "requires is absent from the lowered program",
    "graph-comm-forbidden": "a collective family the mode forbids "
    "appears (e.g. an all-gather in pure DP = param resharding)",
    "graph-comm-bytes": "required-collective byte total outside the "
    "analytic window (model-sized sync dropped or duplicated)",
    "graph-comm-in-loop": "a model-sized collective inside the local-"
    "step loop body — per-step sync in a mode whose tau knob exists "
    "to amortize it",
    "graph-replicated-param": "a tensor/expert-parallel mode whose "
    "params all lowered fully replicated (the TP win silently lost)",
    "graph-carry-reshard": "train-step carry returns with different "
    "shardings than it was passed in — every round pays a reshard",
    "graph-dtype-upcast": "a dot/convolution with f32 operands in a "
    "bf16 config — a smuggled upcast off the structural allowlist",
    "graph-undonated-carry": "train-step carry buffers not donated — "
    "the step holds two copies of params+slots",
    "graph-recompile-hazard": "re-lowering with a bumped iteration "
    "counter changed the StableHLO — the step recompiles every call",
    "graph-layout-transpose": "an nhwc mode lowered with interior "
    "rank-4 (image-blob) transposes in its StableHLO — the channels-"
    "last path exists to carry orientation through dimension_numbers, "
    "so a data-formatting transpose means a layer fell off it",
    "graph-manifest-missing": "no banked manifest for this mode "
    "(run `python -m sparknet_tpu.analysis graph --update`)",
    "graph-manifest-drift": "lowered contract differs from the banked "
    "manifest — regenerate with --update if the change is intended",
}

# source files whose edits invalidate the banked manifests (hashed into
# docs/graph_contracts/SOURCES.json by --update; the graftlint rule
# graph-manifest-fresh compares against it)
GRAPH_SOURCE_PATTERNS = (
    "sparknet_tpu/parallel/",
    "sparknet_tpu/serve/",
    "sparknet_tpu/loop/",
    "sparknet_tpu/models/zoo.py",
    "sparknet_tpu/analysis/graphcheck.py",
    "sparknet_tpu/analysis/comm_model.py",
    "sparknet_tpu/solvers/solver.py",
    "sparknet_tpu/solvers/updates.py",
    "sparknet_tpu/ops/pallas_kernels.py",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

# `%x = f32[2,3]{1,0} all-reduce(...)` / tuple results / async -start
# forms; -done forms never match (the kind must be followed by `(`)
_COLLECTIVE_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"all-to-all|reduce-scatter|collective-permute-start|"
    r"collective-permute)\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->")
_CALLEE_RE = re.compile(
    r"(?:body|condition|calls|to_apply|branch_computations)="
    r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_WHILE_BODY_RE = re.compile(r"\bwhile\([^)]*\).*?body=%?([\w.\-]+)")


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of an HLO result shape (handles tuples + scalars)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue  # token[] etc. — no payload bytes
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str  # normalized: -start folded into the base kind
    bytes: int
    computation: str
    in_loop: bool


def collective_census(hlo_text: str) -> list[CollectiveOp]:
    """Every collective in a post-SPMD HLO module, attributed to its
    computation and flagged when that computation is (transitively)
    reachable from a while-loop body — the static form of 'runs once
    per round' vs 'runs every local step'."""
    # pass 1: computation spans + call edges + while bodies
    comp_of_line: list[str] = []
    edges: dict[str, set[str]] = {}
    bodies: set[str] = set()
    current = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            current = m.group(1)
        comp_of_line.append(current)
        for em in _CALLEE_RE.finditer(line):
            for callee in em.group(1).split(","):
                edges.setdefault(current, set()).add(
                    callee.strip().lstrip("%"))
        wm = _WHILE_BODY_RE.search(line)
        if wm:
            bodies.add(wm.group(1))
    # pass 2: computations transitively reachable from loop bodies
    in_loop: set[str] = set()
    stack = list(bodies)
    while stack:
        c = stack.pop()
        if c in in_loop:
            continue
        in_loop.add(c)
        stack.extend(edges.get(c, ()))
    # pass 3: the collectives themselves
    ops: list[CollectiveOp] = []
    for i, line in enumerate(hlo_text.splitlines()):
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(2).replace("-start", "")
        ops.append(CollectiveOp(
            kind=kind,
            bytes=_shape_bytes(m.group(1)),
            computation=comp_of_line[i],
            in_loop=comp_of_line[i] in in_loop,
        ))
    return ops


def census_summary(ops: list[CollectiveOp]) -> dict:
    """{kind: {count, bytes, in_loop_count, in_loop_bytes}} with stable
    key order — the manifest's comm block."""
    out: dict[str, dict] = {}
    for kind in COLLECTIVE_KINDS:
        mine = [o for o in ops if o.kind == kind]
        if not mine:
            continue
        out[kind] = {
            "count": len(mine),
            "bytes": sum(o.bytes for o in mine),
            "in_loop_count": sum(1 for o in mine if o.in_loop),
            "in_loop_bytes": sum(o.bytes for o in mine if o.in_loop),
        }
    return out


_DOT_CONV_RE = re.compile(
    r"stablehlo\.(dot_general|convolution)\b[^\n]*?:\s*\(([^)]*)\)\s*->")

# `stablehlo.transpose %x, dims = [0, 3, 1, 2] : (tensor<8x32x32x3xf32>) ...`
_SHLO_TRANSPOSE_RE = re.compile(
    r"stablehlo\.transpose\b[^\n]*?dims = \[([\d, ]*)\][^\n]*?"
    r"tensor<([0-9x]+)x(\w+)>")
# HLO `%name = f32[8,3,32,32]{...} transpose(` / `copy(`
_HLO_FMT_RE = re.compile(r"=\s+(\w+)\[([\d,]*)\][^\s]*\s+(transpose|copy)\(")


def layout_census(stablehlo_text: str, hlo_text: str) -> dict:
    """Count data-formatting ops per module.

    StableHLO transposes split by rank: rank-4 operands are image-blob
    reorientations (the data-formatting tax the nhwc layout exists to
    erase); rank<=2 transposes are matmul weight flips that every
    layout emits.  The compiled-module counts record what the BACKEND's
    layout assignment adds on top (CPU here — backend-specific, banked
    as a drift-pinned baseline, not modeled)."""
    total = r4 = r4_elems = 0
    for m in _SHLO_TRANSPOSE_RE.finditer(stablehlo_text):
        total += 1
        dims = [d for d in m.group(1).replace(" ", "").split(",") if d]
        if len(dims) >= 4:
            r4 += 1
            n = 1
            for d in m.group(2).split("x"):
                n *= int(d)
            r4_elems += n
    hlo_t = hlo_t4 = hlo_c = 0
    for m in _HLO_FMT_RE.finditer(hlo_text):
        if m.group(3) == "copy":
            hlo_c += 1
            continue
        hlo_t += 1
        if len([d for d in m.group(2).split(",") if d]) >= 4:
            hlo_t4 += 1
    return {
        "stablehlo_transposes": total,
        "stablehlo_transposes_4d": r4,
        "stablehlo_transpose_4d_elems": r4_elems,
        "hlo_transposes": hlo_t,
        "hlo_transposes_4d": hlo_t4,
        "hlo_copies": hlo_c,
    }


def dtype_census(stablehlo_text: str) -> dict:
    """Count dot/conv ops by operand element type in a StableHLO
    module.  ``f32_ops`` lists (op, operand-types) for the offenders a
    bf16 config must not contain."""
    total = 0
    f32_ops: list[list[str]] = []
    for m in _DOT_CONV_RE.finditer(stablehlo_text):
        total += 1
        operand_types = m.group(2)
        if re.search(r"x?f32>", operand_types):
            f32_ops.append([m.group(1), operand_types.strip()[:120]])
    return {"dot_conv_total": total, "dot_conv_f32": len(f32_ops),
            "f32_ops": f32_ops}


# ---------------------------------------------------------------------------
# Tracing (the only part that touches jax — lazily)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Artifacts:
    """Everything :func:`audit_target` reads, all host-side text/flags —
    produced once per mode by :func:`trace_artifacts`."""

    stablehlo: str
    stablehlo_alt: str | None  # the bumped-iteration re-lower
    hlo: str  # post-SPMD compiled module
    donated: list  # per-arg list of (leaf_donated: list[bool])
    arg_leaf_bytes: list  # per-arg list of leaf byte sizes
    in_specs: list | None  # carry-leaf PartitionSpec strings (inputs)
    out_specs: list | None  # output-leaf PartitionSpec strings
    sharded_params: int = 0
    replicated_params: int = 0


def _pin_cpu_mesh(n_devices: int) -> None:
    """Force the virtual CPU mesh BEFORE any backend initializes: the
    env vars for this process and its children, the config update in
    case jax was already imported."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags += f" --xla_force_host_platform_device_count={n_devices}"
    elif int(m.group(1)) < n_devices:
        flags = flags.replace(
            m.group(0),
            f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = flags.strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    found = len(jax.devices())
    if found < n_devices:
        raise RuntimeError(
            f"graphcheck needs {n_devices} virtual CPU devices, found "
            f"{found}: a backend initialized before graphcheck could "
            "force the count — launch with XLA_FLAGS=--xla_force_host_"
            f"platform_device_count={n_devices} JAX_PLATFORMS=cpu")


def trace_artifacts(target) -> Artifacts:
    """Lower + CPU-compile one mode's step; no execution."""
    import jax
    from jax.sharding import PartitionSpec as P

    with target.trace_context():
        lowered = target.fn.lower(*target.args)
        stablehlo = lowered.as_text()
        alt = None
        if target.alt_args is not None:
            alt = target.fn.lower(*target.alt_args).as_text()
        compiled = lowered.compile()
    hlo = compiled.as_text()

    leaves = jax.tree_util.tree_leaves
    # args_info is an (args, kwargs) pair mirroring the call signature
    donated = [[bool(a.donated) for a in leaves(info)]
               for info in lowered.args_info[0]]
    def leaf_bytes(l):
        # typed PRNG-key arrays raise on .nbytes — they are never part
        # of a carry, so 0 is the right answer for them
        try:
            return int(l.nbytes)
        except Exception:
            return 0

    arg_leaf_bytes = [[leaf_bytes(l) for l in leaves(arg)]
                      for arg in target.args]

    def spec_str(s):
        # compare PartitionSpecs only: single-device shardings (solo
        # mode) and other sharding types have no spec to audit
        spec = getattr(s, "spec", None)
        return None if spec is None else str(spec)

    # input shardings come from the placed example arrays themselves —
    # compiled.input_shardings cannot be positionally aligned because
    # jit prunes unused args (a fixed-lr step never reads ``it``)
    in_specs = [spec_str(getattr(l, "sharding", None))
                for argnum in target.carry_argnums
                for l in leaves(target.args[argnum])]
    out_specs = None
    try:
        out_specs = [spec_str(s)
                     for s in leaves(compiled.output_shardings)]
    except Exception:  # pragma: no cover - introspection API drift
        pass

    sharded = replicated = 0
    if target.carry_argnums:
        empty = str(P())
        for l in leaves(target.args[0]):
            s = spec_str(getattr(l, "sharding", None))
            if s is None:
                continue
            if s == empty:
                replicated += 1
            else:
                sharded += 1
    return Artifacts(
        stablehlo=stablehlo, stablehlo_alt=alt, hlo=hlo,
        donated=donated, arg_leaf_bytes=arg_leaf_bytes,
        in_specs=in_specs, out_specs=out_specs,
        sharded_params=sharded, replicated_params=replicated,
    )


# ---------------------------------------------------------------------------
# The audits
# ---------------------------------------------------------------------------


def audit_target(target, art: Artifacts,
                 exp: CommExpectation) -> tuple[list[dict], dict]:
    """Run the four contract families over one mode's artifacts.

    Returns ``(problems, contract)``: problems as ``{rule, message}``
    dicts (the caller attaches path/suppression), and the manifest
    ``contract`` block future runs diff against.
    """
    problems: list[dict] = []
    ops = collective_census(art.hlo)
    comm = census_summary(ops)

    # -- 1. comm budget ----------------------------------------------------
    for kind, window in exp.required.items():
        have = comm.get(kind)
        if have is None:
            problems.append({
                "rule": "graph-comm-missing",
                "message": f"expected {kind} collective(s) absent from "
                           f"the lowered program ({exp.note})",
            })
            continue
        if window is not None:
            lo, hi = window
            if not (lo <= have["bytes"] <= hi):
                problems.append({
                    "rule": "graph-comm-bytes",
                    "message": f"{kind} moves {have['bytes']:,} bytes; "
                               f"the comm model allows [{lo:,}, {hi:,}] "
                               f"({exp.note})",
                })
    for kind in exp.forbidden:
        if kind in comm:
            problems.append({
                "rule": "graph-comm-forbidden",
                "message": f"{comm[kind]['count']} {kind} op(s) in a "
                           f"mode that forbids them ({exp.note})",
            })
    if not exp.loop_collectives_ok:
        big_in_loop = [o for o in ops
                       if o.in_loop and o.bytes > exp.loop_bytes_floor]
        if big_in_loop:
            worst = max(big_in_loop, key=lambda o: o.bytes)
            problems.append({
                "rule": "graph-comm-in-loop",
                "message": f"{len(big_in_loop)} collective(s) over "
                           f"{exp.loop_bytes_floor} B inside the local-"
                           f"step loop (largest: {worst.kind} "
                           f"{worst.bytes:,} B in %{worst.computation}) "
                           "— per-step sync defeats the tau knob",
            })

    # -- 2. sharding audit -------------------------------------------------
    if target.expects_sharded_params and art.in_specs is not None \
            and art.sharded_params == 0:
        problems.append({
            "rule": "graph-replicated-param",
            "message": "mode declares tensor/expert parallelism but "
                       "every param lowered fully replicated — the "
                       "sharding rules matched nothing",
        })
    carry_reshards = 0
    if art.in_specs and art.out_specs is not None \
            and target.carry_out_leaves:
        n = target.carry_out_leaves
        for i, (si, so) in enumerate(zip(art.in_specs[:n],
                                         art.out_specs[:n])):
            if si is None or so is None:
                continue
            if si != so:
                carry_reshards += 1
                if carry_reshards == 1:
                    problems.append({
                        "rule": "graph-carry-reshard",
                        "message": f"carry leaf {i} returns as {so} but "
                                   f"was passed as {si} — every round "
                                   "pays a reshard",
                    })

    # -- 3. dtype audit ----------------------------------------------------
    dt = None
    if target.meta.get("dtype") == "bf16":
        dt = dtype_census(art.stablehlo)
        if dt["dot_conv_f32"]:
            first = dt["f32_ops"][0]
            problems.append({
                "rule": "graph-dtype-upcast",
                "message": f"{dt['dot_conv_f32']} of "
                           f"{dt['dot_conv_total']} dot/conv op(s) take "
                           f"f32 operands in a bf16 config (first: "
                           f"{first[0]} {first[1]}) — a smuggled upcast "
                           "off the structural allowlist (non-matmul "
                           "f32 like softmax/BN stats/loss is fine; "
                           "f32 matmuls burn the 4x MXU rate)",
            })
        dt = {k: v for k, v in dt.items() if k != "f32_ops"}

    # -- 5. layout census --------------------------------------------------
    lay = layout_census(art.stablehlo, art.hlo)
    lay["layout"] = target.meta.get("layout", "nchw")
    if lay["layout"] == "nhwc" and lay["stablehlo_transposes_4d"]:
        problems.append({
            "rule": "graph-layout-transpose",
            "message": f"{lay['stablehlo_transposes_4d']} rank-4 "
                       f"transpose(s) ({lay['stablehlo_transpose_4d_elems']:,}"
                       " elements) in the nhwc StableHLO — a layer is "
                       "reorienting image blobs instead of riding "
                       "dimension_numbers (the data-formatting tax the "
                       "channels-last path exists to erase)",
        })

    # -- 4. donation / recompile -------------------------------------------
    undonated_bytes = 0
    undonated_leaves = 0
    for argnum in target.carry_argnums:
        for don, nbytes in zip(art.donated[argnum],
                               art.arg_leaf_bytes[argnum]):
            if not don:
                undonated_leaves += 1
                undonated_bytes += nbytes
    if undonated_leaves:
        problems.append({
            "rule": "graph-undonated-carry",
            "message": f"{undonated_leaves} carry leaf(s) totalling "
                       f"{undonated_bytes:,} B are not donated — the "
                       "step holds two copies of that state in device "
                       "memory",
        })
    recompiled = False
    if art.stablehlo_alt is not None:
        h0 = hashlib.sha256(art.stablehlo.encode()).hexdigest()
        h1 = hashlib.sha256(art.stablehlo_alt.encode()).hexdigest()
        if h0 != h1:
            recompiled = True
            problems.append({
                "rule": "graph-recompile-hazard",
                "message": "re-lowering with the iteration counter "
                           "bumped changed the StableHLO — a Python "
                           "value is baked into the graph and the step "
                           "recompiles every call",
            })

    contract = {
        "comm": comm,
        "layout": lay,
        "sharding": {
            "params_sharded": art.sharded_params,
            "params_replicated": art.replicated_params,
            "carry_resharded": carry_reshards,
        },
        "dtype": dt,
        "donation": {
            "carry_leaves": sum(
                len(art.donated[a]) for a in target.carry_argnums),
            "undonated_leaves": undonated_leaves,
            "undonated_bytes": undonated_bytes,
        },
        "recompile_hazard": recompiled,
    }
    return problems, contract


# ---------------------------------------------------------------------------
# Manifests
# ---------------------------------------------------------------------------


def manifest_path(mode: str, banked_dir: str | None = None) -> str:
    return os.path.join(banked_dir or MANIFEST_DIR, f"{mode}.json")


def _build_manifest(target, contract: dict, exp: CommExpectation,
                    art: Artifacts) -> dict:
    import jax

    return {
        "mode": target.name,
        "meta": target.meta,
        "contract": contract,
        "model": {
            "param_bytes": target.param_bytes,
            "state_bytes": target.state_bytes,
            "expected": {
                "required": {k: list(v) if v else None
                             for k, v in exp.required.items()},
                "forbidden": list(exp.forbidden),
                "loop_collectives_ok": exp.loop_collectives_ok,
                "note": exp.note,
            },
        },
        # informational only — excluded from the drift diff (the hash
        # moves with jax/XLA versions; the contract block should not)
        "stablehlo_sha256": hashlib.sha256(
            art.stablehlo.encode()).hexdigest(),
        "generated_with": {"jax": jax.__version__},
        "allow": {},
    }


def _diff_contract(banked: dict, fresh: dict, prefix: str = "") -> list[str]:
    """Human-readable leaf diffs between two contract blocks."""
    out: list[str] = []
    keys = sorted(set(banked) | set(fresh))
    for k in keys:
        b, f = banked.get(k), fresh.get(k)
        at = f"{prefix}{k}"
        if isinstance(b, dict) and isinstance(f, dict):
            out.extend(_diff_contract(b, f, at + "."))
        elif b != f:
            out.append(f"{at}: banked {b!r} -> now {f!r}")
    return out


def sources_fingerprint(repo: str | None = None) -> dict:
    """sha256 per graph-contract source file (the freshness record the
    ``graph-manifest-fresh`` lint rule checks edits against)."""
    repo = repo or _REPO
    files: list[str] = []
    for sub in ("parallel", "serve", "loop"):
        pdir = os.path.join(repo, "sparknet_tpu", sub)
        if os.path.isdir(pdir):
            files += [os.path.join(pdir, f)
                      for f in sorted(os.listdir(pdir))
                      if f.endswith(".py")]
    for rel in ("sparknet_tpu/models/zoo.py",
                "sparknet_tpu/ops/layout.py",
                "sparknet_tpu/analysis/graphcheck.py",
                "sparknet_tpu/analysis/comm_model.py",
                "sparknet_tpu/solvers/solver.py",
                "sparknet_tpu/solvers/updates.py",
                "sparknet_tpu/ops/pallas_kernels.py"):
        p = os.path.join(repo, *rel.split("/"))
        if os.path.exists(p):
            files.append(p)
    out = {}
    for p in files:
        with open(p, encoding="utf-8") as f:
            digest = hashlib.sha256(f.read().encode("utf-8")).hexdigest()
        out[os.path.relpath(p, repo).replace(os.sep, "/")] = digest
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _check_mode(name: str, banked_dir: str, update: bool,
                n_devices: int) -> tuple[list[Finding], dict]:
    from sparknet_tpu.parallel.modes import build_target

    target = build_target(name, n_devices)
    exp = expected_comm(name, param_bytes=target.param_bytes,
                        state_bytes=target.state_bytes)
    art = trace_artifacts(target)
    problems, contract = audit_target(target, art, exp)
    manifest = _build_manifest(target, contract, exp, art)
    mpath = manifest_path(name, banked_dir)
    rel = os.path.relpath(mpath, _REPO) if mpath.startswith(_REPO) else mpath

    allow: dict = {}
    if os.path.exists(mpath):
        with open(mpath, encoding="utf-8") as f:
            banked = json.load(f)
        allow = banked.get("allow", {}) or {}
        manifest["allow"] = allow
        if not update:
            drift = _diff_contract(banked.get("contract", {}), contract)
            if drift:
                problems.append({
                    "rule": "graph-manifest-drift",
                    "message": f"lowered contract differs from the "
                               f"banked manifest ({len(drift)} field(s): "
                               + "; ".join(drift[:4])
                               + ("; ..." if len(drift) > 4 else "")
                               + ") — rerun with --update if intended",
                })
    elif not update:
        problems.append({
            "rule": "graph-manifest-missing",
            "message": "no banked manifest — run "
                       "`python -m sparknet_tpu.analysis graph --update`",
        })

    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in allow)
        for p in problems
    ]
    return findings, manifest


def run_graphcheck(modes: list[str] | None = None, *, update: bool = False,
                   banked_dir: str | None = None, n_devices: int = 8,
                   progress=None) -> tuple[list[Finding], dict]:
    """Lower + audit ``modes`` (default: all registered).

    Returns ``(findings, manifests)``.  With ``update=True``, banked
    manifests (and the SOURCES.json freshness fingerprint, when running
    over the full mode set against the default directory) are
    rewritten instead of diffed."""
    _pin_cpu_mesh(n_devices)

    from sparknet_tpu.parallel.modes import list_modes

    all_modes = list_modes()
    modes = list(modes) if modes else all_modes
    unknown = [m for m in modes if m not in all_modes]
    if unknown:
        raise KeyError(f"unknown mode(s): {', '.join(unknown)} "
                       f"(known: {', '.join(all_modes)})")
    banked = banked_dir or MANIFEST_DIR
    findings: list[Finding] = []
    manifests: dict[str, dict] = {}
    for name in modes:
        if progress:
            progress(name)
        f, manifest = _check_mode(name, banked, update, n_devices)
        findings.extend(f)
        manifests[name] = manifest
        if update:
            os.makedirs(banked, exist_ok=True)
            with open(manifest_path(name, banked), "w",
                      encoding="utf-8") as fh:
                json.dump(manifest, fh, indent=1, sort_keys=True)
                fh.write("\n")
    if update and set(modes) == set(all_modes) and banked == MANIFEST_DIR:
        with open(os.path.join(banked, "SOURCES.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(sources_fingerprint(), fh, indent=1, sort_keys=True)
            fh.write("\n")
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, manifests


def iter_rules() -> Iterator[tuple[str, str]]:
    yield from GRAPH_RULES.items()
