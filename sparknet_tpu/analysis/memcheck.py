"""memcheck: static HBM/VMEM footprint analysis of the lowered modes.

The third analysis engine, beside graftlint (source contracts) and
graphcheck (graph contracts): where graphcheck audits what the compiled
program SAYS ON THE WIRE, this audits what it HOLDS IN MEMORY.  Every
parallel mode's train step is traced and CPU-compiled on the virtual
8-device mesh (zero chip time), and
two independent estimators of peak per-device HBM residency are
cross-checked:

1. the **analytic model** (``mem_model.py``): a liveness walk over the
   traced jaxpr — inputs resolved to per-device bytes through their
   actual shardings, donation credited only where the lowering actually
   established aliasing (``lowered.args_info``), scan/while carry and
   body bytes accounted, shard_map bodies walked at their native
   per-shard shapes;
2. **XLA's own buffer assignment**: ``compiled.memory_analysis()`` on
   the same lowering graphcheck performs (argument + output + temp -
   alias).

Agreement is two-sided (mem_model docstring): residency must match
within ``RESIDENCY_TOL_BYTES`` (same physical buffers — a mismatch is
a donation/sharding accounting bug), peak within
``PEAK_RATIO_WINDOW`` (the estimators bracket the backend: the walk
models TPU-style fusion, the CPU cross-check materializes im2col
conv scratch — modeled per conv eqn for the cross-check figure only).
Results are banked as a manifest family in ``docs/mem_contracts/`` and
drift-diffed on every run, exactly like the graph contracts.

On top of the per-mode model:

* a **batch-fit solver** (``--fit``): per zoo family x dtype, two
  abstract traces (``jax.eval_shape`` init — no arrays materialize)
  pin the affine footprint model ``bytes(B) = c0 + c1*B``, solved for
  the max safe batch per parallel mode with the TP/SP/gpipe per-device
  divisors from ``parallel/sharding.py``; banked as
  ``docs/mem_contracts/batch_fit.json`` and consumed by the window
  runner's queue pre-flight (a predicted-OOM job never burns a dial);
* a **static VMEM audit**: each pallas kernel's analytic VMEM bound
  (``ops/pallas_kernels.py`` — the formulas live beside the BlockSpecs
  they describe) checked against the v5e budget.

Import contract: stdlib-only at import; jax loads lazily inside the
run functions after the CPU platform is pinned.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Iterator

from sparknet_tpu.analysis.core import Finding
from sparknet_tpu.analysis.graphcheck import (
    _REPO,
    _diff_contract,
    _pin_cpu_mesh,
)
from sparknet_tpu.analysis import mem_model
from sparknet_tpu.analysis.mem_model import (
    MemEqn,
    MemProgram,
    PEAK_RATIO_WINDOW,
    RESIDENCY_TOL_BYTES,
    V5E_HBM_BYTES,
    V5E_VMEM_BYTES,
    HBM_USABLE_FRAC,
    peak_residency,
)

__all__ = [
    "MEM_RULES",
    "MEM_SOURCE_PATTERNS",
    "MANIFEST_DIR",
    "FIT_TABLE_PATH",
    "extract_program",
    "trace_mem",
    "audit_mem",
    "run_memcheck",
    "run_batch_fit",
    "run_vmem_audit",
    "sources_fingerprint",
    "iter_rules",
]

MANIFEST_DIR = os.path.join(_REPO, "docs", "mem_contracts")
FIT_TABLE_PATH = os.path.join(MANIFEST_DIR, "batch_fit.json")

MEM_RULES = {
    "mem-residency-mismatch": "analytic arg/output/donation accounting "
    "disagrees with XLA's buffer assignment beyond the tolerance — the "
    "class of bug that silently doubles params+slots in HBM",
    "mem-estimator-divergence": "analytic peak-HBM estimate outside the "
    "documented ratio window of XLA's memory_analysis() — a unit error, "
    "dropped carry, or double-counted model",
    "mem-hbm-exceeded": "a mode's predicted per-device footprint "
    "exceeds the usable v5e HBM — the job would OOM, burning a healthy "
    "window for nothing",
    "mem-vmem-exceeded": "a pallas kernel's static VMEM bound exceeds "
    "the v5e VMEM budget — the kernel cannot fit its grid cell",
    "mem-fit-infeasible": "a zoo family's constant footprint term "
    "(params+slots) alone exceeds the usable HBM in some mode",
    "mem-manifest-missing": "no banked memory manifest for this mode "
    "(run `python -m sparknet_tpu.analysis mem --update`)",
    "mem-manifest-drift": "memory contract differs from the banked "
    "manifest — regenerate with --update if the change is intended",
}

# source files whose edits invalidate the banked memory manifests
# (hashed into docs/mem_contracts/SOURCES.json by --update; the
# graftlint rule mem-manifest-fresh compares edits against it)
MEM_SOURCE_PATTERNS = (
    "sparknet_tpu/parallel/",
    "sparknet_tpu/serve/",
    "sparknet_tpu/loop/",
    "sparknet_tpu/models/zoo.py",
    "sparknet_tpu/ops/pallas_kernels.py",
    "sparknet_tpu/ops/layout.py",
    "sparknet_tpu/solvers/solver.py",
    "sparknet_tpu/solvers/updates.py",
    "sparknet_tpu/analysis/memcheck.py",
    "sparknet_tpu/analysis/mem_model.py",
)

# families the batch-fit solver prices: every benchmarkable zoo family
# (models.BENCH_CROPS) plus the small test vehicles; the transformer
# family gives the sequence-parallel divisor a real row
FIT_DTYPES = ("f32", "bf16")
FIT_PROBE_BATCHES = (8, 16)


# ---------------------------------------------------------------------------
# jaxpr -> MemProgram extraction (jax-touching, called lazily)
# ---------------------------------------------------------------------------

_INLINE_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")
_INLINE_PRIMS = ("pjit", "closed_call", "remat", "checkpoint",
                 "custom_jvp_call", "custom_vjp_call",
                 "custom_vjp_call_jaxpr")


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:  # tokens, typed PRNG keys
        return 0


def _conv_scratch(eqn) -> int:
    """im2col patch-buffer bytes for one convolution eqn — the CPU
    backend's materialization the cross-check figure must model (XLA:TPU
    tiles convs through VMEM instead; the TPU-facing estimate excludes
    this).  Generic over forward/input-grad/filter-grad convs: patches
    hold (output spatial positions) x (kernel footprint) elements per
    group."""
    if eqn.primitive.name != "conv_general_dilated":
        return 0
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params.get("dimension_numbers")
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    groups *= int(eqn.params.get("batch_group_count", 1) or 1)
    try:
        cout = out.shape[dn.out_spec[1]]
        return (int(out.size // cout) * int(rhs.size // cout) * groups
                * out.dtype.itemsize)
    except Exception:
        return 0


class _Extractor:
    """Recursive jaxpr walk producing MemEqn records.

    ``batch``/``width``: under GSPMD (no shard_map) intermediate avals
    are global; any buffer whose leading two dims carry the global
    batch is counted at 1/width — the batch-sharding heuristic (grads
    and other param-shaped temps stay full-size, correctly: they are
    replicated per device).  shard_map bodies are walked at their
    native per-shard shapes, no heuristic needed.
    """

    def __init__(self, batch: int = 0, width: int = 1):
        self.eqns: list = []
        self.sizes: dict = {}
        self.n = 0
        self.batch = batch
        self.width = width

    def _div_bytes(self, aval) -> int:
        b = _aval_bytes(aval)
        shape = getattr(aval, "shape", None)
        if self.width > 1 and self.batch and shape:
            if any(d == self.batch for d in shape[:2]):
                return b // self.width
        return b

    def name(self, env: dict, v) -> str | None:
        from jax.extend import core

        if isinstance(v, core.Literal):
            return None
        if v not in env:
            self.n += 1
            nm = f"v{self.n}"
            env[v] = nm
            self.sizes[nm] = self._div_bytes(v.aval)
        return env[v]

    def _batch_like(self, eqn) -> bool:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape and any(d == self.batch for d in shape[:2]):
                return True
        return False

    def _sub_peaks(self, cj, per_shard: bool = False) -> tuple:
        """(tpu_extra, scratch_extra) of a sub-jaxpr body, as transient
        bytes beyond its own inputs (the caller's live set already
        carries those)."""
        inner = cj.jaxpr if hasattr(cj, "jaxpr") else cj
        sub = _Extractor(0 if per_shard else self.batch,
                         1 if per_shard else self.width)
        env: dict = {}
        ins = [sub.name(env, v)
               for v in list(inner.invars) + list(inner.constvars)]
        sub.walk(inner, env)
        outs = [sub.name(env, v) for v in inner.outvars
                if sub.name(env, v) is not None]
        prog = MemProgram(eqns=sub.eqns, sizes=sub.sizes,
                          inputs=[i for i in ins if i], outputs=outs)
        base = prog.input_bytes()
        tpu = max(0, peak_residency(prog)["peak_bytes"] - base)
        xc = max(0, peak_residency(prog, xcheck=True)["peak_bytes"] - base)
        return tpu, max(0, xc - tpu)

    def walk(self, jaxpr, env: dict) -> None:
        from jax.extend import core

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            cj = None
            for k in _INLINE_KEYS:
                if k in eqn.params:
                    cj = eqn.params[k]
                    break
            if prim in _INLINE_PRIMS and cj is not None:
                inner = cj.jaxpr if hasattr(cj, "jaxpr") else cj
                reads = [self.name(env, v) for v in eqn.invars]
                for iv, r in zip(inner.invars, reads):
                    if r is not None:
                        env[iv] = r
                    else:
                        self.name(env, iv)
                for cv in inner.constvars:
                    self.name(env, cv)
                self.walk(inner, env)
                for ov, outer in zip(inner.outvars, eqn.outvars):
                    if isinstance(ov, core.Literal):
                        self.name(env, outer)
                    else:
                        env[outer] = env[ov]
                continue

            extra = scratch = 0
            if prim == "shard_map" and cj is not None:
                # body avals are already per-shard — walk natively
                extra, scratch = self._sub_peaks(cj, per_shard=True)
            elif prim == "scan" and cj is not None:
                extra, scratch = self._sub_peaks(cj)
            elif prim == "while":
                pairs = [self._sub_peaks(eqn.params["body_jaxpr"]),
                         self._sub_peaks(eqn.params["cond_jaxpr"])]
                extra = max(p[0] for p in pairs)
                scratch = max(p[0] + p[1] for p in pairs) - extra
            elif prim == "cond":
                pairs = [self._sub_peaks(b)
                         for b in eqn.params.get("branches", ())] or [(0, 0)]
                extra = max(p[0] for p in pairs)
                scratch = max(p[0] + p[1] for p in pairs) - extra
            else:
                scratch = _conv_scratch(eqn)
                if scratch and self.width > 1 and self.batch \
                        and self._batch_like(eqn):
                    scratch //= self.width

            reads = tuple(r for r in (self.name(env, v)
                                      for v in eqn.invars) if r is not None)
            writes = tuple(w for w in (self.name(env, v)
                                       for v in eqn.outvars) if w is not None)
            self.eqns.append(MemEqn(reads=reads, writes=writes,
                                    extra=extra, scratch=scratch))


def _shard_leaf_bytes(leaf) -> int:
    """Per-device bytes of a placed array (its shard of the sharding it
    actually carries); plain host arrays fall back to full size."""
    import numpy as np

    try:
        shape = leaf.sharding.shard_shape(leaf.shape)
        return int(np.prod(shape)) * leaf.dtype.itemsize
    except Exception:
        try:
            return int(leaf.nbytes)
        except Exception:
            return 0


def extract_program(closed_jaxpr, *, batch: int = 0, width: int = 1,
                    input_bytes: list | None = None,
                    output_bytes: list | None = None,
                    donated_flags: list | None = None) -> MemProgram:
    """Reduce a ClosedJaxpr to the stdlib MemProgram the liveness walk
    consumes.  ``input_bytes``/``output_bytes`` override the flat
    invar/outvar sizes with per-device figures resolved from actual
    shardings (constvars keep their aval sizes); ``donated_flags``
    marks which flat inputs the lowering actually donated."""
    ex = _Extractor(batch=batch, width=width)
    env: dict = {}
    const_names = [ex.name(env, v) for v in closed_jaxpr.jaxpr.constvars]
    in_names = [ex.name(env, v) for v in closed_jaxpr.jaxpr.invars]
    ex.walk(closed_jaxpr.jaxpr, env)
    out_names = [ex.name(env, v) for v in closed_jaxpr.jaxpr.outvars]
    if input_bytes is not None:
        for nm, b in zip(in_names, input_bytes):
            if nm is not None:
                ex.sizes[nm] = b
    if output_bytes is not None:
        for nm, b in zip(out_names, output_bytes):
            if nm is not None:
                ex.sizes[nm] = b
    donated = set()
    if donated_flags is not None:
        for nm, d in zip(in_names, donated_flags):
            if d and nm is not None:
                donated.add(nm)
    inputs = [n for n in const_names + in_names if n is not None]
    outputs = [n for n in out_names if n is not None]
    return MemProgram(eqns=ex.eqns, sizes=ex.sizes, inputs=inputs,
                      outputs=outputs, donated=frozenset(donated))


# ---------------------------------------------------------------------------
# Tracing one mode (jax-touching)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MemArtifacts:
    program: MemProgram
    xla: dict  # memory_analysis fields + derived peak


def trace_mem(target) -> MemArtifacts:
    """Trace + CPU-compile one mode's step; no execution.  The compile
    is the same one graphcheck performs — XLA's buffer assignment is
    the second estimator, so there is no cheaper honest source."""
    import jax.tree_util as jtu

    with target.trace_context():
        traced = target.fn.trace(*target.args)
        lowered = target.fn.lower(*target.args)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    xla = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    xla["peak_bytes"] = (xla["argument_bytes"] + xla["output_bytes"]
                        + xla["temp_bytes"] - xla["alias_bytes"])
    xla["residency_bytes"] = (xla["argument_bytes"] + xla["output_bytes"]
                             - xla["alias_bytes"])

    mesh = target.meta.get("mesh", {}) or {}
    width = 1
    for v in mesh.values():
        width *= int(v)
    flat_leaves = [l for a in target.args for l in jtu.tree_leaves(a)]
    input_bytes = [_shard_leaf_bytes(l) for l in flat_leaves]
    donated_flags: list = []
    for info in lowered.args_info[0]:
        donated_flags.extend(bool(x.donated) for x in jtu.tree_leaves(info))

    closed = traced.jaxpr
    out_avals = [getattr(v, "aval", None) for v in closed.jaxpr.outvars]
    output_bytes = [_aval_bytes(a) if a is not None else 0
                    for a in out_avals]
    try:
        out_shardings = jtu.tree_leaves(compiled.output_shardings)
        if len(out_shardings) == len(out_avals):
            import numpy as np

            for i, (aval, s) in enumerate(zip(out_avals, out_shardings)):
                try:
                    shape = s.shard_shape(aval.shape)
                    output_bytes[i] = (int(np.prod(shape))
                                      * aval.dtype.itemsize)
                except Exception:
                    pass
    except Exception:  # pragma: no cover - introspection API drift
        pass

    program = extract_program(
        closed, batch=int(target.meta.get("batch", 0) or 0), width=width,
        input_bytes=input_bytes, output_bytes=output_bytes,
        donated_flags=donated_flags)
    return MemArtifacts(program=program, xla=xla)


# ---------------------------------------------------------------------------
# The audit
# ---------------------------------------------------------------------------


def audit_mem(target, art: MemArtifacts,
              hbm_bytes: int = V5E_HBM_BYTES) -> tuple:
    """(problems, contract) for one mode — the memcheck analog of
    graphcheck.audit_target."""
    problems: list = []
    analytic = peak_residency(art.program)
    xcheck = peak_residency(art.program, xcheck=True)
    xla = art.xla

    res_delta = abs(analytic["residency_bytes"] - xla["residency_bytes"])
    if res_delta > RESIDENCY_TOL_BYTES:
        problems.append({
            "rule": "mem-residency-mismatch",
            "message": f"analytic residency {analytic['residency_bytes']:,}"
                       f" B vs XLA {xla['residency_bytes']:,} B "
                       f"(delta {res_delta:,} B > {RESIDENCY_TOL_BYTES:,}) "
                       "— arg/output/donation accounting disagrees with "
                       "the compiler's buffer assignment",
        })

    ratio = xcheck["peak_bytes"] / max(1, xla["peak_bytes"])
    lo, hi = PEAK_RATIO_WINDOW
    if not (lo <= ratio <= hi):
        problems.append({
            "rule": "mem-estimator-divergence",
            "message": f"analytic peak {xcheck['peak_bytes']:,} B is "
                       f"{ratio:.2f}x XLA's {xla['peak_bytes']:,} B — "
                       f"outside the documented [{lo}, {hi}] window",
        })

    budget = int(hbm_bytes * HBM_USABLE_FRAC)
    worst = max(analytic["peak_bytes"], xla["peak_bytes"])
    if worst > budget:
        problems.append({
            "rule": "mem-hbm-exceeded",
            "message": f"predicted per-device peak {worst:,} B exceeds "
                       f"the usable v5e HBM budget {budget:,} B — this "
                       "step would OOM on chip",
        })

    contract = {
        "analytic": {
            "peak_bytes": analytic["peak_bytes"],
            "residency_bytes": analytic["residency_bytes"],
            "temp_bytes": analytic["temp_bytes"],
            "xcheck_peak_bytes": xcheck["peak_bytes"],
        },
        "xla": xla,
        "peak_ratio": round(ratio, 3),
        "residency_delta_bytes": res_delta,
        "donated_bytes": art.program.donated_bytes(),
        "n_eqns": len(art.program.eqns),
    }
    return problems, contract


# ---------------------------------------------------------------------------
# VMEM audit (pallas kernels; formulas live beside the BlockSpecs)
# ---------------------------------------------------------------------------


def run_vmem_audit() -> tuple:
    """(problems, contract): every registered pallas-kernel audit point
    vs the v5e VMEM budget.  Pure arithmetic — the bound functions in
    ops/pallas_kernels.py read the kernels' actual tiling constants, so
    a retuned _TILE/_BQ/_BK moves the bound (and trips the manifest
    drift) automatically."""
    from sparknet_tpu.ops.pallas_kernels import vmem_audit_points

    problems: list = []
    points = []
    for p in vmem_audit_points():
        entry = dict(p)
        entry["budget_bytes"] = V5E_VMEM_BYTES
        entry["fits"] = p["bytes"] <= V5E_VMEM_BYTES
        entry["planning_headroom_bytes"] = (
            mem_model.VMEM_PLANNING_BYTES - p["bytes"])
        points.append(entry)
        if not entry["fits"]:
            problems.append({
                "rule": "mem-vmem-exceeded",
                "message": f"pallas kernel {p['kernel']!r} ({p['note']}) "
                           f"needs {p['bytes']:,} B of VMEM; the v5e "
                           f"budget is {V5E_VMEM_BYTES:,} B",
            })
    return problems, {"points": points}


# ---------------------------------------------------------------------------
# Manifests
# ---------------------------------------------------------------------------


def manifest_path(mode: str, banked_dir: str | None = None) -> str:
    return os.path.join(banked_dir or MANIFEST_DIR, f"{mode}.json")


def sources_fingerprint(repo: str | None = None) -> dict:
    """sha256 per memory-contract source file (the freshness record the
    ``mem-manifest-fresh`` lint rule checks edits against)."""
    repo = repo or _REPO
    files: list = []
    for pat in MEM_SOURCE_PATTERNS:
        p = os.path.join(repo, *pat.split("/"))
        if pat.endswith("/"):
            if os.path.isdir(p):
                files += [os.path.join(p, f) for f in sorted(os.listdir(p))
                          if f.endswith(".py")]
        elif os.path.exists(p):
            files.append(p)
    out = {}
    for p in files:
        with open(p, encoding="utf-8") as f:
            digest = hashlib.sha256(f.read().encode("utf-8")).hexdigest()
        out[os.path.relpath(p, repo).replace(os.sep, "/")] = digest
    return out


def _check_mode(name: str, banked_dir: str, update: bool,
                n_devices: int) -> tuple:
    from sparknet_tpu.parallel.modes import build_target

    if name == "kernels":
        problems, contract = run_vmem_audit()
        manifest = {"mode": "kernels", "contract": contract, "allow": {}}
    else:
        target = build_target(name, n_devices)
        art = trace_mem(target)
        problems, contract = audit_mem(target, art)
        manifest = {
            "mode": name,
            "meta": target.meta,
            "contract": contract,
            "model": {"param_bytes": target.param_bytes,
                      "state_bytes": target.state_bytes},
            "tolerance": {
                "residency_tol_bytes": RESIDENCY_TOL_BYTES,
                "peak_ratio_window": list(PEAK_RATIO_WINDOW),
            },
            "allow": {},
        }

    mpath = manifest_path(name, banked_dir)
    rel = os.path.relpath(mpath, _REPO) if mpath.startswith(_REPO) else mpath
    allow: dict = {}
    if os.path.exists(mpath):
        with open(mpath, encoding="utf-8") as f:
            banked = json.load(f)
        allow = banked.get("allow", {}) or {}
        manifest["allow"] = allow
        if not update:
            drift = _diff_contract(banked.get("contract", {}),
                                   manifest["contract"])
            if drift:
                problems.append({
                    "rule": "mem-manifest-drift",
                    "message": f"memory contract differs from the banked "
                               f"manifest ({len(drift)} field(s): "
                               + "; ".join(drift[:4])
                               + ("; ..." if len(drift) > 4 else "")
                               + ") — rerun with --update if intended",
                })
    elif not update:
        problems.append({
            "rule": "mem-manifest-missing",
            "message": "no banked memory manifest — run "
                       "`python -m sparknet_tpu.analysis mem --update`",
        })

    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in allow)
        for p in problems
    ]
    return findings, manifest


def run_memcheck(modes: list | None = None, *, update: bool = False,
                 banked_dir: str | None = None, n_devices: int = 8,
                 progress=None) -> tuple:
    """Trace + audit ``modes`` (default: all registered parallel modes
    plus the ``kernels`` VMEM audit).  Returns ``(findings,
    manifests)``; with ``update=True`` the banked manifests (and
    SOURCES.json on a full default-dir run) are rewritten."""
    _pin_cpu_mesh(n_devices)

    from sparknet_tpu.parallel.modes import list_modes

    all_modes = list_modes() + ["kernels"]
    modes = list(modes) if modes else all_modes
    unknown = [m for m in modes if m not in all_modes]
    if unknown:
        raise KeyError(f"unknown mode(s): {', '.join(unknown)} "
                       f"(known: {', '.join(all_modes)})")
    banked = banked_dir or MANIFEST_DIR
    findings: list = []
    manifests: dict = {}
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


# ---------------------------------------------------------------------------
# Batch-fit solver
# ---------------------------------------------------------------------------


def _fit_family_names() -> list:
    from sparknet_tpu.models import BENCH_CROPS

    return sorted(BENCH_CROPS) + ["cifar10_quick", "transformer"]


def _family_net(family: str, batch: int):
    """(net_param Message, solver_cfg, feed_dtypes) for one fit family."""
    from sparknet_tpu.models import BENCH_CROPS, zoo

    if family in BENCH_CROPS:
        builder = getattr(zoo, family)
        return builder(batch=batch), getattr(zoo, f"{family}_solver")()
    gf = zoo.GRAPH_SWEEP_FAMILIES[family]
    return gf.net(batch), gf.solver()


def _abstract_step_peak(family: str, batch: int, dtype: str) -> dict:
    """The analytic footprint of one family's SOLO train step at
    ``batch``, traced fully abstractly: ``jax.eval_shape`` initializes
    the variables as ShapeDtypeStructs (vgg16's 550 MB of params never
    materialize), the step jaxpr comes from ``jax.make_jaxpr`` over the
    same module-level step builder the Solver jits, and donation is
    credited as the Solver establishes it (argnums 0/1)."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    from sparknet_tpu.common import Phase, get_config, set_config
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.solvers.solver import abstract_train_state, \
        build_train_step
    from sparknet_tpu.solvers.updates import OPTIMIZERS

    @contextlib.contextmanager
    def dtype_ctx():
        if dtype == "f32":
            yield
            return
        prior = get_config().compute_dtype
        set_config(compute_dtype=jnp.bfloat16)
        try:
            yield
        finally:
            set_config(compute_dtype=prior)

    with dtype_ctx():
        net_param, solver_cfg = _family_net(family, batch)
        net = Network(net_param, Phase.TRAIN)
        variables, slots = abstract_train_state(solver_cfg, net)
        specs = net.param_specs_for(variables)
        step = build_train_step(solver_cfg, net, specs)
        feeds = {}
        for name, shape in net.feed_shapes().items():
            feed_dtype = jnp.int32 if name == "label" else jnp.float32
            feeds[name] = jax.ShapeDtypeStruct(shape, feed_dtype)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        closed = jax.make_jaxpr(step)(variables, slots, 0, feeds, key)

    n_vs = len(jtu.tree_leaves(variables)) + len(jtu.tree_leaves(slots))
    donated = [True] * n_vs + [False] * (
        len(closed.jaxpr.invars) - n_vs)
    prog = extract_program(closed, donated_flags=donated)
    res = peak_residency(prog)
    params_b = sum(_aval_bytes(l) for l in jtu.tree_leaves(variables.params))
    slots_b = sum(_aval_bytes(l) for l in jtu.tree_leaves(slots))
    _, n_slots = OPTIMIZERS[solver_cfg.solver_type]
    return {
        "peak_bytes": res["peak_bytes"],
        "params_bytes": params_b,
        "slots_bytes": slots_b,
        "n_slots": n_slots,
        "net_param": net_param,
        "net": net,
        "variables": variables,
    }


def _tp_params_slots_bytes(net, variables, slots_per_param: int,
                           model_parallel: int = 2) -> int:
    """params+slots bytes per device under Megatron TP, using the real
    per-blob sharding decision from parallel/sharding.py (min_tp_dim
    floor and divisibility included)."""
    import jax.tree_util as jtu

    from sparknet_tpu.parallel.sharding import ShardingRules, \
        blob_shard_degree

    rules = ShardingRules()
    total = 0
    for lname, plist in variables.params.items():
        ltype = net.layer_by_name(lname).type
        for p in plist:
            deg = blob_shard_degree(ltype, p.shape, model_parallel, rules)
            total += (_aval_bytes(p) // deg) * (1 + slots_per_param)
    # state (BN statistics etc.) replicates
    total += sum(_aval_bytes(l)
                 for l in jtu.tree_leaves(variables.state))
    return total


def run_batch_fit(*, hbm_bytes: int = V5E_HBM_BYTES, update: bool = False,
                  families: list | None = None, banked_path: str | None = None,
                  n_devices: int = 8, progress=None) -> tuple:
    """Solve max safe batch per zoo family x dtype x mode and bank the
    table (``docs/mem_contracts/batch_fit.json``) the serving admission
    gate consults.  Abstract traces only — zero chip time, zero
    materialized arrays."""
    _pin_cpu_mesh(n_devices)

    budget = int(hbm_bytes * HBM_USABLE_FRAC)
    path = banked_path or FIT_TABLE_PATH
    findings: list = []
    rel = os.path.relpath(path, _REPO) if path.startswith(_REPO) else path
    table: dict = {
        "hbm_bytes": hbm_bytes,
        "usable_frac": HBM_USABLE_FRAC,
        "budget_bytes": budget,
        "probe_batches": list(FIT_PROBE_BATCHES),
        "modes": {m: d["note"] for m, d in mem_model.MODE_DIVISORS.items()},
        "families": {},
    }
    b1, b2 = FIT_PROBE_BATCHES
    for family in (families or _fit_family_names()):
        if progress:
            progress(family)
        table["families"][family] = {}
        for dtype in FIT_DTYPES:
            lo = _abstract_step_peak(family, b1, dtype)
            hi = _abstract_step_peak(family, b2, dtype)
            c0, c1 = mem_model.affine_fit(b1, lo["peak_bytes"],
                                          b2, hi["peak_bytes"])
            ps = lo["params_bytes"] + lo["slots_bytes"]
            entry = {
                "c0": int(c0),
                "c1": int(c1),
                "params_bytes": lo["params_bytes"],
                "slots_bytes": lo["slots_bytes"],
                "params_slots_bytes": ps,
                "tp_params_slots_bytes": _tp_params_slots_bytes(
                    lo["net"], lo["variables"], lo["n_slots"]),
                "max_batch": {},
            }
            for mode in mem_model.MODE_DIVISORS:
                if mode == "sp" and family != "transformer":
                    continue  # sequence parallelism needs a seq axis
                # solve: mode_footprint(entry, mode, B) <= budget, using
                # the mode's own affine coefficients
                probe = mem_model.mode_footprint(entry, mode, b2) \
                    - mem_model.mode_footprint(entry, mode, 0)
                mode_c1 = probe / float(b2)
                mode_c0 = mem_model.mode_footprint(entry, mode, 0)
                mb = mem_model.max_fit_batch(mode_c0, mode_c1, budget)
                entry["max_batch"][mode] = mb
                if mb == 0:
                    findings.append(Finding(
                        "mem-fit-infeasible", rel, 0,
                        f"{family}/{dtype}/{mode}: constant footprint "
                        f"{int(mode_c0):,} B alone exceeds the usable "
                        f"HBM budget {budget:,} B"))
            table["families"][family][dtype] = entry

    if os.path.exists(path) and not update:
        with open(path, encoding="utf-8") as f:
            banked = json.load(f)
        # compare only the families this run solved: a --family-scoped
        # verification run must not report the absent ones as drift
        banked_fams = {k: v for k, v in banked.get("families", {}).items()
                       if k in table["families"]}
        drift = _diff_contract({"families": banked_fams},
                               {"families": table["families"]})
        if drift:
            findings.append(Finding(
                "mem-manifest-drift", rel, 0,
                f"batch-fit table differs from the banked one "
                f"({len(drift)} field(s): " + "; ".join(drift[:4])
                + ("; ..." if len(drift) > 4 else "")
                + ") — rerun with --fit --update if intended"))
    elif not os.path.exists(path) and not update:
        findings.append(Finding(
            "mem-manifest-missing", rel, 0,
            "no banked batch-fit table — run "
            "`python -m sparknet_tpu.analysis mem --fit --update`"))
    if update:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(table, fh, indent=1, sort_keys=True)
            fh.write("\n")
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, table


def iter_rules() -> Iterator:
    yield from MEM_RULES.items()
