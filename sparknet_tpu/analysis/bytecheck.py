"""bytecheck: static per-step HBM traffic census + remat schedule search.

The fifth analysis engine.  graphcheck audits what the compiled
program says on the wire, memcheck what it holds in memory; this one
audits what it MOVES — the step's HBM byte bill.  Two legs:

* **traffic census** (the default run): every parallel mode's step is
  traced + lowered on the virtual CPU mesh (no compile, no execution —
  cheaper than memcheck, zero chip time) and two estimators of its
  byte bill are computed from the extracted jaxpr
  (``byte_model.py``): the gross eqn-level census (the pre-fusion
  analog of XLA's "bytes accessed") and the per-op-class floor (params, grads,
  slots, saved activations out of the jaxpr liveness walk, collective
  bytes from ``comm_model``, feed wire bytes).  Banked as a manifest
  family in ``docs/byte_contracts/`` and drift-diffed on every run;
  given a measured step-bytes figure, the headline config's census
  must reconcile with it within the stated ``HEADLINE_RATIO_WINDOW``.

* **schedule search** (``--remat``): per zoo family x dtype, every
  ``Config.remat`` policy (none/dots/blocks/full) is traced fully
  abstractly (``jax.make_jaxpr`` over ShapeDtypeStructs — vgg16's
  params never materialize; tracing cost is batch-independent, so the
  search runs at each family's headline batch) and scored on the
  class-model floor, with donation placements (params+slots donated
  vs not) scored on the liveness peak.  The bytes-minimal winner per
  (family, dtype) is banked in ``docs/byte_contracts/
  remat_policy.json`` — the table ``Config.remat`` consumers (the
  solo_remat/dp_remat mode twins, ``SPARKNET_REMAT`` runs) route
  through ``parallel/modes._banked_remat_policy``.  The selected
  policy must drop the headline family's modeled bytes by
  ``HEADLINE_DROP_FLOOR`` (>= 25%), and the per-policy saved bytes
  must respect the recompute partial order (more recompute => never
  more saved bytes).

Import contract: stdlib-only at import; jax loads lazily inside the
run functions after the CPU platform is pinned.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Iterator

from sparknet_tpu.analysis.byte_model import (
    HEADLINE_DROP_FLOOR,
    HEADLINE_RATIO_WINDOW,
    REMAT_POLICIES,
    REMAT_RECOMPUTE_PASSES,
    gbytes,
    gross_traffic,
    monotonicity_violations,
    reconcile,
    step_traffic,
)
from sparknet_tpu.analysis.comm_model import expected_comm
from sparknet_tpu.analysis.core import Finding
from sparknet_tpu.analysis.graphcheck import (
    _REPO,
    _diff_contract,
    _pin_cpu_mesh,
)
from sparknet_tpu.analysis.mem_model import peak_residency

__all__ = [
    "BYTE_RULES",
    "BYTE_SOURCE_PATTERNS",
    "MANIFEST_DIR",
    "HEADLINE_PATH",
    "REMAT_TABLE_PATH",
    "trace_traffic",
    "census_mode",
    "run_bytecheck",
    "run_headline",
    "run_remat_search",
    "sources_fingerprint",
    "iter_rules",
]

MANIFEST_DIR = os.path.join(_REPO, "docs", "byte_contracts")
HEADLINE_PATH = os.path.join(MANIFEST_DIR, "headline.json")
REMAT_TABLE_PATH = os.path.join(MANIFEST_DIR, "remat_policy.json")

BYTE_RULES = {
    "byte-floor-exceeds-census": "the per-op-class floor prices more "
    "bytes than the gross eqn census of the same program — the two "
    "estimators disagree on what the step even reads (a double-counted "
    "component or a dropped program region)",
    "byte-headline-divergence": "the headline config's gross census "
    "does not reconcile with the measured step bytes within the stated "
    "window — the analytic model is describing a different program "
    "than the bench measured",
    "byte-remat-no-gain": "the selected remat policy does not drop the "
    "headline family's modeled step bytes by the required fraction — "
    "the schedule search found no schedule worth a chip A/B",
    "byte-remat-nonmonotonic": "a heavier-recompute policy saves MORE "
    "activation bytes than a lighter one — the recompute partial order "
    "is violated, so the scores cannot be trusted to rank schedules",
    "byte-manifest-missing": "no banked byte manifest for this subject "
    "(run `python -m sparknet_tpu.analysis bytes --update`, and "
    "`--remat --update` for the policy table)",
    "byte-manifest-drift": "byte contract differs from the banked "
    "manifest — regenerate with --update if the change is intended",
}

# source files whose edits invalidate the banked byte manifests
# (hashed into docs/byte_contracts/SOURCES.json by --update; the
# graftlint rule byte-manifest-fresh compares edits against it).
# compiler/graph.py is byte source — the BLOCK_SAVE_NAME boundary tags
# it plants are exactly what the "blocks" policy saves.
BYTE_SOURCE_PATTERNS = (
    "sparknet_tpu/parallel/",
    "sparknet_tpu/serve/",
    "sparknet_tpu/models/zoo.py",
    "sparknet_tpu/compiler/graph.py",
    "sparknet_tpu/ops/pallas_kernels.py",
    "sparknet_tpu/ops/layout.py",
    "sparknet_tpu/solvers/solver.py",
    "sparknet_tpu/solvers/updates.py",
    "sparknet_tpu/analysis/bytecheck.py",
    "sparknet_tpu/analysis/byte_model.py",
    "sparknet_tpu/analysis/comm_model.py",
    "sparknet_tpu/analysis/memcheck.py",
    "sparknet_tpu/analysis/mem_model.py",
)

# the headline bench shape the reconciliation gate prices (bench.py's
# defaults on the chip)
HEADLINE_FAMILY = "alexnet"
HEADLINE_BATCH = 256
HEADLINE_DTYPE = "bf16"

# per-family batches the schedule search scores at — each family's
# bench/headline batch (tracing is abstract, so batch size costs
# nothing; scoring at the real batch makes the banked step-bytes
# directly comparable to measured runs)
SEARCH_BATCH_DEFAULT = 256
SEARCH_BATCHES = {"vgg16": 128, "cifar10_quick": 64, "transformer": 32}
SEARCH_DTYPES = ("f32", "bf16")


# ---------------------------------------------------------------------------
# Tracing (jax-touching, called lazily)
# ---------------------------------------------------------------------------


def trace_traffic(target):
    """Trace + lower one mode's step, no compile — the census needs the
    jaxpr and the lowering's donation record (``lowered.args_info``),
    not XLA's buffer assignment, so it stops a compile earlier than
    memcheck.  Returns the extracted ``MemProgram`` (per-device buffer
    sizes resolved through the args' actual shardings; intermediate
    batch-carrying buffers divided by the mesh width via the
    extractor's heuristic)."""
    import jax.tree_util as jtu

    from sparknet_tpu.analysis.memcheck import (
        _shard_leaf_bytes,
        extract_program,
    )

    with target.trace_context():
        traced = target.fn.trace(*target.args)
        lowered = traced.lower()
    mesh = target.meta.get("mesh", {}) or {}
    width = 1
    for v in mesh.values():
        width *= int(v)
    flat_leaves = [l for a in target.args for l in jtu.tree_leaves(a)]
    input_bytes = [_shard_leaf_bytes(l) for l in flat_leaves]
    donated_flags: list = []
    for info in lowered.args_info[0]:
        donated_flags.extend(bool(x.donated) for x in jtu.tree_leaves(info))
    return extract_program(
        traced.jaxpr, batch=int(target.meta.get("batch", 0) or 0),
        width=width, input_bytes=input_bytes, donated_flags=donated_flags)


def _tree_shard_bytes(tree) -> int:
    import jax.tree_util as jtu

    from sparknet_tpu.analysis.memcheck import _shard_leaf_bytes

    return sum(_shard_leaf_bytes(l) for l in jtu.tree_leaves(tree))


def census_mode(target, prog) -> tuple:
    """(problems, contract) for one mode: the gross census, the
    class-model floor, and the floor<=census invariant.

    Ingredient bytes are per-device, resolved from the args' actual
    placements (tau/easgd worker stacking and TP param sharding come
    out right for free).  The invariant is checked only for programs
    whose census saw every eqn: a scan/while body's INTERNAL eqns are
    not in the extracted census (counted once as a liveness ``extra``
    term, matching the HloCostAnalysis body-once convention), so for
    control-flow modes the comparison would be one-sided and is
    recorded as skipped instead.
    """
    meta = target.meta or {}
    width = 1
    for v in (meta.get("mesh") or {}).values():
        width *= int(v)

    a0 = target.args[0]
    if hasattr(a0, "params"):
        params_dev = _tree_shard_bytes(a0.params)
        state_dev = _tree_shard_bytes(a0.state)
    else:
        params_dev = _tree_shard_bytes(a0)
        state_dev = 0
    train = bool(target.carry_argnums)
    slot_dev = 0
    if train and 1 in target.carry_argnums and len(target.args) > 1:
        slot_dev = _tree_shard_bytes(target.args[1])
    extra_carry = sum(_tree_shard_bytes(target.args[i])
                      for i in target.carry_argnums if i >= 2)
    feed_b = sum(
        _tree_shard_bytes(a) for i, a in enumerate(target.args)
        if i != 0 and i not in target.carry_argnums
        and not isinstance(a, int))

    exp = expected_comm(target.name, param_bytes=target.param_bytes,
                        state_bytes=target.state_bytes)
    coll = sum(w[0] for w in exp.required.values() if w)

    policy = meta.get("remat") or "none"
    passes = REMAT_RECOMPUTE_PASSES.get(policy, 1)
    res = peak_residency(prog)
    saved = res["temp_bytes"]

    gross = gross_traffic(prog)
    floor = step_traffic(
        param_bytes=params_dev, state_bytes=state_dev,
        slot_bytes=slot_dev, saved_activation_bytes=saved,
        collective_bytes=coll, feed_bytes=feed_b,
        extra_carry_bytes=extra_carry, train=train,
        recompute_passes=passes)

    has_body = any(e.extra > 0 for e in prog.eqns)
    problems: list = []
    if not has_body and floor["total_bytes"] > gross:
        problems.append({
            "rule": "byte-floor-exceeds-census",
            "message": f"class-model floor {floor['total_bytes']:,} B "
                       f"exceeds the gross eqn census {gross:,} B — the "
                       "floor double-counts a component or the census "
                       "dropped a program region",
        })

    contract = {
        "gross_census_bytes": gross,
        "gross_census_gbytes": gbytes(gross),
        "floor": floor,
        "floor_vs_census_checked": not has_body,
        "ingredients": {
            "param_bytes": params_dev,
            "state_bytes": state_dev,
            "slot_bytes": slot_dev,
            "saved_activation_bytes": saved,
            "collective_bytes": coll,
            "feed_bytes": feed_b,
            "extra_carry_bytes": extra_carry,
            "train": train,
            "recompute_passes": passes,
            "remat_policy": policy,
            "width": width,
        },
        "n_eqns": len(prog.eqns),
    }
    return problems, contract


# ---------------------------------------------------------------------------
# Manifests
# ---------------------------------------------------------------------------


def manifest_path(mode: str, banked_dir: str | None = None) -> str:
    return os.path.join(banked_dir or MANIFEST_DIR, f"{mode}.json")


def sources_fingerprint(repo: str | None = None) -> dict:
    """sha256 per byte-contract source file (the freshness record the
    ``byte-manifest-fresh`` lint rule checks edits against)."""
    repo = repo or _REPO
    files: list = []
    for pat in BYTE_SOURCE_PATTERNS:
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


def _diff_or_missing(manifest: dict, mpath: str, problems: list,
                     update: bool) -> dict:
    """The shared bank/drift/allow loop: merge the banked allow map into
    ``manifest``, append drift/missing problems, return the allow map."""
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
                    "rule": "byte-manifest-drift",
                    "message": f"byte contract differs from the banked "
                               f"manifest ({len(drift)} field(s): "
                               + "; ".join(drift[:4])
                               + ("; ..." if len(drift) > 4 else "")
                               + ") — rerun with --update if intended",
                })
    elif not update:
        problems.append({
            "rule": "byte-manifest-missing",
            "message": "no banked byte manifest — run "
                       "`python -m sparknet_tpu.analysis bytes --update`",
        })
    return allow


def _write_manifest(manifest: dict, mpath: str) -> None:
    os.makedirs(os.path.dirname(mpath), exist_ok=True)
    # graftlint: disable-next-line=bank-guard -- chip-free contract manifest (docs/byte_contracts/), not banked chip evidence
    with open(mpath, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=1, sort_keys=True)
        fh.write("\n")


def _check_mode(name: str, banked_dir: str, update: bool,
                n_devices: int) -> tuple:
    from sparknet_tpu.parallel.modes import build_target

    target = build_target(name, n_devices)
    prog = trace_traffic(target)
    problems, contract = census_mode(target, prog)
    manifest = {
        "mode": name,
        "meta": target.meta,
        "contract": contract,
        "model": {"param_bytes": target.param_bytes,
                  "state_bytes": target.state_bytes},
        "allow": {},
    }
    mpath = manifest_path(name, banked_dir)
    rel = os.path.relpath(mpath, _REPO) if mpath.startswith(_REPO) else mpath
    allow = _diff_or_missing(manifest, mpath, problems, update)
    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in allow)
        for p in problems
    ]
    return findings, manifest


# ---------------------------------------------------------------------------
# Abstract family census (shared by headline + remat search)
# ---------------------------------------------------------------------------


def _abstract_census(family: str, batch: int, dtype: str,
                     policy: str = "none") -> dict:
    """One family's SOLO train step traced fully abstractly under
    (dtype, remat policy): ``jax.eval_shape`` init + ``jax.make_jaxpr``
    over the same step builder the Solver jits (memcheck's batch-fit
    discipline — no array ever materializes).  Returns the extracted
    programs (params+slots donated, and undonated — the two donation
    placements the search scores) plus the ingredient byte totals."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    from sparknet_tpu.analysis.memcheck import (
        _aval_bytes,
        _family_net,
        extract_program,
    )
    from sparknet_tpu.common import Phase, get_config, set_config
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.solvers.solver import abstract_train_state, \
        build_train_step
    from sparknet_tpu.solvers.updates import OPTIMIZERS

    @contextlib.contextmanager
    def build_ctx():
        overrides: dict = {}
        if dtype == "bf16":
            overrides["compute_dtype"] = jnp.bfloat16
        if policy != "none":
            overrides["remat"] = policy
        if not overrides:
            yield
            return
        prior = {k: getattr(get_config(), k) for k in overrides}
        set_config(**overrides)
        try:
            yield
        finally:
            set_config(**prior)

    with build_ctx():
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
    donated = [True] * n_vs + [False] * (len(closed.jaxpr.invars) - n_vs)
    _, n_slots = OPTIMIZERS[solver_cfg.solver_type]
    return {
        "prog": extract_program(closed, donated_flags=donated),
        "prog_undonated": extract_program(closed),
        "params_bytes": sum(_aval_bytes(l)
                            for l in jtu.tree_leaves(variables.params)),
        "state_bytes": sum(_aval_bytes(l)
                           for l in jtu.tree_leaves(variables.state)),
        "slots_bytes": sum(_aval_bytes(l) for l in jtu.tree_leaves(slots)),
        "feed_bytes": sum(_aval_bytes(v) for v in feeds.values()),
        "n_slots": n_slots,
    }


def _family_step_bytes(cen: dict, policy: str) -> dict:
    """The class-model floor for one (family, dtype, policy) census, in
    the two banked parallel placements: solo (zero collectives) and dp
    (the grad all-reduce's lo-window wire bytes on top — params
    replicate under DP, so every other term is per-device identical)."""
    saved = peak_residency(cen["prog"])["temp_bytes"]
    passes = REMAT_RECOMPUTE_PASSES[policy]
    base = dict(
        param_bytes=cen["params_bytes"], state_bytes=cen["state_bytes"],
        slot_bytes=cen["slots_bytes"], saved_activation_bytes=saved,
        feed_bytes=cen["feed_bytes"], train=True, recompute_passes=passes)
    solo = step_traffic(collective_bytes=0, **base)
    dp_comm = expected_comm("dp", param_bytes=cen["params_bytes"],
                            state_bytes=cen["state_bytes"])
    dp = step_traffic(
        collective_bytes=dp_comm.required["all-reduce"][0], **base)
    return {
        "saved_activation_bytes": saved,
        "recompute_passes": passes,
        "step_bytes": {"solo": solo["total_bytes"],
                       "dp": dp["total_bytes"]},
        "step_gbytes": {"solo": gbytes(solo["total_bytes"]),
                        "dp": gbytes(dp["total_bytes"])},
        "peak_bytes_donated": peak_residency(cen["prog"])["peak_bytes"],
        "peak_bytes_undonated":
            peak_residency(cen["prog_undonated"])["peak_bytes"],
    }


# ---------------------------------------------------------------------------
# Leg (a) companion: the headline reconciliation
# ---------------------------------------------------------------------------


def run_headline(*, update: bool = False,
                 banked_path: str | None = None,
                 n_devices: int = 8,
                 measured_step_bytes: float | None = None) -> tuple:
    """Census the headline bench shape (alexnet b256 bf16 solo) and,
    when the caller supplies the step bytes a chip run measured,
    reconcile the gross census with them within
    ``HEADLINE_RATIO_WINDOW``.  No measurement is on record yet (the
    ledger starts with ROADMAP S1), so the default run states a
    vacuous pass.

    Only the CENSUS side is drift-pinned: a measured figure moves
    whenever the bench re-measures, and that must not read as model
    drift — the tolerance window is the contract between the two
    sides, the manifest diff only guards the analytic half."""
    _pin_cpu_mesh(n_devices)
    path = banked_path or HEADLINE_PATH
    rel = os.path.relpath(path, _REPO) if path.startswith(_REPO) else path
    cen = _abstract_census(HEADLINE_FAMILY, HEADLINE_BATCH, HEADLINE_DTYPE)
    gross = gross_traffic(cen["prog"])
    problems: list = []
    manifest = {
        "subject": "headline",
        "meta": {"family": HEADLINE_FAMILY, "batch": HEADLINE_BATCH,
                 "dtype": HEADLINE_DTYPE, "mode": "solo"},
        "contract": {
            "gross_census_bytes": gross,
            "gross_census_gbytes": gbytes(gross),
            "params_bytes": cen["params_bytes"],
            "slots_bytes": cen["slots_bytes"],
            "feed_bytes": cen["feed_bytes"],
        },
        "tolerance": {"ratio_window": list(HEADLINE_RATIO_WINDOW)},
        "allow": {},
    }

    measured = measured_step_bytes
    if measured:
        verdict = reconcile(measured, gross)
        manifest["reconciliation"] = verdict
        if not verdict["within"]:
            problems.append({
                "rule": "byte-headline-divergence",
                "message": f"gross census {verdict['census_gbytes']} GB "
                           f"vs measured {verdict['measured_gbytes']} GB "
                           f"(ratio {verdict['ratio']}) — outside the "
                           f"stated window {verdict['window']}",
            })
    else:
        # no measurement to reconcile against: vacuous pass, but say so
        # in the manifest rather than silently gating nothing
        manifest["reconciliation"] = {
            "note": "no measured step bytes on record — reconciliation "
                    "vacuous until the ledger has a row",
        }

    allow = _diff_or_missing(manifest, path, problems, update)
    if update:
        _write_manifest(manifest, path)
    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in allow)
        for p in problems
    ]
    return findings, manifest


# ---------------------------------------------------------------------------
# Leg (b): the remat/donation schedule search
# ---------------------------------------------------------------------------


def run_remat_search(*, update: bool = False, banked_path: str | None = None,
                     families: list | None = None, progress=None,
                     n_devices: int = 8) -> tuple:
    """Enumerate remat policies x donation placements per zoo family x
    dtype, score each chip-free on the byte model, bank the
    bytes-minimal winner (``docs/byte_contracts/remat_policy.json``).

    Selection is on the solo floor (ties go to the LIGHTER recompute —
    recompute costs chip flops the byte model does not price, so a
    byte-tied heavier policy is strictly worse); the dp figure rides in
    the table so the DP twins and A/Bs can read their own prediction.
    Donation: donating params+slots always at least matches the
    undonated peak (the lowering aliases the update in place), so the
    banked placement is donate-params-slots with both peaks recorded
    as evidence."""
    _pin_cpu_mesh(n_devices)
    from sparknet_tpu.analysis.memcheck import _fit_family_names

    path = banked_path or REMAT_TABLE_PATH
    rel = os.path.relpath(path, _REPO) if path.startswith(_REPO) else path
    problems: list = []
    table: dict = {
        "policies": list(REMAT_POLICIES),
        "search_batches": {},
        "families": {},
        "selected": {},
        "headline": {"family": HEADLINE_FAMILY, "dtype": HEADLINE_DTYPE,
                     "drop_floor": HEADLINE_DROP_FLOOR},
    }
    for family in (families or _fit_family_names()):
        batch = SEARCH_BATCHES.get(family, SEARCH_BATCH_DEFAULT)
        table["search_batches"][family] = batch
        table["families"][family] = {}
        table["selected"][family] = {}
        for dtype in SEARCH_DTYPES:
            if progress:
                progress(f"{family}/{dtype}")
            scores = {}
            for policy in REMAT_POLICIES:
                cen = _abstract_census(family, batch, dtype, policy)
                scores[policy] = _family_step_bytes(cen, policy)
            table["families"][family][dtype] = scores

            bad = monotonicity_violations(
                {p: s["saved_activation_bytes"] for p, s in scores.items()})
            for a, b in bad:
                problems.append({
                    "rule": "byte-remat-nonmonotonic",
                    "message": f"{family}/{dtype}: policy {b!r} saves "
                               f"{scores[b]['saved_activation_bytes']:,} B "
                               f"of activations, MORE than the lighter "
                               f"{a!r}'s "
                               f"{scores[a]['saved_activation_bytes']:,} B",
                })

            winner = min(
                REMAT_POLICIES,
                key=lambda p: (scores[p]["step_bytes"]["solo"],
                               REMAT_POLICIES.index(p)))
            none_b = scores["none"]["step_bytes"]["solo"]
            win_b = scores[winner]["step_bytes"]["solo"]
            drop = (none_b - win_b) / none_b if none_b else 0.0
            table["selected"][family][dtype] = {
                "policy": winner,
                "donation": "donate_params_slots",
                "step_bytes_solo": win_b,
                "step_gbytes_solo": gbytes(win_b),
                "drop_frac_vs_none": round(drop, 4),
            }
            if (family == HEADLINE_FAMILY and dtype == HEADLINE_DTYPE
                    and drop < HEADLINE_DROP_FLOOR):
                problems.append({
                    "rule": "byte-remat-no-gain",
                    "message": f"selected policy {winner!r} drops the "
                               f"headline family's modeled step bytes by "
                               f"{drop:.1%} < the required "
                               f"{HEADLINE_DROP_FLOOR:.0%}",
                })

    manifest = {
        "subject": "remat_policy",
        "contract": {"families": table["families"],
                     "selected": table["selected"]},
        "allow": {},
    }
    allow = _diff_or_missing(manifest, path, problems, update)
    if update:
        # the table file IS the manifest (consumers read it directly:
        # parallel/modes._banked_remat_policy, the Config.remat docs)
        _write_manifest({**table, "allow": allow,
                         "contract": manifest["contract"]}, path)
    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in allow)
        for p in problems
    ]
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, table


# ---------------------------------------------------------------------------
# The engine entry point
# ---------------------------------------------------------------------------


def run_bytecheck(modes: list | None = None, *, update: bool = False,
                  banked_dir: str | None = None, n_devices: int = 8,
                  progress=None) -> tuple:
    """Census ``modes`` (default: all registered parallel modes) plus,
    on a full run, the headline reconciliation and a presence check of
    the banked remat-policy table (the search itself runs via
    ``--remat`` — it is the expensive leg).  Returns ``(findings,
    manifests)``; with ``update=True`` the banked manifests (and
    SOURCES.json on a full default-dir run) are rewritten."""
    _pin_cpu_mesh(n_devices)

    from sparknet_tpu.parallel.modes import list_modes

    all_modes = list_modes()
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
            _write_manifest(manifest, manifest_path(name, banked))

    full_run = set(modes) == set(all_modes)
    if full_run:
        if progress:
            progress("headline")
        hf, hm = run_headline(
            update=update, banked_path=os.path.join(banked, "headline.json"))
        findings.extend(hf)
        manifests["headline"] = hm
        remat_path = os.path.join(banked, "remat_policy.json")
        if not os.path.exists(remat_path):
            findings.append(Finding(
                "byte-manifest-missing",
                os.path.relpath(remat_path, _REPO)
                if remat_path.startswith(_REPO) else remat_path, 0,
                "no banked remat-policy table — run "
                "`python -m sparknet_tpu.analysis bytes --remat --update`"))
    if update and full_run and banked == MANIFEST_DIR:
        # graftlint: disable-next-line=bank-guard -- SOURCES.json fingerprint for the byte-manifest-fresh rule, a chip-free contract artifact
        with open(os.path.join(banked, "SOURCES.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(sources_fingerprint(), fh, indent=1, sort_keys=True)
            fh.write("\n")
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, manifests


def iter_rules() -> Iterator:
    yield from BYTE_RULES.items()
