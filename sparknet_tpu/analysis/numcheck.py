"""numcheck: static numerics contracts + mixed-precision policy search.

The sixth analysis engine.  graphcheck audits the wire, memcheck the
residency, bytecheck the traffic, conccheck the host plane; this one
audits PRECISION — where every bit of every accumulation lives.  Two
legs:

* **dtype-flow census** (the default run): every parallel mode's step
  is traced on the virtual CPU mesh (jaxpr only — no compile, no
  execution, zero chip time) and every eqn is classified into
  precision classes: matmul/conv accumulation (``dot_general`` /
  ``conv_general_dilated`` with their ``preferred_element_type``),
  sum-reductions (the accumulating kind — BN statistics, loss sums,
  avg pools), and the cast census (every ``convert_element_type``
  pair, with the silent double-rounding round-trip shape detected
  structurally: narrow -> f32 -> same narrow where the f32 hop feeds
  nothing else).  The contracts (``num_model.census_problems``):
  accumulation >= f32 under any bf16-storage config, the final scalar
  loss pinned f32 in every config, no smuggled f32->bf16 downcasts in
  modes with no bf16 arm, no round-trips anywhere.  Banked as a
  manifest family in ``docs/num_contracts/`` and drift-diffed on
  every run; ``# numcheck: <rule>=<why>`` comments in the source
  surface suppress a rule engine-wide (the inline analog of the
  manifest allow map).

* **mixed-precision search** (``--mixed``): per zoo family, every
  ``Config.activation_dtype`` storage policy (none/io/blocks/full) is
  scored chip-free on the byte model (bf16 storage halves exactly the
  saved-activation bytes the policy stores — ``num_model.
  mixed_saved_bytes`` over the abstract f32 census) AND gated by a
  deterministic CPU error probe: a concrete loss+grad eval on fixed
  seeds, mixed vs f32, max relative error under the per-family bound
  (``num_model.error_gate``).  The bytes-minimal SAFE policy is
  banked in ``docs/num_contracts/mixed_policy.json`` — the table the
  ``solo_act_bf16``/``dp_act_bf16`` twins and bench.py's
  ``SPARKNET_BENCH_ACT_DTYPE`` arm route through
  ``parallel/modes._banked_act_policy``.  Probes walk the policies in
  ascending modeled bytes and stop at the first safe one, so a
  healthy family costs one baseline + one mixed eval.

Import contract: stdlib-only at import; jax loads lazily inside the
run functions after the CPU platform is pinned.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from typing import Iterator

from sparknet_tpu.analysis.byte_model import gbytes, step_traffic
from sparknet_tpu.analysis.comm_model import expected_comm
from sparknet_tpu.analysis.core import Finding
from sparknet_tpu.analysis.graphcheck import (
    _REPO,
    _diff_contract,
    _pin_cpu_mesh,
)
from sparknet_tpu.analysis.mem_model import peak_residency
from sparknet_tpu.analysis.num_model import (
    ACT_DTYPES,
    ACT_SEARCH_POLICIES,
    MIXED_DROP_FLOOR,
    act_monotonicity_violations,
    census_problems,
    error_gate,
    mixed_saved_bytes,
    normalize_dtype,
    summarize_census,
)

__all__ = [
    "NUM_RULES",
    "NUM_SOURCE_PATTERNS",
    "MANIFEST_DIR",
    "MIXED_TABLE_PATH",
    "trace_numerics",
    "census_mode",
    "run_numcheck",
    "run_mixed_search",
    "inline_allows",
    "sources_fingerprint",
    "iter_rules",
]

MANIFEST_DIR = os.path.join(_REPO, "docs", "num_contracts")
MIXED_TABLE_PATH = os.path.join(MANIFEST_DIR, "mixed_policy.json")

NUM_RULES = {
    "num-accum-dtype": "a dot/conv accumulates below f32 — either an "
    "explicit sub-f32 preferred_element_type, or a narrow storage "
    "operand reached the MXU without the layer-entry upcast under a "
    "bf16-storage config",
    "num-reduce-dtype": "a sum-reduction accumulates a sub-f32 operand "
    "under a bf16-storage config — BN statistics / loss sums / avg "
    "pools must accumulate >= f32",
    "num-f32-pin": "the program's scalar loss output is not f32 — loss "
    "accumulation is pinned f32 in every config",
    "num-cast-roundtrip": "a narrow->f32->narrow convert round-trip "
    "with no compute between the casts — silent double rounding",
    "num-cast-downcast": "an f32->narrow float downcast in a mode with "
    "no bf16 arm configured — a smuggled precision loss",
    "num-mixed-no-gain": "the selected activation-storage policy does "
    "not drop the headline family's modeled step bytes by the required "
    "fraction — the mixed search found no schedule worth a chip A/B",
    "num-mixed-nonmonotonic": "a heavier-storage policy models MORE "
    "saved bytes than a lighter one — the coverage partial order is "
    "violated, so the scores cannot rank policies",
    "num-manifest-missing": "no banked num manifest for this subject "
    "(run `python -m sparknet_tpu.analysis num --update`, and "
    "`--mixed --update` for the policy table)",
    "num-manifest-drift": "numerics contract differs from the banked "
    "manifest — regenerate with --update if the change is intended",
}

# source files whose edits invalidate the banked num manifests (hashed
# into docs/num_contracts/SOURCES.json by --update; the graftlint rule
# num-manifest-fresh compares edits against it).  common.py is num
# source — the activation_dtype policy semantics live there; compiler/
# graph.py plants the storage casts the census counts.
NUM_SOURCE_PATTERNS = (
    "sparknet_tpu/parallel/",
    "sparknet_tpu/serve/",
    "sparknet_tpu/models/zoo.py",
    "sparknet_tpu/compiler/graph.py",
    "sparknet_tpu/common.py",
    "sparknet_tpu/ops/pallas_kernels.py",
    "sparknet_tpu/ops/layout.py",
    "sparknet_tpu/solvers/solver.py",
    "sparknet_tpu/solvers/updates.py",
    "sparknet_tpu/analysis/numcheck.py",
    "sparknet_tpu/analysis/num_model.py",
    "sparknet_tpu/analysis/byte_model.py",
    "sparknet_tpu/analysis/memcheck.py",
    "sparknet_tpu/analysis/mem_model.py",
)

# the mixed search scores at each family's bench batch (tracing is
# abstract — batch costs nothing; the banked step-bytes stay directly
# comparable to the remat table's); probes run concrete, so they drop
# to a tiny batch — the ROUNDING error being probed is
# batch-independent
PROBE_BATCH = 2

# `# numcheck: <rule>=<why>` — the inline suppression grammar
_INLINE_RE = re.compile(r"#\s*numcheck:\s*(num-[\w-]+)\s*=\s*(.+?)\s*$")


# ---------------------------------------------------------------------------
# jaxpr walk (jax-touching, called lazily)
# ---------------------------------------------------------------------------

# reduction primitives the census records; the SUM-like subset (the
# accumulating kind) is classified in num_model.SUM_REDUCE_OPS
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
    "cumsum", "cumprod", "cumlogsumexp", "cummax", "cummin",
})


def _aval_dt(v) -> str:
    """Short dtype name of a jaxpr atom's aval ("other" for tokens /
    typed PRNG keys — never floating, so never narrow)."""
    try:
        return normalize_dtype(str(v.aval.dtype))
    except Exception:
        return "other"


def _iter_jaxprs(obj) -> Iterator:
    """Every (Closed)Jaxpr reachable inside one eqn-params value —
    pjit/scan carry a ClosedJaxpr, while carries two, cond a tuple of
    branches; duck-typed so new call primitives are walked for free."""
    # ClosedJaxpr first: it proxies .eqns, so the bare-Jaxpr test alone
    # would catch it and then trip on the missing .outvars
    if hasattr(obj, "jaxpr") and hasattr(obj.jaxpr, "eqns"):
        yield obj.jaxpr
    elif hasattr(obj, "eqns"):
        yield obj
    elif isinstance(obj, (tuple, list)):
        for o in obj:
            yield from _iter_jaxprs(o)


def _walk_jaxpr(jaxpr, census: dict) -> None:
    """One jaxpr scope: record matmul/reduce/cast eqns, recurse into
    sub-jaxprs.  Round-trip detection is per-scope — a convert chain
    never crosses a call boundary in this codebase's lowerings, and a
    missed cross-scope chain fails SAFE (not flagged)."""
    from jax.extend.core import Literal

    use_count: dict = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, Literal):
                use_count[v] = use_count.get(v, 0) + 1
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            use_count[v] = use_count.get(v, 0) + 1

    # outvar -> original narrow dtype, for converts narrow->f32
    upcast_src: dict = {}
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "convert_element_type":
            v, w = eqn.invars[0], eqn.outvars[0]
            src, dst = _aval_dt(v), _aval_dt(w)
            roundtrip = (
                src == "f32"
                and not isinstance(v, Literal)
                and upcast_src.get(v) == dst
                and use_count.get(v, 0) == 1
            )
            census["casts"].append(
                {"src": src, "dst": dst, "roundtrip": roundtrip})
            if dst == "f32" and not isinstance(v, Literal):
                from sparknet_tpu.analysis.num_model import is_narrow_float
                if is_narrow_float(src):
                    upcast_src[w] = src
        elif prim in ("dot_general", "conv_general_dilated"):
            pet = eqn.params.get("preferred_element_type")
            if pet is not None:
                import numpy as np
                pet = normalize_dtype(str(np.dtype(pet)))
            census["matmuls"].append({
                "op": prim,
                "operands": [_aval_dt(v) for v in eqn.invars[:2]],
                "out": _aval_dt(eqn.outvars[0]),
                "preferred": pet,
            })
        elif prim in _REDUCE_PRIMS:
            census["reduces"].append({
                "op": prim,
                "operand": _aval_dt(eqn.invars[0]),
                "out": _aval_dt(eqn.outvars[0]),
            })
        for val in eqn.params.values():
            for sub in _iter_jaxprs(val):
                _walk_jaxpr(sub, census)


def _census_of(closed) -> dict:
    """Full census of one ClosedJaxpr: the recursive eqn walk plus the
    loss-dtype probe (the LAST scalar floating output — train steps
    return ``(variables, slots, loss)`` with the loss last; forward-
    only programs have no scalar float output and record None)."""
    census: dict = {"matmuls": [], "reduces": [], "casts": [],
                    "loss_dtype": None}
    _walk_jaxpr(closed.jaxpr, census)
    for v in closed.jaxpr.outvars:
        try:
            aval = v.aval
            if getattr(aval, "shape", None) == () and \
                    _aval_dt(v) in ("f64", "f32", "bf16", "f16"):
                census["loss_dtype"] = _aval_dt(v)
        except Exception:
            continue
    return census


def trace_numerics(target) -> dict:
    """Trace one mode's step (no lower, no compile — the dtype census
    is a jaxpr property) and walk it into the record schema
    ``num_model`` classifies."""
    with target.trace_context():
        traced = target.fn.trace(*target.args)
    return _census_of(traced.jaxpr)


def census_mode(target, census: dict) -> tuple:
    """(problems, contract) for one mode: the aggregated census block
    plus the numerics-contract findings over the raw records."""
    meta = target.meta or {}
    problems = census_problems(census, meta)
    contract = summarize_census(census)
    contract["act_policy"] = meta.get("act", "")
    contract["compute_dtype"] = meta.get("dtype", "f32")
    return problems, contract


# ---------------------------------------------------------------------------
# Manifests + inline suppressions
# ---------------------------------------------------------------------------


def manifest_path(mode: str, banked_dir: str | None = None) -> str:
    return os.path.join(banked_dir or MANIFEST_DIR, f"{mode}.json")


def sources_fingerprint(repo: str | None = None) -> dict:
    """sha256 per num-contract source file (the freshness record the
    ``num-manifest-fresh`` lint rule checks edits against)."""
    repo = repo or _REPO
    files: list = []
    for pat in NUM_SOURCE_PATTERNS:
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


def inline_allows(repo: str | None = None) -> dict:
    """``# numcheck: <rule>=<why>`` directives scanned from the source
    surface — the engine-wide inline analog of a manifest allow map
    (census findings carry no source line to anchor a per-line
    directive to, so suppression is per-rule with the why recorded)."""
    repo = repo or _REPO
    allows: dict = {}
    for pat in NUM_SOURCE_PATTERNS:
        p = os.path.join(repo, *pat.split("/"))
        paths = ([os.path.join(p, f) for f in sorted(os.listdir(p))
                  if f.endswith(".py")] if pat.endswith("/")
                 and os.path.isdir(p)
                 else [p] if os.path.exists(p) and not pat.endswith("/")
                 else [])
        for path in paths:
            try:
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        m = _INLINE_RE.search(line)
                        if m and m.group(1) in NUM_RULES:
                            allows[m.group(1)] = m.group(2)
            except OSError:
                continue
    return allows


def _diff_or_missing(manifest: dict, mpath: str, problems: list,
                     update: bool) -> dict:
    """The shared bank/drift/allow loop (bytecheck's, on num rules)."""
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
                    "rule": "num-manifest-drift",
                    "message": f"numerics contract differs from the "
                               f"banked manifest ({len(drift)} field(s): "
                               + "; ".join(drift[:4])
                               + ("; ..." if len(drift) > 4 else "")
                               + ") — rerun with --update if intended",
                })
    elif not update:
        problems.append({
            "rule": "num-manifest-missing",
            "message": "no banked num manifest — run "
                       "`python -m sparknet_tpu.analysis num --update`",
        })
    return allow


def _write_manifest(manifest: dict, mpath: str) -> None:
    os.makedirs(os.path.dirname(mpath), exist_ok=True)
    # graftlint: disable-next-line=bank-guard -- chip-free contract manifest (docs/num_contracts/), not banked chip evidence
    with open(mpath, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=1, sort_keys=True)
        fh.write("\n")


def _check_mode(name: str, banked_dir: str, update: bool,
                n_devices: int, allow_inline: dict) -> tuple:
    from sparknet_tpu.parallel.modes import build_target

    target = build_target(name, n_devices)
    census = trace_numerics(target)
    problems, contract = census_mode(target, census)
    manifest = {
        "mode": name,
        "meta": target.meta,
        "contract": contract,
        "allow": {},
    }
    mpath = manifest_path(name, banked_dir)
    rel = os.path.relpath(mpath, _REPO) if mpath.startswith(_REPO) else mpath
    allow = _diff_or_missing(manifest, mpath, problems, update)
    merged = {**allow_inline, **allow}
    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in merged)
        for p in problems
    ]
    return findings, manifest


# ---------------------------------------------------------------------------
# The mixed-precision policy search (`num --mixed`)
# ---------------------------------------------------------------------------


def _family_mixed_census(family: str, batch: int) -> dict:
    """One family's SOLO train step traced fully abstractly at the f32
    baseline (no policy — the search discounts analytically), plus the
    two byte splits the policies store: floating feed bytes ("io") and
    pooling-boundary output bytes ("blocks", from ``net.blob_info()``
    — populated by the abstract init, shapes are concrete under
    eval_shape)."""
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    from sparknet_tpu.analysis.memcheck import (
        _aval_bytes,
        _family_net,
        extract_program,
    )
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.solvers.solver import abstract_train_state, \
        build_train_step

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
    prog = extract_program(closed, donated_flags=donated)

    info = net.blob_info()
    boundary = 0
    for layer in net.layers:
        if getattr(layer, "type", "") == "Pooling":
            for top in layer.tops:
                bi = info.get(top)
                if bi is not None:
                    n = 1
                    for d in bi.shape:
                        n *= int(d)
                    boundary += n * 4
    float_feed = sum(
        _aval_bytes(v) for name, v in feeds.items() if name != "label")
    return {
        "saved_bytes": peak_residency(prog)["temp_bytes"],
        "boundary_bytes": boundary,
        "float_feed_bytes": float_feed,
        "params_bytes": sum(_aval_bytes(l)
                            for l in jtu.tree_leaves(variables.params)),
        "state_bytes": sum(_aval_bytes(l)
                           for l in jtu.tree_leaves(variables.state)),
        "slots_bytes": sum(_aval_bytes(l) for l in jtu.tree_leaves(slots)),
        "feed_bytes": sum(_aval_bytes(v) for v in feeds.values()),
    }


def _policy_step_bytes(cen: dict, policy: str) -> dict:
    """The class-model floor for one (family, policy): the baseline
    census with the saved-activation term discounted by what the
    policy stores in bf16 — same ``step_traffic`` the remat table
    banks, so the two tables price in the same currency."""
    saved = mixed_saved_bytes(cen["saved_bytes"], cen["boundary_bytes"],
                              cen["float_feed_bytes"], policy)
    base = dict(
        param_bytes=cen["params_bytes"], state_bytes=cen["state_bytes"],
        slot_bytes=cen["slots_bytes"], saved_activation_bytes=saved,
        feed_bytes=cen["feed_bytes"], train=True, recompute_passes=0)
    solo = step_traffic(collective_bytes=0, **base)
    dp_comm = expected_comm("dp", param_bytes=cen["params_bytes"],
                            state_bytes=cen["state_bytes"])
    dp = step_traffic(
        collective_bytes=dp_comm.required["all-reduce"][0], **base)
    return {
        "saved_activation_bytes": saved,
        "step_bytes": {"solo": solo["total_bytes"],
                       "dp": dp["total_bytes"]},
        "step_gbytes": {"solo": gbytes(solo["total_bytes"]),
                        "dp": gbytes(dp["total_bytes"])},
    }


def _error_probe(family: str, policy: str,
                 batch: int = PROBE_BATCH) -> float:
    """Deterministic concrete error probe: one loss+grad eval of the
    family at a tiny batch on fixed seeds, mixed (storage ``policy``)
    vs the f32 baseline; returns the max of the loss relative error
    and the GLOBAL gradient relative l2 (one norm over every leaf
    concatenated — a per-leaf linf would amplify single ReLU boundary
    flips into double-digit ratios on near-zero leaves and gate on
    probe noise instead of storage fidelity).  Everything is fixed —
    feeds from RandomState(0), a zero PRNG key for init and dropout —
    so the figure is reproducible and bankable."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.analysis.memcheck import _family_net
    from sparknet_tpu.common import Phase, get_config, set_config
    from sparknet_tpu.compiler.graph import NetVars, Network
    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES

    net_param, _ = _family_net(family, batch)
    net = Network(net_param, Phase.TRAIN)
    variables = net.init(jnp.zeros((2,), jnp.uint32))
    rs = np.random.RandomState(0)
    gf = GRAPH_SWEEP_FAMILIES.get(family)
    tokens = gf is not None and gf.feed == "tokens"
    feeds = {}
    for name, shape in net.feed_shapes().items():
        if name == "label":
            feeds[name] = jnp.asarray(
                rs.randint(0, 10, shape).astype(np.int32))
        elif tokens:
            feeds[name] = jnp.asarray(
                rs.randint(0, gf.vocab, shape).astype(np.int32))
        else:
            feeds[name] = jnp.asarray(rs.randn(*shape).astype(np.float32))
    rng = jnp.zeros((2,), jnp.uint32)

    @contextlib.contextmanager
    def policy_ctx(p):
        prior = get_config().activation_dtype
        set_config(activation_dtype=p)
        try:
            yield
        finally:
            set_config(activation_dtype=prior)

    def loss_and_grads(p):
        def loss_fn(params):
            _, _, loss = net.apply(
                NetVars(params=params, state=variables.state), feeds,
                rng, train=True)
            return loss

        with policy_ctx(p):
            val, grads = jax.jit(jax.value_and_grad(loss_fn))(
                variables.params)
        return jax.device_get(val), jax.device_get(grads)

    base_loss, base_grads = loss_and_grads("")
    mix_loss, mix_grads = loss_and_grads(policy)
    eps = 1e-12
    err = abs(float(mix_loss) - float(base_loss)) / (
        abs(float(base_loss)) + eps)
    sq_diff = sq_base = 0.0
    for gb, gm in zip(jax.tree_util.tree_leaves(base_grads),
                      jax.tree_util.tree_leaves(mix_grads)):
        gb = np.asarray(gb, dtype=np.float64)
        gm = np.asarray(gm, dtype=np.float64)
        sq_diff += float(np.sum((gm - gb) ** 2))
        sq_base += float(np.sum(gb ** 2))
    return max(err, sq_diff ** 0.5 / (sq_base ** 0.5 + eps))


def run_mixed_search(*, update: bool = False,
                     banked_path: str | None = None,
                     families: list | None = None, progress=None,
                     n_devices: int = 8) -> tuple:
    """Enumerate activation-storage policies per zoo family, score each
    chip-free on the byte model, gate on the concrete error probe, and
    bank the bytes-minimal SAFE winner
    (``docs/num_contracts/mixed_policy.json``).

    Selection walks policies in ascending modeled solo bytes (ties to
    the LIGHTER storage — narrower storage costs precision the byte
    model does not price) and stops at the first one whose probe error
    clears the family gate; ``"none"`` is always safe (error
    identically zero, no probe spent), so every family selects
    SOMETHING.  The headline family's winner must clear
    ``MIXED_DROP_FLOOR`` vs its own f32 baseline."""
    _pin_cpu_mesh(n_devices)
    from sparknet_tpu.analysis.bytecheck import (
        HEADLINE_FAMILY,
        SEARCH_BATCH_DEFAULT,
        SEARCH_BATCHES,
    )
    from sparknet_tpu.analysis.memcheck import _fit_family_names

    path = banked_path or MIXED_TABLE_PATH
    rel = os.path.relpath(path, _REPO) if path.startswith(_REPO) else path
    act_dtype = ACT_DTYPES[0]
    problems: list = []
    table: dict = {
        "policies": list(ACT_SEARCH_POLICIES),
        "act_dtypes": list(ACT_DTYPES),
        "probe_batch": PROBE_BATCH,
        "search_batches": {},
        "families": {},
        "selected": {},
        "headline": {"family": HEADLINE_FAMILY, "act_dtype": act_dtype,
                     "drop_floor": MIXED_DROP_FLOOR},
    }
    for family in (families or _fit_family_names()):
        batch = SEARCH_BATCHES.get(family, SEARCH_BATCH_DEFAULT)
        table["search_batches"][family] = batch
        if progress:
            progress(f"{family}/{act_dtype}")
        cen = _family_mixed_census(family, batch)
        scores = {p: _policy_step_bytes(cen, p)
                  for p in ACT_SEARCH_POLICIES}
        bad = act_monotonicity_violations(
            {p: s["saved_activation_bytes"] for p, s in scores.items()})
        for a, b in bad:
            problems.append({
                "rule": "num-mixed-nonmonotonic",
                "message": f"{family}: policy {b!r} models "
                           f"{scores[b]['saved_activation_bytes']:,} B "
                           f"saved, MORE than the lighter {a!r}'s "
                           f"{scores[a]['saved_activation_bytes']:,} B",
            })

        gate = error_gate(family)
        order = sorted(
            ACT_SEARCH_POLICIES,
            key=lambda p: (scores[p]["step_bytes"]["solo"],
                           ACT_SEARCH_POLICIES.index(p)))
        winner, winner_err = "none", 0.0
        for policy in order:
            if policy == "none":
                err = 0.0
            else:
                if progress:
                    progress(f"{family}/probe:{policy}")
                err = round(_error_probe(family, policy), 6)
            scores[policy]["probe_error"] = err
            if err <= gate:
                winner, winner_err = policy, err
                break
        table["families"][family] = {act_dtype: scores}

        none_b = scores["none"]["step_bytes"]["solo"]
        win_b = scores[winner]["step_bytes"]["solo"]
        drop = (none_b - win_b) / none_b if none_b else 0.0
        table["selected"][family] = {act_dtype: {
            "policy": winner,
            "probe_error": winner_err,
            "error_gate": gate,
            "step_bytes_solo": win_b,
            "step_gbytes_solo": gbytes(win_b),
            "drop_frac_vs_f32": round(drop, 4),
        }}
        if family == HEADLINE_FAMILY and drop < MIXED_DROP_FLOOR:
            problems.append({
                "rule": "num-mixed-no-gain",
                "message": f"selected policy {winner!r} drops the "
                           f"headline family's modeled step bytes by "
                           f"{drop:.1%} < the required "
                           f"{MIXED_DROP_FLOOR:.0%}",
            })

    manifest = {
        "subject": "mixed_policy",
        "contract": {"families": table["families"],
                     "selected": table["selected"]},
        "allow": {},
    }
    allow = _diff_or_missing(manifest, path, problems, update)
    if update:
        # the table file IS the manifest (consumers read it directly:
        # parallel/modes._banked_act_policy, bench.py's act-dtype arm)
        _write_manifest({**table, "allow": allow,
                         "contract": manifest["contract"]}, path)
    merged = {**inline_allows(), **allow}
    findings = [
        Finding(p["rule"], rel, 0, p["message"],
                suppressed=p["rule"] in merged)
        for p in problems
    ]
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, table


# ---------------------------------------------------------------------------
# The engine entry point
# ---------------------------------------------------------------------------


def run_numcheck(modes: list | None = None, *, update: bool = False,
                 banked_dir: str | None = None, n_devices: int = 8,
                 progress=None) -> tuple:
    """Census ``modes`` (default: all registered parallel modes) plus,
    on a full run, a presence check of the banked mixed-policy table
    (the search itself runs via ``--mixed`` — it is the leg with the
    concrete probes).  Returns ``(findings, manifests)``; with
    ``update=True`` the banked manifests (and SOURCES.json on a full
    default-dir run) are rewritten."""
    _pin_cpu_mesh(n_devices)

    from sparknet_tpu.parallel.modes import list_modes

    all_modes = list_modes()
    modes = list(modes) if modes else all_modes
    unknown = [m for m in modes if m not in all_modes]
    if unknown:
        raise KeyError(f"unknown mode(s): {', '.join(unknown)} "
                       f"(known: {', '.join(all_modes)})")
    banked = banked_dir or MANIFEST_DIR
    allow_inline = inline_allows()
    findings: list = []
    manifests: dict = {}
    for name in modes:
        if progress:
            progress(name)
        f, manifest = _check_mode(name, banked, update, n_devices,
                                  allow_inline)
        findings.extend(f)
        manifests[name] = manifest
        if update:
            _write_manifest(manifest, manifest_path(name, banked))

    full_run = set(modes) == set(all_modes)
    if full_run:
        mixed_path = os.path.join(banked, "mixed_policy.json")
        if not os.path.exists(mixed_path):
            findings.append(Finding(
                "num-manifest-missing",
                os.path.relpath(mixed_path, _REPO)
                if mixed_path.startswith(_REPO) else mixed_path, 0,
                "no banked mixed-policy table — run "
                "`python -m sparknet_tpu.analysis num --mixed --update`"))
    if update and full_run and banked == MANIFEST_DIR:
        # graftlint: disable-next-line=bank-guard -- SOURCES.json fingerprint for the num-manifest-fresh rule, a chip-free contract artifact
        with open(os.path.join(banked, "SOURCES.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(sources_fingerprint(), fh, indent=1, sort_keys=True)
            fh.write("\n")
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, manifests


def iter_rules() -> Iterator:
    yield from NUM_RULES.items()
