"""graftlint rule set: the repo's banking/obs/manifest contracts,
machine-checked.

Rules are AST heuristics, deliberately tuned to catch the in-tree shapes
that actually burned us — a rule that cries wolf gets suppressed into
uselessness, so each one documents its known blind spots instead of
chasing them.

Adding a rule: write ``def check_x(ctx) -> Iterator[(lineno, msg)]``,
decorate with ``@rule("rule-id", "one-line summary")``, add fixtures to
``tests/test_graftlint.py`` (positive, suppressed, clean) and a catalog
entry to ``docs/LINTING.md``.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from typing import Iterator

from sparknet_tpu.analysis.core import ModuleContext, call_name, rule

# ---------------------------------------------------------------------------
# bank-guard
# ---------------------------------------------------------------------------

# What counts as banked chip evidence: the *_last*.json ratchet files.
# Sweep outputs (tau_sweep_*.json) are CPU-runnable convergence
# artifacts, deliberately outside this pattern.
_EVIDENCE = re.compile(r"_last[a-z0-9_]*\.json")


def _is_write_open(call: ast.Call) -> bool:
    if call_name(call) != "open":
        return False
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    return isinstance(mode, str) and mode[:1] in ("w", "a", "x")


@rule(
    "bank-guard",
    "evidence files (docs/*_last*.json) may only be written through "
    "common.bank_guard, which diverts unmeasured runs away from docs/",
)
def check_bank_guard(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """CPU runs of evidence tools must never bank.  The blessed sink is
    ``sparknet_tpu.common.bank_guard(path, payload, measured=...)`` —
    it stamps and diverts rehearsal payloads to /tmp.
    This rule flags any direct write-mode ``open`` in a scope that
    mentions an evidence path, except inside ``bank_guard`` itself.
    Module-level evidence strings (path constants) are ambient: they
    arm every scope in the file.
    """
    module_evidence = any(
        _EVIDENCE.search(s) for s in ctx.module_strings())
    for scope in ctx.scopes():
        if scope.name == "bank_guard":
            continue
        has_evidence = module_evidence or any(
            _EVIDENCE.search(s.value) for s in scope.strings())
        if not has_evidence:
            continue
        for c in scope.calls():
            if _is_write_open(c):
                yield (
                    c.lineno,
                    "direct write to an evidence path — route it through "
                    "sparknet_tpu.common.bank_guard(path, payload, "
                    "measured=...) so unmeasured runs divert to /tmp "
                    "instead of overwriting banked chip evidence",
                )


# ---------------------------------------------------------------------------
# graph-manifest-fresh
# ---------------------------------------------------------------------------

# the graph-contract source surface: editing any of these changes what
# graphcheck lowers, so the banked manifests must be regenerated in the
# same PR (kept in sync with graphcheck.GRAPH_SOURCE_PATTERNS — spelled
# out here too so this module stays importable without graphcheck)
_GRAPH_SOURCE_DIR = "sparknet_tpu/parallel/"
_GRAPH_SOURCE_FILES = (
    "sparknet_tpu/models/zoo.py",
    "sparknet_tpu/analysis/graphcheck.py",
    "sparknet_tpu/analysis/comm_model.py",
    "sparknet_tpu/solvers/solver.py",
    "sparknet_tpu/solvers/updates.py",
    "sparknet_tpu/ops/pallas_kernels.py",
)
_REGEN = ("regenerate with `python -m sparknet_tpu.analysis graph "
          "--update`")


def _graph_source_rel(path: str) -> tuple[str, str] | None:
    """(repo_root, repo_relative_path) when ``path`` is part of the
    graph-contract source surface, else None."""
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_GRAPH_SOURCE_DIR) or rel in _GRAPH_SOURCE_FILES:
        return root, rel
    return None


@rule(
    "graph-manifest-fresh",
    "a PR touching parallel/, models/zoo.py, solvers/solver.py, "
    "solvers/updates.py or ops/pallas_kernels.py (or graphcheck itself) "
    "must regenerate the docs/graph_contracts/ manifests",
)
def check_graph_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The golden graph manifests are only worth diffing against if
    they describe the code as it is NOW: an edit to the parallel
    machinery or the zoo sweep that skips regeneration leaves future
    PRs diffing against a stale baseline.  ``graphcheck --update``
    banks a sha256 per source file in
    ``docs/graph_contracts/SOURCES.json``; this rule re-hashes the
    linted source and flags any mismatch.  Blind spot: an edit that
    reverts to the banked bytes passes (correctly — the lowered graphs
    are the banked ones again).
    """
    hit = _graph_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    src = os.path.join(root, "docs", "graph_contracts", "SOURCES.json")
    if not os.path.exists(src):
        yield (1, f"{rel} is graph-contract source but no manifests are "
                  f"banked (docs/graph_contracts/SOURCES.json missing) "
                  f"— {_REGEN}")
        return
    try:
        with open(src, encoding="utf-8") as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        yield (1, f"docs/graph_contracts/SOURCES.json unreadable — {_REGEN}")
        return
    want = recorded.get(rel)
    digest = hashlib.sha256(ctx.source.encode("utf-8")).hexdigest()
    if want is None:
        yield (1, f"{rel} is new graph-contract source not covered by "
                  f"the banked manifests — {_REGEN}")
    elif want != digest:
        yield (1, f"{rel} changed since the graph manifests were banked "
                  f"— {_REGEN}")


# ---------------------------------------------------------------------------
# obs-fenced-span
# ---------------------------------------------------------------------------


@rule(
    "obs-fenced-span",
    "a Recorder span around device work must close with a fence stamp "
    "(span.fence/fence_value) or declare host=True — unstamped walls "
    "are refused by the obs report",
)
def check_obs_fenced_span(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The obs Recorder (``sparknet_tpu/obs``) journals span walls as
    evidence, and the report renderer refuses any wall without a fence
    stamp — but a refused wall is a silently lost measurement, so the
    contract is also enforced at the source: every ``with ...span(...)``
    in a jax-importing module must either call ``<var>.fence(out)`` /
    ``<var>.fence_value(v)`` somewhere in its body or declare
    ``host=True`` (no device work enclosed).  A span with no ``as``
    binding can never be stamped and is flagged outright.

    Blind spot: a span variable handed to a helper that fences it
    elsewhere is flagged — fence where you time, or mark the span host.
    """
    if not ctx.imports_jax():
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            call = item.context_expr
            if not (isinstance(call, ast.Call)
                    and call_name(call) == "span"):
                continue
            host = any(
                kw.arg == "host" and isinstance(kw.value, ast.Constant)
                and kw.value.value is True for kw in call.keywords)
            if host:
                continue
            var = item.optional_vars
            if not isinstance(var, ast.Name):
                yield (
                    call.lineno,
                    "Recorder span without an `as` binding can never be "
                    "fence-stamped — bind it (`with rec.span(...) as "
                    "sp:`) and close with sp.fence(out), or declare "
                    "host=True for a host-only span",
                )
                continue
            fenced = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr in ("fence", "fence_value")
                and isinstance(n.func.value, ast.Name)
                and n.func.value.id == var.id
                for n in ast.walk(node))
            if not fenced:
                yield (
                    call.lineno,
                    f"span {var.id!r} closes without a fence stamp — "
                    "call sp.fence(out) on the enclosed program's own "
                    "output (common.value_fence contract), or declare "
                    "host=True if the span truly encloses no device "
                    "work; the obs report refuses unstamped walls",
                )


# ---------------------------------------------------------------------------
# mem-manifest-fresh
# ---------------------------------------------------------------------------

# the memory-contract source surface: editing any of these changes what
# memcheck traces (layer geometry, optimizer slot counts, donation,
# sharding divisors, pallas tiling) so the banked docs/mem_contracts/
# manifests must be regenerated in the same PR (kept in sync with
# memcheck.MEM_SOURCE_PATTERNS — spelled out here too so this module
# stays importable without memcheck)
_MEM_SOURCE_DIR = "sparknet_tpu/parallel/"
_MEM_SOURCE_FILES = (
    "sparknet_tpu/models/zoo.py",
    "sparknet_tpu/ops/pallas_kernels.py",
    "sparknet_tpu/ops/layout.py",
    "sparknet_tpu/solvers/solver.py",
    "sparknet_tpu/solvers/updates.py",
    "sparknet_tpu/analysis/memcheck.py",
    "sparknet_tpu/analysis/mem_model.py",
)
_MEM_REGEN = ("regenerate with `python -m sparknet_tpu.analysis mem "
              "--update` (+ `--fit --update` for the batch-fit table)")


def _mem_source_rel(path: str) -> tuple[str, str] | None:
    """(repo_root, repo_relative_path) when ``path`` is part of the
    memory-contract source surface, else None."""
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_MEM_SOURCE_DIR) or rel in _MEM_SOURCE_FILES:
        return root, rel
    return None


@rule(
    "mem-manifest-fresh",
    "a PR touching parallel/, models/zoo.py, ops/pallas_kernels.py, "
    "solvers/, or memcheck itself must regenerate the "
    "docs/mem_contracts/ manifests",
)
def check_mem_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The memory manifests predict what a step will hold in HBM; the
    serving engine's admission gate refuses loads off the banked
    batch-fit table.  A stale table is worse than none — it would veto
    (or wave through) loads against a model that no longer exists.  ``memcheck
    --update`` banks a sha256 per source file in
    ``docs/mem_contracts/SOURCES.json``; this rule re-hashes the linted
    source and flags any mismatch, exactly like ``graph-manifest-fresh``
    does for the graph contracts.  Blind spot: an edit that reverts to
    the banked bytes passes (correctly — the traced programs are the
    banked ones again).
    """
    hit = _mem_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    src = os.path.join(root, "docs", "mem_contracts", "SOURCES.json")
    if not os.path.exists(src):
        yield (1, f"{rel} is memory-contract source but no manifests are "
                  f"banked (docs/mem_contracts/SOURCES.json missing) "
                  f"— {_MEM_REGEN}")
        return
    try:
        with open(src, encoding="utf-8") as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        yield (1, f"docs/mem_contracts/SOURCES.json unreadable — {_MEM_REGEN}")
        return
    want = recorded.get(rel)
    digest = hashlib.sha256(ctx.source.encode("utf-8")).hexdigest()
    if want is None:
        yield (1, f"{rel} is new memory-contract source not covered by "
                  f"the banked manifests — {_MEM_REGEN}")
    elif want != digest:
        yield (1, f"{rel} changed since the memory manifests were banked "
                  f"— {_MEM_REGEN}")


# ---------------------------------------------------------------------------
# elastic-manifest-fresh
# ---------------------------------------------------------------------------

# The elastic source surface lives inside sparknet_tpu/parallel/, so the
# graph-/mem-manifest-fresh rules already hash-check its EDITS.  What
# they cannot see is COVERAGE: whether the width-parameterized elastic
# twin manifests (elastic_w*.json, ISSUE 8's >= 2 mesh widths) were
# ever banked in a family, and whether the banked SOURCES fingerprints
# fold elastic.py in at all (a SOURCES.json predating the elastic layer
# hash-passes every other file while silently not covering this one).
_ELASTIC_SOURCE = "sparknet_tpu/parallel/elastic.py"
_ELASTIC_MIN_WIDTHS = 2
_ELASTIC_REGEN = {
    "graph_contracts": "regenerate with `python -m sparknet_tpu.analysis "
                       "graph --update`",
    "mem_contracts": "regenerate with `python -m sparknet_tpu.analysis "
                     "mem --update`",
}


def _elastic_source_rel(path: str) -> tuple[str, str] | None:
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel == _ELASTIC_SOURCE:
        return root, rel
    return None


@rule(
    "elastic-manifest-fresh",
    "the elastic trainer (parallel/elastic.py) must be folded into the "
    "graph+mem SOURCES fingerprints with elastic_w* twin manifests "
    "banked at >= 2 mesh widths in both families",
)
def check_elastic_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The elastic twins are the proof that the comm/HBM contracts hold
    ACROSS mesh re-formation — one banked width would only prove the
    fixed-mesh case all over again.  graph-/mem-manifest-fresh already
    flag a stale elastic.py hash (it sits on their parallel/ surface);
    this rule owns the elastic-specific coverage: the banked
    SOURCES.json must record elastic.py at all, and each manifest
    family must carry at least ``_ELASTIC_MIN_WIDTHS`` elastic_w*
    twins.  Blind spot (deliberate): hash staleness is NOT re-checked
    here — one finding per stale file belongs to the dir-surface rules.
    """
    hit = _elastic_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    for fam, regen in _ELASTIC_REGEN.items():
        cdir = os.path.join(root, "docs", fam)
        src = os.path.join(cdir, "SOURCES.json")
        if not os.path.exists(src):
            yield (1, f"{rel} is elastic contract source but no "
                      f"manifests are banked (docs/{fam}/SOURCES.json "
                      f"missing) — {regen}")
            continue
        try:
            with open(src, encoding="utf-8") as f:
                recorded = json.load(f)
        except (OSError, ValueError):
            yield (1, f"docs/{fam}/SOURCES.json unreadable — {regen}")
            continue
        if rel not in recorded:
            yield (1, f"{rel} is not folded into the docs/{fam} SOURCES "
                      f"fingerprint — the banked manifests predate the "
                      f"elastic layer; {regen}")
        try:
            twins = [n for n in os.listdir(cdir)
                     if n.startswith("elastic_w") and n.endswith(".json")]
        except OSError:
            twins = []
        if len(twins) < _ELASTIC_MIN_WIDTHS:
            yield (1, f"docs/{fam} banks {len(twins)} elastic_w* twin "
                      f"manifest(s); the width-parameterized contract "
                      f"needs >= {_ELASTIC_MIN_WIDTHS} mesh widths — "
                      f"{regen}")


# ---------------------------------------------------------------------------
# serve-manifest-fresh
# ---------------------------------------------------------------------------

# Same shape as elastic-manifest-fresh, for the serving engine: the
# serve/ package is graph-/mem-contract source (its bucket programs ARE
# the serve_b* twins), so the banked SOURCES fingerprints must fold
# every serve/*.py in, and each manifest family must carry the full
# AOT bucket ladder — a SOURCES.json predating the serving layer
# hash-passes everything else while silently not covering it.
_SERVE_SOURCE_DIR = "sparknet_tpu/serve/"
_SERVE_MIN_BUCKETS = 4
_SERVE_REGEN = _ELASTIC_REGEN


def _serve_source_rel(path: str) -> tuple[str, str] | None:
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_SERVE_SOURCE_DIR) and rel.endswith(".py"):
        return root, rel
    return None


@rule(
    "serve-manifest-fresh",
    "the serving engine (sparknet_tpu/serve/) must be folded into the "
    "graph+mem SOURCES fingerprints with serve_b* twin manifests "
    "banked for the full AOT bucket ladder in both families",
)
def check_serve_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The serve twins pin the very programs the engine AOT-compiles —
    an unbanked bucket is a program no contract audits.  As with the
    elastic rule, hash STALENESS belongs to graph-/mem-manifest-fresh
    (serve/ sits on both dir surfaces); this rule owns coverage: the
    banked SOURCES.json must record this serve/ file at all, and each
    manifest family must carry >= ``_SERVE_MIN_BUCKETS`` serve_b*
    twins (the 1/8/64/256 ladder).
    """
    hit = _serve_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    for fam, regen in _SERVE_REGEN.items():
        cdir = os.path.join(root, "docs", fam)
        src = os.path.join(cdir, "SOURCES.json")
        if not os.path.exists(src):
            yield (1, f"{rel} is serving contract source but no "
                      f"manifests are banked (docs/{fam}/SOURCES.json "
                      f"missing) — {regen}")
            continue
        try:
            with open(src, encoding="utf-8") as f:
                recorded = json.load(f)
        except (OSError, ValueError):
            yield (1, f"docs/{fam}/SOURCES.json unreadable — {regen}")
            continue
        if rel not in recorded:
            yield (1, f"{rel} is not folded into the docs/{fam} SOURCES "
                      f"fingerprint — the banked manifests predate the "
                      f"serving layer; {regen}")
        try:
            twins = [n for n in os.listdir(cdir)
                     if n.startswith("serve_b") and n.endswith(".json")]
        except OSError:
            twins = []
        if len(twins) < _SERVE_MIN_BUCKETS:
            yield (1, f"docs/{fam} banks {len(twins)} serve_b* twin "
                      f"manifest(s); the AOT ladder contract needs >= "
                      f"{_SERVE_MIN_BUCKETS} buckets — {regen}")


# ---------------------------------------------------------------------------
# loop-manifest-fresh
# ---------------------------------------------------------------------------

# The production loop (sparknet_tpu/loop/) composes programs the
# contracts already audit — ElasticTrainer rounds (elastic_w* twins)
# and the engine's bucket forwards (serve_b* twins) — so it banks no
# twin manifests of its own.  But its modules ARE contract source (they
# decide which programs lower and with what feeds), so the banked
# SOURCES fingerprints must fold every loop/*.py in: a SOURCES.json
# predating the loop layer hash-passes everything else while silently
# not covering it.  Coverage only — no twin count (the twins belong to
# the elastic/serve rules).
_LOOP_SOURCE_DIR = "sparknet_tpu/loop/"
_LOOP_REGEN = _ELASTIC_REGEN


def _loop_source_rel(path: str) -> tuple[str, str] | None:
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_LOOP_SOURCE_DIR) and rel.endswith(".py"):
        return root, rel
    return None


@rule(
    "loop-manifest-fresh",
    "the production loop (sparknet_tpu/loop/) must be folded into the "
    "graph+mem SOURCES fingerprints in both contract families",
)
def check_loop_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """Coverage twin of serve-manifest-fresh for the train-to-serve
    loop.  Hash STALENESS belongs to graph-/mem-manifest-fresh (loop/
    sits on both dir surfaces); this rule owns coverage: the banked
    SOURCES.json must record this loop/ file at all.  No twin-manifest
    count — the loop lowers exclusively through programs the elastic_w*
    and serve_b* twins already pin.
    """
    hit = _loop_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    for fam, regen in _LOOP_REGEN.items():
        cdir = os.path.join(root, "docs", fam)
        src = os.path.join(cdir, "SOURCES.json")
        if not os.path.exists(src):
            yield (1, f"{rel} is loop contract source but no manifests "
                      f"are banked (docs/{fam}/SOURCES.json missing) — "
                      f"{regen}")
            continue
        try:
            with open(src, encoding="utf-8") as f:
                recorded = json.load(f)
        except (OSError, ValueError):
            yield (1, f"docs/{fam}/SOURCES.json unreadable — {regen}")
            continue
        if rel not in recorded:
            yield (1, f"{rel} is not folded into the docs/{fam} SOURCES "
                      f"fingerprint — the banked manifests predate the "
                      f"loop layer; {regen}")


# ---------------------------------------------------------------------------
# replica-manifest-fresh
# ---------------------------------------------------------------------------

# The replica router (serve/router.py) is the pod-scale layer over the
# engine: K single-device copies whose zero-collective placement is its
# OWN contract claim, pinned by the width-parameterized serve_r* twins
# (like the elastic trainer's elastic_w* widths).  serve-manifest-fresh
# already checks that router.py is folded into the SOURCES fingerprints
# (it sits on the serve/ surface); what it cannot see is whether the
# replica-width twins were ever banked — one width would only re-prove
# the single-copy serve_b* case.  Anchored on router.py alone so the
# pool-coverage finding lands once, not once per serve/ file.
_REPLICA_SOURCE = "sparknet_tpu/serve/router.py"
_REPLICA_MIN_WIDTHS = 2
_REPLICA_REGEN = _ELASTIC_REGEN


def _replica_source_rel(path: str) -> tuple[str, str] | None:
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel == _REPLICA_SOURCE:
        return root, rel
    return None


@rule(
    "replica-manifest-fresh",
    "the replica router (serve/router.py) must be folded into the "
    "graph+mem SOURCES fingerprints with serve_r* twin manifests "
    "banked at >= 2 pool widths in both families",
)
def check_replica_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The serve_r* twins pin the pod placement contract — K replicas'
    forwards lower with ZERO collectives between them (serving is
    embarrassingly parallel; any cross-replica comm is a placement
    bug).  One banked width would only re-prove the single-copy case,
    so each manifest family must carry >= ``_REPLICA_MIN_WIDTHS``
    widths, and the banked SOURCES.json must record router.py at all.
    Blind spot (deliberate): hash staleness is NOT re-checked here —
    that belongs to graph-/mem-manifest-fresh on the serve/ surface.
    """
    hit = _replica_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    for fam, regen in _REPLICA_REGEN.items():
        cdir = os.path.join(root, "docs", fam)
        src = os.path.join(cdir, "SOURCES.json")
        if not os.path.exists(src):
            yield (1, f"{rel} is pod-serving contract source but no "
                      f"manifests are banked (docs/{fam}/SOURCES.json "
                      f"missing) — {regen}")
            continue
        try:
            with open(src, encoding="utf-8") as f:
                recorded = json.load(f)
        except (OSError, ValueError):
            yield (1, f"docs/{fam}/SOURCES.json unreadable — {regen}")
            continue
        if rel not in recorded:
            yield (1, f"{rel} is not folded into the docs/{fam} SOURCES "
                      f"fingerprint — the banked manifests predate the "
                      f"replica layer; {regen}")
        try:
            twins = [n for n in os.listdir(cdir)
                     if n.startswith("serve_r") and n.endswith(".json")]
        except OSError:
            twins = []
        if len(twins) < _REPLICA_MIN_WIDTHS:
            yield (1, f"docs/{fam} banks {len(twins)} serve_r* twin "
                      f"manifest(s); the width-parameterized pool "
                      f"contract needs >= {_REPLICA_MIN_WIDTHS} "
                      f"widths — {regen}")


# ---------------------------------------------------------------------------
# paged-manifest-fresh
# ---------------------------------------------------------------------------

# The paged decode engine (serve/paged.py) is the cached token-serving
# layer: its contract claim is SHAPE STABILITY across occupancy — the
# decode step at occupancy 1 and at full arena must lower to the same
# program (that IS the zero-post-warmup-compiles guarantee, made
# machine-checkable), pinned by the occupancy-parameterized
# decode_paged_o* twins next to the decode_rect rectangle baseline.
# serve-manifest-fresh already checks that paged.py is folded into the
# graph+mem SOURCES fingerprints (it sits on the serve/ surface); what
# it cannot see is whether the occupancy twins were ever banked, nor
# the byte_contracts family (the capacity claim is a BYTES claim).
# Anchored on paged.py alone so the coverage finding lands once.
_PAGED_SOURCE = "sparknet_tpu/serve/paged.py"
_PAGED_MIN_OCCUPANCIES = 2
_PAGED_REGEN = {
    **_ELASTIC_REGEN,
    "byte_contracts": "regenerate with `python -m sparknet_tpu.analysis "
                      "bytes --update`",
}


def _paged_source_rel(path: str) -> tuple[str, str] | None:
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel == _PAGED_SOURCE:
        return root, rel
    return None


@rule(
    "paged-manifest-fresh",
    "the paged decode engine (serve/paged.py) must be folded into the "
    "graph+mem+byte SOURCES fingerprints with decode_paged_o* twins "
    "banked at >= 2 occupancies plus the decode_rect baseline in "
    "every family",
)
def check_paged_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The decode_paged_o* twins pin the occupancy shape-stability
    contract — the cached step's program must not depend on how many
    rows are live (occupancy changes DATA, never a shape), which is
    what keeps the recompile sentinel at zero across admission churn.
    One banked occupancy would prove nothing about stability, so each
    manifest family must carry >= ``_PAGED_MIN_OCCUPANCIES`` of them,
    plus the decode_rect baseline the A/B is priced against, and the
    banked SOURCES.json must record paged.py at all.  Blind spot
    (deliberate): hash staleness is NOT re-checked here — that belongs
    to graph-/mem-/byte-manifest-fresh on the serve/ surface.
    """
    hit = _paged_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    for fam, regen in _PAGED_REGEN.items():
        cdir = os.path.join(root, "docs", fam)
        src = os.path.join(cdir, "SOURCES.json")
        if not os.path.exists(src):
            yield (1, f"{rel} is paged-decode contract source but no "
                      f"manifests are banked (docs/{fam}/SOURCES.json "
                      f"missing) — {regen}")
            continue
        try:
            with open(src, encoding="utf-8") as f:
                recorded = json.load(f)
        except (OSError, ValueError):
            yield (1, f"docs/{fam}/SOURCES.json unreadable — {regen}")
            continue
        if rel not in recorded:
            yield (1, f"{rel} is not folded into the docs/{fam} SOURCES "
                      f"fingerprint — the banked manifests predate the "
                      f"paged decode layer; {regen}")
        try:
            names = os.listdir(cdir)
        except OSError:
            names = []
        twins = [n for n in names
                 if n.startswith("decode_paged_o") and n.endswith(".json")]
        if len(twins) < _PAGED_MIN_OCCUPANCIES:
            yield (1, f"docs/{fam} banks {len(twins)} decode_paged_o* "
                      f"twin manifest(s); the occupancy shape-stability "
                      f"contract needs >= {_PAGED_MIN_OCCUPANCIES} "
                      f"occupancies — {regen}")
        if "decode_rect.json" not in names:
            yield (1, f"docs/{fam} lacks the decode_rect baseline twin "
                      f"the paged A/B is priced against — {regen}")


# ---------------------------------------------------------------------------
# conc-manifest-fresh
# ---------------------------------------------------------------------------

# the concurrency-contract source surface: editing any of these can
# change what conccheck derives (lock declarations, guarded-by maps,
# acquisition edges, thread/process roles), so the banked
# docs/conc_contracts/ manifests must be regenerated in the same PR
# (kept in sync with conccheck.CONC_SOURCE_PATTERNS — spelled out here
# too so this module stays importable without conccheck)
_CONC_SOURCE_DIRS = (
    "sparknet_tpu/serve/",
    "sparknet_tpu/loop/",
    "sparknet_tpu/obs/",
)
_CONC_SOURCE_FILES = (
    "sparknet_tpu/data/pipeline.py",
    "sparknet_tpu/data/records.py",
    "sparknet_tpu/worker_store.py",
    "sparknet_tpu/common.py",
    "sparknet_tpu/_chaoslock.py",
    "sparknet_tpu/analysis/conc_model.py",
    "sparknet_tpu/analysis/conccheck.py",
)
_CONC_REGEN = ("regenerate with `python -m sparknet_tpu.analysis conc "
               "--update`")


def _conc_source_rel(path: str) -> tuple[str, str] | None:
    """(repo_root, repo_relative_path) when ``path`` is part of the
    concurrency-contract source surface, else None."""
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_CONC_SOURCE_DIRS) or rel in _CONC_SOURCE_FILES:
        return root, rel
    return None


@rule(
    "conc-manifest-fresh",
    "a PR touching the audited concurrency surface (serve/, loop/, "
    "obs/, the feed pipeline, common.py, or conccheck itself) must regenerate the docs/conc_contracts/ "
    "manifests",
)
def check_conc_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The concurrency manifests are what the chaos-schedule dryrun
    gate diffs observed lock acquisitions against (obs/__main__.py
    ``_chaos_gate``): a stale static graph either misses a real edge
    (the gate cries wolf) or blesses one that no longer exists.
    ``conc --update`` banks a sha256 per audited file in
    ``docs/conc_contracts/SOURCES.json``; this rule re-hashes the
    linted source and flags any mismatch — the mem-manifest-fresh
    mechanism on the concurrency surface.  Blind spot: an edit that
    reverts to the banked bytes passes (correctly — the derived
    contracts are the banked ones again)."""
    hit = _conc_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    src = os.path.join(root, "docs", "conc_contracts", "SOURCES.json")
    if not os.path.exists(src):
        yield (1, f"{rel} is concurrency-contract source but no "
                  f"manifests are banked (docs/conc_contracts/"
                  f"SOURCES.json missing) — {_CONC_REGEN}")
        return
    try:
        with open(src, encoding="utf-8") as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        yield (1, f"docs/conc_contracts/SOURCES.json unreadable — "
                  f"{_CONC_REGEN}")
        return
    want = recorded.get(rel)
    digest = hashlib.sha256(ctx.source.encode("utf-8")).hexdigest()
    if want is None:
        yield (1, f"{rel} is new concurrency-contract source not "
                  f"covered by the banked manifests — {_CONC_REGEN}")
    elif want != digest:
        yield (1, f"{rel} changed since the concurrency manifests were "
                  f"banked — {_CONC_REGEN}")


# ---------------------------------------------------------------------------
# byte-manifest-fresh
# ---------------------------------------------------------------------------

# the byte-contract source surface: editing any of these changes what
# bytecheck censuses (layer geometry, optimizer traffic, layout, the
# comm windows, the block-boundary save tags) so the banked
# docs/byte_contracts/ manifests — census, headline reconciliation,
# AND the remat-policy table Config.remat consumers read — must be
# regenerated in the same PR (kept in sync with
# bytecheck.BYTE_SOURCE_PATTERNS — spelled out here too so this module
# stays importable without bytecheck)
_BYTE_SOURCE_DIRS = (
    "sparknet_tpu/parallel/",
    "sparknet_tpu/serve/",
)
_BYTE_SOURCE_FILES = (
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
_BYTE_REGEN = ("regenerate with `python -m sparknet_tpu.analysis bytes "
               "--update` (+ `--remat --update` for the policy table)")


def _byte_source_rel(path: str) -> tuple[str, str] | None:
    """(repo_root, repo_relative_path) when ``path`` is part of the
    byte-contract source surface, else None."""
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_BYTE_SOURCE_DIRS) or rel in _BYTE_SOURCE_FILES:
        return root, rel
    return None


@rule(
    "byte-manifest-fresh",
    "a PR touching the byte-contract surface (parallel/, serve/, "
    "compiler/graph.py, models/zoo.py, ops/, solvers/, or bytecheck "
    "itself) must regenerate the docs/byte_contracts/ manifests",
)
def check_byte_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The byte manifests are the repo's step-bytes contract: the
    headline reconciliation says the analytic census still describes
    the program the bench measured, and the remat-policy table is what
    ``Config.remat`` actually routes (parallel/modes.
    _banked_remat_policy).  A stale table silently runs yesterday's
    schedule.  ``bytes --update`` banks a sha256 per source file in
    ``docs/byte_contracts/SOURCES.json``; this rule re-hashes the
    linted source and flags any mismatch — the mem-manifest-fresh
    mechanism on the traffic surface.  Blind spot: an edit that
    reverts to the banked bytes passes (correctly — the censused
    programs are the banked ones again)."""
    hit = _byte_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    src = os.path.join(root, "docs", "byte_contracts", "SOURCES.json")
    if not os.path.exists(src):
        yield (1, f"{rel} is byte-contract source but no manifests are "
                  f"banked (docs/byte_contracts/SOURCES.json missing) "
                  f"— {_BYTE_REGEN}")
        return
    try:
        with open(src, encoding="utf-8") as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        yield (1, f"docs/byte_contracts/SOURCES.json unreadable — "
                  f"{_BYTE_REGEN}")
        return
    want = recorded.get(rel)
    digest = hashlib.sha256(ctx.source.encode("utf-8")).hexdigest()
    if want is None:
        yield (1, f"{rel} is new byte-contract source not covered by "
                  f"the banked manifests — {_BYTE_REGEN}")
    elif want != digest:
        yield (1, f"{rel} changed since the byte manifests were banked "
                  f"— {_BYTE_REGEN}")


# ---------------------------------------------------------------------------
# num-manifest-fresh
# ---------------------------------------------------------------------------

# the numerics-contract source surface: editing any of these changes
# what numcheck censuses (the dtype flow of the traced programs, the
# activation-storage cast sites, the policy semantics in common.py, or
# the classification rules themselves) so the banked
# docs/num_contracts/ manifests — per-mode census AND the mixed-policy
# table Config.activation_dtype consumers read — must be regenerated
# in the same PR (kept in sync with numcheck.NUM_SOURCE_PATTERNS —
# spelled out here too so this module stays importable without
# numcheck)
_NUM_SOURCE_DIRS = (
    "sparknet_tpu/parallel/",
    "sparknet_tpu/serve/",
)
_NUM_SOURCE_FILES = (
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
_NUM_REGEN = ("regenerate with `python -m sparknet_tpu.analysis num "
              "--update` (+ `--mixed --update` for the policy table)")


def _num_source_rel(path: str) -> tuple[str, str] | None:
    """(repo_root, repo_relative_path) when ``path`` is part of the
    numerics-contract source surface, else None."""
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel.startswith(_NUM_SOURCE_DIRS) or rel in _NUM_SOURCE_FILES:
        return root, rel
    return None


@rule(
    "num-manifest-fresh",
    "a PR touching the numerics-contract surface (parallel/, serve/, "
    "compiler/graph.py, common.py, models/zoo.py, ops/, solvers/, or "
    "numcheck itself) must regenerate the docs/num_contracts/ "
    "manifests",
)
def check_num_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The num manifests are the repo's precision contract: every
    traced mode's accumulation/reduction/cast census is drift-pinned,
    and the mixed-policy table is what ``Config.activation_dtype``
    actually routes (parallel/modes._banked_act_policy).  A stale
    table silently stores yesterday's precision.  ``num --update``
    banks a sha256 per source file in ``docs/num_contracts/
    SOURCES.json``; this rule re-hashes the linted source and flags
    any mismatch — the byte-manifest-fresh mechanism on the dtype
    surface.  Blind spot: an edit that reverts to the banked census
    passes (correctly — the censused programs are the banked ones
    again)."""
    hit = _num_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    src = os.path.join(root, "docs", "num_contracts", "SOURCES.json")
    if not os.path.exists(src):
        yield (1, f"{rel} is numerics-contract source but no manifests "
                  f"are banked (docs/num_contracts/SOURCES.json "
                  f"missing) — {_NUM_REGEN}")
        return
    try:
        with open(src, encoding="utf-8") as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        yield (1, f"docs/num_contracts/SOURCES.json unreadable — "
                  f"{_NUM_REGEN}")
        return
    want = recorded.get(rel)
    digest = hashlib.sha256(ctx.source.encode("utf-8")).hexdigest()
    if want is None:
        yield (1, f"{rel} is new numerics-contract source not covered "
                  f"by the banked manifests — {_NUM_REGEN}")
    elif want != digest:
        yield (1, f"{rel} changed since the num manifests were banked "
                  f"— {_NUM_REGEN}")


# ---------------------------------------------------------------------------
# ctl-manifest-fresh
# ---------------------------------------------------------------------------

# the control-plane contract surface: editing any of these changes what
# the scenario replay derives (burn-window math, controller decision
# order, the traffic programs themselves, or the gate manifest the
# engine loads), so the banked docs/ctl_contracts/ action traces must
# be regenerated in the same PR (kept in sync with SOURCE_FILES in
# tools/ctl_scenarios.py — spelled out here too so this module stays
# importable without the harness)
_CTL_SOURCES = (
    "sparknet_tpu/obs/burn.py",
    "sparknet_tpu/loop/autoctl.py",
    "tools/ctl_scenarios.py",
)
# non-python source the linter never visits: re-hashed from disk on any
# surface hit (the manifest decides every gate's bound and id)
_CTL_DATA_SOURCE = "docs/slo_manifest.json"
_CTL_SCENARIOS = ("diurnal_ramp", "flash_crowd", "straggler_storm",
                  "poison_canary")
_CTL_REGEN = "regenerate with `python tools/ctl_scenarios.py --update`"


def _ctl_source_rel(path: str) -> tuple[str, str] | None:
    """(repo_root, repo_relative_path) when ``path`` is part of the
    control-plane contract surface, else None.  Two anchors: the
    surface spans the package (burn engine + controller) AND tools/
    (the replay harness that banks the traces)."""
    norm = os.path.abspath(path).replace(os.sep, "/")
    for anchor in ("/sparknet_tpu/", "/tools/"):
        idx = norm.rfind(anchor)
        if idx < 0:
            continue
        root, rel = norm[:idx], norm[idx + 1:]
        if rel in _CTL_SOURCES:
            return root, rel
    return None


@rule(
    "ctl-manifest-fresh",
    "a PR touching the control-plane surface (obs/burn.py, "
    "loop/autoctl.py, tools/ctl_scenarios.py, or docs/slo_manifest."
    "json) must regenerate the docs/ctl_contracts/ action traces",
)
def check_ctl_manifest_fresh(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """The ctl manifests are the controller's banked behavior: the
    exact action trace each scenario replay must reproduce before
    ``obs dryrun --ctl`` passes.  A stale trace either blesses
    yesterday's decision order or fails a correct controller against
    retired expectations.  ``tools/ctl_scenarios.py --update`` banks a
    sha256 per source file in ``docs/ctl_contracts/SOURCES.json``;
    this rule re-hashes the linted source (plus the gate manifest,
    which the linter never visits as python) and flags any mismatch —
    the conc-manifest-fresh mechanism on the control surface.  Blind
    spot: an edit that reverts to the banked bytes passes (correctly —
    the derived traces are the banked ones again)."""
    hit = _ctl_source_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    src = os.path.join(root, "docs", "ctl_contracts", "SOURCES.json")
    if not os.path.exists(src):
        yield (1, f"{rel} is control-plane contract source but no "
                  f"traces are banked (docs/ctl_contracts/SOURCES.json "
                  f"missing) — {_CTL_REGEN}")
        return
    try:
        with open(src, encoding="utf-8") as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        yield (1, f"docs/ctl_contracts/SOURCES.json unreadable — "
                  f"{_CTL_REGEN}")
        return
    want = recorded.get(rel)
    digest = hashlib.sha256(ctx.source.encode("utf-8")).hexdigest()
    if want is None:
        yield (1, f"{rel} is new control-plane contract source not "
                  f"covered by the banked traces — {_CTL_REGEN}")
    elif want != digest:
        yield (1, f"{rel} changed since the ctl traces were banked — "
                  f"{_CTL_REGEN}")
    # the gate manifest is data, not a linted module — re-hash it from
    # disk while we are on a surface hit so a bound change cannot ride
    # in without a re-bank
    data = os.path.join(root, _CTL_DATA_SOURCE)
    try:
        with open(data, "rb") as f:
            data_digest = hashlib.sha256(f.read()).hexdigest()
    except OSError:
        data_digest = None
    if recorded.get(_CTL_DATA_SOURCE) != data_digest:
        yield (1, f"{_CTL_DATA_SOURCE} changed since the ctl traces "
                  f"were banked — {_CTL_REGEN}")
    for name in _CTL_SCENARIOS:
        if not os.path.exists(os.path.join(
                root, "docs", "ctl_contracts", f"{name}.json")):
            yield (1, f"docs/ctl_contracts/{name}.json missing — the "
                      f"scenario catalog banks all four traces — "
                      f"{_CTL_REGEN}")


# ---------------------------------------------------------------------------
# feed-shm-cleanup
# ---------------------------------------------------------------------------

# function names that count as a cleanup path: unlink() reached from any
# of these always runs on teardown (finally-block unlinks qualify too)
_SHM_CLEANUP_SCOPES = frozenset(
    {"close", "unlink", "cleanup", "_cleanup", "__exit__", "__del__",
     "teardown", "tearDown"})


def _creates_shared_memory(call: ast.Call) -> bool:
    if call_name(call) != "SharedMemory":
        return False
    return any(kw.arg == "create" and isinstance(kw.value, ast.Constant)
               and kw.value.value is True for kw in call.keywords)


def _has_finally_unlink(tree: ast.AST) -> bool:
    for n in ast.walk(tree):
        if isinstance(n, ast.Try):
            for stmt in n.finalbody:
                for sub in ast.walk(stmt):
                    if (isinstance(sub, ast.Call)
                            and call_name(sub) == "unlink"):
                        return True
    return False


def _has_cleanup_scope_unlink(ctx: ModuleContext) -> bool:
    for scope in ctx.scopes():
        if scope.name not in _SHM_CLEANUP_SCOPES:
            continue
        if any(call_name(c) == "unlink" for c in scope.calls()):
            return True
    return False


@rule(
    "feed-shm-cleanup",
    "SharedMemory(create=True) must be paired with an unlink() on a "
    "finally/close teardown path — /dev/shm segments outlive the "
    "process and leak host RAM",
)
def check_feed_shm_cleanup(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """A shared-memory ring that dies without ``unlink`` leaves its
    segment pinned in ``/dev/shm`` until reboot — on the evidence box
    that is training-batch-sized host RAM gone per leaked run, invisible
    until allocation fails mid-window.  Any module that calls
    ``SharedMemory(create=True)`` must also call ``unlink()`` somewhere
    teardown-shaped: inside a ``finally`` block, or inside a function
    named like a cleanup path (``close``/``unlink``/``cleanup``/
    ``__exit__``/``__del__``/``teardown``).  Attach-side opens
    (``SharedMemory(name=...)``, no ``create=True``) are exempt — the
    creator owns the lifetime (``data/pipeline.py`` contract).

    Blind spot: an unlink inside an ordinary helper the teardown calls
    indirectly is not recognized — route it through a conventionally
    named cleanup method (which is also where readers look for it).
    """
    has_cleanup = (_has_finally_unlink(ctx.tree)
                   or _has_cleanup_scope_unlink(ctx))
    for n in ast.walk(ctx.tree):
        if isinstance(n, ast.Call) and _creates_shared_memory(n):
            if not has_cleanup:
                yield (
                    n.lineno,
                    "SharedMemory(create=True) with no unlink() on any "
                    "finally/close teardown path in this module — the "
                    "segment outlives the process in /dev/shm; pair "
                    "creation with unlink in a close()/finally path "
                    "(see data/pipeline.py ProcessPipeline.close)",
                )


# ---------------------------------------------------------------------------
# obs-vocab-coverage
# ---------------------------------------------------------------------------

# The obs journal schema (sparknet_tpu/obs/schema.py EVENTS) is the
# vocabulary three consumers must agree on: the emitters (schema-checked
# at write time), the report renderer (obs/report.py), and the human
# contract (docs/OBSERVABILITY.md).  A name added to EVENTS but not to
# the renderer silently vanishes from every report; one missing from the
# docs is an undocumented wire format.  Anchored on schema.py alone so
# the finding lands once, at the offending EVENTS key's own line.
_OBS_SCHEMA_SOURCE = "sparknet_tpu/obs/schema.py"
_OBS_REPORT_REL = "sparknet_tpu/obs/report.py"
_OBS_DOC_REL = "docs/OBSERVABILITY.md"


def _obs_schema_rel(path: str) -> tuple[str, str] | None:
    norm = os.path.abspath(path).replace(os.sep, "/")
    idx = norm.rfind("/sparknet_tpu/")
    if idx < 0:
        return None
    root, rel = norm[:idx], norm[idx + 1:]
    if rel == _OBS_SCHEMA_SOURCE:
        return root, rel
    return None


def _events_keys(tree: ast.AST) -> list[tuple[str, int]]:
    """``(name, lineno)`` per string key of the module-level EVENTS
    dict literal (plain or annotated assignment)."""
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.AnnAssign):
            target, value = node.target, node.value
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            target, value = node.targets[0], node.value
        else:
            continue
        if (isinstance(target, ast.Name) and target.id == "EVENTS"
                and isinstance(value, ast.Dict)):
            return [(k.value, k.lineno) for k in value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)]
    return []


@rule(
    "obs-vocab-coverage",
    "every obs schema event name must be rendered by obs/report.py (as "
    "a quoted literal) and documented in docs/OBSERVABILITY.md (as a "
    "backticked term)",
)
def check_obs_vocab_coverage(ctx: ModuleContext) -> Iterator[tuple[int, str]]:
    """Vocabulary drift guard for the obs journal.  For each key of
    schema.py's EVENTS dict: ``obs/report.py`` must contain the name as
    a quoted string literal (``"name"`` or ``'name'`` — how the
    renderer dispatches on ``ev.get("event")``), and
    ``docs/OBSERVABILITY.md`` must contain it backticked (the event
    vocabulary table).  Resolved from this file's own repo root, so
    fixture trees exercise both directions without touching the real
    repo.  Blind spot (deliberate): a literal inside a dead branch of
    report.py satisfies the check — renderer CORRECTNESS is pinned by
    the golden-report test, not a lint heuristic.
    """
    hit = _obs_schema_rel(ctx.path)
    if hit is None:
        return
    root, rel = hit
    names = _events_keys(ctx.tree)
    if not names:
        yield (1, f"{rel} declares no parseable module-level EVENTS "
                  "dict literal — the vocabulary-coverage contract "
                  "has nothing to check")
        return
    consumers = []
    for crel in (_OBS_REPORT_REL, _OBS_DOC_REL):
        try:
            with open(os.path.join(root, crel), encoding="utf-8") as f:
                consumers.append((crel, f.read()))
        except OSError:
            yield (1, f"{crel} missing or unreadable next to {rel} — "
                      "every EVENTS name must be rendered and "
                      "documented there")
            consumers.append((crel, None))
    for name, lineno in names:
        for crel, text in consumers:
            if text is None:
                continue
            hits = (f'"{name}"' in text or f"'{name}'" in text
                    if crel == _OBS_REPORT_REL else f"`{name}`" in text)
            if not hits:
                what = ("rendered as a quoted literal"
                        if crel == _OBS_REPORT_REL
                        else "documented as a backticked term")
                yield (lineno, f"obs event {name!r} is in the schema "
                               f"vocabulary but not {what} in {crel} — "
                               "events must never silently vanish from "
                               "reports or docs")
