"""conccheck — static concurrency contracts for the serving/feed/loop
plane (``python -m sparknet_tpu.analysis conc``).

The fourth analysis engine (graftlint / graphcheck / memcheck are the
other three; docs/LINTING.md "Concurrency contracts").  Three legs over
the :mod:`~sparknet_tpu.analysis.conc_model` extraction:

**(a) lock discipline** — for every class owning a lock, infer the
per-attribute guarded-by map from where ``self._*`` writes sit relative
to ``with <lock>:`` scopes, then flag writes that (i) skip a lock the
same attribute is guarded by elsewhere, or (ii) run with no lock at all
in code reachable from a second thread/process entry point
(``Thread(target=...)``/``Process(target=...)`` roots).  ``*_locked``
methods are caller-held by repo convention.  Suppressions are inline
and must carry a reason: ``# conccheck: unguarded=<why>``.

**(b) lock order + blocking calls** — build the static acquisition
graph (nested ``with``-lock scopes, closed over calls across the
audited modules with light type inference), fail on any cycle, and
fail on blocking calls made while holding a lock: AOT ``.compile()``,
zero-arg ``queue.get()`` with no timeout, zero-arg ``.join()``,
shared-memory ``.unlink()`` — PR 10's "compile on the caller's thread,
execute drained tickets OUTSIDE the lock" rules, machine-checked.  The
thread/process roles also machine-checks "ring workers never touch
jax" (``conc-jax-in-worker``).

**(c) banked manifests** — the acquisition graph and the roles are
banked as ``docs/conc_contracts/{lock_graph,roles}.json`` with a
``SOURCES.json`` fingerprint (the ``conc-manifest-fresh`` graftlint
rule refuses stale banks; regenerate with ``--update``).  The chaos
scheduler (``SPARKNET_CHAOS_SCHED``, sparknet_tpu/_chaoslock.py) diffs
*observed* acquisition edges against the banked static graph during
``obs dryrun --serve/--replica/--loop``.

Zero chip time; stdlib-only imports (the analysis package contract).
"""

from __future__ import annotations

import hashlib
import json
import os
import re

from sparknet_tpu.analysis.conc_model import (
    FuncModel,
    ModuleModel,
    build_model,
)
from sparknet_tpu.analysis.core import Finding

__all__ = [
    "CONC_RULES",
    "CONC_SOURCE_PATTERNS",
    "MANIFEST_DIR",
    "iter_rules",
    "run_conccheck",
    "sources_fingerprint",
]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MANIFEST_DIR = os.path.join(_REPO, "docs", "conc_contracts")

CONC_RULES = {
    "conc-unguarded-write": (
        "shared-attribute write without the inferred lock in a class "
        "that owns one (suppress: `# conccheck: unguarded=<why>`)"),
    "conc-lock-order-cycle": (
        "cycle in the static lock-acquisition graph (AB-BA deadlock "
        "shape)"),
    "conc-blocking-under-lock": (
        "blocking call while holding a lock: .compile(), zero-arg "
        ".get()/.join() with no timeout, or .unlink() (suppress: "
        "`# conccheck: blocking=<why>`)"),
    "conc-jax-in-worker": (
        "jax touched in code reachable from a Process(target=...) "
        "worker — ring workers never touch jax (suppress: "
        "`# conccheck: jax=<why>`)"),
    "conc-manifest-missing": (
        "docs/conc_contracts/ manifest missing — run `python -m "
        "sparknet_tpu.analysis conc --update`"),
    "conc-manifest-drift": (
        "static concurrency contract drifted from the banked "
        "manifest — inspect, then re-bank with `--update`"),
}

# the audited surface (dirs end with "/"); keep in sync with
# _CONC_SOURCE_* in sparknet_tpu/analysis/rules.py (conc-manifest-fresh)
CONC_SOURCE_PATTERNS = (
    "sparknet_tpu/serve/",
    "sparknet_tpu/loop/",
    "sparknet_tpu/obs/",
    "sparknet_tpu/data/pipeline.py",
    "sparknet_tpu/data/records.py",
    "sparknet_tpu/worker_store.py",
    "sparknet_tpu/common.py",
    "sparknet_tpu/_chaoslock.py",
    "sparknet_tpu/analysis/conc_model.py",
    "sparknet_tpu/analysis/conccheck.py",
)

# name-match fallback for attribute calls with no type evidence skips
# ubiquitous container/str/thread method names — they would resolve to
# unrelated audited methods and flood the graph with phantom edges
_NAME_MATCH_BLOCKLIST = frozenset({
    "add", "acquire", "append", "clear", "copy", "count", "decode",
    "discard", "encode", "endswith", "exists", "extend", "flush",
    "format", "get", "index", "insert", "is_set", "items", "join",
    "keys", "lower", "mkdir", "notify", "notify_all", "open", "pop",
    "put", "read", "readline", "release", "remove", "replace",
    "reverse", "set", "sort", "split", "start", "startswith", "strip",
    "touch", "update", "upper", "values", "wait", "write",
})

_SUPPRESS_RE = re.compile(
    r"#\s*conccheck:\s*(unguarded|blocking|order|jax)\s*=\s*(\S.*)")

_SUPPRESS_KIND = {
    "conc-unguarded-write": "unguarded",
    "conc-blocking-under-lock": "blocking",
    "conc-lock-order-cycle": "order",
    "conc-jax-in-worker": "jax",
}


def iter_rules():
    yield from sorted(CONC_RULES.items())


# ---------------------------------------------------------------------------
# source collection + fingerprint
# ---------------------------------------------------------------------------


def _collect_files(repo: str, patterns=CONC_SOURCE_PATTERNS
                   ) -> dict[str, str]:
    """rel-path -> source for every audited .py file."""
    out: dict[str, str] = {}
    for pat in patterns:
        full = os.path.join(repo, pat)
        if pat.endswith("/"):
            if not os.path.isdir(full):
                continue
            for name in sorted(os.listdir(full)):
                if name.endswith(".py"):
                    rel = pat + name
                    with open(os.path.join(full, name),
                              encoding="utf-8") as f:
                        out[rel] = f.read()
        elif os.path.isfile(full):
            with open(full, encoding="utf-8") as f:
                out[pat] = f.read()
    return out


def sources_fingerprint(repo: str | None = None) -> dict[str, str]:
    """sha256 per audited file (the SOURCES.json payload)."""
    files = _collect_files(repo or _REPO)
    return {rel: hashlib.sha256(src.encode("utf-8")).hexdigest()
            for rel, src in sorted(files.items())}


# ---------------------------------------------------------------------------
# cross-module resolution
# ---------------------------------------------------------------------------


class _Index:
    """Global call-resolution tables over every audited module."""

    def __init__(self, models: dict[str, ModuleModel]):
        self.models = models
        self.methods_by_name: dict[str, list[str]] = {}
        self.class_methods: dict[str, dict[str, str]] = {}
        self.attr_classes: dict[str, set[str]] = {}
        self.funcs: dict[str, FuncModel] = {}
        self.dotted_rel: dict[str, str] = {}
        self.subclasses: dict[str, set[str]] = {}
        for rel, m in models.items():
            dotted = rel[:-3].replace("/", ".") if rel.endswith(".py") \
                else rel.replace("/", ".")
            self.dotted_rel[dotted] = rel
            for qual, fm in m.functions.items():
                key = m.key(qual)
                self.funcs[key] = fm
                if fm.cls and qual.count(".") == 1:
                    cls, meth = qual.split(".", 1)
                    self.class_methods.setdefault(cls, {})[meth] = key
                    self.methods_by_name.setdefault(
                        meth, []).append(key)
            for cls, types in m.attr_types.items():
                for attr, tname in types.items():
                    self.attr_classes.setdefault(attr, set()).add(tname)
            for cls, bases in m.class_bases.items():
                for base in bases:
                    self.subclasses.setdefault(base, set()).add(cls)
        # transitive closure: a receiver typed as a base class can hold
        # any subclass, so its calls resolve to every override
        changed = True
        while changed:
            changed = False
            for base, subs in list(self.subclasses.items()):
                for sub in list(subs):
                    extra = self.subclasses.get(sub, set()) - subs
                    if extra:
                        subs |= extra
                        changed = True

    def module_func(self, m: ModuleModel, name: str) -> str | None:
        if name in m.functions and m.functions[name].cls is None:
            return m.key(name)
        return None

    def resolve(self, call, m: ModuleModel, fm: FuncModel) -> list[str]:
        """Call site -> candidate function keys (over-approximate)."""
        if call.kind == "bare":
            own = self.module_func(m, call.name)
            if own:
                return [own]
            alias = m.import_aliases.get(call.name)
            if alias:
                mod, orig = alias
                rel = self.dotted_rel.get(mod)
                if rel:
                    other = self.models[rel]
                    target = self.module_func(other, orig)
                    if target:
                        return [target]
            return []
        if call.kind == "self" and fm.cls:
            own = self.class_methods.get(fm.cls, {}).get(call.name)
            if own:
                return [own]
            return []
        # attribute call: typed receiver first
        classes: set[str] = set()
        if call.base_attr and call.base_attr in self.attr_classes:
            classes |= self.attr_classes[call.base_attr]
        if call.base_name:
            loc = fm.local_types.get(call.base_name)
            if loc:
                classes.add(loc)
        if classes:
            # subclass closure: base-typed receivers dispatch to every
            # audited override (over-approximate, the sound direction)
            for c in sorted(classes):
                classes = classes | self.subclasses.get(c, set())
            return [self.class_methods[c][call.name]
                    for c in sorted(classes)
                    if call.name in self.class_methods.get(c, {})]
        if call.name in _NAME_MATCH_BLOCKLIST:
            return []
        return list(self.methods_by_name.get(call.name, ()))


def _first_acquires(index: _Index) -> dict[str, set[str]]:
    """For every function: the locks it can acquire while the CALLER's
    lock is still the innermost held one (direct top-level acquires
    plus, transitively, top-level calls).  Matches the chaos recorder's
    (stack top, new) edge semantics."""
    memo: dict[str, set[str]] = {}

    def fa(key: str, seen: frozenset) -> set[str]:
        if key in memo:
            return memo[key]
        if key in seen:
            return set()
        fm = index.funcs[key]
        m = index.models[key.split("::", 1)[0]]
        out: set[str] = set()
        for acq in fm.acquires:
            if not acq.held:
                out.add(acq.lock)
        for call in fm.calls:
            if call.held:
                continue
            for target in index.resolve(call, m, fm):
                out |= fa(target, seen | {key})
        memo[key] = out
        return out

    for key in index.funcs:
        fa(key, frozenset())
    return memo


def _build_edges(index: _Index) -> dict[tuple[str, str],
                                        tuple[str, int]]:
    """The static acquisition graph: (outer, inner) -> witness site."""
    firstacq = _first_acquires(index)
    edges: dict[tuple[str, str], tuple[str, int]] = {}

    def note(outer: str, inner: str, rel: str, lineno: int) -> None:
        if inner == outer:
            return
        edges.setdefault((outer, inner), (rel, lineno))

    for key, fm in index.funcs.items():
        rel = key.split("::", 1)[0]
        m = index.models[rel]
        for acq in fm.acquires:
            if acq.held and acq.lock not in acq.held:
                note(acq.held[-1], acq.lock, rel, acq.lineno)
        for call in fm.calls:
            if not call.held:
                continue
            top = call.held[-1]
            for target in index.resolve(call, m, fm):
                for inner in firstacq.get(target, ()):
                    if inner not in call.held:
                        note(top, inner, rel, call.lineno)
    return edges


def _find_cycles(edges) -> list[list[str]]:
    """Every elementary cycle reachable by DFS (deduped by node set)."""
    graph: dict[str, list[str]] = {}
    for (a, b) in edges:
        graph.setdefault(a, []).append(b)
    cycles: list[list[str]] = []
    seen_sets: set[frozenset] = set()

    def dfs(node: str, path: list[str], on_path: set[str]) -> None:
        for nxt in sorted(graph.get(node, ())):
            if nxt in on_path:
                cyc = path[path.index(nxt):] + [nxt]
                key = frozenset(cyc)
                if key not in seen_sets:
                    seen_sets.add(key)
                    cycles.append(cyc)
                continue
            dfs(nxt, path + [nxt], on_path | {nxt})

    for start in sorted(graph):
        dfs(start, [start], {start})
    return cycles


def _reachable(index: _Index, roots: list[str]) -> set[str]:
    """Function-key closure from entry points, across resolve()."""
    out: set[str] = set()
    work = [r for r in roots if r in index.funcs]
    while work:
        key = work.pop()
        if key in out:
            continue
        out.add(key)
        fm = index.funcs[key]
        m = index.models[key.split("::", 1)[0]]
        for call in fm.calls:
            for target in index.resolve(call, m, fm):
                if target not in out:
                    work.append(target)
        # nested defs run on the same entry point's thread
        prefix = key + "."
        for other in index.funcs:
            if other.startswith(prefix):
                work.append(other)
    return out


def _resolve_roots(index: _Index) -> tuple[dict[str, list[str]],
                                           dict[str, str]]:
    """Thread/process root descriptors -> function keys."""
    roots: dict[str, list[str]] = {"thread": [], "process": []}
    labels: dict[str, str] = {}
    for rel, m in index.models.items():
        for kind, descr, lineno, site in m.thread_roots:
            key = None
            tag, _, val = descr.partition(":")
            if tag == "bare":
                key = index.module_func(m, val)
            elif tag == "method":
                cls, _, meth = val.partition(".")
                key = index.class_methods.get(cls, {}).get(meth)
            elif tag == "name":
                hits = [k for k in index.methods_by_name.get(val, ())]
                key = hits[0] if len(hits) == 1 else None
            if key:
                roots[kind].append(key)
                labels[key] = f"{rel}:{lineno} ({site})"
    return roots, labels


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------


def _suppressions(files: dict[str, str]) -> dict[str, dict[int, str]]:
    """rel -> {lineno: kind} for every `# conccheck: kind=why` line."""
    out: dict[str, dict[int, str]] = {}
    for rel, src in files.items():
        table: dict[int, str] = {}
        for i, line in enumerate(src.splitlines(), start=1):
            hit = _SUPPRESS_RE.search(line)
            if hit:
                table[i] = hit.group(1)
        if table:
            out[rel] = table
    return out


def _is_suppressed(rule: str, rel: str, lineno: int,
                   sup: dict[str, dict[int, str]]) -> bool:
    kind = _SUPPRESS_KIND.get(rule)
    if kind is None:
        return False
    table = sup.get(rel, {})
    return table.get(lineno) == kind or table.get(lineno - 1) == kind


def _discipline_findings(index: _Index, thread_reach: set[str],
                         process_reach: set[str]
                         ) -> tuple[list, dict]:
    """Leg (a): unguarded writes + per-class guarded-by maps."""
    findings = []
    guarded_by: dict[str, dict[str, list[str]]] = {}
    reach = thread_reach | process_reach
    for rel, m in sorted(index.models.items()):
        for cls, locks in sorted(m.classes.items()):
            if not locks:
                continue
            writes: dict[str, list] = {}
            for qual, fm in m.functions.items():
                if fm.cls != cls:
                    continue
                if qual == f"{cls}.__init__" \
                        or qual.startswith(f"{cls}.__init__."):
                    continue
                for w in fm.writes:
                    if w.target == "self" and w.attr not in locks:
                        writes.setdefault(w.attr, []).append((fm, w))
            gmap: dict[str, list[str]] = {}
            for attr, sites in sorted(writes.items()):
                guards: set[str] = set()
                for fm, w in sites:
                    if w.held:
                        guards.update(w.held)
                    elif fm.caller_held:
                        guards.add("(caller-held)")
                if guards:
                    gmap[attr] = sorted(guards)
                for fm, w in sites:
                    if w.held or fm.caller_held:
                        continue
                    key = m.key(fm.qualname)
                    if guards:
                        why = (f"{cls}.{attr} is guarded by "
                               f"{'/'.join(sorted(guards))} elsewhere")
                    elif key in reach:
                        root = ("thread" if key in thread_reach
                                else "process")
                        why = (f"{cls} owns {'/'.join(sorted(locks))} "
                               f"and this write runs on a second "
                               f"{root} entry point")
                    else:
                        continue
                    findings.append((
                        "conc-unguarded-write", rel, w.lineno,
                        f"unguarded write to self.{attr} in "
                        f"{fm.qualname}: {why}"))
            if gmap:
                guarded_by[cls] = gmap
        # module-global discipline: same inference at module scope
        if m.module_locks:
            gwrites: dict[str, list] = {}
            for fm in m.functions.values():
                for w in fm.writes:
                    if w.target == "<module>" \
                            and w.attr not in m.module_locks:
                        gwrites.setdefault(w.attr, []).append((fm, w))
            for name, sites in sorted(gwrites.items()):
                guards = {h for _, w in sites for h in w.held}
                if not guards:
                    continue
                for fm, w in sites:
                    if not w.held and not fm.caller_held:
                        findings.append((
                            "conc-unguarded-write", rel, w.lineno,
                            f"unguarded write to module global "
                            f"{name} in {fm.qualname}: guarded by "
                            f"{'/'.join(sorted(guards))} elsewhere"))
    return findings, guarded_by


_BLOCKING_DESCR = {
    "compile": "AOT .compile() compiles on whatever thread holds the "
               "lock — compile on the caller's thread BEFORE taking it",
    "get": "zero-arg .get() with no timeout can block forever while "
           "the lock starves every other holder",
    "join": "zero-arg .join() with no timeout under a lock is a "
            "deadlock with any target that needs the same lock",
    "unlink": "shared-memory unlink under a lock serializes teardown "
              "against the hot path",
}


def _blocking_findings(index: _Index) -> list:
    findings = []
    for key, fm in sorted(index.funcs.items()):
        rel = key.split("::", 1)[0]
        for call in fm.calls:
            if not call.held:
                continue
            name = call.name
            bad = (
                name == "compile"
                or (name == "get" and call.nargs == 0
                    and "timeout" not in call.kwnames
                    and "block" not in call.kwnames)
                or (name == "join" and call.nargs == 0
                    and "timeout" not in call.kwnames)
                or name == "unlink"
            )
            if bad:
                findings.append((
                    "conc-blocking-under-lock", rel, call.lineno,
                    f".{name}() while holding {call.held[-1]} in "
                    f"{fm.qualname}: {_BLOCKING_DESCR[name]}"))
    return findings


def _jax_findings(index: _Index, process_reach: set[str]) -> list:
    findings = []
    for key in sorted(process_reach):
        fm = index.funcs.get(key)
        if fm is None:
            continue
        rel = key.split("::", 1)[0]
        m = index.models[rel]
        lines = sorted(set(fm.jax_lines))
        if m.module_imports_jax:
            lines = lines or [fm.lineno]
        for lineno in lines[:1]:
            findings.append((
                "conc-jax-in-worker", rel, lineno,
                f"{fm.qualname} is reachable from a Process(target=...)"
                f" worker and touches jax"
                + (" (module-level jax import)"
                   if m.module_imports_jax and not fm.jax_lines
                   else "")))
    return findings


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------


def _diff_contract(banked, fresh, prefix: str = "") -> list[str]:
    """Leaf-level diffs between two JSON-able contracts (same shape as
    graphcheck's)."""
    diffs: list[str] = []
    if isinstance(banked, dict) and isinstance(fresh, dict):
        for k in sorted(set(banked) | set(fresh)):
            path = f"{prefix}.{k}" if prefix else str(k)
            if k not in banked:
                diffs.append(f"{path}: added {fresh[k]!r}")
            elif k not in fresh:
                diffs.append(f"{path}: removed (was {banked[k]!r})")
            else:
                diffs.extend(_diff_contract(banked[k], fresh[k], path))
        return diffs
    if banked != fresh:
        diffs.append(f"{prefix}: {banked!r} -> {fresh!r}")
    return diffs


def _check_manifest(name: str, contract: dict, manifest_dir: str,
                    update: bool) -> tuple[list, dict]:
    """Compare/update ONE manifest; returns (findings, manifest)."""
    rel = os.path.join("docs", os.path.basename(manifest_dir),
                       f"{name}.json")
    path = os.path.join(manifest_dir, f"{name}.json")
    banked = None
    if os.path.isfile(path):
        with open(path, encoding="utf-8") as f:
            banked = json.load(f)
    allow = (banked or {}).get("allow", {})
    manifest = {"contract": contract, "allow": allow}
    problems = []
    if banked is None:
        if not update:
            problems.append((
                "conc-manifest-missing", rel, 0,
                f"no banked {name} manifest"))
    elif not update:
        drift = _diff_contract(banked.get("contract", {}), contract)
        if drift:
            problems.append((
                "conc-manifest-drift", rel, 0,
                f"{name} drifted: " + "; ".join(drift[:4])
                + ("" if len(drift) <= 4
                   else f" (+{len(drift) - 4} more)")))
    if update:
        os.makedirs(manifest_dir, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
            f.write("\n")
    findings = [
        Finding(rule, p, line, msg, suppressed=rule in allow)
        for rule, p, line, msg in problems]
    return findings, manifest


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run_conccheck(paths=None, *, update: bool = False,
                  manifest_dir: str | None = None,
                  repo: str | None = None):
    """Run every leg; returns (findings, manifests).

    ``paths`` (rel paths or pattern tuple) narrows the audited surface
    for fixture tests; the default is the full CONC_SOURCE_PATTERNS
    scope.  ``update`` re-banks the manifests (and SOURCES.json).
    """
    repo = repo or _REPO
    manifest_dir = manifest_dir or MANIFEST_DIR
    patterns = tuple(paths) if paths else CONC_SOURCE_PATTERNS
    files = _collect_files(repo, patterns)
    sup = _suppressions(files)
    models = build_model(files)
    index = _Index(models)

    roots, root_labels = _resolve_roots(index)
    thread_reach = _reachable(index, roots["thread"])
    process_reach = _reachable(index, roots["process"])

    raw: list = []
    disc, guarded_by = _discipline_findings(
        index, thread_reach, process_reach)
    raw.extend(disc)
    raw.extend(_blocking_findings(index))
    raw.extend(_jax_findings(index, process_reach))

    edges = _build_edges(index)
    for cyc in _find_cycles(edges):
        wrel, wline = edges[(cyc[0], cyc[1])]
        raw.append((
            "conc-lock-order-cycle", wrel, wline,
            "lock-order cycle: " + " -> ".join(cyc)))

    findings = [
        Finding(rule, rel, lineno, msg,
                suppressed=_is_suppressed(rule, rel, lineno, sup))
        for rule, rel, lineno, msg in sorted(set(raw))]

    lock_graph = {
        "locks": sorted({lid for m in models.values()
                         for lid in list(m.module_locks.values())
                         + [v for c in m.classes.values()
                            for v in c.values()]}),
        "edges": sorted([a, b] for a, b in edges),
    }
    roles = {
        "thread_roots": sorted({f"{k} @ {root_labels[k]}"
                                for k in roots["thread"]}),
        "process_roots": sorted({f"{k} @ {root_labels[k]}"
                                 for k in roots["process"]}),
        "thread_reachable": sorted(thread_reach),
        "process_reachable": sorted(process_reach),
        "guarded_by": guarded_by,
    }

    manifests = {}
    for name, contract in (("lock_graph", lock_graph),
                           ("roles", roles)):
        probs, manifest = _check_manifest(
            name, contract, manifest_dir, update)
        findings.extend(probs)
        manifests[name] = manifest

    if update:
        fp = {rel: hashlib.sha256(src.encode("utf-8")).hexdigest()
              for rel, src in sorted(files.items())}
        with open(os.path.join(manifest_dir, "SOURCES.json"), "w",
                  encoding="utf-8") as f:
            json.dump(fp, f, indent=1, sort_keys=True)
            f.write("\n")

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, manifests
