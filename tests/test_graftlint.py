"""graftlint: fixture snippets per rule + the repo-wide self-lint gate.

Each rule gets three fixtures — a positive hit, the same hit suppressed
with a justified directive, and a clean rewrite — so the rule's
boundary is pinned from both sides.  ``test_repo_self_lint_is_clean``
is the CI wiring: it runs the analyzer over the repo's contract surface
(``sparknet_tpu/``, ``tools/``, ``bench.py``) and fails on any
unsuppressed finding, so future PRs cannot reintroduce unguarded
evidence banking, unfenced obs spans or stale contract manifests.

All smoke-marked: the analyzer is stdlib-AST only, no jax dispatch.
"""

import json
import os

import pytest

from sparknet_tpu.analysis import RULES, lint_paths, lint_source
from sparknet_tpu.analysis.__main__ import default_paths
from sparknet_tpu.analysis.__main__ import main as cli_main

pytestmark = pytest.mark.smoke

EXPECTED_RULES = {
    "bank-guard",
    "graph-manifest-fresh",
    "mem-manifest-fresh",
    "elastic-manifest-fresh",
    "serve-manifest-fresh",
    "loop-manifest-fresh",
    "replica-manifest-fresh",
    "paged-manifest-fresh",
    "obs-fenced-span",
    "feed-shm-cleanup",
    "obs-vocab-coverage",
    "conc-manifest-fresh",
    "byte-manifest-fresh",
    "ctl-manifest-fresh",
    "num-manifest-fresh",
}


def hits(src, rule_id, path="snippet.py"):
    """Unsuppressed findings of one rule for a source fixture."""
    return [f for f in lint_source(src, path)
            if f.rule == rule_id and not f.suppressed]


def suppressed_hits(src, rule_id, path="snippet.py"):
    return [f for f in lint_source(src, path)
            if f.rule == rule_id and f.suppressed]


# -- registry ---------------------------------------------------------------


def test_rule_catalog_complete():
    assert set(RULES) == EXPECTED_RULES
    for info in RULES.values():
        assert info.summary, info.id


# -- bank-guard -------------------------------------------------------------

BANK_BAD = """
import json
import os

def save(rec):
    path = "docs/serve_bench_last.json"
    with open(path + ".tmp", "w") as f:
        json.dump(rec, f)
    os.replace(path + ".tmp", path)
"""

BANK_GOOD = """
from sparknet_tpu.common import bank_guard

def save(rec, on_accel):
    bank_guard("docs/serve_bench_last.json", rec, measured=on_accel)
"""

BANK_MODULE_CONST = """
import json

PATH = "docs/feed_bench_last.json"

def save(rec):
    with open(PATH, "w") as f:
        json.dump(rec, f)
"""


def test_bank_guard_positive():
    found = hits(BANK_BAD, "bank-guard")
    assert len(found) == 1
    assert "bank_guard" in found[0].message


def test_bank_guard_sees_module_level_path_constants():
    # string at module scope, write in a function — module strings are
    # ambient
    assert len(hits(BANK_MODULE_CONST, "bank-guard")) == 1


def test_bank_guard_suppressed():
    src = BANK_BAD.replace(
        'with open(path + ".tmp", "w") as f:',
        'with open(path + ".tmp", "w") as f:  '
        "# graftlint: disable=bank-guard -- offline re-attribution tool")
    assert not hits(src, "bank-guard")
    assert suppressed_hits(src, "bank-guard")


def test_bank_guard_clean_via_helper():
    assert not hits(BANK_GOOD, "bank-guard")


def test_bank_guard_read_is_fine():
    src = ('import json\n'
           'def load():\n'
           '    with open("docs/feed_bench_last.json") as f:\n'
           '        return json.load(f)\n')
    assert not hits(src, "bank-guard")


def test_bank_guard_non_evidence_write_is_fine():
    src = ('import json\n'
           'def save(rec):\n'
           '    with open("docs/tau_sweep_alexnet.json", "w") as f:\n'
           '        json.dump(rec, f)\n')
    assert not hits(src, "bank-guard")


# -- graph-manifest-fresh ---------------------------------------------------

FRESH_SRC = "import jax\n\ndef round_fn(v):\n    return v\n"


def _graph_tree(tmp_path, src=FRESH_SRC, record=True, stale=False):
    """A fake repo: sparknet_tpu/parallel/x.py (+ optional SOURCES.json
    recording its hash, optionally stale)."""
    import hashlib
    import json as _json

    mod = tmp_path / "sparknet_tpu" / "parallel" / "x.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(src)
    if record:
        digest = hashlib.sha256(src.encode()).hexdigest()
        if stale:
            digest = "0" * 64
        cdir = tmp_path / "docs" / "graph_contracts"
        cdir.mkdir(parents=True)
        (cdir / "SOURCES.json").write_text(
            _json.dumps({"sparknet_tpu/parallel/x.py": digest}))
    return str(mod)


def test_graph_manifest_fresh_positive_on_stale_hash(tmp_path):
    path = _graph_tree(tmp_path, stale=True)
    found = hits(FRESH_SRC, "graph-manifest-fresh", path=path)
    assert len(found) == 1
    assert "--update" in found[0].message


def test_graph_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _graph_tree(tmp_path, record=False)
    found = hits(FRESH_SRC, "graph-manifest-fresh", path=path)
    assert len(found) == 1
    assert "SOURCES.json missing" in found[0].message


def test_graph_manifest_fresh_suppressed(tmp_path):
    path = _graph_tree(tmp_path, stale=True)
    src = ("# graftlint: disable-file=graph-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "graph-manifest-fresh", path=path)
    assert suppressed_hits(src, "graph-manifest-fresh", path=path)


def test_graph_manifest_fresh_clean_when_hash_matches(tmp_path):
    path = _graph_tree(tmp_path)
    assert not hits(FRESH_SRC, "graph-manifest-fresh", path=path)


# -- byte-manifest-fresh ----------------------------------------------------


def _byte_tree(tmp_path, src=FRESH_SRC, record=True, stale=False,
               rel="sparknet_tpu/solvers/solver.py"):
    """A fake repo: one byte-contract source file (+ optional
    docs/byte_contracts/SOURCES.json recording its hash)."""
    import hashlib
    import json as _json

    mod = tmp_path.joinpath(*rel.split("/"))
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(src)
    if record:
        digest = hashlib.sha256(src.encode()).hexdigest()
        if stale:
            digest = "0" * 64
        cdir = tmp_path / "docs" / "byte_contracts"
        cdir.mkdir(parents=True)
        (cdir / "SOURCES.json").write_text(_json.dumps({rel: digest}))
    return str(mod)


def test_byte_manifest_fresh_positive_on_stale_hash(tmp_path):
    path = _byte_tree(tmp_path, stale=True)
    found = hits(FRESH_SRC, "byte-manifest-fresh", path=path)
    assert len(found) == 1
    assert "bytes --update" in found[0].message


def test_byte_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _byte_tree(tmp_path, record=False)
    found = hits(FRESH_SRC, "byte-manifest-fresh", path=path)
    assert len(found) == 1
    assert "SOURCES.json missing" in found[0].message


def test_byte_manifest_fresh_covers_the_serve_dir(tmp_path):
    path = _byte_tree(tmp_path, record=False,
                      rel="sparknet_tpu/serve/engine.py")
    assert hits(FRESH_SRC, "byte-manifest-fresh", path=path)


def test_byte_manifest_fresh_ignores_non_surface_files(tmp_path):
    path = _byte_tree(tmp_path, record=False,
                      rel="sparknet_tpu/obs/report.py")
    assert not hits(FRESH_SRC, "byte-manifest-fresh", path=path)


def test_byte_manifest_fresh_suppressed(tmp_path):
    path = _byte_tree(tmp_path, stale=True)
    src = ("# graftlint: disable-file=byte-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "byte-manifest-fresh", path=path)
    assert suppressed_hits(src, "byte-manifest-fresh", path=path)


def test_byte_manifest_fresh_clean_when_hash_matches(tmp_path):
    path = _byte_tree(tmp_path)
    assert not hits(FRESH_SRC, "byte-manifest-fresh", path=path)


# -- num-manifest-fresh -----------------------------------------------------


def _num_tree(tmp_path, src=FRESH_SRC, record=True, stale=False,
              rel="sparknet_tpu/common.py"):
    """A fake repo: one numerics-contract source file (+ optional
    docs/num_contracts/SOURCES.json recording its hash).  Defaults to
    common.py — num surface (the activation_dtype policy semantics)
    but NOT byte surface, so the two rules stay distinguishable."""
    import hashlib
    import json as _json

    mod = tmp_path.joinpath(*rel.split("/"))
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(src)
    if record:
        digest = hashlib.sha256(src.encode()).hexdigest()
        if stale:
            digest = "0" * 64
        cdir = tmp_path / "docs" / "num_contracts"
        cdir.mkdir(parents=True)
        (cdir / "SOURCES.json").write_text(_json.dumps({rel: digest}))
    return str(mod)


def test_num_manifest_fresh_positive_on_stale_hash(tmp_path):
    path = _num_tree(tmp_path, stale=True)
    found = hits(FRESH_SRC, "num-manifest-fresh", path=path)
    assert len(found) == 1
    assert "num --update" in found[0].message


def test_num_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _num_tree(tmp_path, record=False)
    found = hits(FRESH_SRC, "num-manifest-fresh", path=path)
    assert len(found) == 1
    assert "SOURCES.json missing" in found[0].message


def test_num_manifest_fresh_covers_common_py_unlike_byte(tmp_path):
    # common.py carries the activation_dtype policy semantics: num
    # surface, but deliberately NOT byte surface
    path = _num_tree(tmp_path, record=False)
    assert hits(FRESH_SRC, "num-manifest-fresh", path=path)
    assert not hits(FRESH_SRC, "byte-manifest-fresh", path=path)


def test_num_manifest_fresh_ignores_non_surface_files(tmp_path):
    path = _num_tree(tmp_path, record=False,
                     rel="sparknet_tpu/obs/report.py")
    assert not hits(FRESH_SRC, "num-manifest-fresh", path=path)


def test_num_manifest_fresh_suppressed(tmp_path):
    path = _num_tree(tmp_path, stale=True)
    src = ("# graftlint: disable-file=num-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "num-manifest-fresh", path=path)
    assert suppressed_hits(src, "num-manifest-fresh", path=path)


def test_num_manifest_fresh_clean_when_hash_matches(tmp_path):
    path = _num_tree(tmp_path)
    assert not hits(FRESH_SRC, "num-manifest-fresh", path=path)


def test_num_manifest_fresh_surface_matches_numcheck():
    # the rule duplicates numcheck.NUM_SOURCE_PATTERNS so rules.py
    # stays importable without jax-adjacent modules; pin the two lists
    # against each other so they cannot drift apart silently
    from sparknet_tpu.analysis import rules
    from sparknet_tpu.analysis.numcheck import NUM_SOURCE_PATTERNS

    dup = set(rules._NUM_SOURCE_DIRS) | set(rules._NUM_SOURCE_FILES)
    assert dup == set(NUM_SOURCE_PATTERNS)


def test_graph_manifest_fresh_ignores_non_contract_files(tmp_path):
    other = tmp_path / "sparknet_tpu" / "ops" / "y.py"
    other.parent.mkdir(parents=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "graph-manifest-fresh", path=str(other))
    # and plain fixture paths (no sparknet_tpu/ segment) never fire
    assert not hits(FRESH_SRC, "graph-manifest-fresh")


# -- mem-manifest-fresh -----------------------------------------------------


def _mem_tree(tmp_path, rel="sparknet_tpu/solvers/solver.py",
              src=FRESH_SRC, record=True, stale=False):
    """A fake repo: one memory-contract source file (+ optional
    docs/mem_contracts/SOURCES.json recording its hash)."""
    import hashlib
    import json as _json

    mod = tmp_path / rel
    mod.parent.mkdir(parents=True)
    mod.write_text(src)
    if record:
        digest = hashlib.sha256(src.encode()).hexdigest()
        if stale:
            digest = "0" * 64
        cdir = tmp_path / "docs" / "mem_contracts"
        cdir.mkdir(parents=True)
        (cdir / "SOURCES.json").write_text(_json.dumps({rel: digest}))
    return str(mod)


def test_mem_manifest_fresh_positive_on_stale_hash(tmp_path):
    path = _mem_tree(tmp_path, stale=True)
    found = hits(FRESH_SRC, "mem-manifest-fresh", path=path)
    assert len(found) == 1
    assert "mem --update" in found[0].message


def test_mem_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _mem_tree(tmp_path, rel="sparknet_tpu/ops/pallas_kernels.py",
                     record=False)
    found = hits(FRESH_SRC, "mem-manifest-fresh", path=path)
    assert len(found) == 1
    assert "SOURCES.json missing" in found[0].message


def test_mem_manifest_fresh_suppressed(tmp_path):
    path = _mem_tree(tmp_path, stale=True)
    src = ("# graftlint: disable-file=mem-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "mem-manifest-fresh", path=path)
    assert suppressed_hits(src, "mem-manifest-fresh", path=path)


def test_mem_manifest_fresh_clean_when_hash_matches(tmp_path):
    path = _mem_tree(tmp_path)
    assert not hits(FRESH_SRC, "mem-manifest-fresh", path=path)


def _conc_tree(tmp_path, rel="sparknet_tpu/serve/batcher.py",
               src=FRESH_SRC, record=True, stale=False):
    """A fake repo: one concurrency-contract source file (+ optional
    docs/conc_contracts/SOURCES.json recording its hash)."""
    import hashlib
    import json as _json

    mod = tmp_path / rel
    mod.parent.mkdir(parents=True)
    mod.write_text(src)
    if record:
        digest = hashlib.sha256(src.encode()).hexdigest()
        if stale:
            digest = "0" * 64
        cdir = tmp_path / "docs" / "conc_contracts"
        cdir.mkdir(parents=True)
        (cdir / "SOURCES.json").write_text(_json.dumps({rel: digest}))
    return str(mod)


def test_conc_manifest_fresh_positive_on_stale_hash(tmp_path):
    path = _conc_tree(tmp_path, stale=True)
    found = hits(FRESH_SRC, "conc-manifest-fresh", path=path)
    assert len(found) == 1
    assert "conc --update" in found[0].message


def test_conc_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _conc_tree(tmp_path, rel="sparknet_tpu/loop/controller.py",
                      record=False)
    found = hits(FRESH_SRC, "conc-manifest-fresh", path=path)
    assert len(found) == 1
    assert "SOURCES.json missing" in found[0].message


def test_conc_manifest_fresh_suppressed(tmp_path):
    path = _conc_tree(tmp_path, stale=True)
    src = ("# graftlint: disable-file=conc-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "conc-manifest-fresh", path=path)
    assert suppressed_hits(src, "conc-manifest-fresh", path=path)


def test_conc_manifest_fresh_clean_when_hash_matches(tmp_path):
    path = _conc_tree(tmp_path)
    assert not hits(FRESH_SRC, "conc-manifest-fresh", path=path)


def test_conc_manifest_fresh_ignores_non_contract_files(tmp_path):
    # parallel/ is graph/mem surface, not concurrency surface
    other = tmp_path / "sparknet_tpu" / "parallel" / "modes.py"
    other.parent.mkdir(parents=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "conc-manifest-fresh", path=str(other))


def test_mem_manifest_fresh_ignores_non_contract_files(tmp_path):
    # ops/vision.py changes the math, not the memory contract surface
    other = tmp_path / "sparknet_tpu" / "ops" / "vision.py"
    other.parent.mkdir(parents=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "mem-manifest-fresh", path=str(other))
    assert not hits(FRESH_SRC, "mem-manifest-fresh")


# -- elastic-manifest-fresh -------------------------------------------------


def _elastic_tree(tmp_path, record=True, covered=True, widths=(8, 6),
                  families=("graph_contracts", "mem_contracts")):
    """A fake repo around parallel/elastic.py: SOURCES.json (optionally
    not covering it) + elastic_w*.json twin manifests per family."""
    import hashlib
    import json as _json

    rel = "sparknet_tpu/parallel/elastic.py"
    mod = tmp_path / rel
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(FRESH_SRC)
    digest = hashlib.sha256(FRESH_SRC.encode()).hexdigest()
    for fam in families:
        cdir = tmp_path / "docs" / fam
        cdir.mkdir(parents=True, exist_ok=True)
        if record:
            entry = {rel: digest} if covered else {"other.py": digest}
            (cdir / "SOURCES.json").write_text(_json.dumps(entry))
        for w in widths:
            (cdir / f"elastic_w{w}.json").write_text("{}")
    return str(mod)


def test_elastic_manifest_fresh_clean_when_banked(tmp_path):
    path = _elastic_tree(tmp_path)
    assert not hits(FRESH_SRC, "elastic-manifest-fresh", path=path)


def test_elastic_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _elastic_tree(tmp_path, record=False, widths=())
    found = hits(FRESH_SRC, "elastic-manifest-fresh", path=path)
    assert len(found) == 2  # one per family
    assert "SOURCES.json missing" in found[0].message


def test_elastic_manifest_fresh_positive_when_not_folded_in(tmp_path):
    # manifests exist but predate the elastic layer: elastic.py absent
    # from the fingerprint — exactly the silent-non-coverage hole the
    # dir-hash rules cannot see
    path = _elastic_tree(tmp_path, covered=False)
    found = hits(FRESH_SRC, "elastic-manifest-fresh", path=path)
    assert len(found) == 2
    assert all("not folded into" in f.message for f in found)


def test_elastic_manifest_fresh_positive_below_min_widths(tmp_path):
    path = _elastic_tree(tmp_path, widths=(8,))
    found = hits(FRESH_SRC, "elastic-manifest-fresh", path=path)
    assert len(found) == 2
    assert all(">= 2 mesh widths" in f.message for f in found)


def test_elastic_manifest_fresh_suppressed(tmp_path):
    path = _elastic_tree(tmp_path, record=False, widths=())
    src = ("# graftlint: disable-file=elastic-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "elastic-manifest-fresh", path=path)
    assert suppressed_hits(src, "elastic-manifest-fresh", path=path)


def test_elastic_manifest_fresh_ignores_other_parallel_files(tmp_path):
    other = tmp_path / "sparknet_tpu" / "parallel" / "trainer.py"
    other.parent.mkdir(parents=True, exist_ok=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "elastic-manifest-fresh", path=str(other))
    assert not hits(FRESH_SRC, "elastic-manifest-fresh")


# -- serve-manifest-fresh ---------------------------------------------------


def _serve_tree(tmp_path, record=True, covered=True,
                buckets=(1, 8, 64, 256),
                families=("graph_contracts", "mem_contracts")):
    """A fake repo around serve/engine.py: SOURCES.json (optionally not
    covering it) + serve_b*.json twin manifests per family."""
    import hashlib
    import json as _json

    rel = "sparknet_tpu/serve/engine.py"
    mod = tmp_path / rel
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(FRESH_SRC)
    digest = hashlib.sha256(FRESH_SRC.encode()).hexdigest()
    for fam in families:
        cdir = tmp_path / "docs" / fam
        cdir.mkdir(parents=True, exist_ok=True)
        if record:
            entry = {rel: digest} if covered else {"other.py": digest}
            (cdir / "SOURCES.json").write_text(_json.dumps(entry))
        for b in buckets:
            (cdir / f"serve_b{b}.json").write_text("{}")
    return str(mod)


def test_serve_manifest_fresh_clean_when_banked(tmp_path):
    path = _serve_tree(tmp_path)
    assert not hits(FRESH_SRC, "serve-manifest-fresh", path=path)


def test_serve_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _serve_tree(tmp_path, record=False, buckets=())
    found = hits(FRESH_SRC, "serve-manifest-fresh", path=path)
    assert len(found) == 2  # one per family
    assert "SOURCES.json missing" in found[0].message


def test_serve_manifest_fresh_positive_when_not_folded_in(tmp_path):
    # manifests exist but predate the serving layer: engine.py absent
    # from the fingerprint — the silent-non-coverage hole
    path = _serve_tree(tmp_path, covered=False)
    found = hits(FRESH_SRC, "serve-manifest-fresh", path=path)
    assert len(found) == 2
    assert all("not folded into" in f.message for f in found)


def test_serve_manifest_fresh_positive_below_bucket_ladder(tmp_path):
    path = _serve_tree(tmp_path, buckets=(1, 8))
    found = hits(FRESH_SRC, "serve-manifest-fresh", path=path)
    assert len(found) == 2
    assert all("4 buckets" in f.message for f in found)


def test_serve_manifest_fresh_suppressed(tmp_path):
    path = _serve_tree(tmp_path, record=False, buckets=())
    src = ("# graftlint: disable-file=serve-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "serve-manifest-fresh", path=path)
    assert suppressed_hits(src, "serve-manifest-fresh", path=path)


def test_serve_manifest_fresh_ignores_other_packages(tmp_path):
    other = tmp_path / "sparknet_tpu" / "parallel" / "trainer.py"
    other.parent.mkdir(parents=True, exist_ok=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "serve-manifest-fresh", path=str(other))
    assert not hits(FRESH_SRC, "serve-manifest-fresh")


# -- replica-manifest-fresh -------------------------------------------------


def _replica_tree(tmp_path, record=True, covered=True, widths=(1, 2, 4),
                  families=("graph_contracts", "mem_contracts")):
    """A fake repo around serve/router.py: SOURCES.json (optionally not
    covering it) + serve_r*.json pool-width twin manifests per family."""
    import hashlib
    import json as _json

    rel = "sparknet_tpu/serve/router.py"
    mod = tmp_path / rel
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(FRESH_SRC)
    digest = hashlib.sha256(FRESH_SRC.encode()).hexdigest()
    for fam in families:
        cdir = tmp_path / "docs" / fam
        cdir.mkdir(parents=True, exist_ok=True)
        if record:
            entry = {rel: digest} if covered else {"other.py": digest}
            (cdir / "SOURCES.json").write_text(_json.dumps(entry))
        for w in widths:
            (cdir / f"serve_r{w}.json").write_text("{}")
    return str(mod)


def test_replica_manifest_fresh_clean_when_banked(tmp_path):
    path = _replica_tree(tmp_path)
    assert not hits(FRESH_SRC, "replica-manifest-fresh", path=path)


def test_replica_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _replica_tree(tmp_path, record=False, widths=())
    found = hits(FRESH_SRC, "replica-manifest-fresh", path=path)
    assert len(found) == 2  # one per family
    assert "SOURCES.json missing" in found[0].message


def test_replica_manifest_fresh_positive_when_not_folded_in(tmp_path):
    # manifests exist but predate the replica layer: router.py absent
    # from the fingerprint — the silent-non-coverage hole
    path = _replica_tree(tmp_path, covered=False)
    found = hits(FRESH_SRC, "replica-manifest-fresh", path=path)
    assert len(found) == 2
    assert all("not folded into" in f.message for f in found)


def test_replica_manifest_fresh_positive_below_min_widths(tmp_path):
    path = _replica_tree(tmp_path, widths=(4,))
    found = hits(FRESH_SRC, "replica-manifest-fresh", path=path)
    assert len(found) == 2
    assert all(">= 2" in f.message for f in found)


def test_replica_manifest_fresh_suppressed(tmp_path):
    path = _replica_tree(tmp_path, record=False, widths=())
    src = ("# graftlint: disable-file=replica-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "replica-manifest-fresh", path=path)
    assert suppressed_hits(src, "replica-manifest-fresh", path=path)


def test_replica_manifest_fresh_ignores_other_serve_files(tmp_path):
    other = tmp_path / "sparknet_tpu" / "serve" / "engine.py"
    other.parent.mkdir(parents=True, exist_ok=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "replica-manifest-fresh", path=str(other))
    assert not hits(FRESH_SRC, "replica-manifest-fresh")


# -- paged-manifest-fresh ---------------------------------------------------


def _paged_tree(tmp_path, record=True, covered=True, occupancies=(1, 4),
                rect=True,
                families=("graph_contracts", "mem_contracts",
                          "byte_contracts")):
    """A fake repo around serve/paged.py: SOURCES.json (optionally not
    covering it) + decode_paged_o*.json occupancy twins and the
    decode_rect.json baseline per family."""
    import hashlib
    import json as _json

    rel = "sparknet_tpu/serve/paged.py"
    mod = tmp_path / rel
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(FRESH_SRC)
    digest = hashlib.sha256(FRESH_SRC.encode()).hexdigest()
    for fam in families:
        cdir = tmp_path / "docs" / fam
        cdir.mkdir(parents=True, exist_ok=True)
        if record:
            entry = {rel: digest} if covered else {"other.py": digest}
            (cdir / "SOURCES.json").write_text(_json.dumps(entry))
        for o in occupancies:
            (cdir / f"decode_paged_o{o}.json").write_text("{}")
        if rect:
            (cdir / "decode_rect.json").write_text("{}")
    return str(mod)


def test_paged_manifest_fresh_clean_when_banked(tmp_path):
    path = _paged_tree(tmp_path)
    assert not hits(FRESH_SRC, "paged-manifest-fresh", path=path)


def test_paged_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _paged_tree(tmp_path, record=False, occupancies=(), rect=False)
    found = hits(FRESH_SRC, "paged-manifest-fresh", path=path)
    assert len(found) == 3  # one per family (graph + mem + byte)
    assert "SOURCES.json missing" in found[0].message


def test_paged_manifest_fresh_positive_when_not_folded_in(tmp_path):
    # manifests exist but predate the paged layer: paged.py absent
    # from the fingerprint — the silent-non-coverage hole
    path = _paged_tree(tmp_path, covered=False)
    found = hits(FRESH_SRC, "paged-manifest-fresh", path=path)
    assert len(found) == 3
    assert all("not folded into" in f.message for f in found)


def test_paged_manifest_fresh_positive_below_min_occupancies(tmp_path):
    path = _paged_tree(tmp_path, occupancies=(4,))
    found = hits(FRESH_SRC, "paged-manifest-fresh", path=path)
    assert len(found) == 3
    assert all(">= 2" in f.message for f in found)


def test_paged_manifest_fresh_positive_without_rect_baseline(tmp_path):
    path = _paged_tree(tmp_path, rect=False)
    found = hits(FRESH_SRC, "paged-manifest-fresh", path=path)
    assert len(found) == 3
    assert all("decode_rect" in f.message for f in found)


def test_paged_manifest_fresh_suppressed(tmp_path):
    path = _paged_tree(tmp_path, record=False, occupancies=(), rect=False)
    src = ("# graftlint: disable-file=paged-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "paged-manifest-fresh", path=path)
    assert suppressed_hits(src, "paged-manifest-fresh", path=path)


def test_paged_manifest_fresh_ignores_other_serve_files(tmp_path):
    other = tmp_path / "sparknet_tpu" / "serve" / "continuous.py"
    other.parent.mkdir(parents=True, exist_ok=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "paged-manifest-fresh", path=str(other))
    assert not hits(FRESH_SRC, "paged-manifest-fresh")


# -- loop-manifest-fresh ----------------------------------------------------


def _loop_tree(tmp_path, record=True, covered=True,
               families=("graph_contracts", "mem_contracts")):
    """A fake repo around loop/controller.py: SOURCES.json per family,
    optionally not covering it (the loop banks no twins of its own)."""
    import hashlib
    import json as _json

    rel = "sparknet_tpu/loop/controller.py"
    mod = tmp_path / rel
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(FRESH_SRC)
    digest = hashlib.sha256(FRESH_SRC.encode()).hexdigest()
    for fam in families:
        cdir = tmp_path / "docs" / fam
        cdir.mkdir(parents=True, exist_ok=True)
        if record:
            entry = {rel: digest} if covered else {"other.py": digest}
            (cdir / "SOURCES.json").write_text(_json.dumps(entry))
    return str(mod)


def test_loop_manifest_fresh_clean_when_banked(tmp_path):
    path = _loop_tree(tmp_path)
    assert not hits(FRESH_SRC, "loop-manifest-fresh", path=path)


def test_loop_manifest_fresh_positive_when_never_banked(tmp_path):
    path = _loop_tree(tmp_path, record=False)
    found = hits(FRESH_SRC, "loop-manifest-fresh", path=path)
    assert len(found) == 2  # one per family
    assert "SOURCES.json missing" in found[0].message


def test_loop_manifest_fresh_positive_when_not_folded_in(tmp_path):
    # manifests exist but predate the loop layer: controller.py absent
    # from the fingerprint — the silent-non-coverage hole
    path = _loop_tree(tmp_path, covered=False)
    found = hits(FRESH_SRC, "loop-manifest-fresh", path=path)
    assert len(found) == 2
    assert all("not folded into" in f.message for f in found)


def test_loop_manifest_fresh_suppressed(tmp_path):
    path = _loop_tree(tmp_path, record=False)
    src = ("# graftlint: disable-file=loop-manifest-fresh -- "
           "manifest regen follows in this PR\n" + FRESH_SRC)
    assert not hits(src, "loop-manifest-fresh", path=path)
    assert suppressed_hits(src, "loop-manifest-fresh", path=path)


def test_loop_manifest_fresh_ignores_other_packages(tmp_path):
    other = tmp_path / "sparknet_tpu" / "serve" / "engine.py"
    other.parent.mkdir(parents=True, exist_ok=True)
    other.write_text(FRESH_SRC)
    assert not hits(FRESH_SRC, "loop-manifest-fresh", path=str(other))
    assert not hits(FRESH_SRC, "loop-manifest-fresh")


# -- feed-shm-cleanup -------------------------------------------------------

SHM_BAD = """
from multiprocessing import shared_memory

def build_ring(nbytes):
    shm = shared_memory.SharedMemory(create=True, size=nbytes)
    return shm
"""

SHM_GOOD_FINALLY = """
from multiprocessing import shared_memory

def run(nbytes):
    shm = shared_memory.SharedMemory(create=True, size=nbytes)
    try:
        work(shm)
    finally:
        shm.close()
        shm.unlink()
"""

SHM_GOOD_CLOSE_METHOD = """
from multiprocessing import shared_memory

class Ring:
    def __init__(self, nbytes):
        self.shm = shared_memory.SharedMemory(create=True, size=nbytes)

    def close(self):
        self.shm.close()
        self.shm.unlink()
"""

SHM_ATTACH_ONLY = """
from multiprocessing import shared_memory

def attach(name):
    return shared_memory.SharedMemory(name=name)
"""


def test_shm_cleanup_positive_without_unlink():
    assert hits(SHM_BAD, "feed-shm-cleanup")


def test_shm_cleanup_clean_with_finally_unlink():
    assert not hits(SHM_GOOD_FINALLY, "feed-shm-cleanup")


def test_shm_cleanup_clean_with_close_method():
    assert not hits(SHM_GOOD_CLOSE_METHOD, "feed-shm-cleanup")


def test_shm_cleanup_attach_side_exempt():
    assert not hits(SHM_ATTACH_ONLY, "feed-shm-cleanup")


def test_shm_cleanup_unlink_in_ordinary_helper_still_flagged():
    """An unlink buried in a non-teardown-named helper is the rule's
    documented blind-spot boundary: still a finding."""
    assert hits(SHM_BAD + "\ndef helper(shm):\n    shm.unlink()\n",
                "feed-shm-cleanup")


def test_shm_cleanup_suppressible():
    src = SHM_BAD.replace(
        "create=True, size=nbytes)",
        "create=True, size=nbytes)  # graftlint: disable=feed-shm-cleanup"
        " -- fixture: lifetime owned by the caller")
    assert not hits(src, "feed-shm-cleanup")
    assert suppressed_hits(src, "feed-shm-cleanup")


# -- obs-fenced-span --------------------------------------------------------

SPAN_BAD = """
import jax

def timed(rec, step, feeds):
    with rec.span("train") as sp:
        out = step(feeds)
    return out
"""

SPAN_BAD_NO_AS = """
import jax

def timed(rec, step, feeds):
    with rec.span("train"):
        out = step(feeds)
    return out
"""

SPAN_GOOD_FENCED = """
import jax

def timed(rec, step, feeds):
    with rec.span("train") as sp:
        out = step(feeds)
        sp.fence(out)
    return out
"""

SPAN_GOOD_FENCE_VALUE = """
import jax

def timed(rec, solver, fn):
    with rec.span("solve") as sp:
        loss = solver.solve(fn)
        sp.fence_value(loss)
    return loss
"""

SPAN_GOOD_HOST = """
import jax

def staged(rec, paths):
    with rec.span("stage-db", host=True):
        return [open(p).read() for p in paths]
"""


def test_obs_fenced_span_positive():
    found = hits(SPAN_BAD, "obs-fenced-span")
    assert len(found) == 1
    assert "fence stamp" in found[0].message


def test_obs_fenced_span_positive_without_as_binding():
    found = hits(SPAN_BAD_NO_AS, "obs-fenced-span")
    assert len(found) == 1
    assert "`as` binding" in found[0].message


def test_obs_fenced_span_suppressed():
    src = SPAN_BAD.replace(
        '    with rec.span("train") as sp:',
        '    with rec.span("train") as sp:  '
        "# graftlint: disable=obs-fenced-span -- fenced by the helper")
    assert not hits(src, "obs-fenced-span")
    assert suppressed_hits(src, "obs-fenced-span")


def test_obs_fenced_span_clean_when_fenced():
    assert not hits(SPAN_GOOD_FENCED, "obs-fenced-span")
    assert not hits(SPAN_GOOD_FENCE_VALUE, "obs-fenced-span")


def test_obs_fenced_span_clean_when_host():
    assert not hits(SPAN_GOOD_HOST, "obs-fenced-span")


def test_obs_fenced_span_ignores_non_jax_modules():
    # a host-side tool's span times host work by construction
    assert not hits(SPAN_BAD.replace("import jax", "import os"),
                    "obs-fenced-span")


# -- suppression machinery --------------------------------------------------

BANK_TWO = BANK_BAD + """
def save_again(rec):
    with open("docs/serve_bench_last.json", "w") as f:
        json.dump(rec, f)
"""


def test_disable_next_line_directive():
    src = BANK_BAD.replace(
        '    with open(path + ".tmp", "w") as f:',
        "    # graftlint: disable-next-line=bank-guard -- rig\n"
        '    with open(path + ".tmp", "w") as f:')
    assert not hits(src, "bank-guard")
    assert len(suppressed_hits(src, "bank-guard")) == 1


def test_disable_file_directive():
    src = ("# graftlint: disable-file=bank-guard -- whole-file rig\n"
           + BANK_TWO)
    assert not hits(src, "bank-guard")
    assert len(suppressed_hits(src, "bank-guard")) == 2


def test_disable_all_and_comma_lists():
    src = BANK_BAD.replace(
        'with open(path + ".tmp", "w") as f:',
        'with open(path + ".tmp", "w") as f:  # graftlint: disable=all')
    assert not hits(src, "bank-guard")
    src2 = SHM_BAD.replace(
        "create=True, size=nbytes)",
        "create=True, size=nbytes)  "
        "# graftlint: disable=feed-shm-cleanup,bank-guard -- x")
    assert not hits(src2, "feed-shm-cleanup")


def test_suppression_is_per_line_not_per_file():
    # a directive on ONE hit must not hide the other
    src = BANK_TWO.replace(
        'with open(path + ".tmp", "w") as f:',
        'with open(path + ".tmp", "w") as f:  '
        "# graftlint: disable=bank-guard -- only this one")
    assert len(hits(src, "bank-guard")) == 1


def test_parse_error_is_reported_not_raised():
    findings = lint_source("def broken(:\n", "bad.py")
    assert findings and findings[0].rule == "parse-error"


# -- CLI --------------------------------------------------------------------


def test_cli_json_format_and_exit_code(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(SHM_BAD)
    rc = cli_main([str(bad), "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["unsuppressed"] == 1
    assert out["findings"][0]["rule"] == "feed-shm-cleanup"


def test_cli_clean_file_exits_zero(tmp_path, capsys):
    good = tmp_path / "good.py"
    good.write_text(BANK_GOOD)
    rc = cli_main([str(good)])
    assert rc == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_cli_single_rule_filter(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(SHM_BAD + BANK_BAD)
    rc = cli_main([str(bad), "--rule", "bank-guard", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["rule"] for f in out["findings"]} == {"bank-guard"}


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in EXPECTED_RULES:
        assert rule_id in out


def test_cli_unknown_rule_is_usage_error(capsys):
    assert cli_main(["--rule", "no-such-rule"]) == 2


# -- CI wiring: the repo lints itself ---------------------------------------


def test_default_scope_covers_contract_surface():
    paths = default_paths()
    tails = {p.rsplit("/", 1)[-1] for p in paths}
    assert {"sparknet_tpu", "tools", "bench.py"} <= tails


def test_repo_self_lint_is_clean():
    """THE ratchet: zero unsuppressed findings over sparknet_tpu/,
    tools/, and bench.py.  A new violation fails tier-1; an intentional
    exception must carry a justified ``# graftlint: disable=...``."""
    findings = lint_paths(default_paths())
    bad = [f for f in findings if not f.suppressed]
    assert not bad, "unsuppressed graftlint findings:\n" + "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in bad)


# -- obs-vocab-coverage -----------------------------------------------------


_VOCAB_SCHEMA = (
    'EVENTS: dict[str, tuple[dict, dict]] = {\n'
    '    "round": ({"run_id": str}, {}),\n'
    '    "serve": ({"run_id": str, "kind": str}, {}),\n'
    '}\n'
)


def _vocab_tree(tmp_path, report_has=("round", "serve"),
                doc_has=("round", "serve"), write_report=True,
                write_doc=True):
    """A fake repo around obs/schema.py: a report.py rendering some
    event names as quoted literals, an OBSERVABILITY.md documenting
    some as backticked terms."""
    rel = tmp_path / "sparknet_tpu" / "obs" / "schema.py"
    rel.parent.mkdir(parents=True, exist_ok=True)
    rel.write_text(_VOCAB_SCHEMA)
    if write_report:
        body = "\n".join(
            f'    if ev.get("event") == "{n}":\n        pass'
            for n in report_has)
        (rel.parent / "report.py").write_text(
            f"def render(ev):\n{body or '    pass'}\n")
    if write_doc:
        docs = tmp_path / "docs"
        docs.mkdir(exist_ok=True)
        (docs / "OBSERVABILITY.md").write_text(
            "# obs\n" + "".join(f"the `{n}` event\n" for n in doc_has))
    return str(rel)


def test_obs_vocab_clean_when_fully_covered(tmp_path):
    path = _vocab_tree(tmp_path)
    assert not hits(_VOCAB_SCHEMA, "obs-vocab-coverage", path=path)


def test_obs_vocab_positive_when_report_misses_an_event(tmp_path):
    path = _vocab_tree(tmp_path, report_has=("round",))
    found = hits(_VOCAB_SCHEMA, "obs-vocab-coverage", path=path)
    assert len(found) == 1
    assert "'serve'" in found[0].message
    assert "report.py" in found[0].message
    # the finding lands at the offending EVENTS key's own line
    assert found[0].line == 3


def test_obs_vocab_positive_when_docs_miss_an_event(tmp_path):
    path = _vocab_tree(tmp_path, doc_has=("serve",))
    found = hits(_VOCAB_SCHEMA, "obs-vocab-coverage", path=path)
    assert len(found) == 1
    assert "'round'" in found[0].message
    assert "OBSERVABILITY.md" in found[0].message


def test_obs_vocab_positive_when_consumer_files_missing(tmp_path):
    path = _vocab_tree(tmp_path, write_report=False, write_doc=False)
    found = hits(_VOCAB_SCHEMA, "obs-vocab-coverage", path=path)
    # two missing-consumer findings; per-name findings only against
    # the consumers that could be read
    assert len(found) == 2
    assert all("missing or unreadable" in f.message for f in found)


def test_obs_vocab_ignores_other_obs_files(tmp_path):
    # the rule anchors on schema.py alone — report.py itself (which
    # contains the same names) must not trigger it
    tree = _vocab_tree(tmp_path)
    report = os.path.join(os.path.dirname(tree), "report.py")
    assert not hits(_VOCAB_SCHEMA, "obs-vocab-coverage", path=report)
    assert not hits(_VOCAB_SCHEMA, "obs-vocab-coverage")


def test_obs_vocab_suppressible(tmp_path):
    path = _vocab_tree(tmp_path, report_has=("round",))
    src = ("# graftlint: disable-file=obs-vocab-coverage -- "
           "renderer lands later in this PR\n" + _VOCAB_SCHEMA)
    assert not hits(src, "obs-vocab-coverage", path=path)
    assert suppressed_hits(src, "obs-vocab-coverage", path=path)


def test_obs_vocab_real_repo_is_covered():
    """The live schema/report/docs triple passes its own rule."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    real = os.path.join(root, "sparknet_tpu", "obs", "schema.py")
    with open(real, encoding="utf-8") as f:
        src = f.read()
    assert not hits(src, "obs-vocab-coverage", path=real)
