"""conccheck: fixture snippets per defect class + the repo-wide gate.

Mirrors test_graftlint's structure for the fourth analysis engine: each
rule gets a positive fixture, a suppressed twin, and a clean rewrite,
all run through ``run_conccheck`` against a tmp repo so the engine's
boundary is pinned from both sides with zero chip time.  The repo-wide
test at the bottom is the CI wiring for the acceptance criterion:
``python -m sparknet_tpu.analysis conc`` exits 0 with every suppression
justified inline and the docs/conc_contracts/ manifests fresh.
"""

import json
import os

import pytest

from sparknet_tpu.analysis.conccheck import (
    CONC_RULES,
    iter_rules,
    run_conccheck,
)

pytestmark = pytest.mark.smoke


def _run(tmp_path, files, *, update=False, patterns=None):
    """Materialize fixture files into a tmp repo and run the engine."""
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    return run_conccheck(
        paths=patterns or tuple(files),
        repo=str(tmp_path),
        manifest_dir=str(tmp_path / "docs" / "conc_contracts"),
        update=update)


def _hits(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


def _suppressed(findings, rule):
    return [f for f in findings if f.rule == rule and f.suppressed]


# -- registry ---------------------------------------------------------------


def test_rule_catalog():
    rules = dict(iter_rules())
    assert rules == CONC_RULES
    assert set(CONC_RULES) == {
        "conc-unguarded-write", "conc-lock-order-cycle",
        "conc-blocking-under-lock", "conc-jax-in-worker",
        "conc-manifest-missing", "conc-manifest-drift"}


# -- conc-unguarded-write ---------------------------------------------------

UNGUARDED = """
import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def guarded(self):
        with self._lock:
            self._n = 1

    def bare(self):
        self._n = 2
"""


def test_unguarded_write_positive(tmp_path):
    findings, _ = _run(tmp_path, {"fix.py": UNGUARDED})
    found = _hits(findings, "conc-unguarded-write")
    assert len(found) == 1
    assert "Counter._n" in found[0].message or "_n" in found[0].message
    assert "guarded by" in found[0].message


def test_unguarded_write_suppressed(tmp_path):
    src = UNGUARDED.replace(
        "        self._n = 2",
        "        # conccheck: unguarded=single-writer init race is "
        "benign here\n        self._n = 2")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-unguarded-write")
    assert _suppressed(findings, "conc-unguarded-write")


def test_unguarded_write_clean_when_all_guarded(tmp_path):
    src = UNGUARDED.replace(
        "    def bare(self):\n        self._n = 2",
        "    def bare(self):\n        with self._lock:\n"
        "            self._n = 2")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-unguarded-write")


def test_locked_suffix_methods_are_caller_held(tmp_path):
    src = UNGUARDED.replace(
        "    def bare(self):\n        self._n = 2",
        "    def _bump_locked(self):\n        self._n = 2")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-unguarded-write")


# -- conc-lock-order-cycle --------------------------------------------------

CYCLE = """
import threading

class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def ab(self):
        with self._a:
            with self._b:
                pass

    def ba(self):
        with self._b:
            with self._a:
                pass
"""


def test_lock_order_cycle_positive(tmp_path):
    findings, manifests = _run(tmp_path, {"fix.py": CYCLE})
    found = _hits(findings, "conc-lock-order-cycle")
    assert len(found) == 1
    assert "Pair._a" in found[0].message
    assert "Pair._b" in found[0].message
    edges = {tuple(e)
             for e in manifests["lock_graph"]["contract"]["edges"]}
    assert ("Pair._a", "Pair._b") in edges
    assert ("Pair._b", "Pair._a") in edges


def test_lock_order_clean_when_consistent(tmp_path):
    src = CYCLE.replace(
        "        with self._b:\n            with self._a:",
        "        with self._a:\n            with self._b:")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-lock-order-cycle")


def test_cross_function_cycle_through_calls(tmp_path):
    # inner acquisitions reached THROUGH a call under a held lock
    src = """
import threading

class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def take_a(self):
        with self._a:
            pass

    def take_b(self):
        with self._b:
            pass

    def ab(self):
        with self._a:
            self.take_b()

    def ba(self):
        with self._b:
            self.take_a()
"""
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert len(_hits(findings, "conc-lock-order-cycle")) == 1


# -- conc-blocking-under-lock -----------------------------------------------

BLOCKING = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()

    def bad(self, lowered, q, t):
        with self._lock:
            lowered.compile()
            q.get()
            t.join()

    def fine(self, lowered, q, t):
        lowered.compile()
        with self._lock:
            q.get(timeout=1.0)
            t.join(timeout=1.0)
"""


def test_blocking_under_lock_positive(tmp_path):
    findings, _ = _run(tmp_path, {"fix.py": BLOCKING})
    found = _hits(findings, "conc-blocking-under-lock")
    assert len(found) == 3
    names = " ".join(f.message for f in found)
    assert ".compile()" in names
    assert ".get()" in names
    assert ".join()" in names


def test_blocking_under_lock_suppressed(tmp_path):
    src = BLOCKING.replace(
        "            lowered.compile()\n",
        "            # conccheck: blocking=warmup path, no concurrent "
        "holders yet\n            lowered.compile()\n").replace(
        "            q.get()\n",
        "            # conccheck: blocking=producer is this thread\n"
        "            q.get()\n").replace(
        "            t.join()\n",
        "            # conccheck: blocking=target never takes this "
        "lock\n            t.join()\n")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-blocking-under-lock")
    assert len(_suppressed(findings, "conc-blocking-under-lock")) == 3


def test_blocking_clean_with_timeouts_outside(tmp_path):
    src = BLOCKING.replace(
        "            lowered.compile()\n            q.get()\n"
        "            t.join()\n", "            pass\n")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-blocking-under-lock")


def test_shm_unlink_under_lock_flagged(tmp_path):
    src = """
import threading

class Ring:
    def __init__(self):
        self._lock = threading.Lock()

    def teardown(self, shm):
        with self._lock:
            shm.unlink()
"""
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert len(_hits(findings, "conc-blocking-under-lock")) == 1


# -- conc-jax-in-worker -----------------------------------------------------

JAX_WORKER = """
import multiprocessing as mp


def worker(src):
    import jax
    return jax.devices()


def spawn():
    p = mp.Process(target=worker, args=(None,))
    p.start()
"""


def test_jax_in_worker_positive(tmp_path):
    findings, manifests = _run(tmp_path, {"fix.py": JAX_WORKER})
    found = _hits(findings, "conc-jax-in-worker")
    assert len(found) == 1
    assert "worker" in found[0].message
    tax = manifests["roles"]["contract"]
    assert any("worker" in r for r in tax["process_roots"])
    assert "fix.py::worker" in tax["process_reachable"]


def test_jax_in_worker_suppressed(tmp_path):
    src = JAX_WORKER.replace(
        "    import jax\n",
        "    # conccheck: jax=device-bound worker by design, not a "
        "ring worker\n    import jax\n")
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-jax-in-worker")
    assert _suppressed(findings, "conc-jax-in-worker")


def test_jax_clean_in_host_only_worker(tmp_path):
    src = """
import multiprocessing as mp


def worker(src):
    return src.get(0, 0)


def spawn():
    p = mp.Process(target=worker, args=(None,))
    p.start()
"""
    findings, _ = _run(tmp_path, {"fix.py": src})
    assert not _hits(findings, "conc-jax-in-worker")


def test_typed_param_resolves_worker_callee_across_modules(tmp_path):
    # the records.py shape: the worker's source parameter is typed by
    # annotation and its .get override lives in ANOTHER audited module
    files = {
        "base.py": """
import multiprocessing as mp


class Source:
    def get(self, epoch, index):
        raise NotImplementedError


def worker(source: Source):
    return source.get(0, 0)


def spawn():
    mp.Process(target=worker).start()
""",
        "sub.py": """
from base import Source


class JaxSource(Source):
    def get(self, epoch, index):
        import jax
        return jax.numpy.zeros(())
""",
    }
    findings, manifests = _run(tmp_path, files)
    found = _hits(findings, "conc-jax-in-worker")
    assert any("JaxSource.get" in f.message for f in found)
    reach = manifests["roles"]["contract"]["process_reachable"]
    assert "sub.py::JaxSource.get" in reach


# -- manifest bank / drift / allow loop -------------------------------------


def test_manifest_bank_drift_allow_loop(tmp_path):
    files = {"fix.py": UNGUARDED.replace(
        "    def bare(self):\n        self._n = 2\n", "")}
    # 1. unbanked: missing findings for both manifests
    findings, _ = _run(tmp_path, files)
    assert len(_hits(findings, "conc-manifest-missing")) == 2

    # 2. bank, then re-run clean
    _run(tmp_path, files, update=True)
    mdir = tmp_path / "docs" / "conc_contracts"
    assert sorted(p.name for p in mdir.iterdir()) == [
        "SOURCES.json", "lock_graph.json", "roles.json"]
    findings, _ = _run(tmp_path, files)
    assert not [f for f in findings if not f.suppressed]

    # 3. drift: a second lock changes the contract
    drifted = dict(files)
    drifted["fix.py"] += (
        "\n_extra = threading.Lock()\n"
        "def touch():\n    with _extra:\n        pass\n")
    findings, _ = _run(tmp_path, drifted)
    drift = _hits(findings, "conc-manifest-drift")
    assert drift and "lock_graph" in drift[0].message

    # 4. allow: an explicit allow entry suppresses the drift finding
    for name in ("lock_graph", "roles"):
        path = mdir / f"{name}.json"
        data = json.loads(path.read_text())
        data["allow"] = {"conc-manifest-drift":
                         "intentional fixture drift"}
        path.write_text(json.dumps(data))
    findings, _ = _run(tmp_path, drifted)
    assert not _hits(findings, "conc-manifest-drift")
    assert _suppressed(findings, "conc-manifest-drift")

    # 5. --update re-banks and clears the drift (allow map survives)
    _run(tmp_path, drifted, update=True)
    findings, _ = _run(tmp_path, drifted)
    assert not [f for f in findings if f.rule == "conc-manifest-drift"]
    kept = json.loads((mdir / "lock_graph.json").read_text())
    assert kept["allow"] == {"conc-manifest-drift":
                             "intentional fixture drift"}


# -- CLI + repo-wide gate ---------------------------------------------------


def test_cli_list_rules_and_json(capsys):
    from sparknet_tpu.analysis.__main__ import main as cli_main

    rc = cli_main(["conc", "--list-rules"])
    out = capsys.readouterr().out
    assert rc == 0
    for rule in CONC_RULES:
        assert rule in out


def test_repo_wide_conc_is_clean_and_manifests_fresh(capsys):
    """The acceptance criterion: zero unsuppressed findings over the
    real audited surface, against the banked manifests."""
    from sparknet_tpu.analysis.__main__ import main as cli_main

    rc = cli_main(["conc", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["unsuppressed"] == 0
    # the suppressions that ARE banked must each carry a justification
    # (the grammar requires one; this pins the count so a new stray
    # suppression shows up in review)
    assert payload["suppressed"] == 3


def test_repo_manifests_match_sources_fingerprint():
    """SOURCES.json covers exactly the audited surface."""
    from sparknet_tpu.analysis.conccheck import (
        MANIFEST_DIR, sources_fingerprint)

    with open(os.path.join(MANIFEST_DIR, "SOURCES.json"),
              encoding="utf-8") as f:
        banked = json.load(f)
    assert banked == sources_fingerprint()
