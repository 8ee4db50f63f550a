"""Declarative SLO gates (sparknet_tpu/obs/slo.py; docs/slo_manifest.json).

Two layers: gate semantics on synthetic journals (burn detection,
vacuous passes, the disturbance suspension that keeps fault-rehearsal
legs honest), and the repo-level smoke check — every banked evidence
journal, including the four chip-free dryrun specimens in
docs/evidence_r7/, must pass the checked-in manifest.  A burn here
means either the telemetry regressed or the manifest's promise did;
both are PR-blocking by design.

Stdlib-only under the obs-package contract (no jax import anywhere on
this path), so the whole file rides the smoke tier.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

from sparknet_tpu.obs import schema, slo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.smoke


def _results_by_id(results):
    return {r["id"]: r for r in results}


def _request(run_id="r", wait=1.0, model="live", bucket=8, **extra):
    return {"event": "request", "run_id": run_id, "model": model,
            "bucket": bucket, "queue_wait_ms": wait,
            "batch_assembly_ms": 0.1, "device_ms": 2.0,
            "total_ms": wait + 2.1, **extra}


@pytest.fixture
def manifest():
    return slo.load_manifest()


# -- manifest ---------------------------------------------------------------


def test_manifest_loads_and_every_kind_has_an_evaluator(manifest):
    for spec in manifest["slos"]:
        assert spec["kind"] in slo._GATES, spec


def test_unknown_gate_kind_burns_loudly():
    results = slo.evaluate([], {"slos": [{"id": "x", "kind": "nope"}]})
    assert results[0]["ok"] is False
    assert "unknown gate kind" in results[0]["detail"]


# -- gate semantics ---------------------------------------------------------


def test_all_gates_vacuous_without_subject_events(manifest):
    events = [{"event": "run_start", "run_id": "r1"},
              {"event": "run_end", "run_id": "r1", "rounds": 0,
               "spans": 0, "compiles": 0}]
    results = slo.evaluate(events, manifest)
    assert all(r["ok"] for r in results)
    assert all(not r["applicable"] for r in results)


def test_warm_queue_p99_skips_warmup_then_burns_on_the_tail(manifest):
    # 8 warmup tickets at 500 ms are forgiven; steady traffic at 40+ms
    # burns the 40 ms bound
    events = [_request(wait=500.0) for _ in range(8)]
    events += [_request(wait=80.0) for _ in range(50)]
    by_id = _results_by_id(slo.evaluate(events, manifest))
    gate = by_id["warm-queue-p99"]
    assert gate["applicable"] and not gate["ok"]
    assert gate["value"] > 40.0


def test_warm_queue_p99_passes_on_steady_traffic(manifest):
    events = [_request(wait=500.0) for _ in range(8)]
    events += [_request(wait=3.0) for _ in range(50)]
    gate = _results_by_id(slo.evaluate(events, manifest))["warm-queue-p99"]
    assert gate["applicable"] and gate["ok"]


def test_warm_queue_p99_suspends_on_disturbance_journals(manifest):
    # a replica kill mid-traffic: elevated waits are BY DESIGN, the
    # journal answers to zero-drop/compiles-zero — the latency gate
    # must suspend itself (vacuous pass, reason in the detail), never
    # silently forgive nor falsely burn
    events = [_request(wait=500.0) for _ in range(60)]
    events.append({"event": "replica", "run_id": "r",
                   "kind": "replica_down", "replica": 1, "rerouted": 3})
    gate = _results_by_id(slo.evaluate(events, manifest))["warm-queue-p99"]
    assert gate["ok"] and not gate["applicable"]
    assert "disturbance" in gate["detail"]


def test_slot_wait_share_burns_past_five_percent(manifest):
    feed = {"event": "feed", "run_id": "r", "name": "train",
            "batches": 10, "images": 100, "wall_s": 1.0,
            "stages": {"slot_wait": 0.2, "source": 0.8, "write": 1.0}}
    gate = _results_by_id(slo.evaluate([feed], manifest))["slot-wait-share"]
    assert gate["applicable"] and not gate["ok"]
    assert gate["value"] == 0.1  # 0.2 of 2.0 staged seconds


def test_compiles_zero_burns_on_unexpected_but_not_expected(manifest):
    expected = {"event": "recompile", "run_id": "r", "count": 1,
                "total": 1, "where": "elastic", "expected": True}
    gate = _results_by_id(
        slo.evaluate([expected], manifest))["post-warmup-compiles"]
    assert gate["applicable"] and gate["ok"]
    unexpected = dict(expected, expected=False)
    gate = _results_by_id(
        slo.evaluate([unexpected], manifest))["post-warmup-compiles"]
    assert not gate["ok"]


def test_dropped_zero_burns_on_any_dropped_ticket(manifest):
    summary = {"event": "replica", "run_id": "r", "kind": "summary",
               "requests": 100, "dropped": 1}
    gate = _results_by_id(slo.evaluate([summary], manifest))["zero-drop"]
    assert gate["applicable"] and not gate["ok"]


def test_roofline_gate_burns_on_value_above_bound(manifest):
    bench = {"event": "bench", "run_id": "r", "metric": "m",
             "measured": True, "fenced": True,
             "record": {"metric": "m", "value": 99999.0,
                        "roofline_img_s_upper_bound": 13213.0}}
    gate = _results_by_id(
        slo.evaluate([bench], manifest))["roofline-ceiling"]
    assert gate["applicable"] and not gate["ok"]
    # a rehearsal (measured: false) record is not evidence and not gated
    rehearsal = dict(bench, measured=False)
    gate = _results_by_id(
        slo.evaluate([rehearsal], manifest))["roofline-ceiling"]
    assert not gate["applicable"] and gate["ok"]


# -- verdict event ----------------------------------------------------------


def test_verdict_fields_make_a_schema_valid_slo_event(manifest):
    results = slo.evaluate([_request()], manifest)
    fields = slo.verdict_fields(
        "some_job", results, journal="docs/evidence_r7/x.jsonl",
        manifest_path="docs/slo_manifest.json")
    line = schema.make_event("slo", **fields)
    assert schema.validate_line(line) == []
    assert line["ok"] is True and "burned" not in line


def test_verdict_fields_name_the_burned_gates(manifest):
    events = [_request(wait=500.0) for _ in range(60)]
    results = slo.evaluate(events, manifest)
    fields = slo.verdict_fields("j", results)
    assert fields["ok"] is False
    assert "warm-queue-p99" in fields["burned"]


# -- the repo's own evidence passes its own gates ---------------------------


def test_every_banked_evidence_journal_passes_the_manifest(manifest):
    """The acceptance gate: `python -m sparknet_tpu.obs slo` green over
    all docs/evidence_r*/ journals — the four dryrun specimens.
    """
    journals = sorted(glob.glob(
        os.path.join(ROOT, "docs", "evidence_r*", "*.jsonl")))
    assert len(journals) >= 4  # the four r7 dryruns
    names = {os.path.basename(p) for p in journals}
    for required in ("elastic_dryrun.jsonl", "serve_dryrun.jsonl",
                     "loop_dryrun.jsonl", "replica_dryrun.jsonl"):
        assert required in names, f"banked dryrun specimen missing: {required}"
    for path in journals:
        results = slo.evaluate_journal(path, manifest)
        burned = [r for r in results if not r["ok"]]
        assert not burned, (path, burned)


def test_slo_cli_discovers_and_passes(tmp_path):
    """`obs slo` with no args discovers the banked journals; exit 0."""
    proc = subprocess.run(
        [sys.executable, "-m", "sparknet_tpu.obs", "slo", "--quiet"],
        capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_slo_cli_burns_exit_nonzero(tmp_path):
    journal = tmp_path / "burn.jsonl"
    events = [_request(wait=500.0) for _ in range(60)]
    journal.write_text("".join(json.dumps(e) + "\n" for e in events))
    proc = subprocess.run(
        [sys.executable, "-m", "sparknet_tpu.obs", "slo", str(journal)],
        capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 1
    assert "BURN" in proc.stdout
