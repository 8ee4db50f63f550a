"""memcheck: per-defect fixtures + the banked memory-contract smoke gate.

Mirrors test_graphcheck.py for the third analysis engine: the liveness
walk is pinned against a hand-computed toy program (including the
donation credit whose absence double-counts the carry), the two
estimators must agree on the cheap real modes (solo + dp) within the
documented tolerance, the batch-fit arithmetic is monotone by
construction, the VMEM audit flags an over-budget kernel, the manifest
loop round-trips bank/drift/allow.  The full mode sweep is the
slow-marked twin (tests/test_memcheck_sweep.py).
"""

import json

import jax
import jax.numpy as jnp
import pytest

from sparknet_tpu.analysis import mem_model
from sparknet_tpu.analysis.mem_model import (
    HBM_USABLE_FRAC,
    MemEqn,
    MemProgram,
    PEAK_RATIO_WINDOW,
    RESIDENCY_TOL_BYTES,
    V5E_HBM_BYTES,
    V5E_VMEM_BYTES,
    affine_fit,
    max_fit_batch,
    mode_footprint,
    peak_residency,
    predicted_bytes,
)
from sparknet_tpu.analysis.memcheck import (
    MEM_RULES,
    extract_program,
    run_batch_fit,
    run_memcheck,
    run_vmem_audit,
    sources_fingerprint,
    trace_mem,
)

pytestmark = pytest.mark.smoke


# -- the liveness walk vs a hand-computed toy program -----------------------


def _toy(donated=("a",)):
    """inputs a=100 (donated by default), b=40; a -> t1(30) -> out(20).

    Hand walk (donated case): entry live {a, b} = 140; eqn0 writes t1
    -> 170 (the peak; a dies after, its last read); eqn1 writes out ->
    90.  Donation credit subtracts a's 100 once (the donated input and
    the output aliasing it are one allocation): peak 70, residency
    100+40+20-100 = 60, temp 10.
    """
    return MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("t1",)),
              MemEqn(reads=("t1", "b"), writes=("out",))],
        sizes={"a": 100, "b": 40, "t1": 30, "out": 20},
        inputs=["a", "b"], outputs=["out"],
        donated=frozenset(donated),
    )


def test_liveness_walk_matches_hand_computation():
    res = peak_residency(_toy())
    assert res == {"peak_bytes": 70, "residency_bytes": 60,
                   "temp_bytes": 10, "peak_at_eqn": 0}


def test_undonated_carry_is_counted_twice():
    """Dropping the donation holds both the input carry and the output
    alongside each other: residency and peak grow by exactly the
    carry's bytes — the 2x params+slots class the residency tolerance
    exists to catch."""
    donated = peak_residency(_toy())
    undonated = peak_residency(_toy(donated=()))
    assert undonated["residency_bytes"] - donated["residency_bytes"] == 100
    # peak grows by AT LEAST the carry (here more: the undying input
    # also overlaps the buffers the donated walk had already freed)
    assert undonated["peak_bytes"] - donated["peak_bytes"] >= 100
    assert undonated["peak_bytes"] == 190  # 140 entry + t1 + out, hand-walked


def test_scratch_term_only_counts_on_the_xcheck_side():
    prog = MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("out",), scratch=1000)],
        sizes={"a": 10, "out": 10}, inputs=["a"], outputs=["out"])
    assert peak_residency(prog)["peak_bytes"] == 20
    assert peak_residency(prog, xcheck=True)["peak_bytes"] == 1020


def test_extract_program_credits_only_established_donation():
    """The same step jitted with and without donate_argnums: only the
    lowering that actually establishes aliasing gets the credit."""
    def step(w, x):
        return w + x.sum(), (w * w).sum()

    w = jnp.ones((128,), jnp.float32)
    x = jnp.ones((16,), jnp.float32)
    traced = jax.jit(step, donate_argnums=(0,)).trace(w, x)
    donated = extract_program(traced.jaxpr, donated_flags=[True, False])
    plain = extract_program(traced.jaxpr, donated_flags=[False, False])
    assert donated.donated_bytes() == w.nbytes
    assert plain.donated_bytes() == 0
    d = peak_residency(donated)
    p = peak_residency(plain)
    assert p["residency_bytes"] - d["residency_bytes"] == w.nbytes


# -- the estimator-agreement gate on the cheap real modes -------------------


def test_memcheck_smoke_gate_solo_and_dp():
    """THE ratchet, memory edition: the two cheap modes must match the
    banked manifests with zero unsuppressed findings, and the two
    independent estimators must agree within the documented tolerance
    (residency tight, peak inside the ratio window)."""
    findings, manifests = run_memcheck(["solo", "dp"])
    bad = [f for f in findings if not f.suppressed]
    assert not bad, "unsuppressed memcheck findings:\n" + "\n".join(
        f"{f.path}: [{f.rule}] {f.message}" for f in bad)
    for mode in ("solo", "dp"):
        c = manifests[mode]["contract"]
        assert c["residency_delta_bytes"] <= RESIDENCY_TOL_BYTES
        lo, hi = PEAK_RATIO_WINDOW
        assert lo <= c["peak_ratio"] <= hi
        # donation is established on both real modes (the carry credit)
        assert c["donated_bytes"] > 0
        budget = int(V5E_HBM_BYTES * HBM_USABLE_FRAC)
        assert c["analytic"]["peak_bytes"] < budget
    # dp shards the batch over 8 devices: its per-device activation
    # footprint must come in below solo's single-chip one
    assert (manifests["dp"]["contract"]["analytic"]["peak_bytes"]
            < manifests["solo"]["contract"]["analytic"]["peak_bytes"])


def test_trace_mem_residency_matches_xla_on_solo():
    from sparknet_tpu.parallel.modes import build_target

    art = trace_mem(build_target("solo", 8))
    res = peak_residency(art.program)
    assert abs(res["residency_bytes"] - art.xla["residency_bytes"]) \
        <= RESIDENCY_TOL_BYTES


# -- batch-fit arithmetic ---------------------------------------------------


def test_affine_fit_and_monotonicity():
    c0, c1 = affine_fit(8, 800, 16, 1600)
    assert (c0, c1) == (0, 100)
    assert predicted_bytes(c0, c1, 32) == 3200
    # monotone in budget, anti-monotone in the coefficients
    assert max_fit_batch(0, 100, 10_000) == 96  # floor to multiple of 8
    assert max_fit_batch(0, 100, 20_000) >= max_fit_batch(0, 100, 10_000)
    assert max_fit_batch(5_000, 100, 10_000) <= max_fit_batch(0, 100, 10_000)
    assert max_fit_batch(0, 200, 10_000) <= max_fit_batch(0, 100, 10_000)
    # an infeasible constant term is 0, not negative
    assert max_fit_batch(20_000, 100, 10_000) == 0
    with pytest.raises(ValueError):
        affine_fit(8, 1, 8, 2)


def test_mode_footprint_divisors():
    entry = {"c0": 1000, "c1": 10, "params_slots_bytes": 600,
             "tp_params_slots_bytes": 350}
    solo = mode_footprint(entry, "solo", 80)
    assert solo == 1000 + 800
    # dp divides the activation term by the data axis (8), not params
    assert mode_footprint(entry, "dp", 80) == 1000 + 100
    # tp swaps in the per-blob-sharded params+slots figure
    assert mode_footprint(entry, "tp", 80) == 1000 - 600 + 350 + 800
    # gpipe places 1/S of the params but holds every microbatch
    gpipe = mode_footprint(entry, "gpipe", 80)
    assert gpipe == int(1000 - 600 + 600 / 8 + 800)


def test_batch_fit_real_family_is_monotone(tmp_path):
    """cifar10_quick through the real abstract-trace path: activations
    linear in batch, bf16 fits at least as many images as f32, dp at
    least as many as solo."""
    findings, table = run_batch_fit(
        families=["cifar10_quick"],
        banked_path=str(tmp_path / "fit.json"), update=True)
    assert findings == []
    fam = table["families"]["cifar10_quick"]
    for dtype in ("f32", "bf16"):
        entry = fam[dtype]
        assert entry["c1"] > 0
        assert entry["max_batch"]["dp"] >= entry["max_batch"]["solo"]
    assert (fam["bf16"]["max_batch"]["solo"]
            >= fam["f32"]["max_batch"]["solo"])
    # the table reloads clean (bank -> verify round-trip)
    findings, _ = run_batch_fit(
        families=["cifar10_quick"],
        banked_path=str(tmp_path / "fit.json"))
    assert findings == []


# -- VMEM audit -------------------------------------------------------------


def test_vmem_audit_real_kernels_fit():
    problems, contract = run_vmem_audit()
    assert problems == []
    assert len(contract["points"]) >= 3
    for p in contract["points"]:
        assert p["fits"] and p["bytes"] <= V5E_VMEM_BYTES


def test_vmem_audit_flags_over_budget_kernel(monkeypatch):
    import sparknet_tpu.ops.pallas_kernels as pk

    points = pk.vmem_audit_points() + [{
        "kernel": "flash",
        "note": "fixture: S=1M full-fiber K/V",
        "bytes": pk.flash_vmem_bytes(1 << 20, 64),
    }]
    monkeypatch.setattr(pk, "vmem_audit_points", lambda: points)
    problems, contract = run_vmem_audit()
    assert [p["rule"] for p in problems] == ["mem-vmem-exceeded"]
    assert "fixture" in problems[0]["message"]
    assert contract["points"][-1]["fits"] is False


def test_vmem_bounds_read_the_tiling_constants():
    from sparknet_tpu.ops.pallas_kernels import (
        _BK, _TILE, flash_vmem_bytes, lrn_vmem_bytes)

    # linear in the channel fiber / sequence length by construction
    assert lrn_vmem_bytes(256) == 2 * lrn_vmem_bytes(128)
    assert lrn_vmem_bytes(96) == 7 * 96 * _TILE * 4
    assert flash_vmem_bytes(4096, 64) > flash_vmem_bytes(2048, 64)
    # sequence length rounds up to the K-step tile
    assert flash_vmem_bytes(_BK + 1, 16) == flash_vmem_bytes(2 * _BK, 16)


# -- manifest machinery -----------------------------------------------------


def test_manifest_bank_diff_and_allow(tmp_path):
    """moe (sub-second to trace) exercises the full manifest loop:
    missing -> banked -> clean -> drift -> allow-suppressed."""
    banked = str(tmp_path / "contracts")
    findings, _ = run_memcheck(["moe"], banked_dir=banked)
    assert [f.rule for f in findings] == ["mem-manifest-missing"]

    findings, _ = run_memcheck(["moe"], banked_dir=banked, update=True)
    assert findings == []
    mpath = tmp_path / "contracts" / "moe.json"
    assert mpath.exists()

    findings, _ = run_memcheck(["moe"], banked_dir=banked)
    assert findings == []  # steady state: re-run diffs clean

    banked_manifest = json.loads(mpath.read_text())
    banked_manifest["contract"]["analytic"]["peak_bytes"] = 99
    mpath.write_text(json.dumps(banked_manifest))
    findings, _ = run_memcheck(["moe"], banked_dir=banked)
    assert [f.rule for f in findings] == ["mem-manifest-drift"]
    assert not findings[0].suppressed
    assert "peak_bytes" in findings[0].message

    banked_manifest["allow"] = {
        "mem-manifest-drift": "fixture: tampered peak"}
    mpath.write_text(json.dumps(banked_manifest))
    findings, _ = run_memcheck(["moe"], banked_dir=banked)
    assert [f.rule for f in findings] == ["mem-manifest-drift"]
    assert findings[0].suppressed


def test_sources_fingerprint_covers_the_contract_surface():
    fp = sources_fingerprint()
    for rel in ("sparknet_tpu/models/zoo.py",
                "sparknet_tpu/parallel/sharding.py",
                "sparknet_tpu/ops/pallas_kernels.py",
                "sparknet_tpu/solvers/solver.py",
                "sparknet_tpu/analysis/mem_model.py"):
        assert rel in fp
    assert all(len(h) == 64 for h in fp.values())


def test_rule_catalog():
    assert set(MEM_RULES) == {
        "mem-residency-mismatch", "mem-estimator-divergence",
        "mem-hbm-exceeded", "mem-vmem-exceeded", "mem-fit-infeasible",
        "mem-manifest-missing", "mem-manifest-drift",
    }


# -- CLI: shared schema with lint/graph -------------------------------------


def test_cli_mem_json_schema(tmp_path, capsys, monkeypatch):
    from sparknet_tpu.analysis import memcheck as mc
    from sparknet_tpu.analysis.__main__ import main as cli_main

    monkeypatch.setattr(mc, "MANIFEST_DIR", str(tmp_path))
    rc = cli_main(["mem", "--mode", "moe", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1  # manifest missing in the tmp dir
    assert set(out) == {"findings", "unsuppressed", "suppressed"}
    assert out["findings"][0]["rule"] == "mem-manifest-missing"
    for key in ("rule", "path", "line", "message", "suppressed"):
        assert key in out["findings"][0]

    rc = cli_main(["mem", "--mode", "moe", "--update"])
    assert rc == 0
    capsys.readouterr()
    rc = cli_main(["mem", "--mode", "moe", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["unsuppressed"] == 0


def test_cli_mem_unknown_mode_is_usage_error(capsys):
    from sparknet_tpu.analysis.__main__ import main as cli_main

    assert cli_main(["mem", "--mode", "no-such-mode"]) == 2


def test_cli_mem_list_rules(capsys):
    from sparknet_tpu.analysis.__main__ import main as cli_main

    assert cli_main(["mem", "--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "mem-estimator-divergence" in out
    assert "mem-vmem-exceeded" in out


def test_cli_parse_bytes():
    from sparknet_tpu.analysis.__main__ import _parse_bytes

    assert _parse_bytes("16GiB") == 16 * 2**30
    assert _parse_bytes("8g") == 8 * 2**30
    assert _parse_bytes("123456") == 123456
    with pytest.raises(ValueError):
        _parse_bytes("lots")
