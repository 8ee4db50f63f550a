"""bytecheck: per-defect fixtures + the banked byte-contract smoke gate.

Mirrors test_memcheck.py for the fifth analysis engine: the class-model
floor is pinned against hand computation, the floor<=census invariant
fires on a doctored program, the manifest loop round-trips
bank/drift/allow, the headline census reconciles with a supplied
measured step-bytes figure inside the stated window (and a doctored
measurement trips the divergence rule), the remat search's saved-bytes
monotonicity and winner selection are pinned on a real family plus
defect fixtures, and the off-by-default path is the IDENTITY — the
mechanism by which every banked graph/mem manifest stays byte-unchanged
with ``Config.remat`` off.
"""

import json
import os
import types

import jax.numpy as jnp
import pytest

from sparknet_tpu.analysis.byte_model import (
    HEADLINE_RATIO_WINDOW,
    REMAT_POLICIES,
    REMAT_RECOMPUTE_ORDER,
    gbytes,
    gross_traffic,
    monotonicity_violations,
    reconcile,
    selected_policy,
    step_traffic,
    xla_cost_step_bytes,
)
from sparknet_tpu.analysis.bytecheck import (
    BYTE_RULES,
    census_mode,
    run_bytecheck,
    run_headline,
    run_remat_search,
    sources_fingerprint,
)
from sparknet_tpu.analysis.mem_model import MemEqn, MemProgram

pytestmark = pytest.mark.smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the class-model floor vs hand computation ------------------------------


def test_step_traffic_hand_computation():
    """S=100 params, 10 slots, 20 saved activations, 5 feed: forward
    read 100 + backward read 100 + update write 100; grads written and
    read = 200; slots r+w = 20; activations w+r = 40; feed 5."""
    t = step_traffic(param_bytes=100, slot_bytes=10,
                     saved_activation_bytes=20, feed_bytes=5)
    assert t["params_read_bytes"] == 200
    assert t["params_write_bytes"] == 100
    assert t["grad_bytes"] == 200
    assert t["slot_bytes"] == 20
    assert t["saved_activation_bytes"] == 40
    assert t["total_bytes"] == 200 + 100 + 200 + 20 + 40 + 5


def test_step_traffic_recompute_trades_param_reads_for_activations():
    """One recompute pass adds exactly one forward's param reads — the
    byte-side price of rematerialization the search weighs against the
    activation savings."""
    none = step_traffic(param_bytes=100, saved_activation_bytes=200)
    full = step_traffic(param_bytes=100, saved_activation_bytes=5,
                        recompute_passes=1)
    assert full["params_read_bytes"] - none["params_read_bytes"] == 100
    # the trade pays iff 2*saved_delta > extra param reads (here 390 > 100)
    assert full["total_bytes"] < none["total_bytes"]
    # ...and does NOT pay when the activation footprint is small
    small = step_traffic(param_bytes=100, saved_activation_bytes=5,
                         recompute_passes=1)
    base = step_traffic(param_bytes=100, saved_activation_bytes=50)
    assert small["total_bytes"] > base["total_bytes"]


def test_step_traffic_forward_only():
    t = step_traffic(param_bytes=100, slot_bytes=10, state_bytes=7,
                     saved_activation_bytes=3, train=False)
    assert t["params_read_bytes"] == 100
    assert t["params_write_bytes"] == 0
    assert t["grad_bytes"] == 0 and t["slot_bytes"] == 0
    assert t["state_bytes"] == 14 and t["saved_activation_bytes"] == 6


def test_gross_traffic_toy():
    prog = MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("t1",)),
              MemEqn(reads=("t1", "b"), writes=("out",))],
        sizes={"a": 100, "b": 40, "t1": 30, "out": 20},
        inputs=["a", "b"], outputs=["out"])
    # eqn0: 100+30; eqn1: 30+40+20
    assert gross_traffic(prog) == 220


# -- the single source of "step bytes" (bench.py / cli.py reconcile) --------


def test_xla_cost_step_bytes_shapes():
    assert xla_cost_step_bytes({"bytes accessed": 3.0}) == 3.0
    assert xla_cost_step_bytes([{"bytes accessed": 4.0}]) == 4.0  # old jax
    assert xla_cost_step_bytes([]) == 0.0
    assert xla_cost_step_bytes(None) == 0.0
    assert xla_cost_step_bytes({"flops": 1.0}) == 0.0


def test_gbytes_is_the_one_rounding():
    assert gbytes(12_334_999_999) == 12.33
    assert gbytes(0) == 0.0


def test_bench_and_cli_route_through_the_byte_model():
    """The reconciliation's two sides must share one extraction: both
    bench.py (banks step_gbytes) and the CLI's --hlo branch (prints
    hbm_bytes_per_step) read XLA's cost dict through
    ``byte_model.xla_cost_step_bytes`` — no inline re-implementation
    allowed to drift."""
    with open(os.path.join(ROOT, "bench.py"), encoding="utf-8") as f:
        bench_src = f.read()
    with open(os.path.join(ROOT, "sparknet_tpu", "cli.py"),
              encoding="utf-8") as f:
        cli_src = f.read()
    assert "xla_cost_step_bytes" in bench_src
    assert "xla_cost_step_bytes" in cli_src
    for src in (bench_src, cli_src):
        assert 'float(cost.get("bytes accessed"' not in src


# -- reconciliation + table arithmetic --------------------------------------


def test_reconcile_window():
    good = reconcile(10e9, 12e9)
    assert good["within"] and good["ratio"] == 1.2
    assert good["census_gbytes"] == 12.0
    lo, hi = HEADLINE_RATIO_WINDOW
    assert not reconcile(10e9, (hi + 1) * 10e9)["within"]
    assert not reconcile(10e9, (lo / 2) * 10e9)["within"]
    assert not reconcile(0, 12e9)["within"]  # no measurement != pass


def test_selected_policy_defaults():
    table = {"selected": {"alexnet": {"bf16": {"policy": "dots"}}}}
    assert selected_policy(table, "alexnet", "bf16") == "dots"
    assert selected_policy(table, "vgg16", "bf16") == "full"
    assert selected_policy({}, "alexnet", "bf16") == "full"
    assert selected_policy(None, "alexnet", "bf16") == "full"
    bad = {"selected": {"alexnet": {"bf16": {"policy": "no_such"}}}}
    assert selected_policy(bad, "alexnet", "bf16") == "full"


def test_monotonicity_violations():
    ok = {"none": 100, "dots": 40, "blocks": 30, "full": 10}
    assert monotonicity_violations(ok) == []
    bad = {"none": 100, "dots": 40, "blocks": 30, "full": 60}
    assert monotonicity_violations(bad) == [("dots", "full"),
                                            ("blocks", "full")]
    # absent policies are skipped, not violated
    assert monotonicity_violations({"none": 1}) == []
    # every ordered pair is over policies the search actually runs
    for a, b in REMAT_RECOMPUTE_ORDER:
        assert a in REMAT_POLICIES and b in REMAT_POLICIES


# -- off-by-default is the identity path ------------------------------------


def test_remat_off_is_the_identity_path():
    """The bit-identity mechanism: with both knobs off, apply_remat
    returns the SAME function object — the step builders trace exactly
    the pre-remat program, which is why every banked graph/mem
    manifest's stablehlo_sha256 stays byte-unchanged."""
    from sparknet_tpu.common import get_config
    from sparknet_tpu.solvers.solver import apply_remat, remat_policy

    assert get_config().remat == ""  # SPARKNET_REMAT unset => off

    def loss_fn(x):
        return x

    assert apply_remat(loss_fn, "") is loss_fn
    assert apply_remat(loss_fn, "none") is loss_fn
    assert apply_remat(loss_fn, "full") is not loss_fn
    with pytest.raises(ValueError):
        apply_remat(loss_fn, "everything")

    from sparknet_tpu import models
    cfg = models.cifar10_quick_solver()
    assert remat_policy(cfg) == ""  # both knobs off


def test_config_remat_validation():
    from sparknet_tpu.common import set_config

    try:
        assert set_config(remat="none").remat == ""
        assert set_config(remat="dots").remat == "dots"
        with pytest.raises(ValueError):
            set_config(remat="most")
    finally:
        set_config(remat="")  # never leak a policy into later tests


# -- per-defect fixture: floor exceeds census -------------------------------


def _fake_target(name="solo", param_elems=1000):
    """A minimal trainer-shaped target: big params, tiny feed."""
    return types.SimpleNamespace(
        name=name,
        args=(jnp.zeros((param_elems,), jnp.float32),
              jnp.zeros((8,), jnp.float32), 0,
              jnp.zeros((4,), jnp.float32)),
        carry_argnums=(0, 1),
        param_bytes=param_elems * 4,
        state_bytes=0,
        meta={},
    )


def test_census_flags_floor_exceeding_census():
    """A program whose eqn census moves almost nothing while the args
    say 4 KB of params must trip the invariant — the two estimators
    are describing different programs."""
    prog = MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("out",))],
        sizes={"a": 10, "out": 10}, inputs=["a"], outputs=["out"])
    problems, contract = census_mode(_fake_target(), prog)
    assert [p["rule"] for p in problems] == ["byte-floor-exceeds-census"]
    assert contract["floor_vs_census_checked"] is True
    assert contract["floor"]["total_bytes"] > contract["gross_census_bytes"]


def test_census_skips_the_invariant_for_control_flow_bodies():
    """A scan/while body's internals are not in the census (counted
    once as liveness ``extra``), so the floor comparison would be
    one-sided — recorded as skipped, never a false positive."""
    prog = MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("out",), extra=512)],
        sizes={"a": 10, "out": 10}, inputs=["a"], outputs=["out"])
    problems, contract = census_mode(_fake_target(), prog)
    assert problems == []
    assert contract["floor_vs_census_checked"] is False


# -- the smoke gate on the cheap real modes ---------------------------------


def test_bytecheck_smoke_gate_solo_and_dp():
    """THE ratchet, traffic edition: the two cheap modes must match the
    banked manifests with zero unsuppressed findings, and the floor
    must sit at or below the gross census wherever the comparison is
    two-sided."""
    findings, manifests = run_bytecheck(["solo", "dp"])
    bad = [f for f in findings if not f.suppressed]
    assert not bad, "unsuppressed bytecheck findings:\n" + "\n".join(
        f"{f.path}: [{f.rule}] {f.message}" for f in bad)
    for mode in ("solo", "dp"):
        c = manifests[mode]["contract"]
        if c["floor_vs_census_checked"]:
            assert c["floor"]["total_bytes"] <= c["gross_census_bytes"]
        assert c["ingredients"]["param_bytes"] > 0
        assert c["ingredients"]["train"] is True
    # dp pays the grad all-reduce solo never does
    assert manifests["dp"]["contract"]["ingredients"]["collective_bytes"] > 0
    assert manifests["solo"]["contract"]["ingredients"][
        "collective_bytes"] == 0


def test_remat_twin_censuses_the_banked_policy():
    """solo_remat's census must carry the banked winner's policy and a
    recompute pass — the twin exists to prove the modeled drop lowers."""
    findings, manifests = run_bytecheck(["solo_remat"])
    assert not [f for f in findings if not f.suppressed]
    ing = manifests["solo_remat"]["contract"]["ingredients"]
    assert ing["remat_policy"] in REMAT_POLICIES[1:]  # never "none"
    assert ing["recompute_passes"] == 1


# -- manifest machinery -----------------------------------------------------


def test_manifest_bank_diff_and_allow(tmp_path):
    """moe (sub-second to trace) exercises the full manifest loop:
    missing -> banked -> clean -> drift -> allow-suppressed."""
    banked = str(tmp_path / "contracts")
    findings, _ = run_bytecheck(["moe"], banked_dir=banked)
    assert [f.rule for f in findings] == ["byte-manifest-missing"]

    findings, _ = run_bytecheck(["moe"], banked_dir=banked, update=True)
    assert findings == []
    mpath = tmp_path / "contracts" / "moe.json"
    assert mpath.exists()

    findings, _ = run_bytecheck(["moe"], banked_dir=banked)
    assert findings == []  # steady state: re-run diffs clean

    banked_manifest = json.loads(mpath.read_text())
    banked_manifest["contract"]["gross_census_bytes"] = 99
    mpath.write_text(json.dumps(banked_manifest))
    findings, _ = run_bytecheck(["moe"], banked_dir=banked)
    assert [f.rule for f in findings] == ["byte-manifest-drift"]
    assert not findings[0].suppressed
    assert "gross_census_bytes" in findings[0].message

    banked_manifest["allow"] = {
        "byte-manifest-drift": "fixture: tampered census"}
    mpath.write_text(json.dumps(banked_manifest))
    findings, _ = run_bytecheck(["moe"], banked_dir=banked)
    assert [f.rule for f in findings] == ["byte-manifest-drift"]
    assert findings[0].suppressed


def test_sources_fingerprint_covers_the_contract_surface():
    fp = sources_fingerprint()
    for rel in ("sparknet_tpu/models/zoo.py",
                "sparknet_tpu/compiler/graph.py",
                "sparknet_tpu/solvers/solver.py",
                "sparknet_tpu/parallel/modes.py",
                "sparknet_tpu/serve/engine.py",
                "sparknet_tpu/analysis/byte_model.py"):
        assert rel in fp
    assert all(len(h) == 64 for h in fp.values())


def test_lint_rule_surface_matches_the_engine():
    """The byte-manifest-fresh lint rule duplicates the source surface
    (rules.py stays importable without bytecheck); the two spellings
    must never drift."""
    from sparknet_tpu.analysis.bytecheck import BYTE_SOURCE_PATTERNS
    from sparknet_tpu.analysis.rules import (
        _BYTE_SOURCE_DIRS,
        _BYTE_SOURCE_FILES,
    )

    assert set(BYTE_SOURCE_PATTERNS) == \
        set(_BYTE_SOURCE_DIRS) | set(_BYTE_SOURCE_FILES)


def test_rule_catalog():
    assert set(BYTE_RULES) == {
        "byte-floor-exceeds-census", "byte-headline-divergence",
        "byte-remat-no-gain", "byte-remat-nonmonotonic",
        "byte-manifest-missing", "byte-manifest-drift",
    }


# -- the headline reconciliation gate ---------------------------------------


def test_headline_reconciles_with_a_supplied_measurement(tmp_path):
    """The alexnet b256 bf16 census banks and round-trips, and a
    measured step-bytes figure inside the stated window reconciles —
    the 'bytes-bound' sentence as a machine check, fed by whoever
    holds a chip measurement."""
    path = str(tmp_path / "headline.json")
    findings, manifest = run_headline(banked_path=path, update=True)
    assert findings == []
    gross = manifest["contract"]["gross_census_bytes"]
    findings, manifest = run_headline(
        banked_path=path, measured_step_bytes=gross / 2.0)
    assert findings == []
    rec = manifest["reconciliation"]
    assert rec["within"] is True
    lo, hi = HEADLINE_RATIO_WINDOW
    assert lo <= rec["ratio"] <= hi
    assert manifest["tolerance"]["ratio_window"] == [lo, hi]


def test_headline_divergence_fixture(tmp_path, monkeypatch):
    """A doctored measurement far outside the window must trip
    byte-headline-divergence (census side stubbed: the defect under
    test is the gate, not the trace)."""
    import sparknet_tpu.analysis.bytecheck as bc

    prog = MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("out",))],
        sizes={"a": 500, "out": 500}, inputs=["a"], outputs=["out"])
    monkeypatch.setattr(bc, "_abstract_census", lambda *a, **k: {
        "prog": prog, "prog_undonated": prog, "params_bytes": 400,
        "state_bytes": 0, "slots_bytes": 400, "feed_bytes": 100,
        "n_slots": 1})
    findings, manifest = run_headline(
        banked_path=str(tmp_path / "headline.json"),
        measured_step_bytes=1000.0e9)
    assert "byte-headline-divergence" in [f.rule for f in findings]
    assert manifest["reconciliation"]["within"] is False


def test_headline_without_measurement_is_a_stated_vacuous_pass(
        tmp_path, monkeypatch):
    import sparknet_tpu.analysis.bytecheck as bc

    prog = MemProgram(
        eqns=[MemEqn(reads=("a",), writes=("out",))],
        sizes={"a": 500, "out": 500}, inputs=["a"], outputs=["out"])
    monkeypatch.setattr(bc, "_abstract_census", lambda *a, **k: {
        "prog": prog, "prog_undonated": prog, "params_bytes": 400,
        "state_bytes": 0, "slots_bytes": 400, "feed_bytes": 100,
        "n_slots": 1})
    findings, manifest = run_headline(
        banked_path=str(tmp_path / "headline.json"), update=True)
    assert findings == []
    assert "vacuous" in manifest["reconciliation"]["note"]


# -- the remat schedule search ----------------------------------------------


def test_remat_search_real_family_is_monotone(tmp_path, monkeypatch):
    """cifar10_quick through the real abstract-trace path: heavier
    recompute never saves more activation bytes, the winner's drop is
    non-negative, and the banked table reloads clean."""
    import sparknet_tpu.analysis.bytecheck as bc

    monkeypatch.setattr(bc, "SEARCH_DTYPES", ("f32",))
    path = str(tmp_path / "remat_policy.json")
    findings, table = run_remat_search(
        families=["cifar10_quick"], banked_path=path, update=True)
    assert findings == []
    scores = table["families"]["cifar10_quick"]["f32"]
    assert set(scores) == set(REMAT_POLICIES)
    for a, b in REMAT_RECOMPUTE_ORDER:
        assert scores[b]["saved_activation_bytes"] \
            <= scores[a]["saved_activation_bytes"]
    for policy in REMAT_POLICIES:
        # donating params+slots never raises the liveness peak
        assert scores[policy]["peak_bytes_donated"] \
            <= scores[policy]["peak_bytes_undonated"]
    sel = table["selected"]["cifar10_quick"]["f32"]
    assert sel["policy"] in REMAT_POLICIES
    assert sel["donation"] == "donate_params_slots"
    assert sel["drop_frac_vs_none"] >= 0
    assert sel["step_bytes_solo"] == \
        scores[sel["policy"]]["step_bytes"]["solo"]
    # bank -> verify round-trip diffs clean
    findings, _ = run_remat_search(
        families=["cifar10_quick"], banked_path=path)
    assert findings == []


def test_remat_search_defect_fixtures(tmp_path, monkeypatch):
    """Doctored scores: a nonmonotonic save table and a no-gain winner
    for the headline family must each raise their rule."""
    import sparknet_tpu.analysis.bytecheck as bc

    monkeypatch.setattr(bc, "_abstract_census", lambda *a, **k: None)
    flat = {p: {"saved_activation_bytes":
                {"none": 10, "dots": 40, "blocks": 5, "full": 5}[p],
                "recompute_passes": 0 if p == "none" else 1,
                "step_bytes": {"solo": 1000, "dp": 1100},
                "step_gbytes": {"solo": 0.0, "dp": 0.0},
                "peak_bytes_donated": 1, "peak_bytes_undonated": 2}
            for p in REMAT_POLICIES}
    monkeypatch.setattr(bc, "_family_step_bytes",
                        lambda cen, policy: dict(flat[policy]))
    monkeypatch.setattr(bc, "SEARCH_DTYPES", ("bf16",))
    findings, table = run_remat_search(
        families=["alexnet"],
        banked_path=str(tmp_path / "remat_policy.json"))
    rules = sorted(f.rule for f in findings)
    # dots saves MORE than none => nonmonotonic; every policy byte-tied
    # => winner "none", drop 0 < 25% => no-gain
    assert "byte-remat-nonmonotonic" in rules
    assert "byte-remat-no-gain" in rules
    assert table["selected"]["alexnet"]["bf16"]["policy"] == "none"


def test_banked_remat_policy_reader(tmp_path, monkeypatch):
    """parallel/modes reads the banked table through selected_policy;
    a missing table falls back to 'full' (deterministic before the
    first bank)."""
    import sparknet_tpu.parallel.modes as modes

    assert modes._banked_remat_policy("no_such_family", "f32") in \
        REMAT_POLICIES  # table present or not, always a valid policy


# -- CLI: shared schema with lint/graph/mem/conc ----------------------------


def test_cli_bytes_json_schema(tmp_path, capsys, monkeypatch):
    from sparknet_tpu.analysis import bytecheck as bc
    from sparknet_tpu.analysis.__main__ import main as cli_main

    monkeypatch.setattr(bc, "MANIFEST_DIR", str(tmp_path))
    rc = cli_main(["bytes", "--mode", "moe", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1  # manifest missing in the tmp dir
    assert set(out) == {"findings", "unsuppressed", "suppressed"}
    assert out["findings"][0]["rule"] == "byte-manifest-missing"
    for key in ("rule", "path", "line", "message", "suppressed"):
        assert key in out["findings"][0]

    rc = cli_main(["bytes", "--mode", "moe", "--update"])
    assert rc == 0
    capsys.readouterr()
    rc = cli_main(["bytes", "--mode", "moe", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["unsuppressed"] == 0


def test_cli_bytes_unknown_mode_is_usage_error(capsys):
    from sparknet_tpu.analysis.__main__ import main as cli_main

    assert cli_main(["bytes", "--mode", "no-such-mode"]) == 2


def test_cli_bytes_list_rules(capsys):
    from sparknet_tpu.analysis.__main__ import main as cli_main

    assert cli_main(["bytes", "--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "byte-headline-divergence" in out
    assert "byte-remat-nonmonotonic" in out
