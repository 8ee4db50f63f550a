"""obsnet (sparknet_tpu/obs): schema, Recorder, sentinel, report, hooks.

Four contracts pinned here:

1. **Disabled path is bit-identical** — with SPARKNET_OBS off, the
   instrumented ``Solver.step`` / ``ParallelTrainer.train_round`` lower
   to the same StableHLO and dispatch the same number of device calls
   as an uninstrumented run (the acceptance criterion of the obs PR).
2. **Per-round records** — dp and tau rounds on the virtual 8-device
   CPU mesh journal fenced walls, img/s, loss EMA, and the
   comm_model-predicted collective budget.
3. **Recompile sentinel** — backend compilations are counted, and a
   shape-polymorphic step recompiling after warmup is flagged live.
4. **Report honesty** — golden-file rendering, refusal of unstamped
   walls, refusal of any throughput above its stated roofline bound.

Schema/validator/report tests are smoke-tier (stdlib-fast, CI wiring
per the obs PR); trainer-round tests ride the default tier; the full
dp+tau dryrun CLI is slow-tier.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.common import bank_guard
from sparknet_tpu.layers_dsl import (
    InnerProductLayer,
    NetParam,
    RDDLayer,
    SoftmaxWithLoss,
)
from sparknet_tpu.obs import schema
from sparknet_tpu.obs.recorder import Recorder, set_recorder
from sparknet_tpu.obs.report import render, render_path
from sparknet_tpu.obs.sentinel import get_sentinel
from sparknet_tpu.parallel import ParallelTrainer
from sparknet_tpu.solvers import Solver, SolverConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def rec(tmp_path):
    """An armed Recorder as the process singleton, detached afterwards."""
    path = str(tmp_path / "journal.jsonl")
    recorder = set_recorder(Recorder(path, run_id="test"))
    yield recorder
    set_recorder(None)


def events_of(recorder, kind=None):
    evs = schema.load_journal(recorder.path)
    return [e for e in evs if kind is None or e.get("event") == kind]


# -- nets -------------------------------------------------------------------


def tiny_net(batch):
    return NetParam(
        "obs_net",
        RDDLayer("data", shape=[batch, 4]),
        RDDLayer("label", shape=[batch]),
        InnerProductLayer("ip", ["data"], num_output=10),
        SoftmaxWithLoss("loss", ["ip", "label"]),
    )


def tiny_feeds(batch, tau=0, seed=0):
    rs = np.random.RandomState(seed)
    data = rs.randn(batch, 4).astype(np.float32)
    label = rs.randint(0, 10, batch).astype(np.int32)
    if tau:
        data = np.stack([data] * tau)
        label = np.stack([label] * tau)
    return {"data": data, "label": label}


def tiny_solver(batch=8):
    return Solver(SolverConfig(base_lr=0.1), tiny_net(batch))


# -- schema -----------------------------------------------------------------


@pytest.mark.smoke
def test_make_event_stamps_and_validates():
    line = schema.make_event("run_start", run_id="r1", pid=3)
    assert line["event"] == "run_start" and line["pid"] == 3
    assert schema.validate_line(line) == []


@pytest.mark.smoke
def test_make_event_rejects_schema_violations():
    with pytest.raises(ValueError, match="missing required"):
        schema.make_event("run_start")  # no run_id
    with pytest.raises(ValueError, match="unknown event"):
        schema.make_event("no_such_event", x=1)
    with pytest.raises(ValueError, match="unknown field"):
        schema.make_event("run_start", run_id="r1", bogus=2)
    with pytest.raises(ValueError, match="schema wants"):
        schema.make_event("run_start", run_id="r1", pid="one")


@pytest.mark.smoke
def test_existing_evidence_journals_validate():
    """Every banked journal passes the schema."""
    import glob

    paths = sorted(glob.glob(
        os.path.join(ROOT, "docs", "evidence_r*", "*.jsonl")))
    assert paths, "no banked journals found"
    for path in paths:
        n, errors = schema.validate_journal(path)
        assert n > 0
        assert not errors, "\n".join(errors)


@pytest.mark.smoke
def test_validator_cli(tmp_path, capsys):
    from sparknet_tpu.obs.__main__ import validate_main

    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(schema.make_event("run_start",
                                                 run_id="r1")) + "\n")
    assert validate_main([str(good)]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "run_end"}\n')
    assert validate_main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out


# -- report (golden + refusals) ---------------------------------------------

GOLDEN_EVENTS = [
    {"event": "run_start", "run_id": "golden",
     "utc": "2026-08-04 00:00:00Z", "pid": 1},
    {"event": "round", "run_id": "golden", "utc": "2026-08-04 00:00:01Z",
     "mode": "dp", "tau": 1, "devices": 8, "iters": 1, "batch": 16,
     "wall_s": 0.5, "images_per_sec": 32.0, "loss": 2.3026,
     "loss_ema": 2.3026, "fenced": True, "compiles": 12,
     "comm": {"param_bytes": 1000, "state_bytes": 0,
              "predicted": {"all-reduce": [950, 1665]},
              "note": "tau=1 sync SGD"}},
    {"event": "round", "run_id": "golden", "utc": "2026-08-04 00:00:02Z",
     "mode": "tau", "tau": 3, "devices": 8, "iters": 3, "batch": 16,
     "wall_s": 0.25, "images_per_sec": 192.0, "loss": 2.2,
     "loss_ema": 2.2923, "fenced": False, "compiles": 0},
    {"event": "span", "run_id": "golden", "utc": "2026-08-04 00:00:03Z",
     "name": "solver.solve", "wall_s": 1.25, "fenced": True,
     "fence_value": 0.125},
    {"event": "span", "run_id": "golden", "utc": "2026-08-04 00:00:04Z",
     "name": "stage-db", "wall_s": 0.01, "fenced": False, "host": True},
    {"event": "span", "run_id": "golden", "utc": "2026-08-04 00:00:05Z",
     "name": "leaky", "wall_s": 0.5, "fenced": False},
    {"event": "recompile", "run_id": "golden",
     "utc": "2026-08-04 00:00:06Z", "count": 2, "total": 14,
     "where": "dp", "expected": False},
    {"event": "bench", "run_id": "golden", "utc": "2026-08-04 00:00:07Z",
     "metric": "alexnet_train_images_per_sec_per_chip", "measured": True,
     "fenced": True,
     "record": {"metric": "alexnet_train_images_per_sec_per_chip",
                "value": 12290.0, "unit": "img/s",
                "roofline_img_s_upper_bound": 13213.0}},
    {"event": "bench", "run_id": "golden", "utc": "2026-08-04 00:00:08Z",
     "metric": "bogus_img_s", "measured": True, "fenced": True,
     "record": {"metric": "bogus_img_s", "value": 99999.0,
                "unit": "img/s", "roofline_img_s_upper_bound": 13213.0}},
    {"event": "bank", "run_id": "golden", "utc": "2026-08-04 00:00:09Z",
     "path": "docs/feed_bench_last.json", "measured": True,
     "metric": "alexnet_train_images_per_sec_per_chip", "value": 12290.0},
    {"event": "bank", "run_id": "golden", "utc": "2026-08-04 00:00:10Z",
     "path": "/tmp/serve_bench_rehearsal.json", "measured": False,
     "rehearsal": True},
    {"event": "request", "run_id": "golden",
     "utc": "2026-08-04 00:00:10Z", "model": "live", "bucket": 8,
     "queue_wait_ms": 1.5, "batch_assembly_ms": 0.2, "device_ms": 4.0,
     "total_ms": 5.7, "batch_n": 5, "padded": True,
     "lineage": {"span": "req:live:1", "parent": "gen:live:v1"}},
    {"event": "request", "run_id": "golden",
     "utc": "2026-08-04 00:00:10Z", "model": "live", "bucket": 8,
     "queue_wait_ms": 1.9, "batch_assembly_ms": 0.2, "device_ms": 4.0,
     "total_ms": 6.1, "deadline_flush": True,
     "lineage": {"span": "req:live:2", "parent": "gen:live:v1"}},
    {"event": "metrics", "run_id": "golden",
     "utc": "2026-08-04 00:00:11Z", "seq": 1,
     "counters": {"serve/requests": 2},
     "gauges": {"train/loss_ema/dp": 2.3026},
     "hists": {"serve/total_ms/live/b8": {
         "count": 2, "sum": 11.8, "min": 5.7, "max": 6.1,
         "buckets": {"30": 1, "31": 1}}}},
    {"event": "run_end", "run_id": "golden", "utc": "2026-08-04 00:00:11Z",
     "rounds": 2, "spans": 3, "compiles": 14},
    # an SLO verdict (no run_id: it judges a whole journal) renders in
    # its own section
    {"event": "slo", "utc": "2026-08-04 00:00:12Z", "job": "loop_dryrun",
     "ok": True, "gates": 5, "applicable": 2,
     "journal": "docs/evidence_r7/loop_dryrun.jsonl",
     "manifest": "docs/slo_manifest.json"},
]


@pytest.mark.smoke
def test_golden_events_are_schema_valid():
    for ev in GOLDEN_EVENTS:
        assert schema.validate_line(ev) == [], ev


@pytest.mark.smoke
def test_report_golden_file(tmp_path):
    """The rendered report is pinned byte-for-byte: formatting drift is
    a deliberate decision (regenerate tests/data/obs_report_golden.md),
    not an accident."""
    journal = tmp_path / "golden.jsonl"
    journal.write_text(
        "".join(json.dumps(ev) + "\n" for ev in GOLDEN_EVENTS))
    text = render_path(str(journal))
    golden = os.path.join(ROOT, "tests", "data", "obs_report_golden.md")
    with open(golden, encoding="utf-8") as f:
        assert text == f.read()


@pytest.mark.smoke
def test_report_refuses_unstamped_walls():
    text = render(GOLDEN_EVENTS, source="t")
    # the unfenced tau round's throughput is withheld
    assert "REFUSED (unfenced)" in text
    assert "192.0" not in text
    # the unfenced, non-host span's wall is withheld
    assert "span closed without a fence stamp" in text


@pytest.mark.smoke
def test_report_never_prints_throughput_above_roofline():
    text = render(GOLDEN_EVENTS, source="t")
    assert "exceeds its stated roofline bound" in text
    assert "99999" not in text  # the bogus value never prints
    # the honest bench record still prints, with its bound
    assert "12290" in text


# -- Recorder ---------------------------------------------------------------


def test_disabled_recorder_is_falsy_and_writes_nothing(tmp_path):
    recorder = Recorder(None)
    assert not recorder
    recorder.round(mode="solo", tau=1, devices=1, iters=1, batch=4,
                   wall_s=0.1, loss=1.0, fenced=True)
    with recorder.span("x") as sp:
        sp.fence(jnp.float32(1.0))  # no-op when disabled
    recorder.close()


def test_span_fence_and_unfenced_marking(rec):
    with rec.span("fenced") as sp:
        sp.fence(jnp.float32(2.5))
    with rec.span("unfenced"):
        pass
    with rec.span("host-side", host=True):
        pass
    spans = {e["name"]: e for e in events_of(rec, "span")}
    assert spans["fenced"]["fenced"] is True
    assert spans["fenced"]["fence_value"] == 2.5
    assert spans["unfenced"]["fenced"] is False
    assert spans["host-side"]["host"] is True


def test_bank_guard_writes_are_journaled(rec, tmp_path):
    """bank_guard and obs share one code path for measured stamping:
    every banked write lands in the journal with the same flag."""
    measured_path = str(tmp_path / "x_last.json")
    bank_guard(measured_path,
               {"metric": "m", "value": 1.5, "measured": True},
               measured=True)
    bank_guard(str(tmp_path / "y_last.json"), {"metric": "m2"},
               measured=False)  # diverts to /tmp + rehearsal stamp
    banks = events_of(rec, "bank")
    assert len(banks) == 2
    assert banks[0]["path"] == measured_path
    assert banks[0]["measured"] is True and banks[0]["value"] == 1.5
    assert banks[1]["measured"] is False
    assert banks[1]["rehearsal"] is True
    assert "y_last_rehearsal" in banks[1]["path"]
    # a detached recorder stops observing
    set_recorder(None)
    bank_guard(str(tmp_path / "z_last.json"), {"metric": "m3"},
               measured=False)
    assert len(events_of(rec, "bank")) == 2


# -- sentinel ---------------------------------------------------------------


def test_sentinel_counts_backend_compiles():
    sentinel = get_sentinel().install()
    assert sentinel.available
    f = jax.jit(lambda x: x * 2 + 1)
    c0 = sentinel.count
    f(jnp.ones((3,)))
    assert sentinel.count > c0  # cold call compiled
    c1 = sentinel.count
    f(jnp.ones((3,)))
    assert sentinel.count == c1  # cache hit: no compile event
    f(jnp.ones((5,)))
    assert sentinel.count > c1  # new shape: recompile


def test_recompile_flagged_on_shape_polymorphic_step(rec):
    """A step whose feed shapes change after warmup recompiles; the
    sentinel flags it live (expected=False) — the runtime complement of
    graphcheck's static graph-recompile-hazard."""
    solver = tiny_solver(batch=8)
    solver.step(1, lambda it: tiny_feeds(8))     # warmup round: expected
    solver.step(1, lambda it: tiny_feeds(6))     # batch moved: recompile
    rounds = events_of(rec, "round")
    assert len(rounds) == 2
    assert rounds[1]["compiles"] > 0
    alarms = events_of(rec, "recompile")
    assert alarms and alarms[0]["expected"] is False
    assert alarms[0]["where"] == "solo"


def test_absorb_compiles_keeps_deploy_builds_expected(rec):
    """Deploy-arm candidate AOT builds happen BETWEEN training rounds;
    absorb_compiles folds them into the by-design ledger so the next
    round does not claim them as phantom unexpected recompiles (the
    ProductionLoop.rollout -> elastic round seam)."""
    get_sentinel().install()
    rec.round(mode="elastic", tau=1, devices=2, iters=1, batch=8,
              wall_s=0.1, loss=1.0, fenced=True)  # warms "elastic"
    # a candidate build compiles off the round path
    jax.jit(lambda x: x * 3 - 1)(jnp.ones((11,)))
    n = rec.absorb_compiles("deploy")
    assert n > 0
    alarms = events_of(rec, "recompile")
    assert len(alarms) == 1
    assert alarms[0]["where"] == "deploy"
    assert alarms[0]["expected"] is True
    assert alarms[0]["count"] == n
    # the next warm round sees a clean ledger: no phantom alarm
    rec.round(mode="elastic", tau=1, devices=2, iters=1, batch=8,
              wall_s=0.1, loss=1.0, fenced=True)
    assert len(events_of(rec, "recompile")) == 1
    # idempotent when nothing compiled since
    assert rec.absorb_compiles("deploy") == 0
    assert len(events_of(rec, "recompile")) == 1


# -- Solver instrumentation -------------------------------------------------


def test_solver_round_record_contents(rec):
    solver = tiny_solver(batch=8)
    loss = solver.step(3, lambda it: tiny_feeds(8, seed=it))
    rounds = events_of(rec, "round")
    assert len(rounds) == 1
    r = rounds[0]
    assert r["mode"] == "solo" and r["tau"] == 1 and r["devices"] == 1
    assert r["iters"] == 3 and r["batch"] == 8
    assert r["fenced"] is True
    assert r["images_per_sec"] > 0 and r["wall_s"] > 0
    assert np.isfinite(r["loss"]) and np.isfinite(r["loss_ema"])
    assert r["iteration"] == 3
    assert np.isfinite(loss)


def test_solver_solve_emits_fenced_span(rec):
    solver = Solver(SolverConfig(base_lr=0.1, max_iter=2,
                                 snapshot_after_train=False), tiny_net(8))
    solver.solve(lambda it: tiny_feeds(8, seed=it))
    spans = events_of(rec, "span")
    assert [s["name"] for s in spans] == ["solver.solve"]
    assert spans[0]["fenced"] is True
    # the inner step() call journaled its own round under the span
    assert len(events_of(rec, "round")) == 1


# -- the disabled-path guarantee --------------------------------------------


def _lowered_text(solver):
    feeds = {k: jnp.asarray(v) for k, v in tiny_feeds(8).items()}
    return solver._train_step.lower(
        solver.variables, solver.slots, 0, feeds, solver._key).as_text()


def test_disabled_path_stablehlo_identical(tmp_path):
    """SPARKNET_OBS=0 (default): the solver's lowered StableHLO is the
    same whether or not obs instrumentation ever ran — the hooks live
    entirely outside the jitted programs."""
    baseline = tiny_solver(batch=8)
    text_off = _lowered_text(baseline)

    instrumented = tiny_solver(batch=8)
    recorder = set_recorder(
        Recorder(str(tmp_path / "j.jsonl"), run_id="hash"))
    try:
        instrumented.step(2, lambda it: tiny_feeds(8, seed=it))
        text_on = _lowered_text(instrumented)
    finally:
        set_recorder(None)
    assert events_of(recorder, "round"), "obs was armed and recording"
    assert text_on == text_off


def test_disabled_path_dispatch_count_identical(tmp_path):
    """Same dispatch count with obs on and off: the fence is a VALUE
    fetch of an existing output, never an extra device call."""

    def count_dispatches(solver, armed):
        calls = []
        orig = solver._train_step

        def counting(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        solver._train_step = counting
        if armed:
            set_recorder(Recorder(str(tmp_path / "d.jsonl"),
                                  run_id="dispatch"))
        try:
            solver.step(3, lambda it: tiny_feeds(8, seed=it))
        finally:
            if armed:
                set_recorder(None)
        return len(calls)

    assert count_dispatches(tiny_solver(batch=8), armed=False) == 3
    assert count_dispatches(tiny_solver(batch=8), armed=True) == 3


# -- ParallelTrainer rounds on the 8-device mesh ----------------------------


def test_dp_round_records_on_cpu_mesh(rec):
    assert jax.device_count() == 8, "conftest must fake 8 CPU devices"
    trainer = ParallelTrainer(tiny_solver(batch=16), tau=1)
    for i in range(2):
        loss = trainer.train_round(lambda it: tiny_feeds(16, seed=it))
    assert np.isfinite(loss)
    rounds = events_of(rec, "round")
    assert len(rounds) == 2
    r = rounds[0]
    assert r["mode"] == "dp" and r["tau"] == 1
    assert r["devices"] == 8 and r["workers"] == 8
    assert r["iters"] == 1 and r["batch"] == 16
    assert r["fenced"] is True and r["images_per_sec"] > 0
    # the analytic comm budget rides the record: one grad-sized
    # all-reduce window derived from the ACTUAL param bytes
    comm = r["comm"]
    lo, hi = comm["predicted"]["all-reduce"]
    assert lo <= comm["param_bytes"] <= hi
    # round 2 of a warm mode must not recompile
    assert rounds[1]["compiles"] == 0
    assert not events_of(rec, "recompile")


def test_tau_round_records_on_cpu_mesh(rec):
    tau = 2
    trainer = ParallelTrainer(tiny_solver(batch=2), tau=tau)
    for i in range(2):
        trainer.train_round(lambda it: tiny_feeds(16, tau=tau, seed=it))
    rounds = events_of(rec, "round")
    assert len(rounds) == 2
    r = rounds[0]
    assert r["mode"] == "tau" and r["tau"] == tau
    assert r["iters"] == tau and r["batch"] == 16
    assert r["fenced"] is True
    # tau's budget is the round's ONE model-sized pmean (params+state)
    comm = r["comm"]
    lo, hi = comm["predicted"]["all-reduce"]
    assert lo <= comm["param_bytes"] + comm["state_bytes"] <= hi
    assert r["loss_ema"] == pytest.approx(r["loss"], rel=1e-6)
    assert rounds[1]["compiles"] == 0


# -- Timer (satellite: fence-by-value, contract-clean) ----------------------


def test_timer_stop_fences_by_value():
    from sparknet_tpu.utils.timing import Timer

    t = Timer().start()
    out = jax.jit(lambda x: jnp.sum(x) * 2)(jnp.ones((4,)))
    ms = t.stop(out)
    assert ms >= 0 and t.elapsed_ms == ms


def test_timer_stop_rejects_large_leaf():
    from sparknet_tpu.utils.timing import Timer

    with pytest.raises(ValueError, match="last leaf"):
        Timer().start().stop(jnp.zeros((512, 1024), jnp.float32))


# -- the dryrun CLI (the zero-chip-time acceptance path) --------------------


@pytest.mark.slow
def test_dryrun_cli_journal_and_report(tmp_path):
    from sparknet_tpu.obs.__main__ import main

    out = str(tmp_path / "dry.jsonl")
    assert main(["dryrun", "--out", out, "--rounds", "2"]) == 0
    assert main(["validate", out]) == 0
    rounds = [e for e in schema.load_journal(out)
              if e.get("event") == "round"]
    assert {r["mode"] for r in rounds} == {"dp", "tau"}
    assert all(r["fenced"] and r["images_per_sec"] > 0 for r in rounds)
    assert all("comm" in r for r in rounds)
    text = render_path(out)
    assert "| dp |" in text and "| tau |" in text
    # every wall in a dryrun is fenced: no refusal markers in the body
    assert "REFUSED (unfenced)" not in text
    assert "REFUSED:" not in text and "REFUSED —" not in text
