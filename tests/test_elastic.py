"""Elastic τ-averaging: fault injection on the virtual 8-device mesh.

The suite pins ISSUE 8's contracts with zero chip time:

* deterministic shard reassignment (``round_shards`` modulo ownership —
  no example dropped or double-counted across a resize);
* the loss-trajectory-equivalence gates: kill-at-the-first-boundary ==
  never-started-with-that-worker (exact), and kill-mid-run == a fresh
  pool of the surviving width seeded from the survivors' state;
* staleness damping: s = 0 reduces exactly to the fixed-mesh tau
  trajectory (vs ``ParallelTrainer``), a rejoining straggler enters the
  weighted average with the documented ``decay ** s`` weight (checked
  against a hand-built per-worker simulation), and a worker past the
  staleness bound is dropped, never averaged;
* membership telemetry: worker_lost / worker_joined / mesh_resize
  events schema-validate and render in the obs report.
"""

import json
import os

import jax
import numpy as np
import pytest

from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES
from sparknet_tpu.parallel.elastic import (
    ElasticTrainer,
    FaultEvent,
    FaultPlan,
    delay,
    join,
    kill,
    round_shards,
)
from sparknet_tpu.solvers.solver import Solver

FAM = GRAPH_SWEEP_FAMILIES["cifar10_quick"]
B = 2  # per-worker batch


def shard_fn(g):
    """The shard-id data contract: a pure function of g."""
    from sparknet_tpu.parallel.modes import _feeds_for

    return _feeds_for(FAM, B, np.random.RandomState(g % 1009))


def make_trainer(width, tau=2, plan=None, **kw):
    return ElasticTrainer(Solver(FAM.solver(), FAM.net(B)), width=width,
                          tau=tau, plan=plan, **kw)


# -- shard reassignment -----------------------------------------------------


def test_round_shards_modulo_ownership():
    grid = round_shards(cursor=5, tau=3, width=4)
    assert grid.shape == (3, 4)
    for w in range(4):
        assert all(int(g) % 4 == w for g in grid[:, w])
    # consecutive block, nothing dropped or double-counted
    assert sorted(grid.ravel().tolist()) == list(range(5, 17))


def test_round_shards_cover_epoch_across_resize():
    """An epoch's ids are consumed exactly once even when the width
    changes mid-epoch (the cursor advances by tau*W' per round)."""
    consumed = []
    cursor = 0
    for width in (8, 6, 4, 7):  # a resize between every round
        grid = round_shards(cursor, 2, width)
        consumed.extend(int(g) for g in grid.ravel())
        cursor += 2 * width
    assert sorted(consumed) == list(range(cursor))
    assert len(set(consumed)) == len(consumed)


def test_round_shards_validation():
    with pytest.raises(ValueError, match="width"):
        round_shards(0, 1, 0)


# -- fault plan -------------------------------------------------------------


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan([FaultEvent(round=0, kind="explode")])
    with pytest.raises(ValueError, match="steps > 0"):
        FaultPlan([FaultEvent(round=0, kind="delay", worker=0, steps=0)])
    with pytest.raises(ValueError, match="count > 0"):
        FaultPlan([FaultEvent(round=0, kind="join", count=0)])
    plan = FaultPlan([kill(1, at_round=2), join(at_round=1)])
    assert [e.round for e in plan.events] == [1, 2]
    assert plan.at(2) == [kill(1, at_round=2)]


def test_kill_unknown_or_last_worker_raises():
    tr = make_trainer(2, plan=FaultPlan([kill(9, at_round=0)]))
    with pytest.raises(ValueError, match="not active"):
        tr.train_round(shard_fn)
    tr1 = make_trainer(1, plan=FaultPlan([kill(0, at_round=0)]))
    with pytest.raises(ValueError, match="last active worker"):
        tr1.train_round(shard_fn)


# -- loss-trajectory-equivalence gates --------------------------------------


def test_kill_at_start_equals_never_started():
    """The headline gate: a worker killed at the first round boundary
    leaves a trajectory identical to a pool that never had it —
    deterministic shard reassignment + per-position RNG + the hard
    averaging boundary make the equality exact, not approximate."""
    killed = make_trainer(6, plan=FaultPlan([kill(5, at_round=0)]))
    never = make_trainer(5)
    lk = [killed.train_round(shard_fn) for _ in range(3)]
    ln = [never.train_round(shard_fn) for _ in range(3)]
    assert killed.width == 5
    np.testing.assert_allclose(lk, ln, rtol=0, atol=0)


def test_kill_mid_run_equals_restart_without_worker():
    """Kill at a later boundary: the continuation equals a fresh
    trainer of the surviving width seeded from the survivors' state
    (params are the round consensus; each survivor keeps its own slot
    history — the optimizer-state-carrying handoff)."""
    tr = make_trainer(4, plan=FaultPlan([kill(3, at_round=2)]))
    for _ in range(2):
        tr.train_round(shard_fn)
    # state snapshot BEFORE the boundary applies: take it from a twin
    # trainer that ran the same two rounds with no plan, then drop the
    # doomed worker's row by hand
    twin = make_trainer(4)
    for _ in range(2):
        twin.train_round(shard_fn)
    state = twin.state_dict()
    keep = [0, 1, 2]
    state["width"] = 3
    state["wids"] = [state["wids"][i] for i in keep]
    state["variables"] = jax.tree_util.tree_map(
        lambda x: x[keep], state["variables"])
    state["slots"] = jax.tree_util.tree_map(
        lambda x: x[keep], state["slots"])
    fresh = make_trainer(3)
    fresh.load_state_dict(state)
    lc = [tr.train_round(shard_fn) for _ in range(2)]
    lf = [fresh.train_round(shard_fn) for _ in range(2)]
    assert tr.width == 3
    np.testing.assert_allclose(lc, lf, rtol=0, atol=0)


def test_staleness_zero_reduces_to_plain_tau():
    """s = 0 (no faults, all weights 1): the weighted round IS the
    fixed-mesh SparkNet tau round — the elastic trainer's trajectory
    matches ParallelTrainer on the same assembled feeds."""
    from sparknet_tpu.parallel.trainer import ParallelTrainer

    tau, W = 3, 8
    el = make_trainer(W, tau=tau)
    pt = ParallelTrainer(Solver(FAM.solver(), FAM.net(B)), tau=tau)
    cursor = 0
    le, lp = [], []
    for _ in range(3):
        grid = round_shards(cursor, tau, W)
        steps = []
        for t in range(tau):
            per = [shard_fn(int(g)) for g in grid[t]]
            steps.append({k: np.concatenate([f[k] for f in per])
                          for k in per[0]})
        feeds = {k: np.stack([s[k] for s in steps]) for k in steps[0]}
        le.append(el.train_round(shard_fn))
        lp.append(pt.train_round(lambda it: feeds))
        cursor += tau * W
    np.testing.assert_allclose(le, lp, rtol=1e-6, atol=1e-7)


# -- staleness damping ------------------------------------------------------


def test_straggler_rejoins_with_documented_weight():
    """A worker parked for s rounds rejoins with weight decay**s in the
    round average: verified against a hand-built simulation that runs
    every worker's tau steps through the Solver's own step function and
    forms the weighted average x̄ = Σ w_i x_i / Σ w_i on host."""
    decay, tau, W = 0.5, 1, 4
    # park worker 0 at round 1 for one round (steps=tau -> 1 round)
    tr = make_trainer(W, tau=tau, staleness_decay=decay,
                      plan=FaultPlan([delay(0, at_round=1, steps=tau)]))
    tr.train_round(shard_fn)  # round 0: full pool
    tr.train_round(shard_fn)  # round 1: worker 0 parked (W=3)
    assert tr.width == W - 1
    # boundary of round 2: worker 0 rejoins with s=1 -> weight 0.5
    state = tr.state_dict()
    parked = tr._parked[0]
    rows_v = [jax.tree_util.tree_map(lambda x, i=i: np.asarray(x[i]),
                                     state["variables"])
              for i in range(W - 1)] + [parked.variables]
    rows_s = [jax.tree_util.tree_map(lambda x, i=i: np.asarray(x[i]),
                                     state["slots"])
              for i in range(W - 1)] + [parked.slots]
    cursor, it = tr.cursor, tr.iter
    loss = tr.train_round(shard_fn)  # round 2: rejoin round
    assert tr.width == W
    assert np.isfinite(loss)
    np.testing.assert_allclose(tr._round_weights,
                               [1.0, 1.0, 1.0, decay])

    # hand simulation of the rejoin round
    step = tr.solver._make_train_step(debug=False)
    grid = round_shards(cursor, tau, W)
    post_v = []
    for pos in range(W):
        v, sl = rows_v[pos], rows_s[pos]
        wkey = jax.random.fold_in(tr.solver._key, pos)
        for t in range(tau):
            v, sl, _ = step(
                jax.tree_util.tree_map(np.asarray, v), sl,
                it + t, shard_fn(int(grid[t, pos])), wkey)
        post_v.append(jax.tree_util.tree_map(np.asarray, v))
    w = np.asarray([1.0, 1.0, 1.0, decay])

    def wavg(*xs):
        return np.tensordot(w / w.sum(), np.stack(xs), axes=1)

    want = jax.tree_util.tree_map(wavg, *post_v)
    got = jax.tree_util.tree_map(
        lambda x: np.asarray(x[0]), jax.device_get(tr.variables))
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_staleness_bound_drops_worker():
    """A straggler past the bound is dropped (worker_lost), never
    averaged: the pool stays at the shrunken width and every weight is
    fresh (1.0)."""
    tr = make_trainer(4, tau=1, staleness_bound=1,
                      plan=FaultPlan([delay(2, at_round=1, steps=3)]))
    for _ in range(5):
        tr.train_round(shard_fn)
    # parked for 3 rounds > bound 1 -> dropped at its rejoin boundary
    assert tr.width == 3
    assert not tr._parked
    np.testing.assert_allclose(tr._round_weights, np.ones(3))


# -- membership telemetry ---------------------------------------------------


def test_membership_events_schema_valid_and_rendered(tmp_path):
    from sparknet_tpu.obs import schema
    from sparknet_tpu.obs.recorder import Recorder, set_recorder
    from sparknet_tpu.obs.report import render_path

    out = str(tmp_path / "elastic.jsonl")
    set_recorder(Recorder(out))
    try:
        plan = FaultPlan([kill(3, at_round=1), join(at_round=2),
                          delay(0, at_round=2, steps=2)])
        tr = make_trainer(4, tau=2, plan=plan)
        for _ in range(4):
            tr.train_round(shard_fn)
    finally:
        set_recorder(None)
    n, errors = schema.validate_journal(out)
    assert not errors, errors
    events = [e["event"] for e in schema.load_journal(out)]
    assert "worker_lost" in events
    assert "worker_joined" in events
    assert "mesh_resize" in events
    rounds = [e for e in schema.load_journal(out) if e["event"] == "round"]
    assert all(r["mode"] == "elastic" and r["fenced"] for r in rounds)
    text = render_path(out)
    assert "elastic membership" in text
    assert "worker_lost" in text and "mesh_resize" in text


def test_obs_off_emits_nothing(tmp_path):
    """Disarmed recorder: the elastic loop journals nothing and the
    membership helper is a no-op (the off-contract)."""
    from sparknet_tpu.obs.recorder import Recorder, set_recorder

    set_recorder(Recorder(None))
    try:
        tr = make_trainer(3, tau=1,
                          plan=FaultPlan([kill(2, at_round=1)]))
        for _ in range(2):
            tr.train_round(shard_fn)
        assert tr.width == 2
    finally:
        set_recorder(None)


# -- state surface ----------------------------------------------------------


def test_state_dict_roundtrip_continues_trajectory():
    a = make_trainer(3, tau=2)
    for _ in range(2):
        a.train_round(shard_fn)
    b = make_trainer(3, tau=2)
    b.load_state_dict(a.state_dict())
    la = [a.train_round(shard_fn) for _ in range(2)]
    lb = [b.train_round(shard_fn) for _ in range(2)]
    np.testing.assert_allclose(la, lb, rtol=0, atol=0)


def test_sync_to_solver_folds_consensus():
    tr = make_trainer(3, tau=1)
    tr.train_round(shard_fn)
    tr.sync_to_solver()
    assert tr.solver.iter == tr.iter
    # post-round replicas are the consensus: every row equals the mean
    host = jax.device_get(tr.variables)
    for leaf in jax.tree_util.tree_leaves(host.params):
        np.testing.assert_allclose(leaf[0], leaf.mean(0), rtol=1e-6,
                                   atol=1e-6)


def test_join_adopts_entry_consensus_including_departing():
    """A kill and a join at the same boundary: the joiner's slots are
    the mean over the ENTRY pool — the departing worker's optimizer
    state folds into the consensus it adopts (the handoff contract)."""
    tr = make_trainer(3, tau=1,
                      plan=FaultPlan([kill(2, at_round=1),
                                      join(at_round=1)]))
    tr.train_round(shard_fn)  # round 0: slots diverge per worker
    host_s = jax.device_get(tr.slots)
    entry_rows = [jax.tree_util.tree_map(lambda x, i=i: np.asarray(x[i]),
                                         host_s) for i in range(3)]
    want = jax.tree_util.tree_map(
        lambda *xs: np.mean(np.stack(xs), axis=0), *entry_rows)
    tr._apply_boundary(1)
    assert tr._wids == [0, 1, 3]  # 2 killed, 3 joined
    got = jax.tree_util.tree_map(
        lambda x: np.asarray(x[2]), jax.device_get(tr.slots))
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# -- graph/mem twins --------------------------------------------------------


def test_elastic_modes_registered_at_banked_widths():
    from sparknet_tpu.parallel.modes import ELASTIC_WIDTHS, list_modes

    modes = list_modes()
    assert len(ELASTIC_WIDTHS) >= 2
    for w in ELASTIC_WIDTHS:
        assert f"elastic_w{w}" in modes


def test_elastic_manifests_banked_in_both_families():
    """The width-parameterized contract twins exist on disk with the
    width actually recorded — the coverage the elastic-manifest-fresh
    lint rule enforces at the source side."""
    from sparknet_tpu.analysis.graphcheck import MANIFEST_DIR as GDIR
    from sparknet_tpu.analysis.memcheck import MANIFEST_DIR as MDIR
    from sparknet_tpu.parallel.modes import ELASTIC_WIDTHS

    for w in ELASTIC_WIDTHS:
        for d in (GDIR, MDIR):
            path = os.path.join(d, f"elastic_w{w}.json")
            assert os.path.exists(path), path
            with open(path, encoding="utf-8") as f:
                manifest = json.load(f)
            assert manifest["meta"]["mesh"] == {"data": w}
            assert manifest["meta"]["elastic"] is True
    # the comm contract is width-invariant: same collective families,
    # model-sized window, in every banked width
    kinds = set()
    for w in ELASTIC_WIDTHS:
        with open(os.path.join(GDIR, f"elastic_w{w}.json"),
                  encoding="utf-8") as f:
            comm = json.load(f)["contract"]["comm"]
        kinds.add(tuple(sorted(comm)))
        assert "all-reduce" in comm
    assert len(kinds) == 1, kinds


@pytest.mark.slow
def test_elastic_graphcheck_slice_green():
    """Lower + audit the banked elastic twins against their manifests
    (the drift gate for the width-parameterized contract)."""
    from sparknet_tpu.analysis.graphcheck import run_graphcheck
    from sparknet_tpu.parallel.modes import ELASTIC_WIDTHS

    findings, _ = run_graphcheck(
        [f"elastic_w{w}" for w in ELASTIC_WIDTHS])
    assert not [f for f in findings if not f.suppressed], findings
