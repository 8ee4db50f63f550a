"""The step program's own HBM account (PR 52): ``utils/profiling.
step_account`` reads ``memory_analysis()`` off the executable a step has
just compiled and run, and the ``sn.step`` / ``sn.round`` span inside
which it was compiled carries it; every fence carries the live bytes
where the backend counts them.  Taking the account must compile nothing.
"""

import dataclasses

import jax
import numpy as np
import pytest

from sparknet_tpu import cli, models
from sparknet_tpu.obs.recorder import flight
from sparknet_tpu.obs.sentinel import get_sentinel
from sparknet_tpu.parallel.mesh import data_parallel_mesh
from sparknet_tpu.parallel.trainer import ParallelTrainer
from sparknet_tpu.solvers.solver import Solver
from sparknet_tpu.utils import profiling

ACCOUNT = ("hbm_args_bytes", "hbm_out_bytes", "hbm_alias_bytes",
           "hbm_temps_bytes", "hbm_code_bytes", "hbm_devices")
REMOVED = ("ssm_chunk", "ssm_saved_bytes", "gdn_chunk", "gdn_saved_bytes",
           "swa_window", "swa_full_layers")
BATCH, CHIPS = 8, 4


def make_solver():
    return Solver(models.cifar10_quick_solver(), models.cifar10_quick(BATCH))


def batch_of(it, *lead):
    rng = np.random.default_rng(it)
    lead = lead or (BATCH,)
    return {"data": (40 * rng.standard_normal((*lead, 3, 32, 32))).astype(
                np.float32),
            "label": rng.integers(0, 10, lead).astype(np.int32)}


def make_trainer(tau):
    trainer = ParallelTrainer(make_solver(), mesh=data_parallel_mesh(CHIPS),
                              tau=tau)
    lead = (BATCH * CHIPS,) if tau == 1 else (tau, BATCH * CHIPS)
    return trainer, lambda it: batch_of(it, *lead)


class Recorded:
    """The spans the main thread closes inside the block, by name, and the
    compile listener's events of the thread meanwhile."""

    def __enter__(self):
        self.sentinel = get_sentinel().install()
        self._n = len(flight()[0])
        self._compiles = self.sentinel.thread_count()
        self._lowerings = self.sentinel.thread_lowerings()
        return self

    def __exit__(self, *exc):
        self.spans = flight()[0][self._n:]
        self.compiles = self.sentinel.thread_count() - self._compiles
        self.lowerings = self.sentinel.thread_lowerings() - self._lowerings

    def named(self, *names):
        return [s[4] for s in self.spans if s[0] in names]


def accounts(stats):
    return [s for s in stats if "hbm_args_bytes" in s]


# -------------------------------------------------- (a) the solo step
def test_the_first_step_carries_the_account_and_a_warm_one_none():
    solver = make_solver()
    with Recorded() as rec:
        solver.step(3, batch_of)
    first, *warm = rec.named("sn.step")
    assert set(ACCOUNT) <= set(first) and first["hbm_devices"] == 1
    assert first["hbm_args_bytes"] > 0 and first["hbm_temps_bytes"] > 0
    # the donated state is aliased into the outputs
    assert 0 < first["hbm_alias_bytes"] <= first["hbm_out_bytes"]
    assert "hbm_limit_bytes" not in first  # the CPU gives no memory_stats()
    assert len(warm) == 2 and not accounts(warm)
    assert solver._accounted == {solver._train_step: 1}


# -------------------------------------------------- (b) the trainer's round
@pytest.mark.parametrize("tau", [1, 2])
def test_the_first_round_carries_the_account_of_four_devices(tau):
    trainer, data_fn = make_trainer(tau)
    with Recorded() as rec:
        for _ in range(3):
            trainer.train_round(data_fn)
    trainer.close()
    first, *warm = rec.named("sn.round")
    assert set(ACCOUNT) <= set(first) and first["hbm_devices"] == CHIPS
    assert first["compiles"] >= 1
    assert len(warm) == 2 and not accounts(warm)


def test_fused_rounds_carry_the_account_on_the_round_that_compiled():
    trainer, data_fn = make_trainer(1)
    with Recorded() as rec:
        trainer.train_rounds(3, data_fn)
        trainer.train_rounds(3, data_fn)
        trainer.train_round(data_fn)  # another program: the plain round
    rounds = rec.named("sn.round")
    assert [r["it"] for r in rounds] == [0, 3, 6]
    assert [bool(accounts([r])) for r in rounds] == [True, False, True]
    assert rounds[0]["hbm_devices"] == CHIPS
    assert rounds[0]["hbm_args_bytes"] > rounds[2]["hbm_args_bytes"]  # 3 batches
    assert [f["it"] for f in rec.named("sn.round.fence")] == [0, 3, 6]


# -------------------------------------------------- (c) it compiles nothing
@pytest.mark.parametrize("drive", ["donated", "sharded"])
def test_taking_the_account_compiles_nothing(drive, monkeypatch):
    """The span's ``compiles`` and the thread's backend-compile and
    lowering events are those of the program without the account."""
    def run():
        if drive == "donated":
            solver = make_solver()
            with Recorded() as rec:
                solver.step(2, batch_of)
            return rec, rec.named("sn.step")
        trainer, data_fn = make_trainer(2)
        with Recorded() as rec:
            trainer.train_round(data_fn)
            trainer.train_round(data_fn)
        trainer.close()
        return rec, rec.named("sn.round")

    with_account, steps = run()
    assert accounts(steps[:1])
    for module in ("sparknet_tpu.solvers.solver",
                   "sparknet_tpu.parallel.trainer"):
        monkeypatch.setattr(f"{module}.account_compiled",
                            lambda *a, **k: None)
    without, plain = run()
    assert not accounts(plain)
    assert (with_account.compiles, with_account.lowerings) == (
        without.compiles, without.lowerings)
    assert [s.get("compiles", 0) for s in steps] == [
        s.get("compiles", 0) for s in plain] == [1, 0]


def test_a_fresh_lowering_takes_no_account_and_no_compile():
    """Asked of a call that was never made, jax lowers afresh: the helper
    returns nothing and hands nothing to the compiler."""
    fn = jax.jit(lambda x: x * 3 + 1)
    fn(np.ones(4, np.float32))
    with Recorded() as rec:
        assert profiling.step_account(fn, np.ones(5, np.float32)) == {}
    assert (rec.lowerings, rec.compiles) == (1, 0)
    with Recorded() as rec:
        account = profiling.step_account(fn, np.ones(4, np.float32))
    assert account["hbm_args_bytes"] == 16 and account["hbm_devices"] == 1
    assert (rec.lowerings, rec.compiles) == (0, 0)


# -------------------------------------------------- (d) a recompile
def test_a_step_that_recompiles_carries_a_fresh_account():
    solver = make_solver()
    with Recorded() as rec:
        solver.step(2, batch_of)
        solver.step(2, lambda it: batch_of(it, BATCH // 2))  # a ragged batch
    steps = rec.named("sn.step")
    assert [bool(accounts([s])) for s in steps] == [True, False, True, False]
    assert steps[2]["compiles"] == 1
    assert steps[2]["hbm_args_bytes"] < steps[0]["hbm_args_bytes"]
    assert steps[2]["hbm_temps_bytes"] < steps[0]["hbm_temps_bytes"]


# -------------------------------------------------- (e) the scanned chunk
def test_a_scanned_chunk_carries_the_account_where_it_compiled():
    solver = make_solver()
    with Recorded() as rec:
        solver.step(8, batch_of, scan_chunk=4)
        solver.step(2, batch_of, scan_chunk=2)  # a new chunk: a new program
    chunks = rec.named("sn.step")
    assert [c["it"] for c in chunks] == [0, 4, 8]
    assert [bool(accounts([c])) for c in chunks] == [True, False, True]
    assert chunks[0]["hbm_args_bytes"] > chunks[2]["hbm_args_bytes"]
    assert len(solver._accounted) == 2


# -------------------------------------------------- (f) the live bytes
class Chip:
    def __init__(self, in_use):
        self.memory_stats = lambda: {"bytes_in_use": in_use}


@pytest.mark.parametrize("drive", ["step", "callback", "scanned", "round"])
def test_the_fences_carry_the_fullest_chips_live_bytes_or_nothing(drive):
    def run(owner):
        if drive == "round":
            owner.train_round(data_fn)
        elif drive == "callback":
            owner.step(2, batch_of, callback=lambda it, loss: None)
        else:
            owner.step(4, batch_of, scan_chunk=2 if drive == "scanned" else 1)

    if drive == "round":
        owner, data_fn = make_trainer(2)
    else:
        owner = make_solver()
    with Recorded() as rec:
        run(owner)
    fences = rec.named("sn.step.fence", "sn.round.fence")
    assert fences and not any("hbm_live_bytes" in f for f in fences)
    owner._devices = [Chip(5 << 20), Chip(9 << 20), Chip(7 << 20)]
    with Recorded() as rec:
        run(owner)
    fences = rec.named("sn.step.fence", "sn.round.fence")
    assert fences and all(f["hbm_live_bytes"] == 9 << 20 for f in fences)


# -------------------------------------------------- (g) the same program
def test_the_account_is_memory_analysis_of_the_same_step():
    solver = make_solver()
    fn, variables, slots, key = solver.jitted_train_step(donate=True)
    feeds = batch_of(0)
    mem = fn.lower(variables, slots, 0, feeds, key).compile().memory_analysis()
    with Recorded() as rec:
        solver.step(1, lambda it: feeds)
    (account,) = accounts(rec.named("sn.step"))
    assert (account["hbm_temps_bytes"] + account["hbm_args_bytes"]
            + account["hbm_out_bytes"] - account["hbm_alias_bytes"]) == (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert account["hbm_temps_bytes"] == mem.temp_size_in_bytes


# -------------------------------------------------- (h) the operator's line
@pytest.mark.parametrize("extra", [[], ["--tau", "2"]])
def test_the_set_up_line_ends_with_the_step_programs_account(capsys, tmp_path,
                                                             extra):
    assert cli.main(["train", "--solver", "zoo:cifar10_quick", "--data",
                     "synthetic", "--batch", str(BATCH), "--iterations", "4",
                     *extra, "--output", str(tmp_path / "out")]) == 0
    (line,) = [l for l in capsys.readouterr().out.splitlines()
               if "set-up:" in l]
    tail = line.rsplit("; ", 1)[1]
    assert tail.startswith("step program: ") and "hbm_" not in line
    assert " GB arguments + " in tail and tail.endswith(" GB temporaries")


def test_the_set_up_line_gives_the_share_of_the_limit(monkeypatch):
    """Where the chip gives a limit: arguments + temporaries over it."""
    import threading

    me = threading.get_ident()
    spans = [("sn.main", me, 0, 10, {}),
             ("sn.step", me, 20, 10, {
                 "it": 0, "hbm_args_bytes": 5_884_000_000,
                 "hbm_temps_bytes": 6_687_000_000, "hbm_devices": 1,
                 "hbm_limit_bytes": 16_909_000_000}),
             ("sn.step.fence", me, 40, 10, {"it": 1})]
    monkeypatch.setattr("sparknet_tpu.obs.recorder.flight",
                        lambda: (spans, 0))
    assert cli._setup_line().endswith(
        "; step program: 5.88 GB arguments + 6.69 GB temporaries of 16.91 "
        "(74 %)")


# -------------------------------------------------- (i) what the fence kept
PHI4 = dict(batch=2, seq_len=32, vocab=97, hidden=64, heads=4, kv_heads=2,
            mlp_dim=96, layers=12, window=8, d_state=4,
            kept_layers=(0, 1, 6, 7, 8, 9, 10, 11))
QWEN3NEXT = dict(batch=2, seq_len=32, vocab=97, hidden=64, layers=4, heads=4,
                 kv_heads=2, head_dim=16, linear_k_heads=2, linear_v_heads=4,
                 linear_k_dim=8, linear_v_dim=16, experts=16, top_k=3,
                 expert_dim=24, shared_dim=24, experts_held=4, first_expert=4)
LAGUNA = dict(batch=2, seq_len=32, vocab=97, hidden=64, layers=5,
              heads_per_layer=(4, 8, 8, 8), kv_heads=2, head_dim=16, window=8,
              rope_parameters={
                  "full_attention": {
                      "rope_type": "yarn", "rope_theta": 500000, "factor": 32,
                      "original_max_position_embeddings": 4096,
                      "beta_fast": 64, "beta_slow": 1,
                      "partial_rotary_factor": 0.5},
                  "sliding_attention": {
                      "rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}},
              dense_dim=96, experts=16, top_k=3, expert_dim=24, shared_dim=24,
              experts_held=4, first_expert=4)


@pytest.mark.parametrize("zoo,preset,kept", [
    ("phi4_flash", PHI4, ("ssm_layers", "ssm_kernel_layers",
                          "attn_core_layers", "attn_kernel_layers")),
    ("qwen3_next", QWEN3NEXT, ("gdn_layers", "gdn_kernel_layers",
                               "attn_core_layers", "attn_kernel_layers")),
    ("laguna", LAGUNA, ("attn_core_layers", "attn_kernel_layers",
                        "swa_window_layers", "swa_band_layers",
                        "swa_block_share"))])
def test_the_fence_names_the_path_and_not_the_prototxts_constants(
        zoo, preset, kept):
    cfg = dataclasses.replace(getattr(models, f"{zoo}_solver")(),
                              random_seed=3)
    stats = Solver(cfg, getattr(models, zoo)(**preset))._fence_stats()
    assert set(kept) <= set(stats)
    assert not set(REMOVED) & set(stats)
