"""``harness/decoder_check.py``'s comparison on hand-made facts: what the
update's mask keeps, what the floor under the held experts' rows refuses,
and the bias settling on a stub forward."""

import numpy as np
import pytest

from benchmarks.harness import decoder_check as dc

E, K, T = 16, 2, 64  # experts, experts a token, tokens
SHARE = (4, 4)       # experts [4, 8) are held


def _run(chosen, grad, change, rate=0.001):
    """A run's facts in the form ``compare`` takes, from one layer's
    routing and one leaf's reference gradient and change."""
    load = np.bincount(chosen.reshape(-1), minlength=E).astype(np.float32)
    return {"total": 2.0, "main": 1.5, "mtp": 1.0,
            "logits": np.ones((1, 4, 8), np.float32),
            "mtp_logits": np.ones((1, 4, 8), np.float32),
            "chosen": {"moe": chosen}, "load": {"moe": load},
            "scores": {"moe": np.full((T, E), 0.5, np.float32)},
            "bias": {"moe": rate * np.sign(load.mean() - load)},
            "grad": {"router": grad}, "change": {"router": change}}


def _balanced():
    return (np.arange(T * K) % E).reshape(T, K).astype(np.int32)


def test_the_update_mask_leaves_out_entries_without_a_gradient():
    """Three quarters of the router's entries have no gradient: the median
    of ALL sizes is 0 and would keep them; a wrong change there must not
    be what the fact reads, and a wrong change among the sure must."""
    grad = np.zeros((E, 8), np.float32)
    grad[:4] = np.linspace(1, 2, 32).reshape(4, 8)
    change = -np.sign(grad) * 1e-3
    want = _run(_balanced(), grad, change)
    got = _run(_balanced(), grad, change.copy())
    got["change"]["router"][8:] = 1e-3           # coins where g = 0
    facts = dc.compare(got, want, K, {"moe": np.zeros(E)}, 0.001, SHARE)
    assert facts["update_entries.router"] == 4   # the largest tenth of 32
    assert facts["update_rel.router"] == 0.0
    assert facts["update_rel_half.router"] == 0.0
    assert facts["update_rel_all.router"] > 1.0
    got["change"]["router"][3] *= -1             # wrong signs among the sure
    facts = dc.compare(got, want, K, {"moe": np.zeros(E)}, 0.001, SHARE)
    assert facts["update_rel.router"] == pytest.approx(2.0)
    assert facts["update_rel_half.router"] == pytest.approx(2.0 * 0.5 ** 0.5)


def test_a_router_that_was_not_updated_is_not_correct():
    grad = np.ones((E, 8), np.float32)
    want = _run(_balanced(), grad, -np.sign(grad) * 1e-3)
    got = _run(_balanced(), grad, np.zeros_like(grad))
    facts = dc.compare(got, want, K, {"moe": np.zeros(E)}, 0.001, SHARE)
    assert facts["update_rel.router"] == pytest.approx(1.0)
    assert not facts["update_rel.router"] <= dc.TOL["update_rel.router"]


@pytest.mark.parametrize("rows,ok", [(T * K // E, True), (0, False)])
def test_the_held_experts_rows_are_reported_and_floored(rows, ok):
    chosen = _balanced()
    if not rows:  # everything lands outside the share
        chosen = np.where((chosen >= 4) & (chosen < 8), chosen + 4, chosen)
    grad = np.ones((E, 8), np.float32)
    run = _run(chosen, grad, -grad)
    facts = dc.compare(run, run, K, {"moe": np.zeros(E)}, 0.001, SHARE)
    assert facts["held_rows_min"] == rows
    assert facts["held_pair_share"] == pytest.approx(25.0 if ok else 0.0)
    assert (facts["held_rows_min"] >= dc.TOL_REHEARSE["held_rows_min"]) is ok
    assert "held_rows_min" in dc.FLOORS and "held_rows_min" in dc.TOL


def test_a_bias_entry_may_differ_only_where_the_counters_disagree():
    """Expert 0 is over the mean in the program's counter and under it in
    the reference's: its entry differs and is explained.  The same
    difference with equal counters is not."""
    grad = np.ones((E, 8), np.float32)
    ref_chosen = _balanced()
    ref_chosen[ref_chosen == 0] = 1  # the reference sent expert 0's pairs on
    want = _run(ref_chosen, grad, -grad)
    got = _run(_balanced(), grad, -grad)
    got["chosen"]["moe"][0, 0] = 0
    got["chosen"]["moe"][1, 0] = 0   # ... and the program two more to it
    got = _run(got["chosen"]["moe"], grad, -grad)
    facts = dc.compare(got, want, K, {"moe": np.zeros(E)}, 0.001, SHARE)
    assert facts["bias_differ"] >= 1 and facts["bias_differ_unexplained"] == 0
    assert facts["bias_rule_broken"] == 0
    got["bias"]["moe"] = got["bias"]["moe"].copy()
    got["bias"]["moe"][5] += 0.001   # a wrong entry where the counters agree
    facts = dc.compare(got, want, K, {"moe": np.zeros(E)}, 0.001, SHARE)
    assert facts["bias_differ_unexplained"] == 1
    assert facts["bias_rule_broken"] == 1


def test_settling_follows_its_schedule_and_levels_the_load():
    """A stub forward whose load follows the bias: every expert's load is
    the mean times exp of its score + bias against the others'."""
    class Solver:
        class variables:
            state = {"moe": {"bias": np.zeros(E, np.float32)}}

    score = np.linspace(-0.05, 0.05, E)
    calls = []

    def forward(variables, feeds):
        calls.append(1)
        z = np.exp(40.0 * (score + np.asarray(variables.state["moe"]["bias"])))
        return {"load": {"moe": (T * K * z / z.sum()).astype(np.float32)}}

    seen = dc.settle_bias(Solver, forward, {"data": np.zeros((1, 4))},
                          [(0.004, 30), (0.001, 10), (0.00025, 10)])
    assert len(calls) == 51 and len(seen) == 51
    assert seen[0] > 3.0 and seen[-1] < 1.1
    bias = np.asarray(Solver.variables.state["moe"]["bias"])
    assert bias[0] > bias[-1]  # the emptiest was raised, the fullest lowered
