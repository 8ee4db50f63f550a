"""The language-model cells' readers on a hand-made trace in the neutral
form, and what they return where the program carries no such scope."""

import pytest

from benchmarks.harness import load_by_name, lm_flops
from benchmarks.metrics import _lm_scopes

US = 1000
# chip 0: [start_ns, dur_ns, name, scope]
TRACE = {
    "window": [0, 100 * US],
    "chips": {"0": [
        [0, 10 * US, "fusion.1", "jit(step)/L.attn1/A.core/dot"],
        [10 * US, 5 * US, "fusion.2", "jit(step)/L.attn1/dot"],
        [20 * US, 20 * US, "gmm.1", "jit(step)/L.moe1/M.experts/gmm"],
        [40 * US, 20 * US, "gmm.2",
         "jit(step)/transpose(jvp(L.moe1))/M.experts/tgmm"],
        [60 * US, 4 * US, "sort.1", "jit(step)/L.moe1/M.dispatch/sort"],
        [64 * US, 3 * US, "fusion.3", "jit(step)/L.moe1/M.route/dot"],
        [67 * US, 3 * US, "fusion.4", "jit(step)/L.moe1/M.combine/gather"],
        [70 * US, 10 * US, "fusion.5", "jit(step)/S.update/mul"],
        [200 * US, 10 * US, "gmm.3", "jit(step)/L.moe1/M.experts/gmm"],  # outside
    ]},
    "host": [],
}
FENCES = [
    {"start_ns": 50 * US, "stats": {"it": 8, "moe_load_max": 3000,
                                    "moe_pairs": 131072, "moe_experts": 64}},
    {"start_ns": 90 * US, "stats": {"it": 16, "moe_load_max": 5000,
                                    "moe_pairs": 131072, "moe_experts": 64}},
    {"start_ns": 300 * US, "stats": {"it": 24, "moe_load_max": 9000,
                                     "moe_pairs": 131072, "moe_experts": 64}},
    {"start_ns": 95 * US, "stats": {"it": 16}},  # a CNN's fence: no counter
]


def summary_of(lm):
    busy = {"attn1": 15e-6, "moe1": 30e-6}
    return {"window_s": 100e-6, "lm_scopes": lm, "chips": {"0": {
        "busy_s": 75e-6, "layer_fwd_s": busy,
        "layer_bwd_s": {"moe1": 20e-6}, "unscoped_s": {"S.update": 10e-6}}}}


def test_reduce_books_self_time_by_inner_scope_inside_the_window():
    lm = _lm_scopes.reduce(TRACE, FENCES)
    assert lm["scope_s"] == pytest.approx({
        "A.core": 10e-6, "M.experts": 40e-6, "M.dispatch": 4e-6,
        "M.route": 3e-6, "M.combine": 3e-6})
    assert [s["moe_load_max"] for s in lm["load"]] == [3000, 5000]


def test_readers():
    summary = summary_of(_lm_scopes.reduce(TRACE, FENCES))
    config = {"hidden_size": 2048, "num_experts": 64, "num_experts_per_tok": 8,
              "intermediate_size": 1024, "num_hidden_layers": 1,
              "vocab_rows": 12576}
    run = {"lm_parts": lm_flops.parts(config, 4, 4096), "steps_traced": 1,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    # floors 25.1 ms and 4.19 ms against the 40 us and 10 us of the toy trace
    assert read("moe.experts_roofline") == pytest.approx(
        100 * 25.1157e-3 / 40e-6, rel=1e-3)
    assert read("attn.core_roofline") == pytest.approx(
        100 * 4.18596e-3 / 10e-6, rel=1e-3)
    assert read("moe.dispatch_share") == pytest.approx(100 * 10 / 75)
    assert read("moe.load_max_over_mean") == pytest.approx(
        (3000 + 5000) / 2 * 64 / 131072)
    assert read("lm.update_share") == pytest.approx(100 * 10 / 75)


@pytest.mark.parametrize("name", [
    "moe.experts_roofline", "attn.core_roofline", "moe.dispatch_share",
    "moe.load_max_over_mean", "lm.update_share"])
def test_a_program_without_the_scopes_reads_none(name):
    """The parent of PR 26, or a CNN cell: nothing to read, no raise."""
    empty = {"scope_s": dict.fromkeys(_lm_scopes.SCOPES, 0.0), "load": []}
    summary = summary_of(empty)
    summary["chips"]["0"]["unscoped_s"] = {"jit(step)/mul": 10e-6}
    run = {"lm_parts": [], "steps_traced": 1,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    reader = load_by_name("metrics", name)
    assert reader.read(summary, run) is None
    assert reader.read(None, run) is None
