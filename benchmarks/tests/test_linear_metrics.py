"""The linear-attention decoder cell's six readers on a hand-made trace in
the neutral form, and what they return where the program carries no such
scope or counter (the parent of PR 47, another cell)."""

import json
import os

import pytest

from benchmarks.harness import linear_flops, load_by_name
from benchmarks.metrics import _decoder_scopes, _linear_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "qwen3-next-80b-a3b-l4-ep16-v8-bf16.json")) as f:
    CONFIG = json.load(f)
CELL = "qwen3next-solo-s4096"
US = 1000
NEW = ["gdn.core_share", "gdn.mix_share", "gdn.core_roofline",
       "attn.gated_core_roofline", "moe.wide_route_share",
       "moe.wide_held_pair_share"]
# chip 0: [start_ns, dur_ns, name, scope]
TRACE = {
    "window": [0, 100 * US],
    "chips": {"0": [
        [0, 4 * US, "fusion.1", "jit(step)/L.gdn0/dot"],
        [4 * US, 10 * US, "while.1", "jit(step)/L.gdn0/D.delta/while"],
        [14 * US, 20 * US, "while.2",
         "jit(step)/transpose(jvp(L.gdn0))/D.delta/D.delta/while"],
        [34 * US, 6 * US, "fusion.2", "jit(step)/transpose(jvp(L.gdn0))/dot"],
        [40 * US, 5 * US, "while.3", "jit(step)/L.gdn2/D.delta/while"],
        [45 * US, 2 * US, "splash.1", "jit(step)/L.attn3/A.core/splash_mqa_fwd"],
        [47 * US, 4 * US, "splash.2",
         "jit(step)/transpose(jvp(L.attn3))/A.core/splash_mqa_dkv"],
        [51 * US, 3 * US, "fusion.3", "jit(step)/L.moe3/M.route/top_k"],
        [54 * US, 5 * US, "fusion.4",
         "jit(step)/transpose(jvp(L.moe3))/M.combine/scatter"],
        [59 * US, 1 * US, "gmm.1", "jit(step)/L.moe3/M.experts/gmm"],
        [60 * US, 2 * US, "fusion.5", "jit(step)/L.moe3/M.shared/dot"],
        [62 * US, 18 * US, "fusion.6", "jit(step)/S.update/mul"],
        [200 * US, 9 * US, "while.9", "jit(step)/L.gdn0/D.delta/x"],  # outside
    ]},
    "host": [],
}
LAYER_S = {"gdn0": 30e-6, "gdn2": 5e-6}
FENCES = [{"start_ns": 10 * US, "stats": {
               "moe_pairs": 40960, "moe_layers": 4, "moe_pairs_held": 8192}},
          {"start_ns": 20 * US, "stats": {
               "moe_pairs": "40960", "moe_layers": "4",
               "moe_pairs_held": "12288"}},
          {"start_ns": 500 * US, "stats": {
               "moe_pairs": 40960, "moe_layers": 4, "moe_pairs_held": 1}}]


def summary_of(linear, decoder=None):
    fwd = {"gdn0": 14e-6, "gdn2": 5e-6, "attn3": 2e-6, "moe3": 6e-6}
    bwd = {"gdn0": 26e-6, "attn3": 4e-6, "moe3": 5e-6}
    return {"window_s": 100e-6, "linear_scopes": linear,
            "decoder_scopes": decoder, "chips": {"0": {
                "busy_s": 80e-6, "layer_fwd_s": fwd, "layer_bwd_s": bwd,
                "unscoped_s": {"S.update": 18e-6}}}}


def run_facts():
    return {"decoder_parts": linear_flops.parts(CONFIG, 1, 4096),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "steps_traced": 2}


def test_reduce_books_self_time_by_layer_under_the_scope_in_the_window():
    assert _linear_scopes.reduce(TRACE)["layer_s"] == pytest.approx(LAYER_S)


def test_the_six_readers_on_the_hand_made_trace():
    summary = summary_of({"layer_s": LAYER_S},
                         _decoder_scopes.reduce(TRACE, FENCES))
    run = run_facts()
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    total = 80e-6  # fwd + bwd + unscoped
    assert read("gdn.core_share") == pytest.approx(100 * 35e-6 / total)
    # the DeltaNet layers hold 45 us (gdn1 left no op), 35 under D.delta
    assert read("gdn.mix_share") == pytest.approx(100 * 10e-6 / total)
    # bandwidth-bound: q, k, v, g, beta in and o out, three passes, 2 B
    core_floor = 3 * 2 * 4096 * (2 * 2048 + 4096 + 4 * 32 + 4096) / 819e9
    assert read("gdn.core_roofline") == pytest.approx(
        100 * 3 * core_floor * 2 / 35e-6, rel=1e-6)
    attn_floor = 6 * (4096 * 4097 // 2) * 16 * 512 / 197e12  # compute-bound
    assert read("attn.gated_core_roofline") == pytest.approx(
        100 * attn_floor * 2 / 6e-6, rel=1e-6)
    assert read("moe.wide_route_share") == pytest.approx(100 * 8e-6 / total)
    assert read("moe.wide_held_pair_share") == pytest.approx(
        100 * (8192 + 12288) / 2 / (40960 * 4))


@pytest.mark.parametrize("name", NEW)
def test_absent_scope_or_counter_reads_none(name):
    """A program without the scope or the counter (the parent), a cell
    without such parts (another configuration's run), no trace at all:
    None, and nothing raised."""
    reader = load_by_name("metrics", name)
    run = run_facts()
    empty = {"scope_s": dict.fromkeys(_decoder_scopes.SCOPES, 0.0),
             "fences": []}
    assert reader.read(summary_of({"layer_s": {}}, empty), run) is None
    assert reader.read(None, run) is None
    assert reader.read(summary_of(None, None), run) is None
    bare = dict(run, decoder_parts=[])
    if name in ("gdn.mix_share", "gdn.core_roofline",
                "attn.gated_core_roofline"):
        other = summary_of({"layer_s": {"gdn7": 7e-6}},
                           dict(empty, scope_s=dict(empty["scope_s"],
                                                    **{"A.core": 1e-6})))
        assert reader.read(other, bare) is None


def test_the_readers_are_declared_together_and_for_the_new_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 6] == NEW and at > names.index("loop.mean_exit_step")
    mine = bench["per_layer"][at:at + 6]
    assert all(m["workloads"] == [CELL] and m["moves"] == "images_per_s"
               and m["unit"] == "%" for m in mine)
    assert [m["better"] for m in mine] == ["lower", "lower", "higher",
                                           "higher", "lower", "higher"]
    assert [m["source"] for m in mine] == ["device_trace"] * 5 + [
        "program_counter"]
    # the readers that list every cell find this cell's rows
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "lm-linear-solo"
