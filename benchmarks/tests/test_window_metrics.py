"""The window-and-full attention decoder cell's nine readers on a
hand-made trace in the neutral form, and what they return where the
program carries no such scope or counter (the parent of PR 50, another
cell)."""

import json
import os

import pytest

from benchmarks.harness import load_by_name, window_flops
from benchmarks.metrics import _decoder_scopes, _hybrid_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "laguna-xs2-l5-v8-bf16.json")) as f:
    CONFIG = json.load(f)
CELL = "laguna-solo-s8192"
US = 1000
NEW = ["swa.window_core_roofline", "swa.full_core_roofline",
       "swa.core_share", "swa.mix_share", "swa.held_pair_share",
       "swa.route_share", "swa.load_max_over_mean", "swa.update_share",
       "swa.block_share"]
# those that read the fence's counters or a scope outside the attention
# layers: no ``decoder_parts`` row decides what they find
NO_PARTS = set(NEW[4:])
# chip 0: [start_ns, dur_ns, name, scope]
TRACE = {
    "window": [0, 100 * US],
    "chips": {"0": [
        [0, 4 * US, "fusion.1", "jit(step)/L.attn0/dot"],
        [4 * US, 2 * US, "fusion.2", "jit(step)/L.attn0/A.rope/mul"],
        [6 * US, 10 * US, "splash.1",
         "jit(step)/L.attn0/A.core/splash_mqa_fwd"],
        [16 * US, 20 * US, "splash.2",
         "jit(step)/transpose(jvp(L.attn0))/A.core/splash_mqa_dkv"],
        [36 * US, 1 * US, "fusion.3", "jit(step)/L.attn0/A.gate/logistic"],
        [37 * US, 3 * US, "fusion.4", "jit(step)/transpose(jvp(L.attn0))/dot"],
        [40 * US, 2 * US, "splash.3",
         "jit(step)/L.attn1/A.core/splash_mqa_fwd"],
        [42 * US, 4 * US, "splash.4",
         "jit(step)/transpose(jvp(L.attn2))/A.core/splash_mqa_dkv"],
        [46 * US, 3 * US, "fusion.5",
         "jit(step)/transpose(jvp(L.attn1))/A.rope/mul"],
        [49 * US, 5 * US, "fusion.6", "jit(step)/L.moe1/M.route/top_k"],
        [54 * US, 6 * US, "fusion.7", "jit(step)/L.mlp0/dot"],
        [60 * US, 20 * US, "fusion.8", "jit(step)/S.update/mul"],
        [200 * US, 9 * US, "splash.9", "jit(step)/L.attn0/A.core/x"],  # outside
    ]},
    "host": [],
}
# what ``_hybrid_scopes.reduce`` books of it: ``A.core`` by layer (``A.rope``
# and ``A.gate`` stay in their layer's time outside the core)
LAYER_S = {"attn0/A.core": 30e-6, "attn1/A.core": 2e-6, "attn2/A.core": 4e-6}
FENCES = [{"start_ns": 10 * US, "stats": {
               "moe_pairs": 65536, "moe_layers": 4, "moe_pairs_held": 16384,
               "moe_load_max": 8192, "moe_experts": 256,
               "swa_window": 512, "swa_block_share": 22.79}},
          {"start_ns": 20 * US, "stats": {
               "moe_pairs": "65536", "moe_layers": "4",
               "moe_pairs_held": "8192", "moe_load_max": "4096",
               "moe_experts": "256", "swa_block_share": "22.79"}},
          {"start_ns": 500 * US, "stats": {
               "moe_pairs": 65536, "moe_layers": 4, "moe_pairs_held": 1}}]


def summary_of(window, decoder=None, update=20e-6):
    fwd = {"attn0": 17e-6, "attn1": 2e-6, "moe1": 5e-6, "mlp0": 6e-6}
    bwd = {"attn0": 23e-6, "attn1": 3e-6, "attn2": 4e-6}
    return {"window_s": 100e-6, "hybrid_scopes": window,
            "decoder_scopes": decoder, "chips": {"0": {
                "busy_s": 80e-6, "layer_fwd_s": fwd, "layer_bwd_s": bwd,
                "unscoped_s": {"S.update": update} if update else {}}}}


def run_facts():
    return {"decoder_parts": window_flops.parts(CONFIG, 1, 8192),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "steps_traced": 2}


def test_the_cores_are_booked_by_layer_in_the_window():
    assert _hybrid_scopes.reduce(TRACE)["layer_scope_s"] == pytest.approx(
        LAYER_S)


def test_the_five_readers_on_the_hand_made_trace():
    summary = summary_of({"layer_scope_s": LAYER_S},
                         _decoder_scopes.reduce(TRACE, FENCES))
    run = run_facts()
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    total = 80e-6  # fwd + bwd + unscoped
    # compute-bound floors: three passes of what the mask asks
    win_floor = 6 * 4_063_488 * 64 * 256 / 197e12   # 2.03 ms a layer
    full_floor = 6 * (8192 * 8193 // 2) * 48 * 256 / 197e12  # 12.56 ms
    # three window layers' floors over the time of those that left an op
    assert read("swa.window_core_roofline") == pytest.approx(
        100 * 3 * win_floor * 2 / 6e-6, rel=1e-6)
    assert read("swa.full_core_roofline") == pytest.approx(
        100 * 2 * full_floor * 2 / 30e-6, rel=1e-6)
    assert read("swa.core_share") == pytest.approx(100 * 36e-6 / total)
    # the attention layers hold 49 us, 36 of them under A.core
    assert read("swa.mix_share") == pytest.approx(100 * 13e-6 / total)
    assert read("swa.held_pair_share") == pytest.approx(
        100 * (16384 + 8192) / 2 / (65536 * 4))


def test_the_four_readers_of_the_routers_the_update_and_the_blocks():
    """What the cell's expert layers, its AdamW update and its windowed
    cores' blocks leave in the same trace: the scopes and counters other
    cells' entries read elsewhere, under this cell's names."""
    summary = summary_of({"layer_scope_s": LAYER_S},
                         _decoder_scopes.reduce(TRACE, FENCES))
    run = run_facts()
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    assert read("swa.route_share") == pytest.approx(100 * 5e-6 / 80e-6)
    # every token at the same 8 of 256 at one fence, half of them at the
    # next; the fence outside the window is not read
    assert read("swa.load_max_over_mean") == pytest.approx((32 + 16) / 2)
    assert read("swa.update_share") == pytest.approx(100 * 20e-6 / 80e-6)
    assert read("swa.block_share") == pytest.approx(22.79)


@pytest.mark.parametrize("name", NEW)
def test_absent_scope_or_counter_reads_none(name):
    """A program without the scope or the counter (the parent), a cell
    without such parts (another configuration's run), no trace at all:
    None, and nothing raised."""
    reader = load_by_name("metrics", name)
    run = run_facts()
    empty = {"scope_s": dict.fromkeys(_decoder_scopes.SCOPES, 0.0),
             "fences": []}
    assert reader.read(summary_of({"layer_scope_s": {}}, empty, update=0),
                       run) is None
    assert reader.read(None, run) is None
    assert reader.read(summary_of(None, None, update=0), run) is None
    if name not in NO_PARTS:
        # another cell's trace has A.core in layers this file does not name
        other = summary_of({"layer_scope_s": {"attn9/A.core": 7e-6}}, empty)
        assert reader.read(other, dict(run, decoder_parts=[])) is None
        assert reader.read(summary_of({"layer_scope_s": LAYER_S}, empty),
                           dict(run, decoder_parts=[])) is None


def test_the_readers_are_declared_together_and_for_the_new_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:] == NEW and at > names.index("moe.wide_held_pair_share")
    mine = bench["per_layer"][at:]
    assert all(m["workloads"] == [CELL] and m["moves"] == "images_per_s"
               for m in mine)
    assert [m["unit"] for m in mine] == ["%"] * 6 + ["x", "%", "%"]
    assert [m["better"] for m in mine] == [
        "higher", "higher", "lower", "lower", "higher"] + ["lower"] * 4
    assert [m["source"] for m in mine] == ["device_trace"] * 4 + [
        "program_counter", "device_trace", "program_counter", "device_trace",
        "program_counter"]
    assert [m["layer"] for m in mine] == ["kernels"] * 2 + [
        "model step"] * 5 + ["solver", "kernels"]
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "laguna-xs2-l5-v8-bf16",
        "traffic": "lm-window-solo", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert bench["configs"][-1]["reduced"] == CONFIG["reduced"]
    assert bench["configs"][-1]["source"] == CONFIG["source"]
    assert all(len(e["why"]) <= 200 for e in
               (bench["workloads"][-1], bench["configs"][-1]))
    # the traffic's parameters are the decoder job's
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "lm-window-solo.json")) as f:
        traffic = json.load(f)
    assert traffic["job"] == "lm_decoder_solo"
    assert traffic["steps_per_fence"] == 8 and "settle_schedule" not in traffic


def test_the_rehearsal_preset_builds_the_cells_net_at_a_tiny_size():
    """``rehearse_preset`` laid over the file (``jobs/lm_decoder_solo.py
    sized``) builds through the same builder: five blocks, both kinds of
    mixer, the check's leaves and readings all present.  The walk itself
    is ``test_rehearse.py``'s case for this cell."""
    from benchmarks.harness import window_check

    job = load_by_name("jobs", "lm_decoder_solo")
    tiny = {**CONFIG, **CONFIG["rehearse_preset"]}
    net = job.zoo_net(tiny)
    kinds = [l.get_str("type") for l in net.get_all("layer")]
    assert kinds.count("GatedAttention") == 5 and kinds.count("MoE") == 4
    rcfg = window_check.reference_config(tiny)
    assert rcfg["kinds"] == ("full_attention",) + ("sliding_attention",) * 3 + (
        "full_attention",)
    assert rcfg["heads"] == (4, 8, 8, 8, 4) and rcfg["window"] == 8
    assert dict(rcfg["mixed_readings"]) == {"full": "attn0", "window": "attn1"}
    assert set(window_check.leaves(tiny)) == {
        k.split(".")[1] for k in window_check.TOL if k.startswith("update")}
    assert set(window_check.TOL) == set(window_check.TOL_REHEARSE)
