"""The hybrid decoder cell's five readers on a hand-made trace in the
neutral form, and what they return where the program carries no such
scope (the parent of PR 32, another cell)."""

import json
import os

import pytest

from benchmarks.harness import hybrid_flops, load_by_name
from benchmarks.metrics import _hybrid_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "phi4-mini-flash-l6-v8-bf16.json")) as f:
    CONFIG = json.load(f)
US = 1000
NEW = ["ssm.scan_roofline", "ssm.scan_share", "ssm.mix_share",
       "attn.window_roofline", "attn.full_roofline"]
# chip 0: [start_ns, dur_ns, name, scope]
TRACE = {
    "window": [0, 100 * US],
    "chips": {"0": [
        [0, 4 * US, "fusion.1", "jit(step)/L.mamba0/dot"],
        [4 * US, 10 * US, "while.1", "jit(step)/L.mamba0/R.scan/while"],
        [14 * US, 20 * US, "while.2",
         "jit(step)/transpose(jvp(L.mamba0))/R.scan/R.scan/while"],
        [34 * US, 6 * US, "fusion.2", "jit(step)/transpose(jvp(L.mamba0))/dot"],
        [40 * US, 5 * US, "while.3", "jit(step)/L.mamba16/R.scan/while"],
        [45 * US, 2 * US, "splash.1", "jit(step)/L.attn1/A.core/splash_mqa_fwd"],
        [47 * US, 4 * US, "splash.2",
         "jit(step)/transpose(jvp(L.attn1))/A.core/splash_mqa_dkv"],
        [51 * US, 3 * US, "splash.3", "jit(step)/L.attn17/A.core/splash_mqa_fwd"],
        [54 * US, 5 * US, "splash.4",
         "jit(step)/transpose(jvp(L.xattn19))/A.core/splash_mqa_dkv"],
        [59 * US, 1 * US, "fusion.3", "jit(step)/L.gmu18/R.gate/mul"],
        [60 * US, 2 * US, "fusion.4", "jit(step)/L.gmu18/dot"],
        [62 * US, 18 * US, "fusion.5", "jit(step)/S.update/mul"],
        [200 * US, 9 * US, "while.9", "jit(step)/L.mamba0/R.scan/x"],  # outside
    ]},
    "host": [],
}
SCOPE_S = {"mamba0/R.scan": 30e-6, "mamba16/R.scan": 5e-6,
           "attn1/A.core": 6e-6, "attn17/A.core": 3e-6,
           "xattn19/A.core": 5e-6, "gmu18/R.gate": 1e-6}


def summary_of(scopes):
    fwd = {"mamba0": 14e-6, "mamba16": 5e-6, "attn1": 2e-6, "attn17": 3e-6,
           "gmu18": 3e-6}
    bwd = {"mamba0": 26e-6, "attn1": 4e-6, "xattn19": 5e-6}
    return {"window_s": 100e-6, "hybrid_scopes": scopes, "chips": {"0": {
        "busy_s": 80e-6, "layer_fwd_s": fwd, "layer_bwd_s": bwd,
        "unscoped_s": {"S.update": 18e-6}}}}


def run_facts():
    return {"decoder_parts": hybrid_flops.parts(CONFIG, 1, 2048),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "steps_traced": 2}


def test_reduce_books_self_time_by_layer_and_inner_scope_in_the_window():
    got = _hybrid_scopes.reduce(TRACE)["layer_scope_s"]
    assert got == pytest.approx(SCOPE_S)


def test_the_five_readers_on_the_hand_made_trace():
    summary = summary_of({"layer_scope_s": SCOPE_S})
    run = run_facts()
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    total = 80e-6  # fwd + bwd + unscoped
    assert read("ssm.scan_share") == pytest.approx(100 * 35e-6 / total)
    # the Mamba layers hold 45 us, 35 of them under R.scan
    assert read("ssm.mix_share") == pytest.approx(100 * 10e-6 / total)
    scan_floor = 2 * 3 * 2 * 2048 * (3 * 5120 + 2 * 16 + 5120 * 16 / 2048
                                     + 5120 / 2048) / 819e9
    assert read("ssm.scan_roofline") == pytest.approx(
        100 * scan_floor * 2 / 35e-6, rel=1e-6)
    window_floor = 6 * 3_441_600 * 2048 / 197e12
    assert read("attn.window_roofline") == pytest.approx(
        100 * window_floor * 2 / 6e-6, rel=1e-6)
    full_floor = 2 * 6 * 7_868_160 * 2048 / 197e12
    assert read("attn.full_roofline") == pytest.approx(
        100 * full_floor * 2 / 8e-6, rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_absent_scope_reads_none(name):
    """A program without the scopes (the parent), a cell without such
    parts (another configuration's run), no trace at all."""
    reader = load_by_name("metrics", name)
    run = run_facts()
    assert reader.read(summary_of({"layer_scope_s": {}}), run) is None
    assert reader.read(None, run) is None
    other = summary_of({"layer_scope_s": {"attn2/A.core": 7e-6}})
    bare = dict(run, decoder_parts=[])
    if name != "ssm.scan_share":
        assert reader.read(other, bare) is None
    assert reader.read(summary_of(None), run) is None


def test_the_readers_are_declared_together_and_for_the_new_cell_only():
    """Five entries in a row, in the issue's order, after every entry PR 31
    had (a later PR appends after them: this does not ask to be last)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 5] == NEW and at > names.index("moe.bias_load_max_over_mean")
    mine = bench["per_layer"][at:at + 5]
    assert all(m["workloads"] == ["phi4flash-solo-s2048"]
               and m["moves"] == "images_per_s" and m["unit"] == "%"
               and m["source"] == "device_trace" for m in mine)
    assert [m["better"] for m in mine] == ["higher", "lower", "lower",
                                           "higher", "higher"]
