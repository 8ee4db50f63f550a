"""The latent-attention decoder cell's six readers on a hand-made trace in
the neutral form, and what they return where the program carries no such
scope or counter."""

import pytest

from benchmarks.harness import decoder_flops, load_by_name
from benchmarks.metrics import _decoder_scopes

US = 1000
NEW = ["mla.core_roofline", "mla.latent_share", "moe.route_share",
       "mtp.share", "moe.held_pair_share", "moe.bias_load_max_over_mean"]
# chip 0: [start_ns, dur_ns, name, scope]
TRACE = {
    "window": [0, 100 * US],
    "chips": {"0": [
        [0, 10 * US, "splash.1", "jit(step)/L.attn2/A.core/splash_mha_fwd"],
        [10 * US, 8 * US, "fusion.2", "jit(step)/L.attn2/A.latent/dot"],
        [18 * US, 2 * US, "fusion.3",
         "jit(step)/transpose(jvp(L.attn2))/A.latent/dot"],
        [20 * US, 6 * US, "splash.2",
         "jit(step)/transpose(jvp(L.mtp_attn))/A.core/splash_mha_dkv"],
        [30 * US, 4 * US, "sort.1", "jit(step)/L.moe2/M.dispatch/sort"],
        [34 * US, 3 * US, "fusion.4", "jit(step)/L.moe2/M.route/dot"],
        [37 * US, 3 * US, "fusion.5", "jit(step)/L.mtp_moe/M.combine/gather"],
        [40 * US, 5 * US, "gmm.1", "jit(step)/L.moe2/M.experts/gmm"],
        [45 * US, 5 * US, "fusion.6", "jit(step)/L.moe2/M.shared/dot"],
        [50 * US, 9 * US, "fusion.7", "jit(step)/L.mtp_head/dot"],
        [60 * US, 20 * US, "fusion.8", "jit(step)/S.update/mul"],
        [200 * US, 10 * US, "splash.3", "jit(step)/L.attn2/A.core/x"],  # outside
    ]},
    "host": [],
}
FENCES = [
    {"start_ns": 50 * US, "stats": {
        "it": 8, "moe_load_max": 300, "moe_pairs": 32768, "moe_experts": 256,
        "moe_layers": 5, "moe_pairs_held": 5000, "moe_bias_min": -0.008,
        "moe_bias_max": 0.008, "mtp_loss": 9.1}},
    {"start_ns": 90 * US, "stats": {
        "it": 16, "moe_load_max": 500, "moe_pairs": 32768, "moe_experts": 256,
        "moe_layers": 5, "moe_pairs_held": 5240, "moe_bias_min": -0.016,
        "moe_bias_max": 0.016, "mtp_loss": 8.7}},
    {"start_ns": 300 * US, "stats": {  # outside the window
        "it": 24, "moe_load_max": 900, "moe_pairs": 32768, "moe_experts": 256,
        "moe_layers": 5, "moe_pairs_held": 9000, "moe_bias_max": 0.02}},
    # OLMoE's fence: a whole layer, no bias
    {"start_ns": 95 * US, "stats": {"it": 16, "moe_load_max": 5000,
                                    "moe_pairs": 131072, "moe_experts": 64}},
]


def summary_of(scopes):
    fwd = {"attn2": 18e-6, "moe2": 17e-6, "mtp_moe": 3e-6, "mtp_head": 9e-6}
    bwd = {"attn2": 2e-6, "mtp_attn": 6e-6}
    return {"window_s": 100e-6, "decoder_scopes": scopes, "chips": {"0": {
        "busy_s": 75e-6, "layer_fwd_s": fwd, "layer_bwd_s": bwd,
        "unscoped_s": {"S.update": 20e-6}}}}


def test_reduce_books_self_time_by_inner_scope_inside_the_window():
    ds = _decoder_scopes.reduce(TRACE, FENCES)
    assert ds["scope_s"] == pytest.approx({
        "A.core": 16e-6, "A.latent": 10e-6, "M.dispatch": 4e-6,
        "M.route": 3e-6, "M.combine": 3e-6, "M.experts": 5e-6,
        "M.shared": 5e-6})
    assert [s["moe_pairs_held"] for s in ds["fences"]] == [5000, 5240]


def test_readers():
    summary = summary_of(_decoder_scopes.reduce(TRACE, FENCES))
    config = {"hidden_size": 2048, "num_attention_heads": 32,
              "q_lora_rank": 1536, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "intermediate_size": 7168,
              "first_k_dense_replace": 1, "n_routed_experts": 8,
              "n_routed_experts_published": 256, "num_experts_per_tok": 8,
              "moe_intermediate_size": 768, "n_shared_experts": 1,
              "num_hidden_layers": 5, "num_nextn_predict_layers": 1,
              "vocab_rows": 16160}
    run = {"decoder_parts": decoder_flops.parts(config, 1, 4096),
           "steps_traced": 1,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: load_by_name("metrics", name).read(summary, run)
    # six cores of floor 2.616 ms against the 16 us of the toy trace
    assert read("mla.core_roofline") == pytest.approx(
        100 * 6 * 2.6161e-3 / 16e-6, rel=1e-3)
    assert read("mla.latent_share") == pytest.approx(100 * 10 / 75)
    assert read("moe.route_share") == pytest.approx(100 * 10 / 75)
    assert read("mtp.share") == pytest.approx(100 * (3 + 9 + 6) / 75)
    assert read("moe.held_pair_share") == pytest.approx(
        100 * (5000 + 5240) / 2 / (5 * 32768))
    assert read("moe.bias_load_max_over_mean") == pytest.approx(
        (300 + 500) / 2 * 256 / 32768)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_reads_none(name):
    """The parent of PR 30, or another cell: nothing to read, no raise."""
    empty = {"scope_s": dict.fromkeys(_decoder_scopes.SCOPES, 0.0),
             "fences": []}
    summary = summary_of(empty)
    summary["chips"]["0"].update(
        layer_fwd_s={"conv1": 30e-6}, layer_bwd_s={"conv1": 25e-6})
    run = {"decoder_parts": [], "steps_traced": 1,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    reader = load_by_name("metrics", name)
    assert reader.read(summary, run) is None
    assert reader.read(None, run) is None


def test_olmoes_fence_is_not_read_as_a_biased_router():
    ds = _decoder_scopes.reduce(TRACE, FENCES[3:])
    assert ds["fences"] == []
    summary = summary_of(ds)
    for name in ("moe.held_pair_share", "moe.bias_load_max_over_mean"):
        assert load_by_name("metrics", name).read(summary, {}) is None


def test_benchmark_json_lists_the_six_readers_for_the_new_cell_only():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-6:]] == NEW
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == ["joyai-solo-s4096"]
        assert m["moves"] == "images_per_s"
        assert os.path.exists(os.path.join(root, "benchmarks", "metrics",
                                           m["name"] + ".py"))
    assert bench["workloads"][-1] == {
        "name": "joyai-solo-s4096", "config": "joyai-llm-flash-l5-ep32-bf16",
        "traffic": "lm-decoder-solo", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
