"""harness/window_flops.py against counts worked out by hand (ISSUE 50's
per-token figures), the row format the CNN cells' readers take, the two
kinds of core's operations and bytes, and the configuration's file
against the catalog's published keys."""

import json
import os

import pytest

from benchmarks.harness import flops, window_flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "laguna-xs2-l5-v8-bf16"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
S = 8192
FULL = 2 * 2048 * 6144 + 2 * 2048 * 1024
SLIDE = 2 * 2048 * 8192 + 2 * 2048 * 1024
WINDOW_KEYS = (512 * 513 // 2 + (S - 512) * 512) / S  # 496.03 a query


@pytest.mark.parametrize("part,macs,shown", [
    ("attention_projections", 2 * FULL + 3 * SLIDE, 343.9),   # 43.6 %
    ("gates", 2 * 2048 * 48 + 3 * 2048 * 64, 1.2),
    ("full_cores", 2 * 48 * 256 * (S + 1) / 2, 201.4),        # 25.5 %
    ("window_cores", 3 * 64 * 256 * WINDOW_KEYS, 48.8),       # 6.2 %
    ("dense_mlp", 3 * 2048 * 8192, 100.7),                    # 12.8 %
    ("routers", 4 * 2048 * 256, 4.2),
    ("shared_experts", 4 * 3 * 2048 * 512, 25.2),
    ("held_experts_balanced", 4 * 0.5 * 3 * 2048 * 512, 12.6),
    ("head", 2048 * 12544, 51.4),                             # 6.5 %
])
def test_forward_mflop_per_token(part, macs, shown):
    got = window_flops.forward_mflop_per_token(CONFIG, S)
    assert got[part] == pytest.approx(2 * macs / 1e6, rel=1e-9)
    assert round(got[part], 1) == shown
    assert CONFIG["totals"]["forward_mflop_per_token"][part] == round(got[part], 2)


def test_the_flop_rows_sum_to_the_files_totals():
    parts = window_flops.parts(CONFIG, 1, S)
    rows = window_flops.layer_rows(parts)
    assert [r["name"] for r in rows] == [
        "attn0", "mlp0", "attn1", "moe1", "attn2", "moe2", "attn3", "moe3",
        "attn4", "moe4", "lm_head"]
    assert all(set(r) == {"name", "kind", "macs", "in_elems", "out_elems",
                          "weight_elems", "from_data"} for r in rows)
    per_token = window_flops.forward_mflop_per_token(CONFIG, S)
    # 789 MFLOP a token forward, 19.4 TFLOP a step: the issue's
    assert per_token["total"] == pytest.approx(789.2, abs=0.05)
    assert CONFIG["totals"]["forward_mflop_per_token"]["total"] == round(
        per_token["total"], 2)
    assert sum(v for k, v in per_token.items() if k != "total") == \
        pytest.approx(per_token["total"])
    assert flops.step_flops(rows) == 3 * sum(2 * r["macs"] for r in parts)
    assert flops.step_flops(rows) / 1e12 == pytest.approx(19.396, abs=0.001)
    assert CONFIG["totals"]["step_tflop"] == 19.396
    share = {k: v / per_token["total"] for k, v in per_token.items()}
    assert share["attention_projections"] == pytest.approx(0.436, abs=0.001)
    assert share["full_cores"] + share["window_cores"] == pytest.approx(
        0.317, abs=0.001)
    # the kinds of the cores' rows are what the readers pick them by
    assert [r["kind"] for r in parts if r["name"].endswith(".core")] == [
        "full_core", "window_core", "window_core", "window_core", "full_core"]


@pytest.mark.parametrize("seq_len,window,pairs", [
    (8192, 0, 8192 * 8193 // 2), (8192, 512, 4_063_488),
    (8192, 8192, 8192 * 8193 // 2), (4, 2, 1 + 2 + 2 + 2), (4, 1, 4),
    (2048, 512, 512 * 513 // 2 + 1536 * 512)])
def test_the_mask_counts_its_pairs(seq_len, window, pairs):
    assert window_flops.seen_pairs(seq_len, window) == pairs
    assert window_flops.seen_pairs(seq_len, window) == sum(
        min(t + 1, window or seq_len) for t in range(seq_len))


def test_both_cores_are_compute_bound_at_8k_and_the_window_does_an_eighth():
    win = window_flops.core_row("attn1.core", "window_core", 1, S, 64, 8, 128,
                                512)
    full = window_flops.core_row("attn0.core", "full_core", 1, S, 48, 8, 128)
    assert win["macs"] == 4_063_488 * 64 * 256
    assert full["macs"] == (S * (S + 1) // 2) * 48 * 256
    assert win["in_elems"] == S * (64 + 16) * 128 and win["out_elems"] == S * 64 * 128
    assert full["in_elems"] == S * (48 + 16) * 128 and win["weight_elems"] == 0
    t_win, bound_win = flops.layer_floor_s(win, 197e12, 819e9)
    t_full, bound_full = flops.layer_floor_s(full, 197e12, 819e9)
    assert (bound_win, bound_full) == ("compute", "compute")
    assert t_win == pytest.approx(2.028e-3, rel=1e-3)    # the issue's 2.0 ms
    assert t_full == pytest.approx(12.56e-3, rel=1e-3)   # and 12.6 ms
    # a head's work: 1/8 of a full core's at 8,192, 1/4 at 4,096
    assert (win["macs"] / 64) / (full["macs"] / 48) == pytest.approx(
        0.121, abs=0.001)
    assert window_flops.seen_pairs(4096, 512) / window_flops.seen_pairs(
        4096, 0) == pytest.approx(0.234, abs=0.001)
    # the kernel's blocks are no argument of the row
    assert "block" not in window_flops.core_row.__code__.co_varnames


def test_the_configuration_keeps_every_published_number():
    """The catalog's ``config`` for Laguna-XS.2, key for key; only the
    keys under ``reduced`` differ, and the nested groups and the per-layer
    lists are whole."""
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5}
    differ = {k for k, v in published.items() if CONFIG[k] != v}
    assert differ == {"num_experts", "num_hidden_layers"}
    assert set(CONFIG["reduced"]) == differ | {"vocab_rows", "train_tokens"}
    assert CONFIG["layer_types"] == (
        ["full_attention"] + ["sliding_attention"] * 3) * 10
    assert CONFIG["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert CONFIG["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    assert CONFIG["num_experts_published"] == 256
    assert CONFIG["num_hidden_layers_published"] == 40
    assert CONFIG["kept_layers"] == [0, 1, 2, 3, 4]
    assert CONFIG["vocab_rows"] * 8 == CONFIG["vocab_size"]
    assert set(CONFIG["reduced_notes"]) == set(CONFIG["reduced"])
    assert {"head_wise_gate", "router", "auxiliary_loss", "attention",
            "init_std", "optimizer", "lr_policy", "seq_len"} <= set(
                CONFIG["assumed"])
    assert "16 that share each layer" in CONFIG["deployment"]
    assert "8 stages" in CONFIG["deployment"]


def test_the_parameter_count_is_the_builders():
    import jax

    from benchmarks.harness import load_by_name
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    job = load_by_name("jobs", "lm_decoder_solo")
    net = Network(job.zoo_net(CONFIG), Phase.TRAIN)
    shapes = jax.eval_shape(lambda k: net.init(k, None, None).params,
                            jax.random.key(0))
    count = sum(a.size for blobs in shapes.values() for a in blobs)
    totals = CONFIG["totals"]
    assert count == CONFIG["parameters"] == totals["parameters"] == 490_297_344
    assert totals["state_bytes"] == 16 * count
    assert (totals["parameters_dense_layer"]
            + 3 * totals["parameters_sliding_expert_layer"]
            + totals["parameters_full_expert_layer"]
            + totals["parameters_embedding_and_head"]
            + totals["parameters_final_norm"]) == count
    assert (totals["parameters_dense_layer"],
            totals["parameters_sliding_expert_layer"],
            totals["parameters_full_expert_layer"]) == (
                79_794_176, 91_885_568, 83_464_192)
    # the whole model with a head-wise gate is the card's 33.4B
    assert totals["parameters_whole_model"] == 33_442_596_864
    # the largest of 32 / 16 / 8 held whose step program fits 15.0 GB
    aot = totals["aot_step_program_gb"]
    assert aot["experts_held_32"] > 15.0 >= aot["experts_held_16"] >= 4.0
    assert CONFIG["num_experts"] == 16


def test_the_prototxt_is_the_builders_at_the_files_sizes():
    from benchmarks.harness import load_by_name
    from sparknet_tpu.proto.text_format import serialize

    job = load_by_name("jobs", "lm_decoder_solo")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".train.prototxt")) as f:
        text = f.read()
    body = "".join(l for l in text.splitlines(True) if not l.startswith("#"))
    assert body == serialize(job.zoo_net(CONFIG))
    assert body.count("window: 512") == 3 and body.count("rope_scaling {") == 2
    assert body.count("experts_held: 16") == 4
