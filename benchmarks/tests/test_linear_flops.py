"""harness/linear_flops.py against counts worked out by hand (ISSUE 47's
per-token figures), the row format the CNN cells' readers take, the delta
rule's and the gated core's operations and bytes, and the configuration's
file against the catalog's published keys."""

import json
import os

import pytest

from benchmarks.harness import flops, linear_flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "qwen3-next-80b-a3b-l4-ep16-v8-bf16"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
S = 4096
GDN = 2048 * 12288 + 2048 * 64 + 4096 * 2048
ATTN = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048


@pytest.mark.parametrize("part,macs,shown", [
    ("deltanet_projections", 3 * GDN, 202.1),           # 47 % of the work
    ("delta_rule", 3 * 32 * 128 * 128 * 3.5, 11.0),      # 2.6 %
    ("attention_projections", ATTN, 54.5),
    ("attention_core", 16 * 512 * (S + 1) / 2, 33.6),
    ("routers", 4 * 2048 * 512, 8.4),
    ("shared_experts", 4 * (3 * 2048 * 512 + 2048), 25.2),
    ("held_experts_balanced", 4 * 0.625 * 3 * 2048 * 512, 15.7),
    ("head", 2048 * 18992, 77.8),                        # 18 %
])
def test_forward_mflop_per_token(part, macs, shown):
    got = linear_flops.forward_mflop_per_token(CONFIG, S)
    assert got[part] == pytest.approx(2 * macs / 1e6, rel=1e-9)
    assert round(got[part], 1) == shown
    assert CONFIG["totals"]["forward_mflop_per_token"][part] == round(got[part], 2)


def test_step_operations_and_rows_for_the_cnn_readers():
    parts = linear_flops.parts(CONFIG, 1, S)
    rows = linear_flops.layer_rows(parts)
    assert [r["name"] for r in rows] == [
        "gdn0", "moe0", "gdn1", "moe1", "gdn2", "moe2", "attn3", "moe3",
        "lm_head"]
    assert all(set(r) == {"name", "kind", "macs", "in_elems", "out_elems",
                          "weight_elems", "from_data"} for r in rows)
    per_token = linear_flops.forward_mflop_per_token(CONFIG, S)
    # 428 MFLOP a token forward: the issue's 430
    assert per_token["total"] == pytest.approx(428.3, abs=0.05)
    assert CONFIG["totals"]["forward_mflop_per_token"]["total"] == round(
        per_token["total"], 2)
    # the delta rule IS in its layer's row (module docstring)
    assert flops.step_flops(rows) == 3 * sum(2 * r["macs"] for r in parts)
    assert flops.step_flops(rows) / 1e12 == pytest.approx(5.263, abs=0.001)
    assert CONFIG["totals"]["step_tflop"] == 5.263
    share = {k: v / per_token["total"] for k, v in per_token.items()}
    assert share["deltanet_projections"] == pytest.approx(0.472, abs=0.001)
    assert share["head"] == pytest.approx(0.182, abs=0.001)


def test_the_delta_rule_counts_the_recurrence_and_is_bandwidth_bound():
    row = linear_flops.delta_core_row("gdn0.core", 1, S, 16, 32, 128, 128)
    assert 2 * row["macs"] == 7 * 128 * 128 * 32 * S       # 15.0 GFLOP
    assert row["in_elems"] == S * (2 * 2048 + 4096 + 4 * 32)
    assert row["out_elems"] == S * 4096 and row["weight_elems"] == 0
    t, bound = flops.layer_floor_s(row, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(0.3726e-3, rel=1e-3)
    # whatever the chunk: the row takes none
    assert "chunk" not in linear_flops.delta_core_row.__code__.co_varnames


def test_the_gated_core_is_compute_bound_at_4k():
    row = linear_flops.gated_core_row("attn3.core", 1, S, 16, 2, 256)
    assert row["macs"] == (S * (S + 1) // 2) * 16 * 512
    assert row["in_elems"] == S * (16 + 4) * 256
    t, bound = flops.layer_floor_s(row, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(2.093e-3, rel=1e-3)


def test_the_configuration_keeps_every_published_number():
    """The catalog's ``config`` for Qwen3-Next-80B-A3B-Instruct, key for
    key; only the keys under ``reduced`` differ."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    differ = {k for k, v in published.items() if CONFIG[k] != v}
    assert differ == {"num_experts", "num_hidden_layers"}
    assert set(CONFIG["reduced"]) == differ | {"vocab_rows", "train_tokens"}
    assert CONFIG["num_experts_published"] == 512
    assert CONFIG["num_hidden_layers_published"] == 48
    assert CONFIG["vocab_rows"] * 8 == CONFIG["vocab_size"]
    assert set(CONFIG["reduced_notes"]) == set(CONFIG["reduced"])


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
    assert count == CONFIG["parameters"] == 625_667_136
    assert CONFIG["totals"]["state_bytes"] == 16 * count
