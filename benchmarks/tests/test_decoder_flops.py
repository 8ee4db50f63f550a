"""harness/decoder_flops.py against counts worked out by hand (ISSUE 30's
per-token figures), the row format the CNN cells' readers take, the
latent-attention core's operations and bytes, and the configuration's
file against the catalog's published keys."""

import json
import os

import pytest

from benchmarks.harness import decoder_flops, flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "joyai-llm-flash-l5-ep32-bf16"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
MLA = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048


@pytest.mark.parametrize("part,mflop,shown", [
    ("proj", 6 * 2 * MLA / 1e6, 316.1),                    # 6 blocks x 52.69
    ("core", 6 * 2 * 32 * (192 + 128) * 2048 / 1e6, 251.7),  # 6 x 41.94
    ("mlp", 2 * 3 * 2048 * 7168 / 1e6, 88.1),              # the dense layer
    ("shared", 5 * 2 * 3 * 2048 * 768 / 1e6, 47.2),        # 5 x 9.44
    ("router", 5 * 2 * 2048 * 256 / 1e6, 5.2),             # 5 x 1.05
    ("experts", 5 * 2 * 8 * (8 / 256) * 3 * 2048 * 768 / 1e6, 11.8),  # 5 x 2.36
    ("mtp_proj", 2 * 4096 * 2048 / 1e6, 16.8),
    ("head", (2 * 4096 - 1) / 4096 * 2 * 2048 * 16160 / 1e6, 132.4),
])
def test_forward_mflop_per_token(part, mflop, shown):
    got = decoder_flops.forward_mflop_per_token(CONFIG, 4096)
    assert got[part] == pytest.approx(mflop, rel=1e-9)
    assert round(got[part], 1) == shown


def test_step_operations_and_rows_for_the_cnn_readers():
    parts = decoder_flops.parts(CONFIG, 1, 4096)
    rows = decoder_flops.layer_rows(parts)
    assert [r["name"] for r in rows] == [
        "attn1", "mlp1", "attn2", "moe2", "attn3", "moe3", "attn4", "moe4",
        "attn5", "moe5", "lm_head", "mtp_proj", "mtp_attn", "mtp_moe",
        "mtp_head"]
    assert all(set(r) == {"name", "kind", "macs", "in_elems", "out_elems",
                          "weight_elems", "from_data"} for r in rows)
    per_token = sum(decoder_flops.forward_mflop_per_token(CONFIG, 4096).values())
    assert per_token == pytest.approx(869.25, abs=0.01)
    assert flops.step_flops(rows) == 3 * sum(2 * r["macs"] for r in parts)
    assert flops.step_flops(rows) / 1e12 == pytest.approx(10.681, abs=0.001)
    assert CONFIG["totals"]["step_tflop"] == 10.681
    # MLA 65 %, heads 15 %, dense + shared MLPs 16 %, routed experts 1.4 %
    share = {k: v / per_token for k, v in
             decoder_flops.forward_mflop_per_token(CONFIG, 4096).items()}
    assert share["proj"] + share["core"] == pytest.approx(0.653, abs=0.001)
    assert share["head"] == pytest.approx(0.152, abs=0.001)
    assert share["experts"] == pytest.approx(0.0136, abs=0.0002)


def test_the_core_counts_keys_of_192_and_values_of_128():
    row = decoder_flops.mla_core_row("c", 1, 4096, 32, 192, 128)
    # S^2/2 (query, key) pairs a head, 192 MACs in QK^T and 128 in PV
    assert row["macs"] == (4096 * 4096 // 2) * 32 * (192 + 128)
    assert row["in_elems"] == 4096 * 32 * (192 + 192 + 128)
    assert row["out_elems"] == 4096 * 32 * 128 and row["weight_elems"] == 0
    t, bound = flops.layer_floor_s(row, 197e12, 819e9)
    # 515 GFLOP over three passes against 0.50 GB: compute, 2.62 ms
    assert bound == "compute" and t == pytest.approx(2.616e-3, rel=1e-3)
    by_name = {r["name"]: r for r in decoder_flops.parts(CONFIG, 1, 4096)}
    assert by_name["attn3.core"] == dict(row, name="attn3.core")
    assert by_name["mtp_attn.core"]["kind"] == "mla_core"
    # equal widths give lm_flops' count: 2 * d MACs a pair over all heads
    same = decoder_flops.mla_core_row("c", 4, 4096, 16, 128, 128)
    assert same["macs"] == 4 * (4096 * 4096 // 2) * 2 * 2048


def test_floors_and_what_bounds_them():
    by_name = {r["name"]: r for r in decoder_flops.parts(CONFIG, 1, 4096)}
    peak, bw = 197e12, 819e9
    assert by_name["attn1.proj"]["weight_elems"] == MLA == 26_345_472
    t, bound = flops.layer_floor_s(by_name["attn1.proj"], peak, bw)
    assert bound == "compute" and t == pytest.approx(3.287e-3, rel=1e-3)
    # 1,024 balanced pairs on 8 experts: the weights' bytes, not the work
    assert by_name["moe2.experts"]["macs"] == 1024 * 3 * 2048 * 768
    assert flops.layer_floor_s(by_name["moe2.experts"], peak, bw)[1] == "memory"
    assert flops.layer_floor_s(by_name["moe2.router"], peak, bw)[1] == "memory"
    assert by_name["mtp_head"]["macs"] == 4095 * 2048 * 16160


def test_parameter_totals_in_the_configuration_file():
    d, v, t = 2048, 16160, CONFIG["totals"]
    attn = MLA + 1536 + 512
    expert_layer = attn + 2 * d + 256 * d + 8 * 3 * 768 * d + 3 * 768 * d
    assert t["parameters_dense_layer"] == attn + 2 * d + 3 * 7168 * d == 70_391_808
    assert t["parameters_expert_layer"] == expert_layer == 69_343_232
    assert t["parameters_mtp_module"] == expert_layer + 2 * d * d + 3 * d
    assert t["parameters_embedding_and_head"] == 2 * v * d
    assert CONFIG["parameters"] == t["parameters"] == (
        t["parameters_dense_layer"] + 4 * expert_layer
        + t["parameters_mtp_module"] + 2 * v * d + d) == 491_696_128
    assert t["state_bytes"] == 16 * 491_696_128


def test_the_file_holds_every_published_key_and_lists_its_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == {"num_hidden_layers", "n_routed_experts"}
    assert changed | {"vocab_rows", "train_tokens"} == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["n_routed_experts_published"], CONFIG["vocab_rows"]) == (
        5, 8, 256, 129280 // 8)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]


def test_the_committed_prototxt_is_the_zoo_net_of_the_file():
    from benchmarks.harness import load_by_name
    from sparknet_tpu.proto.text_format import serialize

    job = load_by_name("jobs", "lm_decoder_solo")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".train.prototxt")) as f:
        text = f.read()
    body = "".join(l for l in text.splitlines(True) if not l.startswith("#"))
    assert body == serialize(job.zoo_net(CONFIG))
    kwargs = job.zoo_kwargs(CONFIG)
    assert (kwargs["experts"], kwargs["experts_held"], kwargs["layers"],
            kwargs["vocab"], kwargs["shared_dim"]) == (256, 8, 5, 16160, 768)
