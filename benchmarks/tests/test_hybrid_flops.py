"""harness/hybrid_flops.py against counts worked out by hand (ISSUE 32's
per-token figures), the row format the CNN cells' readers take, the scan's
and the cores' operations and bytes, and the configuration's file against
the catalog's published keys."""

import json
import os

import pytest

from benchmarks.harness import flops, hybrid_flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "phi4-mini-flash-l6-v8-bf16"
with open(os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
S = 2048
MAMBA = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
WINDOW_KEYS = (512 * 513 // 2 + 1536 * 512) / S   # mean keys a query: 448.125
FULL_KEYS = (S + 1) / 2                           # 1024.5


@pytest.mark.parametrize("part,macs,shown", [
    ("mlps", 6 * 3 * 2560 * 10240, 943.7),              # 66 % of the work
    ("mamba_projections", 2 * MAMBA, 164.5),             # 11.5 %
    ("attention_projections", 2 * (2560 * 5120 + 2560 * 2560), 78.6),
    ("cross_attention_projections", 2 * 2560 * 2560, 26.2),
    ("gmu", 2 * 2560 * 5120, 52.4),
    ("head", 2560 * 25008, 128.0),                       # 8.9 %
    ("attention_window_core", 40 * 192 * WINDOW_KEYS, 6.9),
    ("attention_full_core", 40 * 192 * FULL_KEYS, 15.7),
    ("cross_attention_full_core", 40 * 192 * FULL_KEYS, 15.7),
    ("mamba_scan_vector_unit", 2 * 5120 * 16 * 3, 1.0),
])
def test_forward_mflop_per_token(part, macs, shown):
    got = hybrid_flops.forward_mflop_per_token(CONFIG, S)
    assert got[part] == pytest.approx(2 * macs / 1e6, rel=1e-9)
    assert round(got[part], 1) == shown
    assert CONFIG["totals"]["forward_mflop_per_token"][part] == round(got[part], 2)


def test_step_operations_and_rows_for_the_cnn_readers():
    parts = hybrid_flops.parts(CONFIG, 1, S)
    rows = hybrid_flops.layer_rows(parts)
    assert [r["name"] for r in rows] == [
        "mamba0", "mlp0", "attn1", "mlp1", "mamba16", "mlp16", "attn17",
        "mlp17", "gmu18", "mlp18", "xattn19", "mlp19", "lm_head"]
    assert all(set(r) == {"name", "kind", "macs", "in_elems", "out_elems",
                          "weight_elems", "from_data"} for r in rows)
    per_token = hybrid_flops.forward_mflop_per_token(CONFIG, S)
    # 715.9 M multiply-adds a token forward: the issue's 716 M
    assert per_token["total"] == pytest.approx(2 * 715_948_480 / 1e6, rel=1e-9)
    assert CONFIG["totals"]["forward_mflop_per_token"]["total"] == 1431.9
    # the scan's vector-unit arithmetic is in no layer row
    matmul = [r for r in parts if r["kind"] != "scan"]
    assert flops.step_flops(rows) == 3 * sum(2 * r["macs"] for r in matmul)
    assert flops.step_flops(rows) / 1e12 == pytest.approx(8.798, abs=0.001)
    assert CONFIG["totals"]["step_tflop"] == 8.798
    share = {k: v / per_token["total"] for k, v in per_token.items()}
    assert share["mlps"] == pytest.approx(0.659, abs=0.001)
    assert share["mamba_projections"] == pytest.approx(0.115, abs=0.001)
    assert (share["attention_projections"] + share["gmu"]
            + share["cross_attention_projections"]) == pytest.approx(0.110, abs=0.001)
    assert share["head"] == pytest.approx(0.089, abs=0.001)
    cores = (share["attention_window_core"] + share["attention_full_core"]
             + share["cross_attention_full_core"])
    assert cores == pytest.approx(0.027, abs=0.001)


def test_the_scan_row_counts_its_inputs_and_output_once_and_no_state():
    row = hybrid_flops.scan_row("s", 1, S, 5120, 16)
    assert row["macs"] == S * 5120 * 16 * 3
    # c and Δ at 5120, B and C at 16 in; y out; A and D once; never h
    assert row["in_elems"] == S * (2 * 5120 + 2 * 16)
    assert row["out_elems"] == S * 5120
    assert row["weight_elems"] == 5120 * 16 + 5120
    t, bound = flops.layer_floor_s(row, 197e12, 819e9)
    # 63.2 MB a pass, three passes: 0.232 ms against 7.7 us of arithmetic
    assert bound == "memory" and t == pytest.approx(2.316e-4, rel=1e-3)
    by_name = {r["name"]: r for r in hybrid_flops.parts(CONFIG, 1, S)}
    assert by_name["mamba16.scan"] == dict(row, name="mamba16.scan")
    assert [r["name"] for r in by_name.values() if r["kind"] == "scan"] == [
        "mamba0.scan", "mamba16.scan"]


def test_the_cores_count_the_band_and_the_triangle():
    s = hybrid_flops.sizes(CONFIG)
    window = hybrid_flops.window_core_row("w", 1, S, s)
    full = hybrid_flops.full_core_row("f", 1, S, s)
    pairs_w = 512 * 513 // 2 + (S - 512) * 512
    assert window["macs"] == pairs_w * 40 * (64 + 128) == 3_441_600 * S
    assert full["macs"] == (S * (S + 1) // 2) * 40 * 192 == 7_868_160 * S
    for row in (window, full):
        assert row["in_elems"] == S * (40 + 20 + 20) * 64
        assert row["out_elems"] == S * 40 * 128 and row["weight_elems"] == 0
    # a window no shorter than the sequence is the triangle
    assert hybrid_flops.window_core_row("w", 1, 256, s)["macs"] == \
        hybrid_flops.full_core_row("f", 1, 256, s)["macs"]
    t_w, bound_w = flops.layer_floor_s(window, 197e12, 819e9)
    t_f, bound_f = flops.layer_floor_s(full, 197e12, 819e9)
    assert (bound_w, bound_f) == ("compute", "compute")
    assert t_w == pytest.approx(2.147e-4, rel=1e-3)
    assert t_f == pytest.approx(4.908e-4, rel=1e-3)
    by_name = {r["name"]: r for r in hybrid_flops.parts(CONFIG, 1, S)}
    assert by_name["attn1.core"]["kind"] == "window_core"
    assert by_name["attn17.core"]["kind"] == "full_core"
    assert by_name["xattn19.core"] == dict(full, name="xattn19.core")


def test_floors_and_what_bounds_them():
    by_name = {r["name"]: r for r in hybrid_flops.parts(CONFIG, 1, S)}
    peak, bw = 197e12, 819e9
    assert by_name["mamba0.proj"]["weight_elems"] == MAMBA == 41_123_840
    assert by_name["attn1.proj"]["weight_elems"] == 19_660_800
    assert by_name["xattn19.proj"]["weight_elems"] == 13_107_200
    assert by_name["gmu18"]["weight_elems"] == 26_214_400
    assert by_name["lm_head"]["macs"] == S * 2560 * 25008
    for name in ("mamba0.proj", "mlp0", "attn1.proj", "gmu18", "lm_head"):
        assert flops.layer_floor_s(by_name[name], peak, bw)[1] == "compute"
    t, _ = flops.layer_floor_s(by_name["mlp17"], peak, bw)
    assert t == pytest.approx(6 * S * 78_643_200 / peak, rel=1e-9)


def test_parameter_totals_in_the_configuration_file():
    t = CONFIG["totals"]
    mlp, norms = 3 * 2560 * 10240, 4 * 2560
    mamba = MAMBA + 5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120
    attn = 2560 * 5120 + 2560 * 2560 + 4 * 64 + 128
    assert mamba == 41_241_600 and attn == 19_661_184
    assert t["parameters_mamba_layer"] == mamba + mlp + norms == 119_895_040
    assert t["parameters_attention_layer"] == attn + mlp + norms == 98_314_624
    assert t["parameters_gmu_layer"] == 26_214_400 + mlp + norms
    assert t["parameters_cross_attention_layer"] == 13_107_584 + mlp + norms
    assert t["parameters_embedding_and_head_tied"] == 25008 * 2560
    assert CONFIG["parameters"] == t["parameters"] == (
        2 * t["parameters_mamba_layer"] + 2 * t["parameters_attention_layer"]
        + t["parameters_gmu_layer"] + t["parameters_cross_attention_layer"]
        + 25008 * 2560 + 2 * 2560) == 697_073_792
    assert t["state_bytes"] == 16 * 697_073_792
    assert t["parameters_whole_model"] == 3_852_457_984


def test_the_file_holds_every_published_key_and_lists_its_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == {"num_hidden_layers"}
    assert changed | {"vocab_rows", "train_tokens"} == set(CONFIG["reduced"])
    assert set(CONFIG["reduced"]) == set(CONFIG["reduced_notes"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_hidden_layers_published"],
            CONFIG["kept_layers"], CONFIG["vocab_rows"]) == (
        6, 32, [0, 1, 16, 17, 18, 19], 200064 // 8)
    assert CONFIG["dt_rank"] == -(-CONFIG["hidden_size"] // 16)
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
    assert (kwargs["layers"], kwargs["kept_layers"], kwargs["vocab"],
            kwargs["window"], kwargs["dt_rank"]) == (
        32, [0, 1, 16, 17, 18, 19], 25008, 512, 160)
