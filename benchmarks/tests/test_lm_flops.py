"""harness/lm_flops.py against counts worked out by hand (ISSUE 26's
per-token figures), and the row format the CNN cells' readers take."""

import json
import os

import pytest

from benchmarks.harness import flops, lm_flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "olmoe-1b-7b-l1-bf16.json")) as f:
    CONFIG = json.load(f)


@pytest.mark.parametrize("part,mflop", [
    ("proj", 2 * 4 * 2048 * 2048 / 1e6),            # q, k, v, o: 33.55
    ("core", 2 * 2 * 2048 * (4096 / 2) / 1e6),      # QK^T + AV, causal: 16.78
    ("router", 2 * 2048 * 64 / 1e6),                # 0.26
    ("experts", 2 * 8 * 3 * 2048 * 1024 / 1e6),     # 100.66
    ("lm_head", 2 * 2048 * 12576 / 1e6),            # 51.51
])
def test_forward_mflop_per_token(part, mflop):
    got = lm_flops.forward_mflop_per_token(CONFIG, 4096)
    assert got[part] == pytest.approx(mflop, rel=1e-12)
    assert round(mflop, 1) == {"proj": 33.6, "core": 16.8, "router": 0.3,
                               "experts": 100.7, "lm_head": 51.5}[part]


def test_step_operations_and_rows_for_the_cnn_readers():
    parts = lm_flops.parts(CONFIG, 4, 4096)
    rows = lm_flops.layer_rows(parts)
    assert [r["name"] for r in rows] == ["attn1", "moe1", "lm_head"]
    assert all(set(r) == {"name", "kind", "macs", "in_elems", "out_elems",
                          "weight_elems", "from_data"} for r in rows)
    # three passes of every matmul: 16,384 tokens x 202.77 MFLOP x 3
    per_token = sum(lm_flops.forward_mflop_per_token(CONFIG, 4096).values())
    assert per_token == pytest.approx(202.77, abs=0.01)
    assert flops.step_flops(rows) == 3 * sum(2 * r["macs"] for r in parts)
    assert flops.step_flops(rows) / 1e12 == pytest.approx(9.966, abs=0.001)


def test_floors_and_what_bounds_them():
    by_name = {r["name"]: r for r in lm_flops.parts(CONFIG, 4, 4096)}
    peak, bw = 197e12, 819e9
    t, bound = flops.layer_floor_s(by_name["moe1.experts"], peak, bw)
    # 131,072 rows x 3 matrices of 2048x1024, three passes
    assert by_name["moe1.experts"]["macs"] == 131072 * 3 * 2048 * 1024
    assert bound == "compute" and t == pytest.approx(25.1e-3, rel=0.01)
    t, bound = flops.layer_floor_s(by_name["attn1.core"], peak, bw)
    assert by_name["attn1.core"]["macs"] == 4 * (4096 * 4096 // 2) * 2 * 2048
    assert bound == "compute" and t == pytest.approx(4.19e-3, rel=0.01)
    # the router's 64 outputs per token: its bytes, not its operations
    assert flops.layer_floor_s(by_name["moe1.router"], peak, bw)[1] == "memory"


def test_parameter_totals_in_the_configuration_file():
    d, e, h, v = 2048, 64, 1024, 12576
    layer = 4 * d * d + 4 * d + e * d + 3 * e * h * d
    assert CONFIG["totals"]["parameters_in_the_layer"] == layer == 419_569_664
    assert CONFIG["totals"]["parameters_in_experts"] == 3 * e * h * d
    assert CONFIG["parameters"] == 2 * v * d + layer + d == 471_083_008
