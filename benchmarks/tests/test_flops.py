"""harness/flops.py against counts worked out by hand."""

import os

import pytest

from benchmarks.harness import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rows_of(config: str, chw):
    from sparknet_tpu.proto.text_format import parse_file

    net = parse_file(os.path.join(ROOT, "benchmarks", "configs",
                                  config + ".train.prototxt"))
    return {r["name"]: r for r in flops.walk(net, 1, chw)}


def test_alexnet_layers():
    rows = rows_of("alexnet-b256-bf16", (3, 227, 227))
    # conv1: 96 maps of 55x55 ((227-11)/4+1), each 3x11x11 MACs
    assert rows["conv1"]["macs"] == 96 * 55 * 55 * 3 * 11 * 11 == 105_415_200
    assert rows["conv1"]["from_data"] is True
    # conv2, two groups: 256 maps of 27x27 (pool1 ceil((55-3)/2)+1), each
    # sees 96/2 = 48 input maps through 5x5
    assert rows["conv2"]["macs"] == 256 * 27 * 27 * 48 * 5 * 5 == 223_948_800
    # fc6: pool5 is 256x6x6 = 9216 -> 4096
    assert rows["fc6"]["macs"] == 9216 * 4096 == 37_748_736
    assert rows["fc6"]["weight_elems"] == 9216 * 4096
    total = sum(r["macs"] for r in rows.values())
    assert total == (105_415_200 + 223_948_800 + 384 * 13 * 13 * 256 * 9
                     + 384 * 13 * 13 * 192 * 9 + 256 * 13 * 13 * 192 * 9
                     + 37_748_736 + 4096 * 4096 + 4096 * 1000) == 724_406_816
    # forward+backward: 3 passes, conv1 only 2 (no gradient of the images)
    assert flops.step_flops(rows.values()) == 2 * (3 * total - rows["conv1"]["macs"])


def test_resnet50_bottleneck():
    rows = rows_of("resnet50-b256-bf16", (3, 224, 224))
    # res2a sits on pool1's 64x56x56 (conv1 112, pool ceil((112-3)/2)+1 = 56)
    assert rows["res2a_branch1"]["macs"] == 256 * 56 * 56 * 64 == 51_380_224
    assert rows["res2a_branch2a"]["macs"] == 64 * 56 * 56 * 64 == 12_845_056
    assert rows["res2a_branch2b"]["macs"] == 64 * 56 * 56 * 64 * 9 == 115_605_504
    assert rows["res2a_branch2c"]["macs"] == 256 * 56 * 56 * 64 == 51_380_224
    # the stride-2 block of stage 3 halves the map on branch1 and branch2a
    assert rows["res3a_branch2a"]["macs"] == 128 * 28 * 28 * 256
    assert len(rows) == 54  # 53 convs + fc1000
    total = sum(r["macs"] for r in rows.values())
    assert total == pytest.approx(3.86e9, rel=0.02)  # published ~3.8-4.1 GMAC


def test_layer_floor_names_its_bound():
    rows = rows_of("alexnet-b256-bf16", (3, 227, 227))
    t, bound = flops.layer_floor_s(rows["fc6"], 197e12, 819e9)
    # at batch 1 an fc layer is its weights: 3 passes x 2 B x 37.7 M / 819 GB/s
    assert bound == "memory"
    assert t == pytest.approx(3 * 2 * (9216 + 4096 + 9216 * 4096) / 819e9)
    big = dict(rows["conv3"], macs=rows["conv3"]["macs"] * 1024,
               in_elems=rows["conv3"]["in_elems"] * 1024,
               out_elems=rows["conv3"]["out_elems"] * 1024)
    assert flops.layer_floor_s(big, 197e12, 819e9)[1] == "compute"


def test_peaks_table_refuses_an_unknown_device():
    from benchmarks.harness import peaks

    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
