"""Write a configuration's solver + train prototxt from the zoo builders.

Run by hand when a configuration is ADDED (never by run.py):

    python benchmarks/scratch/make_config_prototxt.py alexnet 256 227 \
        benchmarks/configs/alexnet-b256-bf16 --bvlc-mults

The zoo nets declare ``JavaData`` (RDD) inputs with a fixed geometry, and
``cli._data_fns`` takes crop/mirror/mean only from a ``Data`` layer's
``transform_param``; so the two input layers are replaced by ONE Caffe
``Data`` layer (tops data+label) that declares the published transform,
every other layer is the zoo's, byte for byte.  ``--bvlc-mults`` adds the
published per-blob multipliers of bvlc_alexnet/train_val.prototxt
(weights lr 1 / decay 1, biases lr 2 / decay 0), which the zoo builder
leaves at their defaults.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("zoo")
    ap.add_argument("batch", type=int)
    ap.add_argument("crop", type=int)
    ap.add_argument("prefix", help="output path prefix (config name)")
    ap.add_argument("--bvlc-mults", action="store_true")
    args = ap.parse_args()

    from sparknet_tpu import models
    from sparknet_tpu.proto.text_format import Message, serialize

    net = getattr(models, args.zoo)(args.batch)
    cfg = getattr(models, f"{args.zoo}_solver")()

    data = Message().set("name", "data").set("type", "Data")
    data.add("top", "data").add("top", "label")
    tp = Message().set("mirror", True).set("crop_size", args.crop)
    for v in (104.0, 117.0, 123.0):  # the per-channel ImageNet mean (BGR)
        tp.add("mean_value", v)
    data.set("transform_param", tp)
    data.set("data_param", Message().set("batch_size", args.batch))

    out = Message().set("name", net.get_str("name"))
    out.add("layer", data)
    for layer in net.get_all("layer"):
        if layer.get_str("type") == "JavaData":
            continue
        layer = layer.copy()
        if args.bvlc_mults and layer.get_str("type") in ("Convolution", "InnerProduct"):
            layer.add("param", Message().set("lr_mult", 1.0).set("decay_mult", 1.0))
            layer.add("param", Message().set("lr_mult", 2.0).set("decay_mult", 0.0))
        out.add("layer", layer)

    base = os.path.basename(args.prefix)
    with open(args.prefix + ".train.prototxt", "w") as f:
        f.write(serialize(out))
    solver = Message().set("net", base + ".train.prototxt")
    defaults = dataclasses.asdict(type(cfg)())
    for k, v in dataclasses.asdict(cfg).items():
        if v == defaults[k] or k == "snapshot_prefix":
            continue
        if k == "solver_type":
            k = "type"
        for item in (v if isinstance(v, tuple) else (v,)):
            solver.add(k, item)
    with open(args.prefix + ".solver.prototxt", "w") as f:
        f.write(serialize(solver))


if __name__ == "__main__":
    main()
