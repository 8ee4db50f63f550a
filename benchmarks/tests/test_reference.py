"""Each plain reference against the program at a tiny size on the CPU,
in float32, where they must agree to rounding: this is what holds the
arithmetic.  The chip run holds the bf16 program to looser, written
bounds (harness/check.py)."""

import os
import re

import pytest

from benchmarks.harness import check, dataset, front_door, load_by_name

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# f32 against f32: sums in another order, nothing else
F32_TOL = {"loss_rel": 1e-5, "logits_rel": 2e-4, "update_rel": 1e-3,
           "update_rel_last": 1e-4, "decay_exact_rel": 1e-6}


@pytest.mark.parametrize("config,reference,crop,batch", [
    ("alexnet-b256-bf16", "alexnet", 227, 2),
    # batch 4: batch statistics over 2 images are ill-conditioned
    ("resnet50-b256-bf16", "resnet50", 224, 4),
])
def test_program_matches_reference_in_f32(tmp_path, monkeypatch, config,
                                          reference, crop, batch):
    monkeypatch.setattr(dataset, "CACHE_DIR", str(tmp_path / "cache"))
    db = dataset.ensure_db(5, 8, (3, 256, 256), 1000)
    for suffix in (".solver.prototxt", ".train.prototxt"):
        with open(os.path.join(ROOT, "benchmarks", "configs", config + suffix)) as f:
            text = re.sub(r"batch_size: \d+", f"batch_size: {batch}", f.read())
        (tmp_path / (config + suffix)).write_text(text)
    seen = {}

    def body(args):
        solver = front_door.build_solver(args)
        host = front_door.open_feed(args, solver)(0)
        x = check.center_crop_mean(host["data"], crop, [104.0, 117.0, 123.0])
        ref = check.Reference(load_by_name("reference", reference))
        seen["facts"], seen["bad"] = check.check_step(
            solver, ref, x, host["label"], F32_TOL)
        return 0

    rc = front_door.run_as_train(
        ["--solver", str(tmp_path / (config + ".solver.prototxt")),
         "--data", f"db:{db}", "--dtype", "f32", "--augment", "device",
         "--prefetch", "3", "--seed", "5"], body)
    assert rc == 0
    assert not seen["bad"], seen["facts"]
