"""Plain reference: ResNet-50 (He et al. 2015;
KaimingHe/deep-residual-networks ResNet-50 prototxt, Caffe naming).

conv1 7x7/2 -> BN+Scale -> relu -> maxpool 3x3/2 (ceil), then 3+4+6+3
bottleneck blocks (1x1 -> 3x3 -> 1x1x4, stride on branch1 and branch2a of
the first block of stages 3-5, projection shortcut on each stage's first
block), global average pool, fc1000.  BatchNorm in TRAINING mode (batch
statistics, eps 1e-5) followed by a learned Scale; convs carry no bias.
"""

from benchmarks.harness.plain_ops import (
    batch_norm_train, conv, fc, global_ave_pool, max_pool, relu, scale)

LOGITS = "fc1000"
# (layer, blob): the last fc's weight and bias.  Not the first conv: at
# msra initialisation bf16 moves the logits by 12 % of their range, the
# loss gradient with them, and every BatchNorm backward (a difference of
# means) amplifies it: against the f32 reference the program's bf16
# update is off by rel-L2 1.29-1.32 on conv1 and 0.89 on the last conv
# (chip, 32 images), 0.8-1.3 on every conv and Scale below stage 5c and
# 0.13-0.25 on scale5c_branch2c (CPU, 8 images); only the classifier's
# own gradient (0.088 weight, 0.006 bias) still follows it.  With
# --dtype f32 every leaf agrees to 5e-6 (tests/test_reference.py), so
# that is rounding, not arithmetic.  (my runs, PR 22)
LEAVES = (("fc1000", 0), ("fc1000", 1))
SOLVER = {"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-4}
# 53 weight layers of bf16 rounding from msra-filled weights: measured on
# the chip at 32 images loss_rel 3.9e-3 to 6.2e-3, logits_rel 0.12 to
# 0.13, last-bias update 5.2e-3 to 9.2e-3 (my chip run, PR 22).  With
# --dtype f32 the same comparisons give 4e-7, 4e-5 and 5e-6, so these
# bounds hold bf16's noise and the f32 test holds the arithmetic.
TOL = {"loss_rel": 2e-2, "logits_rel": 3e-1, "update_rel": 3e-1,
       "update_rel_last": 5e-2}
# a zero batch through 49 BatchNorm layers has zero variance everywhere:
# the backward pass multiplies by 1/sqrt(eps) per layer and overflows, so
# the exact weight-decay check is not made here (PERF.md, Open questions)
ZERO_BATCH_EXACT = False

STAGES = ((2, 64, 3), (3, 128, 4), (4, 256, 6), (5, 512, 3))


def multipliers(layer: str, blob: int) -> tuple[float, float]:
    return (1.0, 1.0)  # the published prototxt sets none


def _bn_scale(p, x, name):
    return scale(batch_norm_train(x), *p["scale" + name])


def _bottleneck(p, x, n, stride, project):
    shortcut = x
    if project:
        shortcut = _bn_scale(
            p, conv(x, p[f"res{n}_branch1"][0], stride=stride),
            f"{n}_branch1")
    y = conv(x, p[f"res{n}_branch2a"][0], stride=stride)
    y = relu(_bn_scale(p, y, f"{n}_branch2a"))
    y = conv(y, p[f"res{n}_branch2b"][0], pad=1)
    y = relu(_bn_scale(p, y, f"{n}_branch2b"))
    y = conv(y, p[f"res{n}_branch2c"][0])
    y = _bn_scale(p, y, f"{n}_branch2c")
    return relu(shortcut + y)


def forward(p, x, masks):
    x = conv(x, p["conv1"][0], stride=2, pad=3)
    x = relu(_bn_scale(p, x, "_conv1"))
    x = max_pool(x, 3, 2)
    for stage, _width, blocks in STAGES:
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 2) else 1
            x = _bottleneck(p, x, f"{stage}{'abcdef'[i]}", stride, i == 0)
    return fc(global_ave_pool(x), *p["fc1000"])
