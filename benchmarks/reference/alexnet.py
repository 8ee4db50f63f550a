"""Plain reference: BVLC AlexNet (Krizhevsky et al. 2012;
caffe/models/bvlc_alexnet/train_val.prototxt + solver.prototxt).

conv -> relu -> LRN -> pool order (CaffeNet swaps pool and LRN), grouped
conv2/4/5, dropout 0.5 after fc6 and fc7 in training.  ``params`` is
``{layer: [weight, bias]}`` by prototxt layer name, float32; ``masks`` is
``{dropout layer: keep mask}`` (the mask is an input: a reference cannot
share a random stream with the program).
"""

from benchmarks.harness.plain_ops import (
    conv, dropout, fc, lrn, max_pool, relu)

LOGITS = "fc8"
# (layer, blob index): first conv weight, last fc bias
LEAVES = (("conv1", 0), ("fc8", 1))
SOLVER = {"lr": 0.01, "momentum": 0.9, "weight_decay": 5e-4}
# a zero image batch makes conv1's weight gradient exactly zero, so its
# update is the weight-decay term alone (no BatchNorm to blow up on it)
ZERO_BATCH_EXACT = True


def multipliers(layer: str, blob: int) -> tuple[float, float]:
    """(lr_mult, decay_mult): weights 1/1, biases 2/0, as published."""
    return (1.0, 1.0) if blob == 0 else (2.0, 0.0)


def forward(p, x, masks):
    x = relu(conv(x, *p["conv1"], stride=4))
    x = max_pool(lrn(x), 3, 2)
    x = relu(conv(x, *p["conv2"], pad=2, group=2))
    x = max_pool(lrn(x), 3, 2)
    x = relu(conv(x, *p["conv3"], pad=1))
    x = relu(conv(x, *p["conv4"], pad=1, group=2))
    x = relu(conv(x, *p["conv5"], pad=1, group=2))
    x = max_pool(x, 3, 2)
    x = dropout(relu(fc(x, *p["fc6"])), masks["drop6"])
    x = dropout(relu(fc(x, *p["fc7"])), masks["drop7"])
    return fc(x, *p["fc8"])
