"""The reference model zoo, rebuilt with the layer DSL.

TPU-first equivalents of the prototxt model family the reference ships
(ref: caffe/examples/mnist/lenet_train_test.prototxt,
caffe/examples/cifar10/cifar10_{quick,full}_train_test.prototxt,
caffe/models/bvlc_alexnet/train_val.prototxt,
caffe/models/bvlc_reference_caffenet/train_val.prototxt,
caffe/models/bvlc_googlenet/train_val.prototxt).  Architectures are the
published ones; the definitions here are programmatic builders rather than
checked-in prototxt, because on TPU the model config *is* the program —
it compiles straight to one XLA computation.

Data enters through RDD layers (the JavaData/RDDLayer path,
ref: src/main/scala/libs/Layers.scala:18-40) so every model is fed from the
host input pipeline; batch is a builder argument, not baked into the file.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

from sparknet_tpu.layers_dsl import (
    AccuracyLayer,
    BatchNormLayer,
    ConcatLayer,
    ConvolutionLayer,
    DifferentialAttentionLayer,
    DropoutLayer,
    EltwiseLayer,
    EmbedLayer,
    EuclideanLossLayer,
    ExitWeightedLossLayer,
    FlattenLayer,
    GatedAttentionLayer,
    GatedDeltaNetLayer,
    GatedMemoryUnitLayer,
    GatedMLPLayer,
    InnerProductLayer,
    LatentAttentionLayer,
    LayerNormLayer,
    LoopRegion,
    LRNLayer,
    MambaLayer,
    MoELayer,
    MultiHeadAttentionLayer,
    NetParam,
    Pooling,
    PoolingLayer,
    RDDLayer,
    ReLULayer,
    RMSNormLayer,
    ScaleLayer,
    SigmoidCrossEntropyLossLayer,
    SigmoidLayer,
    SliceLayer,
    SoftmaxWithLoss,
    _filler,
)
from sparknet_tpu.proto.text_format import Message
from sparknet_tpu.solvers.solver import SolverConfig


def _gauss(std: float) -> Message:
    return _filler("gaussian", std=std)


def _const(v: float) -> Message:
    return _filler("constant", value=v)


def _msra() -> Message:
    return _filler("msra")


# ---------------------------------------------------------------------------
# LeNet (ref: caffe/examples/mnist/lenet_train_test.prototxt; the README's
# own inline example, README.md:115-128)
# ---------------------------------------------------------------------------
def lenet(batch: int = 64, num_classes: int = 10) -> Message:
    return NetParam(
        "LeNet",
        RDDLayer("data", shape=[batch, 1, 28, 28]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(5, 5), num_output=20),
        PoolingLayer("pool1", ["conv1"], Pooling.Max, kernel=(2, 2), stride=(2, 2)),
        ConvolutionLayer("conv2", ["pool1"], kernel=(5, 5), num_output=50),
        PoolingLayer("pool2", ["conv2"], Pooling.Max, kernel=(2, 2), stride=(2, 2)),
        InnerProductLayer("ip1", ["pool2"], num_output=500),
        ReLULayer("relu1", ["ip1"], in_place=True),
        InnerProductLayer("ip2", ["ip1"], num_output=num_classes),
        SoftmaxWithLoss("loss", ["ip2", "label"]),
        AccuracyLayer("accuracy", ["ip2", "label"], phase="TEST"),
    )


def lenet_solver() -> SolverConfig:
    """ref: caffe/examples/mnist/lenet_solver.prototxt."""
    return SolverConfig(
        base_lr=0.01, lr_policy="inv", gamma=1e-4, power=0.75,
        momentum=0.9, weight_decay=5e-4, max_iter=10000,
        solver_type="SGD", display=100,
    )


# ---------------------------------------------------------------------------
# CIFAR-10 quick (ref: caffe/examples/cifar10/cifar10_quick_train_test.prototxt)
# ---------------------------------------------------------------------------
def cifar10_quick(batch: int = 100, num_classes: int = 10) -> Message:
    return NetParam(
        "CIFAR10_quick",
        RDDLayer("data", shape=[batch, 3, 32, 32]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(5, 5), num_output=32,
                         pad=(2, 2), weight_filler=_gauss(1e-4)),
        PoolingLayer("pool1", ["conv1"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        ReLULayer("relu1", ["pool1"], in_place=True),
        ConvolutionLayer("conv2", ["pool1"], kernel=(5, 5), num_output=32,
                         pad=(2, 2), weight_filler=_gauss(0.01)),
        ReLULayer("relu2", ["conv2"], in_place=True),
        PoolingLayer("pool2", ["conv2"], Pooling.Ave, kernel=(3, 3), stride=(2, 2)),
        ConvolutionLayer("conv3", ["pool2"], kernel=(5, 5), num_output=64,
                         pad=(2, 2), weight_filler=_gauss(0.01)),
        ReLULayer("relu3", ["conv3"], in_place=True),
        PoolingLayer("pool3", ["conv3"], Pooling.Ave, kernel=(3, 3), stride=(2, 2)),
        InnerProductLayer("ip1", ["pool3"], num_output=64,
                          weight_filler=_gauss(0.1)),
        InnerProductLayer("ip2", ["ip1"], num_output=num_classes,
                          weight_filler=_gauss(0.1)),
        SoftmaxWithLoss("loss", ["ip2", "label"]),
        AccuracyLayer("accuracy", ["ip2", "label"], phase="TEST"),
    )


def cifar10_quick_solver() -> SolverConfig:
    """ref: caffe/examples/cifar10/cifar10_quick_solver.prototxt."""
    return SolverConfig(
        base_lr=1e-3, lr_policy="fixed", momentum=0.9, weight_decay=0.004,
        max_iter=4000, solver_type="SGD", display=100,
    )


# ---------------------------------------------------------------------------
# CIFAR-10 full — the CifarApp model (ref:
# caffe/examples/cifar10/cifar10_full_train_test.prototxt; the _java_ variant
# swaps in JavaData layers, which RDDLayer plays here —
# src/main/scala/apps/CifarApp.scala:78-80)
# ---------------------------------------------------------------------------
def cifar10_full(batch: int = 100, num_classes: int = 10) -> Message:
    return NetParam(
        "CIFAR10_full",
        RDDLayer("data", shape=[batch, 3, 32, 32]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(5, 5), num_output=32,
                         pad=(2, 2), weight_filler=_gauss(1e-4)),
        PoolingLayer("pool1", ["conv1"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        ReLULayer("relu1", ["pool1"], in_place=True),
        LRNLayer("norm1", ["pool1"], local_size=3, alpha=5e-5, beta=0.75,
                 norm_region="WITHIN_CHANNEL"),
        ConvolutionLayer("conv2", ["norm1"], kernel=(5, 5), num_output=32,
                         pad=(2, 2), weight_filler=_gauss(0.01)),
        ReLULayer("relu2", ["conv2"], in_place=True),
        PoolingLayer("pool2", ["conv2"], Pooling.Ave, kernel=(3, 3), stride=(2, 2)),
        LRNLayer("norm2", ["pool2"], local_size=3, alpha=5e-5, beta=0.75,
                 norm_region="WITHIN_CHANNEL"),
        ConvolutionLayer("conv3", ["norm2"], kernel=(5, 5), num_output=64,
                         pad=(2, 2), weight_filler=_gauss(0.01)),
        ReLULayer("relu3", ["conv3"], in_place=True),
        PoolingLayer("pool3", ["conv3"], Pooling.Ave, kernel=(3, 3), stride=(2, 2)),
        InnerProductLayer("ip1", ["pool3"], num_output=num_classes,
                          weight_filler=_gauss(0.01)),
        SoftmaxWithLoss("loss", ["ip1", "label"]),
        AccuracyLayer("accuracy", ["ip1", "label"], phase="TEST"),
    )


def cifar10_full_solver() -> SolverConfig:
    """ref: caffe/examples/cifar10/cifar10_full_solver.prototxt (the
    CifarApp recipe — BASELINE.md CIFAR-10 row)."""
    return SolverConfig(
        base_lr=1e-3, lr_policy="fixed", momentum=0.9, weight_decay=0.004,
        max_iter=60000, solver_type="SGD", display=200,
    )


# ---------------------------------------------------------------------------
# AlexNet (ref: caffe/models/bvlc_alexnet/train_val.prototxt; order is
# conv->relu->norm->pool, vs CaffeNet's conv->relu->pool->norm)
# ---------------------------------------------------------------------------
def _alex_tail(fc6_bottom: str, num_classes: int) -> list[Message]:
    return [
        InnerProductLayer("fc6", [fc6_bottom], num_output=4096,
                          weight_filler=_gauss(0.005), bias_filler=_const(0.1)),
        ReLULayer("relu6", ["fc6"], in_place=True),
        DropoutLayer("drop6", ["fc6"], ratio=0.5, in_place=True),
        InnerProductLayer("fc7", ["fc6"], num_output=4096,
                          weight_filler=_gauss(0.005), bias_filler=_const(0.1)),
        ReLULayer("relu7", ["fc7"], in_place=True),
        DropoutLayer("drop7", ["fc7"], ratio=0.5, in_place=True),
        InnerProductLayer("fc8", ["fc7"], num_output=num_classes,
                          weight_filler=_gauss(0.01)),
        SoftmaxWithLoss("loss", ["fc8", "label"]),
        AccuracyLayer("accuracy", ["fc8", "label"], phase="TEST"),
    ]


def alexnet(batch: int = 256, num_classes: int = 1000, crop: int = 227) -> Message:
    return NetParam(
        "AlexNet",
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(11, 11), num_output=96,
                         stride=(4, 4), weight_filler=_gauss(0.01)),
        ReLULayer("relu1", ["conv1"], in_place=True),
        LRNLayer("norm1", ["conv1"], local_size=5, alpha=1e-4, beta=0.75),
        PoolingLayer("pool1", ["norm1"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        ConvolutionLayer("conv2", ["pool1"], kernel=(5, 5), num_output=256,
                         pad=(2, 2), group=2, weight_filler=_gauss(0.01),
                         bias_filler=_const(0.1)),
        ReLULayer("relu2", ["conv2"], in_place=True),
        LRNLayer("norm2", ["conv2"], local_size=5, alpha=1e-4, beta=0.75),
        PoolingLayer("pool2", ["norm2"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        ConvolutionLayer("conv3", ["pool2"], kernel=(3, 3), num_output=384,
                         pad=(1, 1), weight_filler=_gauss(0.01)),
        ReLULayer("relu3", ["conv3"], in_place=True),
        ConvolutionLayer("conv4", ["conv3"], kernel=(3, 3), num_output=384,
                         pad=(1, 1), group=2, weight_filler=_gauss(0.01),
                         bias_filler=_const(0.1)),
        ReLULayer("relu4", ["conv4"], in_place=True),
        ConvolutionLayer("conv5", ["conv4"], kernel=(3, 3), num_output=256,
                         pad=(1, 1), group=2, weight_filler=_gauss(0.01),
                         bias_filler=_const(0.1)),
        ReLULayer("relu5", ["conv5"], in_place=True),
        PoolingLayer("pool5", ["conv5"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        *_alex_tail("pool5", num_classes),
    )


def alexnet_solver() -> SolverConfig:
    """ref: caffe/models/bvlc_alexnet/solver.prototxt (the ImageNet recipe —
    BASELINE.md ImageNet row)."""
    return SolverConfig(
        base_lr=0.01, lr_policy="step", gamma=0.1, stepsize=100000,
        momentum=0.9, weight_decay=5e-4, max_iter=450000,
        solver_type="SGD", display=20,
    )


# ---------------------------------------------------------------------------
# CaffeNet — the ImageNetApp model (ref:
# caffe/models/bvlc_reference_caffenet/train_val.prototxt;
# src/main/scala/apps/ImageNetApp.scala uses this with RDD data layers)
# ---------------------------------------------------------------------------
def caffenet(batch: int = 256, num_classes: int = 1000, crop: int = 227) -> Message:
    return NetParam(
        "CaffeNet",
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(11, 11), num_output=96,
                         stride=(4, 4), weight_filler=_gauss(0.01)),
        ReLULayer("relu1", ["conv1"], in_place=True),
        PoolingLayer("pool1", ["conv1"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        LRNLayer("norm1", ["pool1"], local_size=5, alpha=1e-4, beta=0.75),
        ConvolutionLayer("conv2", ["norm1"], kernel=(5, 5), num_output=256,
                         pad=(2, 2), group=2, weight_filler=_gauss(0.01),
                         bias_filler=_const(1.0)),
        ReLULayer("relu2", ["conv2"], in_place=True),
        PoolingLayer("pool2", ["conv2"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        LRNLayer("norm2", ["pool2"], local_size=5, alpha=1e-4, beta=0.75),
        ConvolutionLayer("conv3", ["norm2"], kernel=(3, 3), num_output=384,
                         pad=(1, 1), weight_filler=_gauss(0.01)),
        ReLULayer("relu3", ["conv3"], in_place=True),
        ConvolutionLayer("conv4", ["conv3"], kernel=(3, 3), num_output=384,
                         pad=(1, 1), group=2, weight_filler=_gauss(0.01),
                         bias_filler=_const(1.0)),
        ReLULayer("relu4", ["conv4"], in_place=True),
        ConvolutionLayer("conv5", ["conv4"], kernel=(3, 3), num_output=256,
                         pad=(1, 1), group=2, weight_filler=_gauss(0.01),
                         bias_filler=_const(1.0)),
        ReLULayer("relu5", ["conv5"], in_place=True),
        PoolingLayer("pool5", ["conv5"], Pooling.Max, kernel=(3, 3), stride=(2, 2)),
        *_alex_tail("pool5", num_classes),
    )


def caffenet_solver() -> SolverConfig:
    """ref: caffe/models/bvlc_reference_caffenet/solver.prototxt."""
    return alexnet_solver()


# ---------------------------------------------------------------------------
# GoogLeNet — the compiler stress test: 9 inception modules, multi-tower
# concat DAG (ref: caffe/models/bvlc_googlenet/train_val.prototxt, 166
# layers; main tower — the two training-time auxiliary loss heads are
# omitted, as at inference in the reference)
# ---------------------------------------------------------------------------
def _inception(name: str, bottom: str, c1: int, c3r: int, c3: int,
               c5r: int, c5: int, cp: int) -> list[Message]:
    """One inception module: 1x1 / 3x3(reduced) / 5x5(reduced) / pool-proj
    towers concatenated on channels."""
    w = lambda: _filler("xavier")
    b = lambda: _const(0.2)
    n = f"inception_{name}"
    layers = [
        ConvolutionLayer(f"{n}/1x1", [bottom], kernel=(1, 1), num_output=c1,
                         weight_filler=w(), bias_filler=b()),
        ReLULayer(f"{n}/relu_1x1", [f"{n}/1x1"], in_place=True),
        ConvolutionLayer(f"{n}/3x3_reduce", [bottom], kernel=(1, 1),
                         num_output=c3r, weight_filler=w(), bias_filler=b()),
        ReLULayer(f"{n}/relu_3x3_reduce", [f"{n}/3x3_reduce"], in_place=True),
        ConvolutionLayer(f"{n}/3x3", [f"{n}/3x3_reduce"], kernel=(3, 3),
                         num_output=c3, pad=(1, 1), weight_filler=w(),
                         bias_filler=b()),
        ReLULayer(f"{n}/relu_3x3", [f"{n}/3x3"], in_place=True),
        ConvolutionLayer(f"{n}/5x5_reduce", [bottom], kernel=(1, 1),
                         num_output=c5r, weight_filler=w(), bias_filler=b()),
        ReLULayer(f"{n}/relu_5x5_reduce", [f"{n}/5x5_reduce"], in_place=True),
        ConvolutionLayer(f"{n}/5x5", [f"{n}/5x5_reduce"], kernel=(5, 5),
                         num_output=c5, pad=(2, 2), weight_filler=w(),
                         bias_filler=b()),
        ReLULayer(f"{n}/relu_5x5", [f"{n}/5x5"], in_place=True),
        PoolingLayer(f"{n}/pool", [bottom], Pooling.Max, kernel=(3, 3),
                     stride=(1, 1), pad=(1, 1)),
        ConvolutionLayer(f"{n}/pool_proj", [f"{n}/pool"], kernel=(1, 1),
                         num_output=cp, weight_filler=w(), bias_filler=b()),
        ReLULayer(f"{n}/relu_pool_proj", [f"{n}/pool_proj"], in_place=True),
        ConcatLayer(f"{n}/output",
                    [f"{n}/1x1", f"{n}/3x3", f"{n}/5x5", f"{n}/pool_proj"]),
    ]
    return layers


def _googlenet_aux_head(i: int, bottom: str, num_classes: int) -> list[Message]:
    """Auxiliary classifier tower ``loss{i}`` — ave_pool 5x5/3 → 1x1 conv 128
    → fc 1024 → drop 0.7 → fc num_classes → SoftmaxWithLoss at weight 0.3.
    The published recipe trains with BOTH aux heads in every phase (ref:
    caffe/models/bvlc_googlenet/train_val.prototxt:823-953 loss1,
    :1586-1716 loss2; loss_weight 0.3 at :933 and :1696)."""
    w = lambda: _filler("xavier")
    b = lambda: _const(0.2)
    p = f"loss{i}"
    return [
        PoolingLayer(f"{p}/ave_pool", [bottom], Pooling.Ave,
                     kernel=(5, 5), stride=(3, 3)),
        ConvolutionLayer(f"{p}/conv", [f"{p}/ave_pool"], kernel=(1, 1),
                         num_output=128, weight_filler=w(), bias_filler=b()),
        ReLULayer(f"{p}/relu_conv", [f"{p}/conv"], in_place=True),
        InnerProductLayer(f"{p}/fc", [f"{p}/conv"], num_output=1024,
                          weight_filler=w(), bias_filler=b()),
        ReLULayer(f"{p}/relu_fc", [f"{p}/fc"], in_place=True),
        DropoutLayer(f"{p}/drop_fc", [f"{p}/fc"], ratio=0.7, in_place=True),
        InnerProductLayer(f"{p}/classifier", [f"{p}/fc"],
                          num_output=num_classes, weight_filler=w(),
                          bias_filler=_const(0.0)),
        SoftmaxWithLoss(f"{p}/loss", [f"{p}/classifier", "label"],
                        loss_weight=0.3, top=f"{p}/loss{i}"),
        AccuracyLayer(f"{p}/top-1", [f"{p}/classifier", "label"], phase="TEST"),
        AccuracyLayer(f"{p}/top-5", [f"{p}/classifier", "label"], top_k=5,
                      phase="TEST"),
    ]


def googlenet(batch: int = 32, num_classes: int = 1000, crop: int = 224) -> Message:
    w = lambda: _filler("xavier")
    b = lambda: _const(0.2)
    layers: list[Message] = [
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1/7x7_s2", ["data"], kernel=(7, 7), num_output=64,
                         stride=(2, 2), pad=(3, 3), weight_filler=w(),
                         bias_filler=b()),
        ReLULayer("conv1/relu_7x7", ["conv1/7x7_s2"], in_place=True),
        PoolingLayer("pool1/3x3_s2", ["conv1/7x7_s2"], Pooling.Max,
                     kernel=(3, 3), stride=(2, 2)),
        LRNLayer("pool1/norm1", ["pool1/3x3_s2"], local_size=5, alpha=1e-4,
                 beta=0.75),
        ConvolutionLayer("conv2/3x3_reduce", ["pool1/norm1"], kernel=(1, 1),
                         num_output=64, weight_filler=w(), bias_filler=b()),
        ReLULayer("conv2/relu_3x3_reduce", ["conv2/3x3_reduce"], in_place=True),
        ConvolutionLayer("conv2/3x3", ["conv2/3x3_reduce"], kernel=(3, 3),
                         num_output=192, pad=(1, 1), weight_filler=w(),
                         bias_filler=b()),
        ReLULayer("conv2/relu_3x3", ["conv2/3x3"], in_place=True),
        LRNLayer("conv2/norm2", ["conv2/3x3"], local_size=5, alpha=1e-4,
                 beta=0.75),
        PoolingLayer("pool2/3x3_s2", ["conv2/norm2"], Pooling.Max,
                     kernel=(3, 3), stride=(2, 2)),
    ]
    layers += _inception("3a", "pool2/3x3_s2", 64, 96, 128, 16, 32, 32)
    layers += _inception("3b", "inception_3a/output", 128, 128, 192, 32, 96, 64)
    layers += [PoolingLayer("pool3/3x3_s2", ["inception_3b/output"],
                            Pooling.Max, kernel=(3, 3), stride=(2, 2))]
    layers += _inception("4a", "pool3/3x3_s2", 192, 96, 208, 16, 48, 64)
    layers += _googlenet_aux_head(1, "inception_4a/output", num_classes)
    layers += _inception("4b", "inception_4a/output", 160, 112, 224, 24, 64, 64)
    layers += _inception("4c", "inception_4b/output", 128, 128, 256, 24, 64, 64)
    layers += _inception("4d", "inception_4c/output", 112, 144, 288, 32, 64, 64)
    layers += _googlenet_aux_head(2, "inception_4d/output", num_classes)
    layers += _inception("4e", "inception_4d/output", 256, 160, 320, 32, 128, 128)
    layers += [PoolingLayer("pool4/3x3_s2", ["inception_4e/output"],
                            Pooling.Max, kernel=(3, 3), stride=(2, 2))]
    layers += _inception("5a", "pool4/3x3_s2", 256, 160, 320, 32, 128, 128)
    layers += _inception("5b", "inception_5a/output", 384, 192, 384, 48, 128, 128)
    # pool5 is a GLOBAL average in intent (7x7 == 224/32, the whole 5b
    # map — ref: bvlc_googlenet/train_val.prototxt pool5/7x7_s1); keep
    # that intent at reduced crops (e.g. the digits-96 convergence
    # walkthrough, examples/12) by sizing the kernel to the actual map.
    # Non-multiples of 32 would leave a ceil-mode map LARGER than
    # crop//32 and silently break the global intent — reject them.
    if crop % 32:
        raise ValueError(f"googlenet: crop must be a multiple of 32 "
                         f"(got {crop})")
    p5 = max(1, crop // 32)
    layers += [
        PoolingLayer("pool5/7x7_s1", ["inception_5b/output"], Pooling.Ave,
                     kernel=(p5, p5), stride=(1, 1)),
        DropoutLayer("pool5/drop_7x7_s1", ["pool5/7x7_s1"], ratio=0.4, in_place=True),
        InnerProductLayer("loss3/classifier", ["pool5/7x7_s1"],
                          num_output=num_classes, weight_filler=w(),
                          bias_filler=_const(0.0)),
        SoftmaxWithLoss("loss3/loss3", ["loss3/classifier", "label"]),
        AccuracyLayer("loss3/top-1", ["loss3/classifier", "label"], phase="TEST"),
        AccuracyLayer("loss3/top-5", ["loss3/classifier", "label"], top_k=5, phase="TEST"),
    ]
    return NetParam("GoogleNet", *layers)


def googlenet_solver() -> SolverConfig:
    """ref: caffe/models/bvlc_googlenet/solver.prototxt."""
    return SolverConfig(
        base_lr=0.01, lr_policy="step", gamma=0.96, stepsize=320000,
        momentum=0.9, weight_decay=2e-4, max_iter=2400000,
        solver_type="SGD", display=40,
    )


# ---------------------------------------------------------------------------
# MNIST siamese — the weight-sharing example (ref:
# caffe/examples/siamese/mnist_siamese_train_test.prototxt): a stacked
# image pair is sliced into two LeNet-style towers whose conv/ip layers
# share weights via `param { name: ... }`; a ContrastiveLoss pulls same-
# class embeddings together and pushes different-class pairs apart.
# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# ResNet-50 — the first post-reference zoo family.  The reference predates
# residual nets; this follows the published Caffe ResNet-50 deploy wiring
# (He et al. 2016: conv bias_term false + BatchNorm/Scale pairs, bottleneck
# branches named res{stage}{blk}_branch{1,2a,2b,2c}, v1 downsampling via
# stride-2 on branch1 and branch2a).  TPU-first rationale: all-MXU
# (no LRN, 3x3/1x1 convs), so unlike the bytes-bound AlexNet family its
# roofline is the compute term — the MFU-exercising zoo member.
# ---------------------------------------------------------------------------
def _bn_scale(prefix: str, bottom: str,
              frac: float = 0.999) -> list[Message]:
    """BatchNorm (stats only) + Scale (gamma/beta), Caffe-ResNet naming."""
    return [
        BatchNormLayer(f"bn{prefix}", [bottom],
                       moving_average_fraction=frac),
        ScaleLayer(f"scale{prefix}", [bottom]),
    ]


def _bottleneck(stage: int, blk: str, bottom: str, width: int,
                stride: int, project: bool,
                bn_fraction: float = 0.999) -> tuple[list[Message], str]:
    """res{stage}{blk}: 1x1(width,s) -> 3x3(width) -> 1x1(4*width) with
    identity or stride-s projection shortcut; sum then ReLU."""
    w = _msra
    n = f"{stage}{blk}"
    layers: list[Message] = []
    shortcut = bottom
    if project:
        layers += [
            ConvolutionLayer(f"res{n}_branch1", [bottom], kernel=(1, 1),
                             num_output=4 * width, stride=(stride, stride),
                             weight_filler=w(), bias_term=False),
            *_bn_scale(f"{n}_branch1", f"res{n}_branch1", bn_fraction),
        ]
        shortcut = f"res{n}_branch1"
    layers += [
        ConvolutionLayer(f"res{n}_branch2a", [bottom], kernel=(1, 1),
                         num_output=width, stride=(stride, stride),
                         weight_filler=w(), bias_term=False),
        *_bn_scale(f"{n}_branch2a", f"res{n}_branch2a", bn_fraction),
        ReLULayer(f"res{n}_branch2a_relu", [f"res{n}_branch2a"],
                  in_place=True),
        ConvolutionLayer(f"res{n}_branch2b", [f"res{n}_branch2a"],
                         kernel=(3, 3), num_output=width, pad=(1, 1),
                         weight_filler=w(), bias_term=False),
        *_bn_scale(f"{n}_branch2b", f"res{n}_branch2b", bn_fraction),
        ReLULayer(f"res{n}_branch2b_relu", [f"res{n}_branch2b"],
                  in_place=True),
        ConvolutionLayer(f"res{n}_branch2c", [f"res{n}_branch2b"],
                         kernel=(1, 1), num_output=4 * width,
                         weight_filler=w(), bias_term=False),
        *_bn_scale(f"{n}_branch2c", f"res{n}_branch2c", bn_fraction),
        EltwiseLayer(f"res{n}", [shortcut, f"res{n}_branch2c"]),
        ReLULayer(f"res{n}_relu", [f"res{n}"], in_place=True),
    ]
    return layers, f"res{n}"


def resnet50(batch: int = 32, num_classes: int = 1000,
             crop: int = 224, bn_fraction: float = 0.999) -> Message:
    """``bn_fraction``: BatchNorm moving-average fraction — the recipe
    0.999 assumes thousands of iterations; short schedules (fine-tunes,
    convergence demos) want 0.9-0.95 so eval stats track training."""
    w = _msra
    layers: list[Message] = [
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(7, 7), num_output=64,
                         stride=(2, 2), pad=(3, 3), weight_filler=w(),
                         bias_term=False),
        *_bn_scale("_conv1", "conv1", bn_fraction),
        ReLULayer("conv1_relu", ["conv1"], in_place=True),
        PoolingLayer("pool1", ["conv1"], Pooling.Max, kernel=(3, 3),
                     stride=(2, 2)),
    ]
    bottom = "pool1"
    stages = [(2, 64, 3), (3, 128, 4), (4, 256, 6), (5, 512, 3)]
    for stage, width, blocks in stages:
        for i in range(blocks):
            blk = "abcdef"[i]
            stride = 2 if (i == 0 and stage > 2) else 1
            ls, bottom = _bottleneck(stage, blk, bottom, width,
                                     stride, project=(i == 0),
                                     bn_fraction=bn_fraction)
            layers += ls
    layers += [
        PoolingLayer("pool5", [bottom], Pooling.Ave, global_pooling=True),
        InnerProductLayer("fc1000", ["pool5"], num_output=num_classes,
                          weight_filler=w(), bias_filler=_const(0.0)),
        SoftmaxWithLoss("loss", ["fc1000", "label"]),
        AccuracyLayer("accuracy", ["fc1000", "label"], phase="TEST"),
        AccuracyLayer("accuracy_top5", ["fc1000", "label"], top_k=5,
                      phase="TEST"),
    ]
    return NetParam("ResNet-50", *layers)


def resnet50_solver() -> SolverConfig:
    """The published recipe: SGD 0.9, base_lr 0.1, weight decay 1e-4,
    /10 steps (He et al.; epoch boundaries depend on dataset scale)."""
    return SolverConfig(
        base_lr=0.1, lr_policy="multistep", momentum=0.9,
        weight_decay=1e-4, gamma=0.1, stepvalue=(150000, 300000),
        max_iter=450000, solver_type="SGD", display=20,
        snapshot_prefix="resnet50",
    )


# ---------------------------------------------------------------------------
# VGG-16 — the second post-reference zoo family (Simonyan & Zisserman
# 2015, configuration D), wired as the published Caffe model-zoo
# VGG_ILSVRC_16_layers train_val: 13 conv3x3/pad1 layers in five
# max-pooled blocks, then the AlexNet-style 4096/4096/1000 FC tail with
# dropout.  TPU-first rationale: it is the zoo's pure compute-roofline
# member — uniform 3x3 convs at full stride keep the MXU saturated
# (~15.5 GFLOP/image forward, an order of magnitude over AlexNet with a
# third of AlexNet's bytes-per-FLOP), so its bench record is bounded by
# the corrected `TPU_PEAK_FLOPS` compute term, not HBM, making it the
# model that keeps the MFU column honest.
# ---------------------------------------------------------------------------
def _vgg_block(idx: int, bottom: str, convs: int, width: int,
               filler) -> list[Message]:
    """conv{idx}_1..convs (3x3 pad 1, ReLU) then 2x2/2 max pool."""
    layers: list[Message] = []
    for j in range(1, convs + 1):
        name = f"conv{idx}_{j}"
        layers += [
            ConvolutionLayer(name, [bottom], kernel=(3, 3), num_output=width,
                             pad=(1, 1), weight_filler=filler(),
                             bias_filler=_const(0.0)),
            ReLULayer(f"relu{idx}_{j}", [name], in_place=True),
        ]
        bottom = name
    layers.append(PoolingLayer(f"pool{idx}", [bottom], Pooling.Max,
                               kernel=(2, 2), stride=(2, 2)))
    return layers


def vgg16(batch: int = 64, num_classes: int = 1000, crop: int = 224,
          msra_init: bool = False) -> Message:
    """``msra_init``: the published zoo file keeps gaussian std 0.01 —
    faithful, but activations vanish ~1e-5 by conv5_3 so config D does
    not train from scratch (the paper bootstrapped it from config A;
    He et al. 2015 §2.2 derives msra filling from exactly this failure).
    Flip on for from-scratch training without a warm start."""
    filler = _msra if msra_init else lambda: _gauss(0.01)
    blocks = [(1, 2, 64), (2, 2, 128), (3, 3, 256), (4, 3, 512), (5, 3, 512)]
    layers: list[Message] = [
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
    ]
    bottom = "data"
    for idx, convs, width in blocks:
        layers += _vgg_block(idx, bottom, convs, width, filler)
        bottom = f"pool{idx}"
    layers += _alex_tail(bottom, num_classes)
    return NetParam("VGG-16", *layers)


def vgg16_solver() -> SolverConfig:
    """The published recipe (Simonyan & Zisserman §3.1): SGD momentum
    0.9, base_lr 0.01 decreased 10x on plateau (step schedule here),
    weight decay 5e-4, batch 256 aggregated (the Caffe zoo train_val
    runs batch 64 with iter_size; on TPU the full batch fits one step)."""
    return SolverConfig(
        base_lr=0.01, lr_policy="step", gamma=0.1, stepsize=100000,
        momentum=0.9, weight_decay=5e-4, max_iter=370000,
        solver_type="SGD", display=20, snapshot_prefix="vgg16",
    )


def _fire(i: int, bottom: str, squeeze: int, expand: int,
          msra: bool = False) -> list[Message]:
    """fire{i}: 1x1 squeeze -> parallel 1x1 + 3x3(pad 1) expands ->
    channel concat (SqueezeNet §3.1 Fire module)."""
    w = _msra if msra else (lambda: _filler("xavier"))
    p = f"fire{i}"
    return [
        ConvolutionLayer(f"{p}/squeeze1x1", [bottom], kernel=(1, 1),
                         num_output=squeeze, weight_filler=w()),
        ReLULayer(f"{p}/relu_squeeze1x1", [f"{p}/squeeze1x1"],
                  in_place=True),
        ConvolutionLayer(f"{p}/expand1x1", [f"{p}/squeeze1x1"],
                         kernel=(1, 1), num_output=expand,
                         weight_filler=w()),
        ReLULayer(f"{p}/relu_expand1x1", [f"{p}/expand1x1"], in_place=True),
        ConvolutionLayer(f"{p}/expand3x3", [f"{p}/squeeze1x1"],
                         kernel=(3, 3), num_output=expand, pad=(1, 1),
                         weight_filler=w()),
        ReLULayer(f"{p}/relu_expand3x3", [f"{p}/expand3x3"], in_place=True),
        ConcatLayer(f"{p}/concat", [f"{p}/expand1x1", f"{p}/expand3x3"]),
    ]


def squeezenet(batch: int = 32, num_classes: int = 1000,
               crop: int = 227, msra_init: bool = False) -> Message:
    """SqueezeNet v1.1 — post-reference family #3, the deploy-efficiency
    member (Iandola et al. 2016; the official release was a Caffe
    prototxt, forresti/SqueezeNet, which this follows: conv1 64x3x3/2,
    eight Fire modules, all-conv 1x1 classifier over a global average
    pool — no fc layers at all).  1,235,496 params at 1000 classes
    (~50x smaller than AlexNet at comparable published accuracy), which
    is exactly the regime the int8 PTQ deploy path (`quant.py`,
    `--fold-bn --int8`) targets.  TPU note: the Fire concat of 1x1+3x3
    expands is a 2-way DAG per module — a lighter cousin of the
    inception stress test the compiler already carries.

    ``msra_init=True``: swap every conv's xavier filler for msra — the
    published xavier wiring loses ~2.5x activation variance per Fire
    module through the ReLU stack (measured round 5: std 0.39 at conv1
    -> 1.7e-3 by fire9 at unit-scale inputs, gradients ~1e-4), the same
    from-scratch trainability gap `zoo:vgg16` documents; the default
    stays faithful to the published prototxt for finetune parity."""
    w = _msra if msra_init else (lambda: _filler("xavier"))
    layers: list[Message] = [
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(3, 3), num_output=64,
                         stride=(2, 2), weight_filler=w()),
        ReLULayer("relu_conv1", ["conv1"], in_place=True),
        PoolingLayer("pool1", ["conv1"], Pooling.Max, kernel=(3, 3),
                     stride=(2, 2)),
    ]
    layers += _fire(2, "pool1", 16, 64, msra_init)
    layers += _fire(3, "fire2/concat", 16, 64, msra_init)
    layers += [PoolingLayer("pool3", ["fire3/concat"], Pooling.Max,
                            kernel=(3, 3), stride=(2, 2))]
    layers += _fire(4, "pool3", 32, 128, msra_init)
    layers += _fire(5, "fire4/concat", 32, 128, msra_init)
    layers += [PoolingLayer("pool5", ["fire5/concat"], Pooling.Max,
                            kernel=(3, 3), stride=(2, 2))]
    layers += _fire(6, "pool5", 48, 192, msra_init)
    layers += _fire(7, "fire6/concat", 48, 192, msra_init)
    layers += _fire(8, "fire7/concat", 64, 256, msra_init)
    layers += _fire(9, "fire8/concat", 64, 256, msra_init)
    layers += [
        DropoutLayer("drop9", ["fire9/concat"], ratio=0.5, in_place=True),
        ConvolutionLayer("conv10", ["fire9/concat"], kernel=(1, 1),
                         num_output=num_classes, weight_filler=_gauss(0.01),
                         bias_filler=_const(0.0)),
        ReLULayer("relu_conv10", ["conv10"], in_place=True),
        PoolingLayer("pool10", ["conv10"], Pooling.Ave,
                     global_pooling=True),
        FlattenLayer("flat10", ["pool10"]),
        SoftmaxWithLoss("loss", ["flat10", "label"]),
        AccuracyLayer("accuracy", ["flat10", "label"], phase="TEST"),
        AccuracyLayer("accuracy_top5", ["flat10", "label"], top_k=5,
                      phase="TEST"),
    ]
    return NetParam("SqueezeNet_v1.1", *layers)


def _dw_sep(name: str, bottom: str, cin: int, cout: int, stride: int,
            bn_fraction: float) -> tuple[list[Message], str]:
    """conv{name}/dw (3x3 depthwise, group=cin) + BN/Scale/ReLU, then
    conv{name}/sep (1x1 pointwise) + BN/Scale/ReLU — the depthwise-
    separable block (Howard et al. 2017 §3.1, the MobileNet-Caffe
    community wiring's layer naming)."""
    dw, sep = f"conv{name}/dw", f"conv{name}/sep"
    layers = [
        ConvolutionLayer(dw, [bottom], kernel=(3, 3), num_output=cin,
                         stride=(stride, stride), pad=(1, 1), group=cin,
                         weight_filler=_msra(), bias_term=False),
        *_bn_scale(f"{name}/dw", dw, bn_fraction),
        ReLULayer(f"relu{name}/dw", [dw], in_place=True),
        ConvolutionLayer(sep, [dw], kernel=(1, 1), num_output=cout,
                         weight_filler=_msra(), bias_term=False),
        *_bn_scale(f"{name}/sep", sep, bn_fraction),
        ReLULayer(f"relu{name}/sep", [sep], in_place=True),
    ]
    return layers, sep


def mobilenet(batch: int = 32, num_classes: int = 1000, crop: int = 224,
              bn_fraction: float = 0.999) -> Message:
    """MobileNet v1 (1.0x, Howard et al. 2017) — post-reference family
    #4, the depthwise-separable member: 13 dw-separable blocks between
    a 3x3/2 stem and a global-average 1x1-conv classifier.  4,231,976
    params at 1000 classes (the standard v1 count; derived conv1 864 +
    dw 44,640 + pointwise 3,139,584 + Scale gamma/beta 21,888 +
    fc 1,025,000 — pinned in tests/test_zoo_sweep.py).  Zoo role: the only family whose hot op
    is GROUPED convolution at group == channels — the MXU's worst-case
    conv orientation (a depthwise 3x3 does 9 MACs/output vs a dense
    conv's thousands, so the op is bandwidth-bound by construction);
    its bench point measures how far XLA's depthwise lowering sits from
    the HBM bound.  ``bn_fraction`` as in ``resnet50``."""
    layers: list[Message] = [
        RDDLayer("data", shape=[batch, 3, crop, crop]),
        RDDLayer("label", shape=[batch]),
        ConvolutionLayer("conv1", ["data"], kernel=(3, 3), num_output=32,
                         stride=(2, 2), pad=(1, 1), weight_filler=_msra(),
                         bias_term=False),
        *_bn_scale("1", "conv1", bn_fraction),
        ReLULayer("relu1", ["conv1"], in_place=True),
    ]
    bottom = "conv1"
    plan = [("2_1", 32, 64, 1), ("2_2", 64, 128, 2),
            ("3_1", 128, 128, 1), ("3_2", 128, 256, 2),
            ("4_1", 256, 256, 1), ("4_2", 256, 512, 2),
            ("5_1", 512, 512, 1), ("5_2", 512, 512, 1),
            ("5_3", 512, 512, 1), ("5_4", 512, 512, 1),
            ("5_5", 512, 512, 1), ("5_6", 512, 1024, 2),
            ("6", 1024, 1024, 1)]
    for name, cin, cout, stride in plan:
        ls, bottom = _dw_sep(name, bottom, cin, cout, stride, bn_fraction)
        layers += ls
    layers += [
        PoolingLayer("pool6", [bottom], Pooling.Ave, global_pooling=True),
        ConvolutionLayer("fc7", ["pool6"], kernel=(1, 1),
                         num_output=num_classes, weight_filler=_gauss(0.01),
                         bias_filler=_const(0.0)),
        FlattenLayer("flat7", ["fc7"]),
        SoftmaxWithLoss("loss", ["flat7", "label"]),
        AccuracyLayer("accuracy", ["flat7", "label"], phase="TEST"),
        AccuracyLayer("accuracy_top5", ["flat7", "label"], top_k=5,
                      phase="TEST"),
    ]
    return NetParam("MobileNet_v1", *layers)


def mobilenet_solver() -> SolverConfig:
    """Adapted recipe (the v1 paper trained with RMSProp on an internal
    system and shipped no Caffe solver): SGD momentum 0.9, base_lr 0.01
    stepped /10 — the BN-ful net is schedule-tolerant."""
    return SolverConfig(
        base_lr=0.01, lr_policy="step", gamma=0.1, stepsize=100000,
        momentum=0.9, weight_decay=4e-5, max_iter=300000,
        solver_type="SGD", display=40, snapshot_prefix="mobilenet",
    )


def squeezenet_solver() -> SolverConfig:
    """The official v1.1 recipe: SGD momentum 0.9, base_lr 0.04 with
    linear (poly power 1) decay, weight decay 2e-4 (forresti/SqueezeNet
    solver.prototxt)."""
    return SolverConfig(
        base_lr=0.04, lr_policy="poly", power=1.0, momentum=0.9,
        weight_decay=2e-4, max_iter=170000, solver_type="SGD",
        display=40, snapshot_prefix="squeezenet",
    )


def _shared(m: Message, *names: str) -> Message:
    """Attach named param{} messages for cross-layer weight sharing.
    lr_mults follow the reference siamese file: weights 1, biases 2."""
    for n, lr in zip(names, (1.0, 2.0)):
        m.add("param", Message().set("name", n).set("lr_mult", lr))
    return m


def _siamese_tower(suffix: str, bottom: str, embed_dim: int) -> list[Message]:
    s = suffix
    return [
        _shared(ConvolutionLayer(f"conv1{s}", [bottom], kernel=(5, 5),
                                 num_output=20), "conv1_w", "conv1_b"),
        PoolingLayer(f"pool1{s}", [f"conv1{s}"], Pooling.Max,
                     kernel=(2, 2), stride=(2, 2)),
        _shared(ConvolutionLayer(f"conv2{s}", [f"pool1{s}"], kernel=(5, 5),
                                 num_output=50), "conv2_w", "conv2_b"),
        PoolingLayer(f"pool2{s}", [f"conv2{s}"], Pooling.Max,
                     kernel=(2, 2), stride=(2, 2)),
        _shared(InnerProductLayer(f"ip1{s}", [f"pool2{s}"], num_output=500),
                "ip1_w", "ip1_b"),
        ReLULayer(f"relu1{s}", [f"ip1{s}"], in_place=True),
        _shared(InnerProductLayer(f"ip2{s}", [f"ip1{s}"], num_output=10),
                "ip2_w", "ip2_b"),
        _shared(InnerProductLayer(f"feat{s}", [f"ip2{s}"],
                                  num_output=embed_dim), "feat_w", "feat_b"),
    ]


def mnist_siamese(batch: int = 64, embed_dim: int = 2, margin: float = 1.0) -> Message:
    slice_layer = Message()
    slice_layer.set("name", "slice_pair").set("type", "Slice")
    slice_layer.add("bottom", "pair_data")
    slice_layer.add("top", "data")
    slice_layer.add("top", "data_p")
    slice_layer.set(
        "slice_param", Message().set("slice_dim", 1).set("slice_point", 1)
    )
    loss = Message()
    loss.set("name", "loss").set("type", "ContrastiveLoss")
    for b in ("feat", "feat_p", "sim"):
        loss.add("bottom", b)
    loss.add("top", "loss")
    loss.set("contrastive_loss_param", Message().set("margin", margin))
    return NetParam(
        "mnist_siamese",
        RDDLayer("pair_data", shape=[batch, 2, 28, 28]),
        RDDLayer("sim", shape=[batch]),
        slice_layer,
        *_siamese_tower("", "data", embed_dim),
        *_siamese_tower("_p", "data_p", embed_dim),
        loss,
    )


def mnist_siamese_solver() -> SolverConfig:
    """ref: caffe/examples/siamese/mnist_siamese_solver.prototxt."""
    return SolverConfig(
        base_lr=0.01, lr_policy="inv", gamma=1e-4, power=0.75,
        momentum=0.9, weight_decay=0.0, max_iter=50000,
        solver_type="SGD", display=500,
    )


def _sparse_gauss(std: float, sparse: int) -> Message:
    m = _filler("gaussian", std=std)
    m.set("sparse", sparse)
    return m


def _ae_ip(name: str, bottom: str, n: int) -> Message:
    """Autoencoder InnerProduct: gaussian(std=1, sparse=15) weights, lr_mult
    1/1 with decay_mult 1/0 (ref: mnist_autoencoder.prototxt:58-84)."""
    m = InnerProductLayer(
        name, [bottom], num_output=n,
        weight_filler=_sparse_gauss(1.0, 15),
        bias_filler=_filler("constant", value=0.0),
    )
    for decay in (1.0, 0.0):
        m.add("param", Message().set("lr_mult", 1.0).set("decay_mult", decay))
    return m


def mnist_autoencoder(batch: int = 100) -> Message:
    """Deep autoencoder 784-1000-500-250-30-250-500-1000-784 with sigmoid
    cross-entropy reconstruction loss and a loss_weight=0 euclidean monitor
    (ref: caffe/examples/mnist/mnist_autoencoder.prototxt)."""
    layers = [
        RDDLayer("data", shape=[batch, 1, 28, 28]),
        FlattenLayer("flatdata", ["data"]),
        _ae_ip("encode1", "data", 1000),
        SigmoidLayer("encode1neuron", ["encode1"]),
        _ae_ip("encode2", "encode1neuron", 500),
        SigmoidLayer("encode2neuron", ["encode2"]),
        _ae_ip("encode3", "encode2neuron", 250),
        SigmoidLayer("encode3neuron", ["encode3"]),
        _ae_ip("encode4", "encode3neuron", 30),
        _ae_ip("decode4", "encode4", 250),
        SigmoidLayer("decode4neuron", ["decode4"]),
        _ae_ip("decode3", "decode4neuron", 500),
        SigmoidLayer("decode3neuron", ["decode3"]),
        _ae_ip("decode2", "decode3neuron", 1000),
        SigmoidLayer("decode2neuron", ["decode2"]),
        _ae_ip("decode1", "decode2neuron", 784),
        SigmoidCrossEntropyLossLayer(
            "loss", ["decode1", "flatdata"], loss_weight=1.0,
            top="cross_entropy_loss"),
        SigmoidLayer("decode1neuron", ["decode1"]),
        EuclideanLossLayer(
            "l2_monitor", ["decode1neuron", "flatdata"], loss_weight=0.0,
            top="l2_error"),
    ]
    return NetParam("MNISTAutoencoder", *layers)


def mnist_autoencoder_solver() -> SolverConfig:
    """ref: caffe/examples/mnist/mnist_autoencoder_solver.prototxt."""
    return SolverConfig(
        base_lr=0.01, lr_policy="step", gamma=0.1, stepsize=10000,
        momentum=0.9, weight_decay=0.0005, max_iter=65000,
        solver_type="SGD", display=100, snapshot=10000,
    )


# ---------------------------------------------------------------------------
# Transformer sequence classifier — long-context extra (no reference
# analog: SURVEY §5 "long-context: absent").  A causal decoder stack built
# entirely from prototxt-compatible layers, so the flagship TPU features
# (ring/Ulysses sequence parallelism via a 'seq' mesh axis, flash
# attention) are reachable from the framework's ordinary model front door.
# ---------------------------------------------------------------------------
def _transformer_block(i: int, bottom: str, embed_dim: int, heads: int,
                       ffn_dim: int, rope: bool = False
                       ) -> tuple[list[Message], str]:
    """Pre-LN-free residual block: attention + residual, per-token FFN
    (InnerProduct axis=2) + residual."""
    attn, res, out = f"attn{i}", f"res{i}", f"blk{i}"
    layers = [
        MultiHeadAttentionLayer(attn, [bottom], num_heads=heads,
                                causal=True, rope=rope, top=attn),
        EltwiseLayer(res, [bottom, attn], top=res),
        InnerProductLayer(f"ffn{i}a", [res], num_output=ffn_dim, axis=2,
                          weight_filler=_gauss(0.05)),
        ReLULayer(f"ffn{i}r", [f"ffn{i}a"], in_place=True),
        InnerProductLayer(f"ffn{i}b", [f"ffn{i}a"], num_output=embed_dim,
                          axis=2, weight_filler=_gauss(0.05)),
        EltwiseLayer(out, [res, f"ffn{i}b"], top=out),
    ]
    return layers, out


def transformer(
    batch: int = 32,
    seq_len: int = 32,
    vocab: int = 64,
    embed_dim: int = 32,
    heads: int = 4,
    ffn_dim: int = 64,
    blocks: int = 2,
    num_classes: int = 10,
) -> Message:
    """Causal transformer over [batch, seq_len] token ids -> sequence
    class.  Trains under `ParallelTrainer` on a (data, seq) mesh with the
    attention cores running ring/Ulysses sequence parallelism."""
    layers = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch]),
        EmbedLayer("embed", ["data"], input_dim=vocab,
                   num_output=embed_dim, top="embed"),
    ]
    bottom = "embed"
    for i in range(1, blocks + 1):
        blk, bottom = _transformer_block(i, bottom, embed_dim, heads, ffn_dim)
        layers += blk
    layers += [
        InnerProductLayer("fc", [bottom], num_output=num_classes,
                          weight_filler=_gauss(0.05)),
        SoftmaxWithLoss("loss", ["fc", "label"]),
        AccuracyLayer("accuracy", ["fc", "label"], phase="TEST"),
    ]
    return NetParam("Transformer", *layers)


def transformer_solver() -> SolverConfig:
    return SolverConfig(
        base_lr=0.1, lr_policy="fixed", momentum=0.9, weight_decay=1e-4,
        max_iter=2000, solver_type="SGD", display=100,
    )


# ---------------------------------------------------------------------------
# Char-level causal language model — the long-context story end to end
# (no reference analog: SURVEY §5 "long-context: absent"; RNN/sequence
# work was the reference's declared future work, ROADMAP.md:12).  Same
# decoder stack as `transformer` but with rotary position embeddings and
# a PER-TOKEN head: InnerProduct(axis=2) logits [B, S, V] against
# shifted labels [B, S] through SoftmaxWithLoss(axis=2) — the causal-LM
# objective expressed entirely in prototxt-compatible layers, so it
# trains/snapshots/deploys through every ordinary path and scales over a
# (data × seq) mesh with ring/Ulysses sequence parallelism unchanged.
# Data side: `data/text.py` (CharVocab + next-char windows).
# ---------------------------------------------------------------------------
def charlm(
    batch: int = 32,
    seq_len: int = 128,
    vocab: int = 128,
    embed_dim: int = 64,
    heads: int = 4,
    ffn_dim: int = 128,
    blocks: int = 2,
) -> Message:
    """Causal char LM over [batch, seq_len] ids -> per-token next-char
    logits.  loss is mean cross-entropy per token (nats); bits/char =
    loss / ln 2."""
    layers = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab,
                   num_output=embed_dim, top="embed"),
    ]
    bottom = "embed"
    for i in range(1, blocks + 1):
        blk, bottom = _transformer_block(i, bottom, embed_dim, heads,
                                         ffn_dim, rope=True)
        layers += blk
    layers += [
        InnerProductLayer("fc", [bottom], num_output=vocab, axis=2,
                          weight_filler=_gauss(0.05)),
        SoftmaxWithLoss("loss", ["fc", "label"], axis=2),
        AccuracyLayer("accuracy", ["fc", "label"], phase="TEST", axis=2),
    ]
    return NetParam("CharLM", *layers)


def charlm_solver() -> SolverConfig:
    # Adam: the standard small-transformer recipe (SGD needs warmup at
    # this depth; cf. docs/CONVERGENCE.md's GoogLeNet optimizer note —
    # there the published recipe was SGD, here there is no published
    # reference recipe to honor).
    return SolverConfig(
        base_lr=2e-3, lr_policy="fixed", momentum=0.9, weight_decay=0.0,
        max_iter=2000, solver_type="Adam", display=100,
    )


# ---------------------------------------------------------------------------
# OLMoE — a real sparse decoder (Muennighoff et al. 2024, arXiv:2409.02060;
# allenai/OLMoE-1B-7B-0125 config.json; no reference analog).  Per layer:
# pre-norm RMSNorm -> fused q/k/v projection without biases -> RMSNorm over
# the whole hidden-wide q and k (QK-norm) -> rotate-half RoPE -> causal
# softmax attention -> output projection -> residual -> RMSNorm -> router
# over the experts (f32 softmax, top-k, weights NOT renormalised) ->
# dropless SwiGLU experts -> residual.  Final RMSNorm, untied head, token
# cross-entropy + load-balancing + router z-loss (per layer, averaged over
# the layers, as the model was trained).  Data side: ``--data tokens:<file>``
# (`data/text.py` token_windows).  `transformer`/`charlm` above keep their
# toy block; their one-definition merge with this one is ROADMAP D10.
# ---------------------------------------------------------------------------
def olmoe(
    batch: int = 4,
    seq_len: int = 4096,
    vocab: int = 50304,
    hidden: int = 2048,
    heads: int = 16,
    experts: int = 64,
    top_k: int = 8,
    expert_dim: int = 1024,
    layers: int = 16,
    rms_norm_eps: float = 1e-5,
    rope_theta: float = 10000.0,
    lb_weight: float = 0.01,
    z_weight: float = 0.001,
    init_std: float = 0.02,
) -> Message:
    """OLMoE-1B-7B at its published sizes by default: [batch, seq_len]
    token ids -> per-token next-token logits.  ``loss`` is the mean
    cross-entropy per token; each layer's ``lb<i>`` / ``z<i>`` tops carry
    ``lb_weight`` / ``z_weight`` over the layer count as their
    ``loss_weight``; ``load<i>`` is the layer's tokens per expert."""
    init = _gauss(init_std)
    net = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="embed"),
    ]
    x = "embed"
    for i in range(1, layers + 1):
        net += [
            RMSNormLayer(f"norm{i}a", [x], eps=rms_norm_eps),
            MultiHeadAttentionLayer(
                f"attn{i}", [f"norm{i}a"], num_heads=heads, causal=True,
                rope=True, rope_theta=rope_theta, bias_term=False,
                qk_norm=True, qk_norm_eps=rms_norm_eps, weight_filler=init),
            EltwiseLayer(f"res{i}a", [x, f"attn{i}"], top=f"res{i}a"),
            RMSNormLayer(f"norm{i}b", [f"res{i}a"], eps=rms_norm_eps),
            MoELayer(
                f"moe{i}", [f"norm{i}b"], num_experts=experts,
                hidden_dim=expert_dim, top_k=top_k, expert_act="swiglu",
                weight_filler=init,
                loss_tops=((f"lb{i}", lb_weight / layers),
                           (f"z{i}", z_weight / layers), (f"load{i}", 0.0))),
            EltwiseLayer(f"res{i}b", [f"res{i}a", f"moe{i}"], top=f"res{i}b"),
        ]
        x = f"res{i}b"
    net += [
        RMSNormLayer("norm_f", [x], eps=rms_norm_eps),
        InnerProductLayer("lm_head", ["norm_f"], num_output=vocab, axis=2,
                          weight_filler=init, bias_term=False),
        SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2),
        AccuracyLayer("accuracy", ["lm_head", "label"], phase="TEST", axis=2),
    ]
    return NetParam("OLMoE", *net)


def olmoe_solver() -> SolverConfig:
    """The OLMoE paper's optimizer (arXiv:2409.02060, Table 12): AdamW,
    peak lr 4e-4, betas 0.9 / 0.95, eps 1e-8, decoupled weight decay 0.1
    on every parameter, gradient clipping at global norm 1.0.  The
    warm-up and cosine schedule are left to the prototxt's lr_policy."""
    return SolverConfig(
        base_lr=4e-4, lr_policy="fixed", momentum=0.9, momentum2=0.95,
        delta=1e-8, weight_decay=0.1, clip_gradients=1.0,
        max_iter=10000, solver_type="AdamW", display=100,
    )


def joyai_flash(
    batch: int = 1,
    seq_len: int = 4096,
    vocab: int = 129280,
    hidden: int = 2048,
    heads: int = 32,
    q_lora_rank: int = 1536,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    dense_dim: int = 7168,
    dense_layers: int = 1,
    experts: int = 256,
    top_k: int = 8,
    expert_dim: int = 768,
    shared_dim: int = 768,
    layers: int = 40,
    experts_held: int | None = None,
    first_expert: int = 0,
    rms_norm_eps: float = 1e-6,
    rope_theta: float = 32e6,
    routed_scaling_factor: float = 2.5,
    bias_update_rate: float = 0.001,
    mtp_weight: float = 0.3,
    init_std: float = 0.006,
) -> Message:
    """JoyAI-LLM-Flash (jdopensource, config.json; the DeepSeek-V3 block)
    at its published sizes by default: [batch, seq_len] token ids ->
    per-token next-token logits, and one multi-token-prediction module.

    ``layers`` blocks of latent attention and a feed-forward: a dense
    gated MLP in the first ``dense_layers``, then sigmoid-routed experts
    beside one shared expert, selected with a balancing bias.
    ``experts_held`` / ``first_expert`` give this chip's share of every
    expert layer (all ``experts`` by default), ``vocab`` the rows of the
    embedding and the head it holds.

    The MTP module (DeepSeek-V3, arXiv:2412.19437 section 2.2) takes the
    residual stream before the final norm and the embedding of the NEXT
    token (``label``), through the main model's embedding and head (shared
    by ``param { name }``), one more whole block, and predicts the token
    after next: ``mtp_loss`` is the mean cross-entropy of positions
    0..S-2 against ``label`` shifted by one, weighted ``mtp_weight``."""
    init = _gauss(init_std)

    def attention(name, bottom):
        return LatentAttentionLayer(
            name, [bottom], num_heads=heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, rope_interleave=True,
            norm_eps=rms_norm_eps, weight_filler=init)

    def expert_layer(name, bottom):
        return MoELayer(
            name, [bottom], num_experts=experts, hidden_dim=expert_dim,
            top_k=top_k, expert_act="swiglu", norm_topk_prob=True,
            scoring_func="sigmoid",
            routed_scaling_factor=routed_scaling_factor,
            bias_update_rate=bias_update_rate, shared_hidden_dim=shared_dim,
            experts_held=experts_held, first_expert=first_expert,
            weight_filler=init)

    def block(x, norm_a, attn, res_a, norm_b, ffn, res_b, dense=False):
        return [
            RMSNormLayer(norm_a, [x], eps=rms_norm_eps),
            attention(attn, norm_a),
            EltwiseLayer(res_a, [x, attn], top=res_a),
            RMSNormLayer(norm_b, [res_a], eps=rms_norm_eps),
            GatedMLPLayer(ffn, [norm_b], dense_dim, weight_filler=init)
            if dense else expert_layer(ffn, norm_b),
            EltwiseLayer(res_b, [res_a, ffn], top=res_b),
        ]

    net = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="embed",
                   param_name="embed_w"),
    ]
    x = "embed"
    for i in range(1, layers + 1):
        dense = i <= dense_layers
        net += block(x, f"norm{i}a", f"attn{i}", f"res{i}a", f"norm{i}b",
                     f"mlp{i}" if dense else f"moe{i}", f"res{i}b", dense)
        x = f"res{i}b"
    net += [
        RMSNormLayer("norm_f", [x], eps=rms_norm_eps),
        InnerProductLayer("lm_head", ["norm_f"], num_output=vocab, axis=2,
                          weight_filler=init, bias_term=False,
                          param_name="head_w"),
        SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2),
        AccuracyLayer("accuracy", ["lm_head", "label"], phase="TEST", axis=2),
        # multi-token prediction: h_i and Emb(t_{i+1}) -> t_{i+2}
        EmbedLayer("mtp_embed", ["label"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="mtp_embed",
                   param_name="embed_w"),
        RMSNormLayer("mtp_norm_h", [x], eps=rms_norm_eps),
        RMSNormLayer("mtp_norm_e", ["mtp_embed"], eps=rms_norm_eps),
        ConcatLayer("mtp_cat", ["mtp_norm_h", "mtp_norm_e"], axis=2),
        InnerProductLayer("mtp_proj", ["mtp_cat"], num_output=hidden, axis=2,
                          weight_filler=init, bias_term=False),
        *block("mtp_proj", "mtp_norm_a", "mtp_attn", "mtp_res_a",
               "mtp_norm_b", "mtp_moe", "mtp_res_b"),
        RMSNormLayer("mtp_norm_f", ["mtp_res_b"], eps=rms_norm_eps),
        # the last position has no token after next: positions 0..S-2
        # against label_1..label_{S-1}
        SliceLayer("mtp_positions", ["mtp_norm_f"],
                   ["mtp_hidden", "mtp_hidden_last"], axis=1,
                   slice_points=[seq_len - 1]),
        SliceLayer("mtp_targets", ["label"], ["label_first", "label_next"],
                   axis=1, slice_points=[1]),
        InnerProductLayer("mtp_head", ["mtp_hidden"], num_output=vocab,
                          axis=2, weight_filler=init, bias_term=False,
                          param_name="head_w"),
        SoftmaxWithLoss("mtp_loss", ["mtp_head", "label_next"],
                        loss_weight=mtp_weight, axis=2, keep_value=True),
    ]
    return NetParam("JoyAI-LLM-Flash", *net)


def joyai_flash_solver() -> SolverConfig:
    """AdamW as DeepSeek-V3 trained its block (arXiv:2412.19437 section
    4.2: betas 0.9 / 0.95, decoupled weight decay 0.1, gradient clipping
    at global norm 1.0, peak lr 2.2e-4); eps 1e-8.  The warm-up and the
    decay schedule are left to the prototxt's lr_policy."""
    return SolverConfig(
        base_lr=2.2e-4, lr_policy="fixed", momentum=0.9, momentum2=0.95,
        delta=1e-8, weight_decay=0.1, clip_gradients=1.0,
        max_iter=10000, solver_type="AdamW", display=100,
    )


# ---------------------------------------------------------------------------
# Phi-4-mini-flash-reasoning — the SambaY decoder-hybrid-decoder (Ren et al.
# 2025, arXiv:2507.06607; microsoft/Phi-4-mini-flash-reasoning config.json,
# ``model_type: phi4flash``; no reference analog).  Every layer is
# u = x + Mix(LayerNorm(x)); x' = u + SwiGLU(LayerNorm(u)), and the token
# mixer is set by the layer's PUBLISHED index (``phi4_flash_role``): a
# self-decoder of Mamba layers and window differential attention, the
# Mamba layer whose scan output is kept as the MEMORY, the one full
# attention layer whose keys and values are kept, and a cross-decoder of
# gated memory units and cross-attention that read those two blobs.  No
# positional encoding; the head is the embedding (``param { name }``).
# ---------------------------------------------------------------------------
def phi4_flash_role(i: int, layers: int, mb_per_layer: int = 2) -> str:
    """The token mixer of published layer ``i`` of ``layers``, as the
    published modeling code assigns it: ``i % mb_per_layer == 0`` is a
    Mamba-side layer, else an attention-side one; below ``layers / 2`` the
    self-decoder ("mamba" / "window"), at ``layers / 2`` the memory's
    Mamba layer ("memory"), the layer after it the full attention layer
    whose keys and values are kept ("full"), then the cross-decoder
    ("gmu" / "cross")."""
    half = layers // 2
    if layers % 2 or half % mb_per_layer:
        raise ValueError(f"{layers} layers: half of them must be a multiple "
                         f"of mb_per_layer ({mb_per_layer})")
    if not 0 <= i < layers:
        raise ValueError(f"layer {i} of {layers}")
    mamba_side = i % mb_per_layer == 0
    if i < half:
        return "mamba" if mamba_side else "window"
    if i == half:
        return "memory"
    if i == half + 1:
        return "full"
    return "gmu" if mamba_side else "cross"


def phi4_flash_lambda_init(i: int) -> float:
    """Differential attention's lambda_init at published depth ``i``
    (arXiv:2410.05258 section 2.1): 0.8 - 0.6 exp(-0.3 i)."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def phi4_flash(
    batch: int = 1,
    seq_len: int = 2048,
    vocab: int = 200064,
    hidden: int = 2560,
    heads: int = 40,
    kv_heads: int = 20,
    mlp_dim: int = 10240,
    layers: int = 32,
    mb_per_layer: int = 2,
    window: int = 512,
    d_state: int = 16,
    d_conv: int = 4,
    expand: int = 2,
    dt_rank: int | None = None,
    layer_norm_eps: float = 1e-5,
    init_std: float = 0.02,
    kept_layers: tuple[int, ...] | None = None,
) -> Message:
    """Phi-4-mini-flash-reasoning at its published sizes by default (32
    layers, 3.85 B parameters): [batch, seq_len] token ids -> per-token
    next-token logits over ``vocab`` rows; ``loss`` is the mean
    cross-entropy over every position.

    ``kept_layers`` (published indices, ascending; all by default) builds
    a cut: every kept layer keeps the role and the lambda_init of its
    published index, and a layer that reads the memory or the kept keys
    and values needs its producer (``layers / 2`` / ``layers / 2 + 1``)
    kept too.  Layer i's prototxt layers are ``norm<i>a``, the mixer
    (``mamba<i>`` / ``attn<i>`` / ``gmu<i>`` / ``xattn<i>``), ``res<i>a``,
    ``norm<i>b``, ``mlp<i>``, ``res<i>b``; the memory is the blob
    ``memory``, the keys and values ``yoco_k`` / ``yoco_v``."""
    init = _gauss(init_std)
    kept = tuple(range(layers)) if kept_layers is None else tuple(kept_layers)
    if list(kept) != sorted(set(kept)):
        raise ValueError(f"kept_layers must ascend: {kept}")
    roles = {i: phi4_flash_role(i, layers, mb_per_layer) for i in kept}
    half = layers // 2
    for role, producer in (("gmu", half), ("cross", half + 1)):
        if role in roles.values() and producer not in roles:
            raise ValueError(
                f"a {role!r} layer is kept without layer {producer}, whose "
                "output it reads")

    def mixer(i, bottom):
        role = roles[i]
        if role in ("mamba", "memory"):
            return MambaLayer(
                f"mamba{i}", [bottom], d_state=d_state, d_conv=d_conv,
                expand=expand, dt_rank=dt_rank, weight_filler=init,
                memory_top="memory" if role == "memory" else None)
        if role == "gmu":
            return GatedMemoryUnitLayer(f"gmu{i}", [bottom, "memory"],
                                        weight_filler=init)
        cross = role == "cross"
        return DifferentialAttentionLayer(
            f"xattn{i}" if cross else f"attn{i}",
            [bottom, "yoco_k", "yoco_v"] if cross else [bottom],
            num_heads=heads, num_kv_heads=kv_heads,
            lambda_init=phi4_flash_lambda_init(i),
            window=window if role == "window" else 0,
            norm_eps=layer_norm_eps, weight_filler=init,
            kv_tops=("yoco_k", "yoco_v") if role == "full" else ())

    net = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="embed",
                   param_name="embed_w"),
    ]
    x = "embed"
    for i in kept:
        mix = mixer(i, f"norm{i}a")
        name = mix.get_str("name")
        net += [
            LayerNormLayer(f"norm{i}a", [x], eps=layer_norm_eps),
            mix,
            EltwiseLayer(f"res{i}a", [x, name], top=f"res{i}a"),
            LayerNormLayer(f"norm{i}b", [f"res{i}a"], eps=layer_norm_eps),
            GatedMLPLayer(f"mlp{i}", [f"norm{i}b"], mlp_dim,
                          weight_filler=init),
            EltwiseLayer(f"res{i}b", [f"res{i}a", f"mlp{i}"], top=f"res{i}b"),
        ]
        x = f"res{i}b"
    net += [
        LayerNormLayer("norm_f", [x], eps=layer_norm_eps),
        # tie_word_embeddings: the head IS the embedding
        InnerProductLayer("lm_head", ["norm_f"], num_output=vocab, axis=2,
                          weight_filler=init, bias_term=False,
                          param_name="embed_w"),
        SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2),
        AccuracyLayer("accuracy", ["lm_head", "label"], phase="TEST", axis=2),
    ]
    return NetParam("Phi-4-mini-flash-reasoning", *net)


def phi4_flash_solver() -> SolverConfig:
    """AdamW, betas 0.9 / 0.95, eps 1e-8, decoupled weight decay 0.1 on
    every parameter, gradient clipping at global norm 1.0, peak lr 4e-4:
    the recipe of this repo's other decoders (OLMoE's Table 12); the
    Phi-4-mini-flash card and config.json state none, so each value is an
    assumption the benchmark's configuration file lists.  The schedule is
    left to the prototxt's lr_policy."""
    return SolverConfig(
        base_lr=4e-4, lr_policy="fixed", momentum=0.9, momentum2=0.95,
        delta=1e-8, weight_decay=0.1, clip_gradients=1.0,
        max_iter=10000, solver_type="AdamW", display=100,
    )


# ---------------------------------------------------------------------------
# Ouro — a looped language model (ByteDance, "Scaling Latent Reasoning via
# Looped Language Models", 2025; ByteDance/Ouro-2.6B config.json,
# ``model_type: ouro``; no reference analog).  ONE stack of decoder blocks
# runs ``ut_steps`` times on the same weights: h_0 = Embed(tokens),
# h_t = N_f(Stack(h_{t-1})), the final RMSNorm inside the loop.  A block
# has four RMSNorms ("sandwich"): a = x + N2(Attn(N1(x))),
# y = a + N4(MLP(N3(a))); attention without biases or QK-norm, rotate-half
# RoPE, a SwiGLU MLP.  Behind the loop one exit gate (Linear hidden -> 1,
# shared over the steps) and the untied head read the state of EVERY
# step, and the loss is the expected cross-entropy under the gate's
# distribution over exit steps with an entropy regulariser
# (ops/loss.py ExitWeightedLoss).  The stack is declared once, as a looped
# region of the layer graph (compiler/graph.py LoopRegion).
# ---------------------------------------------------------------------------
def ouro(
    batch: int = 1,
    seq_len: int = 4096,
    vocab: int = 49152,
    hidden: int = 2048,
    heads: int = 16,
    mlp_dim: int = 5632,
    layers: int = 48,
    ut_steps: int = 4,
    rms_norm_eps: float = 1e-6,
    rope_theta: float = 1e6,
    entropy_weight: float = 0.1,
    init_std: float = 0.02,
) -> Message:
    """Ouro-2.6B at its published sizes by default: [batch, seq_len]
    token ids -> the next-token logits of every one of the ``ut_steps``
    passes (``lm_head``, [ut_steps * batch, seq_len, vocab], pass-major)
    and the exit gate's (``exit_gate``).  ``loss`` is the exit-weighted
    loss; ``step_loss`` [ut_steps] and ``exit_mean_step`` are its
    read-outs at weight 0.  Block i's layers are ``norm<i>a``, ``attn<i>``,
    ``norm<i>b``, ``res<i>a``, ``norm<i>c``, ``mlp<i>``, ``norm<i>d``,
    ``res<i>b``; the region ``ut`` spans ``norm0a`` .. ``norm_f`` and its
    collected top is ``states``."""
    init = _gauss(init_std)
    norm = lambda name, bottom: RMSNormLayer(name, [bottom], eps=rms_norm_eps)
    net = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="embed"),
    ]
    x = "embed"
    for i in range(layers):
        net += [
            norm(f"norm{i}a", x),
            MultiHeadAttentionLayer(
                f"attn{i}", [f"norm{i}a"], num_heads=heads, causal=True,
                rope=True, rope_theta=rope_theta, bias_term=False,
                weight_filler=init),
            norm(f"norm{i}b", f"attn{i}"),
            EltwiseLayer(f"res{i}a", [x, f"norm{i}b"], top=f"res{i}a"),
            norm(f"norm{i}c", f"res{i}a"),
            GatedMLPLayer(f"mlp{i}", [f"norm{i}c"], mlp_dim,
                          weight_filler=init),
            norm(f"norm{i}d", f"mlp{i}"),
            EltwiseLayer(f"res{i}b", [f"res{i}a", f"norm{i}d"],
                         top=f"res{i}b"),
        ]
        x = f"res{i}b"
    net += [
        norm("norm_f", x),
        InnerProductLayer("exit_gate", ["states"], num_output=1, axis=2,
                          weight_filler=init),
        InnerProductLayer("lm_head", ["states"], num_output=vocab, axis=2,
                          weight_filler=init, bias_term=False),
        ExitWeightedLossLayer("loss", ["lm_head", "label", "exit_gate"],
                              steps=ut_steps, entropy_weight=entropy_weight),
    ]
    loop = LoopRegion("ut", ut_steps, "norm0a", "norm_f", carry_in="embed",
                      carry_out="norm_f", collect=[("norm_f", "states")])
    return NetParam("Ouro", *net, loops=[loop])


def ouro_solver() -> SolverConfig:
    """AdamW, lr 3e-4, betas 0.9 / 0.95, eps 1e-8, decoupled weight decay
    0.1 on every parameter, gradient clipping at global norm 1.0, a FIXED
    lr: the report's recipe as remembered, every value an assumption the
    benchmark's configuration file lists.  The schedule is left to the
    prototxt's lr_policy."""
    return SolverConfig(
        base_lr=3e-4, lr_policy="fixed", momentum=0.9, momentum2=0.95,
        delta=1e-8, weight_decay=0.1, clip_gradients=1.0,
        max_iter=10000, solver_type="AdamW", display=100,
    )


# ---------------------------------------------------------------------------
# Qwen3-Next-80B-A3B — gated-DeltaNet linear attention three layers in four,
# output-gated grouped softmax attention every fourth, and in every layer
# top-10-of-512 softmax-routed experts beside one sigmoid-gated shared
# expert (Qwen/Qwen3-Next-80B-A3B-Instruct config.json, ``model_type:
# qwen3_next``; Gated Delta Networks, arXiv:2412.06464; no reference
# analog).  Pre-norm blocks with zero-centred RMSNorm, untied head.
# ---------------------------------------------------------------------------
def qwen3_next(
    batch: int = 1,
    seq_len: int = 4096,
    vocab: int = 151936,
    hidden: int = 2048,
    layers: int = 48,
    full_attention_interval: int = 4,
    heads: int = 16,
    kv_heads: int = 2,
    head_dim: int = 256,
    partial_rotary_factor: float = 0.25,
    rope_theta: float = 1e7,
    linear_k_heads: int = 16,
    linear_v_heads: int = 32,
    linear_k_dim: int = 128,
    linear_v_dim: int = 128,
    conv_kernel: int = 4,
    experts: int = 512,
    top_k: int = 10,
    expert_dim: int = 512,
    shared_dim: int = 512,
    experts_held: int | None = None,
    first_expert: int = 0,
    rms_norm_eps: float = 1e-6,
    aux_loss_coef: float = 0.001,
    init_std: float = 0.02,
) -> Message:
    """Qwen3-Next-80B-A3B at its published sizes by default: [batch,
    seq_len] token ids -> per-token next-token logits.  Block i (from 0):
    ``norm<i>a`` -> the mixer (``attn<i>`` where (i + 1) %
    ``full_attention_interval`` == 0, else ``gdn<i>``) -> ``res<i>a`` ->
    ``norm<i>b`` -> ``moe<i>`` -> ``res<i>b``.  ``loss`` is the mean
    cross-entropy per token; each expert layer's ``lb<i>`` top carries the
    load-balancing loss at ``aux_loss_coef`` (the family's
    ``router_aux_loss_coef``: a sum over the layers, not a mean).
    ``experts_held`` / ``first_expert`` give this chip's share of every
    expert layer (all ``experts`` by default), ``vocab`` the rows of the
    embedding and the head it holds.  The family's multi-token-prediction
    module is not built."""
    init = _gauss(init_std)
    norm = lambda name, bottom: RMSNormLayer(
        name, [bottom], eps=rms_norm_eps, zero_centered=True)
    net = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="embed"),
    ]
    x = "embed"
    for i in range(layers):
        if (i + 1) % full_attention_interval == 0:
            mixer = GatedAttentionLayer(
                f"attn{i}", [f"norm{i}a"], num_heads=heads,
                num_kv_heads=kv_heads, head_dim=head_dim,
                rotary_dim=int(head_dim * partial_rotary_factor),
                rope_theta=rope_theta, norm_eps=rms_norm_eps,
                weight_filler=init)
        else:
            mixer = GatedDeltaNetLayer(
                f"gdn{i}", [f"norm{i}a"], num_k_heads=linear_k_heads,
                num_v_heads=linear_v_heads, head_k_dim=linear_k_dim,
                head_v_dim=linear_v_dim, conv_kernel=conv_kernel,
                norm_eps=rms_norm_eps, weight_filler=init)
        name = mixer.get_str("name")
        net += [
            norm(f"norm{i}a", x),
            mixer,
            EltwiseLayer(f"res{i}a", [x, name], top=f"res{i}a"),
            norm(f"norm{i}b", f"res{i}a"),
            MoELayer(
                f"moe{i}", [f"norm{i}b"], num_experts=experts,
                hidden_dim=expert_dim, top_k=top_k, expert_act="swiglu",
                norm_topk_prob=True, shared_hidden_dim=shared_dim,
                shared_gate=True, experts_held=experts_held,
                first_expert=first_expert, weight_filler=init,
                loss_tops=((f"lb{i}", aux_loss_coef),)),
            EltwiseLayer(f"res{i}b", [f"res{i}a", f"moe{i}"], top=f"res{i}b"),
        ]
        x = f"res{i}b"
    net += [
        norm("norm_f", x),
        InnerProductLayer("lm_head", ["norm_f"], num_output=vocab, axis=2,
                          weight_filler=init, bias_term=False),
        SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2),
        AccuracyLayer("accuracy", ["lm_head", "label"], phase="TEST", axis=2),
    ]
    return NetParam("Qwen3-Next", *net)


def qwen3_next_solver() -> SolverConfig:
    """AdamW, lr 3e-4, betas 0.9 / 0.95, eps 1e-8, decoupled weight decay
    0.1 on every parameter, gradient clipping at global norm 1.0, a FIXED
    lr: the family's card states no recipe, so every value is an
    assumption the benchmark's configuration file lists.  The schedule is
    left to the prototxt's lr_policy."""
    return SolverConfig(
        base_lr=3e-4, lr_policy="fixed", momentum=0.9, momentum2=0.95,
        delta=1e-8, weight_decay=0.1, clip_gradients=1.0,
        max_iter=10000, solver_type="AdamW", display=100,
    )


# ---------------------------------------------------------------------------
# Laguna-XS.2 — grouped softmax attention whose layers differ by KIND: three
# in four see a window of 512 keys with 64 query heads and plain RoPE over
# the whole head, every fourth sees every key with 48 query heads and YaRN
# over half a head; all over 8 key/value heads of 128, each with a
# head-wise sigmoid gate on its output; one leading dense SwiGLU, then
# top-8-of-256 sigmoid-routed experts beside one shared expert
# (poolside/Laguna-XS.2 config.json, ``model_type: laguna``; no reference
# analog).  Pre-norm blocks, plain RMSNorm, untied head.
# ---------------------------------------------------------------------------
LAGUNA_ROPE_PARAMETERS = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}


def laguna(
    batch: int = 1,
    seq_len: int = 8192,
    vocab: int = 100352,
    hidden: int = 2048,
    layers: int = 40,
    layer_types: tuple = ("full_attention",) + ("sliding_attention",) * 3,
    heads_per_layer: tuple = (48, 64, 64, 64),
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 39,
    kv_heads: int = 8,
    head_dim: int = 128,
    window: int = 512,
    rope_parameters: dict | None = None,
    dense_dim: int = 8192,
    experts: int = 256,
    top_k: int = 8,
    expert_dim: int = 512,
    shared_dim: int = 512,
    routed_scaling_factor: float = 2.5,
    experts_held: int | None = None,
    first_expert: int = 0,
    rms_norm_eps: float = 1e-6,
    aux_loss_coef: float = 0.001,
    init_std: float = 0.02,
) -> Message:
    """Laguna-XS.2 at its published sizes by default: [batch, seq_len]
    token ids -> per-token next-token logits.  Block i (from 0):
    ``norm<i>a`` -> ``attn<i>`` -> ``res<i>a`` -> ``norm<i>b`` ->
    ``mlp<i>`` (dense) or ``moe<i>`` -> ``res<i>b``, each sized by the
    published per-layer lists: ``layer_types[i]`` (window or not, and
    which entry of ``rope_parameters``, the family's own group of that
    name, turns its heads), ``heads_per_layer[i]`` query heads,
    ``mlp_layer_types[i]``.  A list shorter than ``layers`` is a period
    and repeats; a longer one (the whole model's, beside a cut's depth)
    is read from its start.  ``loss`` is the mean cross-entropy per
    token; each expert layer's ``lb<i>`` top carries the load-balancing
    loss at ``aux_loss_coef`` (a sum over the layers).  ``experts_held``
    / ``first_expert`` give this chip's share of every expert layer (all
    ``experts`` by default), ``vocab`` the rows of the embedding and the
    head it holds."""
    init = _gauss(init_std)
    ropes = rope_parameters or LAGUNA_ROPE_PARAMETERS
    net = [
        RDDLayer("data", shape=[batch, seq_len]),
        RDDLayer("label", shape=[batch, seq_len]),
        EmbedLayer("embed", ["data"], input_dim=vocab, num_output=hidden,
                   weight_filler=init, bias_term=False, top="embed"),
    ]
    x = "embed"
    for i in range(layers):
        kind = layer_types[i % len(layer_types)]
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        r = ropes[kind]
        rope_type = r.get("rope_type", "default")
        if rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {rope_type!r} of {kind}")
        if mlp_layer_types[i % len(mlp_layer_types)] == "dense":
            ffn = GatedMLPLayer(f"mlp{i}", [f"norm{i}b"], dense_dim,
                                weight_filler=init)
        else:
            ffn = MoELayer(
                f"moe{i}", [f"norm{i}b"], num_experts=experts,
                hidden_dim=expert_dim, top_k=top_k, expert_act="swiglu",
                norm_topk_prob=True, scoring_func="sigmoid",
                routed_scaling_factor=routed_scaling_factor,
                shared_hidden_dim=shared_dim, experts_held=experts_held,
                first_expert=first_expert, weight_filler=init,
                loss_tops=((f"lb{i}", aux_loss_coef),))
        name = ffn.get_str("name")
        net += [
            RMSNormLayer(f"norm{i}a", [x], eps=rms_norm_eps),
            GatedAttentionLayer(
                f"attn{i}", [f"norm{i}a"],
                num_heads=heads_per_layer[i % len(heads_per_layer)],
                num_kv_heads=kv_heads, head_dim=head_dim,
                rotary_dim=int(head_dim * r.get("partial_rotary_factor", 1)),
                rope_theta=float(r["rope_theta"]), norm_eps=rms_norm_eps,
                weight_filler=init, qk_norm=False, head_gate=True,
                window=window if kind == "sliding_attention" else 0,
                rope_scaling={k: r[k] for k in (
                    "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow", "attention_factor")
                    if k in r} if rope_type == "yarn" else None),
            EltwiseLayer(f"res{i}a", [x, f"attn{i}"], top=f"res{i}a"),
            RMSNormLayer(f"norm{i}b", [f"res{i}a"], eps=rms_norm_eps),
            ffn,
            EltwiseLayer(f"res{i}b", [f"res{i}a", name], top=f"res{i}b"),
        ]
        x = f"res{i}b"
    net += [
        RMSNormLayer("norm_f", [x], eps=rms_norm_eps),
        InnerProductLayer("lm_head", ["norm_f"], num_output=vocab, axis=2,
                          weight_filler=init, bias_term=False),
        SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2),
        AccuracyLayer("accuracy", ["lm_head", "label"], phase="TEST", axis=2),
    ]
    return NetParam("Laguna", *net)


def laguna_solver() -> SolverConfig:
    """AdamW, lr 3e-4, betas 0.9 / 0.95, eps 1e-8, decoupled weight decay
    0.1 on every parameter, gradient clipping at global norm 1.0, a FIXED
    lr: the model's card states no recipe, so every value is an
    assumption the benchmark's configuration file lists.  The schedule is
    left to the prototxt's lr_policy."""
    return SolverConfig(
        base_lr=3e-4, lr_policy="fixed", momentum=0.9, momentum2=0.95,
        delta=1e-8, weight_decay=0.1, clip_gradients=1.0,
        max_iter=10000, solver_type="AdamW", display=100,
    )


# ---------------------------------------------------------------------------
# Cached per-token decode step (ISSUE 19, ROADMAP item 4).
#
# The rectangle decode path (serve/continuous.py) rebuilds the FULL
# [slots, seq_len] forward for every emitted token — O(seq_len) recompute
# per token, because the prototxt graph has no KV cache (the gap
# models/generate.py documents).  The builders below grow the
# transformer families a cached twin: ``build_decode_step`` replays the
# SAME layer graph one token at a time against a block-paged KV pool
# (ops/pallas_kernels.paged_attention), and ``build_prefill`` runs the
# ordinary full-window forward once while also writing every layer's
# K/V into the pool.  Both are mini-interpreters over ``network.layers``
# that call each non-attention layer's own ``layer.apply`` — Embed /
# Eltwise / InnerProduct(axis=2) / ReLU math is literally the layer's
# own code, so there is no second implementation to drift; only the
# attention core is swapped for its cached form (the exact qkv/rope/
# out-proj expressions from ops/attention.py with the S axis narrowed
# to the current token).
#
# Pool layout (shared with serve/paged.py): K/V arenas
# [n_attn_layers, num_blocks, block_tokens, heads, head_dim]; one
# per-slot block table [MB] int32 shared by all layers (every layer
# caches the same token at the same (block, offset)); block 0 is the
# null block inactive table entries point at — masked columns
# contribute exactly 0.0 after softmax, so its garbage never reaches a
# live row's output.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """Static geometry of one transformer family's cached decode path
    (what serve/paged.py prices blocks and arenas from)."""

    vocab: int
    embed_dim: int
    heads: int
    head_dim: int
    seq_len: int
    attn_layers: tuple
    end: str


def decode_spec(network, end: str = "fc") -> DecodeSpec:
    """Introspect a TEST-phase transformer ``Network`` into the static
    geometry the paged decode step needs.  Raises ``ValueError`` for
    any family whose graph the cached step cannot replay exactly: the
    head must be a per-token InnerProduct (axis=2 — the charlm LM head;
    the axis=1 sequence CLASSIFIER head has no per-token decode
    meaning), attention must be causal with one head count, and every
    layer up to the head must be one of the five cached-twin types."""
    from sparknet_tpu.ops.attention import MultiHeadAttentionLayer as _Attn
    from sparknet_tpu.ops.blocks import Eltwise, Embed, InnerProduct
    from sparknet_tpu.ops.data_layers import InputLayer
    from sparknet_tpu.ops.neuron import ReLU

    if network.loops:
        raise ValueError(
            f"net {network.name!r} has a looped region "
            f"({network.loops[0].name!r}): the cached decode step keeps one "
            "set of keys and values a layer, not one a pass")
    ei = network.layer_index(end)
    head = network.layers[ei]
    if not isinstance(head, InnerProduct) or head.lp.get_msg(
            "inner_product_param").get_int("axis", 1) != 2:
        raise ValueError(
            f"decode head {end!r} must be a per-token InnerProduct "
            "(axis=2); sequence-classifier heads have no cached decode")
    vocab = head.lp.get_msg("inner_product_param").get_int("num_output")
    embed_dim = None
    heads = None
    attn: list = []
    for layer in network.layers[: ei + 1]:
        if isinstance(layer, InputLayer):
            continue
        if isinstance(layer, _Attn):
            if not layer.causal:
                raise ValueError(
                    f"{layer.name}: cached decode needs causal attention")
            if layer.qk_norm or not layer.bias_term:
                raise ValueError(
                    f"{layer.name}: the cached decode replays biased "
                    "projections without QK-norm only")
            if heads is None:
                heads = layer.num_heads
            elif heads != layer.num_heads:
                raise ValueError("cached decode needs one head count "
                                 "across attention layers")
            attn.append(layer.name)
        elif isinstance(layer, Embed):
            embed_dim = layer.lp.get_msg("embed_param").get_int("num_output")
        elif not isinstance(layer, (Eltwise, InnerProduct, ReLU)):
            raise ValueError(
                f"layer {layer.name!r} ({layer.type}) has no cached "
                "decode twin")
    if not attn or embed_dim is None or heads is None:
        raise ValueError("cached decode needs an Embed front and at "
                         "least one attention layer")
    seq_len = int(network.feed_shapes()["data"][1])
    return DecodeSpec(vocab=vocab, embed_dim=embed_dim, heads=heads,
                      head_dim=embed_dim // heads, seq_len=seq_len,
                      attn_layers=tuple(attn), end=end)


def build_decode_step(network, end: str = "fc", proposed_width: int = 1):
    """One cached decode step over a block-paged KV pool.

    Returns ``step(variables, k_pool, v_pool, tokens, positions,
    tables) -> (k_pool, v_pool, logits)`` — pools first (the carry
    convention; callers jit with the pools donated), ``tokens`` [B, W]
    int32, ``positions`` [B] int32 absolute position of each row's
    token, ``tables`` [B, MB] int32 block tables.  Each attention layer
    writes the token's K/V through the table at ``(pos // T, pos % T)``
    and attends via :func:`paged_attention` — per-token work is
    O(position), never O(seq_len) recompute, and every row's output is
    a pure function of its own (token, position, table), which is the
    interleaved == alone exactness gate.

    ``proposed_width`` is the speculative-decoding seam (next PR): the
    step's token axis is [B, W]; only W == 1 lowers today."""
    if proposed_width != 1:
        raise NotImplementedError(
            "speculative decode (proposed_ids width > 1) is the "
            "declared seam — not lowered yet")
    import jax.numpy as jnp

    from sparknet_tpu.ops.attention import (
        MultiHeadAttentionLayer as _Attn, rope_at)
    from sparknet_tpu.ops.data_layers import InputLayer
    from sparknet_tpu.ops.pallas_kernels import paged_attention

    spec = decode_spec(network, end=end)
    ei = network.layer_index(end)
    H, D = spec.heads, spec.head_dim

    def step(variables, k_pool, v_pool, tokens, positions, tables):
        T = k_pool.shape[2]
        B = tokens.shape[0]
        blob = {"data": tokens.astype(jnp.int32)}
        a = 0
        for layer in network.layers[: ei + 1]:
            if isinstance(layer, InputLayer):
                continue
            p = network._resolve_shared(
                layer, variables.params.get(layer.name, []),
                variables.params)
            ins = [blob[b] for b in layer.bottoms]
            if isinstance(layer, _Attn):
                x = ins[0]  # [B, 1, E]
                w_qkv, b_qkv, w_out, b_out = p
                E = x.shape[-1]
                qkv = jnp.einsum("bse,fe->bsf", x, w_qkv) + b_qkv
                q, k, v = jnp.split(qkv, 3, axis=-1)
                split = lambda t: t.reshape(B, 1, H, D).transpose(0, 2, 1, 3)
                q, k, v = split(q), split(k), split(v)  # [B, H, 1, D]
                if layer.rope:
                    pw = positions[:, None]
                    q, k = rope_at(q, pw), rope_at(k, pw)
                blk = jnp.take_along_axis(
                    tables, (positions // T)[:, None], axis=1)[:, 0]
                off = positions % T
                k_pool = k_pool.at[a, blk, off].set(k[:, :, 0, :])
                v_pool = v_pool.at[a, blk, off].set(v[:, :, 0, :])
                o = paged_attention(q[:, :, 0, :], k_pool[a], v_pool[a],
                                    tables, positions)  # [B, H, D]
                y = jnp.einsum("bse,fe->bsf", o.reshape(B, 1, E),
                               w_out) + b_out
                blob[layer.tops[0]] = y
                a += 1
                continue
            out = layer.apply(p, variables.state.get(layer.name, {}),
                              ins, train=False, rng=None)
            for top, o in zip(layer.tops, out.outputs):
                blob[top] = o
        return k_pool, v_pool, blob[network.layers[ei].tops[0]]

    return step


def build_prefill(network, end: str = "fc"):
    """The prompt pass of the disaggregated serve path: one ordinary
    full-window causal forward (the same einsum/rope/flash-attention
    expressions ops/attention.py lowers — NOT a second attention
    implementation) that also writes every layer's K/V through the
    block tables.  Returns ``prefill(variables, tokens, lengths,
    k_pool, v_pool, tables) -> (k_pool, v_pool, last_logits)`` with
    ``last_logits`` [B, vocab] taken at each row's ``lengths - 1``
    (the first generated token's distribution).  Padded positions >=
    length write garbage K/V into the slot's own blocks; the decode
    step overwrites position p before any row ever attends to it, so
    the garbage is dead by construction."""
    import jax.numpy as jnp

    from sparknet_tpu.ops.attention import (
        MultiHeadAttentionLayer as _Attn, rope)
    from sparknet_tpu.ops.data_layers import InputLayer
    from sparknet_tpu.ops.pallas_kernels import flash_attention

    spec = decode_spec(network, end=end)
    ei = network.layer_index(end)
    H, D = spec.heads, spec.head_dim

    def prefill(variables, tokens, lengths, k_pool, v_pool, tables):
        T = k_pool.shape[2]
        B, S = tokens.shape
        blob = {"data": tokens.astype(jnp.int32)}
        a = 0
        for layer in network.layers[: ei + 1]:
            if isinstance(layer, InputLayer):
                continue
            p = network._resolve_shared(
                layer, variables.params.get(layer.name, []),
                variables.params)
            ins = [blob[b] for b in layer.bottoms]
            if isinstance(layer, _Attn):
                x = ins[0]  # [B, S, E]
                w_qkv, b_qkv, w_out, b_out = p
                E = x.shape[-1]
                qkv = jnp.einsum("bse,fe->bsf", x, w_qkv) + b_qkv
                q, k, v = jnp.split(qkv, 3, axis=-1)
                split = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3)
                q, k, v = split(q), split(k), split(v)  # [B, H, S, D]
                if layer.rope:
                    q, k = rope(q), rope(k)
                pos = jnp.arange(S, dtype=jnp.int32)
                blk = jnp.take_along_axis(
                    tables, jnp.broadcast_to(pos // T, (B, S)), axis=1)
                off = jnp.broadcast_to(pos % T, (B, S))
                k_pool = k_pool.at[a, blk, off].set(k.transpose(0, 2, 1, 3))
                v_pool = v_pool.at[a, blk, off].set(v.transpose(0, 2, 1, 3))
                o = flash_attention(q, k, v, causal=layer.causal)
                o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
                y = jnp.einsum("bse,fe->bsf", o, w_out) + b_out
                blob[layer.tops[0]] = y
                a += 1
                continue
            out = layer.apply(p, variables.state.get(layer.name, {}),
                              ins, train=False, rng=None)
            for top, o in zip(layer.tops, out.outputs):
                blob[top] = o
        logits = blob[network.layers[ei].tops[0]]  # [B, S, V]
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return k_pool, v_pool, last

    return prefill


# ---------------------------------------------------------------------------
# Graph-contract sweep configs (sparknet_tpu/analysis/graphcheck.py).
#
# Tiny, shape-valid instantiations of the zoo families the static graph
# analysis lowers on the virtual 8-device CPU mesh — small enough that a
# CPU compile is seconds, real enough that the lowered collectives are
# the same op set a pod-scale run would emit (collective structure
# depends on mesh axes and layer types, not on batch/crop).  The feed
# field drives synthetic input construction: "image" = float NCHW data +
# int class labels, "tokens" = int id matrix + int class labels.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphFamily:
    """One zoo family as the graph-contract sweep traces it."""

    solver: Any  # () -> SolverConfig
    net: Any  # (batch: int) -> Message
    feed: str  # "image" | "tokens"
    num_classes: int
    image_shape: tuple = ()  # (C, H, W) for image feeds
    seq_len: int = 0  # for token feeds
    vocab: int = 0


GRAPH_SWEEP_FAMILIES: dict[str, GraphFamily] = {
    "cifar10_quick": GraphFamily(
        solver=cifar10_quick_solver,
        net=lambda b: cifar10_quick(b),
        feed="image", num_classes=10, image_shape=(3, 32, 32),
    ),
    # lenet is the TP vehicle: ip1's 500 outputs clear the
    # ShardingRules.min_tp_dim=128 floor and divide a 2-way 'model' axis
    "lenet": GraphFamily(
        solver=lenet_solver,
        net=lambda b: lenet(b),
        feed="image", num_classes=10, image_shape=(1, 28, 28),
    ),
    # the dryrun mode-6b transformer shape: trains on a (data x seq) mesh
    "transformer": GraphFamily(
        solver=transformer_solver,
        net=lambda b: transformer(b, seq_len=32, vocab=32, embed_dim=16,
                                  heads=4, ffn_dim=32, blocks=1),
        feed="tokens", num_classes=10, seq_len=32, vocab=32,
    ),
    # depthwise group conv + synced BN — the sharding interaction the
    # mobilenet_dp mode exists to pin
    "mobilenet": GraphFamily(
        solver=lambda: dataclasses.replace(mobilenet_solver(),
                                           base_lr=1e-3),
        net=lambda b: mobilenet(batch=b, num_classes=5, crop=64),
        feed="image", num_classes=5, image_shape=(3, 64, 64),
    ),
}
