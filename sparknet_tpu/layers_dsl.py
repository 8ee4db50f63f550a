"""Programmatic model-definition DSL.

Equivalent of the Scala layer constructors (ref:
src/main/scala/libs/Layers.scala:18-137 — RDDLayer, ConvolutionLayer,
PoolingLayer, InnerProductLayer, ReLULayer, SoftmaxWithLoss, NetParam) and
of the README's LeNet example (ref: README.md:115-128).  Builders return
``Message`` objects identical to parsed prototxt, so DSL-built and
file-loaded models flow through the same compiler.
"""

from __future__ import annotations

from typing import Sequence

from sparknet_tpu.proto.text_format import Message


def _layer(name: str, type_: str, bottoms: Sequence[str] = (), tops: Sequence[str] | None = None) -> Message:
    m = Message()
    m.set("name", name).set("type", type_)
    for b in bottoms:
        m.add("bottom", b)
    for t in tops if tops is not None else [name]:
        m.add("top", t)
    return m


def _filler(type_: str = "xavier", value: float | None = None, std: float | None = None) -> Message:
    f = Message().set("type", type_)
    if value is not None:
        f.set("value", value)
    if std is not None:
        f.set("std", std)
    return f


def RDDLayer(name: str, shape: Sequence[int]) -> Message:
    """Named input fed by the host data plane (the JavaData/RDD-callback
    analog, ref: Layers.scala:18-40)."""
    m = _layer(name, "JavaData", [], [name])
    p = Message()
    s = Message()
    for d in shape:
        s.add("dim", int(d))
    p.add("shape", s)
    m.set("java_data_param", p)
    return m


def MemoryDataLayer(name: str, batch: int, channels: int, height: int, width: int, tops=("data", "label")) -> Message:
    m = _layer(name, "MemoryData", [], list(tops))
    p = Message()
    p.set("batch_size", batch).set("channels", channels).set("height", height).set("width", width)
    m.set("memory_data_param", p)
    return m


def ConvolutionLayer(
    name: str,
    bottoms: Sequence[str],
    kernel: tuple[int, int],
    num_output: int,
    stride: tuple[int, int] = (1, 1),
    pad: tuple[int, int] = (0, 0),
    group: int = 1,
    weight_filler: Message | None = None,
    bias_filler: Message | None = None,
    bias_term: bool = True,
) -> Message:
    """ref: Layers.scala:42-63.  ``bias_term=False`` for convs whose bias
    a following BatchNorm/Scale pair absorbs (ResNet-style)."""
    m = _layer(name, "Convolution", bottoms)
    p = Message()
    p.set("num_output", num_output)
    p.set("kernel_h", kernel[0]).set("kernel_w", kernel[1])
    p.set("stride_h", stride[0]).set("stride_w", stride[1])
    p.set("pad_h", pad[0]).set("pad_w", pad[1])
    if group != 1:
        p.set("group", group)
    p.set("weight_filler", weight_filler or _filler("xavier"))
    if bias_term:
        p.set("bias_filler", bias_filler or _filler("constant", value=0.0))
    else:
        p.set("bias_term", False)
    m.set("convolution_param", p)
    return m


class Pooling:
    Max = "MAX"
    Ave = "AVE"


def PoolingLayer(
    name: str,
    bottoms: Sequence[str],
    pooling: str = Pooling.Max,
    kernel: tuple[int, int] = (2, 2),
    stride: tuple[int, int] = (2, 2),
    pad: tuple[int, int] = (0, 0),
    global_pooling: bool = False,
) -> Message:
    """ref: Layers.scala:65-86.  ``global_pooling`` collapses the spatial
    dims regardless of kernel (pooling_layer.cpp's global_pooling)."""
    m = _layer(name, "Pooling", bottoms)
    p = Message()
    p.set("pool", pooling)
    if global_pooling:
        p.set("global_pooling", True)
    else:
        p.set("kernel_h", kernel[0]).set("kernel_w", kernel[1])
        p.set("stride_h", stride[0]).set("stride_w", stride[1])
        if pad != (0, 0):
            p.set("pad_h", pad[0]).set("pad_w", pad[1])
    m.set("pooling_param", p)
    return m


def InnerProductLayer(
    name: str,
    bottoms: Sequence[str],
    num_output: int,
    weight_filler: Message | None = None,
    bias_filler: Message | None = None,
    axis: int | None = None,
    bias_term: bool = True,
    param_name: str | None = None,
) -> Message:
    """ref: Layers.scala:88-100.  ``axis`` flattens from that axis
    (Caffe default 1; axis=2 keeps a [B, S, E] sequence per-token).
    ``param_name`` names the weight blob: layers that give one name share
    the array (Caffe's ``param { name }``)."""
    m = _layer(name, "InnerProduct", bottoms)
    if param_name:
        m.add("param", Message().set("name", param_name))
    p = Message()
    p.set("num_output", num_output)
    p.set("weight_filler", weight_filler or _filler("xavier"))
    if bias_term:
        p.set("bias_filler", bias_filler or _filler("constant", value=0.0))
    else:
        p.set("bias_term", False)
    if axis is not None:
        p.set("axis", axis)
    m.set("inner_product_param", p)
    return m


def ReLULayer(name: str, bottoms: Sequence[str], in_place: bool = False) -> Message:
    """ref: Layers.scala:102-113.  ``in_place=True`` reproduces the zoo
    prototxts' top==bottom wiring (Caffe computes ReLU in the bottom blob's
    buffer; here it just rebinds the blob name)."""
    return _layer(name, "ReLU", bottoms, tops=bottoms if in_place else None)


def DropoutLayer(
    name: str, bottoms: Sequence[str], ratio: float = 0.5, in_place: bool = False
) -> Message:
    m = _layer(name, "Dropout", bottoms, tops=bottoms if in_place else None)
    m.set("dropout_param", Message().set("dropout_ratio", ratio))
    return m


def LRNLayer(
    name: str,
    bottoms: Sequence[str],
    local_size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    norm_region: str | None = None,
) -> Message:
    m = _layer(name, "LRN", bottoms)
    p = Message().set("local_size", local_size).set("alpha", alpha).set("beta", beta)
    if norm_region:
        p.set("norm_region", norm_region)
    m.set("lrn_param", p)
    return m


def ConcatLayer(name: str, bottoms: Sequence[str], axis: int = 1) -> Message:
    m = _layer(name, "Concat", bottoms)
    if axis != 1:
        m.set("concat_param", Message().set("axis", axis))
    return m


def SigmoidLayer(name: str, bottoms: Sequence[str], in_place: bool = False) -> Message:
    return _layer(name, "Sigmoid", bottoms, bottoms if in_place else None)


def FlattenLayer(name: str, bottoms: Sequence[str]) -> Message:
    return _layer(name, "Flatten", bottoms)


def _loss_layer(
    name: str, type_: str, bottoms: Sequence[str],
    loss_weight: float | None, top: str | None,
) -> Message:
    m = _layer(name, type_, bottoms, [top] if top else None)
    if loss_weight is not None:
        m.add("loss_weight", loss_weight)
    return m


def EuclideanLossLayer(
    name: str, bottoms: Sequence[str], loss_weight: float | None = None,
    top: str | None = None,
) -> Message:
    return _loss_layer(name, "EuclideanLoss", bottoms, loss_weight, top)


def SigmoidCrossEntropyLossLayer(
    name: str, bottoms: Sequence[str], loss_weight: float | None = None,
    top: str | None = None,
) -> Message:
    return _loss_layer(name, "SigmoidCrossEntropyLoss", bottoms, loss_weight, top)


def BatchNormLayer(
    name: str,
    bottoms: Sequence[str],
    in_place: bool = True,
    eps: float = 1e-5,
    moving_average_fraction: float = 0.999,
) -> Message:
    """ref: batch_norm_layer.cpp:10 LayerSetUp, :75 Forward_cpu —
    normalization only; pair with a Scale layer for the learnable affine
    (the convention the published ResNet prototxts use)."""
    m = _layer(name, "BatchNorm", bottoms,
               [bottoms[0]] if in_place else None)
    p = Message()
    if eps != 1e-5:
        p.set("eps", eps)
    if moving_average_fraction != 0.999:
        p.set("moving_average_fraction", moving_average_fraction)
    m.set("batch_norm_param", p)
    return m


def ScaleLayer(
    name: str,
    bottoms: Sequence[str],
    in_place: bool = True,
    bias_term: bool = True,
) -> Message:
    """Channel-wise gamma (+ beta with bias_term), the learnable half of
    the BatchNorm/Scale pair.  No reference counterpart: the SparkNet-era
    Caffe predates ScaleLayer (post-reference BVLC addition); semantics
    follow ops/blocks.py:Scale, which the zoo ResNet wiring requires."""
    m = _layer(name, "Scale", bottoms, [bottoms[0]] if in_place else None)
    if bias_term:
        m.set("scale_param", Message().set("bias_term", True))
    return m


def EltwiseLayer(
    name: str,
    bottoms: Sequence[str],
    operation: str = "SUM",
    top: str | None = None,
) -> Message:
    """ref: eltwise_layer.cpp (PROD / SUM / MAX over bottoms)."""
    m = _layer(name, "Eltwise", bottoms, [top] if top else None)
    if operation != "SUM":
        m.set("eltwise_param", Message().set("operation", operation))
    return m


def SoftmaxLayer(name: str, bottoms: Sequence[str]) -> Message:
    return _layer(name, "Softmax", bottoms)


def SoftmaxWithLoss(
    name: str, bottoms: Sequence[str], loss_weight: float | None = None,
    top: str | None = None, axis: int | None = None,
    keep_value: bool = False,
) -> Message:
    """ref: Layers.scala:115-128 (bottoms = [scores, label]).  ``loss_weight``
    scales this loss term in the total objective — the GoogLeNet auxiliary
    classifiers train at 0.3 (bvlc_googlenet/train_val.prototxt:933,1696).
    ``axis`` picks the class axis (softmax_param.axis, ref:
    softmax_loss_layer.cpp) — e.g. 2 for per-token [B, S, V] LM logits."""
    m = _loss_layer(name, "SoftmaxWithLoss", bottoms, loss_weight, top)
    if axis is not None:
        m.set("softmax_param", Message().set("axis", axis))
    if keep_value:  # the value stays in the layer's state, for the fence
        m.set("loss_param", Message().set("keep_value", True))
    return m


def ExitWeightedLossLayer(
    name: str, bottoms: Sequence[str], steps: int,
    entropy_weight: float = 0.0,
    tops: Sequence[str] = ("loss", "step_loss", "exit_mean_step"),
) -> Message:
    """The exit-weighted loss of a looped model (ops/loss.py
    ExitWeightedLoss): bottoms = [the ``steps`` passes' logits, label,
    the exit gate's logits]; the first top is the loss, the others are
    read-outs at weight 0."""
    m = _layer(name, "ExitWeightedLoss", bottoms, tops)
    return m.set("exit_loss_param", Message().set("steps", steps).set(
        "entropy_weight", entropy_weight))


def AccuracyLayer(
    name: str,
    bottoms: Sequence[str],
    top_k: int = 1,
    phase: str | None = None,
    axis: int | None = None,
) -> Message:
    """``phase="TEST"`` adds the include rule the reference prototxts put on
    every Accuracy layer (e.g. caffe/examples/mnist/lenet_train_test.prototxt:
    ``include { phase: TEST }``)."""
    m = _layer(name, "Accuracy", bottoms)
    if top_k != 1 or axis is not None:
        p = Message()
        if top_k != 1:
            p.set("top_k", top_k)
        if axis is not None:
            p.set("axis", axis)
        m.set("accuracy_param", p)
    if phase is not None:
        m.add("include", Message().set("phase", phase))
    return m


def EmbedLayer(
    name: str,
    bottoms: Sequence[str],
    input_dim: int,
    num_output: int,
    weight_filler: Message | None = None,
    top: str | None = None,
    bias_term: bool = True,
    param_name: str | None = None,
) -> Message:
    """Embedding lookup (ref: embed_layer.cpp; ops/blocks.py Embed).
    ``param_name``: as ``InnerProductLayer``'s."""
    m = _layer(name, "Embed", bottoms, [top] if top else None)
    if param_name:
        m.add("param", Message().set("name", param_name))
    p = Message()
    p.set("input_dim", input_dim)
    p.set("num_output", num_output)
    p.set("weight_filler", weight_filler or _filler("xavier"))
    if not bias_term:
        p.set("bias_term", False)
    return m.set("embed_param", p)


def RMSNormLayer(name: str, bottoms: Sequence[str], eps: float = 1e-5,
                 top: str | None = None,
                 zero_centered: bool = False) -> Message:
    """RMSNorm over the last axis (ops/blocks.py RMSNorm);
    ``zero_centered``: the weight is 1 + w, w from zero."""
    m = _layer(name, "RMSNorm", bottoms, [top] if top else None)
    p = Message().set("eps", eps)
    if zero_centered:
        p.set("zero_centered", True)
    return m.set("rms_norm_param", p)


def SliceLayer(name: str, bottoms: Sequence[str], tops: Sequence[str],
               axis: int = 1, slice_points: Sequence[int] = ()) -> Message:
    """ref: slice_layer.cpp (one top per piece)."""
    m = _layer(name, "Slice", bottoms, tops)
    p = Message().set("axis", axis)
    for pt in slice_points:
        p.add("slice_point", pt)
    return m.set("slice_param", p)


def GatedMLPLayer(name: str, bottoms: Sequence[str], hidden_dim: int,
                  weight_filler: Message | None = None,
                  top: str | None = None) -> Message:
    """Gated SiLU feed-forward over the last axis (ops/blocks.py
    GatedMLP): ``(silu(x W_g) * x W_u) W_d``, no biases."""
    m = _layer(name, "GatedMLP", bottoms, [top] if top else None)
    p = Message().set("hidden_dim", hidden_dim)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("gated_mlp_param", p)


def LayerNormLayer(name: str, bottoms: Sequence[str], eps: float = 1e-5,
                   top: str | None = None) -> Message:
    """LayerNorm over the last axis, weight and bias (ops/blocks.py
    LayerNorm)."""
    m = _layer(name, "LayerNorm", bottoms, [top] if top else None)
    return m.set("layer_norm_param", Message().set("eps", eps))


def MambaLayer(name: str, bottoms: Sequence[str], d_state: int = 16,
               d_conv: int = 4, expand: int = 2, dt_rank: int | None = None,
               weight_filler: Message | None = None,
               memory_top: str | None = None) -> Message:
    """Selective state-space layer (ops/ssm.py MambaLayer).
    ``memory_top`` names a second top: the scan's output before the gate,
    for ``GatedMemoryUnitLayer``s to read."""
    m = _layer(name, "Mamba", bottoms,
               [name, memory_top] if memory_top else None)
    p = Message().set("d_state", d_state).set("d_conv", d_conv)
    p.set("expand", expand)
    if dt_rank is not None:
        p.set("dt_rank", dt_rank)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("mamba_param", p)


def GatedMemoryUnitLayer(name: str, bottoms: Sequence[str],
                         weight_filler: Message | None = None) -> Message:
    """``W_2 (silu(W_1 x) * m)``; bottoms [x, m] (ops/blocks.py
    GatedMemoryUnit)."""
    m = _layer(name, "GatedMemoryUnit", bottoms)
    p = Message()
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("gmu_param", p)


def DifferentialAttentionLayer(
    name: str,
    bottoms: Sequence[str],
    num_heads: int,
    num_kv_heads: int,
    lambda_init: float,
    window: int = 0,
    norm_eps: float | None = None,
    weight_filler: Message | None = None,
    kv_tops: Sequence[str] = (),
) -> Message:
    """Causal differential attention with grouped heads (ops/attention.py
    DifferentialAttentionLayer).  Bottoms [x], or [x, k, v] for a layer
    that reads another's keys and values; ``kv_tops`` names two further
    tops under which this layer hands its own on."""
    m = _layer(name, "DifferentialAttention", bottoms, [name, *kv_tops])
    p = Message().set("num_heads", num_heads)
    p.set("num_kv_heads", num_kv_heads).set("lambda_init", lambda_init)
    if window:
        p.set("window", window)
    if norm_eps is not None:
        p.set("norm_eps", norm_eps)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("attention_param", p)


def LatentAttentionLayer(
    name: str,
    bottoms: Sequence[str],
    num_heads: int,
    q_lora_rank: int,
    kv_lora_rank: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    rope_theta: float | None = None,
    rope_interleave: bool = False,
    norm_eps: float | None = None,
    weight_filler: Message | None = None,
    top: str | None = None,
) -> Message:
    """Causal multi-head latent attention (ops/attention.py
    LatentAttentionLayer): low-rank query and key/value paths with a norm
    inside each, one rotary key shared by the heads."""
    m = _layer(name, "LatentAttention", bottoms, [top] if top else None)
    p = Message().set("num_heads", num_heads)
    p.set("q_lora_rank", q_lora_rank).set("kv_lora_rank", kv_lora_rank)
    p.set("qk_nope_head_dim", qk_nope_head_dim)
    p.set("qk_rope_head_dim", qk_rope_head_dim).set("v_head_dim", v_head_dim)
    if rope_theta is not None:
        p.set("rope_theta", rope_theta)
    if rope_interleave:
        p.set("rope_interleave", True)
    if norm_eps is not None:
        p.set("norm_eps", norm_eps)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("attention_param", p)


def GatedAttentionLayer(
    name: str,
    bottoms: Sequence[str],
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rotary_dim: int | None = None,
    rope_theta: float = 10000.0,
    norm_eps: float = 1e-6,
    weight_filler: Message | None = None,
    qk_norm: bool = True,
    head_gate: bool = False,
    window: int = 0,
    rope_scaling: dict | None = None,
) -> Message:
    """Causal grouped attention with heads of ``head_dim``, RoPE on the
    first ``rotary_dim`` features of a head and a sigmoid gate on its
    output (ops/attention.py GatedAttentionLayer).  The defaults are the
    Qwen3-Next layer (per-head QK-norm, a gate as wide as the heads);
    ``qk_norm=False``, ``head_gate`` (one gate a head), ``window`` and
    ``rope_scaling`` (YaRN: ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``attention_factor``) are written only
    where they differ from them."""
    m = _layer(name, "GatedAttention", bottoms)
    p = Message().set("num_heads", num_heads).set("num_kv_heads", num_kv_heads)
    p.set("head_dim", head_dim)
    if rotary_dim is not None:
        p.set("rotary_dim", rotary_dim)
    p.set("rope_theta", rope_theta).set("norm_eps", norm_eps)
    p.set("causal", True)
    if not qk_norm:
        p.set("qk_norm", False)
    if head_gate:
        p.set("head_gate", True)
    if window:
        p.set("window", window)
    if rope_scaling is not None:
        r = Message().set("type", "yarn")
        for key, value in rope_scaling.items():
            r.set(key, value)
        p.set("rope_scaling", r)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("attention_param", p)


def GatedDeltaNetLayer(
    name: str,
    bottoms: Sequence[str],
    num_k_heads: int,
    num_v_heads: int,
    head_k_dim: int,
    head_v_dim: int,
    conv_kernel: int = 4,
    norm_eps: float = 1e-6,
    weight_filler: Message | None = None,
) -> Message:
    """Gated-DeltaNet linear attention (ops/linear_attention.py
    GatedDeltaNetLayer)."""
    m = _layer(name, "GatedDeltaNet", bottoms)
    p = Message().set("num_k_heads", num_k_heads)
    p.set("num_v_heads", num_v_heads).set("head_k_dim", head_k_dim)
    p.set("head_v_dim", head_v_dim).set("conv_kernel", conv_kernel)
    p.set("norm_eps", norm_eps)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("delta_param", p)


def MultiHeadAttentionLayer(
    name: str,
    bottoms: Sequence[str],
    num_heads: int,
    causal: bool = False,
    rope: bool = False,
    top: str | None = None,
    bias_term: bool = True,
    qk_norm: bool = False,
    qk_norm_eps: float | None = None,
    rope_theta: float | None = None,
    weight_filler: Message | None = None,
) -> Message:
    """Sequence-model extra (no reference analog; ops/attention.py).
    ``rope=True`` turns on parameter-free rotary position embeddings
    (base ``rope_theta``); ``qk_norm`` RMS-normalizes q and k;
    ``bias_term=False`` drops the projection biases."""
    m = _layer(name, "MultiHeadAttention", bottoms, [top] if top else None)
    p = Message().set("num_heads", num_heads)
    if causal:
        p.set("causal", True)
    if rope:
        p.set("rope", True)
    if rope_theta is not None:
        p.set("rope_theta", rope_theta)
    if not bias_term:
        p.set("bias_term", False)
    if qk_norm:
        p.set("qk_norm", True)
    if qk_norm_eps is not None:
        p.set("qk_norm_eps", qk_norm_eps)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("attention_param", p)


def MoELayer(
    name: str,
    bottoms: Sequence[str],
    num_experts: int,
    hidden_dim: int = 0,
    top: str | None = None,
    top_k: int = 1,
    expert_act: str = "relu",
    norm_topk_prob: bool = False,
    bias_term: bool = True,
    loss_tops: Sequence[tuple[str, float]] = (),
    weight_filler: Message | None = None,
    scoring_func: str = "softmax",
    routed_scaling_factor: float = 1.0,
    bias_update_rate: float = 0.0,
    shared_hidden_dim: int = 0,
    experts_held: int | None = None,
    first_expert: int = 0,
    shared_gate: bool = False,
) -> Message:
    """Mixture-of-experts extra (no reference analog; ops/moe.py).
    ``loss_tops``: up to three further (top name, loss_weight) pairs, in
    the layer's order: load-balancing loss, router z-loss, tokens per
    expert.  ``scoring_func`` / ``routed_scaling_factor`` /
    ``bias_update_rate`` / ``shared_hidden_dim``: the DeepSeek-V3
    family's router and shared expert; ``experts_held`` /
    ``first_expert``: the experts of the router's ``num_experts`` this
    layer holds (all by default); ``shared_gate``: the shared expert's
    output times sigmoid(x w_g)."""
    tops = [top or name, *(t for t, _ in loss_tops)]
    m = _layer(name, "MoE", bottoms, tops)
    if loss_tops:
        for w in (0.0, *(w for _, w in loss_tops)):
            m.add("loss_weight", w)
    p = Message().set("num_experts", num_experts)
    if hidden_dim:
        p.set("hidden_dim", hidden_dim)
    if top_k != 1:
        p.set("top_k", top_k)
    if expert_act != "relu":
        p.set("expert_act", expert_act)
    if norm_topk_prob:
        p.set("norm_topk_prob", True)
    if not bias_term:
        p.set("bias_term", False)
    if scoring_func != "softmax":
        p.set("scoring_func", scoring_func)
    if routed_scaling_factor != 1.0:
        p.set("routed_scaling_factor", routed_scaling_factor)
    if bias_update_rate:
        p.set("bias_update_rate", bias_update_rate)
    if shared_hidden_dim:
        p.set("shared_hidden_dim", shared_hidden_dim)
    if shared_gate:
        p.set("shared_gate", True)
    if experts_held is not None and (experts_held, first_expert) != (
            num_experts, 0):
        p.set("experts_held", experts_held).set("first_expert", first_expert)
    if weight_filler is not None:
        p.set("weight_filler", weight_filler)
    return m.set("moe_param", p)


def LoopRegion(name: str, count: int, first: str, last: str,
               carry_in: str, carry_out: str,
               collect: Sequence[tuple[str, str]] = ()) -> Message:
    """A net-level ``loop`` (compiler/graph.py LoopRegion): the layers
    ``first`` .. ``last`` run ``count`` times on one set of parameters,
    ``carry_out`` of a pass is ``carry_in`` of the next, and each
    ``(blob, top)`` of ``collect`` leaves the region as the blob's value
    in every pass, pass-major along axis 0."""
    m = Message().set("name", name).set("count", count)
    m.set("first", first).set("last", last)
    m.set("carry_in", carry_in).set("carry_out", carry_out)
    for blob, top in collect:
        m.add("collect", Message().set("blob", blob).set("top", top))
    return m


def NetParam(name: str, *layers: Message, loops: Sequence[Message] = ()) -> Message:
    """Aggregate layers (and looped regions) into a NetParameter
    (ref: Layers.scala:130-137)."""
    net = Message().set("name", name)
    for l in layers:
        net.add("layer", l)
    for r in loops:
        net.add("loop", r)
    return net
