"""Layer protocol.

TPU-native re-think of Caffe's ``Layer`` base (ref:
caffe/include/caffe/layer.hpp:335-351): instead of mutable Blob tops/bottoms
with Forward_{cpu,gpu}/Backward dispatch, a layer is a *pure function*
``apply(params, state, inputs) -> (outputs, new_state)``.  Backward is
``jax.grad`` — there are no hand-written backward passes anywhere in the
framework, which is exactly the role XLA:TPU plays relative to the
reference's .cu kernels.

Params are a list of arrays per layer, mirroring Caffe's ``blobs_`` ordering
(e.g. Convolution = [weight, bias]) so the WeightCollection exchange format
(ref: src/main/scala/libs/Net.scala:14-47) and .caffemodel import map 1:1.
State holds non-learnable mutables (BatchNorm moving stats).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import numpy as np

from sparknet_tpu.common import Phase
from sparknet_tpu.proto.text_format import Message

Array = jax.Array
Shape = tuple[int, ...]


@dataclasses.dataclass
class ParamSpec:
    """Per-blob learning-rate / decay multipliers
    (ref: caffe.proto ParamSpec; net.cpp:470+ AppendParam)."""

    lr_mult: float = 1.0
    decay_mult: float = 1.0
    name: str = ""  # for cross-layer weight sharing (share_mode)


@dataclasses.dataclass
class LayerOutput:
    outputs: list[Any]
    state: dict[str, Any] = dataclasses.field(default_factory=dict)


class Layer:
    """Base class. Subclasses set ``TYPE`` and implement init/apply."""

    TYPE: str = ""
    # Layers whose type name ends in "Loss" produce a loss top with default
    # weight 1 (ref: layer.hpp SetLossWeights / caffe.proto loss_weight).
    IS_LOSS: bool = False
    # Blob positions that keep the parameter dtype when the compute dtype
    # is narrower (compiler/graph.py casts the others at the layer's
    # door): values a layer's own f32 arithmetic reads.
    F32_BLOBS: tuple[int, ...] = ()

    def __init__(self, lp: Message, phase: Phase):
        self.lp = lp
        self.phase = phase
        self.name = lp.get_str("name")
        self.type = lp.get_str("type")
        self.bottoms: list[str] = [str(b) for b in lp.get_all("bottom")]
        self.tops: list[str] = [str(t) for t in lp.get_all("top")]

    # ---- learnable params -------------------------------------------------
    def init(self, key: Array, in_shapes: Sequence[Shape]) -> tuple[list[Array], dict]:
        """Returns (params, state). Default: stateless, param-free."""
        return [], {}

    def param_specs(self, num_params: int) -> list[ParamSpec]:
        """ParamSpecs for each blob, honoring repeated ``param {}`` messages."""
        msgs = self.lp.get_all("param")
        specs = []
        for i in range(num_params):
            if i < len(msgs):
                m = msgs[i]
                specs.append(
                    ParamSpec(
                        lr_mult=m.get_float("lr_mult", 1.0),
                        decay_mult=m.get_float("decay_mult", 1.0),
                        name=m.get_str("name", ""),
                    )
                )
            else:
                specs.append(ParamSpec())
        return specs

    # ---- forward ----------------------------------------------------------
    def apply(
        self,
        params: list[Array],
        state: dict,
        inputs: list[Array],
        *,
        train: bool,
        rng: Array | None = None,
    ) -> LayerOutput:
        raise NotImplementedError(self.type)

    # ---- loss weights -----------------------------------------------------
    def loss_weights(self) -> list[float]:
        explicit = [float(w) for w in self.lp.get_all("loss_weight")]
        n_tops = max(len(self.tops), 1)
        if explicit:
            return explicit + [0.0] * (n_tops - len(explicit))
        return [1.0 if (self.IS_LOSS and i == 0) else 0.0 for i in range(n_tops)]

    def __repr__(self):
        return f"<{self.type} {self.name!r} {self.bottoms}->{self.tops}>"


# ---------------------------------------------------------------------------
# Shared helpers for prototxt conv/pool-style size fields
# ---------------------------------------------------------------------------


def hw_param(m: Message, base: str, default: int | None = None) -> tuple[int, int]:
    """Resolve Caffe's `kernel_size`-or-`kernel_h/kernel_w` field trio."""
    h_key, w_key = f"{base}_h", f"{base}_w"
    if m.has(h_key) or m.has(w_key):
        if not (m.has(h_key) and m.has(w_key)):
            raise ValueError(f"{h_key}/{w_key} must both be set when either is")
        return m.get_int(h_key), m.get_int(w_key)
    vals = m.get_all(f"{base}_size" if base == "kernel" else base)
    if vals:
        if len(vals) == 1:
            return int(vals[0]), int(vals[0])
        return int(vals[0]), int(vals[1])
    if default is None:
        raise ValueError(f"missing required {base} param")
    return default, default


def conv_out_dim(size: int, kernel: int, pad: int, stride: int, dilation: int = 1) -> int:
    ke = dilation * (kernel - 1) + 1
    out = (size + 2 * pad - ke) // stride + 1
    if out <= 0:
        # fail with the geometry in hand, not as a negative shape deep in
        # conv_general_dilated (same contract as pool_out_dim below)
        raise ValueError(
            f"conv kernel {kernel} (stride {stride}, pad {pad}, dilation "
            f"{dilation}) produces no output for input size {size}"
        )
    return out


def pool_out_dim(size: int, kernel: int, pad: int, stride: int) -> int:
    """Caffe's ceil-mode pooling shape rule (ref:
    caffe/src/caffe/layers/pooling_layer.cpp Reshape: ceil((H+2p-k)/s)+1,
    then shrink if the last window would start in the padding)."""
    out = int(np.ceil((size + 2 * pad - kernel) / float(stride))) + 1
    if pad > 0 and (out - 1) * stride >= size + pad:
        out -= 1
    if out <= 0:
        # a kernel larger than the padded input (e.g. GoogLeNet's 7x7
        # pool5 fed a sub-224 crop) must fail HERE with the geometry in
        # hand, not as a zero-size shape exploding in a downstream layer
        raise ValueError(
            f"pooling kernel {kernel} (stride {stride}, pad {pad}) "
            f"produces no output for input size {size}"
        )
    return out
