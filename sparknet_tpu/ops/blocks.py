"""Common layers (ref: caffe/include/caffe/common_layers.hpp + layer impls).

InnerProduct lands on the MXU as a single GEMM; shaping/routing layers
(Concat/Slice/Flatten/Reshape/...) are free reshapes under XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.common import get_config
from sparknet_tpu.ops import fillers, layout
from sparknet_tpu.ops.base import Layer, LayerOutput
from sparknet_tpu.ops.registry import register
from sparknet_tpu.proto.text_format import Message


def _canon_axis(axis: int, ndim: int) -> int:
    return axis + ndim if axis < 0 else axis


def _canon_shape(shape) -> tuple:
    """The canonical (NCHW blob-order) view of an internal shape — layer
    parameters (axis, num_axes, blob dims) always speak canonical
    coordinates regardless of ``Config.layout`` (ops/layout.py)."""
    return layout.canonical_shape(shape)


@register
class InnerProduct(Layer):
    """Fully connected (ref: inner_product_layer.cpp).  Flattens from
    ``axis`` (default 1, i.e. C*H*W in NCHW order — this ordering is what
    makes .caffemodel FC weights line up).  W blob: (num_output, dim)."""

    TYPE = "InnerProduct"

    def _conf(self):
        p = self.lp.get_msg("inner_product_param")
        return (
            p.get_int("num_output"),
            p.get_int("axis", 1),
            p.get_bool("bias_term", True),
            p.get_msg("weight_filler"),
            p.get_msg("bias_filler"),
        )

    def init(self, key, in_shapes):
        n_out, axis, bias, wf, bf = self._conf()
        # the weight's column order is the CANONICAL flatten (C*H*W for a
        # 4D bottom) in every layout — that is the .caffemodel contract
        cshape = _canon_shape(in_shapes[0])
        axis = _canon_axis(axis, len(cshape))
        dim = int(np.prod(cshape[axis:]))
        kw, kb = jax.random.split(key)
        dtype = get_config().param_dtype
        params = [fillers.fill(wf, kw, (n_out, dim), dtype)]
        if bias:
            params.append(fillers.fill(bf, kb, (n_out,), dtype))
        return params, {}

    def apply(self, params, state, inputs, *, train, rng=None):
        n_out, axis, bias, _, _ = self._conf()
        x = inputs[0]
        axis = _canon_axis(axis, x.ndim)
        if x.ndim == 4 and layout.is_nhwc():
            return self._apply_nhwc(params, x, n_out, axis, bias, train)
        lead = x.shape[:axis]
        flat = x.reshape((-1, int(np.prod(x.shape[axis:]))))
        if not train:
            # int8 deploy path (sparknet_tpu.quant) — see Convolution
            from sparknet_tpu.quant import int8_matmul, layer_qparams

            q = layer_qparams(self.name)
            if q is not None:
                y = int8_matmul(flat, q)
                if bias:
                    y = y + params[1].astype(y.dtype)
                return LayerOutput(
                    [y.astype(x.dtype).reshape(lead + (n_out,))]
                )
        y = flat @ params[0].astype(x.dtype).T
        if bias:
            y = y + params[1].astype(x.dtype)
        return LayerOutput([y.reshape(lead + (n_out,))])

    def _apply_nhwc(self, params, x, n_out, axis, bias, train):
        """4D bottom under channels-last: the conv→fc boundary.

        The weight stays (num_output, C·H·W) wire order; reshaped OIHW
        (free) it IS the kernel of a full-map VALID convolution — the
        classic fc-as-conv identity, so the contraction is element-exact
        with the NCHW ``flat @ W.T`` path from the SAME bytes, and both
        forward and backward lower through ``dimension_numbers`` alone:
        zero layout transposes at the one place a naive NHWC flatten
        would need one (the layout census pins this —
        ``python -m sparknet_tpu.analysis graph``, family ``layout``).
        Non-channel flatten axes fall back to a canonicalizing
        transpose (no zoo model takes that path)."""
        n, h, w, c = x.shape
        if axis != 1:
            xc = x.transpose(0, 3, 1, 2)
            lead = xc.shape[:axis]
            flat = xc.reshape((-1, int(np.prod(xc.shape[axis:]))))
            y = flat @ params[0].astype(x.dtype).T
            if bias:
                y = y + params[1].astype(x.dtype)
            return LayerOutput([y.reshape(lead + (n_out,))])
        if not train:
            from sparknet_tpu.quant import int8_matmul, layer_qparams

            q = layer_qparams(self.name)
            if q is not None:
                # inference-only: canonicalize so the int8 weight's
                # column order lines up (one transpose, deploy path)
                flat = x.transpose(0, 3, 1, 2).reshape(n, -1)
                y = int8_matmul(flat, q)
                if bias:
                    y = y + params[1].astype(y.dtype)
                return LayerOutput([y.astype(x.dtype)])
        w4 = params[0].astype(x.dtype).reshape(n_out, c, h, w)
        y = jax.lax.conv_general_dilated(
            x, w4, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
        ).reshape(n, n_out)
        if bias:
            y = y + params[1].astype(x.dtype)
        return LayerOutput([y])


@register
class BatchNorm(Layer):
    """ref: batch_norm_layer.cpp (2015 Caffe: no learnable scale/shift —
    pair with a Scale layer).  Mutable blobs [mean_sum, var_sum, scale_factor]
    live in *state* but are exported in the weight collection for
    .caffemodel parity; Caffe forces their lr_mult to 0 the same way."""

    TYPE = "BatchNorm"

    def init(self, key, in_shapes):
        shape = in_shapes[0]
        if len(shape) > 1:
            ch = shape[layout.channel_axis(ndim=len(shape))]
        else:
            ch = 1
        dtype = get_config().param_dtype
        state = {
            "mean": jnp.zeros((ch,), dtype),
            "variance": jnp.zeros((ch,), dtype),
            "scale_factor": jnp.zeros((1,), dtype),
        }
        return [], state

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("batch_norm_param")
        eps = p.get_float("eps", 1e-5)
        frac = p.get_float("moving_average_fraction", 0.999)
        use_global = p.get_bool("use_global_stats", not train)
        x = inputs[0]
        # Statistics ALWAYS in f32: under bf16 mixed precision the
        # E[x^2]-E[x]^2 cancellation is catastrophic in an 8-bit mantissa
        # (measured: output std 293 instead of 1 on mean-100 activations).
        # Normalization-layer stats in f32 is the standard mixed-precision
        # contract; only the normalized output returns in x's dtype.
        xf = x.astype(jnp.float32)
        if x.ndim == 4 and layout.is_nhwc():
            axes = (0, 1, 2)  # all but the trailing channel axis
        else:
            axes = (0,) + tuple(range(2, x.ndim))
        if use_global:
            scale = jnp.where(state["scale_factor"][0] == 0, 1.0, 1.0 / jnp.maximum(state["scale_factor"][0], 1e-30))
            mean = state["mean"].astype(jnp.float32) * scale
            var = state["variance"].astype(jnp.float32) * scale
            new_state = state
        else:
            mean = jnp.mean(xf, axis=axes)
            # biased, E[x^2]-E[x]^2 as Caffe — clamped: the cancellation
            # can dip (beyond eps) below zero in f32 on large unnormalized
            # activations, and sqrt(var+eps) then NaNs the whole net
            var = jnp.maximum(
                jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean), 0.0)
            new_state = {
                "mean": state["mean"] * frac + mean.astype(state["mean"].dtype),
                "variance": state["variance"] * frac + var.astype(state["variance"].dtype),
                "scale_factor": state["scale_factor"] * frac + 1.0,
            }
        shape = layout.channel_bshape(x.ndim)
        # same clamp on the use site: global stats restored from a
        # checkpoint may carry the unclamped accumulation
        denom = jnp.sqrt(
            jnp.maximum(var.reshape(shape), 0.0) + eps)
        y = ((xf - mean.reshape(shape)) / denom).astype(x.dtype)
        return LayerOutput([y], new_state)


def _broadcast_canon(vec, x, axis):
    """Broadcast a canonical-ordered blob ``vec`` against internal ``x``
    from canonical ``axis`` (Scale/Bias semantics).  Under nchw this is
    the plain reshape; under nhwc on a 4D blob the broadcast shape is
    permuted (and the tiny param transposed when it spans more than one
    non-unit canonical axis) so the SAME blob bytes scale the same
    logical elements in either layout."""
    cb = (1,) * axis + tuple(vec.shape) + (1,) * (x.ndim - axis - vec.ndim)
    v = vec.astype(x.dtype).reshape(cb)
    if x.ndim == 4 and layout.is_nhwc():
        if sum(int(d) > 1 for d in cb[1:]) > 1:
            v = v.transpose(0, 2, 3, 1)
        else:
            v = v.reshape((cb[0], cb[2], cb[3], cb[1]))
    return v


@register
class Scale(Layer):
    """Channel-wise scale (+ optional bias); companion of BatchNorm in
    later zoo prototxts.  axis/num_axes control the broadcast shape
    (canonical blob coordinates in every layout)."""

    TYPE = "Scale"

    def _shape(self, in_shapes):
        p = self.lp.get_msg("scale_param")
        shape0 = _canon_shape(in_shapes[0])
        axis = _canon_axis(p.get_int("axis", 1), len(shape0))
        num_axes = p.get_int("num_axes", 1)
        if len(in_shapes) > 1:
            return None, axis  # scale comes from second bottom
        if num_axes == -1:
            return tuple(shape0[axis:]), axis
        return tuple(shape0[axis : axis + num_axes]), axis

    def init(self, key, in_shapes):
        p = self.lp.get_msg("scale_param")
        shape, _ = self._shape(in_shapes)
        dtype = get_config().param_dtype
        params = []
        if shape is None:
            # scale arrives via the second bottom; a learnable bias (shaped
            # like the bottom-supplied scale) may still be declared
            if p.get_bool("bias_term", False):
                bshape = tuple(in_shapes[1])
                params.append(fillers.fill(p.get_msg("bias_filler"), key, bshape, dtype))
            return params, {}
        filler = p.get_msg("filler")
        if not filler.has("type"):
            filler = filler.copy()
            filler.set("type", "constant").set("value", 1.0)
        params.append(fillers.fill(filler, key, shape, dtype))
        if p.get_bool("bias_term", False):
            params.append(fillers.fill(p.get_msg("bias_filler"), key, shape, dtype))
        return params, {}

    def apply(self, params, state, inputs, *, train, rng=None):
        x = inputs[0]
        shape, axis = self._shape([i.shape for i in inputs])
        if len(inputs) > 1:
            scale, bias = inputs[1], (params[0] if params else None)
        else:
            scale, bias = params[0], (params[1] if len(params) > 1 else None)
        y = x * _broadcast_canon(scale, x, axis)
        if bias is not None:
            y = y + _broadcast_canon(bias, x, axis)
        return LayerOutput([y])


@register
class Bias(Layer):
    """Channel-wise additive bias layer."""

    TYPE = "Bias"

    def init(self, key, in_shapes):
        if len(in_shapes) > 1:
            return [], {}
        p = self.lp.get_msg("bias_param")
        shape0 = _canon_shape(in_shapes[0])
        axis = _canon_axis(p.get_int("axis", 1), len(shape0))
        num_axes = p.get_int("num_axes", 1)
        shape = tuple(shape0[axis:]) if num_axes == -1 else tuple(shape0[axis : axis + num_axes])
        return [fillers.fill(p.get_msg("filler"), key, shape, get_config().param_dtype)], {}

    def apply(self, params, state, inputs, *, train, rng=None):
        x = inputs[0]
        p = self.lp.get_msg("bias_param")
        axis = _canon_axis(p.get_int("axis", 1), x.ndim)
        b = inputs[1] if len(inputs) > 1 else params[0]
        return LayerOutput([x + _broadcast_canon(b, x, axis)])


@register
class Embed(Layer):
    """Embedding lookup (ref: embed_layer.cpp): W blob (input_dim, num_output),
    output shape = input shape + (num_output,)."""

    TYPE = "Embed"

    def init(self, key, in_shapes):
        p = self.lp.get_msg("embed_param")
        shape = (p.get_int("input_dim"), p.get_int("num_output"))
        kw, kb = jax.random.split(key)
        dtype = get_config().param_dtype
        params = [fillers.fill(p.get_msg("weight_filler"), kw, shape, dtype)]
        if p.get_bool("bias_term", True):
            params.append(fillers.fill(p.get_msg("bias_filler"), kb, (shape[1],), dtype))
        return params, {}

    def apply(self, params, state, inputs, *, train, rng=None):
        idx = inputs[0].astype(jnp.int32)
        y = jnp.take(params[0], idx, axis=0)
        if len(params) > 1:
            y = y + params[1]
        return LayerOutput([y])


@register
class Eltwise(Layer):
    """PROD / SUM (with coeffs) / MAX over N bottoms (ref: eltwise_layer.cpp)."""

    TYPE = "Eltwise"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("eltwise_param")
        op = p.get_str("operation", "SUM")
        if op == "PROD":
            y = inputs[0]
            for x in inputs[1:]:
                y = y * x
        elif op == "MAX":
            y = inputs[0]
            for x in inputs[1:]:
                y = jnp.maximum(y, x)
        else:  # SUM
            coeffs = [float(c) for c in p.get_all("coeff")] or [1.0] * len(inputs)
            if len(coeffs) != len(inputs):
                raise ValueError(
                    f"Eltwise {self.name}: {len(coeffs)} coeffs for {len(inputs)} bottoms"
                )
            y = coeffs[0] * inputs[0]
            for c, x in zip(coeffs[1:], inputs[1:]):
                y = y + c * x
        return LayerOutput([y])


@register
class Concat(Layer):
    """ref: concat_layer.cpp (axis, legacy concat_dim)."""

    TYPE = "Concat"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("concat_param")
        axis = p.get_int("axis", p.get_int("concat_dim", 1))
        axis = layout.internal_axis(
            _canon_axis(axis, inputs[0].ndim), inputs[0].ndim)
        return LayerOutput([jnp.concatenate(inputs, axis=axis)])


@register
class Slice(Layer):
    """ref: slice_layer.cpp — slice_point list or equal split into #tops."""

    TYPE = "Slice"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("slice_param")
        axis = _canon_axis(p.get_int("axis", p.get_int("slice_dim", 1)), inputs[0].ndim)
        axis = layout.internal_axis(axis, inputs[0].ndim)
        points = [int(s) for s in p.get_all("slice_point")]
        x = inputs[0]
        n_tops = len(self.tops)
        if not points:
            size = x.shape[axis] // n_tops
            points = [size * i for i in range(1, n_tops)]
        return LayerOutput(jnp.split(x, points, axis=axis))


@register
class Split(Layer):
    """Identity fan-out (ref: split_layer.cpp).  Under autodiff the diff
    accumulation Caffe inserts split layers for is automatic."""

    TYPE = "Split"

    def apply(self, params, state, inputs, *, train, rng=None):
        return LayerOutput([inputs[0] for _ in self.tops])


@register
class Flatten(Layer):
    """Flatten axis..end_axis (ref: flatten_layer.cpp)."""

    TYPE = "Flatten"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("flatten_param")
        x = inputs[0]
        axis = _canon_axis(p.get_int("axis", 1), x.ndim)
        end = _canon_axis(p.get_int("end_axis", -1), x.ndim)
        if x.ndim == 4 and layout.is_nhwc() and end > axis:
            # the flattened blob's element order is canonical C-major
            # (what downstream fc weights index); a global-pooled head
            # (H == W == 1, the zoo's only nhwc flatten) keeps that
            # order for free, anything else pays one canonicalizing
            # transpose
            if not (x.shape[1] == 1 and x.shape[2] == 1):
                x = x.transpose(0, 3, 1, 2)
            else:
                x = x.reshape(x.shape[0], x.shape[3], 1, 1)
            mid = int(np.prod(x.shape[axis : end + 1]))
            return LayerOutput(
                [x.reshape(x.shape[:axis] + (mid,) + x.shape[end + 1 :])])
        mid = int(np.prod(x.shape[axis : end + 1]))
        return LayerOutput([x.reshape(x.shape[:axis] + (mid,) + x.shape[end + 1 :])])


@register
class Reshape(Layer):
    """ref: reshape_layer.cpp — dims 0 (copy) and -1 (infer), axis/num_axes."""

    TYPE = "Reshape"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("reshape_param")
        shape_msg = p.get_msg("shape")
        dims = [int(d) for d in shape_msg.get_all("dim")]
        x = inputs[0]
        nhwc4 = x.ndim == 4 and layout.is_nhwc()
        if nhwc4:
            # reshape dims speak canonical blob order: canonicalize in,
            # re-orient a still-4D result back to internal below
            x = x.transpose(0, 3, 1, 2)
        axis = _canon_axis(p.get_int("axis", 0), x.ndim)
        num_axes = p.get_int("num_axes", -1)
        end = x.ndim if num_axes == -1 else axis + num_axes
        head, mid_in, tail = x.shape[:axis], x.shape[axis:end], x.shape[end:]
        out_mid = []
        for i, d in enumerate(dims):
            if d == 0:
                out_mid.append(mid_in[i])
            else:
                out_mid.append(d)
        if -1 in out_mid:
            known = int(np.prod([d for d in out_mid if d != -1]))
            total = int(np.prod(mid_in)) if mid_in else 1
            out_mid[out_mid.index(-1)] = total // max(known, 1)
        y = x.reshape(head + tuple(out_mid) + tail)
        if nhwc4 and y.ndim == 4:
            y = y.transpose(0, 2, 3, 1)
        return LayerOutput([y])


@register
class Tile(Layer):
    """ref: tile_layer.cpp."""

    TYPE = "Tile"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("tile_param")
        x = inputs[0]
        axis = layout.internal_axis(
            _canon_axis(p.get_int("axis", 1), x.ndim), x.ndim)
        tiles = p.get_int("tiles")
        reps = [1] * x.ndim
        reps[axis] = tiles
        return LayerOutput([jnp.tile(x, reps)])


@register
class ArgMax(Layer):
    """ref: argmax_layer.cpp — per-sample top_k over flattened non-batch
    dims; output (N, 1, top_k) or (N, 2, top_k) with out_max_val."""

    TYPE = "ArgMax"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("argmax_param")
        top_k = p.get_int("top_k", 1)
        out_max_val = p.get_bool("out_max_val", False)
        x = inputs[0]
        if x.ndim == 4 and layout.is_nhwc():
            # returned INDICES address the canonical C*H*W flatten
            x = x.transpose(0, 3, 1, 2)
        flat = x.reshape(x.shape[0], -1)
        vals, idxs = jax.lax.top_k(flat, top_k)
        idxs = idxs.astype(x.dtype)
        if out_max_val:
            y = jnp.stack([idxs, vals], axis=1)  # (N, 2, top_k)
        else:
            y = idxs[:, None, :]  # (N, 1, top_k)
        return LayerOutput([y])


@register
class BatchReindex(Layer):
    """output = x[permutation] (ref: batch_reindex_layer.cpp)."""

    TYPE = "BatchReindex"

    def apply(self, params, state, inputs, *, train, rng=None):
        return LayerOutput([jnp.take(inputs[0], inputs[1].astype(jnp.int32), axis=0)])


@register
class Reduction(Layer):
    """SUM/ASUM/SUMSQ/MEAN over tail dims from ``axis`` (ref: reduction_layer.cpp)."""

    TYPE = "Reduction"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("reduction_param")
        op = p.get_str("operation", "SUM")
        coeff = p.get_float("coeff", 1.0)
        x = inputs[0]
        if x.ndim == 4 and layout.is_nhwc():
            # tail-flatten semantics are canonical; the reductions are
            # permutation-invariant but the kept head axes are not
            x = x.transpose(0, 3, 1, 2)
        axis = _canon_axis(p.get_int("axis", 0), x.ndim)
        flat = x.reshape(x.shape[:axis] + (-1,)) if axis < x.ndim else x[..., None]
        if op == "ASUM":
            y = jnp.sum(jnp.abs(flat), axis=-1)
        elif op == "SUMSQ":
            y = jnp.sum(flat * flat, axis=-1)
        elif op == "MEAN":
            y = jnp.mean(flat, axis=-1)
        else:
            y = jnp.sum(flat, axis=-1)
        return LayerOutput([coeff * y])


@register
class MVN(Layer):
    """Mean-variance normalization per sample (ref: mvn_layer.cpp)."""

    TYPE = "MVN"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("mvn_param")
        across = p.get_bool("across_channels", False)
        norm_var = p.get_bool("normalize_variance", True)
        eps = p.get_float("eps", 1e-9)
        x = inputs[0]
        if x.ndim == 4 and layout.is_nhwc() and not across:
            axes: tuple = layout.spatial_axes()  # per-channel moments
        else:
            axes = tuple(range(1, x.ndim)) if across else tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        y = x - mean
        if norm_var:
            std = jnp.sqrt(jnp.mean(jnp.square(y), axis=axes, keepdims=True))
            y = y / (std + eps)
        return LayerOutput([y])


@register
class RMSNorm(Layer):
    """Root-mean-square normalization over the last axis (Zhang &
    Sennrich 2019; the norm of the OLMo / Llama-style decoders):
    ``y = w · x / sqrt(mean(x²) + eps)``.  One weight blob (D,), ones at
    initialisation, no bias.  The statistics are taken in f32 whatever
    the compute dtype; the normalized value returns to the input's dtype
    before the weight multiplies it.  ``rms_norm_param { zero_centered:
    true }``: the blob starts at zeros and the weight is ``1 + w`` (the
    Qwen3-Next family's norm: weight decay then draws the weight to 1)."""

    TYPE = "RMSNorm"

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("rms_norm_param")
        self.eps = p.get_float("eps", 1e-5)
        self.zero_centered = p.get_bool("zero_centered", False)

    def init(self, key, in_shapes):
        fill = jnp.zeros if self.zero_centered else jnp.ones
        return [fill((in_shapes[0][-1],), get_config().param_dtype)], {}

    def apply(self, params, state, inputs, *, train, rng=None):
        weight = 1.0 + params[0] if self.zero_centered else params[0]
        return LayerOutput([rms_norm(inputs[0], weight, self.eps)])


def rms_norm(x, weight, eps: float, axis=-1):
    """The RMSNorm layer's arithmetic (also the q/k norm of
    ``MultiHeadAttention``, whose features lie on two axes of a
    head-major value; ``weight`` broadcasts against ``x``)."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=axis, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * weight.astype(x.dtype)


@register
class LayerNorm(Layer):
    """Layer normalization over the last axis (Ba et al. 2016):
    ``y = w · (x - mean) / sqrt(var + eps) + b``.  Blobs [w (D), b (D)],
    ones and zeros at initialisation.  The statistics are taken in f32
    whatever the compute dtype; the normalized value returns to the
    input's dtype before the weight multiplies it."""

    TYPE = "LayerNorm"

    def init(self, key, in_shapes):
        d, dtype = in_shapes[0][-1], get_config().param_dtype
        return [jnp.ones((d,), dtype), jnp.zeros((d,), dtype)], {}

    def apply(self, params, state, inputs, *, train, rng=None):
        x = inputs[0]
        eps = self.lp.get_msg("layer_norm_param").get_float("eps", 1e-5)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
        return LayerOutput([y * params[0].astype(x.dtype)
                            + params[1].astype(x.dtype)])


# device scope of the gated memory unit's product; in common.CACHE_SCOPES
GATE_SCOPE = "R.gate"


@register
class GatedMemoryUnit(Layer):
    """The SambaY cross-decoder's gated memory unit (arXiv:2507.06607
    section 2): ``y = W_2 (silu(W_1 x) ⊙ m)`` over the last axis, x the
    layer's input [B, S, E] and m [B, S, d] a MEMORY another layer wrote
    (a ``Mamba`` layer's second top).  Bottoms [x, m]; blobs W_1 (d, E),
    W_2 (E, d), no biases; d is the memory's width."""

    TYPE = "GatedMemoryUnit"

    def init(self, key, in_shapes):
        e, d = in_shapes[0][-1], in_shapes[1][-1]
        p = self.lp.get_msg("gmu_param")
        wf = (p.get_msg("weight_filler") if p.has("weight_filler")
              else Message().set("type", "xavier"))
        dtype = get_config().param_dtype
        k1, k2 = jax.random.split(key)
        return [fillers.fill(wf, k1, (d, e), dtype),
                fillers.fill(wf, k2, (e, d), dtype)], {}

    def apply(self, params, state, inputs, *, train, rng=None):
        x, memory = inputs
        gate = x @ params[0].T
        with jax.named_scope(GATE_SCOPE):
            gated = jax.nn.silu(gate) * memory.astype(gate.dtype)
        return LayerOutput([gated @ params[1].T])


def gated_mlp(x, w_gate, w_up, w_down):
    """``(silu(x W_gateᵀ) · x W_upᵀ) W_downᵀ`` over the last axis, every
    matrix ``[out, in]``: the dense SwiGLU feed-forward of the
    DeepSeek / Llama-style decoders.  The ``GatedMLP`` layer and the MoE
    layer's shared expert (ops/moe.py) are both this function."""
    h = jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)
    return h @ w_down.T


@register
class GatedMLP(Layer):
    """Gated SiLU feed-forward over the last axis (:func:`gated_mlp`).
    ``gated_mlp_param { hidden_dim: 7168 }``; blobs [W_gate (H, D),
    W_up (H, D), W_down (D, H)], no biases."""

    TYPE = "GatedMLP"

    def init(self, key, in_shapes):
        p = self.lp.get_msg("gated_mlp_param")
        d, h = in_shapes[0][-1], p.get_int("hidden_dim")
        wf = (p.get_msg("weight_filler") if p.has("weight_filler")
              else Message().set("type", "xavier"))
        dtype = get_config().param_dtype
        kg, ku, kd = jax.random.split(key, 3)
        return [fillers.fill(wf, kg, (h, d), dtype),
                fillers.fill(wf, ku, (h, d), dtype),
                fillers.fill(wf, kd, (d, h), dtype)], {}

    def apply(self, params, state, inputs, *, train, rng=None):
        return LayerOutput([gated_mlp(inputs[0], *params)])


@register
class Silence(Layer):
    """Consumes bottoms, produces nothing (ref: silence_layer.cpp)."""

    TYPE = "Silence"

    def apply(self, params, state, inputs, *, train, rng=None):
        return LayerOutput([])


@register
class Filter(Layer):
    """ref: filter_layer.cpp — select items where the selector is nonzero.
    Output batch size is data-dependent; jit requires static shapes, so in
    compiled graphs this masks (zeroes) filtered items instead of dropping
    them, and the eager path performs a true gather."""

    TYPE = "Filter"

    def apply(self, params, state, inputs, *, train, rng=None):
        *data, selector = inputs
        sel = selector.reshape(selector.shape[0])
        if isinstance(sel, jax.core.Tracer):
            mask = (sel != 0).astype(data[0].dtype)
            outs = [x * mask.reshape((-1,) + (1,) * (x.ndim - 1)) for x in data]
        else:
            idx = jnp.nonzero(sel)[0]
            outs = [jnp.take(x, idx, axis=0) for x in data]
        return LayerOutput(outs)
