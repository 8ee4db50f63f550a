"""Loss + evaluation layers (ref: caffe/include/caffe/loss_layers.hpp and
caffe/src/caffe/layers/*_loss_layer.cpp).  Scalar tops; the graph executor
applies ``loss_weight`` and autodiff replaces every hand-written Backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.ops import layout
from sparknet_tpu.ops.base import Layer, LayerOutput
from sparknet_tpu.ops.registry import register

_FLT_MIN = float(np.finfo(np.float32).tiny)
_LOG_THRESHOLD = 1e-20  # ref: loss layers clip probabilities at kLOG_THRESHOLD


def _softmax(x, axis):
    return jax.nn.softmax(x, axis=axis)


@register
class Softmax(Layer):
    """Plain softmax along ``axis`` (ref: softmax_layer.cpp)."""

    TYPE = "Softmax"

    def apply(self, params, state, inputs, *, train, rng=None):
        axis = self.lp.get_msg("softmax_param").get_int("axis", 1)
        x = inputs[0]
        axis = layout.internal_axis(axis + x.ndim if axis < 0 else axis,
                                    x.ndim)
        return LayerOutput([_softmax(x, axis)])


class _LossBase(Layer):
    IS_LOSS = True

    def _loss_param(self):
        lp = self.lp.get_msg("loss_param")
        ignore = lp.get_int("ignore_label") if lp.has("ignore_label") else None
        normalize = lp.get_bool("normalize", True)
        return ignore, normalize

    def _keeps_value(self) -> bool:
        """``loss_param { keep_value: true }``: the layer keeps its last
        value in its state (``value``), for a reader that looks only at
        fences (``Solver._fence_stats``): one term of a total that the
        step returns only summed."""
        return self.lp.get_msg("loss_param").get_bool("keep_value", False)


@register
class SoftmaxWithLoss(_LossBase):
    """ref: softmax_loss_layer.cpp:50-81 — softmax over ``axis`` (default 1),
    NLL with FLT_MIN clipping, optional ignore_label; normalize=true divides
    by the count of non-ignored positions, else by outer_num (batch)."""

    TYPE = "SoftmaxWithLoss"

    def init(self, key, in_shapes):
        keeps = self._keeps_value()
        return [], {"value": jnp.zeros((), jnp.float32)} if keeps else {}

    def apply(self, params, state, inputs, *, train, rng=None):
        x, label = inputs[0], inputs[1]
        axis = self.lp.get_msg("softmax_param").get_int("axis", 1)
        # class axis is canonical (NCHW blob order); on internal nhwc 4D
        # blobs it sits last, where the label grid (N, H, W) already
        # matches the moved probability block elementwise
        axis = layout.internal_axis(axis + x.ndim if axis < 0 else axis,
                                    x.ndim)
        ignore, normalize = self._loss_param()
        prob = _softmax(x, axis)
        lab = label.astype(jnp.int32)
        # Gather p[n, label, spatial...]: move class axis last.
        p_moved = jnp.moveaxis(prob, axis, -1)
        lab_flat = lab.reshape(p_moved.shape[:-1])
        if ignore is not None:
            # clamp ignored labels before the gather: an out-of-range index
            # gathers a NaN fill that would poison the masked product
            gather_lab = jnp.where(lab_flat == ignore, 0, lab_flat)
        else:
            gather_lab = lab_flat
        picked = jnp.take_along_axis(p_moved, gather_lab[..., None], axis=-1)[..., 0]
        nll = -jnp.log(jnp.maximum(picked, _FLT_MIN))
        if ignore is not None:
            valid = (lab_flat != ignore).astype(nll.dtype)
            nll = nll * valid
            count = jnp.sum(valid)
        else:
            count = jnp.array(nll.size, nll.dtype)
        outer = x.shape[0]
        denom = count if normalize else jnp.array(outer, nll.dtype)
        loss = jnp.sum(nll) / jnp.maximum(denom, 1)
        outs = [loss]
        if len(self.tops) > 1:
            outs.append(prob)
        return LayerOutput(
            outs, {"value": loss} if self._keeps_value() else {})


def exit_distribution(gate_logits):
    """[T, ...] exit-gate logits -> log p [T, ...], the distribution over
    exit steps of a looped model: with lambda_t = sigmoid(g_t),
    p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T and
    p_T = prod_{j<T} (1 - lambda_j) (step T takes what is left; its own
    gate is not read).  From log sigmoid(g) and log sigmoid(-g), never
    from a product of rounded probabilities."""
    stay = jax.nn.log_sigmoid(-gate_logits[:-1])  # log (1 - lambda_j), j < T
    before = jnp.concatenate(
        [jnp.zeros_like(gate_logits[:1]), jnp.cumsum(stay, axis=0)], axis=0)
    return before + jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]),
         jnp.zeros_like(gate_logits[:1])], axis=0)


@register
class ExitWeightedLoss(_LossBase):
    """The training loss of a looped language model with an exit gate
    (Ouro / LoopLM, "Scaling Latent Reasoning via Looped Language Models",
    section 3, Stage I): the expected next-token cross-entropy under the
    distribution over exit steps, with an entropy regulariser,

        loss = mean_tokens[ sum_t p_t L_t - beta H(p) ],
        H(p) = -sum_t p_t log p_t.

    ``exit_loss_param { steps: T  entropy_weight: beta }``.  Bottoms: the
    logits of the T passes, pass-major along axis 0 ([T N, S, V], as a
    looped region collects them); the labels [N, S]; the exit gate's
    logits [T N, S, 1].  Tops: ``loss``; at weight 0 the T mean per-step
    cross-entropies [T] and the mean exit step, mean_tokens sum_t t p_t
    (1-based).  The two weight-0 tops are kept in the layer's state too
    (``step_loss`` / ``exit_mean_step``) for the fence's span
    (``Solver._fence_stats``).  f32 throughout; the backward is jax's."""

    TYPE = "ExitWeightedLoss"

    def _conf(self):
        p = self.lp.get_msg("exit_loss_param")
        return p.get_int("steps", 1), p.get_float("entropy_weight", 0.0)

    def init(self, key, in_shapes):
        steps, _ = self._conf()
        if in_shapes[0][0] != steps * in_shapes[1][0]:
            raise ValueError(
                f"{self.name}: {in_shapes[0][0]} rows of logits are not "
                f"{steps} passes of the labels' {in_shapes[1][0]}")
        return [], {"step_loss": jnp.zeros((steps,), jnp.float32),
                    "exit_mean_step": jnp.zeros((), jnp.float32)}

    def apply(self, params, state, inputs, *, train, rng=None):
        steps, beta = self._conf()
        logits, label, gate = inputs
        lab = label.astype(jnp.int32)
        logits = logits.reshape((steps, *lab.shape, logits.shape[-1]))
        picked = jnp.take_along_axis(
            logits, jnp.broadcast_to(lab, logits.shape[:-1])[..., None],
            axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked  # L_t, [T, N, S]
        logp = exit_distribution(gate.reshape((steps, *lab.shape)))
        p = jnp.exp(logp)
        entropy = -jnp.sum(p * logp, axis=0)
        loss = jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)
        step_loss = jnp.mean(nll, axis=tuple(range(1, nll.ndim)))
        order = jnp.arange(1, steps + 1, dtype=jnp.float32).reshape(
            (steps,) + (1,) * lab.ndim)
        exit_mean = jnp.mean(jnp.sum(order * p, axis=0))
        kept = {"step_loss": step_loss, "exit_mean_step": exit_mean}
        return LayerOutput([loss, step_loss, exit_mean][:len(self.tops)], kept)


@register
class EuclideanLoss(_LossBase):
    """0.5/N * sum((a-b)^2) (ref: euclidean_loss_layer.cpp)."""

    TYPE = "EuclideanLoss"

    def apply(self, params, state, inputs, *, train, rng=None):
        a, b = inputs[0], inputs[1]
        n = a.shape[0]
        return LayerOutput([jnp.sum(jnp.square(a - b)) / (2.0 * n)])


@register
class HingeLoss(_LossBase):
    """ref: hinge_loss_layer.cpp — v_nk = x_nk (k!=y), -x_ny (k==y);
    loss = sum max(0, 1+v)^p / N with p in {1,2} (norm L1/L2)."""

    TYPE = "HingeLoss"

    def apply(self, params, state, inputs, *, train, rng=None):
        norm = self.lp.get_msg("hinge_loss_param").get_str("norm", "L1")
        x, label = inputs[0], inputs[1]
        n = x.shape[0]
        flat = x.reshape(n, -1)
        lab = label.reshape(n).astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, flat.shape[1], dtype=flat.dtype)
        v = flat * (1.0 - 2.0 * onehot)
        margins = jnp.maximum(0.0, 1.0 + v)
        if norm == "L2":
            loss = jnp.sum(margins * margins) / n
        else:
            loss = jnp.sum(margins) / n
        return LayerOutput([loss])


@register
class MultinomialLogisticLoss(_LossBase):
    """Bottom is already probabilities (ref: multinomial_logistic_loss_layer.cpp):
    -1/N sum log(max(p[y], kLOG_THRESHOLD))."""

    TYPE = "MultinomialLogisticLoss"

    def apply(self, params, state, inputs, *, train, rng=None):
        p, label = inputs[0], inputs[1]
        n = p.shape[0]
        flat = p.reshape(n, -1)
        lab = label.reshape(n).astype(jnp.int32)
        picked = jnp.take_along_axis(flat, lab[:, None], axis=1)[:, 0]
        return LayerOutput([-jnp.sum(jnp.log(jnp.maximum(picked, _LOG_THRESHOLD))) / n])


@register
class InfogainLoss(_LossBase):
    """ref: infogain_loss_layer.cpp — loss = -1/N sum_k H[y,k] log(p_k);
    H (infogain matrix) comes from the third bottom (matrix-from-file is
    handled at graph build via DummyData/MemoryData feeding)."""

    TYPE = "InfogainLoss"

    def apply(self, params, state, inputs, *, train, rng=None):
        p, label = inputs[0], inputs[1]
        n = p.shape[0]
        flat = p.reshape(n, -1)
        k = flat.shape[1]
        H = inputs[2].reshape(k, k) if len(inputs) > 2 else jnp.eye(k, dtype=flat.dtype)
        lab = label.reshape(n).astype(jnp.int32)
        logp = jnp.log(jnp.maximum(flat, _LOG_THRESHOLD))
        rows = jnp.take(H, lab, axis=0)  # (N, K)
        return LayerOutput([-jnp.sum(rows * logp) / n])


@register
class SigmoidCrossEntropyLoss(_LossBase):
    """Numerically-stable elementwise BCE on logits, summed and divided by
    batch size (ref: sigmoid_cross_entropy_loss_layer.cpp)."""

    TYPE = "SigmoidCrossEntropyLoss"

    def apply(self, params, state, inputs, *, train, rng=None):
        x, t = inputs[0], inputs[1]
        n = x.shape[0]
        loss = jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
        return LayerOutput([jnp.sum(loss) / n])


@register
class ContrastiveLoss(_LossBase):
    """ref: contrastive_loss_layer.cpp:30-62 — d2 = ||a-b||^2;
    similar: d2; dissimilar: legacy max(margin-d2,0), else max(margin-d,0)^2;
    loss = sum / (2N)."""

    TYPE = "ContrastiveLoss"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("contrastive_loss_param")
        margin = p.get_float("margin", 1.0)
        legacy = p.get_bool("legacy_version", False)
        a, b, y = inputs[0], inputs[1], inputs[2]
        n = a.shape[0]
        d2 = jnp.sum(jnp.square(a.reshape(n, -1) - b.reshape(n, -1)), axis=1)
        sim = y.reshape(n).astype(d2.dtype)
        if legacy:
            dis = jnp.maximum(margin - d2, 0.0)
        else:
            # safe sqrt: grad(sqrt) at 0 is inf, and the outer maximum does
            # not mask it (margin - 0 > 0 keeps the branch live), so identical
            # dissimilar-pair embeddings would NaN the whole gradient
            d = jnp.sqrt(jnp.where(d2 > 0.0, d2, 1.0)) * (d2 > 0.0)
            dis = jnp.square(jnp.maximum(margin - d, 0.0))
        return LayerOutput([jnp.sum(sim * d2 + (1.0 - sim) * dis) / (2.0 * n)])


@register
class Accuracy(Layer):
    """Top-k accuracy over the label axis, with ignore_label
    (ref: accuracy_layer.cpp).  Evaluation-only; never contributes loss."""

    TYPE = "Accuracy"

    def apply(self, params, state, inputs, *, train, rng=None):
        p = self.lp.get_msg("accuracy_param")
        top_k = p.get_int("top_k", 1)
        axis = p.get_int("axis", 1)
        ignore = p.get_int("ignore_label") if p.has("ignore_label") else None
        x, label = inputs[0], inputs[1]
        axis = layout.internal_axis(axis + x.ndim if axis < 0 else axis,
                                    x.ndim)
        scores = jnp.moveaxis(x, axis, -1)  # (..., classes)
        lab = label.astype(jnp.int32).reshape(scores.shape[:-1])
        gather_lab = jnp.where(lab == ignore, 0, lab) if ignore is not None else lab
        true_score = jnp.take_along_axis(scores, gather_lab[..., None], axis=-1)
        # rank of true class = #classes strictly greater (ties count as correct,
        # matching Caffe's ">=" comparison scanning in index order)
        higher = jnp.sum((scores > true_score).astype(jnp.int32), axis=-1)
        correct = (higher < top_k).astype(jnp.float32)
        if ignore is not None:
            valid = (lab != ignore).astype(jnp.float32)
            acc = jnp.sum(correct * valid) / jnp.maximum(jnp.sum(valid), 1)
        else:
            acc = jnp.mean(correct)
        return LayerOutput([acc])
