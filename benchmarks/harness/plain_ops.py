"""Plain layer arithmetic for the configurations' references.

Straightforward ``jax.numpy``/``lax`` in float32, Caffe semantics, NCHW
blobs and OIHW weights, no kernels, no fusion tricks, nothing imported
from the program.  Callers run these under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise done in bf16 passes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.harness.flops import pool_out


def conv(x, w, b=None, stride=1, pad=0, group=1):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=group)
    return y if b is None else y + b[None, :, None, None]


def relu(x):
    return jnp.where(x > 0, x, 0.0)


def lrn(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    """Across channels: x / (k + alpha/size * sum_window x^2)^beta."""
    half = size // 2
    sq = jnp.pad(x * x, ((0, 0), (half, half), (0, 0), (0, 0)))
    c = x.shape[1]
    win = sum(sq[:, i:i + c] for i in range(size))
    return x / (k + (alpha / size) * win) ** beta


def max_pool(x, k, s, pad=0):
    n, c, h, w = x.shape
    oh, ow = pool_out(h, k, s, pad), pool_out(w, k, s, pad)
    eh = max((oh - 1) * s + k - h - pad, 0)
    ew = max((ow - 1) * s + k - w - pad, 0)
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad, eh), (pad, ew)),
                 constant_values=-jnp.inf)
    return lax.reduce_window(xp, -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, s, s), "VALID")


def global_ave_pool(x):
    return jnp.mean(x, axis=(2, 3), keepdims=True)


def fc(x, w, b=None):
    y = x.reshape(x.shape[0], -1) @ w.T
    return y if b is None else y + b


def dropout(x, mask, ratio=0.5):
    """Inverted dropout with a GIVEN keep mask (dropout_layer.cpp)."""
    return jnp.where(mask, x / (1.0 - ratio), 0.0)


def batch_norm_train(x, eps=1e-5):
    """Batch statistics over N,H,W as Caffe's BatchNorm layer takes them
    (batch_norm_layer.cpp): mean = E[x], variance = E[x^2] - E[x]^2
    (biased), y = (x - mean) / sqrt(variance + eps).  The textbook
    E[(x - mean)^2] is the same number but not the same float32 gradient:
    against it the program's last-conv update differs by 1.7e-2 in f32,
    against this form by 5e-6.  The clamp at zero only guards the square
    root against cancellation on a constant channel."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(x * x, axis=(0, 2, 3), keepdims=True) - mean * mean
    return (x - mean) / jnp.sqrt(jnp.maximum(var, 0.0) + eps)


def scale(x, gamma, beta):
    return x * gamma[None, :, None, None] + beta[None, :, None, None]


def softmax_loss(logits, labels):
    """SoftmaxWithLoss: mean over the batch of -log p[label]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def caffe_sgd(w, g, hist, *, lr, momentum, weight_decay, lr_mult=1.0,
              decay_mult=1.0):
    """SGDSolver: g += wd*decay_mult*w; h = mu*h + lr*lr_mult*g; w -= h."""
    g = g + (weight_decay * decay_mult) * w
    hist = momentum * hist + (lr * lr_mult) * g
    return w - hist, hist
