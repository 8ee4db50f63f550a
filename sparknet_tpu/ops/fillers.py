"""Weight fillers (ref: caffe/include/caffe/filler.hpp).

Each filler takes a prototxt ``FillerParameter`` message, a PRNG key, and
the blob shape; returns an initialized array.  Fan-in follows Caffe's
convention: ``fan_in = count / num`` (first axis is the output dim for both
conv OIHW and inner-product (out, in) blobs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.proto.text_format import Message


def _fans(shape) -> tuple[int, int]:
    count = int(np.prod(shape))
    num = shape[0] if shape else 1
    fan_in = count // max(num, 1)
    # fan_out = count / channels for conv (ref filler.hpp MSRAFiller)
    fan_out = count // max(shape[1], 1) if len(shape) > 1 else count
    return fan_in, fan_out


def normal(key: jax.Array, shape, dtype) -> jax.Array:
    """The sampler's result as a program of its own leaves it: inside one
    jitted init XLA would fold a filler's ``std`` into the sampler's last
    product (one ulp off the eager value); the barrier is a no-op eagerly."""
    return jax.lax.optimization_barrier(jax.random.normal(key, shape, dtype))


def fill(filler: Message, key: jax.Array, shape, dtype=jnp.float32) -> jax.Array:
    ftype = filler.get_str("type", "constant")
    if ftype == "constant":
        return jnp.full(shape, filler.get_float("value", 0.0), dtype)
    if ftype == "uniform":
        lo, hi = filler.get_float("min", 0.0), filler.get_float("max", 1.0)
        return jax.random.uniform(key, shape, dtype, lo, hi)
    if ftype == "gaussian":
        mean, std = filler.get_float("mean", 0.0), filler.get_float("std", 1.0)
        out = mean + std * normal(key, shape, dtype)
        sparse = filler.get_int("sparse", -1)
        if sparse >= 0:
            # ref filler.hpp GaussianFiller: bernoulli mask with
            # p = sparse / num_outputs, num_outputs = blob shape[0]
            num_outputs = shape[0] if shape else 1
            prob = min(1.0, sparse / max(num_outputs, 1))
            k2 = jax.random.split(key, 2)[1]
            # a select: what XLA makes of ``out * mask`` inside a larger
            # program anyway (a dropped entry is +0, never a -0 product)
            out = jnp.where(jax.random.bernoulli(k2, prob, shape), out, 0)
        return out
    if ftype == "positive_unitball":
        x = jax.random.uniform(key, shape, dtype)
        flat = x.reshape(shape[0], -1)
        flat = flat / jnp.sum(flat, axis=1, keepdims=True)
        return flat.reshape(shape)
    if ftype == "xavier":
        fan_in, fan_out = _fans(shape)
        n = _variance_norm_n(filler, fan_in, fan_out)
        scale = float(np.sqrt(3.0 / n))
        return jax.random.uniform(key, shape, dtype, -scale, scale)
    if ftype == "msra":
        fan_in, fan_out = _fans(shape)
        n = _variance_norm_n(filler, fan_in, fan_out)
        std = float(np.sqrt(2.0 / n))
        return std * normal(key, shape, dtype)
    if ftype == "bilinear":
        return jnp.asarray(_bilinear_kernel(shape), dtype)
    raise ValueError(f"unknown filler type {ftype!r}")


def _variance_norm_n(filler: Message, fan_in: int, fan_out: int) -> float:
    norm = filler.get_str("variance_norm", "FAN_IN")
    if norm == "FAN_OUT":
        return float(fan_out)
    if norm == "AVERAGE":
        return (fan_in + fan_out) / 2.0
    return float(fan_in)


def _bilinear_kernel(shape) -> np.ndarray:
    """Upsampling kernel for Deconvolution (ref: filler.hpp BilinearFiller)."""
    assert len(shape) == 4 and shape[2] == shape[3], "bilinear needs square 4D blob"
    k = shape[3]
    f = int(np.ceil(k / 2.0))
    c = (2 * f - 1 - f % 2) / (2.0 * f)
    out = np.zeros(shape, np.float32)
    coords = np.arange(k)
    kern1d = 1 - np.abs(coords / f - c)
    kern2d = np.outer(kern1d, kern1d)
    out[...] = kern2d  # broadcast over leading dims
    return out
