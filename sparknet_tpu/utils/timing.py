"""Timing utilities: device-fenced timer + per-layer cost breakdown.

Equivalents of Caffe's cudaEvent ``Timer`` (ref:
caffe/src/caffe/util/benchmark.cpp:18-82) and the ``caffe time`` brew's
per-layer forward/backward timing loop (ref:
caffe/tools/caffe.cpp:290-380).  On TPU a real training step is ONE fused
XLA program, so per-layer numbers here are diagnostic (each layer jitted
and fenced in isolation) — the fused step is strictly faster; use
``jax.profiler`` traces for the true schedule.

:meth:`Timer.stop` closes its wall through ``common.value_fence`` — a
VALUE fetch of the timed program's own output.  To make that fence
cover a whole layer, :func:`time_layers` has each jitted program return
a scalar checksum with data dependence on every output/gradient leaf,
and stops the timer on that checksum.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.common import value_fence


class Timer:
    """start/stop wall timer whose stop edge is a value fence (the
    cudaEvent-synchronize analog, minus the readiness trap).

    ``stop(fence=out)`` fetches the VALUE of ``out``'s last pytree leaf
    via ``common.value_fence`` before reading the clock; arrange for
    that leaf to be a small scalar computed inside the timed program
    (a loss, a checksum).  ``stop()`` with no fence is a bare host wall.
    """

    def __init__(self):
        self._t0 = None
        self.elapsed_ms = 0.0

    def start(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def stop(self, fence: Any = None) -> float:
        if fence is not None:
            value_fence(fence)
        self.elapsed_ms = (time.perf_counter() - self._t0) * 1e3
        return self.elapsed_ms


def _checksum(leaves) -> jax.Array:
    """Scalar with data dependence on every leaf, computed INSIDE the
    jitted program that produced them — fetching its value is therefore
    a true execution fence for that program (a derived second dispatch
    would not be: ``value_fence`` trap 2)."""
    total = jnp.float32(0)
    for leaf in leaves:
        total = total + jnp.sum(leaf).astype(jnp.float32)
    return total


def time_layers(network, variables, feeds, iterations: int = 10) -> list[dict]:
    """Per-layer forward+backward timing (the ``caffe time`` table).

    Executes the net layer-by-layer with each layer's apply jitted and
    fenced separately; returns [{layer, type, forward_ms, backward_ms}].
    """
    rng = jax.random.PRNGKey(0)
    blobs: dict[str, Any] = dict(feeds)
    rows: list[dict] = []
    for layer in network.layers:
        lname = layer.name
        if not layer.bottoms and all(t in blobs for t in layer.tops):
            continue  # input layer: its tops are the feeds
        params = variables.params.get(lname, [])
        state = variables.state.get(lname, {})
        inputs = [blobs[b] for b in layer.bottoms]

        def fwd(params, state, inputs):
            out = layer.apply(params, state, inputs, train=True, rng=rng)
            return out.outputs, _checksum(out.outputs)

        jfwd = jax.jit(fwd)
        tops, chk = jfwd(params, state, inputs)  # compile + capture outputs
        t = Timer().start()
        for _ in range(iterations):
            tops, chk = jfwd(params, state, inputs)
        fwd_ms = t.stop(chk) / iterations

        bwd_ms = float("nan")
        float_idx = [
            i for i, x in enumerate(inputs)
            if np.issubdtype(np.asarray(x).dtype, np.floating)
        ]
        if float_idx:
            # differentiate w.r.t. params + the float inputs only (labels
            # and other integer bottoms are non-differentiable)
            def loss_like(params, float_ins):
                full = list(inputs)
                for i, x in zip(float_idx, float_ins):
                    full[i] = x
                out = layer.apply(params, state, full, train=True, rng=rng)
                return sum(jax.numpy.sum(t) for t in out.outputs)

            def bwd(params, float_ins):
                g = jax.grad(loss_like, argnums=(0, 1))(params, float_ins)
                return g, _checksum(jax.tree_util.tree_leaves(g))

            jbwd = jax.jit(bwd)
            try:
                g, gchk = jbwd(params, [inputs[i] for i in float_idx])
                t = Timer().start()
                for _ in range(iterations):
                    g, gchk = jbwd(params, [inputs[i] for i in float_idx])
                bwd_ms = t.stop(gchk) / iterations
            except Exception:
                pass  # non-differentiable layer (Accuracy, ArgMax, ...)

        for name, top in zip(layer.tops, tops):
            blobs[name] = top
        rows.append(
            {
                "layer": lname,
                "type": layer.TYPE,
                "forward_ms": round(fwd_ms, 3),
                "backward_ms": None if np.isnan(bwd_ms) else round(bwd_ms, 3),
            }
        )
    return rows
