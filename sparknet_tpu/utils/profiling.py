"""jax.profiler integration: device traces for the training loop.

The TPU answer to the reference's three profiling layers (app event log,
cudaEvent Timer, `caffe time` — SURVEY §5 tracing): a trace context that
captures XLA device timelines viewable in TensorBoard/Perfetto, plus a
step-annotation helper so outer-loop rounds show up as named spans.
"""

from __future__ import annotations

import contextlib
import os

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device+host profile into ``log_dir``.

    Usage::

        with profiling.trace("/tmp/profile"):
            trainer.train(10, data_fn)
    """
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir, create_perfetto_link=False)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def step_span(name: str, step: int):
    """Named span for one training step or round: a block in the trace
    (a ``StepTraceAnnotation``) and a line of the program's record
    (``obs.recorder.flight``), with what the step compiled, if anything."""
    from sparknet_tpu.obs.recorder import Span

    return Span(None, name, host=True, step=step, compile_stats=True)


def device_memory_stats() -> dict:
    """Per-device live/peak memory, where the backend exposes it."""
    out = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            out[f"{d.platform}:{d.id}"] = {
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
    return out
