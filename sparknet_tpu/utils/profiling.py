"""jax.profiler integration: device traces for the training loop.

The TPU answer to the reference's three profiling layers (app event log,
cudaEvent Timer, `caffe time` — SURVEY §5 tracing): a trace context that
captures XLA device timelines viewable in TensorBoard/Perfetto, plus a
step-annotation helper so outer-loop rounds show up as named spans.
"""

from __future__ import annotations

import contextlib
import os
import time

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


def step_account(jitted, *args) -> dict:
    """The HBM account of the program ``jitted`` has just compiled (or
    loaded) and run for ``args``, by class and per device as XLA reports
    it: ``hbm_args_bytes``, ``hbm_out_bytes``, ``hbm_alias_bytes`` (the
    donated arguments the outputs are written over), ``hbm_temps_bytes``,
    ``hbm_code_bytes``, the ``hbm_devices`` the program spans, and
    ``hbm_limit_bytes`` where the first of them on this host has a
    ``memory_stats()``.

    ``args`` is the call as it was made; only shapes, dtypes and the
    committed arrays' shardings are taken from it, which a donated array
    keeps.  After the call ``lower(...).compile()`` hands back the cached
    lowering and the cached executable, so this is ``memory_analysis()``
    of the very program that ran, in milliseconds.  It must never
    compile one: where jax lowers afresh all the same (the compile
    listener heard a lowering on this thread) there is no account,
    ``{}``, and no compile either."""
    import numpy as np

    from sparknet_tpu.obs.sentinel import get_sentinel

    spans: frozenset = frozenset()  # the widest device set of an argument

    def aval(x):
        nonlocal spans
        if isinstance(x, jax.Array):
            if len(x.sharding.device_set) > len(spans):
                spans = frozenset(x.sharding.device_set)
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding if x.committed else None)
        if isinstance(x, (bool, int, float)):
            return x  # weakly typed, as the call passed it
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    avals = jax.tree_util.tree_map(aval, args)
    sentinel = get_sentinel().install()
    lowerings = sentinel.thread_lowerings()
    lowered = jitted.lower(*avals)
    if sentinel.thread_lowerings() != lowerings:
        return {}
    mem = lowered.compile().memory_analysis()
    if mem is None:
        return {}
    account = {
        "hbm_args_bytes": int(mem.argument_size_in_bytes),
        "hbm_out_bytes": int(mem.output_size_in_bytes),
        "hbm_alias_bytes": int(mem.alias_size_in_bytes),
        "hbm_temps_bytes": int(mem.temp_size_in_bytes),
        "hbm_code_bytes": int(mem.generated_code_size_in_bytes),
        "hbm_devices": max(len(spans), 1),
    }
    # the first of them this process can ask (another host's raises)
    local = [d for d in spans if d.process_index == jax.process_index()]
    stats = min(local, key=lambda d: d.id).memory_stats() if local else None
    if stats and "bytes_limit" in stats:
        account["hbm_limit_bytes"] = int(stats["bytes_limit"])
    return account


def account_compiled(span, seen: dict, jitted, *args) -> None:
    """:func:`step_account` of ``jitted`` for ``args``, set on ``span``,
    if the call ``jitted`` has just served inside ``span`` compiled or
    loaded its program: its jit cache has grown since ``seen``, the
    caller's ``{jitted: entries}``, was last written.  On every other
    step this is one comparison.  ``hbm_account_ms`` beside the account
    is what taking it cost.  A callable that is no jitted function (a
    test's wrapper) has no program to account for."""
    cache_size = getattr(jitted, "_cache_size", None)
    entries = cache_size() if cache_size else None
    if seen.get(jitted) != entries:
        seen[jitted] = entries
        t0 = time.perf_counter()
        account = step_account(jitted, *args)
        if account:
            span.set(**account,
                     hbm_account_ms=1e3 * (time.perf_counter() - t0))


def hbm_live(devices) -> dict:
    """``{"hbm_live_bytes": n}`` for a fence's span: the largest
    ``memory_stats()["bytes_in_use"]`` over ``devices``, what the process
    keeps there between steps.  ``{}`` where the backend has no
    ``memory_stats()`` (the CPU): the stat is then absent, not 0."""
    live = [stats["bytes_in_use"] for stats in
            (d.memory_stats() for d in devices)
            if stats and "bytes_in_use" in stats]
    return {"hbm_live_bytes": int(max(live))} if live else {}
