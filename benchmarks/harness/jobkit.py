"""What the job kinds share: the set-up checks, the traced window, and
the facts a run hands to the metric readers."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness import check, flops, peaks, trace


def assert_zoo_shapes(ctx, solver) -> None:
    """The configuration's prototxt must build the zoo's net: equal
    parameter names and shapes against ``models.<zoo>(batch)``."""
    import jax

    from sparknet_tpu import models
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    zoo = Network(getattr(models, ctx.config["zoo"])(ctx.batch), Phase.TRAIN)
    want = jax.eval_shape(lambda k: zoo.init(k, None, None).params,
                          jax.random.key(0))
    want = {k: [tuple(a.shape) for a in v] for k, v in want.items() if v}
    got = {k: [tuple(a.shape) for a in v]
           for k, v in solver.variables.params.items() if v}
    if got != want:
        diff = sorted(k for k in set(got) | set(want)
                      if got.get(k) != want.get(k))
        raise SystemExit(
            f"{ctx.config['name']}: the prototxt's parameters differ from "
            f"models.{ctx.config['zoo']}({ctx.batch}): {diff[:6]}")


def sample_from(ctx, host_batch: dict, n: int):
    """The first ``n`` records of a host batch through the published TEST
    transform (centre crop, mean), as f32 -- the sample both the program
    and the reference are given."""
    t = ctx.config["transform"]
    x = check.center_crop_mean(np.asarray(host_batch["data"][:n]),
                               t["crop"], t["mean_value"])
    return x, np.asarray(host_batch["label"][:n], np.int32)


def model_facts(ctx) -> dict:
    """Operations and per-layer floors of one step on one chip, from the
    configuration's own prototxt (real size, also in a rehearsal: these
    are counts, not timings)."""
    import jax

    from sparknet_tpu.proto.text_format import parse_file

    net = parse_file(f"{ctx.root}/benchmarks/configs/"
                     f"{ctx.config['name']}.train.prototxt")
    rows = flops.walk(net, ctx.batch, tuple(ctx.config["input_chw"]))
    facts = {
        "flops_per_step": flops.step_flops(rows), "layer_rows": rows,
        "lrn_layers": [l.get_str("name") for l in net.get_all("layer")
                       if l.get_str("type") == "LRN"],
    }
    kind = jax.devices()[0].device_kind
    if not ctx.rehearse:
        facts["peaks"] = peaks.peaks_for(kind)  # unknown device: an error
    return facts


def compiles_counter():
    """The program's own recompile sentinel (obs/sentinel.py): one event
    per program handed to the compiler, cache hit or not; a jit-cache hit
    fires none."""
    from sparknet_tpu.obs.sentinel import get_sentinel

    return get_sentinel().install()


def traced(ctx, body) -> dict | None:
    """Run ``body()`` under the profiler inside a ``bench.window`` span and
    reduce the trace.  Host python tracing is off: the spans the
    benchmark needs are its own TraceAnnotations."""
    import jax

    d = ctx.trace_dir()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            body()
    finally:
        jax.profiler.stop_trace()
    t = time.perf_counter()
    summary = trace.summarize(trace.load_xplane(trace.find_xplane(d)))
    ctx.log(f"trace reduced in {time.perf_counter() - t:.1f}s: window "
            f"{summary['window_s']:.3f}s, chips {sorted(summary['chips'])}")
    return summary


def count_failed(losses, per_item: int = 1) -> int:
    return sum(per_item for v in losses if not math.isfinite(v))
