"""Job kind ``lm_decoder_solo``: one worker training a decoder,
``tpunet train --data tokens:<file> --prefetch N``.

``jobs/lm_solo.py``'s loop (``Solver.step(k, data_fn)`` in chunks of k
steps through the program's threaded ``DevicePrefetcher`` on windows of
the seeded token file; throughput counts WHOLE chunks between the first
fence and the last, in SEQUENCES of ``seq_len`` tokens), with everything
that is the model's taken from what the configuration's file names:

  ``zoo`` + ``zoo_args``   the net builder in ``sparknet_tpu.models`` and,
                           per argument, the configuration key it takes
  ``flops``                ``harness/<flops>.py``: ``parts`` / ``layer_rows``
  ``check``                ``harness/<check>.py``: ``check_step`` /
                           ``tolerances``
  ``reference``            ``reference/<reference>.py``
  ``rehearse_preset``      the tiny sizes a CPU rehearsal lays over it

so the next decoder adds data files (and its own reference, flop rows and
check where its mathematics differs) and no job.  The readers that have
no ``workloads`` list get ``flops.walk``'s row format, one row per
multiplying prototxt layer (``run["layer_rows"]``); the configuration's
own readers take ``run["decoder_parts"]``.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmarks.harness import (
    dataset, flops, front_door, jobkit, load_by_name, peaks, tokens,
)


def sized(ctx) -> dict:
    """The configuration as this run builds it: its own file, or with its
    tiny preset laid over it in a rehearsal."""
    if not ctx.rehearse:
        return ctx.config
    return {**ctx.config, **ctx.config["rehearse_preset"]}


def zoo_kwargs(config: dict) -> dict:
    """The builder's arguments: ``zoo_args`` maps each to the
    configuration key that holds it."""
    return {arg: config[key] for arg, key in config["zoo_args"].items()}


def zoo_net(config: dict):
    from sparknet_tpu import models

    return getattr(models, config["zoo"])(**zoo_kwargs(config))


def configs_dir(ctx, config: dict) -> str:
    """Where the flags' ``{configs}`` points: the committed prototxts, or
    a rehearsal's tiny twins written from the same builder."""
    configs = os.path.join(ctx.root, "benchmarks", "configs")
    if not ctx.rehearse:
        return configs
    from sparknet_tpu.proto.text_format import serialize

    out = os.path.join(dataset.CACHE_DIR, "rehearse", ctx.cell["name"])
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    name = config["name"]
    shutil.copy(os.path.join(configs, name + ".solver.prototxt"), out)
    with open(os.path.join(out, name + ".train.prototxt"), "w") as f:
        f.write(serialize(zoo_net(config)))
    return out


def train_flags(ctx, config: dict) -> list[str]:
    path = tokens.ensure_tokens(ctx.seed, config["train_tokens"],
                                config["vocab_rows"],
                                config["dataset"]["zipf_s"])
    ctx.log(f"token file ready: {path}")
    flags = [f.replace("{tokens}", path).replace(
        "{configs}", configs_dir(ctx, config)) for f in config["train_flags"]]
    return [*flags, "--seed", str(ctx.seed)]


def assert_zoo_shapes(config: dict, solver) -> None:
    """The prototxt must build the zoo's net at the configuration's
    sizes: equal parameter names and shapes."""
    import jax

    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    zoo = Network(zoo_net(config), Phase.TRAIN)
    want = jax.eval_shape(lambda k: zoo.init(k, None, None).params,
                          jax.random.key(0))
    want = {k: [tuple(a.shape) for a in v] for k, v in want.items() if v}
    got = {k: [tuple(a.shape) for a in v]
           for k, v in solver.variables.params.items() if v}
    if got != want:
        diff = sorted(k for k in set(got) | set(want)
                      if got.get(k) != want.get(k))
        raise SystemExit(f"{config['name']}: the prototxt's parameters "
                         f"differ from models.{config['zoo']}: {diff[:6]}")


def model_facts(ctx) -> dict:
    """Operations and floors of one step at the REAL sizes (also in a
    rehearsal: these are counts, not timings)."""
    import jax

    real = ctx.config
    counts = load_by_name("harness", real["flops"])
    part_rows = counts.parts(real, real["sequences_per_step"],
                             real["seq_len"])
    rows = counts.layer_rows(part_rows)
    facts = {"flops_per_step": flops.step_flops(rows), "layer_rows": rows,
             "decoder_parts": part_rows, "lrn_layers": []}
    if not ctx.rehearse:
        facts["peaks"] = peaks.peaks_for(jax.devices()[0].device_kind)
    return facts


def run(ctx) -> dict:
    out: dict = {}
    config = sized(ctx)
    batch, seq_len = config["sequences_per_step"], config["seq_len"]
    checker = load_by_name("harness", config["check"])

    def body(args) -> int:
        spans = front_door.Spans()
        t = time.perf_counter()
        solver = front_door.build_solver(args)
        ctx.log(f"solver built in {time.perf_counter() - t:.1f}s: "
                f"{config['name']} {batch} x {seq_len} tokens")
        assert_zoo_shapes(config, solver)
        train_fn = front_door.open_feed(args, solver)
        first = train_fn(0)
        ctx.log("feed open, first host batch read")

        # the check's sequence must give every held expert rows, and
        # N(0, 0.006) routers send one sequence to a dozen of their outputs:
        # the layers' own rule levels the bias on that sequence first
        forward = checker.forward_program(solver)
        t = time.perf_counter()
        seen = checker.settle_bias(solver, forward, first,
                                   ctx.knob("settle_schedule"))
        ctx.log(f"selection bias levelled on the check's sequence in "
                f"{len(seen) - 1} forwards, {time.perf_counter() - t:.1f}s: "
                f"fullest expert over the mean {seen[0]:.2f} -> {seen[-1]:.2f}")

        # correctness, outside the window: reference check on a sample;
        # it takes the solver's first step, on the feed's first window
        n = int(ctx.knob("check_sequences"))
        t = time.perf_counter()
        facts, problems = checker.check_step(
            solver, load_by_name("reference", config["reference"]), config,
            np.array(first["data"][:n]), np.array(first["label"][:n]),
            checker.tolerances(ctx.rehearse), forward)
        ctx.log(f"reference check in {time.perf_counter() - t:.1f}s: {facts}")

        k = int(ctx.knob("steps_per_fence"))
        sentinel = jobkit.compiles_counter()
        pf, data_fn = front_door.solo_feed(args, solver, train_fn, spans)
        with pf:
            # one chunk: compile (or load) the step and reach steady state
            solver.step(k, data_fn, scan_chunk=args.scan)
            compiles0 = sentinel.count
            spans.reset()
            t0 = time.perf_counter()
            setup_s = t0 - ctx.t_start
            ctx.log(f"set-up done in {setup_s:.1f}s ({compiles0} compile(s) in "
                    f"the warm-up chunk); measuring {ctx.seconds}s")
            stamps, losses, routing = [], [], []
            while True:
                with spans.span("bench.chunk"):
                    losses.append(solver.step(k, data_fn, scan_chunk=args.scan))
                stamps.append(time.perf_counter())
                routing.append(checker.routing_now(solver, config))
                if stamps[-1] - t0 >= ctx.seconds:
                    break
            wall = stamps[-1] - t0
            compiles = sentinel.count - compiles0
            span_totals = dict(spans.total)
            chunks = len(stamps)

            summary = None
            n_traced = int(ctx.knob("trace_chunks"))
            if ctx.trace:
                def window():
                    for _ in range(n_traced):
                        with spans.span("bench.chunk"):
                            losses.append(
                                solver.step(k, data_fn, scan_chunk=args.scan))
                        routing.append(checker.routing_now(solver, config))
                summary = jobkit.traced(ctx, window)

        if compiles:
            problems.append(f"{compiles} compile(s) inside the window")
        bad = jobkit.count_failed(losses, k)
        if bad:
            problems.append(f"non-finite loss in {bad} step(s)")
        ctx.log(f"window: {chunks} chunks x {k} steps in {wall:.3f}s, "
                f"last loss {losses[-1]:.4f}, spans {span_totals}, chunk ends "
                f"{[round(t - t0, 3) for t in stamps]}; (fullest expert over "
                f"the mean, % of pairs on held experts) at each fence, the "
                f"traced ones last: {routing}")
        out.update(
            attempted=chunks * k, failed=bad + compiles, problems=problems,
            end_to_end={"images_per_s": chunks * k * batch / wall,
                        "setup_s": setup_s},
            summary=summary,
            run=dict(model_facts(ctx), job="lm_decoder_solo", chips=1,
                     batch=batch, seq_len=seq_len, window_wall_s=wall,
                     spans=span_totals,
                     feed_wait_s=span_totals.get("bench.feed_wait", 0.0),
                     steps_traced=n_traced * k),
        )
        return 0

    rc = front_door.run_as_train(train_flags(ctx, config), body)
    if rc or not out:
        raise SystemExit(f"the train job ended early (rc {rc})")
    return out
