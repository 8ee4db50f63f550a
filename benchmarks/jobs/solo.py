"""Job kind ``solo``: one worker, ``tpunet train --prefetch N``.

The measured loop is ``Solver.step(k, data_fn)`` in chunks of k steps with
the program's threaded ``DevicePrefetcher`` feeding it; each ``step``
call returns the smoothed loss, which fences on the device.  No per-step
callback (it would make ``Solver._step_impl`` block on every loss).
Throughput counts WHOLE chunks between the first fence (the end of
warm-up) and the last fence of the window.
"""

from __future__ import annotations

import time

from benchmarks.harness import check, front_door, jobkit, load_by_name


def run(ctx) -> dict:
    out: dict = {}

    def body(args) -> int:
        spans = front_door.Spans()
        solver = front_door.build_solver(args)
        jobkit.assert_zoo_shapes(ctx, solver)
        ctx.log(f"solver built: {ctx.config['name']} batch {ctx.batch}")
        train_fn = front_door.open_feed(args, solver)
        first = train_fn(0)
        ctx.log("feed open, first host batch read")

        # correctness, outside the window: reference check on a sample
        x, y = jobkit.sample_from(ctx, first, ctx.knob("check_images"))
        reference = check.Reference(
            load_by_name("reference", ctx.config["reference"]))
        facts, problems = check.check_step(
            solver, reference, x, y,
            check.tolerances(reference.ref, ctx.rehearse))
        ctx.log(f"reference check: {facts}")
        del reference

        k = max(1, int(ctx.knob("images_per_fence")) // ctx.batch)
        sentinel = jobkit.compiles_counter()
        pf, data_fn = front_door.solo_feed(args, solver, train_fn, spans)
        with pf:
            # one chunk: compile (or load) the step and reach steady state
            solver.step(k, data_fn, scan_chunk=args.scan)
            compiles0 = sentinel.count
            spans.reset()
            t0 = time.perf_counter()
            setup_s = t0 - ctx.t_start
            ctx.log(f"set-up done in {setup_s:.1f}s; measuring {ctx.seconds}s")
            stamps, losses = [], []
            while True:
                with spans.span("bench.chunk"):
                    losses.append(solver.step(k, data_fn, scan_chunk=args.scan))
                stamps.append(time.perf_counter())
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
                summary = jobkit.traced(ctx, window)

        if compiles:
            problems.append(f"{compiles} compile(s) inside the window")
        bad = jobkit.count_failed(losses, k)
        if bad:
            problems.append(f"non-finite loss in {bad} step(s)")
        images = chunks * k * ctx.batch
        ctx.log(f"window: {chunks} chunks x {k} steps in {wall:.3f}s, "
                f"last loss {losses[-1]:.4f}, spans {span_totals}, chunk ends "
                f"{[round(t - t0, 3) for t in stamps]}")
        out.update(
            attempted=chunks * k, failed=bad + compiles, problems=problems,
            end_to_end={"images_per_s": images / wall, "setup_s": setup_s},
            summary=summary,
            run=dict(jobkit.model_facts(ctx), job="solo", chips=1,
                     batch=ctx.batch, window_wall_s=wall, spans=span_totals,
                     feed_wait_s=span_totals.get("bench.feed_wait", 0.0),
                     steps_traced=n_traced * k),
        )
        return 0

    rc = front_door.run_as_train(ctx.train_flags(), body)
    if rc or not out:
        raise SystemExit(f"the train job ended early (rc {rc})")
    return out
