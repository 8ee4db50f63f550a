"""Job kind ``tau_round``: ``tpunet train --tau N`` over the host's chips.

The measured loop is ``ParallelTrainer.train_round(tau_fn)``: it packs
tau x workers host batches (``cli._stack_tau``), places them, applies the
device augment, dispatches the round program and blocks on the loss, so
every round is fenced by the program itself.  The first
``one_device_share`` of the window runs the SAME trainer on a one-device
mesh (same tau, per-chip batch and feed); the rest runs it over every
chip.  ``scaling_eff`` = mesh rate / (chips x one-device rate), weak
scaling, both phases in one run.  Throughput counts whole rounds between
the first and last fence of each phase.
"""

from __future__ import annotations

import time

from benchmarks.harness import check, front_door, jobkit, load_by_name


def _phase(trainer, tau_fn, spans, until: float, t_origin: float):
    """Rounds until ``until`` seconds after ``t_origin``; (wall, losses)."""
    t0 = time.perf_counter()
    losses, ends = [], []
    while True:
        with spans.span("bench.round"):
            losses.append(trainer.train_round(tau_fn))
        now = time.perf_counter()
        ends.append(round(now - t0, 3))
        if now - t_origin >= until:
            return now - t0, losses, ends


def run(ctx) -> dict:
    out: dict = {}

    def body(args) -> int:
        import jax

        spans = front_door.Spans()
        chips = ctx.cell["chips"]
        solver = front_door.build_solver(args)
        jobkit.assert_zoo_shapes(ctx, solver)
        ctx.log(f"solver built: {ctx.config['name']} batch {ctx.batch}")
        train_fn = front_door.open_feed(args, solver)

        # correctness, outside the window
        first = train_fn(0)
        reference = check.Reference(
            load_by_name("reference", ctx.config["reference"]))
        n = int(ctx.knob("check_images"))
        x, y = jobkit.sample_from(ctx, first, n)
        tol = check.tolerances(reference.ref, ctx.rehearse)
        facts, problems = check.check_step(solver, reference, x, y, tol)
        ctx.log(f"reference check: {facts}")
        ctau = int(ctx.knob("check_tau"))
        xs, ys = jobkit.sample_from(ctx, first, ctau * chips * n)

        def check_trainer(tau):
            from sparknet_tpu.parallel.trainer import ParallelTrainer

            return ParallelTrainer(solver, tau=tau)

        tfacts, tbad = check.check_tau_round(
            solver, reference, check_trainer, xs, ys, ctau, tol)
        ctx.log(f"tau={ctau} round check: {tfacts}")
        problems += tbad
        del reference

        one, one_fn = front_door.make_trainer(args, solver, train_fn, spans,
                                              num_devices=1)
        mesh, mesh_fn = front_door.make_trainer(args, solver, train_fn, spans)
        if mesh.num_workers != chips:
            raise SystemExit(f"mesh has {mesh.num_workers} workers, the cell "
                             f"asks for {chips}")
        for trainer in (one, mesh):  # a span on the placement
            trainer._put_feeds = spans.wrap("bench.put", trainer._put_feeds)
        sentinel = jobkit.compiles_counter()
        for trainer, fn in ((one, one_fn), (mesh, mesh_fn)):
            trainer.train_round(fn)  # compile (or load) the round program
        ctx.log("both round programs warm")
        compiles0 = sentinel.count
        spans.reset()
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_start
        ctx.log(f"set-up done in {setup_s:.1f}s; measuring {ctx.seconds}s")
        share = float(ctx.knob("one_device_share"))
        wall1, losses1, ends1 = _phase(one, one_fn, spans, share * ctx.seconds, t0)
        spans1 = dict(spans.total)
        spans.reset()
        wallN, lossesN, endsN = _phase(mesh, mesh_fn, spans, ctx.seconds, t0)
        spansN = dict(spans.total)
        compiles = sentinel.count - compiles0
        rounds1, roundsN = len(losses1), len(lossesN)

        summary = None
        n_traced = int(ctx.knob("trace_rounds"))
        if ctx.trace:
            def window():
                for _ in range(n_traced):
                    with spans.span("bench.round"):
                        lossesN.append(mesh.train_round(mesh_fn))
            summary = jobkit.traced(ctx, window)

        tau = args.tau
        per_round = tau * ctx.batch
        rate1 = rounds1 * per_round / wall1
        rateN = roundsN * per_round * chips / wallN
        rounds = rounds1 + roundsN
        if compiles:
            problems.append(f"{compiles} compile(s) inside the window")
        bad = jobkit.count_failed(losses1 + lossesN)
        if bad:
            problems.append(f"non-finite loss in {bad} round(s)")
        for label, got, want in (
                ("one-device", rounds1, ctx.knob("min_one_device_rounds")),
                ("mesh", roundsN, ctx.knob("min_mesh_rounds"))):
            if not ctx.rehearse and got < int(want):
                ctx.log(f"WARNING: only {got} {label} rounds in the window "
                        f"(the cell wants >= {want})")
        ctx.log(f"window: one-device {rounds1} rounds in {wall1:.3f}s "
                f"({rate1:.1f} img/s) spans {spans1}; mesh {roundsN} "
                f"rounds in {wallN:.3f}s ({rateN:.1f} img/s) spans {spansN}; "
                f"round ends one-device {ends1} mesh {endsN}")
        feed_wait = spansN.get("bench.pack", 0.0) + spansN.get("bench.put", 0.0)
        out.update(
            attempted=rounds, failed=bad + compiles, problems=problems,
            end_to_end={
                "images_per_s": rateN, "setup_s": setup_s,
                "scaling_eff": 100.0 * rateN / (chips * rate1)},
            summary=summary,
            run=dict(jobkit.model_facts(ctx), job="tau_round", chips=chips,
                     batch=ctx.batch, tau=tau, window_wall_s=wallN,
                     spans=spansN, spans_one_device=spans1,
                     feed_wait_s=feed_wait, steps_traced=n_traced * tau,
                     one_device_images_per_s=rate1),
        )
        return 0

    rc = front_door.run_as_train(ctx.train_flags(), body)
    if rc or not out:
        raise SystemExit(f"the train job ended early (rc {rc})")
    return out
