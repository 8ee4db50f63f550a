"""The program's front door, as the benchmark drives it.

A cell is a job a user types: ``tpunet train <flags>``.  ``cli.main``
builds its parser inline and ``cmd_train`` runs a fixed iteration count,
so the benchmark cannot call either to measure a time window.  Instead:

* ``run_as_train`` hands ``cli.main`` the cell's flags with ``cmd_train``
  swapped for the job's body.  The parser, its defaults, the compile
  cache placement and the ``--dtype`` config scope are then the
  program's own, not copies.
* ``build_solver`` / ``open_feed`` / ``solo_feed`` / ``make_trainer`` are
  the ~30 lines of ``cmd_train``'s wiring, calling the SAME functions it
  calls.  No step, feed, placement or averaging code lives here.  This
  copy is a debt: the program owes one library entry point ("train for N
  steps or seconds") that ``cmd_train`` and the benchmark both call
  (PERF.md, list for the tracing issue).
"""

from __future__ import annotations

import time

# effectively endless: the prefetcher is closed when the window ends
ENDLESS = 1 << 40


def run_as_train(flags: list[str], body) -> int:
    """``tpunet train <flags>`` with ``body(args)`` in cmd_train's place."""
    from sparknet_tpu import cli

    orig = cli.cmd_train
    cli.cmd_train = body
    try:
        return cli.main(["train", *flags])
    finally:
        cli.cmd_train = orig


def build_solver(args):
    """cmd_train's first two lines."""
    from sparknet_tpu import cli

    net_param, solver_cfg = cli._build_net_and_solver(args)
    return cli._make_solver(solver_cfg, net_param, args)


def open_feed(args, solver):
    """cmd_train's host data fn for ``--data`` (train side only)."""
    from sparknet_tpu import cli

    train_fn, _ = cli._data_fns(args, solver.train_net,
                                test_net=solver.test_net)
    return train_fn


class Spans:
    """Host spans the benchmark puts around its calls into the program:
    seconds per name on the host clock, and the same names as
    ``TraceAnnotation`` so a traced window carries them on the profiler's
    clock beside the device ops."""

    def __init__(self):
        self.total: dict[str, float] = {}
        self.count: dict[str, int] = {}

    def span(self, name: str):
        return _Span(self, name)

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()

    def wrap(self, name: str, fn):
        def wrapped(*a, **k):
            with _Span(self, name):
                return fn(*a, **k)

        return wrapped


class _Span:
    def __init__(self, spans: Spans, name: str):
        import jax

        self._s, self._n = spans, name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._t = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        self._ann.__exit__(*exc)
        self._s.total[self._n] = self._s.total.get(self._n, 0.0) + dt
        self._s.count[self._n] = self._s.count.get(self._n, 0) + 1


def solo_feed(args, solver, train_fn, spans: Spans):
    """cmd_train's ``--prefetch N`` branch: the threaded DevicePrefetcher
    with the feed's ``device_fn``.  Returns (context, data_fn); the
    data_fn's wait for the next batch is the ``bench.feed_wait`` span."""
    from sparknet_tpu.data.prefetch import DevicePrefetcher

    if args.prefetch <= 0:
        raise SystemExit("the solo job drives the --prefetch N front door")
    pf = DevicePrefetcher(
        train_fn, ENDLESS, depth=args.prefetch, start_iter=solver.iter,
        device_fn=getattr(train_fn, "device_fn", None))
    pf_iter = iter(pf)

    def data_fn(it):
        with spans.span("bench.feed_wait"):
            return next(pf_iter)

    return pf, data_fn


def make_trainer(args, solver, train_fn, spans: Spans, num_devices=None):
    """cmd_train's ``--tau N`` branch: ParallelTrainer + the post-placement
    device augment + ``_stack_tau``.  ``num_devices`` cuts the data mesh
    from the first N devices (the one-device phase of scaling_eff); None
    is cmd_train's own default mesh over every visible device.

    Spans: ``bench.pack`` is ``_stack_tau`` (host batches concatenated
    and stacked), ``bench.augment`` the ``feed_device_fn`` dispatch; what
    lies between them inside ``train_round`` is ``_put_feeds``, and what
    follows is dispatch + the blocking ``float(loss)``."""
    from sparknet_tpu import cli
    from sparknet_tpu.parallel.mesh import data_parallel_mesh
    from sparknet_tpu.parallel.trainer import ParallelTrainer

    mesh = None if num_devices is None else data_parallel_mesh(num_devices)
    trainer = ParallelTrainer(solver, mesh=mesh, tau=args.tau,
                              elastic_alpha=args.elastic_alpha)
    aug_fn = getattr(train_fn, "trainer_device_fn", None)
    if aug_fn is not None:
        trainer.feed_device_fn = spans.wrap("bench.augment", aug_fn)
    tau_fn = spans.wrap(
        "bench.pack",
        cli._stack_tau(train_fn, args.tau, trainer.num_local_workers))
    return trainer, tau_fn
